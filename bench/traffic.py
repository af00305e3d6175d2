"""The one traffic generator: reads a mix file ``traffic/<mix>.json``.

Every seed gets the same work, in another order.  Requests come in blocks
of ``block``; each block holds the whole multiset (prompt and output
lengths at ``block`` evenly spaced quantiles of their lognormals, prompts
snapped to the nearest bucket in log scale, outputs clipped), and the seed
permutes each block on its own and draws the prompt ids.  So any run of consecutive requests, such as
those a window sees, holds the same lengths whatever the seed.  Open-loop
inter-arrival gaps are evenly spaced quantiles of the exponential
distribution, in the seed's order.

A mix file holds:

  source           where the length distributions come from
  loop             "closed" (``clients``, all started together, each send
                   their next request when the previous one finished;
                   requests still in flight when the window closes are
                   cancelled) or "open" (Poisson arrivals at
                   ``rate`` requests/s, after ``ramp_s`` seconds of warm-up
                   arrivals that are served but not counted)
  max_slots, s_max the engine's decode slots and per-slot token budget
  kv_pool_tokens   tokens the paged KV pool holds (all slots together)
  max_queue        the server's admission queue bound
  prompt           {"mean", "sigma", "buckets"}: lognormal prompt lengths,
                   each snapped to the nearest of ``buckets`` in log scale
                   (one prefill program per bucket)
  output           {"mean", "sigma", "min", "max"}: clipped lognormal
  block            requests per block, each block the whole multiset
  drain_s          the most a run waits after the window for in-flight work
  test_size        overrides for the small rehearsal on the CPU
"""
from __future__ import annotations

import json
import pathlib
import statistics

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load(name: str, *, test_size: bool = False) -> dict:
    mix = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    if test_size:
        mix = {**mix, **mix.get("test_size", {})}
    mix.pop("test_size", None)
    mix["name"] = name
    return mix


def _quantiles(n: int):
    return (np.arange(n) + 0.5) / n


def _lognormal(spec: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of the lognormal of mean ``spec["mean"]``
    and log-scale deviation ``spec["sigma"]``."""
    z = np.asarray([statistics.NormalDist().inv_cdf(q) for q in _quantiles(n)])
    s = spec["sigma"]
    return spec["mean"] * np.exp(s * z - s * s / 2)


def prompt_lengths(mix: dict) -> np.ndarray:
    p = mix["prompt"]
    b = np.asarray(p["buckets"], np.int64)
    x = np.log2(_lognormal(p, mix["block"]))
    return b[np.argmin(np.abs(x[:, None] - np.log2(b)[None, :]), axis=1)]


def output_lengths(mix: dict) -> np.ndarray:
    o = mix["output"]
    x = np.round(_lognormal(o, mix["block"]))
    return np.clip(x, o["min"], o["max"]).astype(np.int64)


class Requests:
    """The seed's requests: ``spec(i)`` -> (prompt ids, max_new_tokens)."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.vocab, self.seed = mix, vocab, int(seed) % (1 << 63)
        self.prompt_len = prompt_lengths(mix)
        self.max_new = output_lengths(mix)

    def spec(self, i: int):
        b, j = divmod(i, self.mix["block"])
        rng = np.random.default_rng([self.seed, 1, b])
        T = self.prompt_len[rng.permutation(len(self.prompt_len))[j]]
        n = self.max_new[rng.permutation(len(self.max_new))[j]]
        rng = np.random.default_rng([self.seed, 2, i])
        return rng.integers(0, self.vocab, int(T)).tolist(), int(n)

    def arrivals(self, seconds: float) -> np.ndarray:
        """Open loop: send times from the start of the ramp.  Exactly
        round(rate * (ramp_s + seconds)) arrivals, the gaps at evenly spaced
        exponential quantiles scaled to span that time, in the seed's
        order."""
        total = self.mix["ramp_s"] + seconds
        n = max(1, int(round(self.mix["rate"] * total)))
        rng = np.random.default_rng([self.seed, 3])
        gaps = rng.permutation(-np.log1p(-_quantiles(n)))
        gaps *= total / gaps.sum()
        return np.cumsum(gaps) - gaps[0]

    def warmup_lengths(self):
        """One prompt length per bucket in use: the shapes the window will
        use."""
        return sorted({int(b) for b in self.prompt_len})
