"""Whether what the timed path served is correct.

After the window a sample of the requests finished in it is drawn from
the seed, the longest one always in it, until it holds ``SAMPLE_TOKENS``
served tokens.  The configuration's plain float32 reference
(``reference/<family>.py``) runs once over each prompt with its served
tokens, and the number compared is the widest gap by which a served
token's reference logit lies below the reference's best logit at that
position (greedy serving would have picked the best, up to rounding).

The limits of each cell are ``limits/<workload>.json``: which of
:func:`numbers` it compares, and each one's limit, set from the sound
program's readings over many seeds and the control's (the reference in
the precision below the configuration's, see ``reference/common.py``).
"""
from __future__ import annotations

import importlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
SAMPLE_TOKENS = 384
SAMPLE_MAX = 8


def sample(reqs, seed: int) -> list:
    """Finished requests drawn from the seed, the longest first."""
    done = [r for r in reqs if r.ok]
    if not done:
        return []
    rng = np.random.default_rng([int(seed) % (1 << 63), 7])
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    out, n = [longest], len(longest.tokens)
    for r in rest:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        out.append(r)
        n += len(r.tokens)
    return out


def reference(cfg: dict):
    return importlib.import_module(f"reference.{cfg['reference']}")


def served_gaps(m: dict, cfg: dict, seed: int, reqs: list, length: int,
                quant=None) -> tuple:
    """(gaps of the served tokens under the reference, gaps of the tokens
    the reference at ``quant`` puts first); the second is None without
    ``quant``.  One gap per served token, in logits.  ``length`` is the
    mix's S_max: every run of a cell hands the reference the same shapes."""
    ref = reference(cfg)
    seqs = [r.prompt + r.tokens for r in reqs]
    pos = [list(range(len(r.prompt) - 1, len(r.prompt) - 1 + len(r.tokens)))
           for r in reqs]
    served = np.concatenate([r.tokens for r in reqs]).astype(np.int32)
    n = len(served)
    # the sample holds fewer than SAMPLE_TOKENS + one request's tokens, so
    # every run of a cell hands the reference the same shapes
    kw = {"rows": SAMPLE_MAX, "length": length, "n_pad": SAMPLE_TOKENS + length}
    logits = ref.logits_at(m, seed, seqs, pos, **kw)
    served = np.pad(served, (0, kw["n_pad"] - n))
    gaps = np.asarray(_gap(logits, served))[:n]
    ctrl = None
    if quant is not None:
        low = ref.logits_at(m, seed, seqs, pos, quant=quant, **kw)
        ctrl = np.asarray(_gap(logits, _argmax(low)))[:n]
    return gaps, ctrl


@jax.jit
def _gap(logits, tokens):
    """How far each token's logit lies below its row's best."""
    return (jnp.max(logits, axis=-1)
            - jnp.take_along_axis(logits, tokens[:, None], 1)[:, 0])


@jax.jit
def _argmax(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def numbers(gaps) -> dict:
    """The numbers a cell may compare, from the served tokens' gaps: the
    widest, and the mean, which one routing flip between near-tied experts
    does not set alone."""
    g = np.asarray(gaps, np.float64)
    if not g.size:
        return {}
    return {"max_logit_gap": float(g.max()), "mean_logit_gap": float(g.mean())}


def limits(workload: str, *, test_size: bool = False) -> dict:
    """{number: limit} of a cell."""
    path = HERE / "limits" / f"{workload}.json"
    d = json.loads(path.read_text())
    d = d.get("test_size", {}) if test_size else d
    return {k: v["limit"] for k, v in d.items() if k != "test_size"}


def compare(numbers: dict, lims: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}): every number within its
    limit, and every limit read."""
    out = {k: {"value": numbers.get(k), "limit": lim}
           for k, lim in lims.items()}
    ok = bool(lims) and all(v["value"] is not None and v["value"] <= v["limit"]
                            for v in out.values())
    return ok, out
