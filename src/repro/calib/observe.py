"""Calibration observers — streaming per-tensor statistics from a forward pass.

A calibration pass runs the model's ordinary forward code under
``observing(Observer())``; every linear call site (``models.layers
.apply_linear`` / ``effective_weight``, keyed by the same layer-path strings
``resolve_policy`` sees) then streams *reduced* statistics for its weight and
activation tensors to the observer:

* ``abs_max``      — saturation / dynamic-range witness,
* ``hist``         — a log2-magnitude histogram: count of values with
                     ``floor(log2|x|) == s`` per binade ``s`` (the exact
                     quantity posit tapered accuracy is parameterized by —
                     ``calib.errmodel`` maps it to expected round-trip error
                     per ``(nbits, es)`` candidate),
* ``sum_sq``       — RMS magnitude (layer-importance weighting in the search),
* ``zeros``        — exact zeros (posit encodes them exactly; excluded from
                     the error integral).

Nothing else crosses the device->host boundary: the per-tensor reduction is
one 2-float head plus an int32 ``NBINS + 1`` count vector (the extra slot is
the nonfinite count — the serving numerics probes' NaR/inf witness, free for
calibration) shipped through ``jax.debug.callback``, so the hooks work
identically inside ``lax.scan`` stacks and ``jax.checkpoint`` bodies, and no
activation trace is ever materialized.  (Counts ride in int32 — a float32
scatter-add saturates at 2^24 per binade, which one full-size linear
exceeds.)  Call sites check ``is_active()`` at trace time — when no observer
is installed the hook is dead code and costs nothing.

This reduction core is shared by two consumers: calibration
(``calib.search`` — this module's original client) and the serving-plane
numerical-health probes (``repro.obs.numerics``), which install the same
``Observer`` under a cadenced decode executable and read saturation /
underflow / drift off the same histograms (DESIGN.md §12).

Stats are keyed by ``(path, kind)`` with ``kind in ("weight", "act",
"grad")``.  All depth-layers of a scanned stack share one call-site path, so
their statistics merge into one histogram — exactly the granularity at which
``PrecisionPolicy`` rules resolve (DESIGN.md §9/§11).

The ``"grad"`` kind is the training-plane channel (DESIGN.md §16): under
``jax.value_and_grad``, :func:`grad_tap` — a ``custom_vjp`` identity whose
backward rule records its cotangent — streams the gradient arriving at each
linear site's input through the same reduction.  The tap only enters the
trace when the active observer asks for gradients, so forward-only consumers
(calibration, serving probes) and un-observed training steps never carry it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Binade range covered by the histogram: floor(log2|x|) in [BIN_LO, BIN_HI].
# BIN_HI must be >= the largest max_scale whose saturation we need to *see*:
# p8 es3 saturates at 2^48, so the top bin sits above it (s=48 mass must not
# clamp into an in-range bin, where it would be scored as truncated-es error
# instead of the ~4x larger clamp error and vanish from outlier_mass).  p16
# es2/es3 saturation (2^56 / 2^112) still clamps into the top bin — that
# only ever *under*-states the error of astronomically large outliers.
BIN_LO = -80
NBINS = 130
BIN_HI = BIN_LO + NBINS - 1

KINDS = ("weight", "act", "grad")


@dataclasses.dataclass
class TensorStats:
    """Mergeable streamed statistics of one tensor (or stream of tensors)."""

    n: float = 0.0                 # total elements seen (zeros included)
    zeros: float = 0.0             # exact zeros
    abs_max: float = 0.0
    sum_sq: float = 0.0
    nonfinite: float = 0.0         # NaN/inf elements (posit NaR witness)
    hist: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((NBINS,), np.float64))
    size: int = 0                  # per-record element count (static shape)
    shape: Tuple[int, ...] = ()    # shape of one recorded tensor

    def merge_vec(self, size: int, shape: Tuple[int, ...],
                  head: np.ndarray, counts: np.ndarray) -> None:
        """Fold one streamed record: head [abs_max, sum_sq], int32 counts.

        ``counts`` is the NBINS-binade histogram with one trailing slot for
        the nonfinite count (a bare NBINS histogram — old records — means
        nonfinite 0).
        """
        counts = np.asarray(counts, np.float64)
        self.n += float(size)
        self.abs_max = max(self.abs_max, float(head[0]))
        self.sum_sq += float(head[1])
        if counts.shape[0] == NBINS + 1:
            self.nonfinite += float(counts[-1])
            counts = counts[:-1]
        self.hist += counts
        self.zeros = self.n - float(self.hist.sum()) - self.nonfinite
        self.size = size
        self.shape = tuple(shape)

    @property
    def rms(self) -> float:
        return float(np.sqrt(self.sum_sq / self.n)) if self.n else 0.0

    @property
    def probs(self) -> np.ndarray:
        """Per-binade probability mass (zeros excluded from every bin; the
        zero fraction is ``zeros / n``)."""
        return self.hist / self.n if self.n else self.hist

    def nonzero_frac(self) -> float:
        return 1.0 - self.zeros / self.n if self.n else 0.0

    def hist_json(self) -> dict:
        """Compact JSON form of the binade histogram (artifact schema §11/§12):
        leading/trailing zero bins trimmed, ``bin_lo`` anchors the rest.
        The drift detector (``repro.obs.numerics``) loads these back as the
        calibration-time baseline distribution."""
        nz = np.flatnonzero(self.hist)
        if nz.size == 0:
            return {"bin_lo": 0, "counts": [], "n": self.n}
        lo, hi = int(nz[0]), int(nz[-1])
        return {"bin_lo": BIN_LO + lo,
                "counts": [int(c) for c in self.hist[lo:hi + 1]],
                "n": self.n}

    @staticmethod
    def hist_from_json(d: dict) -> "TensorStats":
        """Inverse of ``hist_json``: a TensorStats holding just the
        distribution (n + hist) — enough for drift scoring."""
        st = TensorStats()
        st.n = float(d.get("n", 0.0))
        for i, c in enumerate(d.get("counts", ())):
            b = int(d["bin_lo"]) + i - BIN_LO
            if 0 <= b < NBINS:
                st.hist[b] = float(c)
        st.zeros = st.n - float(st.hist.sum())
        return st


def _stat_vec(arr: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Device-side reduction: ([abs_max, sum_sq], int32 counts[NBINS + 1]).

    ``counts[:NBINS]`` is the binade histogram, ``counts[-1]`` the nonfinite
    count (NaN/inf — what would encode to posit NaR; the serving probes'
    health witness).  Counts accumulate in int32: a float32 scatter-add
    silently saturates at 2^24 per binade, which a single full-size linear
    (~1e8 elements) exceeds.
    """
    x = jnp.abs(arr.astype(jnp.float32)).reshape(-1)
    finite = jnp.isfinite(x)
    x = jnp.where(finite, x, 0.0)
    nonzero = x > 0.0
    # frexp gives x = m * 2^e with m in [0.5, 1): floor(log2|x|) == e - 1,
    # exactly (no float-log rounding at binade boundaries)
    _, e = jnp.frexp(x)
    idx = jnp.where(finite, jnp.clip(e - 1, BIN_LO, BIN_HI) - BIN_LO, NBINS)
    counts = jnp.zeros((NBINS + 1,), jnp.int32).at[idx].add(
        (nonzero | ~finite).astype(jnp.int32))
    head = jnp.stack([jnp.max(x, initial=0.0), jnp.sum(x * x)])
    return head, counts


class Observer:
    """Accumulates ``TensorStats`` per ``(path, kind)`` key on the host.

    ``kinds`` restricts which tensor kinds stream: calibration wants weights
    and activations (the default); the serving numerics probes pass
    ``("act",)`` — weights are static during serving — and the training
    telemetry probes pass ``("act", "grad")``.  Because the filter applies at
    *trace* time, the skipped kinds' reductions and callbacks never enter the
    probed executable.  ``"grad"`` is deliberately not in the default: it
    inserts :func:`grad_tap` wrappers into observed forwards, which
    forward-only consumers have no use for.
    """

    def __init__(self, kinds: Tuple[str, ...] = ("weight", "act")):
        assert all(k in KINDS for k in kinds), kinds
        self.kinds = tuple(kinds)
        self.stats: Dict[Tuple[str, str], TensorStats] = {}

    # -- host side -----------------------------------------------------------
    def _accum(self, key: Tuple[str, str], size: int,
               shape: Tuple[int, ...], head, hist) -> None:
        st = self.stats.get(key)
        if st is None:
            st = self.stats[key] = TensorStats()
        st.merge_vec(size, shape, np.asarray(head), np.asarray(hist))

    # -- trace side ----------------------------------------------------------
    def record(self, path: str, kind: str, arr: jax.Array) -> None:
        assert kind in KINDS, kind
        if kind not in self.kinds:
            return
        head, hist = _stat_vec(arr)
        jax.debug.callback(
            functools.partial(self._accum, (path, kind),
                              int(arr.size), tuple(arr.shape)),
            head, hist)

    # -- results -------------------------------------------------------------
    def paths(self) -> Tuple[str, ...]:
        return tuple(sorted({p for p, _ in self.stats}))

    def get(self, path: str, kind: str) -> Optional[TensorStats]:
        return self.stats.get((path, kind))


_ACTIVE: Optional[Observer] = None


def is_active() -> bool:
    return _ACTIVE is not None


def get_active() -> Optional[Observer]:
    return _ACTIVE


@contextlib.contextmanager
def observing(obs: Observer):
    """Install ``obs`` as the active calibration observer for the block."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = obs
    try:
        yield obs
    finally:
        _ACTIVE = prev


def record(path: str, kind: str, arr: jax.Array) -> None:
    """Call-site hook: stream stats for ``arr`` if an observer is active.

    This is the function ``models.layers`` calls next to every
    ``resolve_policy``; it must stay free to call when inactive (plain global
    read at trace time).
    """
    if _ACTIVE is not None:
        _ACTIVE.record(path, kind, arr)


# ------------------------------------------------------------ gradient tap ----

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _grad_tap(path: str, x):
    return x


def _grad_tap_fwd(path: str, x):
    return x, None


def _grad_tap_bwd(path: str, _res, g):
    # Runs once per backward trace (custom_vjp bwd is not replayed by
    # jax.checkpoint the way forward residual recomputation is), so the grad
    # histogram counts every cotangent element exactly once per step.
    record(path, "grad", g)
    return (g,)


_grad_tap.defvjp(_grad_tap_fwd, _grad_tap_bwd)


def grad_tap(path: str, x: jax.Array) -> jax.Array:
    """Identity whose cotangent streams to the active observer's ``"grad"``
    channel, keyed by the same ``path`` the act/weight records use.

    Trace-time gated exactly like :func:`record`: when no observer wants
    gradients the function returns ``x`` untouched and the executable carries
    neither the custom_vjp wrapper nor the backward callback.
    """
    if _ACTIVE is not None and "grad" in _ACTIVE.kinds:
        return _grad_tap(path, x)
    return x


def collect_stats(forward_fn, batches) -> Observer:
    """Run ``forward_fn`` over ``batches`` under a fresh observer.

    ``forward_fn(batch)`` is any callable that executes the model's forward
    code (e.g. ``lambda b: model.forward(params, b, policy)``).  Returns the
    populated observer after draining all pending host callbacks.
    """
    obs = Observer()
    with observing(obs):
        for batch in batches:
            out = forward_fn(batch)
            jax.block_until_ready(out)
    # debug.callback effects are asynchronous; drain them before reading stats
    jax.effects_barrier()
    return obs
