"""Per-kernel cost profiler: call counts, analytic bytes/FLOPs, wall time.

The paper's premise is that posit kernels pay for themselves in moved bytes;
this module measures whether a given run actually *hits* those kernels and
what each dispatch should have cost.  A :class:`KernelProfiler` installed via
``profiling(...)`` receives one record per execution of a
``kernels/posit_{gemm,quire_gemm,attention,codec,softmax}`` entry point (and
of the XLA-fused linear path in ``models.layers`` — the same GEMM contract,
just not hand-lowered), carrying:

* **analytic cost** — FLOPs and mandatory HBM bytes from
  ``launch/roofline.py``'s per-kernel cost model (one formula shared with the
  whole-step roofline analysis, so the two can never disagree);
* **attribution** — the layer path from the innermost :func:`site` context
  (linear sites pass their path directly; ``models.attention`` wraps its
  kernel calls), falling back to family-level aggregation;
* **wall time** — measured with ``block_until_ready`` when the dispatch is
  *eager* (concrete arrays).  Executions under a ``jit`` trace are counted as
  ``traced`` instead: they happen once per compile, not once per step, so
  timing them would be a lie.

Everything is trace-time gated exactly like ``calib.observe``: when no
profiler is installed the hooks are one global read and the entry points are
byte-identical to their un-instrumented selves.  ``report()`` emits the
roofline-attribution JSON (``repro/kernel-profile`` v1) and ``markdown()``
the human table ``launch/train.py --profile-out`` and ``serve.py`` write.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax

__all__ = [
    "KernelProfiler", "KernelRecord", "profiling", "site", "current_site",
    "dispatch", "is_active", "get_active",
    "gemm_cost", "attention_cost", "codec_cost", "softmax_cost",
]

FAMILIES = ("gemm", "quire_gemm", "attention", "codec", "softmax")


def _fmt_bytes(fmt) -> float:
    """Storage bytes per element of a pcsr operand slot (f32 fallback)."""
    return float(getattr(fmt, "storage_bytes", 4))


# ------------------------------------------------- cost extraction helpers ----
# Shapes come off the live arrays (tracers carry shapes too, so these work
# identically under jit traces); formulas live in launch.roofline.  Imported
# lazily: kernels/*/ops.py import this module at call time and must not drag
# the launch package into every kernel import.

def gemm_cost(a, b, slots, *, bias=None, residual=None) -> dict:
    from repro.launch import roofline

    m = 1.0
    for s in a.shape[:-1]:
        m *= s
    return roofline.gemm_cost(
        m, float(a.shape[-1]), float(b.shape[-1]),
        a_bytes=_fmt_bytes(slots.rs1), b_bytes=_fmt_bytes(slots.rs2),
        out_bytes=_fmt_bytes(slots.rd),
        bias=bias is not None, residual=residual is not None)


def linear_cost(x, n: float, *, w_bytes: float, bias: bool = False,
                residual: bool = False) -> dict:
    """A model-side linear y = x @ W: activations at their live width, the
    weight at its at-rest storage width (the fused decode reads codes)."""
    from repro.launch import roofline

    m = 1.0
    for s in x.shape[:-1]:
        m *= s
    xb = float(x.dtype.itemsize)
    return roofline.gemm_cost(m, float(x.shape[-1]), n, a_bytes=xb,
                              b_bytes=w_bytes, out_bytes=xb,
                              bias=bias, residual=residual)


def attention_cost(q, k_codes, *, kv_bits: int) -> dict:
    from repro.launch import roofline

    b, hq, d = q.shape
    hkv, s = k_codes.shape[1], k_codes.shape[2]
    kv_bytes = kv_bits / 8.0 if kv_bits else float(k_codes.dtype.itemsize)
    qb = float(q.dtype.itemsize)
    return roofline.attention_decode_cost(
        float(b), float(hq), float(hkv), float(s), float(d),
        kv_bytes=kv_bytes, q_bytes=qb, out_bytes=qb)


def codec_cost(arr, *, nbits: int, value_bytes: float = 4.0) -> dict:
    from repro.launch import roofline

    n = 1.0
    for s in arr.shape:
        n *= s
    return roofline.codec_cost(n, code_bytes=(nbits + 7) // 8,
                               value_bytes=value_bytes)


def softmax_cost(codes, *, nbits: int) -> dict:
    from repro.launch import roofline

    rows = 1.0
    for s in codes.shape[:-1]:
        rows *= s
    return roofline.softmax_cost(rows, float(codes.shape[-1]),
                                 code_bytes=(nbits + 7) // 8)


# --------------------------------------------------------------- recording ----

@dataclasses.dataclass
class KernelRecord:
    """Accumulated profile of one (path, family, impl) dispatch site."""

    path: str
    family: str
    impl: str
    calls: int = 0           # eager executions (each one timed)
    traced: int = 0          # executions under a jit trace (once per compile)
    flops: float = 0.0
    bytes: float = 0.0
    seconds: float = 0.0     # measured wall time over eager calls

    def to_dict(self, peaks: Optional[dict]) -> dict:
        d = dataclasses.asdict(self)
        d.update(_bound_fields(self.flops, self.bytes, self.seconds, peaks))
        return d


def _bound_fields(flops: float, byts: float, seconds: Optional[float],
                  peaks: Optional[dict]) -> dict:
    """Roofline floor of ``flops``/``byts`` on a chip with ``peaks``, and the
    achieved-vs-bound ratio (1.0 = at the bound).  All None ("not
    measured") when the running device has no entry in the peaks table."""
    if peaks is None:
        return dict.fromkeys(
            ("t_compute_s", "t_memory_s", "bound", "bound_s", "achieved_frac"))
    from repro.launch import roofline

    bt = roofline.bound_times(flops, byts, peaks=peaks)
    return {"t_compute_s": bt["t_compute_s"], "t_memory_s": bt["t_memory_s"],
            "bound": bt["dominant"], "bound_s": bt["bound_s"],
            "achieved_frac": bt["bound_s"] / seconds if seconds else None}


_ACTIVE: Optional["KernelProfiler"] = None
_SITE: List[str] = []


def is_active() -> bool:
    return _ACTIVE is not None


def get_active() -> Optional["KernelProfiler"]:
    return _ACTIVE


def current_site() -> str:
    return _SITE[-1] if _SITE else ""


@contextlib.contextmanager
def site(path: str):
    """Attribute kernel dispatches inside the block to layer ``path``."""
    if _ACTIVE is None:
        yield
        return
    _SITE.append(path)
    try:
        yield
    finally:
        _SITE.pop()


@contextlib.contextmanager
def profiling(prof: "KernelProfiler"):
    """Install ``prof`` as the active kernel profiler for the block."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = prof
    try:
        yield prof
    finally:
        _ACTIVE = prev


def dispatch(family: str, impl: str, cost: dict, fn: Callable, *,
             primary=None, path: Optional[str] = None):
    """Run ``fn()`` under the active profiler (entry-point hook).

    ``primary`` is the dispatch's main input array: a ``jax`` tracer means
    this execution is a trace, not a step — counted but never timed.
    Call sites guard with ``is_active()`` so the inactive path never builds
    ``cost``.
    """
    prof = _ACTIVE
    if prof is None:
        return fn()
    traced = isinstance(primary, jax.core.Tracer)
    if traced or not prof.timed:
        out = fn()
        prof.record(family, impl, cost, path=path, traced=True)
        return out
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    prof.record(family, impl, cost, path=path,
                seconds=time.perf_counter() - t0)
    return out


def _us(seconds: Optional[float]) -> str:
    return "not measured" if seconds is None else f"{seconds * 1e6:.2f}"


class KernelProfiler:
    """Accumulates :class:`KernelRecord` rows keyed by (path, family, impl)."""

    def __init__(self, *, timed: bool = True):
        self.timed = timed
        self.records: Dict[Tuple[str, str, str], KernelRecord] = {}

    def record(self, family: str, impl: str, cost: dict, *,
               path: Optional[str] = None, seconds: Optional[float] = None,
               traced: bool = False) -> None:
        key = (current_site() if path is None else path, family, impl)
        rec = self.records.get(key)
        if rec is None:
            rec = self.records[key] = KernelRecord(*key)
        if traced:
            rec.traced += 1
        else:
            rec.calls += 1
            rec.seconds += seconds or 0.0
        rec.flops += cost["flops"]
        rec.bytes += cost["bytes"]

    # -- reporting ------------------------------------------------------------
    def report(self, *, measured_total_s: Optional[float] = None) -> dict:
        from repro.launch import roofline

        peaks = roofline.device_peaks()
        rows = [self.records[k].to_dict(peaks) for k in sorted(self.records)]
        tot_flops = sum(r["flops"] for r in rows)
        tot_bytes = sum(r["bytes"] for r in rows)
        bounds = _bound_fields(tot_flops, tot_bytes, measured_total_s, peaks)
        return {
            "version": 1,
            "kind": "repro/kernel-profile",
            "peaks": peaks,
            "rows": rows,
            "totals": {
                "dispatches": sum(r["calls"] + r["traced"] for r in rows),
                "flops": tot_flops, "bytes": tot_bytes,
                "bound_s": bounds["bound_s"], "bound": bounds["bound"],
                "measured_s": measured_total_s,
                "achieved_frac": bounds["achieved_frac"],
            },
        }

    def markdown(self, *, measured_total_s: Optional[float] = None) -> str:
        rep = self.report(measured_total_s=measured_total_s)
        lines = [
            "| path | family | impl | calls | traced | GFLOPs | MB moved "
            "| bound | bound_us | measured_us |",
            "|---|---|---|---|---|---|---|---|---|---|",
        ]
        for r in sorted(rep["rows"], key=lambda r: -r["bytes"]):
            lines.append(
                "| {path} | {family} | {impl} | {calls} | {traced} "
                "| {gf:.3f} | {mb:.3f} | {bound} | {bus} | {mus} |".format(
                    path=r["path"] or "—", family=r["family"], impl=r["impl"],
                    calls=r["calls"], traced=r["traced"],
                    gf=r["flops"] / 1e9, mb=r["bytes"] / 1e6,
                    bound=r["bound"] or "not measured",
                    bus=_us(r["bound_s"]),
                    mus=(f"{r['seconds'] * 1e6:.1f}" if r["calls"] else "—")))
        t = rep["totals"]
        floor = (f"{t['bound']}-bound floor {_us(t['bound_s'])} us"
                 if t["bound"] else "roofline floor not measured (no peaks "
                 "for this device)")
        lines.append(
            f"\ntotals: {t['dispatches']} dispatches, "
            f"{t['flops'] / 1e9:.3f} GFLOPs, {t['bytes'] / 1e6:.3f} MB, "
            + floor)
        return "\n".join(lines)

    def save(self, path: str, *, measured_total_s: Optional[float] = None
             ) -> dict:
        """Write the JSON report to ``path`` and the markdown table next to
        it (same stem, ``.md``); returns the report dict."""
        rep = self.report(measured_total_s=measured_total_s)
        with open(path, "w") as f:
            json.dump(rep, f, indent=1)
        with open(os.path.splitext(path)[0] + ".md", "w") as f:
            f.write(self.markdown(measured_total_s=measured_total_s) + "\n")
        return rep
