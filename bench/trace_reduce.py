"""Reduce a JAX profiler trace (``.xplane.pb``) to device time.

A trace has device planes (``/device:TPU:<n>``) whose ``XLA Ops`` line holds
one event per operation run and whose ``XLA Modules`` line holds one event
per program run, and a host plane (``/host:CPU``) whose thread lines hold
the ``TraceAnnotation`` spans of the host.  All times are on one clock.

* busy time: the union of the intervals in which an operation ran;
* programs: each program run is labelled with the host annotation open when
  it started (``repro.decode_step``, ``repro.prefill``); a program (its
  module name, which carries a hash of the program) takes the label most of
  its runs have;
* idle gaps: each interval of the window with no operation, labelled with
  the innermost host annotation open at its midpoint.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os


@dataclasses.dataclass
class Ev:
    start: float        # seconds
    end: float
    name: str
    group: str = ""     # program identity (module name with its hash)
    depth: int = 0


@dataclasses.dataclass
class Trace:
    ops: list           # device operations, per device
    modules: list       # device program runs, per device
    host: list          # host annotations
    n_devices: int


def load(trace_dir: str, host_prefixes=("repro.", "bench.")) -> Trace:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    ops, modules, host = [], [], []
    n_dev = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            n_dev += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [Ev(e.start_ns * 1e-9,
                               (e.start_ns + e.duration_ns) * 1e-9,
                               e.name.split(" = ")[0])
                            for e in line.events]
                elif line.name == "XLA Modules":
                    modules += [Ev(e.start_ns * 1e-9,
                                   (e.start_ns + e.duration_ns) * 1e-9,
                                   e.name, e.name) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                stack = []
                for e in sorted(line.events, key=lambda e: e.start_ns):
                    if not e.name.startswith(host_prefixes):
                        continue
                    s, t = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
                    while stack and stack[-1] <= s:
                        stack.pop()
                    host.append(Ev(s, t, e.name, depth=len(stack)))
                    stack.append(t)
    return Trace(ops, modules, host, n_dev)


def union(intervals, t0: float, t1: float) -> list:
    """Merged [start, end) intervals clipped to [t0, t1]."""
    out = []
    for s, e in sorted((max(a, t0), min(b, t1)) for a, b in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_s(tr: Trace, t0: float, t1: float) -> float:
    """Seconds of [t0, t1] in which an operation ran, averaged over the
    devices."""
    evs = tr.ops or tr.modules
    total = sum(e - s for s, e in union([(x.start, x.end) for x in evs],
                                        t0, t1))
    return total / max(tr.n_devices, 1)


def host_label(tr: Trace, t: float) -> str:
    """The innermost host annotation open at ``t`` (the benchmark's own
    ``bench.window`` marks the traced window and labels nothing)."""
    best = None
    for h in tr.host:
        if h.name != "bench.window" and h.start <= t < h.end and \
                (best is None or h.depth >= best.depth):
            best = h
    return best.name if best is not None else "none"


def idle_gaps(tr: Trace, t0: float, t1: float, top: int = 10,
              at: bool = False) -> list:
    """The longest intervals of [t0, t1] with no operation on the device:
    [[label, seconds], ...], longest first; with ``at``, [[label, seconds,
    midpoint], ...]."""
    evs = tr.ops or tr.modules
    busy = union([(x.start, x.end) for x in evs], t0, t1)
    gaps, cur = [], t0
    for s, e in busy + [[t1, t1]]:
        if s > cur:
            mid = (s + cur) / 2
            gaps.append((s - cur, host_label(tr, mid), mid))
        cur = max(cur, e)
    gaps.sort(reverse=True)
    return [[label, dt, mid] if at else [label, dt]
            for dt, label, mid in gaps[:top]]


def leaf_ops(ops: list) -> list:
    """The operations that hold no other: a loop or call whose body's
    operations are listed too is left out, so no time counts twice."""
    ops = sorted(ops, key=lambda x: (x.start, -x.end))
    return [x for x, y in zip(ops, ops[1:] + [None])
            if y is None or y.start >= x.end]


def top_ops(tr: Trace, t0: float, t1: float, top: int = 10) -> list:
    """Device operations by total time in [t0, t1]: [[name, seconds], ...]."""
    tot = collections.Counter()
    for x in leaf_ops(tr.ops):
        d = min(x.end, t1) - max(x.start, t0)
        if d > 0:
            tot[x.name] += d
    return [[k, v] for k, v in tot.most_common(top)]


def program_runs(tr: Trace, label: str, t0: float, t1: float,
                 slack: float = 5e-3) -> list:
    """Program runs in [t0, t1] of the programs whose runs mostly start
    under the host annotation ``label``, give or take ``slack`` seconds
    (the host and device timelines of a trace agree to about a
    millisecond)."""
    anns = sorted((h.start, h.end) for h in tr.host if h.name == label)
    votes = collections.defaultdict(collections.Counter)
    starts = [a - slack for a, _ in anns]
    for x in tr.modules:
        i = bisect.bisect_right(starts, x.start) - 1
        hit = i >= 0 and x.start <= anns[i][1] + slack
        votes[x.group][hit] += 1
    mine = {g for g, c in votes.items() if c[True] > c[False]}
    return [x for x in tr.modules
            if x.group in mine and x.start >= t0 and x.end <= t1]
