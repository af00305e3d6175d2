"""Host time per decode step: the mean, over the decode-step cycles of the
traced window, of the cycle less the time the host spent blocked on the
device in it.

A cycle runs from the start of one ``repro.decode_step`` span to the start
of the next, both inside the traced window; the time blocked is the summed
``repro.step.wait`` spans that start in it.  Cycles that hold the start of
a ``repro.admit`` span are left out: an admission stalls the grid for a
prefill, which is not the step's own host work.  Reads the host spans of
the trace alone.  No value without a device in the trace (a rehearsal on
the CPU), without ``repro.step.wait`` spans (a program that does not open
them), or with fewer than two such cycles in the window.
"""
import bisect


def read(ctx):
    if ctx.trace is None or not ctx.trace.n_devices:
        return None
    a, b = ctx.trace_window
    host = ctx.trace.host
    steps = sorted(h.start for h in host
                   if h.name == "repro.decode_step" and a <= h.start < b)
    waits = sorted((h.start, h.end - h.start) for h in host
                   if h.name == "repro.step.wait")
    if not waits:
        return None
    wait_starts = [s for s, _ in waits]
    admits = sorted(h.start for h in host if h.name == "repro.admit")
    cycles = []
    for s, e in zip(steps, steps[1:]):
        if bisect.bisect_left(admits, e) > bisect.bisect_left(admits, s):
            continue
        i, j = (bisect.bisect_left(wait_starts, t) for t in (s, e))
        cycles.append(e - s - sum(d for _, d in waits[i:j]))
    if len(cycles) < 2:
        return None
    return sum(cycles) / len(cycles) * 1e3
