"""What a metric reader is given: the run's records and the reduced trace.

Host records are on this process's ``time.perf_counter`` clock; the trace
is on the profiler's.  The ``bench.window`` annotation, opened at a known
host time, ties the two together.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses

import numpy as np

import trace_reduce


def records(completions, offset: float) -> tuple:
    """(decode steps, prefills) rebuilt from the engine's request records,
    on this process's clock (``offset``: this clock minus the engine's).

    A request's first token comes from its prefill, launched at its
    admission; every later token from one decode step, stamped with the
    step's time, so the rows of a step are the requests holding a token of
    that time.  Steps: [(time, [live length of each row])], a row's length
    counting the token the step writes.  Prefills: [(time, prompt tokens)].
    """
    steps = collections.defaultdict(list)
    fills = []
    for c in completions:
        fills.append((c.admitted_time + offset, c.prompt_len))
        for k, t in enumerate(c.token_times[1:], 1):
            steps[t + offset].append(c.prompt_len + k)
    return sorted(steps.items()), sorted(fills)


@dataclasses.dataclass
class Context:
    cell: object
    m: dict
    mix: dict
    win: object
    engine_completions: list
    steps: list            # (host time, live lengths) per decode step
    prefills: list         # (host time, prompt tokens) per prefill
    #                        (both from :func:`records`)
    setup_s: float
    peaks: dict
    n_devices: int
    trace: object = None
    trace_window: tuple = None   # (t0, t1) on the trace's clock
    offset: float = 0.0          # trace clock minus host clock
    admissions: list = ()        # (admitted, first token) per request, host

    # ------------------------------------------------------- host records --
    def stamps_in_window(self) -> np.ndarray:
        t0, t1 = self.win.t0, self.win.t1
        return np.asarray([t for r in self.win.all for t in r.stamps
                           if t0 <= t < t1])

    def token_gaps(self) -> np.ndarray:
        """Gaps between consecutive tokens of a request ending in the
        window."""
        t0, t1 = self.win.t0, self.win.t1
        out = []
        for r in self.win.all:
            s = r.stamps
            out += [b - a for a, b in zip(s, s[1:]) if t0 <= b < t1]
        return np.asarray(out)

    def completions(self) -> dict:
        return {c.rid: c for c in self.engine_completions}

    def admitting(self, t: float) -> bool:
        """Whether an admission (its prefill and first token) was under way
        at ``t`` on the trace's clock."""
        t -= self.offset
        return any(a <= t < b for a, b in self.admissions)

    def lateness_p99_ms(self):
        late = self.win.lateness
        return float(np.percentile(late, 99) * 1e3) if late else None

    # -------------------------------------------------------------- trace --
    def load_trace(self, trace_dir: str) -> None:
        self.trace = trace_reduce.load(trace_dir)
        ann = [h for h in self.trace.host if h.name == "bench.window"]
        if not ann:
            raise RuntimeError("the trace holds no bench.window annotation")
        self.trace_window = (ann[0].start, ann[0].end)
        self.offset = ann[0].start - self.win.trace[0]

    def busy_s(self) -> float:
        return trace_reduce.busy_s(self.trace, *self.trace_window)

    def unique(self, label: str, records: list) -> tuple:
        """(device seconds of the programs launched under ``label`` in the
        traced window, [the payload of each record that launched one]): a
        record whose work runs as several programs counts once."""
        pairs = self.matched(label, records, index=True)
        took = sum(x.end - x.start for x, _ in pairs)
        return took, [records[i][1] for i in sorted({i for _, i in pairs})]

    def program_runs(self, label: str) -> list:
        if self.trace is None:
            return []
        return trace_reduce.program_runs(self.trace, label,
                                         *self.trace_window)

    def matched(self, label: str, records: list, *, index=False) -> list:
        """[(program run, record payload)]: each run of the programs
        launched under ``label`` in the traced window, with the host record
        of the call that launched it (the latest one before it started);
        with ``index``, the record's index instead of its payload."""
        runs = self.program_runs(label)
        times = [t + self.offset for t, _ in records]
        out = []
        for x in runs:
            i = bisect.bisect_right(times, x.start) - 1
            if i >= 0:
                out.append((x, i if index else records[i][1]))
        return out

    def breakdown(self) -> dict:
        """Top device operations, and the longest idle gaps labelled with
        the host annotation open in them, or else with what the engine was
        doing by its records: an admission, or the host's work between two
        decode steps."""
        a, b = self.trace_window
        gaps = [[lab if lab != "none" else
                 "admission" if self.admitting(t) else "between steps", dt]
                for lab, dt, t in trace_reduce.idle_gaps(self.trace, a, b,
                                                         at=True)]
        return {"device_ops": trace_reduce.top_ops(self.trace, a, b),
                "idle_gaps": gaps}
