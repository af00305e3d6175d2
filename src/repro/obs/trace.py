"""Request tracing: Chrome-trace timelines + jax profiler annotations.

Two aligned views of the same serving run (DESIGN.md §12):

* **Host spans** — :class:`TraceRecorder` collects per-request lifecycle
  spans (``queued -> admitted -> prefill -> decode -> finished/evicted``)
  and per-step engine spans, serialized as Chrome trace-event JSON
  (``serve.py --trace-out``); open the file in ``chrome://tracing`` or
  Perfetto.  Rows (tids): tid 0 is the engine's decode-step track, tid
  ``slot+1`` is that slot's request timeline — a request's whole life
  (queue wait, prefill, decode) renders as contiguous spans on the slot row
  it was admitted to, so slot churn / occupancy gaps are visible at a
  glance.
* **Profiler spans** — :func:`annotate` opens a
  ``jax.profiler.TraceAnnotation``, a host span on the same clock as the
  device's operations in a ``jax.profiler`` trace (and :func:`named_scope`
  tags traced computations via ``jax.named_scope``).  With no profiler
  session open a span costs about a microsecond.

Profiler span vocabulary, all opened on the engine's drive thread (the
``repro.`` prefix is what the benchmark's trace reduction keeps):

=========================  ==================================================
``repro.engine.step``      the whole of ``engine.step()`` (one decode step)
``repro.step.prepare``     deadline evictions, block appends, copy-on-write
                           and the block-table push before the dispatch
``repro.decode_step``      the decode program's dispatch (``repro.
                           decode_probed`` for the numerics-probed twin)
``repro.step.sample``      the sampling program's dispatch
``repro.step.wait``        ``np.asarray(toks)``: the host blocked on the
                           decode and sampling programs
``repro.step.emit``        the per-slot emit/finish/evict loop and the
                           ``last_token`` round trip
``repro.step.observe``     the metrics/trace feed and the snapshotter hook
``repro.drive.ops``        one drain of the server's client-op inbox
``repro.admit``            one admission, prefill to emitted first token
                           (``rid=`` argument)
``repro.prefill``          the B=1 prefill program's dispatch
``repro.admit.kv_scatter`` the prefill rows' scatter into the batch cache
                           (pool blocks and table push, or the slot row)
``repro.admit.wait``       the host blocked on the prefill (row length,
                           first token)
=========================  ==================================================

The benchmark reads these by name (``bench/trace_reduce.py`` attributes
programs to ``repro.decode_step`` / ``repro.prefill``; ``step_host_ms``
reads ``repro.decode_step``, ``repro.step.wait`` and ``repro.admit``; idle
gaps carry the innermost span open at their midpoint), so a span that the
benchmark reads keeps its name and what it wraps.

All timestamps are seconds on the caller's monotonic clock
(``time.perf_counter`` epoch); Chrome trace wants integer microseconds, the
conversion happens at serialization.
"""
from __future__ import annotations

import json
from typing import Optional

import jax

__all__ = ["TraceRecorder", "annotate", "named_scope"]


def annotate(name: str, **args):
    """Host-side profiler span (``args`` ride on the event, not its name)."""
    return jax.profiler.TraceAnnotation(name, **args)


def named_scope(name: str):
    """Trace-time scope: tags the ops a traced function emits so device
    profiles show ``name``."""
    return jax.named_scope(name)


class TraceRecorder:
    """Buffers Chrome trace events; ``save`` writes the JSON object format.

    ``span`` records a complete ("ph": "X") event, ``instant`` a point mark
    ("ph": "i") — both O(1) dict appends on the host, no jax involvement.
    ``max_events`` bounds memory on long runs (drops further events, counts
    the drops — a truncated trace is still valid JSON).
    """

    def __init__(self, max_events: int = 200_000):
        self.events: list = []
        self.max_events = max_events
        self.dropped = 0

    def _push(self, ev: dict) -> None:
        if len(self.events) >= self.max_events:
            self.dropped += 1
            return
        self.events.append(ev)

    def span(self, name: str, t0: float, t1: float, *, tid: int = 0,
             args: Optional[dict] = None) -> None:
        self._push({
            "name": name, "ph": "X", "pid": 0, "tid": tid,
            "ts": round(t0 * 1e6, 3), "dur": round(max(t1 - t0, 0.0) * 1e6, 3),
            **({"args": args} if args else {}),
        })

    def instant(self, name: str, t: float, *, tid: int = 0,
                args: Optional[dict] = None) -> None:
        self._push({
            "name": name, "ph": "i", "s": "t", "pid": 0, "tid": tid,
            "ts": round(t * 1e6, 3),
            **({"args": args} if args else {}),
        })

    def label_track(self, tid: int, label: str) -> None:
        """Name a tid row (Chrome's thread_name metadata event)."""
        self._push({"name": "thread_name", "ph": "M", "pid": 0, "tid": tid,
                    "args": {"name": label}})

    def to_json(self) -> dict:
        return {
            "traceEvents": list(self.events),
            "displayTimeUnit": "ms",
            "otherData": {"dropped_events": self.dropped},
        }

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f)
