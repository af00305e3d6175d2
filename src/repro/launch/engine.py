"""Slot-based continuous-batching serving engine over the ragged KV cache.

The decode batch is a fixed grid of ``max_slots`` slots sharing one model
cache (``model.init_cache(max_slots, S_max, policy)``).  Requests arrive on a
queue (Poisson arrivals in the benchmark driver), are *prefilled into a free
slot* the moment one exists (B=1 prefill, then a scatter of that row into the
batch cache — no other slot is touched or stalled), decode lockstep as one
batch while each row masks by its own ``len``, and are evicted (slot recycled)
on EOS or max-length.  This is exactly the memory-system serving shape the
posit KV cache is for: decode attention is HBM-bound, the cache stores 8/16-bit
posit codes, and the flash-decode kernel path decodes tiles on the fly
(``TransPolicy.attn_impl``, DESIGN.md §10).

The engine is model-agnostic over the decoder families (dense / moe / gemma3 /
vlm / zamba / xlstm): anything ``build_model`` returns with a ``prefill`` entry
point.  Greedy decoding is ``temperature=0``; otherwise temperature / top-k
sampling with a per-engine PRNG key.

Timing note: prefill compiles once per distinct prompt length — drivers that
care about compile time should draw prompt lengths from a small set (the
benchmark uses a handful of buckets).

Observability (DESIGN.md §12): pass ``metrics=`` (a
``repro.obs.MetricsRegistry``), ``tracer=`` (a ``TraceRecorder``) and/or
``numerics=`` (a ``NumericsWatcher``) and the engine feeds them per step and
per request — slot occupancy, admission/eviction counters by reason,
queue/TTFT/per-token latency histograms, decode-step durations, KV-byte
utilization, a rolling tokens/s window, Chrome-trace request spans, and
cadenced numerical-health probes.  All three default to ``None`` and cost
nothing when absent.  The numerics probe works by compiling a *second*
decode executable traced under the watcher's observer (``jax.debug.callback``
hooks bake in at trace time, so the ordinary decode step stays callback-free)
and routing every ``numerics.every``-th step through it.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import annotate

#: Drift checks run every this-many probed steps (each check is a few numpy
#: ops per site on (NBINS,) vectors — cheap, but not per-step cheap).
_CHECK_EVERY_PROBES = 16

#: Wire-schema version of Request / Completion JSON.  Snapshots, the HTTP
#: request plane (launch/server.py), and tests all speak this one schema;
#: ``from_json`` rejects any other version loudly instead of best-effort
#: parsing a shape this build never saw.  Dicts without a ``"v"`` key are
#: read as v1 (pre-versioning snapshots).
SCHEMA_VERSION = 1


def _check_schema_version(d: dict, what: str) -> None:
    v = int(d.get("v", 1))
    if v != SCHEMA_VERSION:
        raise ValueError(
            f"{what} JSON declares schema v{v}; this build speaks only "
            f"v{SCHEMA_VERSION} (refusing to guess at an unknown shape)")


@dataclasses.dataclass
class Request:
    """One generation request."""
    rid: int
    prompt: np.ndarray              # (prompt_len,) int32 token ids
    max_new_tokens: int = 16
    arrival_time: float = 0.0       # seconds since engine start
    deadline_s: Optional[float] = None  # wall-clock budget from admission

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    def to_json(self) -> dict:
        return {"v": SCHEMA_VERSION,
                "rid": self.rid, "prompt": np.asarray(self.prompt).tolist(),
                "max_new_tokens": self.max_new_tokens,
                "arrival_time": self.arrival_time,
                "deadline_s": self.deadline_s}

    @classmethod
    def from_json(cls, d: dict) -> "Request":
        _check_schema_version(d, "Request")
        return cls(rid=int(d["rid"]),
                   prompt=np.asarray(d["prompt"], np.int32),
                   max_new_tokens=int(d["max_new_tokens"]),
                   arrival_time=float(d["arrival_time"]),
                   deadline_s=d.get("deadline_s"))


@dataclasses.dataclass
class Completion:
    """Per-request serving record (tokens + latency breakdown)."""
    rid: int
    prompt_len: int
    tokens: list                    # generated token ids (includes EOS if hit)
    arrival_time: float
    admitted_time: float
    finished_time: float
    token_times: list               # absolute emission time of each token
    # eos | max_new | cache_full | cancel | numerics | timeout
    finish_reason: str = ""

    @property
    def queue_s(self) -> float:
        return self.admitted_time - self.arrival_time

    @property
    def ttft_s(self) -> float:
        """Time to first token (arrival -> first sampled token)."""
        return self.token_times[0] - self.arrival_time

    def per_token_s(self) -> list:
        """Inter-token latencies (first token measured from admission)."""
        starts = [self.admitted_time] + self.token_times[:-1]
        return [t - s for s, t in zip(starts, self.token_times)]

    def to_json(self) -> dict:
        return {"v": SCHEMA_VERSION, **dataclasses.asdict(self)}

    @classmethod
    def from_json(cls, d: dict) -> "Completion":
        _check_schema_version(d, "Completion")
        d = {k: v for k, v in d.items() if k != "v"}
        return cls(**d)


def poisson_requests(n: int, *, arrival_rate: float, prompt_lens=(16, 24, 32),
                     max_new_tokens: int = 16, vocab: int = 32000,
                     seed: int = 0) -> list:
    """n requests with exponential inter-arrival times (rate = req/s).

    ``arrival_rate <= 0`` means everything arrives at t=0 (closed-loop /
    offline batch).  Prompt lengths cycle through ``prompt_lens`` buckets so
    prefill compiles a bounded number of programs.
    """
    rng = np.random.default_rng(seed)
    t = 0.0
    reqs = []
    for i in range(n):
        if arrival_rate > 0:
            t += float(rng.exponential(1.0 / arrival_rate))
        plen = int(prompt_lens[i % len(prompt_lens)])
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, vocab, (plen,)).astype(np.int32),
            max_new_tokens=max_new_tokens, arrival_time=t))
    return reqs


#: Cache containers whose ``k``/``v`` leaves are KV code arrays (shared with
#: serve.py's byte accounting and the ft fault-injection plane).
KV_CONTAINERS = ("kv", "shared_kv", "self", "cross")


def _slot_index(leaf, slot):
    """Index tuple selecting row ``slot`` of a KV leaf.

    KV code arrays come in two layouts: ``(B, H, S, hd)`` (per-layer list —
    gemma3 / zamba shared_kv / encdec) and ``(L, B, H, S, hd)`` (a vmapped
    layer stack).  The batch axis is 0 or 1 by rank.
    """
    return (slice(None), slot) if leaf.ndim == 5 else (slot,)


def map_kv_rows(cache, fn):
    """Apply ``fn(path_keys, leaf)`` to every K/V code leaf; other leaves
    pass through.  The traversal knows which leaves are KV (``k``/``v``
    inside a KV container) so callers (slot scrub, NaR fault injection)
    don't re-derive the cache layout."""
    def visit(path, leaf):
        keys = [p.key for p in path if hasattr(p, "key")]
        if keys and keys[-1] in ("k", "v") \
                and any(k in KV_CONTAINERS for k in keys[:-1]):
            return fn(keys, leaf)
        return leaf
    return jax.tree_util.tree_map_with_path(visit, cache)


def scrub_slot(cache, slot: int):
    """Zero the KV rows of ``slot`` (quarantine): a slot evicted for
    nonfinite logits leaves NaR codes in its cache rows, and the decode grid
    keeps computing over *every* row — without the scrub the dead row would
    feed NaN activations into the numerics probes forever (and re-trip the
    degradation ladder on healthy traffic).  Code 0 decodes to exact 0.0 in
    every posit format, so the scrubbed row is numerically inert."""
    def zero(keys, leaf):
        return leaf.at[_slot_index(leaf, slot)].set(
            jnp.zeros((), leaf.dtype))
    return map_kv_rows(cache, zero)


def _write_slot(full, one, slot):
    """Scatter row 0 of the B=1 cache ``one`` into row ``slot`` of ``full``.

    The batch axis of each leaf is found structurally: it is the unique axis
    where the two shapes differ (the single-request cache was built with the
    same S_max/layout, B=1).  Leaves with identical shapes (the scalar
    ``pos`` counter) are shared state the engine manages itself and are left
    untouched.
    """
    def wr(f, o):
        if f.shape == o.shape:
            return f
        axes = [i for i, (a, b) in enumerate(zip(f.shape, o.shape)) if a != b]
        if len(axes) != 1 or o.shape[axes[0]] != 1:
            raise ValueError(
                f"ambiguous batch axis for cache leaf {f.shape} vs {o.shape}")
        return jax.lax.dynamic_update_slice_in_dim(f, o, slot, axis=axes[0])
    return jax.tree.map(wr, full, one)


def _sample(logits, key, temperature: float, top_k: int):
    """(B, V) logits -> (B,) tokens. temperature==0 is greedy argmax."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


class ContinuousBatchingEngine:
    """Admission + decode + eviction over a fixed slot grid.

    Drive it either with :meth:`run` (wall-clock loop honoring request
    arrival times) or manually with :meth:`submit` / :meth:`admit` /
    :meth:`step` (deterministic staggered-admission tests).
    """

    def __init__(self, model, params, policy, *, max_slots: int, S_max: int,
                 eos_id: Optional[int] = None, temperature: float = 0.0,
                 top_k: int = 0, seed: int = 0,
                 prefill_kwargs: Optional[Callable] = None,
                 metrics=None, tracer=None, numerics=None,
                 snapshotter=None, faults=None, watchdog=None,
                 deadline_s: Optional[float] = None,
                 check_every_probes: int = _CHECK_EVERY_PROBES):
        if model.prefill is None:
            raise ValueError(
                f"family {model.cfg.family!r} has no prefill entry point")
        self.model, self.params, self.policy = model, params, policy
        self.max_slots, self.S_max = max_slots, S_max
        self.eos_id, self.temperature, self.top_k = eos_id, temperature, top_k
        # per-arg callable for families needing extra prefill inputs (vlm
        # patch embeds); receives the Request, returns a kwargs dict
        self._prefill_kwargs = prefill_kwargs or (lambda req: {})
        # observability sinks (all optional; None = feature off, zero cost)
        self.metrics, self.tracer, self.numerics = metrics, tracer, numerics
        # fault-tolerance plane (repro.ft.serving, DESIGN.md §13): cadenced
        # crash-safe snapshots, chaos injection under test/bench control, the
        # numerics-driven degradation watchdog, per-request deadlines
        self.snapshotter, self.faults, self.watchdog = \
            snapshotter, faults, watchdog
        self.deadline_s = deadline_s
        self.check_every_probes = check_every_probes
        if tracer is not None:
            tracer.label_track(0, "engine")
            for s in range(max_slots):
                tracer.label_track(s + 1, f"slot {s}")
        self._init_state(seed)
        self._build_executables(policy)
        # the pre-write cache is donated too: admission must not copy the
        # whole S_max cache to update one row
        self._write = jax.jit(_write_slot, donate_argnums=(0,))

    def _build_executables(self, policy) -> None:
        """(Re)build the jitted decode/prefill programs for ``policy``.

        Called at init and by :meth:`apply_policy` when the degradation
        watchdog widens a site's weight format — the KV-cache layout lives on
        the policy *base*, which overlays never touch, so the live cache
        stays valid across a swap.
        """
        model, S_max = self.model, self.S_max

        # named functions, so profiles show jit_decode_step / jit_prefill
        def decode_step(p, t, c):
            return model.decode_step(p, t, c, policy)

        def decode_step_probed(p, t, c):
            return model.decode_step(p, t, c, policy)

        def prefill(p, toks, kw):
            return model.prefill(p, toks, policy, S_max=S_max, **kw)

        # the cache is donated: decode updates the KV buffers in place
        # instead of copying S_max-sized arrays every step (the engine never
        # reads a pre-step cache again; on backends without donation support
        # this degrades to the copy)
        self._decode = jax.jit(decode_step, donate_argnums=(2,))
        # the numerics-probed twin: identical computation, but *traced* under
        # the watcher's observer so the per-site debug-callback reductions
        # bake into this executable only — the plain step stays probe-free
        # and the probe cost amortizes over the cadence (DESIGN.md §12)
        self._decode_probed = None
        if self.numerics is not None:
            self._decode_probed = jax.jit(decode_step_probed,
                                          donate_argnums=(2,))
        # compiled per distinct prompt length (admission is on the serving
        # critical path; drivers bucket prompt lengths to bound retraces)
        self._prefill = jax.jit(prefill)

    def apply_policy(self, policy) -> None:
        """Swap the serving policy mid-flight (degradation ladder step).

        Only weight-format overlays are legal: the KV-cache format must be
        unchanged, or the live cache's code arrays would be reinterpreted
        under the wrong codec.
        """
        old_kv = getattr(self.policy, "kv_cache", None)
        new_kv = getattr(policy, "kv_cache", None)
        if (old_kv is None) != (new_kv is None) or \
                (old_kv is not None and old_kv.name != new_kv.name):
            raise ValueError(
                f"apply_policy may not change the KV-cache format "
                f"({old_kv} -> {new_kv}); only weight overlays are hot-"
                f"swappable")
        self.policy = policy
        self._build_executables(policy)

    def _init_cache(self):
        """Device-cache construction hook (the paged engine builds block
        pools + a block table here instead of the dense slot grid)."""
        return self.model.init_cache(self.max_slots, self.S_max, self.policy)

    def _init_state(self, seed: int) -> None:
        self._key = jax.random.key(seed)
        self.cache = self._init_cache()
        self.lens = np.zeros((self.max_slots,), np.int32)
        self.last_token = jnp.zeros((self.max_slots,), jnp.int32)
        self.active = np.zeros((self.max_slots,), bool)
        self.slot_req: list = [None] * self.max_slots
        self.slot_tokens: list = [[] for _ in range(self.max_slots)]
        self.slot_token_times: list = [[] for _ in range(self.max_slots)]
        self.slot_admitted = np.zeros((self.max_slots,), np.float64)
        self.queue: list = []          # pending Requests (FIFO)
        self.completions: list = []
        # rid -> [queue.Queue] of live stream subscribers (transient client
        # state: never snapshotted, cleared by reset)
        self._subs: dict = {}
        self.steps = 0                 # decode steps executed
        self.last_now = 0.0            # newest clock value seen (snapshots
        #                                rebase restored timestamps on it)
        # rolling decode-rate window (created lazily; survives _init_state
        # only via the registry's own histograms — the window restarts)
        self._tok_rate = None
        if self.metrics is not None:
            from repro.obs.metrics import RollingRate
            self._tok_rate = RollingRate(window_s=10.0)
            # pre-resolved instrument handles: _observe_step runs per decode
            # step, so it must not pay registry lookups / bucket construction
            m = self.metrics
            self._m_steps = m.counter("decode_steps", "decode steps executed")
            self._m_tokens = m.counter("tokens_emitted",
                                       "sampled tokens (prefill + decode)")
            self._m_step_s = m.histogram("decode_step_s",
                                         "wall time of one grid step")
            self._m_slots = m.histogram(
                "slots_active", "live slots per decode step",
                buckets=tuple(float(b) for b in range(1, self.max_slots + 1)))
            self._m_occ = m.gauge("slot_occupancy", "live slots / max_slots")
            self._m_kv = m.gauge("kv_utilization",
                                 "share of the KV store in use")
            self._m_queue = m.gauge("queue_depth", "requests waiting")
            self._m_rate = m.gauge("decode_tok_per_s_window",
                                   "tokens/s over the rolling 10s window")
            self._m_recal = m.gauge(
                "numerics_recalibrate",
                "1 when activation drift exceeded threshold")

    def reset(self, seed: int = 0) -> None:
        """Clear all serving state but keep the compiled decode/write programs.

        Equivalence tests use this to run staggered-admission and
        single-request workloads through the *same executables*: XLA:CPU
        compiles are not bit-stable across program instances, so comparing
        tokens across two engines (or against a hand-rolled B=1 loop) can
        flip a near-tied greedy argmax; within one engine the comparison is
        deterministic."""
        self._init_state(seed)

    # ----------------------------------------------------- snapshot/restore --
    def snapshot(self) -> dict:
        """Full engine state as ``{"arrays": pytree, "meta": json-able}``.

        ``arrays`` is everything device-resident (the ragged posit KV cache,
        per-slot last tokens, the sampler PRNG key as raw key data) — a
        checkpointable pytree.  ``meta`` is the host bookkeeping: slot grid
        (lens, active, admitted stamps), emitted-token buffers, the in-flight
        request per slot, the pending queue, finished completions, and the
        step/probe counters.  Together they are sufficient for
        :meth:`restore` to continue every stream **bit-identically** (same
        policy + same executables + same RNG ⇒ same tokens — posit codecs
        are deterministic, so the restored KV codes replay exactly).
        """
        meta = {
            "version": 1,
            "steps": self.steps,
            "last_now": self.last_now,
            "lens": self.lens.tolist(),
            "active": [bool(a) for a in self.active],
            "slot_admitted": self.slot_admitted.tolist(),
            "slot_tokens": [list(t) for t in self.slot_tokens],
            "slot_token_times": [list(t) for t in self.slot_token_times],
            "slots": [r.to_json() if r is not None else None
                      for r in self.slot_req],
            "queue": [r.to_json() for r in self.queue],
            "completions": [c.to_json() for c in self.completions],
            "probes": self.numerics.probes if self.numerics else 0,
            # config fingerprint: restore asserts these match, a snapshot
            # taken under one policy must not silently continue under another
            "max_slots": self.max_slots,
            "S_max": self.S_max,
            "policy": self.policy.describe(),
            "temperature": self.temperature,
            "top_k": self.top_k,
        }
        # host copies, not live references: the decode step DONATES the cache
        # buffers, so a snapshot holding device references would be silently
        # invalidated by the very next step (np.array forces the copy)
        arrays = jax.tree.map(np.array, {
            "cache": self.cache,
            "last_token": self.last_token,
            "rng_key": jax.random.key_data(self._key),
        })
        return {"arrays": arrays, "meta": meta}

    def snapshot_like(self) -> dict:
        """The arrays pytree a checkpoint restore deserializes into (same
        structure/shapes/dtypes as :meth:`snapshot`'s ``arrays``)."""
        return {"cache": self.cache, "last_token": self.last_token,
                "rng_key": jax.random.key_data(self._key)}

    def restore(self, snap: dict, *, now: float = 0.0) -> None:
        """Install a :meth:`snapshot` (possibly loaded from disk).

        ``now`` rebases every restored timestamp so deadlines and latency
        accounting keep working across a process restart: the shift maps the
        snapshot's ``last_now`` onto the restoring clock's ``now``.
        """
        meta, arrays = snap["meta"], snap["arrays"]
        if (meta["max_slots"], meta["S_max"]) != (self.max_slots, self.S_max):
            raise ValueError(
                f"snapshot grid ({meta['max_slots']} slots, S_max "
                f"{meta['S_max']}) does not match this engine "
                f"({self.max_slots}, {self.S_max})")
        if meta["policy"] != self.policy.describe():
            raise ValueError(
                "snapshot policy does not match this engine's policy:\n"
                f"  snapshot: {meta['policy']}\n"
                f"  engine:   {self.policy.describe()}\n"
                "bit-identical continuation requires the same policy")
        shift = now - float(meta.get("last_now", 0.0))
        self.cache = jax.tree.map(jnp.asarray, arrays["cache"])
        self.last_token = jnp.asarray(arrays["last_token"], jnp.int32)
        self._key = jax.random.wrap_key_data(
            jnp.asarray(arrays["rng_key"], jnp.uint32))
        self.steps = int(meta["steps"])
        self.last_now = now
        self.lens = np.asarray(meta["lens"], np.int32)
        self.active = np.asarray(meta["active"], bool)
        self.slot_admitted = np.asarray(meta["slot_admitted"], np.float64) \
            + shift
        self.slot_tokens = [list(t) for t in meta["slot_tokens"]]
        self.slot_token_times = [[t + shift for t in ts]
                                 for ts in meta["slot_token_times"]]
        # requests carry arrival_time too — deadlines and queue-latency
        # accounting measure from it, so it rebases like every other stamp
        def _req(r):
            req = Request.from_json(r)
            req.arrival_time += shift
            return req
        self.slot_req = [_req(r) if r is not None else None
                         for r in meta["slots"]]
        self.queue = [_req(r) for r in meta["queue"]]
        self.completions = [Completion.from_json(c)
                            for c in meta["completions"]]
        if self.numerics is not None:
            self.numerics.probes = int(meta.get("probes", 0))
        self._sync_lens()
        if self.metrics is not None:
            self.metrics.counter(
                "engine_restores", "snapshots restored into the engine").inc()

    # ---------------------------------------------------------- client API ----
    # The stable engine client surface (DESIGN.md §14): submit() -> rid,
    # results()/result(rid) for finished work, subscribe()/stream(rid) for
    # live token streams, cancel(rid).  The HTTP plane (launch/server.py),
    # benchmarks, and tests all drive the engine through these five.

    def submit(self, req: Request) -> int:
        """Enqueue a request; returns its rid (the stream/cancel handle)."""
        self.queue.append(req)
        return req.rid

    def results(self) -> list:
        """All finished Completions, in finish order."""
        return list(self.completions)

    def result(self, rid: int):
        """The Completion for ``rid``, or None while still in flight."""
        for c in self.completions:
            if c.rid == rid:
                return c
        return None

    def subscribe(self, rid: int):
        """A ``queue.Queue`` of stream events for ``rid``.

        Events are dicts: ``{"event": "token", "rid", "token", "index",
        "t"}`` per emitted token, then one ``{"event": "finish", "rid",
        "finish_reason", "n_tokens"}``.  Anything already emitted (or a
        finished request) is replayed first, so a subscriber attached late
        sees the complete stream.  Queues are thread-safe: the serving
        thread puts, a client thread gets.
        """
        import queue as queue_mod
        q = queue_mod.Queue()
        for slot in range(self.max_slots):
            r = self.slot_req[slot]
            if self.active[slot] and r is not None and r.rid == rid:
                for i, (tok, t) in enumerate(zip(self.slot_tokens[slot],
                                                 self.slot_token_times[slot])):
                    q.put({"event": "token", "rid": rid, "token": tok,
                           "index": i, "t": t})
        for c in self.completions:
            if c.rid == rid:
                for i, (tok, t) in enumerate(zip(c.tokens, c.token_times)):
                    q.put({"event": "token", "rid": rid, "token": tok,
                           "index": i, "t": t})
                q.put({"event": "finish", "rid": rid,
                       "finish_reason": c.finish_reason,
                       "n_tokens": len(c.tokens)})
        self._subs.setdefault(rid, []).append(q)
        return q

    def unsubscribe(self, rid: int, q) -> None:
        subs = self._subs.get(rid)
        if subs and q in subs:
            subs.remove(q)
            if not subs:
                del self._subs[rid]

    def stream(self, rid: int, timeout: Optional[float] = None):
        """Blocking generator over :meth:`subscribe` events; ends after the
        finish event.  Drive the engine from another thread (or interleave
        ``admit``/``step`` with consumption); the asyncio server bridges
        this into ``async for`` via a worker thread."""
        q = self.subscribe(rid)
        try:
            while True:
                ev = q.get(timeout=timeout)
                yield ev
                if ev["event"] == "finish":
                    return
        finally:
            self.unsubscribe(rid, q)

    def _emit_token(self, slot: int, tok: int, t: float) -> None:
        self.slot_tokens[slot].append(tok)
        self.slot_token_times[slot].append(t)
        rid = self.slot_req[slot].rid
        for q in self._subs.get(rid, ()):
            q.put({"event": "token", "rid": rid, "token": tok,
                   "index": len(self.slot_tokens[slot]) - 1, "t": t})

    def _finish(self, comp: Completion) -> None:
        self.completions.append(comp)
        for q in self._subs.get(comp.rid, ()):
            q.put({"event": "finish", "rid": comp.rid,
                   "finish_reason": comp.finish_reason,
                   "n_tokens": len(comp.tokens)})

    # ------------------------------------------------------------- admission --
    def free_slots(self) -> list:
        return [i for i in range(self.max_slots) if not self.active[i]]

    def _can_admit(self, req: Request) -> bool:
        """Beyond a free slot, can the cache take this request right now?
        The slot grid always can (every slot owns S_max rows); the paged
        engine gates on block availability (queueing is the backpressure)."""
        return True

    def _prefill_into_slot(self, req: Request, slot: int):
        """Prefill ``req`` and install its KV into ``slot``; returns
        ``(logits, row_len)``.  The paged engine overrides this with
        prefix-matched block admission."""
        tokens = jnp.asarray(req.prompt, jnp.int32)[None]
        with annotate("repro.prefill"):
            logits, one_cache = self._prefill(
                self.params, tokens, self._prefill_kwargs(req))
        # true cache occupancy after prefill (vlm rows include the patch
        # prefix; recurrent families report their prompt length)
        with annotate("repro.admit.wait"):
            row_len = int(one_cache["lens"][0])
        with annotate("repro.admit.kv_scatter"):
            if self.max_slots == 1:
                # every leaf shape matches the B=1 prefill cache, so the
                # structural scatter below would be a silent no-op — the
                # single-request cache *is* the batch cache
                self.cache = one_cache
            else:
                self.cache = self._write(self.cache, one_cache,
                                         jnp.int32(slot))
        return logits, row_len

    def admit(self, now: float = 0.0, clock: Optional[Callable] = None) -> int:
        """Prefill queued requests into free slots; returns #admitted.

        The first token of each admitted request is sampled from the prefill
        logits immediately (it is emitted by this call, not by the next
        decode step).  ``clock`` (when given) re-reads the time after the
        prefill executes so the first token's emission time — and therefore
        TTFT — includes prefill cost; without it both stamps use ``now``.
        """
        admitted = 0
        for slot in self.free_slots():
            if not self.queue:
                break
            if not self._can_admit(self.queue[0]):
                break       # FIFO: later requests must not starve the head
            req = self.queue.pop(0)
            with annotate("repro.admit", rid=req.rid):
                t_admit = clock() if clock else now
                if req.prompt_len + req.max_new_tokens > self.S_max:
                    raise ValueError(
                        f"request {req.rid}: prompt {req.prompt_len} + "
                        f"max_new {req.max_new_tokens} exceeds S_max {self.S_max}")
                logits, row_len = self._prefill_into_slot(req, slot)
                if row_len + req.max_new_tokens > self.S_max:
                    raise ValueError(
                        f"request {req.rid}: prefill occupies {row_len} cache "
                        f"rows (incl. any prefix) + max_new "
                        f"{req.max_new_tokens} exceeds S_max {self.S_max}")
                with annotate("repro.admit.wait"):
                    tok = int(self._next_token(logits)[0])  # blocks on it
                t_first = clock() if clock else now
                self.lens[slot] = row_len
                self.last_token = self.last_token.at[slot].set(tok)
                self.active[slot] = True
                self.slot_req[slot] = req
                self.slot_tokens[slot] = []
                self.slot_token_times[slot] = []
                self.slot_admitted[slot] = t_admit
                self._emit_token(slot, tok, t_first)
                self._sync_lens()
                if self.metrics is not None:
                    self.metrics.counter(
                        "requests_admitted",
                        "requests prefilled into a slot").inc()
                    self.metrics.counter("tokens_emitted",
                                         "sampled tokens (prefill + decode)").inc()
                    self.metrics.histogram(
                        "queue_s", "arrival -> admission wait").observe(
                            t_admit - req.arrival_time)
                    self.metrics.histogram(
                        "prefill_s", "admission -> first token").observe(
                            t_first - t_admit)
                    self.metrics.histogram(
                        "ttft_s", "arrival -> first token").observe(
                            t_first - req.arrival_time)
                    if self._tok_rate is not None:
                        self._tok_rate.add(t_first)
                if self.tracer is not None:
                    tid = slot + 1
                    self.tracer.span(f"queued rid={req.rid}", req.arrival_time,
                                     t_admit, tid=tid,
                                     args={"rid": req.rid,
                                           "prompt_len": req.prompt_len})
                    self.tracer.span(f"prefill rid={req.rid}", t_admit, t_first,
                                     tid=tid, args={"rid": req.rid})
                self._maybe_finish(slot, tok, t_first)  # max_new_tokens == 1
            admitted += 1
        return admitted

    def _next_token(self, logits):
        self._key, sub = jax.random.split(self._key)
        return _sample(logits, sub, self.temperature, self.top_k)

    def _sync_lens(self) -> None:
        """Engine slot lengths are authoritative: push them into the cache's
        per-row positions (freed/recycled slots reset; decode_step increments
        every row, active or not).

        The copy is load-bearing: ``jnp.asarray`` of a host numpy array can
        be zero-copy on CPU, and ``self.lens`` is mutated in place every
        step — an aliased buffer races with the async decode dispatch."""
        self.cache["lens"] = jnp.asarray(self.lens.copy(), jnp.int32)

    # --------------------------------------------------------------- decode ---
    def step(self, now: float = 0.0) -> int:
        """One decode step over the whole slot grid; returns #tokens emitted.

        Its phases are profiler spans (``repro.step.*``, obs/trace.py), so a
        device trace attributes the host's time between two decode steps."""
        with annotate("repro.engine.step"):
            return self._step(now)

    def _step(self, now: float) -> int:
        self.last_now = max(self.last_now, now)
        with annotate("repro.step.prepare"):
            if self.faults is not None:
                # chaos layer (repro.ft.serving.FaultPlan): may stall, inject
                # NaR into KV pages, or raise preemption — before the decode
                # so an injected fault is live in THIS step's computation
                self.faults.on_step(self)
            self._evict_expired(now)
            if self.active.any():
                self._prepare_decode(now)
        if not self.active.any():   # pool pressure may have evicted the rest
            return 0
        t0 = time.perf_counter()
        probed = (self.numerics is not None
                  and self.numerics.should_probe(self.steps))
        if probed:
            # trace-time observer installation: the first probed call bakes
            # the per-site reduction callbacks into _decode_probed only
            with self.numerics.observing(), annotate("repro.decode_probed"):
                logits, self.cache = self._decode_probed(
                    self.params, self.last_token, self.cache)
            self.numerics.note_probe()
        else:
            with annotate("repro.decode_step"):
                logits, self.cache = self._decode(self.params, self.last_token,
                                                  self.cache)
        self.steps += 1
        with annotate("repro.step.sample"):
            toks = self._next_token(logits)
            # nonfinite-logit quarantine (watchdog only — the reduction is an
            # extra device op per step, so the bare engine never pays it): a
            # slot whose logits went NaR is evicted as a partial Completion
            # instead of sampling garbage, and its cache rows are scrubbed so
            # the dead row cannot poison the shared grid or the numerics
            # probes
            bad = None
            if self.watchdog is not None:
                bad = jnp.any(~jnp.isfinite(logits), axis=-1)
        with annotate("repro.step.wait"):
            toks_np = np.asarray(toks)
            if bad is not None:
                bad = np.asarray(bad)
        with annotate("repro.step.emit"):
            self.lens += 1      # mirror decode_step's per-row increment
            emitted = 0
            last_np = np.asarray(self.last_token).copy()
            for slot in range(self.max_slots):
                if not self.active[slot]:
                    continue
                if bad is not None and bad[slot]:
                    self._quarantine(slot, now)
                    last_np[slot] = 0
                    continue
                tok = int(toks_np[slot])
                self._emit_token(slot, tok, now)
                last_np[slot] = tok
                emitted += 1
                self._maybe_finish(slot, tok, now)
            self.last_token = jnp.asarray(last_np)
        with annotate("repro.step.observe"):
            self._observe_step(now, t0, emitted, probed)
            if self.snapshotter is not None:
                self.snapshotter.on_step(self)
        return emitted

    def _prepare_decode(self, now: float) -> None:
        """Pre-step cache maintenance hook.  The slot grid needs none; the
        paged engine allocates block-boundary pages, runs copy-on-write on
        shared tails, and refreshes the device block table here."""

    def _quarantine(self, slot: int, now: float) -> None:
        """Evict a nonfinite-logit slot and neutralize its KV so the dead
        rows cannot poison the shared grid or the numerics probes."""
        self._evict(slot, now, "numerics")
        self.cache = scrub_slot(self.cache, slot)

    def _release_slot(self, slot: int) -> None:
        """Per-eviction cache cleanup hook (the slot grid reuses rows as-is;
        the paged engine drops the slot's block references)."""

    def inject_nar_into(self, slot: int, count: int) -> None:
        """Chaos hook: poison the first ``count`` occupied KV positions of
        ``slot`` with NaR codes (``ft.FaultPlan`` dispatches here so the
        cache layout stays with the engine that owns it)."""
        from repro.ft.serving import _nar_code

        n = max(1, min(count, max(int(self.lens[slot]), 1)))

        def poison(keys, leaf):
            idx = _slot_index(leaf, slot)
            row = leaf[idx]                 # (..., H, S, hd) or (H, S, hd)
            s_ax = row.ndim - 2             # sequence axis of the row
            sl = [slice(None)] * row.ndim
            sl[s_ax] = slice(0, n)
            row = row.at[tuple(sl)].set(_nar_code(leaf))
            return leaf.at[idx].set(row)
        self.cache = map_kv_rows(self.cache, poison)

    def _deadline_of(self, req) -> Optional[float]:
        return req.deadline_s if req.deadline_s is not None else self.deadline_s

    def _evict_expired(self, now: float) -> None:
        """Per-request wall-clock deadline enforcement (measured from
        arrival): expired in-flight slots are evicted as partial Completions
        with ``finish_reason="timeout"``; expired queued requests are
        retired without ever being admitted."""
        for slot in range(self.max_slots):
            if not self.active[slot]:
                continue
            d = self._deadline_of(self.slot_req[slot])
            if d is not None and now - self.slot_req[slot].arrival_time > d:
                self._evict(slot, now, "timeout")
        kept = []
        for req in self.queue:
            d = self._deadline_of(req)
            if d is not None and now - req.arrival_time > d:
                self._finish(Completion(
                    rid=req.rid, prompt_len=req.prompt_len, tokens=[],
                    arrival_time=req.arrival_time, admitted_time=now,
                    finished_time=now, token_times=[],
                    finish_reason="timeout"))
                if self.metrics is not None:
                    self.metrics.counter(
                        "requests_finished",
                        "requests retired, by reason").inc(label="timeout")
            else:
                kept.append(req)
        self.queue = kept

    def _observe_step(self, now: float, t0: float, emitted: int,
                      probed: bool) -> None:
        """Per-step metrics/trace feed (no device syncs beyond what step()
        already does — ``np.asarray(toks)`` blocked on the decode)."""
        if self.numerics is not None and probed \
                and self.numerics.probes % self.check_every_probes == 0:
            self.numerics.check()
            if self.watchdog is not None:
                # degradation controller (repro.ft.serving): reads the fresh
                # SiteHealth rows, may widen formats via apply_policy
                self.watchdog.maybe_degrade(self)
        if self.metrics is not None:
            dt = time.perf_counter() - t0
            n_active = int(self.active.sum())
            self._m_steps.inc()
            self._m_tokens.inc(emitted)
            self._m_step_s.observe(dt)
            self._m_slots.observe(n_active)
            self._m_occ.set(n_active / self.max_slots)
            self._m_kv.set(self._kv_utilization())
            self._m_queue.set(len(self.queue))
            self._tok_rate.add(now, emitted)
            self._m_rate.set(self._tok_rate.rate(now))
            if self.numerics is not None:
                self._m_recal.set(float(self.numerics.recalibrate))
        if self.tracer is not None:
            self.tracer.span("decode_step", t0, time.perf_counter(),
                             tid=0, args={"emitted": emitted,
                                          "probed": probed})

    def _kv_utilization(self) -> float:
        """Share of the KV store in use: occupied rows over allocated rows
        (the paged engine counts pool blocks instead)."""
        return int(self.lens.sum()) / (self.max_slots * self.S_max)

    def _maybe_finish(self, slot: int, tok: int, now: float) -> bool:
        req = self.slot_req[slot]
        # precedence: EOS is the semantic finish; max_new the requested cap;
        # cache_full the forced eviction (only reachable when neither hit)
        reason = ""
        if self.eos_id is not None and tok == self.eos_id:
            reason = "eos"
        elif len(self.slot_tokens[slot]) >= req.max_new_tokens:
            reason = "max_new"
        elif self.lens[slot] + 1 >= self.S_max:  # no room for another write
            reason = "cache_full"
        if reason:
            self._evict(slot, now, reason)
        return bool(reason)

    def _evict(self, slot: int, now: float, reason: str) -> None:
        """Retire ``slot``: record the Completion, free the slot, feed sinks."""
        req = self.slot_req[slot]
        comp = Completion(
            rid=req.rid, prompt_len=req.prompt_len,
            tokens=list(self.slot_tokens[slot]),
            arrival_time=req.arrival_time,
            admitted_time=float(self.slot_admitted[slot]),
            finished_time=now,
            token_times=list(self.slot_token_times[slot]),
            finish_reason=reason)
        self._finish(comp)
        self.active[slot] = False
        self.slot_req[slot] = None
        self._release_slot(slot)
        if self.metrics is not None:
            m = self.metrics
            m.counter("requests_finished",
                      "requests retired, by reason").inc(label=reason)
            m.histogram("request_s", "admission -> finish").observe(
                now - comp.admitted_time)
            h = m.histogram("inter_token_s", "time between consecutive tokens")
            for dt in comp.per_token_s()[1:]:   # [0] is prefill, not decode
                h.observe(dt)
        if self.tracer is not None:
            tid = slot + 1
            if comp.token_times:
                self.tracer.span(f"decode rid={req.rid}", comp.token_times[0],
                                 now, tid=tid,
                                 args={"rid": req.rid,
                                       "tokens": len(comp.tokens),
                                       "finish_reason": reason})
            self.tracer.instant(f"evict rid={req.rid} ({reason})", now,
                                tid=tid, args={"rid": req.rid,
                                               "reason": reason})

    def cancel(self, rid: int, now: float = 0.0) -> bool:
        """Cancel a request by id — mid-flight (slot evicted, partial tokens
        recorded as a Completion with ``finish_reason="cancel"``) or still
        queued (dropped, no Completion).  Returns True if anything matched."""
        for slot in range(self.max_slots):
            req = self.slot_req[slot]
            if self.active[slot] and req is not None and req.rid == rid:
                self._evict(slot, now, "cancel")
                return True
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                del self.queue[i]
                # no Completion for a never-admitted request, but live
                # streams must still terminate
                for q in self._subs.get(rid, ()):
                    q.put({"event": "finish", "rid": rid,
                           "finish_reason": "cancel", "n_tokens": 0})
                if self.metrics is not None:
                    self.metrics.counter("requests_cancelled_queued",
                                         "cancelled before admission").inc()
                return True
        return False

    # ------------------------------------------------------------------ run ---
    def run(self, requests: list, *, clock: Optional[Callable] = None,
            preemption=None, straggler=None) -> list:
        """Serve ``requests`` (sorted by arrival_time) to completion.

        ``clock`` defaults to wall time from the first call; arrivals are
        honored against it, so with a Poisson workload the decode batch
        genuinely breathes (slots drain and refill mid-flight).

        The loop also drains state already inside the engine — active slots
        and queued requests installed by :meth:`restore` — so a resumed
        process calls ``run([])`` (or ``run(leftover)``) and every in-flight
        stream continues to completion.

        ``preemption`` (a ``ft.PreemptionSignal``) makes the loop drain-then-
        snapshot on SIGTERM: the in-flight step finishes, every not-yet-
        submitted request joins the queue, a forced snapshot commits (when a
        snapshotter is attached), and the loop exits with work left — the
        successor process restores and finishes it.  ``straggler`` (a
        ``ft.StragglerMonitor``) observes per-step wall times and feeds the
        ``straggler_steps`` counter.
        """
        pending = sorted(requests, key=lambda r: r.arrival_time)
        t0 = time.perf_counter()
        clock = clock or (lambda: time.perf_counter() - t0)
        while pending or self.queue or self.active.any():
            now = clock()
            while pending and pending[0].arrival_time <= now:
                self.submit(pending.pop(0))
            if preemption is not None and preemption.triggered:
                # graceful drain: everything not yet submitted joins the
                # queue so the forced snapshot carries the full workload
                for req in pending:
                    self.submit(req)
                pending = []
                self.last_now = max(self.last_now, clock())
                if self.snapshotter is not None:
                    self.snapshotter.force(self)
                if self.metrics is not None:
                    self.metrics.counter(
                        "engine_preemptions",
                        "graceful drain-then-snapshot exits").inc()
                break
            if self.queue and self.free_slots():
                self.admit(clock=clock)
            if self.active.any():
                ts = time.perf_counter()
                self.step(now=clock())
                if straggler is not None \
                        and straggler.observe(time.perf_counter() - ts) \
                        and self.metrics is not None:
                    self.metrics.counter(
                        "straggler_steps",
                        "decode steps slower than the EWMA threshold").inc()
            elif pending:
                # idle: nothing active, next request not yet arrived
                time.sleep(min(0.001, pending[0].arrival_time - now))
        return list(self.completions)
