"""Paged continuous-batching engine: prefix-sharing posit KV over block pools.

``ContinuousBatchingEngine`` gives every slot a dense ``S_max`` KV strip —
simple, but at serving scale it wastes exactly what the posit codecs buy:
rows past a request's live length are dead bytes, and requests sharing a
system prompt store the same prefix codes once *per slot*.  This subclass
swaps the strips for fixed-byte pages (``core.paged_kv``, DESIGN.md §14):

* the device cache is one block pool per layer ``(L, N, bt, Hkv*hd)`` plus
  a per-slot block table ``(max_slots, W)``; attention reads go through the
  table (``kernels.posit_attention.ops.posit_decode_attention_paged``: on a
  TPU a Pallas kernel copies only each row's live pages from the pool; the
  one-gather lowering of each row's whole window is the off-TPU path) and
  decode writes scatter into ``table[b, lens[b] // bt]``;
* admission content-addresses every *full* prefill block by a chained
  blake2b over its token prefix — a request whose prompt starts with an
  already-cached chain claims those blocks (refcount++) instead of storing
  duplicates.  Prefill always runs in full (the bit-exactness contract:
  warm and cold admissions must decode token-for-token identically, so the
  shared bytes must be the bytes a cold prefill would have written — sharing
  dedupes *storage*, not FLOPs);
* :meth:`fork` clones a live request block-for-block (parallel sampling);
  the first divergent write hits copy-on-write in :meth:`_prepare_decode`;
* decode-written blocks are never hashed or shared: the decode path reads
  round-tripped posit KV where prefill wrote from float activations, so a
  decode-filled block's codes are not the codes a prefill of the same
  tokens would produce — publishing them would break warm≡cold exactness.

The capacity story is the paper's lightweight-posit pillar at the cache
level: pages are byte-budgeted, so packed-p8 codes (1 B) double the tokens
per page vs p16 and quadruple vs f32 — at a fixed pool byte budget the
paged engine admits several times the concurrent requests of the slot grid
once prompts overlap (benchmarks/bench_prefix_cache.py gates ≥1.5x decode
tokens/s at 90% overlap).

Only the uniform stacked-cache families (dense / moe) page their KV;
gemma3's window buffers, zamba/xlstm recurrent state, and the vlm patch
prefix (not addressable by token ids) keep the slot grid.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.paged_kv import PagedKVCache, PageGeometry, PoolExhausted
from repro.launch.engine import ContinuousBatchingEngine, Request
from repro.obs.trace import annotate

__all__ = ["PagedContinuousBatchingEngine"]


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("n",))
def _copy_span(pool_arr, one_arr, bid, start, n):
    """Copy ``n`` KV rows from a B=1 prefill cache into block ``bid``.

    ``pool_arr``: (L, N, bt, Hkv*hd); ``one_arr``: (L, 1, Hkv, S, hd).
    ``n`` is static (one compile per distinct tail size — prompt-length
    buckets keep that bounded); ``start``/``bid`` are traced so full chunks
    of any position share one program.
    """
    L, _, Hkv, _, hd = one_arr.shape
    chunk = jax.lax.dynamic_slice_in_dim(one_arr[:, 0], start, n, axis=2)
    chunk = jnp.moveaxis(chunk, 1, 2).reshape(L, 1, n, Hkv * hd)
    return jax.lax.dynamic_update_slice(pool_arr, chunk, (0, bid, 0, 0))


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_block(pool_arr, src, dst):
    """Device copy-on-write: clone block ``src`` into ``dst`` (all layers)."""
    row = jax.lax.dynamic_slice_in_dim(pool_arr, src, 1, axis=1)
    return jax.lax.dynamic_update_slice(pool_arr, row, (0, dst, 0, 0))


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("n",))
def _poison_block(pool_arr, bid, code, n):
    """Overwrite the first ``n`` rows of block ``bid`` with ``code``."""
    L, _, _, F = pool_arr.shape
    bad = jnp.full((L, 1, n, F), code, pool_arr.dtype)
    return jax.lax.dynamic_update_slice(pool_arr, bad, (0, bid, 0, 0))


class PagedContinuousBatchingEngine(ContinuousBatchingEngine):
    """Drop-in engine with paged prefix-sharing KV storage.

    Same client surface (``submit``/``results``/``stream``/``cancel``), same
    drivers (:meth:`run`, snapshot/restore, fault plane).  Extra knobs:
    ``page_bytes`` (per-layer K+V bytes of one block) and ``n_blocks`` (pool
    size; default sizes the pool to the slot grid's byte budget,
    ``max_slots * S_max`` token rows).
    """

    def __init__(self, model, params, policy, *, max_slots: int, S_max: int,
                 page_bytes: int = 2048, n_blocks: Optional[int] = None,
                 **kw):
        if model.decode_step_paged is None or model.init_paged_cache is None:
            raise ValueError(
                f"family {model.cfg.family!r} has no paged decode path "
                f"(only the uniform stacked-cache families page their KV)")
        fmt = policy.kv_cache
        code_bytes = (1 if fmt is not None and fmt.nbits == 8 else
                      2 if fmt is not None or policy.compute_dtype != "f32"
                      else 4)
        from repro.models.transformer import attn_cfg
        acfg = attn_cfg(model.cfg)
        self.geom = PageGeometry(
            n_layers=model.cfg.n_layers, n_kv=acfg.n_kv,
            head_dim=acfg.head_dim, code_bytes=code_bytes,
            page_bytes=page_bytes)
        bt = self.geom.block_tokens
        if S_max % bt:
            # pad up: every slot must be able to hold S_max tokens exactly
            S_max = -(-S_max // bt) * bt
        self.table_width = S_max // bt
        self.n_blocks = (n_blocks if n_blocks is not None
                         else self.geom.blocks_for(max_slots * S_max))
        self.manager: Optional[PagedKVCache] = None   # built in _init_state
        self._table_dirty = False
        super().__init__(model, params, policy, max_slots=max_slots,
                         S_max=S_max, **kw)
        if self.metrics is not None:
            m = self.metrics
            self._m_blocks_free = m.gauge(
                "paged_blocks_free", "allocatable blocks (free + evictable)")
            self._m_blocks_cached = m.gauge(
                "paged_blocks_cached", "refcount-0 blocks held for reuse")
            self._m_prefix_hits = m.counter(
                "paged_prefix_hits", "admissions that reused cached blocks")
            self._m_prefix_tokens = m.counter(
                "paged_prefix_hit_tokens", "prompt tokens served from cache")
            self._m_cow = m.counter(
                "paged_cow_copies", "copy-on-write block clones")

    # ------------------------------------------------------------ state ------
    def _init_state(self, seed: int) -> None:
        self.manager = PagedKVCache(self.geom, n_blocks=self.n_blocks,
                                    max_slots=self.max_slots)
        self._table_dirty = False
        super()._init_state(seed)

    def _build_executables(self, policy) -> None:
        model, S_max = self.model, self.S_max

        # named functions, so profiles show jit_decode_step / jit_prefill
        def decode_step(p, t, c):
            return model.decode_step_paged(p, t, c, policy)

        def decode_step_probed(p, t, c):
            return model.decode_step_paged(p, t, c, policy)

        # prefill stays the slot-grid program: it writes a dense B=1 strip
        # whose chunks are then scattered into pool blocks (full prefill is
        # the warm≡cold exactness contract — see the module docstring)
        def prefill(p, toks, kw):
            return model.prefill(p, toks, policy, S_max=S_max, **kw)

        self._decode = jax.jit(decode_step, donate_argnums=(2,))
        self._decode_probed = None
        if self.numerics is not None:
            self._decode_probed = jax.jit(decode_step_probed,
                                          donate_argnums=(2,))
        self._prefill = jax.jit(prefill)

    def _init_cache(self):
        return self.model.init_paged_cache(
            self.max_slots, self.n_blocks, self.geom.block_tokens,
            self.table_width, self.policy)

    # ------------------------------------------------------------ admission --
    def _outstanding_growth(self) -> int:
        """Blocks the pool still owes already-admitted slots: each active
        request will grow to ``lens + remaining_decode (+1 for the write of
        its final sampled token)`` rows, and the blocks beyond what its
        table already holds must stay claimable or decode later dies on
        ``PoolExhausted`` mid-stream.  Derived from live engine state (not
        a counter), so it is automatically right after ``restore()``."""
        owed = 0
        for slot in range(self.max_slots):
            req = self.slot_req[slot]
            if not self.active[slot] or req is None:
                continue
            # every future decode step writes exactly one token before
            # sampling the next, and the final sampled token is evicted
            # unwritten — so the row grows by exactly `remaining` rows
            remaining = max(req.max_new_tokens - len(self.slot_tokens[slot]),
                            0)
            final_len = min(int(self.lens[slot]) + remaining, self.S_max)
            owed += max(0, self.geom.blocks_for(final_len)
                        - len(self.manager.tables[slot]))
        return owed

    def _can_admit(self, req: Request) -> bool:
        """Block-budget gate: admit only when the pool can take the whole
        *lifetime* of the request — prompt plus every decode token it may
        generate — on top of the growth already owed to admitted slots.
        Reserving only the prompt would admit requests whose decode growth
        later hits ``PoolExhausted`` and evicts them mid-stream
        (``cache_full``); with lifetime reservation, queueing is the
        backpressure and an admitted stream always runs to completion.
        Matched prefix blocks still referenced by a live slot are free to
        claim; matched blocks parked in the LRU consume availability like
        fresh allocations (claiming them un-caches them).  The final
        sampled token is never written back, hence the ``- 1`` on the
        lifetime.  (COW copies — possible only on forked streams — are
        deliberately NOT reserved here; a fork under a saturated pool may
        still evict with ``cache_full``, the graceful path.)"""
        match = self.manager.match_prefix(req.prompt)
        matched_live = sum(1 for b in match.bids
                           if self.manager.refcount[b] > 0)
        need = self.geom.blocks_for(
            req.prompt_len + req.max_new_tokens - 1) - matched_live
        return need + self._outstanding_growth() <= self.manager.available()

    def _prefill_into_slot(self, req: Request, slot: int):
        """Prefix-matched admission: full prefill, dedup'd storage.

        The full B=1 prefill always runs (matched blocks hold exactly the
        bytes it would write — the warm path must decode bit-for-bit like
        the cold path, and skipping prefill would also skip the non-KV
        activations the first sampled token depends on).  Matched full
        blocks are claimed by reference; only fresh chunks are scattered
        into newly-allocated pool blocks, and fresh *full* chunks are
        content-addressed for the next request to claim.
        """
        mgr, geom = self.manager, self.geom
        bt = geom.block_tokens
        match = mgr.match_prefix(req.prompt)
        tokens = jnp.asarray(req.prompt, jnp.int32)[None]
        with annotate("repro.prefill"):
            logits, one_cache = self._prefill(
                self.params, tokens, self._prefill_kwargs(req))
        with annotate("repro.admit.wait"):
            row_len = int(one_cache["lens"][0])
        with annotate("repro.admit.kv_scatter"):
            mgr.claim_blocks(match.bids)
            mgr.begin_slot(slot, match.bids)
            if match.bids:
                mgr.hits += 1
                mgr.hit_tokens += match.n_tokens
                if self.metrics is not None:
                    self._m_prefix_hits.inc()
                    self._m_prefix_tokens.inc(match.n_tokens)
            else:
                mgr.misses += 1
            digests = mgr.chunk_digests(req.prompt)
            parent = match.tail_digest
            one_kv = one_cache["kv"]
            pos = match.n_tokens
            while pos < row_len:
                n = min(bt, row_len - pos)
                try:
                    bid = mgr.append_block(slot)
                except PoolExhausted:
                    # _can_admit budgeted for this prompt, but a COW burst in
                    # the same step can race it; unwind and retry later
                    mgr.release_slot(slot)
                    raise
                kv = self.cache["kv"]
                kv["k"] = _copy_span(kv["k"], one_kv["k"], jnp.int32(bid),
                                     jnp.int32(pos), n)
                kv["v"] = _copy_span(kv["v"], one_kv["v"], jnp.int32(bid),
                                     jnp.int32(pos), n)
                if n == bt:
                    digest, chunk = digests[pos // bt]
                    mgr.register_full_block(bid, digest, parent, chunk)
                    parent = digest
                pos += n
            self._table_dirty = True
            self._push_table()
        return logits, row_len

    # --------------------------------------------------------------- decode ---
    def _prepare_decode(self, now: float) -> None:
        """Per-step write-path maintenance, before the grid step runs.

        Every active slot is about to scatter one token at
        ``table[slot, lens // bt]`` offset ``lens % bt``; this hook
        guarantees that target is a *private, existing* block: appends a
        fresh block at block boundaries, and copy-on-writes a shared or
        published tail (fork aliases, prefix-claimed tails).  Pool
        exhaustion evicts the slot as ``cache_full`` — its pages come back,
        so the rest of the grid keeps serving.
        """
        mgr, bt = self.manager, self.geom.block_tokens
        for slot in range(self.max_slots):
            if not self.active[slot]:
                continue
            target = int(self.lens[slot])
            try:
                if len(mgr.tables[slot]) * bt <= target:
                    mgr.append_block(slot)
                    self._table_dirty = True
                else:
                    cow = mgr.ensure_writable(slot)
                    if cow is not None:
                        src, dst = cow
                        kv = self.cache["kv"]
                        kv["k"] = _copy_block(kv["k"], jnp.int32(src),
                                              jnp.int32(dst))
                        kv["v"] = _copy_block(kv["v"], jnp.int32(src),
                                              jnp.int32(dst))
                        self._table_dirty = True
                        if self.metrics is not None:
                            self._m_cow.inc()
            except PoolExhausted:
                self._evict(slot, now, "cache_full")
        self._push_table()
        if self.metrics is not None:
            self._m_blocks_free.set(mgr.available())
            self._m_blocks_cached.set(len(mgr.lru))

    def _push_table(self) -> None:
        if self._table_dirty:
            self.cache["table"] = jnp.asarray(
                self.manager.device_table(self.table_width))
            self._table_dirty = False

    def _kv_utilization(self) -> float:
        """Pool blocks held by live slots over the pool's blocks."""
        return int((self.manager.refcount > 0).sum()) / self.n_blocks

    # ------------------------------------------------------------- eviction ---
    def _release_slot(self, slot: int) -> None:
        self.manager.release_slot(slot)
        self._table_dirty = True
        self._push_table()

    def _quarantine(self, slot: int, now: float) -> None:
        """Evict a nonfinite-logit slot and zero its *private* blocks (code 0
        decodes to exact 0.0).  Shared blocks are merely released — another
        slot's live prefix must not be scrubbed from under it; a poisoned
        hashed block leaving the index via LRU reuse is the correctness
        backstop (alloc zeroes nothing, but writes overwrite fully)."""
        private = self.manager.private_bids(slot)
        self._evict(slot, now, "numerics")      # releases the references
        kv = self.cache["kv"]
        for bid in private:
            kv["k"] = _poison_block(kv["k"], jnp.int32(bid),
                                    jnp.zeros((), kv["k"].dtype),
                                    self.geom.block_tokens)
            kv["v"] = _poison_block(kv["v"], jnp.int32(bid),
                                    jnp.zeros((), kv["v"].dtype),
                                    self.geom.block_tokens)

    def inject_nar_into(self, slot: int, count: int) -> None:
        """Chaos hook override: poison the slot's *tail* block only.  Head
        blocks may be shared with healthy requests — the fault must stay
        contained to the slot it targets, so the tail is made private
        (copy-on-write) first."""
        from repro.ft.serving import _nar_code
        mgr, bt = self.manager, self.geom.block_tokens
        if not mgr.tables[slot]:
            return
        cow = mgr.ensure_writable(slot)
        kv = self.cache["kv"]
        if cow is not None:
            src, dst = cow
            kv["k"] = _copy_block(kv["k"], jnp.int32(src), jnp.int32(dst))
            kv["v"] = _copy_block(kv["v"], jnp.int32(src), jnp.int32(dst))
            self._table_dirty = True
        bid = mgr.tables[slot][-1]
        occupied = int(self.lens[slot]) - (len(mgr.tables[slot]) - 1) * bt
        n = max(1, min(count, max(occupied, 1), bt))
        kv["k"] = _poison_block(kv["k"], jnp.int32(bid),
                                _nar_code(kv["k"]), n)
        kv["v"] = _poison_block(kv["v"], jnp.int32(bid),
                                _nar_code(kv["v"]), n)
        self._push_table()

    # ----------------------------------------------------------------- fork ---
    def fork(self, rid: int, new_rid: int) -> int:
        """Clone a live request into a free slot, sharing every block
        (parallel sampling / n-best).  Returns the new request's rid.  The
        clone starts from the same position with the same emitted tokens;
        the first post-fork write on either side triggers copy-on-write in
        :meth:`_prepare_decode`, so the streams diverge without copying the
        shared history."""
        import dataclasses as _dc
        src = next((s for s in range(self.max_slots)
                    if self.active[s] and self.slot_req[s] is not None
                    and self.slot_req[s].rid == rid), None)
        if src is None:
            raise ValueError(f"fork: rid {rid} is not in flight")
        free = self.free_slots()
        if not free:
            raise PoolExhausted("fork: no free slot")
        dst = free[0]
        self.manager.fork_slot(src, dst)
        self.lens[dst] = self.lens[src]
        self.last_token = self.last_token.at[dst].set(self.last_token[src])
        self.active[dst] = True
        self.slot_req[dst] = _dc.replace(self.slot_req[src], rid=new_rid)
        self.slot_tokens[dst] = list(self.slot_tokens[src])
        self.slot_token_times[dst] = list(self.slot_token_times[src])
        self.slot_admitted[dst] = self.slot_admitted[src]
        self._sync_lens()
        self._table_dirty = True
        self._push_table()
        return new_rid

    # ----------------------------------------------------- snapshot/restore ---
    def snapshot(self) -> dict:
        snap = super().snapshot()
        # block table + refcounts + hash index ride in the snapshot meta;
        # the geometry line extends the config fingerprint (a snapshot taken
        # under one page layout must never restore into another)
        snap["meta"]["paged"] = self.manager.snapshot_meta()
        return snap

    def restore(self, snap: dict, *, now: float = 0.0) -> None:
        if "paged" not in snap["meta"]:
            raise ValueError(
                "snapshot has no paged-cache state (taken by a slot-grid "
                "engine?) — it cannot restore into a paged engine")
        # before anything is installed: a pool of another page layout has
        # as many codes and would reshape into this one without complaint
        self.manager.check_meta(snap["meta"]["paged"])
        super().restore(snap, now=now)
        self.manager.restore_meta(snap["meta"]["paged"])
        self._table_dirty = True
        self._push_table()

    # ------------------------------------------------------------- accounting --
    def prefix_stats(self) -> dict:
        """Pool + sharing counters (also fed to metrics gauges per step)."""
        return self.manager.stats()
