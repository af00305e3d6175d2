"""Paged posit KV cache: geometry, allocator invariants, engine exactness.

Three layers (DESIGN.md §14):

* ``PageGeometry`` — the kv_bits-aware page layout: at a fixed byte budget
  a p8 page holds 2x the tokens of a p16 page and 4x an f32 page.
* ``PagedKVCache`` — pure host allocator: chained block hashes, refcounts,
  COW, LRU retention of released prefixes.  Adversarial admit/fork/evict
  orders must keep :meth:`check_invariants` green after every mutation.
* ``PagedContinuousBatchingEngine`` — the exactness contract: a prefix-hit
  (warm) admission decodes bit-for-bit like the cold one, lifetime block
  reservation means admitted streams never die ``cache_full``, and a
  mid-stream snapshot -> reset -> restore loses zero tokens.

Engine comparisons reuse the same engine object (``reset()`` keeps the
compiled executables): XLA:CPU programs are not bit-identical across
separate compilations.
"""
import dataclasses

import numpy as np
import jax
import pytest

from repro.configs import get_arch
from repro.core.paged_kv import (PagedKVCache, PageGeometry, PoolExhausted,
                                 ROOT_DIGEST)
from repro.core.pcsr import TransPolicy
from repro.launch.engine import Request
from repro.launch.paged_engine import PagedContinuousBatchingEngine
from repro.models.registry import build_model


# --------------------------------------------------------------- geometry ---

def test_page_geometry_kv_bits_scaling():
    """Same page bytes: p8 codes hold 2x the tokens of p16, 4x of f32."""
    mk = lambda cb: PageGeometry(n_layers=2, n_kv=2, head_dim=16,
                                 code_bytes=cb, page_bytes=2048)
    p8, p16, f32 = mk(1), mk(2), mk(4)
    assert p8.block_tokens == 2 * p16.block_tokens == 4 * f32.block_tokens
    assert p8.block_tokens == 2048 // (2 * 2 * 16)
    # pool bytes are budgeted per page, so equal pages => equal bytes
    assert p8.pool_bytes(8) == p16.pool_bytes(8) == f32.pool_bytes(8)


def test_page_geometry_blocks_for_and_validation():
    g = PageGeometry(n_layers=1, n_kv=2, head_dim=16, code_bytes=1,
                     page_bytes=512)          # bt = 8
    assert g.block_tokens == 8
    assert g.blocks_for(1) == 1 and g.blocks_for(8) == 1
    assert g.blocks_for(9) == 2 and g.blocks_for(17) == 3
    with pytest.raises(ValueError, match="code_bytes"):
        PageGeometry(n_layers=1, n_kv=2, head_dim=16, code_bytes=3)
    with pytest.raises(ValueError, match="holds no tokens"):
        PageGeometry(n_layers=1, n_kv=64, head_dim=128, code_bytes=4,
                     page_bytes=64)


# --------------------------------------------------------------- allocator ---

def _mgr(n_blocks=8, max_slots=4, bt=4):
    geom = PageGeometry(n_layers=1, n_kv=1, head_dim=4, code_bytes=1,
                        page_bytes=2 * 4 * bt)
    assert geom.block_tokens == bt
    return PagedKVCache(geom, n_blocks=n_blocks, max_slots=max_slots)


def _admit(mgr, slot, tokens):
    """The engine's prefill bookkeeping, minus the device copies: match,
    claim, append fresh blocks, content-address full fresh chunks."""
    bt = mgr.geom.block_tokens
    match = mgr.match_prefix(tokens)
    mgr.claim_blocks(match.bids)
    mgr.begin_slot(slot, match.bids)
    digests = mgr.chunk_digests(tokens)
    parent = match.tail_digest
    pos = match.n_tokens
    while pos < len(tokens):
        n = min(bt, len(tokens) - pos)
        try:
            bid = mgr.append_block(slot)
        except PoolExhausted:
            mgr.release_slot(slot)      # the engine's unwind path
            raise
        if n == bt:
            digest, chunk = digests[pos // bt]
            mgr.register_full_block(bid, digest, parent, chunk)
            parent = digest
        pos += n
    return match


def test_chained_hash_covers_whole_prefix():
    """Identical chunk tokens after different prefixes hash differently —
    KV codes at a position depend on every earlier token."""
    mgr = _mgr(bt=4)
    a = mgr.chunk_digests([1, 2, 3, 4, 9, 9, 9, 9])
    b = mgr.chunk_digests([5, 6, 7, 8, 9, 9, 9, 9])
    assert a[0][1] != b[0][1] and a[0][0] != b[0][0]
    assert a[1][1] == b[1][1] == (9, 9, 9, 9)
    assert a[1][0] != b[1][0]           # same tokens, different chain
    # and the chain anchors at the module-level root digest
    assert mgr.chunk_digests([])== [] and isinstance(ROOT_DIGEST, str)


def test_block_table_round_trip_and_sentinel():
    mgr = _mgr(n_blocks=8, max_slots=3, bt=4)
    _admit(mgr, 0, list(range(10)))     # 3 blocks (2 full + tail)
    _admit(mgr, 1, list(range(4)))      # prefix hit on block 0
    tab = mgr.device_table(width=4)
    assert tab.shape == (3, 4) and tab.dtype == np.int32
    assert list(tab[0, :3]) == mgr.tables[0] and tab[0, 3] == mgr.sentinel
    assert tab[1, 0] == mgr.tables[0][0]        # shared first block
    assert (tab[2] == mgr.sentinel).all()
    with pytest.raises(AssertionError, match="table width"):
        mgr.device_table(width=2)
    mgr.check_invariants()


def test_prefix_hit_claim_and_lru_retention():
    mgr = _mgr(n_blocks=6, max_slots=2, bt=4)
    _admit(mgr, 0, list(range(8)))              # 2 published blocks
    mgr.release_slot(0)
    # released published blocks park in the LRU, still matchable
    assert len(mgr.lru) == 2 and mgr.available() == 6
    m = mgr.match_prefix(list(range(8)) + [99])
    assert m.n_tokens == 8 and len(m.bids) == 2
    mgr.claim_blocks(m.bids)                    # un-caches them
    mgr.begin_slot(0, m.bids)
    assert len(mgr.lru) == 0
    assert all(mgr.refcount[b] == 1 for b in m.bids)
    mgr.check_invariants()


def test_alloc_recycles_lru_and_unregisters():
    mgr = _mgr(n_blocks=2, max_slots=2, bt=4)
    _admit(mgr, 0, list(range(8)))
    mgr.release_slot(0)
    assert not mgr.free and len(mgr.lru) == 2
    bid = mgr.alloc()                   # recycles the least recently used
    assert bid not in mgr.hash_of       # its cached prefix is gone
    assert mgr.match_prefix(list(range(8))).n_tokens < 8
    mgr.release(bid)
    mgr.check_invariants()


def test_pool_exhausted_and_refcount_underflow():
    mgr = _mgr(n_blocks=1, max_slots=1, bt=4)
    bid = mgr.alloc()
    with pytest.raises(PoolExhausted):
        mgr.alloc()
    mgr.release(bid)
    with pytest.raises(AssertionError, match="underflow"):
        mgr.release(bid)


def test_first_writer_wins_registration():
    mgr = _mgr(bt=4)
    _admit(mgr, 0, list(range(4)))
    first = mgr.tables[0][0]
    # identical prompt admitted again while the first is still live: the
    # newcomer matches (storage dedup), no duplicate registration
    _admit(mgr, 1, list(range(4)))
    assert mgr.tables[1][0] == first and mgr.refcount[first] == 2
    # force a private duplicate and try to re-publish the same digest
    bid = mgr.append_block(1)
    digest, chunk = mgr.chunk_digests(list(range(4)))[0]
    mgr.register_full_block(bid, digest, ROOT_DIGEST, chunk)
    assert mgr.by_hash[digest] == first         # first writer kept
    assert bid not in mgr.hash_of
    mgr.check_invariants()


def test_cow_on_shared_and_published_tails():
    mgr = _mgr(n_blocks=8, max_slots=3, bt=4)
    _admit(mgr, 0, list(range(4)))              # tail full + published
    # published tail is immutable even at refcount 1
    cow = mgr.ensure_writable(0)
    assert cow is not None and cow[1] == mgr.tables[0][-1] != cow[0]
    assert mgr.cow_copies == 1
    mgr.check_invariants()
    # fork: aliased tail; each side's first write gets a private copy
    _admit(mgr, 1, [7, 7, 7, 7, 5])             # tail partial + private
    mgr.fork_slot(1, 2)
    assert mgr.tables[2] == mgr.tables[1]
    shared = mgr.tables[1][-1]
    assert mgr.refcount[shared] == 2
    assert mgr.ensure_writable(1) is not None
    assert mgr.tables[1][-1] != mgr.tables[2][-1] == shared
    assert mgr.ensure_writable(2) is None       # now private again
    mgr.check_invariants()


def test_invariants_under_adversarial_op_order():
    """Random admit / append / fork / COW / release storm; every mutation
    must keep refcounts == table references and the free/LRU/live
    partition exact."""
    rng = np.random.default_rng(0)
    mgr = _mgr(n_blocks=12, max_slots=4, bt=4)
    live = set()
    for _ in range(400):
        op = rng.integers(0, 5)
        try:
            if op == 0:                  # admit a prompt (maybe shared)
                free = [s for s in range(4) if s not in live]
                if free:
                    n = int(rng.integers(1, 10))
                    toks = list(rng.integers(0, 3, size=n))   # tiny vocab:
                    _admit(mgr, free[0], toks)                # hits likely
                    live.add(free[0])
            elif op == 1 and live:       # decode growth
                mgr.append_block(int(rng.choice(sorted(live))))
            elif op == 2 and live:       # COW before a tail write
                mgr.ensure_writable(int(rng.choice(sorted(live))))
            elif op == 3 and live:       # fork into a free slot
                free = [s for s in range(4) if s not in live]
                if free:
                    src = int(rng.choice(sorted(live)))
                    mgr.fork_slot(src, free[0])
                    live.add(free[0])
            elif op == 4 and live:       # eviction
                s = int(rng.choice(sorted(live)))
                mgr.release_slot(s)
                live.remove(s)
        except PoolExhausted:
            if live:                     # engine's response: evict someone
                s = int(rng.choice(sorted(live)))
                mgr.release_slot(s)
                live.remove(s)
        mgr.check_invariants()
    for s in sorted(live):
        mgr.release_slot(s)
    mgr.check_invariants()
    assert int((mgr.refcount > 0).sum()) == 0


def test_snapshot_meta_round_trip_and_geometry_guard():
    mgr = _mgr(n_blocks=8, max_slots=3, bt=4)
    _admit(mgr, 0, list(range(9)))
    _admit(mgr, 1, list(range(4)))
    mgr.ensure_writable(1)
    mgr.release_slot(0)
    meta = mgr.snapshot_meta()
    fresh = _mgr(n_blocks=8, max_slots=3, bt=4)
    fresh.restore_meta(meta)
    assert fresh.stats() == mgr.stats()
    assert fresh.tables == mgr.tables
    assert fresh.seen_digests() == mgr.seen_digests()
    assert list(fresh.lru) == list(mgr.lru)     # LRU order preserved
    wrong = _mgr(n_blocks=8, max_slots=3, bt=8)
    with pytest.raises(ValueError, match="geometry"):
        wrong.restore_meta(meta)
    small = _mgr(n_blocks=4, max_slots=3, bt=4)
    with pytest.raises(ValueError, match="blocks"):
        small.restore_meta(meta)


def test_snapshot_meta_refuses_old_pool_layout():
    """A snapshot taken when the pool was ``(L, N, Hkv, bt, hd)`` carries
    the fingerprint without the ``layout`` field; its pool has as many
    codes, so only the fingerprint can refuse it."""
    mgr = _mgr(n_blocks=8, max_slots=3, bt=4)
    _admit(mgr, 0, list(range(9)))
    meta = mgr.snapshot_meta()
    g = mgr.geom
    meta["geometry"] = (f"paged(bt={g.block_tokens},L={g.n_layers},"
                        f"kv={g.n_kv}x{g.head_dim},code_B={g.code_bytes},"
                        f"page_B={g.page_bytes})")
    fresh = _mgr(n_blocks=8, max_slots=3, bt=4)
    with pytest.raises(ValueError, match="geometry"):
        fresh.restore_meta(meta)
    assert fresh.stats() == _mgr(n_blocks=8, max_slots=3, bt=4).stats()


def test_begin_slot_requires_released_table():
    mgr = _mgr()
    _admit(mgr, 0, [1, 2])
    with pytest.raises(AssertionError, match="not released"):
        mgr.begin_slot(0, [])


# ------------------------------------------------------------------ engine ---

@pytest.fixture(scope="module")
def paged_setup():
    cfg = get_arch("yi-34b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    policy = TransPolicy.from_names(kv_cache="p8_0", compute_dtype="bf16",
                                    attn_impl="kernel")
    return cfg, model, params, policy


def _prompts(cfg, n, prompt_len, overlap):
    rng = np.random.default_rng(1234)
    n_shared = int(round(overlap * prompt_len))
    shared = rng.integers(0, cfg.vocab, size=n_shared)
    rng = np.random.default_rng(7)
    return [np.concatenate([shared,
                            rng.integers(0, cfg.vocab,
                                         size=prompt_len - n_shared)])
            .astype(np.int32) for _ in range(n)]


def _drain(eng):
    while eng.queue or eng.active.any():
        if eng.queue and eng.free_slots():
            eng.admit(now=0.0)
        if eng.active.any():
            eng.step(now=0.0)
    return {c.rid: (list(c.tokens), c.finish_reason)
            for c in eng.completions}


def test_warm_prefix_hit_decodes_bit_for_bit(paged_setup):
    """A prefix-hit admission reads claimed blocks where the cold one wrote
    fresh ones — the sampled streams must be identical, token for token."""
    cfg, model, params, policy = paged_setup
    eng = PagedContinuousBatchingEngine(model, params, policy, max_slots=2,
                                        S_max=64, page_bytes=2048,
                                        n_blocks=24)
    bt = eng.geom.block_tokens
    prompt = _prompts(cfg, 1, 2 * bt + 3, 1.0)[0]
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=5))
    cold = _drain(eng)[0]
    assert eng.prefix_stats()["hits"] == 0
    eng.submit(Request(rid=1, prompt=prompt.copy(), max_new_tokens=5))
    warm = _drain(eng)[1]
    st = eng.prefix_stats()
    assert st["hits"] == 1 and st["hit_tokens"] == 2 * bt
    assert warm == cold, (warm, cold)
    eng.manager.check_invariants()


def test_lifetime_reservation_no_mid_stream_eviction(paged_setup):
    """Admission reserves the whole request lifetime (prompt + decode
    growth): a pool too small for every request at once must queue, never
    evict an admitted stream as ``cache_full``."""
    cfg, model, params, policy = paged_setup
    eng = PagedContinuousBatchingEngine(model, params, policy, max_slots=4,
                                        S_max=64, page_bytes=2048,
                                        n_blocks=6)
    bt = eng.geom.block_tokens
    gen = 4
    prompts = _prompts(cfg, 4, bt + 2, 0.0)     # disjoint: no sharing help
    # 6 blocks, each lifetime needs 2 => at most 3 concurrent, 4 submitted
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=gen))
    done = _drain(eng)
    assert set(done) == set(range(4))
    for rid, (toks, reason) in done.items():
        assert reason == "max_new" and len(toks) == gen, (rid, done[rid])
    eng.manager.check_invariants()
    assert int((eng.manager.refcount > 0).sum()) == 0


def test_fork_cow_streams_complete(paged_setup):
    """A mid-decode fork aliases every block; both streams must finish and
    the divergence must go through copy-on-write, not corruption."""
    cfg, model, params, policy = paged_setup
    eng = PagedContinuousBatchingEngine(model, params, policy, max_slots=2,
                                        S_max=64, page_bytes=2048,
                                        n_blocks=24)
    prompt = _prompts(cfg, 1, eng.geom.block_tokens + 1, 1.0)[0]
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=6))
    eng.admit(now=0.0)
    eng.step(now=0.0)
    eng.fork(0, 1)
    done = _drain(eng)
    assert set(done) == {0, 1}
    assert done[0][1] == done[1][1] == "max_new"
    # greedy sampling: the clone must replay the parent exactly
    assert done[0][0] == done[1][0]
    assert eng.prefix_stats()["cow_copies"] >= 1
    eng.manager.check_invariants()


def test_snapshot_restore_mid_stream_zero_loss(paged_setup):
    """snapshot() after a few decode steps -> drain -> reset -> restore ->
    drain again: every stream finishes with the same tokens (block table,
    refcounts, and hash index ride the snapshot meta)."""
    cfg, model, params, policy = paged_setup
    eng = PagedContinuousBatchingEngine(model, params, policy, max_slots=4,
                                        S_max=64, page_bytes=2048,
                                        n_blocks=32)
    gen = 5
    prompts = _prompts(cfg, 4, 2 * eng.geom.block_tokens + 2, 0.9)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=gen))
    eng.admit(now=0.0)
    for _ in range(2):
        eng.step(now=0.0)
    mid = eng.snapshot()
    expect = _drain(eng)
    eng.reset()
    assert eng.prefix_stats()["hits"] == 0      # reset really cleared it
    eng.restore(mid, now=0.0)
    eng.manager.check_invariants()
    got = _drain(eng)
    assert got == expect
    # a slot-grid snapshot (no paged meta) must be refused
    bare = dict(mid)
    bare["meta"] = {k: v for k, v in mid["meta"].items() if k != "paged"}
    with pytest.raises(ValueError, match="paged"):
        eng.restore(bare, now=0.0)


def test_snapshot_restore_refuses_old_pool_layout(paged_setup):
    """A snapshot whose pools are in the old ``(L, N, Hkv, bt, hd)`` layout,
    under the old fingerprint, is refused before anything is installed:
    reshaped into ``(L, N, bt, Hkv*hd)`` its codes would restore without
    error, head-major read as token-major."""
    cfg, model, params, policy = paged_setup
    eng = PagedContinuousBatchingEngine(model, params, policy, max_slots=2,
                                        S_max=64, page_bytes=2048,
                                        n_blocks=16)
    bt = eng.geom.block_tokens
    for i, p in enumerate(_prompts(cfg, 2, bt + 2, 0.5)):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=4))
    eng.admit(now=0.0)
    eng.step(now=0.0)
    snap = eng.snapshot()
    kv = snap["arrays"]["cache"]["kv"]
    g = eng.geom
    for name in ("k", "v"):
        L, N, _, _ = kv[name].shape
        kv[name] = kv[name].reshape(L, N, bt, g.n_kv, g.head_dim).transpose(
            0, 1, 3, 2, 4)
    snap["meta"]["paged"]["geometry"] = (
        f"paged(bt={bt},L={g.n_layers},kv={g.n_kv}x{g.head_dim},"
        f"code_B={g.code_bytes},page_B={g.page_bytes})")
    lens, steps = eng.lens.copy(), eng.steps
    with pytest.raises(ValueError, match="geometry"):
        eng.restore(snap, now=0.0)
    assert eng.steps == steps and (eng.lens == lens).all()
    eng.manager.check_invariants()
