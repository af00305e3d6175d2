"""Paged decode attention over a posit-coded KV pool (Pallas TPU kernel).

The paged engine's KV lives in a pool of fixed-size pages, ``(N, bt, Hkv*d)``
codes per layer (a page is ``bt`` token rows, every KV head side by side),
and each slot reaches its pages through a row of the block table.  This
kernel reads only each row's live pages, straight from the pool left in HBM:
no de-paged copy of the block-table window, no masked copy, no tile loop
over the batch's longest row.

  grid = (B,)                         one program per slot row, in order
  q:    (B, n_grp, R, G*d)   f32      block-diagonal per head group (below)
  pool: (N, bt, Hkv*d)       codes    left in HBM (``pl.ANY``)
  out:  (B, n_grp, R, G*d)   f32      same blocks; the wrapper keeps the
                                      diagonal
  scratch: K and V chunk buffers VMEM (2, P, bt, Hkv*d) codes (double
           buffer), DMA semaphores (2, 2), m, l (n_grp, R, 1) and acc
           (n_grp, R, G*d) f32, SMEM (2,) int32 pipeline state

Scalar prefetch: ``es`` (1,), ``lengths`` (B,), the block table flattened
to (B*W,).  A row's work is a ``fori_loop`` over its own live chunks of
``P`` pages: each chunk is ``P`` page copies HBM->VMEM that all signal one
semaphore, the next chunk's copies start before the current one is waited
on, and the last chunk of a row starts the next row's first chunk, so the
copies run ahead of the compute across the whole grid.  Pages past a row's
last live page issue no copy and chunks past it run no compute; sentinel
table entries past the length are never read.  Codes are decoded in VMEM
right before the dots.

Heads are handled ``G`` at a time, a lane-aligned slab of ``G*d`` columns:
the group's queries sit block-diagonally in an ``(R, G*d)`` matrix (row
``h*g + i`` holds query ``i`` of head ``h`` in head ``h``'s columns, zeros
elsewhere, ``R = G*g`` rounded up to 8), so the scores of all its heads are
one MXU product with the decoded ``(P*bt, G*d)`` K slab, and ``p @ V`` gives
each head's output in its own columns.  ``G`` is the smallest divisor of
``Hkv`` whose slab is a whole number of 128-lane tiles and whose rows fill a
sublane tile (phi3's d 96: G 8; OLMoE's d 128: G 8; a grouped-query model
with ``g >= 8``: G 1).

The chunk is the smallest whole number of pages that spans 128 tokens (one
MXU / lane width), cut down only if the double-buffered K and V chunks would
not fit the VMEM budget.  Compute, not the copies, bounds the kernel (every
code is decoded before use), and a chunk's positions past its row's length
run masked, so a wider chunk only adds work at each row's tail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.codec import posit_decode

_NEG_INF = -1e30
_CHUNK_TOKENS = 128                  # one MXU pass / lane width
_CHUNK_VMEM_BYTES = 8 * 1024 * 1024  # K+V, double-buffered, of the 16 MiB scoped VMEM


def _chunk_pages(bt: int, width: int, code_bytes: int, w: int) -> int:
    """Pages per chunk: ``ceil(128 / bt)``, at most ``w``, halved until the
    double-buffered K and V chunks (``width`` lanes of ``bt`` rows each)
    fit ``_CHUNK_VMEM_BYTES``."""
    page = bt * (-(-width // 128) * 128) * code_bytes
    p = min(-(-_CHUNK_TOKENS // bt), w)
    while p > 1 and 2 * 2 * p * page > _CHUNK_VMEM_BYTES:
        p //= 2
    return p


def _head_group(hkv: int, g: int, d: int) -> int:
    """Heads per lane slab: the smallest divisor ``G`` of ``hkv`` with
    ``G*d`` a multiple of 128 and ``G*g >= 8``; else all ``hkv`` heads."""
    for G in range(1, hkv + 1):
        if hkv % G == 0 and (G * d) % 128 == 0 and G * g >= 8:
            return G
    return hkv


def _paged_kernel(
    es_ref, len_ref, table_ref,          # scalar prefetch (SMEM)
    q_ref, k_hbm, v_hbm,                 # inputs: q block, pools in HBM
    o_ref,                               # output block
    k_buf, v_buf, sems, m_ref, l_ref, acc_ref, state_ref,   # scratch
    *, kv_bits: int, n_rows: int, width: int, n_blocks: int, max_len: int,
    scale: float, bf16_kv: bool, q_parts: int,
):
    b = pl.program_id(0)
    _, pages, bt, _ = k_buf.shape
    n_grp, _, gw = acc_ref.shape
    chunk = pages * bt

    def row_len(r):
        return jnp.minimum(len_ref[r], max_len)

    def copies(r, c, slot):
        """The chunk's page copies, each guarded by ``page < n_pages``: a
        page past the row's last live page is never read (its table entry
        may be a sentinel)."""
        n_pages = pl.cdiv(row_len(r), bt)
        out = []
        for i in range(pages):
            j = c * pages + i
            bid = jnp.minimum(table_ref[r * width + jnp.minimum(j, width - 1)],
                              n_blocks - 1)
            out.append((j < n_pages, [
                pltpu.make_async_copy(k_hbm.at[bid], k_buf.at[slot, i],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[bid], v_buf.at[slot, i],
                                      sems.at[1, slot])]))
        return out

    def start(r, c, slot):
        for live, cps in copies(r, c, slot):
            @pl.when(live)
            def _():
                for cp in cps:
                    cp.start()

    def wait(r, c, slot):
        for live, cps in copies(r, c, slot):
            @pl.when(live)
            def _():
                for cp in cps:
                    cp.wait()

    @pl.when(b == 0)
    def _first_row():
        state_ref[0] = 0                 # buffer slot of the next chunk
        state_ref[1] = 0                 # 1: this row's chunk 0 is in flight

    length = row_len(b)
    n_chunks = pl.cdiv(length, chunk)

    @pl.when(jnp.logical_and(length > 0, state_ref[1] == 0))
    def _start_row():
        start(b, 0, state_ref[0])

    state_ref[1] = 0
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    # 128-lane column blocks: a p8 lookup reads one vreg of table per block
    lanes = 128 if gw % 128 == 0 else gw
    n_blk = gw // lanes
    if kv_bits == 8 and lanes == 128:
        # p8 has 256 values: decode them once (the codec itself, so the bits
        # match posit_decode) into two 128-lane tables, codes below and from
        # 0x80, and read each code's value with a lane gather
        iota = jax.lax.broadcasted_iota(jnp.int32, (chunk, lanes), 1)
        lo = posit_decode(iota, 8, es_ref[0])
        hi = posit_decode(iota + 128, 8, es_ref[0])

        def decode(codes):               # (P, bt, 128) -> (P*bt, 128) f32
            c = codes.reshape(chunk, lanes).astype(jnp.int32)
            i = c & 127
            return jnp.where(c >= 128, jnp.take_along_axis(hi, i, axis=1),
                             jnp.take_along_axis(lo, i, axis=1))
    else:
        def decode(codes):               # (P, bt, lanes) -> (P*bt, lanes) f32
            if kv_bits:
                x = posit_decode(codes, kv_bits, es_ref[0])
            else:
                x = codes.astype(jnp.float32)
            return x.reshape(chunk, lanes)

    def dot(a, x, contract, parts):
        """f32 ``a`` times ``x`` (decoded K or V) at f32 accuracy.  Where
        ``x`` is exact in bf16 (p8 codes, a bf16 pool), ``a`` is cut into
        ``parts`` bf16 pieces that sum to it (one for a bf16 query, three
        for any f32) and each piece is one bf16 MXU pass with exact
        products; else a six-pass f32 product."""
        dims = (((1,), (contract,)), ((), ()))
        if not bf16_kv:
            return jax.lax.dot_general(a, x, dims,
                                       precision=jax.lax.Precision.HIGHEST,
                                       preferred_element_type=jnp.float32)
        x = x.astype(jnp.bfloat16)
        out = None
        for _ in range(parts):
            piece = a.astype(jnp.bfloat16)
            a = a - piece.astype(jnp.float32)
            y = jax.lax.dot_general(piece, x, dims,
                                    preferred_element_type=jnp.float32)
            out = y if out is None else out + y
        return out

    def chunk_body(c, _):
        slot = state_ref[0]
        nxt = 1 - slot

        @pl.when(c + 1 < n_chunks)
        def _next_chunk():
            start(b, c + 1, nxt)

        next_live = jnp.logical_and(
            b + 1 < n_rows, row_len(jnp.minimum(b + 1, n_rows - 1)) > 0)

        @pl.when(jnp.logical_and(c + 1 == n_chunks, next_live))
        def _next_row():
            start(b + 1, 0, nxt)
            state_ref[1] = 1

        wait(b, c, slot)
        pos = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        valid_rows = pos < length                            # (P*bt, 1)
        valid = (c * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (1, chunk), 1)) < length              # (1, P*bt)
        for grp in range(n_grp):
            qg = q_ref[0, grp]                               # (R, G*d)
            s = None
            for j in range(n_blk):
                k = decode(k_buf[slot, :, :, pl.ds(grp * gw + j * lanes, lanes)])
                y = dot(qg[:, j * lanes:(j + 1) * lanes], k, 1, q_parts)
                s = y if s is None else s + y                # (R, P*bt)
            s = jnp.where(valid, s * scale, _NEG_INF)
            m_prev = m_ref[grp]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_ref[grp] = l_ref[grp] * alpha + jnp.sum(p, axis=1, keepdims=True)
            for j in range(n_blk):
                # a recycled page can hold NaR codes past the length: zero the
                # values so 0 * NaN cannot reach the accumulator
                v = jnp.where(valid_rows, decode(
                    v_buf[slot, :, :, pl.ds(grp * gw + j * lanes, lanes)]), 0.0)
                cols = pl.ds(j * lanes, lanes)
                acc_ref[grp, :, cols] = (acc_ref[grp, :, cols] * alpha
                                         + dot(p, v, 0, 3))
            m_ref[grp] = m_new
        state_ref[0] = nxt
        return 0

    jax.lax.fori_loop(0, n_chunks, chunk_body, 0)
    l = l_ref[...]
    # length-0 rows (free engine slots) emit exact zeros, not 0/0
    o_ref[0] = acc_ref[...] / jnp.where(l == 0, 1.0, l)


@functools.partial(jax.jit, static_argnames=("kv_bits", "scale", "interpret"))
def posit_decode_attention_paged_pallas(
    q: jax.Array,            # (B, Hq, d) float
    k_pool: jax.Array,       # (N_blocks, bt, Hkv*d) posit codes (one layer)
    v_pool: jax.Array,       # (N_blocks, bt, Hkv*d)
    block_table: jax.Array,  # (B, W) int32 block ids; >= N_blocks = empty
    lengths: jax.Array,      # (B,) int32 — valid KV length per batch row
    es,                      # int32 scalar — pcsr pes for the KV cache
    *,
    kv_bits: int,
    scale: float | None = None,
    interpret=False,
) -> jax.Array:
    """``posit_decode_attention_paged``'s contract as one Pallas call: the
    same arguments and output, ragged ``lengths`` (clamped to ``W*bt``),
    sentinel table entries past a row's length never read, exact zeros for
    length-0 rows, ``kv_bits`` 8, 16 or 0 (a float pool, no codec)."""
    B, Hq, d = q.shape
    N, bt, F = k_pool.shape
    W = block_table.shape[1]
    Hkv = F // d
    assert Hkv * d == F and Hq % Hkv == 0 and k_pool.shape == v_pool.shape, (
        q.shape, k_pool.shape, v_pool.shape)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    g = Hq // Hkv
    G = _head_group(Hkv, g, d)
    n_grp, gw = Hkv // G, G * d
    R = -(-(G * g) // 8) * 8
    # block-diagonal queries: row h*g + i of group n holds q[n*G + h, i] in
    # head h's d columns, zeros elsewhere
    eye = jnp.eye(G, dtype=bool)[None, None, :, None, :, None]
    q5 = q.astype(jnp.float32).reshape(B, n_grp, G, g, 1, d)
    qbd = jnp.where(eye, q5, 0.0).reshape(B, n_grp, G * g, gw)
    if R != G * g:
        qbd = jnp.pad(qbd, [(0, 0), (0, 0), (0, R - G * g), (0, 0)])
    pages = _chunk_pages(bt, F, k_pool.dtype.itemsize, W)

    # decoded p8 codes and bf16 pools are exact in bf16 (the dots' bf16
    # path); the query stays unscaled, so a bf16 query is one bf16 piece
    bf16_kv = kv_bits == 8 or (kv_bits == 0 and k_pool.dtype == jnp.bfloat16)
    kernel = functools.partial(
        _paged_kernel, kv_bits=kv_bits, n_rows=B, width=W, n_blocks=N,
        max_len=W * bt, scale=scale, bf16_kv=bf16_kv,
        q_parts=1 if q.dtype == jnp.bfloat16 else 3)
    row = pl.BlockSpec((1, n_grp, R, gw), lambda b, *_: (b, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[row,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((2, pages, bt, F), k_pool.dtype),
                pltpu.VMEM((2, pages, bt, F), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((n_grp, R, 1), jnp.float32),
                pltpu.VMEM((n_grp, R, 1), jnp.float32),
                pltpu.VMEM((n_grp, R, gw), jnp.float32),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, n_grp, R, gw), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # rows run in order: each row's last chunk starts the next row's
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(jnp.asarray([es], jnp.int32), jnp.asarray(lengths, jnp.int32),
      jnp.asarray(block_table, jnp.int32).reshape(-1), qbd, k_pool, v_pool)
    # keep each head's own columns: (B, n_grp, G, g, G, d) -> diagonal in G
    out = out[:, :, :G * g].reshape(B, n_grp, G, g, G, d)
    out = jnp.diagonal(out, axis1=2, axis2=4)          # (B, n_grp, g, d, G)
    out = jnp.moveaxis(out, -1, 2)                     # (B, n_grp, G, g, d)
    return out.reshape(B, Hq, d).astype(q.dtype)
