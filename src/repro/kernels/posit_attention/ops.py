"""Front door for posit-KV decode attention.

Implementations (one contract: flash decode over a possibly-ragged KV cache,
``lengths``-masked per batch row, posit codes decoded tile-wise — the full
cache is never materialized in float):

* ``pallas`` — the TPU flash kernel (posit_attention.py): codes stream
  HBM->VMEM and are decoded in VMEM right before the dot.
* ``tiled``  — the off-TPU serving path: an online-softmax ``while_loop``
  over S tiles with a *dynamic* trip count ``ceil(max(lengths)/block_s)``,
  so per-step decode work scales with the longest live sequence in the
  batch, not with ``S_max``.
* ``xla``    — the pure-jnp oracle (ref.py): full-cache decode + dense
  softmax.  Numerics ground truth for tests.
* ``auto``   — pallas on TPU, tiled elsewhere.

The paged engine's pool has its own front door,
:func:`posit_decode_attention_paged` (same contract, KV reached through a
block table): on a TPU the Pallas kernel in paged_attention.py reads only
each row's live pages from the pool; off-TPU one gather de-pages each row's
block-table window and runs the tiled path.

``kv_bits=0`` means a float KV cache: every path bypasses the codec and
just upcasts tiles (the ragged masking / tiling contract is unchanged).
``rolling=True`` is circular-buffer validity (gemma3 local layers): every
slot written so far is valid, i.e. lengths are clamped to the buffer size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.codec import posit_decode
from repro.kernels.posit_attention.paged_attention import (
    posit_decode_attention_paged_pallas,
)
from repro.kernels.posit_attention.posit_attention import posit_decode_attention
from repro.kernels.posit_attention.ref import posit_decode_attention_ref

_NEG_INF = -1e30


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("kv_bits", "scale", "block_s"))
def posit_decode_attention_tiled(
    q: jax.Array,          # (B, Hq, d) float
    k_codes: jax.Array,    # (B, Hkv, S, d) posit codes (float when kv_bits=0)
    v_codes: jax.Array,    # (B, Hkv, S, d)
    lengths: jax.Array,    # (B,) int32 — valid KV length per batch row
    es,                    # int32 scalar — pcsr pes for the KV cache
    *,
    kv_bits: int,
    scale: float | None = None,
    block_s: int = 256,
) -> jax.Array:
    """Length-bounded flash decode in plain XLA (the kernel contract off-TPU).

    ``lax.while_loop`` with trip count ``ceil(max(lengths)/block_s)``: tiles
    past the longest live row are never sliced, decoded, or dotted — decode
    bytes per step follow the *ragged* occupancy, not the allocated S_max.
    Rows with length 0 return zeros.
    """
    B, Hq, d = q.shape
    _, Hkv, S, _ = k_codes.shape
    g = Hq // Hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    bs = min(block_s, S)
    S_p = -(-S // bs) * bs
    if S_p != S:  # padded tail is masked off via lengths
        pad = [(0, 0), (0, 0), (0, S_p - S), (0, 0)]
        k_codes = jnp.pad(k_codes, pad)
        v_codes = jnp.pad(v_codes, pad)

    qg = q.reshape(B, Hkv, g, d).astype(jnp.float32) * scale
    lengths = jnp.asarray(lengths, jnp.int32)
    # traced tile count, clamped so an over-long row can't spin the loop
    n_live = -(-jnp.minimum(jnp.max(lengths), S) // bs)

    def decode_tile(codes):
        if kv_bits:
            return posit_decode(codes, kv_bits, es).astype(jnp.float32)
        return codes.astype(jnp.float32)

    def body(carry):
        i, m, l, acc = carry
        kt = decode_tile(jax.lax.dynamic_slice_in_dim(k_codes, i * bs, bs, 2))
        vt = decode_tile(jax.lax.dynamic_slice_in_dim(v_codes, i * bs, bs, 2))
        s = jnp.einsum("bkgd,bktd->bkgt", qg, kt)           # (B,Hkv,g,bs)
        pos = i * bs + jnp.arange(bs)
        valid = pos[None, :] < lengths[:, None]             # (B,bs)
        s = jnp.where(valid[:, None, None, :], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        # explicit zero for masked slots: an all-masked row keeps m at
        # _NEG_INF, where exp(s - m) == 1 would leak a uniform average
        p = jnp.where(valid[:, None, None, :], jnp.exp(s - m_new), 0.0)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bkgt,bktd->bkgd", p, vt)
        return i + 1, m_new, l, acc

    init = (jnp.int32(0),
            jnp.full((B, Hkv, g, 1), _NEG_INF, jnp.float32),
            jnp.zeros((B, Hkv, g, 1), jnp.float32),
            jnp.zeros((B, Hkv, g, d), jnp.float32))
    *_, l, acc = jax.lax.while_loop(lambda c: c[0] < n_live, body, init)
    out = acc / jnp.where(l == 0, 1.0, l)
    return out.reshape(B, Hq, d).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("kv_bits", "scale"))
def posit_decode_attention_paged_gather(
    q: jax.Array,            # (B, Hq, d) float
    k_pool: jax.Array,       # (N_blocks, bt, Hkv*d) posit codes (one layer)
    v_pool: jax.Array,       # (N_blocks, bt, Hkv*d)
    block_table: jax.Array,  # (B, W) int32 block ids; >= N_blocks = empty
    lengths: jax.Array,      # (B,) int32 — valid KV length per batch row
    es,                      # int32 scalar — pcsr pes for the KV cache
    *,
    kv_bits: int,
    scale: float | None = None,
) -> jax.Array:
    """The off-TPU lowering of :func:`posit_decode_attention_paged`.

    ONE batched gather de-pages each row's whole block-table window into a
    contiguous ``(B, Hkv, W*bt, d)`` code view, which then runs the tiled
    online-softmax above — the same compiled attention the slot grid uses
    off-TPU, at the same (wide) tile size.  In XLA:CPU, 16-token page tiles
    are dispatch-dominated, so hoisting the indirection into one gather is
    the cheap shape there, and it makes warm-vs-cold bit-identity structural
    rather than empirical.  On a TPU the gather copies every row's whole
    window, live or not, and the Pallas kernel replaces it.

    Table entries past a row's length are sentinels (``>= N_blocks``); their
    clamped gather reads whatever lives in an arbitrary real block, so
    masking must silence *values*, not just scores — a recycled block can
    hold NaR codes that decode to NaN, and ``0 * NaN`` would poison the
    accumulator through the masked-out probability.  Zeroing the gathered
    *codes* suffices: code 0 decodes to exact 0.0 in every posit config
    (and is 0.0 already when ``kv_bits == 0``).
    """
    B, _, d = q.shape
    N, bt, F = k_pool.shape
    Hkv = F // d
    W = block_table.shape[1]
    lengths = jnp.asarray(lengths, jnp.int32)
    table = jnp.minimum(jnp.asarray(block_table, jnp.int32), N - 1)

    def depage(pool):
        codes = pool[table]                        # (B, W, bt, Hkv*d)
        codes = codes.reshape(B, W * bt, Hkv, d)
        return jnp.moveaxis(codes, 2, 1)           # (B, Hkv, W*bt, d)

    valid = (jnp.arange(W * bt)[None, :] < lengths[:, None])[:, None, :, None]
    k_codes = jnp.where(valid, depage(k_pool), 0)
    v_codes = jnp.where(valid, depage(v_pool), 0)
    return posit_decode_attention_tiled(q, k_codes, v_codes, lengths, es,
                                        kv_bits=kv_bits, scale=scale)


def posit_decode_attention_paged(q, k_pool, v_pool, block_table, lengths, es,
                                 *, kv_bits, scale=None, impl="auto"):
    """Decode attention over one layer's paged pool (DESIGN.md §14).

    ``k_pool``/``v_pool``: ``(N_blocks, bt, Hkv*d)`` codes; ``block_table``
    ``(B, W)`` block ids, sentinels (``>= N_blocks``) past each row's
    length; ``lengths`` the valid length per row.  ``impl`` is the
    policy's ``attn_impl``.  ``"auto"`` and ``"kernel"`` run the Pallas
    kernel (paged_attention.py: only each row's live pages, read through
    the table) on a TPU, for pages of whole sublane rows (``bt % 8 == 0``);
    the one-gather lowering (:func:`posit_decode_attention_paged_gather`)
    runs everywhere else, so CPU results stay what the gather gives.  On a
    TPU, ``"kernel"`` with pages the kernel cannot take raises rather than
    quietly measure the gather; ``"auto"`` falls back to it.  ``"xla"``
    always takes the gather.  The ``named_scope`` tag names the path a
    device profile saw.
    """
    from repro.obs.trace import named_scope

    bt = k_pool.shape[1]
    if impl == "kernel" and _on_tpu() and bt % 8:
        raise ValueError(
            f"attn_impl='kernel': the paged kernel needs pages of a multiple "
            f"of 8 tokens, these hold {bt}; use attn_impl='auto' or 'xla'")
    if impl in ("auto", "kernel") and _on_tpu() and bt % 8 == 0:
        with named_scope("repro.decode_attention.paged_pallas"):
            return posit_decode_attention_paged_pallas(
                q, k_pool, v_pool, block_table, lengths, es, kv_bits=kv_bits,
                scale=scale)
    with named_scope("repro.decode_attention.paged_gather"):
        return posit_decode_attention_paged_gather(
            q, k_pool, v_pool, block_table, lengths, es, kv_bits=kv_bits,
            scale=scale)


def decode_attention(q, k_codes, v_codes, lengths, es, *, kv_bits,
                     scale=None, impl="auto", interpret=None, block_s=512,
                     rolling=False):
    """Dispatch one decode-attention step; see module docstring for impls.

    The ``obs.trace.named_scope`` tag makes every decode-attention dispatch
    show up under one name in ``jax.profiler`` device traces, lined up with
    the engine's host-side request spans (DESIGN.md §12); an active
    ``obs.prof`` profiler additionally receives one cost record per dispatch
    (analytic bytes over the allocated S — DESIGN.md §16).
    """
    from repro.obs import prof
    from repro.obs.trace import named_scope

    if rolling:
        # circular window buffer: every slot written so far is valid
        lengths = jnp.minimum(jnp.asarray(lengths, jnp.int32),
                              k_codes.shape[2])
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "tiled"

    def _run():
        with named_scope(f"repro.decode_attention.{impl}"):
            if impl == "pallas":
                interp = interpret if interpret is not None else not _on_tpu()
                return posit_decode_attention(
                    q, k_codes, v_codes, lengths, es, kv_bits=kv_bits,
                    scale=scale, block_s=block_s, interpret=interp)
            if impl == "tiled":
                return posit_decode_attention_tiled(
                    q, k_codes, v_codes, lengths, es, kv_bits=kv_bits,
                    scale=scale, block_s=min(block_s, 256))
            return posit_decode_attention_ref(
                q, k_codes, v_codes, lengths, es, kv_bits=kv_bits, scale=scale)

    if not prof.is_active():
        return _run()
    return prof.dispatch(
        "attention", impl, prof.attention_cost(q, k_codes, kv_bits=kv_bits),
        _run, primary=q)
