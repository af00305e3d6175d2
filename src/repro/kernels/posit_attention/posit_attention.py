"""Decode-step attention over a posit-compressed KV cache (Pallas, flash-style).

The paper's memory-savings result (Table IV: P8 fits a 20x20 GEMM where FP32
fits 12x12) applied to the dominant inference bottleneck: the KV cache lives in
HBM as p8/p16 codes (2–4x fewer bytes than bf16/f32), and each K/V tile is
decoded *in VMEM* right before use — decode-step attention is purely
HBM-bandwidth-bound, so cutting payload bytes cuts step latency ~linearly.

One query token per (batch, head); all query heads of one KV group share a
program, so each K/V tile is fetched and decoded once per group (GQA/MQA) and
the q/out blocks are a whole (8k, d) tile (the TPU (8, 128) block rule).
Online-softmax accumulation over S tiles.

  grid = (B * Hkv, S // bs)          s innermost (arbitrary)
  q:    (B*Hkv, gp, d)   float32     block (1, gp, d)  gp = Hq/Hkv rounded up to 8
  kv:   (B*Hkv, S, d)    posit codes block (1, bs, d)
  out:  (B*Hkv, gp, d)   float32     block (1, gp, d)
  scratch: m, l (VMEM (gp, 1) f32), acc (VMEM (gp, d) f32)

Scalar prefetch: es (1,) int32 + lengths (B,) int32 (valid cache length per
batch row; masked with -inf before the running max). Tiles wholly past a
row's length skip their compute, and their index map repeats the last live
tile so the pipeline issues no new copy for them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.codec import posit_decode

_NEG_INF = -1e30


def _attn_kernel(
    es_ref, len_ref,            # scalar prefetch
    q_ref, k_ref, v_ref, o_ref, # blocks
    m_ref, l_ref, acc_ref,      # scratch
    *, kv_bits: int, hkv: int, block_s: int, n_s: int, scale: float,
):
    s_idx = pl.program_id(1)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[pl.program_id(0) // hkv]

    @pl.when(s_idx * block_s < length)
    def _accumulate():
        q = q_ref[0]                                         # (gp, d) f32
        if kv_bits:
            k = posit_decode(k_ref[0], kv_bits, es_ref[0])   # (bs, d) f32
            v = posit_decode(v_ref[0], kv_bits, es_ref[0])
        else:  # kv_bits=0: float KV cache, no codec, tile-wise astype only
            k = k_ref[0].astype(jnp.float32)
            v = v_ref[0].astype(jnp.float32)

        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (gp, bs)
        pos = s_idx * block_s + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        valid = pos < length
        scores = jnp.where(valid, scores, _NEG_INF)

        m_prev = m_ref[...]                                  # (gp, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # explicit zero for masked slots: a fully-masked row keeps m at
        # _NEG_INF, where exp(scores - m) == 1 would leak stale V
        p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(s_idx == n_s - 1)
    def _emit():
        l = l_ref[...]
        # length-0 rows (free engine slots) emit exact zeros, not 0/0
        o_ref[0] = acc_ref[...] / jnp.where(l == 0, 1.0, l)


@functools.partial(
    jax.jit,
    static_argnames=("kv_bits", "block_s", "interpret", "scale"),
)
def posit_decode_attention(
    q: jax.Array,          # (B, Hq, d) float
    k_codes: jax.Array,    # (B, Hkv, S, d) uint8/uint16 posit codes
    v_codes: jax.Array,    # (B, Hkv, S, d)  (float arrays when kv_bits=0)
    lengths: jax.Array,    # (B,) int32 — valid KV length per batch row
    es,                    # int32 scalar — pcsr pes for the KV cache
    *,
    kv_bits: int,          # 8 | 16 posit codes; 0 = float KV (codec bypassed)
    scale: float | None = None,
    block_s: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, d = q.shape
    Bk, Hkv, S, dk = k_codes.shape
    assert (B, d) == (Bk, dk) and Hq % Hkv == 0, (q.shape, k_codes.shape)
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    bs = min(block_s, S)
    S_p = -(-S // bs) * bs
    if S_p != S:  # pad; padded rows are masked off via `lengths`
        pad = [(0, 0), (0, 0), (0, S_p - S), (0, 0)]
        k_codes = jnp.pad(k_codes, pad)
        v_codes = jnp.pad(v_codes, pad)
    n_s = S_p // bs

    # one program per (batch, KV head): its g query heads, padded to 8 rows
    g = Hq // Hkv
    gp = -(-g // 8) * 8
    q3 = q.astype(jnp.float32).reshape(B * Hkv, g, d)
    if gp != g:
        q3 = jnp.pad(q3, [(0, 0), (0, gp - g), (0, 0)])
    k2 = k_codes.reshape(B * Hkv, S_p, d)
    v2 = v_codes.reshape(B * Hkv, S_p, d)

    def kv_index(bh, s, es_ref, len_ref):
        # tiles past the row's length repeat the last live one: no new copy
        last = jnp.maximum(-(-len_ref[bh // Hkv] // bs) - 1, 0)
        return (bh, jnp.minimum(s, last), 0)

    kernel = functools.partial(
        _attn_kernel, kv_bits=kv_bits, hkv=Hkv, block_s=bs, n_s=n_s,
        scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * Hkv, n_s),
            in_specs=[
                pl.BlockSpec((1, gp, d), lambda bh, s, *_: (bh, 0, 0)),
                pl.BlockSpec((1, bs, d), kv_index),
                pl.BlockSpec((1, bs, d), kv_index),
            ],
            out_specs=pl.BlockSpec((1, gp, d), lambda bh, s, *_: (bh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((gp, 1), jnp.float32),
                pltpu.VMEM((gp, 1), jnp.float32),
                pltpu.VMEM((gp, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, gp, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray([es], jnp.int32), jnp.asarray(lengths, jnp.int32), q3, k2, v2)
    return out[:, :g].reshape(B, Hq, d).astype(q.dtype)
