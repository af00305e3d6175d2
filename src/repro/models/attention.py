"""Attention: GQA/MQA/MHA, causal / bidirectional / sliding-window / cross,
training (full-sequence) and serving (KV-cache prefill + decode) paths.

KV-cache transprecision (the paper's memory-savings claim at the serving
bottleneck): when ``policy.kv_cache`` is a posit format, the cache is stored as
uint8/16 codes; new K/V are encoded on write and tiles are decoded at the
attention boundary (Pallas kernel on TPU, identical-contract XLA path on CPU).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.codec import posit_decode, posit_encode
from repro.core.pcsr import TransPolicy
from repro.kernels.posit_attention import ops as attn_ops
from repro.models.layers import apply_linear, apply_rope, init_linear
from repro.obs import prof
from repro.models.unroll import scan_or_unroll, unrolled

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnCfg:
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    qkv_bias: bool = False
    rope_base: float = 10000.0
    use_rope: bool = True
    causal: bool = True
    window: int = 0          # >0: sliding-window (local) attention
    is_cross: bool = False   # cross-attention (kv from encoder; no rope/causal)


def init_attention(key, cfg: AttnCfg) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    return {
        "wq": init_linear(kq, d, H * hd, bias=cfg.qkv_bias),
        "wk": init_linear(kk, d, Hkv * hd, bias=cfg.qkv_bias),
        "wv": init_linear(kv, d, Hkv * hd, bias=cfg.qkv_bias),
        "wo": init_linear(ko, H * hd, d, scale=(H * hd) ** -0.5),
    }


def _split_heads(x: jax.Array, n: int, hd: int) -> jax.Array:
    return x.reshape(*x.shape[:-1], n, hd)


Q_CHUNK = 512  # query-block size for memory-efficient attention


def _sdpa_block(qg, k, v, scale, *, offset, causal, window):
    """One query block. qg: (B,Lq,Hkv,g,hd); k/v: (B,T,Hkv,hd).

    offset: absolute position of the block's first query. window may be a
    traced scalar (0 = unbounded). Returns (B, Lq, Hkv, g, hd).
    """
    B, Lq = qg.shape[:2]
    T = k.shape[1]
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    if causal or window is not None:
        qp = jnp.arange(Lq)[:, None] + offset
        kp = jnp.arange(T)[None, :]
        m = jnp.ones((Lq, T), bool)
        if causal:
            m &= kp <= qp
        if window is not None:
            weff = jnp.where(window > 0, window, T + 1)
            m &= kp > qp - weff
        scores = jnp.where(m[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))


def _sdpa(q, k, v, scale, *, causal=True, window=None, q_chunk=Q_CHUNK):
    """Memory-efficient SDPA: scan over query blocks so only a
    (B, H, q_chunk, T) score slab is ever live (the XLA-path stand-in for the
    Pallas flash kernel on TPU). q: (B,S,H,hd), k/v: (B,T,Hkv,hd)."""
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, S, Hkv, g, hd)
    if unrolled():
        # cost probes: one full-S block so every attention FLOP is HLO-visible
        out = _sdpa_block(qg, k, v, scale, offset=0, causal=causal,
                          window=window)
        return out.reshape(B, S, H, hd)
    if S <= q_chunk:
        out = _sdpa_block(qg, k, v, scale, offset=0, causal=causal,
                          window=window)
        return out.reshape(B, S, H, hd)
    nc = -(-S // q_chunk)
    Sp = nc * q_chunk
    if Sp != S:
        qg = jnp.pad(qg, ((0, 0), (0, Sp - S), (0, 0), (0, 0), (0, 0)))
    qb = qg.reshape(B, nc, q_chunk, Hkv, g, hd).transpose(1, 0, 2, 3, 4, 5)

    def body(_, idx_qb):
        i, qblk = idx_qb
        out = _sdpa_block(qblk, k, v, scale, offset=i * q_chunk,
                          causal=causal, window=window)
        return None, out

    # remat: without it lax.scan saves every chunk's (B,H,Lq,T) score slab for
    # the backward pass — exactly the S^2 buffer the chunking is here to avoid
    body = jax.checkpoint(body)
    _, outs = scan_or_unroll(body, None, (jnp.arange(nc), qb))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sp, H, hd)
    return out[:, :S]


def make_mask(S: int, T: int, *, causal: bool, window: int,
              offset: int = 0) -> Optional[jax.Array]:
    """(S, T) bool; query position i corresponds to absolute position i+offset."""
    if not causal and window <= 0:
        return None
    qp = jnp.arange(S)[:, None] + offset
    kp = jnp.arange(T)[None, :]
    m = jnp.ones((S, T), bool)
    if causal:
        m &= kp <= qp
    if window > 0:
        m &= kp > qp - window
    return m


def apply_attention(params: dict, cfg: AttnCfg, x: jax.Array,
                    policy: TransPolicy, *,
                    xattn_kv: Optional[jax.Array] = None,
                    positions: Optional[jax.Array] = None,
                    path: str = "attn") -> jax.Array:
    """Training / prefill full-sequence attention. x: (B, S, D).

    ``path`` names this attention instance for per-layer policy
    resolution ("attn" | "self" | "cross" — must match the param-tree
    key so quantize-time and apply-time formats agree, DESIGN.md §9).
    """
    B, S, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    q = _split_heads(apply_linear(params["wq"], x, policy, path=f"{path}/wq"), H, hd)
    kv_src = xattn_kv if cfg.is_cross else x
    k = _split_heads(apply_linear(params["wk"], kv_src, policy, path=f"{path}/wk"), Hkv, hd)
    v = _split_heads(apply_linear(params["wv"], kv_src, policy, path=f"{path}/wv"), Hkv, hd)
    if cfg.use_rope and not cfg.is_cross:
        if positions is None:
            positions = jnp.arange(S)[None]
        q = apply_rope(q, positions, cfg.rope_base)
        k = apply_rope(k, positions, cfg.rope_base)
    out = _sdpa(q, k, v, hd ** -0.5,
                causal=cfg.causal and not cfg.is_cross,
                window=cfg.window if (cfg.window and not cfg.is_cross) else None)
    return apply_linear(params["wo"], out.reshape(B, S, H * hd), policy,
                        path=f"{path}/wo")


def apply_attention_dynwin(params: dict, cfg: AttnCfg, x: jax.Array,
                           policy: TransPolicy, *, window, rope_base,
                           positions: Optional[jax.Array] = None,
                           path: str = "attn") -> jax.Array:
    """apply_attention with window / rope_base as *traced* per-layer scalars.

    Lets heterogeneous layer patterns (gemma3 5-local:1-global) run under one
    lax.scan body: window==0 means unbounded (full causal).
    """
    B, S, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    q = _split_heads(apply_linear(params["wq"], x, policy, path=f"{path}/wq"), H, hd)
    k = _split_heads(apply_linear(params["wk"], x, policy, path=f"{path}/wk"), Hkv, hd)
    v = _split_heads(apply_linear(params["wv"], x, policy, path=f"{path}/wv"), Hkv, hd)
    if cfg.use_rope:
        if positions is None:
            positions = jnp.arange(S)[None]
        q = apply_rope(q, positions, rope_base)
        k = apply_rope(k, positions, rope_base)
    out = _sdpa(q, k, v, hd ** -0.5, causal=True, window=window)
    return apply_linear(params["wo"], out.reshape(B, S, H * hd), policy,
                        path=f"{path}/wo")


# ------------------------------------------------------------- KV cache -------

def init_kv_cache(B: int, S_max: int, cfg: AttnCfg, policy: TransPolicy) -> dict:
    """Cache layout (B, Hkv, S_max, hd); posit codes if policy.kv_cache set."""
    fmt = policy.kv_cache
    if fmt is not None:
        dt = jnp.uint8 if fmt.nbits == 8 else jnp.uint16
    else:
        dt = jnp.float32 if policy.compute_dtype == "f32" else jnp.bfloat16
    shape = (B, cfg.n_kv, S_max, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
            "len": jnp.zeros((B,), jnp.int32)}


def _store(cache_arr, new, pos, policy):
    """Write (B, Hkv, s, hd) ``new`` at sequence offset ``pos``.

    ``pos`` is either a scalar (lockstep batch / prefill block write) or a
    (B,) vector of per-row write indices with s == 1 (ragged decode: every
    row of a continuous batch sits at its own sequence position).  Per-row
    writes use a scatter; out-of-bounds rows (recycled engine slots past
    S_max) are dropped by JAX scatter semantics.
    """
    fmt = policy.kv_cache
    if fmt is not None:
        new = posit_encode(new.astype(jnp.float32), fmt.nbits, fmt.es)
    else:
        new = new.astype(cache_arr.dtype)
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 1:
        B = cache_arr.shape[0]
        return cache_arr.at[jnp.arange(B), :, pos].set(new[:, :, 0],
                                                       mode="drop")
    return jax.lax.dynamic_update_slice(
        cache_arr, new, (0, 0, pos, 0))


def _load(cache_arr, policy):
    fmt = policy.kv_cache
    if fmt is not None:
        return posit_decode(cache_arr, fmt.nbits, fmt.es)
    return cache_arr.astype(jnp.float32)


def prefill_attention(params: dict, cfg: AttnCfg, x: jax.Array, cache: dict,
                      policy: TransPolicy,
                      xattn_kv: Optional[jax.Array] = None,
                      path: str = "attn") -> tuple:
    """Full-sequence attention that also fills the KV cache. x: (B, S, D)."""
    B, S, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    q = _split_heads(apply_linear(params["wq"], x, policy, path=f"{path}/wq"), H, hd)
    kv_src = xattn_kv if cfg.is_cross else x
    k = _split_heads(apply_linear(params["wk"], kv_src, policy, path=f"{path}/wk"), Hkv, hd)
    v = _split_heads(apply_linear(params["wv"], kv_src, policy, path=f"{path}/wv"), Hkv, hd)
    if cfg.use_rope and not cfg.is_cross:
        pos = jnp.arange(S)[None]
        q = apply_rope(q, pos, cfg.rope_base)
        k = apply_rope(k, pos, cfg.rope_base)
    T = k.shape[1]
    out = _sdpa(q, k, v, hd ** -0.5,
                causal=cfg.causal and not cfg.is_cross,
                window=cfg.window if (cfg.window and not cfg.is_cross) else None)
    y = apply_linear(params["wo"], out.reshape(B, S, H * hd), policy,
                        path=f"{path}/wo")
    cache = dict(cache)
    kt, vt = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)  # (B,Hkv,T,hd)
    Sc = cache["k"].shape[2]
    if T > Sc:
        # rolling window buffer (gemma3 local layers): keep the last Sc
        # positions, placed at their (pos % Sc) slots so decode can continue
        kt = jnp.roll(kt[:, :, T - Sc:], shift=T % Sc, axis=2)
        vt = jnp.roll(vt[:, :, T - Sc:], shift=T % Sc, axis=2)
    cache["k"] = _store(cache["k"], kt, 0, policy)
    cache["v"] = _store(cache["v"], vt, 0, policy)
    cache["len"] = jnp.full_like(cache["len"], min(T, Sc))
    return y, cache


def init_paged_kv_pool(n_blocks: int, block_tokens: int, cfg: AttnCfg,
                       policy: TransPolicy) -> dict:
    """One layer's paged KV pool: ``(n_blocks, block_tokens, Hkv*hd)`` codes.

    A page is ``block_tokens`` token rows with every KV head side by side:
    the row is lane-dense whatever ``hd`` is, so the TPU keeps the pool
    row-major and a page is one contiguous copy (a ``(..., bt, hd)`` minor
    tile with ``hd`` 96 would pad to 128 lanes, and the TPU then lays the
    block axis out minor instead).  Same dtype rule as
    :func:`init_kv_cache`; the per-slot ``len`` lives with the engine
    (``cache["lens"]``), and the block table is shared across layers —
    every layer of a slot uses the same block ids (DESIGN.md §14).
    """
    fmt = policy.kv_cache
    if fmt is not None:
        dt = jnp.uint8 if fmt.nbits == 8 else jnp.uint16
    else:
        dt = jnp.float32 if policy.compute_dtype == "f32" else jnp.bfloat16
    shape = (n_blocks, block_tokens, cfg.n_kv * cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _store_paged(pool_arr, new, bids, offs, policy):
    """Scatter (B, Hkv*hd) ``new`` into ``pool[bids[b], offs[b]]``.

    Sentinel block ids (>= n_blocks) drop the write — inactive slots point
    every table entry out of bounds, so the lockstep grid step is a no-op
    for them (mirrors the recycled-slot ``mode="drop"`` in :func:`_store`).
    """
    fmt = policy.kv_cache
    if fmt is not None:
        new = posit_encode(new.astype(jnp.float32), fmt.nbits, fmt.es)
    else:
        new = new.astype(pool_arr.dtype)
    bids = jnp.asarray(bids, jnp.int32)
    offs = jnp.asarray(offs, jnp.int32)
    return pool_arr.at[bids, offs].set(new, mode="drop")


def decode_attention_step_paged(params: dict, cfg: AttnCfg, x_t: jax.Array,
                                pool: dict, block_table: jax.Array,
                                lens, policy: TransPolicy,
                                path: str = "attn") -> tuple:
    """One decode step over a paged KV pool (DESIGN.md §14).

    ``pool`` holds one layer's ``{"k", "v"}`` block arrays
    ``(N, bt, Hkv*hd)``; ``block_table`` is the slot grid's ``(B, W)``
    indirection and ``lens`` the per-row write index (= valid length before
    this token).  The engine guarantees the write target
    ``block_table[b, lens[b] // bt]`` is a *private* block (copy-on-write
    runs before the step), so no two rows ever scatter into the same page.
    Attention reads go through the table: the Pallas paged kernel on a TPU,
    the one-gather tiled path elsewhere or under ``attn_impl="xla"``.
    """
    B, _, _ = x_t.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    bt = pool["k"].shape[1]
    q = _split_heads(apply_linear(params["wq"], x_t, policy,
                                  path=f"{path}/wq"), H, hd)
    kn = _split_heads(apply_linear(params["wk"], x_t, policy,
                                   path=f"{path}/wk"), Hkv, hd)
    vn = _split_heads(apply_linear(params["wv"], x_t, policy,
                                   path=f"{path}/wv"), Hkv, hd)
    lens = jnp.asarray(lens, jnp.int32)
    if cfg.use_rope:
        p1 = jnp.broadcast_to(lens[:, None], (B, 1))
        q = apply_rope(q, p1, cfg.rope_base)
        kn = apply_rope(kn, p1, cfg.rope_base)
    bids = jnp.take_along_axis(jnp.asarray(block_table, jnp.int32),
                               (lens // bt)[:, None], axis=1)[:, 0]
    offs = lens % bt
    new_pool = dict(pool)
    new_pool["k"] = _store_paged(pool["k"], kn.reshape(B, Hkv * hd),
                                 bids, offs, policy)
    new_pool["v"] = _store_paged(pool["v"], vn.reshape(B, Hkv * hd),
                                 bids, offs, policy)
    fmt = policy.kv_cache
    with prof.site(path):
        out = attn_ops.posit_decode_attention_paged(
            q.reshape(B, H, hd), new_pool["k"], new_pool["v"], block_table,
            lens + 1, fmt.es if fmt is not None else 0,
            kv_bits=fmt.nbits if fmt is not None else 0,
            impl=getattr(policy, "attn_impl", "auto"))
    y = apply_linear(params["wo"], out.reshape(B, 1, H * hd).astype(x_t.dtype),
                     policy, path=f"{path}/wo")
    return y, new_pool


def resolve_attn_impl(policy: TransPolicy, cfg: AttnCfg, *,
                      rolling: bool = False) -> str:
    """Resolve ``policy.attn_impl`` for one decode-step attention layer.

    "kernel" routes the step through ``kernels.posit_attention.ops`` (Pallas
    flash decode on TPU, length-bounded tiled XLA path elsewhere — the cache
    is decoded tile-wise, never materialized in full).  The kernel contract
    covers per-row ``len`` masking, rolling (circular-buffer) windows, and
    read-only cross caches; a non-rolling sliding window (a windowed layer
    whose cache is larger than the window) needs the windowed mask only the
    xla path implements.
    """
    impl = getattr(policy, "attn_impl", "auto")
    if impl == "xla":
        return "xla"
    if cfg.window > 0 and not rolling and not cfg.is_cross:
        if impl == "kernel":
            # refuse rather than silently measure xla-vs-xla: the kernel
            # has no windowed mask for a cache larger than the window
            raise ValueError(
                "attn_impl='kernel' cannot serve a non-rolling "
                f"sliding-window layer (window={cfg.window}); use a "
                "window-sized rolling cache or attn_impl='auto'/'xla'")
        return "xla"
    return "kernel"


def decode_attention_step(params: dict, cfg: AttnCfg, x_t: jax.Array,
                          cache: dict, pos, policy: TransPolicy,
                          *, rolling: bool = False,
                          abs_pos=None, path: str = "attn") -> tuple:
    """One decode step. x_t: (B, 1, D); pos: the *cache write index* — an
    int32 scalar (lockstep batch) or a (B,) vector (ragged continuous batch,
    every row at its own position).

    rolling=True: the cache is a circular window buffer (gemma3 local layers):
    every slot written so far is valid and the window bound is implicit in the
    buffer size. ``abs_pos`` is the absolute sequence position for RoPE when it
    differs from the write index (defaults to pos; scalar or (B,)).

    Masking is uniformly ``cache["len"]``-driven per batch row (cross reads
    the prefilled length; self counts the token written this step), so ragged
    batches attend correctly on every path.
    """
    B, _, _ = x_t.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    q = _split_heads(apply_linear(params["wq"], x_t, policy,
                                  path=f"{path}/wq"), H, hd)   # (B,1,H,hd)
    if cfg.is_cross:
        # cross-attention reads the (already prefilled) encoder cache only
        new_cache = cache
        lens = cache["len"]
    else:
        kn = _split_heads(apply_linear(params["wk"], x_t, policy, path=f"{path}/wk"), Hkv, hd)
        vn = _split_heads(apply_linear(params["wv"], x_t, policy, path=f"{path}/wv"), Hkv, hd)
        if cfg.use_rope:
            ap = jnp.asarray(pos if abs_pos is None else abs_pos, jnp.int32)
            p1 = jnp.broadcast_to(jnp.atleast_1d(ap)[:, None], (B, 1))
            q = apply_rope(q, p1, cfg.rope_base)
            kn = apply_rope(kn, p1, cfg.rope_base)
        new_cache = dict(cache)
        new_cache["k"] = _store(cache["k"], kn.transpose(0, 2, 1, 3), pos, policy)
        new_cache["v"] = _store(cache["v"], vn.transpose(0, 2, 1, 3), pos, policy)
        # clamp at the buffer size: a slot never holds more than S_cache valid
        # positions (rolling buffers wrap; recycled engine slots would
        # otherwise grow `len` without bound between eviction and reuse)
        new_cache["len"] = jnp.minimum(cache["len"] + 1, cache["k"].shape[2])
        lens = new_cache["len"]

    impl = resolve_attn_impl(policy, cfg, rolling=rolling)
    if impl == "kernel":
        fmt = policy.kv_cache
        with prof.site(path):
            out = attn_ops.decode_attention(
                q.reshape(B, H, hd),
                new_cache["k"], new_cache["v"], lens,
                fmt.es if fmt is not None else 0,
                kv_bits=fmt.nbits if fmt is not None else 0,
                rolling=rolling)
        out = out.reshape(B, 1, H * hd)
    else:
        k = _load(new_cache["k"], policy)   # (B,Hkv,T,hd)
        v = _load(new_cache["v"], policy)
        S_cache = k.shape[2]
        qf = q.reshape(B, Hkv, H // Hkv, hd).astype(jnp.float32) * (hd ** -0.5)
        scores = jnp.einsum("bkgd,bktd->bkgt", qf, k)
        t = jnp.arange(S_cache)[None, None, None, :]
        lb = jnp.broadcast_to(jnp.asarray(lens, jnp.int32), (B,))
        if rolling:
            # circular buffer: every slot written so far is valid
            lb = jnp.minimum(lb, S_cache)
        valid = t < lb[:, None, None, None]
        if cfg.window > 0 and not rolling and not cfg.is_cross:
            pr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
            valid &= t > (pr - cfg.window)[:, None, None, None]
        scores = jnp.where(valid, scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgt,bktd->bkgd", p, v).reshape(B, 1, H * hd)
    y = apply_linear(params["wo"], out.astype(x_t.dtype), policy,
                     path=f"{path}/wo")
    return y, new_cache
