"""Posit-compressed collectives (shard_map) — the paper's transport-format
insight applied to the slowest links of a multi-pod system.

``compressed_psum``: all-reduce whose *cross-pod* hop moves posit codes
instead of f32/bf16, as a two-hop compressed all-reduce:

    within pod :  psum over ("data",)              — full precision, fast ICI
    hop 1      :  encode -> all_to_all code shards — each pod-rank receives
                  every peer's copy of its own 1/N shard (1–2 B/element)
    local      :  decode + sum (f32)               — the reduction itself
    hop 2      :  encode -> all_gather shards      — reassembled full tensor

Wire bytes per device ≈ 2·(N-1)/N · M · storage_bytes — exactly 2x (p16) or
4x (p8) less than an f32 ring all-reduce at ANY pod count N.

Two uses of the paper's dynamic-es: ``es`` may be chosen per tensor at
runtime (``auto_es``) so one executable serves every gradient scale, and the
f32 error-feedback residual (Karimireddy-style EF) keeps compression unbiased
across steps. All functions are shard_map-compatible (axis names only).

``quire_psum_posit`` / ``exact_psum`` are the PERCIVAL-style counterpoint:
the reduction runs in the quire domain (integer psum of Kulisch limbs), so
the *sum itself* is exact and only encode/readout round — see DESIGN.md §7.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.codec import auto_es, posit_decode, posit_encode
from repro.core.quire import (
    QuireFmt, quire_from_posit, quire_normalize, quire_read,
)
from repro.core.types import PositFmt


def _pow2_scale(x: jax.Array, axis: Optional[str]):
    """Exact power-of-2 normalizer centering |x| at posit's accuracy peak.

    Posit accuracy tapers away from 1.0; gradients live at ~1e-3 where p16_0
    would spend ~10 regime bits. Scaling by 2^-k (k = floor(log2 max|x|)) is
    *exact* (both directions), costs one f32 per tensor, and is the posit
    analogue of fp8 per-tensor scaling (beyond-paper; EXPERIMENTS.md §Perf).
    """
    amax = jnp.max(jnp.abs(x))
    if axis is not None:
        amax = lax.pmax(amax, axis)
    k = jnp.where(amax > 0,
                  jnp.floor(jnp.log2(jnp.maximum(amax, 1e-38))), 0.0)
    inv = jnp.exp2(-k)
    return inv, jnp.exp2(k)


def compressed_allreduce(x: jax.Array, fmt: PositFmt, axis: str,
                         es=None) -> jax.Array:
    """Two-hop posit-compressed all-reduce over `axis` (inside shard_map)."""
    n = lax.axis_size(axis)
    shape = x.shape
    xf = x.astype(jnp.float32).reshape(-1)
    M = xf.shape[0]
    pad = (-M) % n
    if pad:
        xf = jnp.pad(xf, (0, pad))
    inv, back = _pow2_scale(xf, axis)
    xf = xf * inv
    if es is None:
        es = lax.pmax(auto_es(xf, fmt.nbits), axis)
    codes = posit_encode(xf, fmt.nbits, es, ftz=True).reshape(n, -1)
    # hop 1: everyone sends shard j to rank j (codes, 1–2 B/element)
    recv = lax.all_to_all(codes, axis, split_axis=0, concat_axis=0, tiled=False)
    partial = jnp.sum(posit_decode(recv, fmt.nbits, es), axis=0)  # own shard
    # hop 2: share the reduced shards (codes again)
    out_codes = posit_encode(partial, fmt.nbits, es, ftz=True)
    full = lax.all_gather(out_codes, axis, tiled=True)
    out = posit_decode(full, fmt.nbits, es) * back
    if pad:
        out = out[:M]
    return out.reshape(shape).astype(x.dtype)


def quire_psum_posit(codes: jax.Array, fmt: PositFmt, axis: str,
                     es=None, out_es=None) -> jax.Array:
    """EXACT all-reduce of posit values over `axis` (inside shard_map).

    Each device injects its codes into a quire (exact), the int32 limbs are
    integer-psummed (exact: canonical digits stay in int32 for up to 2^14
    devices), and ONE terminal rounding produces the result — bit-identical
    to summing the decoded values in infinite precision and encoding once.
    NaR on any device poisons the reduction to NaR (flag limbs sum).

    The trade is wire bytes for exactness: the quire payload is
    4*(n_limbs+1) B/element (vs 1-2 B for compressed codes), so this is the
    collective for small precision-critical reductions — losses, norms,
    router statistics, quire-GEMM partials over a sharded K — not bulk
    gradient traffic.
    """
    qf = QuireFmt.for_posit(fmt)
    e = fmt.es if es is None else es
    q = quire_from_posit(codes, qf, es=e)
    q = lax.psum(q, axis)
    q = quire_normalize(q, qf)
    return quire_read(q, qf, es_out=e if out_es is None else out_es)


def exact_psum(x: jax.Array, fmt: PositFmt, axis: str, es=None) -> jax.Array:
    """psum of float tensors through the quire domain (inside shard_map).

    Exactly two roundings total regardless of device count: each device
    encodes its contribution to posit once, the quire-domain sum is exact,
    and the readout rounds once. (A ring/tree float all-reduce re-rounds at
    every hop; ``compressed_allreduce`` re-rounds twice more.) The pow2
    prescale is exact in both directions, so it does not add roundings.
    """
    xf = x.astype(jnp.float32)
    inv, back = _pow2_scale(xf, axis)
    xs = xf * inv
    if es is None:
        es = lax.pmax(auto_es(xs, fmt.nbits), axis)
    codes = posit_encode(xs, fmt.nbits, es, ftz=True)
    total = posit_decode(quire_psum_posit(codes, fmt, axis, es=es),
                         fmt.nbits, es) * back
    return total.astype(x.dtype)


def compressed_psum(x: jax.Array, fmt: Optional[PositFmt], *,
                    intra_axis="data", inter_axis: Optional[str] = "pod",
                    residual: Optional[jax.Array] = None, es=None,
                    exact: bool = False):
    """psum over (intra_axis, inter_axis); the inter hop is posit-compressed.

    Returns (sum, new_residual). fmt=None -> plain psum (IEEE bypass).
    Error feedback: `residual` (f32, same shape as x) carries the quantization
    error of *this device's contribution* into the next step.
    ``exact=True`` runs the inter hop in the quire domain: the per-device
    encode rounding still happens (and still feeds the residual), but the
    cross-pod reduction itself is exact with a single readout rounding —
    the rounded-hop noise of the two-hop path disappears entirely.
    """
    y = lax.psum(x, intra_axis)
    if inter_axis is None:
        return y, residual
    if fmt is None:
        return lax.psum(y, inter_axis), residual

    yf = y.astype(jnp.float32)
    if residual is not None:
        yf = yf + residual
    inv, back = _pow2_scale(yf, inter_axis)
    ys = yf * inv
    if es is None:
        es_t = lax.pmax(auto_es(ys, fmt.nbits), inter_axis)
    else:
        es_t = es
    codes = posit_encode(ys, fmt.nbits, es_t, ftz=True)
    sent = posit_decode(codes, fmt.nbits, es_t) * back
    new_residual = yf - sent
    if exact:
        total = posit_decode(quire_psum_posit(codes, fmt, inter_axis, es=es_t),
                             fmt.nbits, es_t) * back
    else:
        total = compressed_allreduce(sent, fmt, inter_axis, es=es_t)
    return total.astype(x.dtype), new_residual


def compressed_all_gather(x_codes: jax.Array, axis: str, fmt: PositFmt,
                          es=None, out_dtype=jnp.float32) -> jax.Array:
    """all_gather posit codes along `axis`, decode once locally (FSDP unshard):
    the wire moves 1–2-byte codes (2–4x less traffic than f32/bf16)."""
    g = lax.all_gather(x_codes, axis, tiled=True)
    e = fmt.es if es is None else es
    return posit_decode(g, fmt.nbits, e).astype(out_dtype)


def make_grad_sync(mesh, fmt: Optional[PositFmt], *, use_pod_axis: bool,
                   exact: bool = False):
    """Pytree gradient synchronizer built on compressed_psum (see steps.py for
    the shard_map integration into the train step). ``exact=True`` (the
    TransPolicy.exact_collectives bit) makes the cross-pod hop a quire-domain
    exact reduction."""
    axes = ("pod", "data") if use_pod_axis else ("data",)
    n_total = 1
    for a in axes:
        n_total *= mesh.shape[a]

    def sync(grads, residuals):
        flat_g, td = jax.tree.flatten(grads)
        flat_r = (td.flatten_up_to(residuals) if residuals is not None
                  else [None] * len(flat_g))
        outs = []
        for g, r in zip(flat_g, flat_r):
            if use_pod_axis:
                s, r2 = compressed_psum(g, fmt, intra_axis="data",
                                        inter_axis="pod", residual=r,
                                        exact=exact)
            else:
                s, r2 = lax.psum(g, "data"), r
            outs.append((s / n_total, r2))
        return (td.unflatten([o[0] for o in outs]),
                td.unflatten([o[1] for o in outs]))

    return sync
