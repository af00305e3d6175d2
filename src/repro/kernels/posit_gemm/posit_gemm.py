"""Fused posit GEMM Pallas kernel — the paper's codec-at-the-FPU-boundary, tiled.

Dataflow per (i, j, k) grid step (paper Fig. 2(b) on the TPU memory hierarchy):

    HBM --BlockSpec--> VMEM:  A tile (bm x bk)   posit codes or float
                              B tile (bk x bn)   posit codes or float
                              bias (1 x bn), residual (bm x bn)   [optional]
    VMEM:   [input decoder]   posit -> bf16/f32  (skipped for float operands)
    MXU:    acc(f32) += A' @ B'                  (the "FPU datapath")
    VMEM:   [fused epilogue]  act(acc + bias) + residual      (last k)
    VMEM:   [output encoder]  f32 -> posit       (skipped for float rd)
    VMEM --BlockSpec--> HBM:  O tile (bm x bn)

Posit operands move through HBM as 1–2-byte codes, so a p8 x p8 GEMM reads 4x
fewer HBM bytes than f32 (the paper's scratchpad-savings, Table IV) and the
decode rides in VMEM next to the MXU (the paper's lightweight-codec claim).
The epilogue (bias add, activation, residual add, output encode) runs inside
the same kernel invocation: one launch and one HBM write per layer instead of
a gemm -> bias -> act -> encode chain of four (DESIGN.md §8).

``es`` for (rs1, rs2, rd) arrives as a scalar-prefetch vector — the pcsr: one
compiled kernel serves every exponent size at runtime.

Grid is (m, n, k) with k innermost/arbitrary; a VMEM f32 scratch accumulates
across k tiles (revisited output pattern).  Block sizes are rounded *up* to
hardware-friendly multiples (lane = 128, sublane per dtype) and the operands
padded, never shrunk to ragged tiles: ``min(block, dim)`` on a small dim used
to produce tiles that violate the TPU (sublane, lane) tiling.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import LANE, pad_to, round_block, sublane

from repro.core.codec import posit_decode, posit_encode
from repro.core.dot import ACTIVATIONS, _apply_activation
from repro.core.lut import _p8_decode_table
from repro.core.pack import split_activations
from repro.core.types import Fmt, PositFmt, compute_dtype_for


def _decode_p8_lane(codes, es, lut_ref):
    """In-kernel p8 decode of one extracted lane: the PR-2 LUT gather where
    the backend tolerates it (``lut_ref`` holds the (4, 256) decode table as
    a kernel input — Pallas kernels can't close over constants), the bit
    pipeline on Mosaic (``lut_ref is None``)."""
    if lut_ref is not None:
        return lut_ref[...][es][codes.astype(jnp.int32)]
    return posit_decode(codes, 8, es)


def _gemm_kernel(
    es_ref,  # scalar prefetch: (3,) int32 = es for rs1, rs2, rd
    *refs,
    a_fmt: Fmt, b_fmt: Fmt, out_fmt: Fmt, compute_dtype, n_k: int,
    activation: str, has_bias: bool, has_residual: bool,
    b_packed: bool = False, codec_impl: str = "bits",
):
    it = iter(refs)
    lut_ref = None
    if b_packed:
        a_lo_ref, a_hi_ref, b_ref = next(it), next(it), next(it)
        if codec_impl == "lut":
            lut_ref = next(it)
    else:
        a_ref, b_ref = next(it), next(it)
    bias_ref = next(it) if has_bias else None
    res_ref = next(it) if has_residual else None
    o_ref, acc_ref = next(it), next(it)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def dec_a(ref):
        a = ref[...]
        if isinstance(a_fmt, PositFmt):
            return posit_decode(a, a_fmt.nbits, es_ref[0]).astype(compute_dtype)
        return a.astype(compute_dtype)

    if b_packed:
        # split-K packed lanes (core/pack.py): the (bk, bn) uint16 tile holds
        # 2*bk p8 codes; each lane extract + decode feeds one full-width MXU
        # contraction against the matching half of A — two dots per tile,
        # half the B words through the BlockSpec pipeline.
        bp = b_ref[...]
        b_lo = _decode_p8_lane(bp & jnp.uint16(0xFF), es_ref[1],
                               lut_ref).astype(compute_dtype)
        b_hi = _decode_p8_lane(bp >> jnp.uint16(8), es_ref[1],
                               lut_ref).astype(compute_dtype)
        acc_ref[...] += (
            jnp.dot(dec_a(a_lo_ref), b_lo, preferred_element_type=jnp.float32)
            + jnp.dot(dec_a(a_hi_ref), b_hi, preferred_element_type=jnp.float32))
    else:
        a = dec_a(a_ref)
        b = b_ref[...]
        if isinstance(b_fmt, PositFmt):
            b = posit_decode(b, b_fmt.nbits, es_ref[1]).astype(compute_dtype)
        else:
            b = b.astype(compute_dtype)
        acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _emit():
        r = acc_ref[...]
        # fused epilogue: act(acc + bias) + residual, all in f32 in VMEM
        if has_bias:
            r = r + bias_ref[...].astype(jnp.float32)
        r = _apply_activation(r, activation)
        if has_residual:
            r = r + res_ref[...].astype(jnp.float32)
        if isinstance(out_fmt, PositFmt):
            o_ref[...] = posit_encode(r, out_fmt.nbits, es_ref[2])
        else:
            o_ref[...] = r.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "a_fmt", "b_fmt", "out_fmt", "block_m", "block_n", "block_k",
        "compute_dtype_name", "activation", "interpret", "b_packed",
        "codec_impl",
    ),
)
def posit_gemm(
    a: jax.Array,
    b: jax.Array,
    es: jax.Array,  # (3,) int32: es for a, b, out (ignored for float slots)
    *,
    a_fmt: Fmt,
    b_fmt: Fmt,
    out_fmt: Fmt,
    bias: Optional[jax.Array] = None,      # (N,) f32
    residual: Optional[jax.Array] = None,  # (M, N) float
    activation: str = "none",
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    compute_dtype_name: Optional[str] = None,
    interpret: bool = False,
    b_packed: bool = False,
    codec_impl: str = "bits",
) -> jax.Array:
    """O = epilogue(decode(A) @ decode(B)), encoded per out_fmt.

    A: (M, K), B: (K, N); epilogue = ``act(acc + bias) + residual`` fused
    into the last k step (one kernel launch, one HBM write per layer).

    ``b_packed=True`` takes B as (ceil(K/2), N) uint16 split-K packed p8
    lanes (core/pack.py): half the B words move HBM->VMEM, both lanes decode
    in VMEM (``codec_impl``: "bits" pipeline, or "lut" gather where the
    backend tolerates it), and each grid step runs two MXU contractions
    against the matching halves of A.
    """
    M, K = a.shape
    if b_packed:
        if not (isinstance(b_fmt, PositFmt) and b_fmt.nbits == 8):
            raise ValueError(f"b_packed requires p8 b_fmt, got {b_fmt}")
        Kh, N = b.shape
        assert Kh == (K + 1) // 2, (a.shape, b.shape)
    else:
        K2, N = b.shape
        assert K == K2, (a.shape, b.shape)
    if activation not in ACTIVATIONS:
        raise ValueError(f"activation must be one of {ACTIVATIONS}, got {activation!r}")
    if compute_dtype_name is None:
        ca, cb = compute_dtype_for(a_fmt), compute_dtype_for(b_fmt)
        compute_dtype = ca if ca == cb else jnp.float32
    else:
        compute_dtype = jnp.dtype(compute_dtype_name)

    if isinstance(out_fmt, PositFmt):
        out_dtype = jnp.uint8 if out_fmt.nbits == 8 else jnp.uint16
    else:
        out_dtype = out_fmt.dtype

    # Lane/sublane-friendly blocks: bm is a sublane dim for *every* array
    # blocked on it (A, the f32 acc/residual, and the output — whose dtype
    # may be narrower than A's), bk a lane dim for A and sublane for B,
    # bn a lane dim for B/out.
    m_mult = max(sublane(a.dtype), sublane(out_dtype), 8)
    k_mult = max(LANE, sublane(b.dtype))
    bm = round_block(M, block_m, m_mult)
    bn = round_block(N, block_n, LANE)
    if b_packed:
        # grid k runs over the *packed* half-K; A splits into the (lo, hi)
        # halves matching the lanes — two BlockSpecs over the two halves
        bk = round_block(Kh, block_k, k_mult)
        a_lo, a_hi = split_activations(a, Kh)  # odd K: zero col pairs pad lane
        a_lo = pad_to(a_lo, (bm, bk))
        a_hi = pad_to(a_hi, (bm, bk))
        b_p = pad_to(b, (bk, bn))
        Mp, Kp = a_lo.shape
        _, Np = b_p.shape
        grid = (Mp // bm, Np // bn, Kp // bk)
        in_specs = [
            pl.BlockSpec((bm, bk), lambda i, j, k, s: (i, k)),
            pl.BlockSpec((bm, bk), lambda i, j, k, s: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k, s: (k, j)),
        ]
        inputs = [a_lo, a_hi, b_p]
        if codec_impl == "lut":
            # the (4, 256) p8 decode table rides along as a (replicated)
            # kernel input — Pallas kernels cannot close over constants
            in_specs.append(pl.BlockSpec((4, 256), lambda i, j, k, s: (0, 0)))
            inputs.append(jnp.asarray(_p8_decode_table()))
    else:
        bk = round_block(K, block_k, k_mult)
        a_p = pad_to(a, (bm, bk))
        b_p = pad_to(b, (bk, bn))
        Mp, Kp = a_p.shape
        _, Np = b_p.shape
        grid = (Mp // bm, Np // bn, Kp // bk)
        in_specs = [
            pl.BlockSpec((bm, bk), lambda i, j, k, s: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k, s: (k, j)),
        ]
        inputs = [a_p, b_p]
    if bias is not None:
        assert bias.shape == (N,), (bias.shape, N)
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k, s: (0, j)))
        inputs.append(pad_to(bias.astype(jnp.float32)[None, :], (1, bn)))
    if residual is not None:
        assert residual.shape == (M, N), (residual.shape, (M, N))
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, k, s: (i, j)))
        inputs.append(pad_to(residual.astype(jnp.float32), (bm, bn)))

    kernel = functools.partial(
        _gemm_kernel,
        a_fmt=a_fmt, b_fmt=b_fmt, out_fmt=out_fmt,
        compute_dtype=compute_dtype, n_k=grid[2],
        activation=activation, has_bias=bias is not None,
        has_residual=residual is not None,
        b_packed=b_packed, codec_impl=codec_impl,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, s: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(es, jnp.int32), *inputs)
    return out[:M, :N]
