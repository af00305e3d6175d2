"""Exact-accumulation posit GEMM Pallas kernel — the quire dataflow, tiled.

Dataflow per (i, j, k) grid step (PERCIVAL's quire brought to the TPU memory
hierarchy):

    HBM --BlockSpec--> VMEM:  A tile (bm x bk)  posit codes
                              B tile (bk x bn)  posit codes
    VMEM:   [field decoder]   posit -> (sign, scale, significand) int fields
    VPU:    per-k outer product -> signed radix-2^16 digits, lazily
            accumulated into the QUIRE SCRATCH (bm x bn x L+1 int32) which
            persists in VMEM across the whole k-grid (revisited-output pattern)
    VMEM:   [quire readout]   single RNE rounding -> posit codes   (last k)
    VMEM --BlockSpec--> HBM:  O tile (bm x bn)

Unlike the fused codec GEMM this path never touches the MXU: exactness is the
product, not FLOPs — every a[i,k]*b[k,j] lands in the output element's quire
with no intermediate rounding, matching a Fraction-arithmetic oracle
bit-for-bit. Carries are propagated once per k tile, well inside the
``MAX_DEFERRED`` lazy-carry budget (requires block_k <= MAX_DEFERRED).

``es`` for (rs1, rs2, rd) arrives as a scalar-prefetch vector: the quire's
binary-point anchor is es-independent (DESIGN.md §7), so one compiled kernel
serves every es — and even mixed-es operand pairs.

Note on layout: the quire scratch keeps limbs on the *trailing* axis so the
kernel shares digit/readout code with ``repro.core.quire`` verbatim. A
TPU-lane-optimal variant would transpose limbs to the leading axis; interpret
mode and correctness (the contract this kernel is tested against) are
layout-independent.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import LANE, pad_to, round_block, sublane

from repro.core.codec import _decode_fields, _es_u32, posit_encode
from repro.core.dot import ACTIVATIONS, _apply_activation
from repro.core.quire import (
    MAX_DEFERRED, QuireFmt, _product_parts, _scatter, quire_normalize,
    quire_read, quire_read_f32,
)
from repro.core.types import PositFmt


def _quire_gemm_kernel(
    es_ref,  # scalar prefetch: (3,) int32 = es for rs1, rs2, rd
    *refs,
    a_fmt: PositFmt, b_fmt: PositFmt, out_fmt: PositFmt,
    qfmt: QuireFmt, n_k: int, block_k: int,
    activation: str, has_bias: bool, has_residual: bool,
):
    it = iter(refs)
    a_ref, b_ref = next(it), next(it)
    bias_ref = next(it) if has_bias else None
    res_ref = next(it) if has_residual else None
    o_ref, q_ref = next(it), next(it)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        q_ref[...] = jnp.zeros_like(q_ref)

    ea, eb = _es_u32(es_ref[0]), _es_u32(es_ref[1])
    na, sa, ga, za, ra = _decode_fields(a_ref[...], a_fmt.nbits, ea)
    nb, sb, gb, zb, rb = _decode_fields(b_ref[...], b_fmt.nbits, eb)

    def step(kk, q):
        def col(x):
            return lax.dynamic_slice_in_dim(x, kk, 1, axis=1)  # (bm, 1)

        def row(x):
            return lax.dynamic_slice_in_dim(x, kk, 1, axis=0)  # (1, bn)
        parts = _product_parts(
            (col(na), col(sa), col(ga), col(za), col(ra)),
            (row(nb), row(sb), row(gb), row(zb), row(rb)),
            a_fmt.nbits, b_fmt.nbits, qfmt.bias, False)
        return _scatter(q, parts, qfmt.n_limbs)

    q = lax.fori_loop(0, block_k, step, q_ref[...])
    q_ref[...] = quire_normalize(q, qfmt)  # carry budget: one tile of products

    @pl.when(pl.program_id(2) == n_k - 1)
    def _emit():
        if not (has_bias or has_residual or activation != "none"):
            # no epilogue: exact single rounding straight into the posit rd
            o_ref[...] = quire_read(q_ref[...], qfmt,
                                    out_nbits=out_fmt.nbits, es_out=es_ref[2])
            return
        # fused epilogue readout: one exact rounding into f32 (the FPU
        # domain the epilogue computes in), then the output encode —
        # still one launch and one HBM write (DESIGN.md §8)
        r = quire_read_f32(q_ref[...], qfmt)
        if has_bias:
            r = r + bias_ref[...].astype(jnp.float32)
        r = _apply_activation(r, activation)
        if has_residual:
            r = r + res_ref[...].astype(jnp.float32)
        o_ref[...] = posit_encode(r, out_fmt.nbits, es_ref[2])


@functools.partial(
    jax.jit,
    static_argnames=(
        "a_fmt", "b_fmt", "out_fmt", "block_m", "block_n", "block_k",
        "activation", "interpret",
    ),
)
def posit_quire_gemm(
    a: jax.Array,
    b: jax.Array,
    es: jax.Array,  # (3,) int32: es for a, b, out
    *,
    a_fmt: PositFmt,
    b_fmt: PositFmt,
    out_fmt: PositFmt,
    bias: jax.Array = None,      # (N,) f32
    residual: jax.Array = None,  # (M, N) float
    activation: str = "none",
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """O = round_once(sum_k decode(A)[i,k] * decode(B)[k,j]), all-posit slots.

    A: (M, K), B: (K, N) posit codes -> (M, N) posit codes in ``out_fmt``.
    The (bm, bn) quire limbs live in VMEM scratch across the k grid.  With an
    epilogue (bias/activation/residual) the readout is one exact RNE into
    f32, the epilogue applies in-register, and the encode emits — still a
    single launch and HBM write.
    """
    for f in (a_fmt, b_fmt, out_fmt):
        if not isinstance(f, PositFmt):
            raise ValueError(f"quire GEMM requires posit slots, got {f}")
    if activation not in ACTIVATIONS:
        raise ValueError(f"activation must be one of {ACTIVATIONS}, got {activation!r}")
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    qfmt = QuireFmt(max(a_fmt.nbits, b_fmt.nbits))

    out_dtype = jnp.uint8 if out_fmt.nbits == 8 else jnp.uint16
    # lane/sublane-friendly blocks (see posit_gemm): round up + pad, never
    # ragged-shrink; bm must satisfy every array blocked on it (A codes,
    # f32 residual, int32 quire scratch, and the — possibly narrower —
    # output codes).  bk stays within the lazy-carry budget.
    bm = round_block(M, block_m, max(sublane(a.dtype), sublane(out_dtype), 8))
    bn = round_block(N, block_n, LANE)
    bk = round_block(K, block_k, max(LANE, sublane(b.dtype)))
    if bk > MAX_DEFERRED:
        raise ValueError(f"block_k {bk} exceeds lazy-carry budget "
                         f"{MAX_DEFERRED}")
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
        _quire_gemm_kernel,
        a_fmt=a_fmt, b_fmt=b_fmt, out_fmt=out_fmt,
        qfmt=qfmt, n_k=grid[2], block_k=bk,
        activation=activation, has_bias=bias is not None,
        has_residual=residual is not None,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, s: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn, qfmt.limbs_axis), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(es, jnp.int32), *inputs)
    return out[:M, :N]
