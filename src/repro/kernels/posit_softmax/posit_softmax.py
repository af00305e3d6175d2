"""Posit softmax Pallas kernel (paper §IV-C benchmark kernel).

Rows of posit-coded logits stream HBM->VMEM, decode, stable-softmax in f32 on
the VPU, re-encode to posit on the way out. Whole class dim per block (the
paper benchmarks softmax-8..128; serving logits fit VMEM comfortably).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.codec import posit_decode, posit_encode


def _softmax_kernel(es_ref, c_ref, o_ref, *, nbits: int, valid_c: int):
    x = posit_decode(c_ref[...], nbits, es_ref[0])
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    x = jnp.where(col < valid_c, x, -jnp.inf)
    m = jnp.max(x, axis=-1, keepdims=True)
    p = jnp.exp(x - m)
    y = p / jnp.sum(p, axis=-1, keepdims=True)
    o_ref[...] = posit_encode(y, nbits, es_ref[0])


@functools.partial(jax.jit, static_argnames=("nbits", "block_rows", "interpret"))
def posit_softmax_kernel(
    codes: jax.Array, es, *, nbits: int, block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    R, C = codes.shape
    br = min(block_rows, R)
    Rp = -(-R // br) * br
    Cp = -(-C // 128) * 128
    padded = jnp.pad(codes, ((0, Rp - R), (0, Cp - C)))
    out = pl.pallas_call(
        functools.partial(_softmax_kernel, nbits=nbits, valid_c=C),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Rp // br,),
            in_specs=[pl.BlockSpec((br, Cp), lambda i, s: (i, 0))],
            out_specs=pl.BlockSpec((br, Cp), lambda i, s: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((Rp, Cp), codes.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.asarray([es], jnp.int32), padded)
    return out[:R, :C]
