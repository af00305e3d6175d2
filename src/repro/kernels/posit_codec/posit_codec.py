"""Elementwise posit decode/encode Pallas kernels (VMEM-tiled streaming codec).

These are the standalone conversion "instructions" (paper Table I) at tensor
granularity: used for checkpoint encode/decode, collective payload
(de)compression, and anywhere a fused consumer kernel is not available.

Layout: ops flatten to (rows, 128) lanes — the VPU-native tile — and stream
row-blocks HBM->VMEM->HBM. The codec math itself is the shared
``repro.core.codec`` source (Mosaic-safe: no clz, only shifts/bitcasts).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.codec import posit_decode, posit_encode

_LANES = 128


def _decode_kernel(es_ref, c_ref, o_ref, *, nbits: int):
    o_ref[...] = posit_decode(c_ref[...], nbits, es_ref[0]).astype(o_ref.dtype)


def _encode_kernel(es_ref, x_ref, o_ref, *, nbits: int):
    o_ref[...] = posit_encode(x_ref[...].astype(jnp.float32), nbits, es_ref[0])


def _tile(x: jax.Array, block_rows: int):
    """Flatten to (rows, 128), padded; returns (tiled, orig_size, rows)."""
    size = x.size
    rows = -(-size // _LANES)
    rows_p = -(-rows // block_rows) * block_rows
    flat = jnp.pad(x.reshape(-1), (0, rows_p * _LANES - size))
    return flat.reshape(rows_p, _LANES), size, rows_p


@functools.partial(
    jax.jit, static_argnames=("nbits", "out_dtype_name", "block_rows", "interpret")
)
def decode_kernel(
    codes: jax.Array, es, *, nbits: int, out_dtype_name: str = "float32",
    block_rows: int = 512, interpret: bool = False,
) -> jax.Array:
    """posit codes (any shape) -> float array of the same shape."""
    shape = codes.shape
    tiled, size, rows_p = _tile(codes, block_rows)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, nbits=nbits),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows_p // block_rows,),
            in_specs=[pl.BlockSpec((block_rows, _LANES), lambda i, s: (i, 0))],
            out_specs=pl.BlockSpec((block_rows, _LANES), lambda i, s: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows_p, _LANES), jnp.dtype(out_dtype_name)),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.asarray([es], jnp.int32).reshape(1), tiled)
    return out.reshape(-1)[:size].reshape(shape)


@functools.partial(jax.jit, static_argnames=("nbits", "block_rows", "interpret"))
def encode_kernel(
    x: jax.Array, es, *, nbits: int, block_rows: int = 512, interpret: bool = False,
) -> jax.Array:
    """float array (any shape) -> posit codes of the same shape."""
    shape = x.shape
    tiled, size, rows_p = _tile(x, block_rows)
    out_dtype = jnp.uint8 if nbits == 8 else jnp.uint16
    out = pl.pallas_call(
        functools.partial(_encode_kernel, nbits=nbits),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows_p // block_rows,),
            in_specs=[pl.BlockSpec((block_rows, _LANES), lambda i, s: (i, 0))],
            out_specs=pl.BlockSpec((block_rows, _LANES), lambda i, s: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows_p, _LANES), out_dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.asarray([es], jnp.int32).reshape(1), tiled)
    return out.reshape(-1)[:size].reshape(shape)
