"""Compile the chip's main-path programs for a described TPU v5e.

No chip is attached: the TPU compiler builds each program for one chip of a
described ``v5e:2x2`` topology, so it refuses here what it would refuse on
the chip (block tiling, Mosaic-illegal ops, VMEM limits, programs that do
not fit in HBM).  Widths are phi3-mini-3.8b's published decode widths.  A
``tpu_custom_call`` in the compiled text shows that a Pallas kernel lowered
for the chip (interpret mode would leave none).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all import
this file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core.pcsr import TransPolicy
from repro.kernels.posit_attention import ops as attn_ops
from repro.kernels.posit_attention.paged_attention import (
    posit_decode_attention_paged_pallas,
)
from repro.kernels.posit_attention.posit_attention import posit_decode_attention
from repro.kernels.posit_codec.posit_codec import decode_kernel, encode_kernel
from repro.models.layers import quantize_params
from repro.models.registry import build_model

B, H, D, S = 8, 32, 96, 2048           # phi3 decode: 8 slots, MHA 32 x 96
HBM_BYTES = 16 * 1024 ** 3             # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a cache entry cannot be read back without a chip: compile afresh
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _on(sharding, tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                       sharding=sharding),
                        tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("kv_bits,code", [(8, jnp.uint8), (16, jnp.uint16)])
def test_decode_attention_compiles(one_chip, kv_bits, code):
    q = jax.ShapeDtypeStruct((B, H, D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, H, S, D), code, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    es = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = _compile(
        lambda q, k, v, n, e: posit_decode_attention(q, k, v, n, e,
                                                     kv_bits=kv_bits),
        q, kv, kv, lens, es)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv_bits,code", [(8, jnp.uint8), (16, jnp.uint16),
                                          (0, jnp.bfloat16)])
@pytest.mark.parametrize("heads,kv_heads,head_dim",
                         [(32, 32, 96), (16, 16, 128)], ids=["phi3", "olmoe"])
def test_paged_decode_attention_compiles(one_chip, heads, kv_heads, head_dim,
                                         kv_bits, code):
    """The paged kernel alone at phi3's and OLMoE's decode widths over the
    benchmark's pool: 32 slots, pages of 16 tokens, 64 a slot, 1,280."""
    slots, bt, width, blocks = 32, 16, 64, 1280
    q = jax.ShapeDtypeStruct((slots, heads, head_dim), jnp.bfloat16,
                             sharding=one_chip)
    pool = jax.ShapeDtypeStruct((blocks, bt, kv_heads * head_dim), code,
                                sharding=one_chip)
    table = jax.ShapeDtypeStruct((slots, width), jnp.int32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    es = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = _compile(
        lambda q, k, v, t, n, e: posit_decode_attention_paged_pallas(
            q, k, v, t, n, e, kv_bits=kv_bits),
        q, pool, pool, table, lens, es)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("nbits", [8, 16])
@pytest.mark.parametrize("direction", ["decode", "encode"])
def test_codec_kernels_compile(one_chip, direction, nbits):
    es = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    if direction == "decode":
        code = jnp.uint8 if nbits == 8 else jnp.uint16
        x = jax.ShapeDtypeStruct((4096, 3072), code, sharding=one_chip)
        fn = lambda c, e: decode_kernel(c, e, nbits=nbits)  # noqa: E731
    else:
        x = jax.ShapeDtypeStruct((4096, 3072), jnp.float32, sharding=one_chip)
        fn = lambda x, e: encode_kernel(x, e, nbits=nbits)  # noqa: E731
    assert "tpu_custom_call" in _compile(fn, x, es).as_text()


@pytest.mark.parametrize("engine", ["paged", "slot_grid"])
def test_decode_step_compiles(one_chip, monkeypatch, engine):
    """One decode step of a 2-layer phi3 at published widths under
    p8-serve with posit-coded weights, as the serving engines jit it.  Both
    steps decode through a Pallas kernel, what ``attn_impl`` picks on a TPU
    (the test steers that choice, since this process sees the CPU): the
    slot grid's flash-decode kernel, the paged step's paged kernel."""
    monkeypatch.setattr(attn_ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(get_arch("phi3-mini-3.8b"), n_layers=2)
    model = build_model(cfg)
    policy = TransPolicy.from_spec("p8-serve")
    s_max, bt = 544, 16                  # 512-token prompt + 32 generated
    params = jax.eval_shape(lambda k: quantize_params(model.init(k), policy),
                            jax.random.key(0))
    if engine == "paged":
        cache = jax.eval_shape(lambda: model.init_paged_cache(
            B, B * s_max // bt, bt, s_max // bt, policy))
        step = model.decode_step_paged
    else:
        cache = jax.eval_shape(lambda: model.init_cache(B, s_max, policy))
        step = model.decode_step
    tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    compiled = jax.jit(lambda p, t, c: step(p, t, c, policy),
                       donate_argnums=(2,)).lower(
        *_on(one_chip, (params, tok, cache))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_paged_step_temporaries_shrink(one_chip, monkeypatch):
    """phi3's whole paged decode step at the benchmark's geometry (32 layers,
    32 slots, 64 pages of 16 tokens a slot, 1,280 pages) needs fewer
    temporary bytes with the paged kernel than with the one-gather lowering
    (``attn_impl="xla"``, the path every TPU step took before the kernel):
    the de-paged ``(B, Hkv, W*bt, d)`` code views are gone."""
    monkeypatch.setattr(attn_ops, "_on_tpu", lambda: True)
    model = build_model(get_arch("phi3-mini-3.8b"))
    slots, bt, width, blocks = 32, 16, 64, 1280
    temp = {}
    for impl in ("auto", "xla"):
        policy = dataclasses.replace(TransPolicy.from_spec("p8-serve"),
                                     attn_impl=impl)
        params = jax.eval_shape(
            lambda k: quantize_params(model.init(k), policy),
            jax.random.key(0))
        cache = jax.eval_shape(lambda: model.init_paged_cache(
            slots, blocks, bt, width, policy))
        tok = jax.ShapeDtypeStruct((slots,), jnp.int32)
        compiled = jax.jit(
            lambda p, t, c: model.decode_step_paged(p, t, c, policy),
            donate_argnums=(2,)).lower(
            *_on(one_chip, (params, tok, cache))).compile()
        assert ("tpu_custom_call" in compiled.as_text()) == (impl == "auto")
        temp[impl] = compiled.memory_analysis().temp_size_in_bytes
    assert temp["auto"] < temp["xla"], temp
