"""The Pallas paged decode-attention kernel against the off-TPU paths.

``posit_decode_attention_paged_pallas`` runs here in the TPU interpret mode
(DMAs, semaphores and scalar prefetch interpreted; scratch memory starts as
NaN, so a stale buffer read shows).  Every case is one batch that holds the
contract's corners at once: an empty row, a one-token row, a row ending on
a page boundary, a full ``W*bt`` row, a row whose first page another row
also reads (a prefix hit), a row whose last page is recycled and holds NaR
codes past its length, sentinel table entries (``N`` and beyond) past each
row's pages, and NaR in every page no row reaches.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.core.codec import posit_encode
from repro.kernels.posit_attention import ops as attn_ops
from repro.kernels.posit_attention.paged_attention import (
    posit_decode_attention_paged_pallas,
)
from repro.kernels.posit_attention.ref import posit_decode_attention_ref

BT, W, N = 16, 4, 24
LENGTHS = [0, 1, BT, W * BT, 37, 23]       # empty, one, page boundary, full, ...
SHAPES = {"phi3": (32, 32, 96), "olmoe": (16, 16, 128), "gqa": (8, 2, 64)}


def _nar(kv_bits):
    return {8: 0x80, 16: 0x8000}.get(kv_bits)


def _batch(shape, kv_bits, seed, q_dtype=jnp.float32, perm=None, es=0,
           pool_dtype=np.float32):
    """q, pools, table and lengths for the corner-case batch above;
    ``pool_dtype`` is the float pool's dtype when ``kv_bits`` is 0."""
    Hq, Hkv, d = shape
    rng = np.random.default_rng(seed)
    B = len(LENGTHS)
    q = jnp.asarray(rng.normal(0, 1, (B, Hq, d)), q_dtype)
    kf = rng.normal(0, 1, (N, BT, Hkv * d)).astype(np.float32)
    vf = rng.normal(0, 1, (N, BT, Hkv * d)).astype(np.float32)
    if kv_bits:
        kp = np.array(posit_encode(jnp.asarray(kf), kv_bits, es))
        vp = np.array(posit_encode(jnp.asarray(vf), kv_bits, es))
    else:
        kp = np.array(jnp.asarray(kf, pool_dtype))
        vp = np.array(jnp.asarray(vf, pool_dtype))
    ids = list(rng.permutation(N)) if perm is None else list(perm)
    table = np.full((B, W), N, np.int32)
    table[:, -1] = N + 5                   # a sentinel beyond N as well
    for b, n in enumerate(LENGTHS):
        for j in range(-(-n // BT)):
            table[b, j] = ids.pop()
    table[4, 0] = table[3, 0]              # prefix hit: row 4 reads row 3's page
    nar = _nar(kv_bits)
    fill = nar if nar is not None else np.nan
    for bid in set(range(N)) - set(int(t) for t in table[table < N].ravel()):
        kp[bid], vp[bid] = fill, fill      # pages no row reaches
    tail = int(table[5, (LENGTHS[5] - 1) // BT])
    kp[tail, LENGTHS[5] % BT:] = fill      # recycled page, past row 5's length
    vp[tail, LENGTHS[5] % BT:] = fill
    return (q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table),
            jnp.asarray(LENGTHS, jnp.int32))


def _pallas(q, kp, vp, table, lengths, kv_bits, es=0):
    return posit_decode_attention_paged_pallas(
        q, kp, vp, table, lengths, es, kv_bits=kv_bits,
        interpret=pltpu.InterpretParams())


def _depaged(pool, table, lengths, d):
    """The rows' ``(B, Hkv, W*bt, d)`` caches, zero past each length."""
    t = np.minimum(np.asarray(table), N - 1)
    x = np.asarray(pool)[t].reshape(t.shape[0], W * BT, -1, d)
    x = np.moveaxis(x, 2, 1).copy()
    for b, n in enumerate(np.asarray(lengths)):
        x[b, :, n:] = 0
    return jnp.asarray(x)


# (kv_bits, es, float pool dtype): p8 and p16 codes, p16 at es 1 (the
# scalar-prefetched es reaches the decoder), an f32 and a bf16 pool
KV = {"8": (8, 0, np.float32), "16": (16, 0, np.float32),
      "16es1": (16, 1, np.float32), "0": (0, 0, np.float32),
      "0bf16": (0, 0, jnp.bfloat16)}


@pytest.mark.parametrize("kv_bits", list(KV), ids=list(KV))
@pytest.mark.parametrize("shape", list(SHAPES), ids=list(SHAPES))
def test_paged_pallas_matches_gather_and_ref(shape, kv_bits):
    """Same output as the one-gather tiled path and the full-softmax
    oracle, at the slot-grid kernel's tolerance; exact zeros for the empty
    row; nothing past a length (NaR, NaN, sentinels) reaches the output."""
    Hq, Hkv, d = SHAPES[shape]
    kv_bits, es, pool_dtype = KV[kv_bits]
    q, kp, vp, table, lengths = _batch(SHAPES[shape], kv_bits, seed=31, es=es,
                                       pool_dtype=pool_dtype)
    assert kp.dtype == {8: jnp.uint8, 16: jnp.uint16}.get(kv_bits, pool_dtype)
    got = np.asarray(_pallas(q, kp, vp, table, lengths, kv_bits, es))
    assert np.isfinite(got).all()
    assert (got[0] == 0).all()
    gather = attn_ops.posit_decode_attention_paged_gather(
        q, kp, vp, table, lengths, es, kv_bits=kv_bits)
    np.testing.assert_allclose(got, np.asarray(gather), rtol=2e-5, atol=2e-5)
    want = posit_decode_attention_ref(
        q, _depaged(kp, table, lengths, d), _depaged(vp, table, lengths, d),
        lengths, es, kv_bits=kv_bits)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", ["phi3", "olmoe"])
def test_paged_pallas_bf16_query(shape):
    """The serving dtype: a bf16 query takes the one-piece bf16 MXU path;
    the output (bf16) is the f32-query output rounded once."""
    q, kp, vp, table, lengths = _batch(SHAPES[shape], 8, seed=32,
                                       q_dtype=jnp.bfloat16)
    got = _pallas(q, kp, vp, table, lengths, 8)
    assert got.dtype == jnp.bfloat16
    want = _pallas(q.astype(jnp.float32), kp, vp, table, lengths, 8)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want.astype(jnp.bfloat16)))


@pytest.mark.parametrize("shape", ["phi3", "olmoe"])
def test_paged_pallas_warm_equals_cold(shape):
    """The same prefix read through different page ids gives identical
    bits: the pool contents move with the table, the output does not."""
    cold = _batch(SHAPES[shape], 8, seed=33, q_dtype=jnp.bfloat16,
                  perm=range(N))
    q, kp, vp, table, lengths = cold
    moved = np.random.default_rng(34).permutation(N)  # page i -> moved[i]
    inv = np.argsort(moved)
    warm_k, warm_v = kp[inv], vp[inv]
    t = np.asarray(table)
    warm_table = jnp.asarray(np.where(t < N, moved[np.minimum(t, N - 1)], t))
    a = np.asarray(_pallas(q, kp, vp, table, lengths, 8))
    b = np.asarray(_pallas(q, warm_k, warm_v, warm_table, lengths, 8))
    np.testing.assert_array_equal(a, b)


def test_paged_dispatch_routes_by_impl(monkeypatch):
    """``impl="auto"`` and ``"kernel"`` take the gather off-TPU (CPU bits
    unchanged) and the kernel on a TPU; ``"xla"`` takes the gather
    everywhere.  On a TPU, pages the kernel cannot take (``bt % 8``) fall
    back to the gather under ``"auto"`` and raise under ``"kernel"``."""
    q, kp, vp, table, lengths = _batch(SHAPES["gqa"], 8, seed=35)
    gather = np.asarray(attn_ops.posit_decode_attention_paged_gather(
        q, kp, vp, table, lengths, 0, kv_bits=8))
    for impl in ("auto", "kernel"):
        off_tpu = attn_ops.posit_decode_attention_paged(
            q, kp, vp, table, lengths, 0, kv_bits=8, impl=impl)
        np.testing.assert_array_equal(np.asarray(off_tpu), gather)
    seen = []
    monkeypatch.setattr(attn_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(attn_ops, "posit_decode_attention_paged_pallas",
                        lambda *a, **k: seen.append("pallas") or a[0])
    for impl in ("auto", "kernel"):
        attn_ops.posit_decode_attention_paged(q, kp, vp, table, lengths, 0,
                                              kv_bits=8, impl=impl)
    xla = attn_ops.posit_decode_attention_paged(
        q, kp, vp, table, lengths, 0, kv_bits=8, impl="xla")
    assert seen == ["pallas", "pallas"]
    np.testing.assert_array_equal(np.asarray(xla), gather)
    # pages of 12 tokens: "auto" takes the gather, "kernel" refuses
    kp12, vp12 = kp[:, :12], vp[:, :12]
    short = jnp.minimum(lengths, 12)
    want = attn_ops.posit_decode_attention_paged_gather(
        q, kp12, vp12, table, short, 0, kv_bits=8)
    auto = attn_ops.posit_decode_attention_paged(
        q, kp12, vp12, table, short, 0, kv_bits=8)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(want))
    with pytest.raises(ValueError, match="multiple of 8"):
        attn_ops.posit_decode_attention_paged(
            q, kp12, vp12, table, short, 0, kv_bits=8, impl="kernel")
    assert seen == ["pallas", "pallas"]
