"""Test fixture: an architecture module that the benchmark takes as a new
file only.  The dense decoder (``reference/dense.py``) with biases on the
q, k and v projections, as Qwen2.5 has them, at any ``head_dim`` (the test
size sets one unequal to hidden_size / num_attention_heads).  The program's
``dense`` family serves both with ``ModelCfg.qkv_bias`` and
``ModelCfg.head_dim``; the biases stay float32 there, as here.

Loaded as ``reference.dense_bias`` once a test adds this directory to the
``reference`` package's path.
"""
from __future__ import annotations

import functools

import jax

import weights
from reference import common, dense
from reference.common import fake_quant, mm, rmsnorm, rope
from reference.dense import (active_params, attn_work,  # noqa: F401
                             head_dim, mlp, page_row_bytes, stacks,
                             top_floats, weights_read)

weights.declare({"bq": 14, "bk": 15, "bv": 16})


def model_fields(m: dict) -> dict:
    return {**dense.model_fields(m), "qkv_bias": True}


def attn_floats(m: dict, words, layer) -> dict:
    a = dense.attn_floats(m, words, layer)
    for w, b in (("wq", "bq"), ("wk", "bk"), ("wv", "bv")):
        a[w]["b"] = weights.normal(words, layer, b, (a[w]["w"].shape[1],))
    return a


def layer_floats(m: dict, words, stack: str, layer) -> dict:
    return {**dense.layer_floats(m, words, stack, layer),
            "attn": attn_floats(m, words, layer)}


@functools.partial(jax.jit, static_argnames=("stack", "mj", "quant"))
def attn(x, lo, hi, stack, layer, mj, quant):
    """Residual attention half of one layer; returns (x + attn, ln2(x+attn))."""
    m = dict(mj)
    a = attn_floats(m, (lo, hi), layer)
    d, hd = m["hidden_size"], head_dim(m)
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    B, T, _ = x.shape
    h = rmsnorm(x, weights.gain(d), m["rms_norm_eps"])

    def proj(w, n):
        y = mm(h, fake_quant(a[w]["w"], quant)) + a[w]["b"]
        return y.reshape(B, T, n, hd)

    q, k, v = proj("wq", hq), proj("wk", hkv), proj("wv", hkv)
    q, k = rope(q, base=m["rope_theta"]), rope(k, base=m["rope_theta"])
    o = common.causal_attention(q, k, v).reshape(B, T, hq * hd)
    x2 = x + mm(o, fake_quant(a["wo"]["w"], quant))
    return x2, rmsnorm(x2, weights.gain(d), m["rms_norm_eps"])
