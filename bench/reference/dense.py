"""The dense decoder: grouped-query attention and a SwiGLU MLP in every
layer, one stack (``blocks``), as the program's ``dense`` family serves it.
What an architecture module gives is in ``reference/__init__.py``; ``moe.py``
takes its attention half from here.

Attention: q, k and v projections of the normed input, the rotary embedding
on q and k, causal attention of ``num_attention_heads`` query heads over
``num_key_value_heads`` shared K/V heads, scaled by head_dim^-0.5, and the
output projection.  MLP: down(silu(gate(h)) * up(h)).
"""
from __future__ import annotations

import functools

import jax

import weights
from reference import common
from reference.common import fake_quant, mm, rmsnorm, rope

weights.declare({"wq": 1, "wk": 2, "wv": 3, "wo": 4,
                 "gate": 5, "up": 6, "down": 7})

top_floats = common.top_floats


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def model_fields(m: dict) -> dict:
    return {"n_heads": m["num_attention_heads"],
            "n_kv": m["num_key_value_heads"],
            "head_dim": m.get("head_dim", 0), "qkv_bias": False,
            "rope_base": float(m["rope_theta"]),
            "d_ff": m["intermediate_size"], "n_experts": 0, "top_k": 0}


def page_row_bytes(m: dict) -> int:
    """K and V codes of one token in one layer."""
    return 2 * m["num_key_value_heads"] * head_dim(m)


def stacks(m: dict) -> dict:
    return {"blocks": range(m["num_hidden_layers"])}


# ---------------------------------------------------------------- weights ---

def attn_floats(m: dict, words, layer) -> dict:
    d, hd = m["hidden_size"], head_dim(m)
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    return {
        "wq": {"w": weights.normal(words, layer, "wq", (d, hq * hd))},
        "wk": {"w": weights.normal(words, layer, "wk", (d, hkv * hd))},
        "wv": {"w": weights.normal(words, layer, "wv", (d, hkv * hd))},
        "wo": {"w": weights.normal(words, layer, "wo", (hq * hd, d))},
    }


def mlp_floats(m: dict, words, layer) -> dict:
    d, f = m["hidden_size"], m["intermediate_size"]
    return {
        "gate": {"w": weights.normal(words, layer, "gate", (d, f))},
        "up": {"w": weights.normal(words, layer, "up", (d, f))},
        "down": {"w": weights.normal(words, layer, "down", (f, d))},
    }


def layer_floats(m: dict, words, stack: str, layer) -> dict:
    d = m["hidden_size"]
    return {"ln1": {"g": weights.gain(d)}, "attn": attn_floats(m, words, layer),
            "ln2": {"g": weights.gain(d)}, "mlp": mlp_floats(m, words, layer)}


# ------------------------------------------------------------------- work ---

def attn_params(m: dict) -> int:
    d, hd = m["hidden_size"], head_dim(m)
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d


def active_params(m: dict) -> int:
    """Weights of the layers one token multiplies through."""
    return m["num_hidden_layers"] * (
        attn_params(m) + 3 * m["hidden_size"] * m["intermediate_size"])


def weights_read(m: dict, rows: int) -> int:
    """Weights of the layers a step over ``rows`` tokens reads."""
    return active_params(m)


def attn_work(m: dict, phase: str) -> dict:
    """Each (query, context token) pair costs 2 FLOPs a head-dimension for
    the score and 2 for the value, in every layer, in either phase; every
    layer caches a K and a V row a token."""
    L = m["num_hidden_layers"]
    return {"pair_flops": 4.0 * m["num_attention_heads"] * head_dim(m) * L,
            "cache_row": L * page_row_bytes(m)}


# -------------------------------------------------------------- reference ---

@functools.partial(jax.jit, static_argnames=("stack", "mj", "quant"))
def attn(x, lo, hi, stack, layer, mj, quant):
    """Residual attention half of one layer; returns (x + attn, ln2(x+attn))."""
    m = dict(mj)
    a = attn_floats(m, (lo, hi), layer)
    d, hd = m["hidden_size"], head_dim(m)
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    B, T, _ = x.shape
    h = rmsnorm(x, weights.gain(d), m["rms_norm_eps"])
    q = mm(h, fake_quant(a["wq"]["w"], quant)).reshape(B, T, hq, hd)
    k = mm(h, fake_quant(a["wk"]["w"], quant)).reshape(B, T, hkv, hd)
    v = mm(h, fake_quant(a["wv"]["w"], quant)).reshape(B, T, hkv, hd)
    q, k = rope(q, base=m["rope_theta"]), rope(k, base=m["rope_theta"])
    o = common.causal_attention(q, k, v).reshape(B, T, hq * hd)
    x2 = x + mm(o, fake_quant(a["wo"]["w"], quant))
    return x2, rmsnorm(x2, weights.gain(d), m["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("stack", "mj", "quant"))
def mlp(h, lo, hi, stack, layer, mj, quant):
    p = mlp_floats(dict(mj), (lo, hi), layer)
    q = lambda w: fake_quant(w, quant)  # noqa: E731
    g = jax.nn.silu(mm(h, q(p["gate"]["w"])))
    return mm(g * mm(h, q(p["up"]["w"])), q(p["down"]["w"]))
