"""The MoE decoder: the dense decoder's attention (``dense.py``) and, in
place of its MLP, a softmax router over ``num_experts``, the top
``num_experts_per_tok`` per token with their weights renormalised to sum to
1 (as the program does; the published OLMoE leaves them unnormalised), and
each chosen expert's SwiGLU MLP.  One stack (``blocks``), as the program's
``moe`` family serves it.  The reference is dropless: every assignment is
computed, whatever the load of an expert."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights
import work
from reference import common, dense
from reference.dense import (attn, attn_params, attn_work,  # noqa: F401
                             page_row_bytes, stacks, top_floats)

weights.declare({"router": 8, "w_gate": 9, "w_up": 10, "w_down": 11})


def model_fields(m: dict) -> dict:
    return {**dense.model_fields(m), "n_experts": m["num_experts"],
            "top_k": m["num_experts_per_tok"]}


# ---------------------------------------------------------------- weights ---

def moe_floats(m: dict, words, layer) -> dict:
    d, f, E = m["hidden_size"], m["intermediate_size"], m["num_experts"]
    return {
        "router": {"w": weights.normal(words, layer, "router", (d, E))},
        "w_gate": weights.normal(words, layer, "w_gate", (E, d, f)),
        "w_up": weights.normal(words, layer, "w_up", (E, d, f)),
        "w_down": weights.normal(words, layer, "w_down", (E, f, d)),
    }


def layer_floats(m: dict, words, stack: str, layer) -> dict:
    d = m["hidden_size"]
    return {"ln1": {"g": weights.gain(d)},
            "attn": dense.attn_floats(m, words, layer),
            "ln2": {"g": weights.gain(d)}, "moe": moe_floats(m, words, layer)}


# ------------------------------------------------------------------- work ---

def _expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def _fixed_params(m: dict) -> int:
    """Weights of one layer that every token uses: attention and router."""
    return attn_params(m) + m["hidden_size"] * m["num_experts"]


def active_params(m: dict) -> int:
    """Weights of the layers one token multiplies through: its top-k
    experts."""
    return m["num_hidden_layers"] * (
        _fixed_params(m) + m["num_experts_per_tok"] * _expert_params(m))


def weights_read(m: dict, rows: int) -> float:
    """Weights of the layers a step over ``rows`` tokens reads: every expert
    the batch's routing can reach (``work.experts_reached``)."""
    return m["num_hidden_layers"] * (
        _fixed_params(m) + work.experts_reached(m, rows) * _expert_params(m))


# -------------------------------------------------------------- reference ---

@functools.partial(jax.jit, static_argnames=("stack", "mj", "quant"))
def route(h, lo, hi, stack, layer, mj, quant):
    m = dict(mj)
    p = moe_floats(m, (lo, hi), layer)
    x = h.reshape(-1, h.shape[-1])
    probs = jax.nn.softmax(
        common.mm(x, common.fake_quant(p["router"]["w"], quant)), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, m["num_experts_per_tok"])
    return top_p / jnp.sum(top_p, axis=-1, keepdims=True), top_e


@functools.partial(jax.jit, static_argnames=("stack", "mj", "quant", "C"))
def experts(h, top_p, top_e, lo, hi, stack, layer, mj, quant, C):
    """Every (token, expert) assignment through its expert; ``C`` is at
    least the largest number of assignments any expert has."""
    m = dict(mj)
    p = moe_floats(m, (lo, hi), layer)
    E, k = m["num_experts"], m["num_experts_per_tok"]
    x = h.reshape(-1, h.shape[-1])
    N = x.shape[0]
    flat_e = top_e.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts
    slot = jnp.zeros_like(flat_e).at[order].set(
        jnp.arange(N * k) - starts[flat_e[order]])
    tok = jnp.repeat(jnp.arange(N), k)
    buf = jnp.zeros((E, C, x.shape[1]), jnp.float32).at[flat_e, slot].set(
        x[tok])
    q = lambda w: common.fake_quant(w, quant)  # noqa: E731
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, q(p["w_gate"]),
                               precision=common.HI))
    u = jnp.einsum("ecd,edf->ecf", buf, q(p["w_up"]), precision=common.HI)
    out = jnp.einsum("ecf,efd->ecd", g * u, q(p["w_down"]),
                     precision=common.HI)
    y = out[flat_e, slot] * top_p.reshape(-1)[:, None]
    return y.reshape(N, k, -1).sum(axis=1).reshape(h.shape)


def mlp(h, lo, hi, stack, layer, mj, quant):
    top_p, top_e = route(h, lo, hi, stack, layer, mj, quant)
    most = int(np.bincount(np.asarray(top_e).ravel()).max())
    C = max(256, 1 << (most - 1).bit_length())
    return experts(h, top_p, top_e, lo, hi, stack, layer, mj, quant, C)
