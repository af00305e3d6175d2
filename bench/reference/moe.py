"""Plain float32 reference of the MoE family: a softmax router over
``num_experts``, the top ``num_experts_per_tok`` per token with their
weights renormalised to sum to 1 (as the program does; the published
OLMoE leaves them unnormalised), and each chosen expert's SwiGLU MLP.
Dropless: every assignment is computed, whatever the load of an expert."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights
from reference import common


@functools.partial(jax.jit, static_argnames=("mj", "quant"))
def route(h, lo, hi, layer, mj, quant):
    m = dict(mj)
    p = weights.layer_floats(m, (lo, hi), layer)["moe"]
    x = h.reshape(-1, h.shape[-1])
    probs = jax.nn.softmax(
        common.mm(x, common.fake_quant(p["router"]["w"], quant)), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, m["num_experts_per_tok"])
    return top_p / jnp.sum(top_p, axis=-1, keepdims=True), top_e


@functools.partial(jax.jit, static_argnames=("mj", "quant", "C"))
def experts(h, top_p, top_e, lo, hi, layer, mj, quant, C):
    """Every (token, expert) assignment through its expert; ``C`` is at
    least the largest number of assignments any expert has."""
    m = dict(mj)
    p = weights.layer_floats(m, (lo, hi), layer)["moe"]
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


def mlp(h, lo, hi, layer, mj, quant):
    top_p, top_e = route(h, lo, hi, layer, mj, quant)
    most = int(np.bincount(np.asarray(top_e).ravel()).max())
    C = max(256, 1 << (most - 1).bit_length())
    return experts(h, top_p, top_e, lo, hi, layer, mj, quant, C)


def logits_at(m, seed, seqs, positions, **kw):
    return common.logits_at(m, seed, seqs, positions, mlp, **kw)
