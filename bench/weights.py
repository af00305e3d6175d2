"""Seeded weights of a configuration, made by the benchmark.

One float32 value per weight is defined by the seed, the layer and the
leaf's name, so the served program and the reference read the same model
without sharing anything the program made: the program's weights are those
floats posit-coded by its own encoder, in one jitted call on the device
(:func:`served_params`); the reference regenerates each layer's floats when
it reaches that layer (:func:`layer_floats`).

Every linear weight is N(0, SIGMA^2) with SIGMA = 0.5, where the posit(8,0)
codes of the served configurations keep 3 to 5 fraction bits (a fan-in
scale, N(0, 1/d) with d in the thousands, would put most weights below
2^-6, posit(8,0)'s smallest magnitude, and serve a different function).
The fan-in scale moves into the RMSNorm gains, 1 / (SIGMA * sqrt(d)), which
stay float32 in the program, so each projection after a norm still
produces unit-variance outputs.  The embedding is N(0, 1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import work

_LEAF_IDS = {"wq": 1, "wk": 2, "wv": 3, "wo": 4, "gate": 5, "up": 6,
             "down": 7, "router": 8, "w_gate": 9, "w_up": 10, "w_down": 11,
             "embed": 12, "lm_head": 13}
_TOP = 1 << 20          # layer index of the leaves outside the blocks


def seed_words(seed: int):
    """(lo, hi) uint32 words of a seed of up to 64 bits."""
    seed = int(seed) % (1 << 64)
    return (jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32(seed >> 32))


def _key(words, layer, leaf: str):
    k = jax.random.key(0)
    k = jax.random.fold_in(k, words[0])
    k = jax.random.fold_in(k, words[1])
    k = jax.random.fold_in(k, layer)
    return jax.random.fold_in(k, _LEAF_IDS[leaf])


SIGMA = 0.5


def _normal(words, layer, leaf, shape, scale=SIGMA):
    return (jax.random.normal(_key(words, layer, leaf), shape, jnp.float32)
            * jnp.float32(scale))


def _gain(d: int):
    return jnp.full((d,), 1.0 / (SIGMA * d ** 0.5), jnp.float32)


def layer_floats(m: dict, words, layer) -> dict:
    """One layer's float32 weights, keyed as the program's param tree."""
    d, f = m["hidden_size"], m["intermediate_size"]
    hq, hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   work.head_dim(m))
    p = {
        "ln1": {"g": _gain(d)},
        "attn": {
            "wq": {"w": _normal(words, layer, "wq", (d, hq * hd))},
            "wk": {"w": _normal(words, layer, "wk", (d, hkv * hd))},
            "wv": {"w": _normal(words, layer, "wv", (d, hkv * hd))},
            "wo": {"w": _normal(words, layer, "wo", (hq * hd, d))},
        },
        "ln2": {"g": _gain(d)},
    }
    if work.is_moe(m):
        E = m["num_experts"]
        p["moe"] = {
            "router": {"w": _normal(words, layer, "router", (d, E))},
            "w_gate": _normal(words, layer, "w_gate", (E, d, f)),
            "w_up": _normal(words, layer, "w_up", (E, d, f)),
            "w_down": _normal(words, layer, "w_down", (E, f, d)),
        }
    else:
        p["mlp"] = {
            "gate": {"w": _normal(words, layer, "gate", (d, f))},
            "up": {"w": _normal(words, layer, "up", (d, f))},
            "down": {"w": _normal(words, layer, "down", (f, d))},
        }
    return p


def top_floats(m: dict, words) -> dict:
    d, V = m["hidden_size"], m["vocab_size"]
    return {
        "embed": {"table": _normal(words, _TOP, "embed", (V, d), 1.0)},
        "final_norm": {"g": _gain(d)},
        "lm_head": {"w": _normal(words, _TOP, "lm_head", (d, V))},
    }


def served_params(m: dict, seed: int, policy, quantize_params):
    """The program's param tree, in the form it serves, from one jitted
    call: each layer's floats are made and coded by ``quantize_params``
    (the program's encoder) inside a ``lax.map``, so the device holds one
    layer's floats at a time beside the codes."""
    def build(lo, hi):
        words = (lo, hi)

        def one(layer):
            return quantize_params({"blocks": layer_floats(m, words, layer)},
                                   policy)["blocks"]

        blocks = jax.lax.map(one, jnp.arange(m["num_hidden_layers"]))
        top = quantize_params(top_floats(m, words), policy)
        return {**top, "blocks": blocks}

    lo, hi = seed_words(seed)
    return jax.jit(build)(lo, hi)
