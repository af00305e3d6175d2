"""Seeded weights of a configuration, made by the benchmark.

One float32 value per weight is defined by the seed, the layer and the
leaf's id, so the served program and the reference read the same model
without sharing anything the program made: the program's weights are those
floats posit-coded by its own encoder, in one jitted call on the device
(:func:`served_params`); the reference regenerates each layer's floats when
it reaches that layer.  Which leaves a layer has is its architecture
module's (``reference/<name>.py``): it declares their ids here
(:func:`declare`) and makes them with :func:`normal` and :func:`gain`.

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

import reference

_IDS: dict = {}          # leaf name -> id, as the modules declare them
TOP = 1 << 20            # layer index of the leaves outside the stacks
SIGMA = 0.5


def declare(ids: dict) -> dict:
    """Register leaf ids (``{name: id}``) that a key is derived from.  An id
    another leaf holds, or a name held under another id, raises: two leaves
    of one seed and layer would otherwise draw the same values."""
    names, held = dict(_IDS), {i: name for name, i in _IDS.items()}
    for name, i in ids.items():
        if held.get(i, name) != name or names.get(name, i) != i:
            raise ValueError(f"leaf {name!r} id {i}: already "
                             f"{held.get(i, name)!r} id {names.get(name, i)}")
        names[name], held[i] = i, name
    _IDS.update(ids)
    return ids


def seed_words(seed: int):
    """(lo, hi) uint32 words of a seed of up to 64 bits."""
    seed = int(seed) % (1 << 64)
    return (jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32(seed >> 32))


def _key(words, layer, leaf: str):
    k = jax.random.key(0)
    k = jax.random.fold_in(k, words[0])
    k = jax.random.fold_in(k, words[1])
    k = jax.random.fold_in(k, layer)
    return jax.random.fold_in(k, _IDS[leaf])


def normal(words, layer, leaf, shape, scale=SIGMA):
    return (jax.random.normal(_key(words, layer, leaf), shape, jnp.float32)
            * jnp.float32(scale))


def gain(d: int):
    return jnp.full((d,), 1.0 / (SIGMA * d ** 0.5), jnp.float32)


def served_params(m: dict, seed: int, policy, quantize_params):
    """The program's param tree, in the form it serves, from one jitted
    call: each layer's floats are made and coded by ``quantize_params``
    (the program's encoder) inside a ``lax.map`` over its stack, so the
    device holds one layer's floats at a time beside the codes."""
    arch = reference.load(m)

    def build(lo, hi):
        words = (lo, hi)
        stacks = {}
        for name, layers in arch.stacks(m).items():
            def one(layer, name=name):
                return quantize_params(
                    {name: arch.layer_floats(m, words, name, layer)},
                    policy)[name]

            stacks[name] = jax.lax.map(
                one, jnp.arange(layers.start, layers.stop))
        top = quantize_params(arch.top_floats(m, words), policy)
        return {**top, **stacks}

    lo, hi = seed_words(seed)
    return jax.jit(build)(lo, hi)
