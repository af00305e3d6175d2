"""Plain float32 reference of the dense family: a SwiGLU MLP,
down(silu(gate(h)) * up(h))."""
from __future__ import annotations

import functools

import jax

import weights
from reference import common


@functools.partial(jax.jit, static_argnames=("mj", "quant"))
def mlp(h, lo, hi, layer, mj, quant):
    p = weights.layer_floats(dict(mj), (lo, hi), layer)["mlp"]
    q = lambda w: common.fake_quant(w, quant)  # noqa: E731
    g = jax.nn.silu(common.mm(h, q(p["gate"]["w"])))
    return common.mm(g * common.mm(h, q(p["up"]["w"])), q(p["down"]["w"]))


def logits_at(m, seed, seqs, positions, **kw):
    return common.logits_at(m, seed, seqs, positions, mlp, **kw)
