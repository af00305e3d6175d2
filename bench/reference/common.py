"""Plain float32 decoder-only transformer, the part every architecture
shares.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no cache,
no batching tricks.  It follows the published decoder (embedding, pre-norm
layers, residual adds, final RMSNorm, untied output head), with the
departures each configuration file lists; the two halves of each layer are
the configuration's architecture module's (``reference/<name>.py``), which
builds them from the pieces here (RMSNorm, the half-split rotary embedding,
causal attention).  Weights are regenerated from the seed one layer at a
time, so the reference fits beside nothing else on the device.  It imports
nothing of the program.

``quant="int4"`` is the control: every linear weight rounded to 4-bit
integers with one absmax scale per output channel, the precision step below
the 8-bit codes the configurations serve.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import reference
import weights

HI = jax.lax.Precision.HIGHEST
Q_CHUNK = 256


def mm(x, w):
    return jnp.matmul(x, w, precision=HI)


def fake_quant(w, quant):
    """Round ``w`` to the control's precision (per output channel)."""
    if quant is None:
        return w
    if quant != "int4":
        raise ValueError(f"unknown control precision {quant!r}")
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 7.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(w / s), -8, 7) * s


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, base=10000.0):
    """x: (B, T, H, hd) at positions 0..T-1, half-split rotation."""
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (base ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv     # (T, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v):
    """q: (B, T, Hq, hd), k/v: (B, T, Hkv, hd); query blocks of Q_CHUNK."""
    B, T, Hq, hd = q.shape
    g = Hq // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    nc = T // Q_CHUNK
    qb = q.reshape(B, nc, Q_CHUNK, Hq, hd).transpose(1, 0, 2, 3, 4)

    def block(args):
        i, qc = args
        s = jnp.einsum("bqhd,bthd->bhqt", qc, k, precision=HI) * hd ** -0.5
        qp = i * Q_CHUNK + jnp.arange(Q_CHUNK)[:, None]
        s = jnp.where(jnp.arange(T)[None, :] <= qp, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqt,bthd->bqhd", p, v, precision=HI)

    out = jax.lax.map(block, (jnp.arange(nc), qb))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, T, Hq, hd)


weights.declare({"embed": 12, "lm_head": 13})


def top_floats(m: dict, words) -> dict:
    """The embedding, the final norm and the untied output head."""
    d, V = m["hidden_size"], m["vocab_size"]
    return {
        "embed": {"table": weights.normal(words, weights.TOP, "embed",
                                          (V, d), 1.0)},
        "final_norm": {"g": weights.gain(d)},
        "lm_head": {"w": weights.normal(words, weights.TOP, "lm_head",
                                        (d, V))},
    }


@functools.partial(jax.jit, static_argnames=("mj",))
def embed(tokens, lo, hi, mj):
    m = dict(mj)
    return reference.load(m).top_floats(m, (lo, hi))["embed"]["table"][tokens]


@functools.partial(jax.jit, static_argnames=("mj", "quant"))
def head(x, rows, cols, lo, hi, mj, quant):
    """Logits at (rows, cols) of x: (N, V)."""
    m = dict(mj)
    t = reference.load(m).top_floats(m, (lo, hi))
    h = rmsnorm(x[rows, cols], t["final_norm"]["g"], m["rms_norm_eps"])
    return mm(h, fake_quant(t["lm_head"]["w"], quant))


def frozen(m: dict):
    """The published sizes as a hashable static argument."""
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, bool, str))))


def logits_at(m: dict, seed: int, seqs, positions, *, quant=None,
              rows: int = 8, length: int = 0, n_pad: int = 0):
    """Logits of the model at given positions of given sequences.

    ``seqs``: token-id lists; ``positions[i]``: positions of ``seqs[i]``
    whose next-token logits are wanted.  The layers run stack by stack,
    each through its architecture module's ``attn`` and ``mlp``.  The batch
    is padded to ``rows`` sequences of at least ``length`` tokens (a
    multiple of Q_CHUNK) and the positions to at least ``n_pad``, so that a
    cell's every run has the same shapes and compiles once.  Returns an
    (N, vocab) float32 device array, rows in the order of ``positions`` (N
    the number of positions, or ``n_pad``; padded rows repeat the last
    position).
    """
    arch = reference.load(m)
    mj = frozen(m)
    lo, hi = weights.seed_words(seed)
    T = max([length] + [len(s) for s in seqs])
    T = -(-T // Q_CHUNK) * Q_CHUNK
    tokens = np.zeros((max(rows, len(seqs)), T), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    x = embed(jnp.asarray(tokens), lo, hi, mj)
    for stack, layers in arch.stacks(m).items():
        for layer in layers:
            li = jnp.int32(layer)
            x2, h = arch.attn(x, lo, hi, stack, li, mj, quant)
            x = x2 + arch.mlp(h, lo, hi, stack, li, mj, quant)
    rows = np.concatenate([np.full(len(p), i, np.int32)
                           for i, p in enumerate(positions)])
    cols = np.concatenate([np.asarray(p, np.int32) for p in positions])
    pad = max(0, n_pad - len(rows))
    rows, cols = (np.pad(a, (0, pad), mode="edge") for a in (rows, cols))
    return head(x, jnp.asarray(rows), jnp.asarray(cols), lo, hi, mj, quant)
