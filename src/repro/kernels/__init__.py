# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Shared tiling helpers for the Pallas-TPU kernel packages."""
import jax.numpy as _jnp

LANE = 128


def sublane(dtype) -> int:
    """Minimum second-minor tile multiple for a dtype: 8 for 4-byte types,
    16 for 2-byte, 32 for 1-byte (the 32-bytes-per-sublane TPU packing rule)."""
    return {4: 8, 2: 16, 1: 32}[_jnp.dtype(dtype).itemsize]


def round_block(dim: int, block: int, mult: int) -> int:
    """Hardware-friendly block size: cap at the dim, then round the block
    *up* to ``mult`` — small dims get one padded tile, never a ragged one.
    ``mult`` must be the max sublane/lane requirement over every array that
    shares the blocked axis (inputs, residual, output)."""
    eff = min(block, dim)
    return -(-eff // mult) * mult


def pad_to(x, mults: tuple):
    """Zero-pad trailing-partial dims up to multiples (0-codes decode to 0.0
    and contribute nothing to an accumulator or a quire)."""
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        x = _jnp.pad(x, pads)
    return x
