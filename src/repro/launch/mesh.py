"""Production mesh construction (pure function — importing this module never
touches jax device state).

Single pod:  (16, 16)      ("data", "model")   = 256 chips (one v5e pod)
Multi pod:   (2, 16, 16)   ("pod", "data", "model") = 512 chips
Production scales the leading "pod" axis (N pods = N x 256 chips); every
sharding rule below only names axes, so the same config runs at any pod count.
"""
from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "run under dryrun.py (it gives the CPU backend 512 devices)")
    return jax.make_mesh(shape, axes, devices=devices[:n])


def batch_axes(mesh) -> tuple:
    """The data-parallel axes (pod included when present)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1
