"""Production mesh builders.

Functions, not module-level constants — importing this module never
touches jax device state (the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import; everything else sees the real device count).
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh

__all__ = [
    "make_production_mesh",
    "make_local_mesh",
    "make_solver_mesh",
    "POD_CHIPS",
]

POD_CHIPS = 256  # one v5e pod = 16×16


def _mk(shape, axes) -> Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 single pod, or 2×16×16 across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_local_mesh(*, data: int | None = None, model: int = 1) -> Mesh:
    """Mesh over whatever devices actually exist (tests / examples)."""
    n = jax.device_count()
    if data is None:
        data = n // model
    assert data * model == n, (data, model, n)
    return _mk((data, model), ("data", "model"))


def make_solver_mesh(devices=None) -> Mesh:
    """1-D mesh over the solver fleet's devices, axis name ``"solve"``.

    The MCOP shard dispatcher (``repro.core.mcop_shard``) splits a tick's
    solve batch along this axis: one shard of graphs per device, gathered
    back bit-identically.  ``devices=None`` takes every device the
    process sees (``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    simulates an N-device fleet on CPU hosts).
    """
    devs = list(jax.devices()) if devices is None else list(devices)
    if not devs:
        raise ValueError("cannot build a solver mesh over zero devices")
    return Mesh(np.array(devs), ("solve",))
