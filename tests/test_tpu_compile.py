"""Ahead-of-time compiles of the MCOP solve path for a described TPU v5e.

Nothing here runs on a chip: each case lowers and compiles a solver for a
``v5e:2x2`` topology that JAX describes without one attached, so a
Mosaic refusal (unaligned tiling, an unsupported primitive, a VMEM
overrun) fails here instead of on the device.  Shapes are
``ShapeDtypeStruct``s carrying shardings; no array is ever placed.

The topology is described inside a module fixture — never at import —
because only one process at a time may load the TPU library.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.core.mcop import _mcop_batch_jit, mcop_stoer_wagner_kernel
from repro.core.mcop_shard import _sharded_dispatch
from repro.kernels.mcop_phase import (
    FUSED_MODEL_KINDS,
    default_block_graphs,
    mcop_fused_solve_kernel,
)
from repro.launch.mesh import make_solver_mesh
from repro.runtime.sharding import solve_batch_spec

BATCH = 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(sharding, *shapes, dtype=jnp.float32):
    return [jax.ShapeDtypeStruct(s, dtype, sharding=sharding) for s in shapes]


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [16, 64, 128, 256])
def test_stoer_wagner_kernel_compiles(one_chip, n):
    adj, wl, wc, pin = _shapes(one_chip, (BATCH, n, n), (BATCH, n), (BATCH, n), (BATCH, n))
    solve = jax.jit(
        lambda a, l, c, p: mcop_stoer_wagner_kernel(a, l, c, p, interpret=False)
    )
    compiled = solve.lower(adj, wl, wc, pin).compile()
    assert _has_kernel(compiled)
    assert default_block_graphs(n) == {16: 256, 64: 256, 128: 88, 256: 16}[n]


@pytest.mark.parametrize("n", [16, 64, 128, 256])
def test_jax_batch_solver_compiles(one_chip, n):
    adj, wl, wc = _shapes(one_chip, (BATCH, n, n), (BATCH, n), (BATCH, n))
    (pin,) = _shapes(one_chip, (BATCH, n), dtype=jnp.bool_)
    compiled = _mcop_batch_jit.lower(adj, wl, wc, pin).compile()
    assert compiled.memory_analysis().output_size_in_bytes >= BATCH * (n + 4)


@pytest.mark.parametrize("kind", FUSED_MODEL_KINDS)
def test_fused_solve_kernel_compiles(one_chip, kind):
    n = 64
    t_local, d_in, d_out, pinned, env = _shapes(
        one_chip, (n,), (n, n), (n, n), (n,), (BATCH, 6)
    )
    solve = jax.jit(
        lambda t, di, do, p, e: mcop_fused_solve_kernel(
            t, di, do, p, e, kind=kind, interpret=False
        )
    )
    assert _has_kernel(solve.lower(t_local, d_in, d_out, pinned, env).compile())


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_four_chip_sharded_flush_compiles(topo, backend):
    """The solver fleet's shard_map flush over a 4-device ``("solve",)``
    mesh: every device gets a quarter of the batch and no collective is
    needed, so none may appear in the program."""
    mesh = make_solver_mesh(topo.devices)
    assert isinstance(mesh, Mesh) and mesh.devices.size == 4
    rows = NamedSharding(mesh, solve_batch_spec(mesh))
    n = 64
    adj, wl, wc = _shapes(rows, (BATCH, n, n), (BATCH, n), (BATCH, n))
    (pin,) = _shapes(rows, (BATCH, n), dtype=jnp.bool_)
    fn = _sharded_dispatch(mesh, backend, False)
    compiled = fn.lower(adj, wl, wc, pin).compile()
    text = compiled.as_text()
    assert _has_kernel(compiled) == (backend == "pallas")
    for collective in ("all-gather", "all-reduce", "collective-permute"):
        assert collective not in text, collective
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < BATCH * n * n * 4  # a quarter of the adjacency each
