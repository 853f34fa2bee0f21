"""Mesh-parallel MCOP solve plane — the "solver fleet".

One broker flush produces a bucket's worth of WCG instances; this module
splits that batch across every device of a 1-D ``("solve",)`` mesh (see
``repro.launch.mesh.make_solver_mesh``) with ``shard_map`` and gathers
the cuts/masks back **bit-identically** to the single-device path.  The
parity argument: the batched solvers (``_mcop_batch_jit``'s vmapped
while_loop and the Pallas grid kernel) do strictly per-graph arithmetic —
lane masking in a vmapped while_loop changes which lanes *update*, never
the update math — so regrouping rows across devices cannot perturb a
single bit.  The parity suite enforces this with ``==``, no tolerances.

Placement is round-robin with inert padding:

* the batch is zero-padded to a multiple of the shard count with graphs
  that are all-pinned with zero weights (the anchor fold absorbs them in
  zero phases; their rows are cropped after the gather), so uneven
  bucket populations keep every device busy instead of idling the tail;
* rows are dealt round-robin (row ``i`` → device ``i mod D``) and the
  inverse permutation restores input order on the host — when callers
  sort work by difficulty, consecutive hard rows land on *different*
  devices instead of serializing on one.

Input buffers are donated to the compiled program (``donate_argnums`` on
the batch pytree) except on the CPU backend, where XLA cannot reuse
donated host buffers and would warn on every dispatch.

The fleet's programs carry names of their own, so a device trace tells
them from the single-device ones: ``jit__mcop_fleet_solve`` (the packed
flush) and ``jit__mcop_fleet_fused`` (the fused build+solve).  With a
tracer, a ``solve.shard_pack`` span covers each flush's padding,
permutation and dispatch enqueue on the host.

``XLA_FLAGS=--xla_force_host_platform_device_count=N`` simulates an
N-device fleet on a CPU host — that is how the parity tests
(``tests/test_mcop_shard.py``) exercise this module without a TPU pod.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.launch.mesh import make_solver_mesh
from repro.obs.trace import NULL_SPAN
from repro.runtime.sharding import solve_batch_spec, solver_axis, solver_shards

__all__ = [
    "ShardPlan",
    "shard_plan",
    "default_solver_mesh",
    "resolve_mesh",
    "sharded_dispatch_arrays",
    "sharded_fused_solver",
]


# ----------------------------------------------------------------------
# Mesh resolution
# ----------------------------------------------------------------------


def default_solver_mesh() -> Mesh | None:
    """The fleet this process can see, or ``None`` on a single device.

    ``None`` keeps single-device hosts on the exact historical dispatch
    path (no shard_map wrapper, no permutation) — multi-device hosts get
    the fleet transparently.
    """
    if jax.device_count() <= 1:
        return None
    return make_solver_mesh()


def resolve_mesh(mesh) -> Mesh | None:
    """Normalize the ``mesh=`` argument the solve entry points accept.

    * ``None``  — auto: :func:`default_solver_mesh`.
    * ``False`` — force the single-device path even on a fleet.
    * a ``Mesh`` — use it; a 1-shard mesh collapses to the plain path
      (identical results, and skipping shard_map avoids a pointless
      permutation round-trip).
    """
    if mesh is None:
        return default_solver_mesh()
    if mesh is False:
        return None
    if not isinstance(mesh, Mesh):
        raise TypeError(f"mesh must be a Mesh, None, or False; got {mesh!r}")
    return mesh if solver_shards(mesh) > 1 else None


# ----------------------------------------------------------------------
# Shard plan: padding + round-robin permutation (pure numpy, testable)
# ----------------------------------------------------------------------


class ShardPlan(NamedTuple):
    """How k rows land on a d-shard fleet.

    ``perm`` reorders the padded batch into device-major blocks (device
    s's rows are contiguous), ``inverse`` undoes it after the gather;
    both have length ``k + pad``.
    """

    shards: int
    k: int
    pad: int
    perm: np.ndarray
    inverse: np.ndarray

    @property
    def rows_per_shard(self) -> int:
        return (self.k + self.pad) // self.shards


def shard_plan(k: int, shards: int) -> ShardPlan:
    """Round-robin placement of k rows onto ``shards`` devices.

    Row ``i`` goes to device ``i mod shards``; padding rows (appended at
    the tail, indices ``k .. k+pad-1``) fill the remainder so every
    device receives exactly ``(k + pad) / shards`` rows.
    """
    if k <= 0:
        raise ValueError(f"cannot plan a shard layout for k={k} rows")
    if shards <= 0:
        raise ValueError(f"cannot shard over {shards} devices")
    pad = (-k) % shards
    kp = k + pad
    perm = np.argsort(np.arange(kp) % shards, kind="stable")
    inverse = np.empty(kp, dtype=np.int64)
    inverse[perm] = np.arange(kp)
    return ShardPlan(shards=shards, k=k, pad=pad, perm=perm, inverse=inverse)


def _donate(mesh: Mesh) -> bool:
    # XLA's CPU client can't alias donated host buffers (it warns and
    # copies anyway) — donation is a device-memory optimization.
    return next(iter(mesh.devices.flat)).platform != "cpu"


# ----------------------------------------------------------------------
# Sharded raw-array dispatch (mcop_batch / WCGBatch flush path)
# ----------------------------------------------------------------------

# Compiled sharded programs, keyed (mesh, backend, interpret, donate);
# jit specializes per input shape underneath, so bucket size and batch
# never appear in the key.  Mesh is hashable and tiny; a process holds a
# handful of meshes at most, so no LRU pressure here.
_SHARDED_DISPATCH_CACHE: dict = {}


def _sharded_dispatch(mesh: Mesh, backend: str, interpret: bool | None):
    key = (mesh, backend, interpret)
    fn = _SHARDED_DISPATCH_CACHE.get(key)
    if fn is None:
        from repro.core.mcop import _dispatch_arrays  # deferred: cycle

        spec = solve_batch_spec(mesh)

        def _mcop_fleet_solve(adj, wl, wc, pin):
            return _dispatch_arrays(adj, wl, wc, pin, backend, interpret)

        # check_vma=False: the bodies contain while_loop / pallas_call,
        # which shard_map's varying-axes checker cannot see through.
        sharded = jax.shard_map(
            _mcop_fleet_solve,
            mesh=mesh,
            in_specs=(spec, spec, spec, spec),
            out_specs=(spec, spec),
            check_vma=False,
        )
        donate = (0, 1, 2, 3) if _donate(mesh) else ()
        fn = _SHARDED_DISPATCH_CACHE[key] = jax.jit(
            sharded, donate_argnums=donate
        )
    return fn


def _pack_span(tracer, plan: ShardPlan, nbytes: int):
    """``solve.shard_pack`` over a flush's host packing: the inert
    padding, the round-robin permutation and the dispatch enqueue.
    ``k`` true rows, ``pad`` inert rows, ``devices``, and ``bytes`` the
    host bytes packed (the padded, permuted inputs)."""
    if tracer is None:
        return NULL_SPAN
    return tracer.span(
        "solve.shard_pack", k=plan.k, pad=plan.pad, devices=plan.shards,
        bytes=nbytes,
    )


def _emit_shard_spans(tracer, plan: ShardPlan, outputs, *, stage: str):
    """Per-shard spans ``<stage>.shard`` with the device's row count.

    The host blocks on the devices' output buffers one after another, in
    shard order, so each span ends at the host-observed completion of its
    shard and its duration is incremental: only the part of the wait left
    once the shard before it was ready (the first starts as the wait
    does).  A shard that finished before its predecessor reads ~0."""
    if tracer is None:
        return
    cuts = outputs[0]
    shards = getattr(cuts, "addressable_shards", None)
    per_device = list(shards) if shards else []
    for s in range(plan.shards):
        rows = int(np.sum((np.arange(plan.k) % plan.shards) == s))
        with tracer.span(
            f"{stage}.shard", shard=s, devices=plan.shards, rows=rows
        ):
            if s < len(per_device):
                jax.block_until_ready(per_device[s].data)


def sharded_dispatch_arrays(
    adj,
    wl,
    wc,
    pin,
    *,
    mesh: Mesh,
    backend: str,
    interpret: bool | None = None,
    tracer=None,
):
    """Solve a packed ``(k, m[, m])`` bucket across the fleet.

    Drop-in for ``core.mcop._dispatch_arrays`` with a mesh: pads +
    round-robins the rows, runs one shard_map program, and returns
    ``(cuts (k,), masks (k, m))`` in input order, bit-identical to the
    single-device dispatch.  Inputs may be numpy or device arrays; the
    permutation runs on the host (exact), the solve on the mesh.
    """
    adj = np.asarray(adj)
    wl = np.asarray(wl)
    wc = np.asarray(wc)
    pin = np.asarray(pin)
    k, m = wl.shape
    plan = shard_plan(k, solver_shards(mesh))
    row_bytes = sum(a.nbytes for a in (adj, wl, wc, pin)) // k
    with _pack_span(tracer, plan, row_bytes * (k + plan.pad)):
        if plan.pad:
            # inert rows: all-pinned, zero weights/edges — the anchor fold
            # collapses them before any phase runs; cropped after the gather
            adj = np.concatenate([adj, np.zeros((plan.pad, m, m), adj.dtype)])
            wl = np.concatenate([wl, np.zeros((plan.pad, m), wl.dtype)])
            wc = np.concatenate([wc, np.zeros((plan.pad, m), wc.dtype)])
            pin = np.concatenate([pin, np.ones((plan.pad, m), pin.dtype)])
        fn = _sharded_dispatch(mesh, backend, interpret)
        cuts_sh, masks_sh = fn(
            adj[plan.perm], wl[plan.perm], wc[plan.perm], pin[plan.perm]
        )
    from repro.core.mcop import _solve_wait, _solver_name  # deferred: cycle

    with _solve_wait(tracer, _solver_name(backend, m, adj.dtype)):
        _emit_shard_spans(tracer, plan, (cuts_sh, masks_sh), stage="solve")
        cuts_sh, masks_sh = jax.device_get((cuts_sh, masks_sh))
    return cuts_sh[plan.inverse][: plan.k], masks_sh[plan.inverse][: plan.k]


# ----------------------------------------------------------------------
# Sharded fused build+solve (solve_envs flush path)
# ----------------------------------------------------------------------


def sharded_fused_solver(build_solve, mesh: Mesh, env_struct):
    """Wrap an *unjitted* fused build+solve closure for the fleet.

    ``build_solve(t_local, data_in, data_out, pinned, env)`` maps K
    environment rows to ``(cuts (K,), masks (K, m))``; the profile
    tensors are replicated to every device, the environment columns
    (an ``EnvArrays``-style pytree of (k,) leaves, structure given by
    ``env_struct``) are sharded along the solve axis.  Returns a jitted
    callable with the same signature.  Padding/permutation live in
    :func:`sharded_solve_envs_call`, not here — this is the cacheable
    compiled object.
    """
    spec = solve_batch_spec(mesh)
    env_specs = jax.tree_util.tree_unflatten(
        env_struct, [spec] * env_struct.num_leaves
    )
    sharded = jax.shard_map(
        build_solve,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), env_specs),
        out_specs=(spec, spec),
        check_vma=False,
    )

    def _mcop_fleet_fused(t_local, data_in, data_out, pinned, env):
        return sharded(t_local, data_in, data_out, pinned, env)

    # donate the env columns (the per-tick varying buffers); the profile
    # tensors are replicated constants the caller reuses across ticks
    donate = (4,) if _donate(mesh) else ()
    return jax.jit(_mcop_fleet_fused, donate_argnums=donate)


def sharded_solve_envs_call(
    fn,
    t_local,
    data_in,
    data_out,
    pinned,
    env_arrays,
    *,
    mesh: Mesh,
    tracer=None,
    solver: str,
):
    """Run a :func:`sharded_fused_solver` program over K environments.

    Pads the environment columns with rows of 1.0 (a benign environment:
    unit bandwidths/powers/speedup — solved and discarded), round-robins
    rows, dispatches once, and restores input order.  Returns
    ``(cuts (k,), masks (k, m))`` as host arrays, bit-identical to the
    unsharded fused program (row-wise build + per-graph solve).
    """
    cols = [np.asarray(c) for c in env_arrays]
    k = cols[0].shape[0]
    plan = shard_plan(k, solver_shards(mesh))
    row_bytes = sum(c.itemsize for c in cols)
    with _pack_span(tracer, plan, row_bytes * (k + plan.pad)):
        if plan.pad:
            cols = [
                np.concatenate([c, np.ones(plan.pad, c.dtype)]) for c in cols
            ]
        cols = [c[plan.perm] for c in cols]
        env_sh = type(env_arrays)(*cols)
        cuts_sh, masks_sh = fn(t_local, data_in, data_out, pinned, env_sh)
    from repro.core.mcop import _solve_wait  # deferred: cycle

    with _solve_wait(tracer, solver):
        _emit_shard_spans(tracer, plan, (cuts_sh, masks_sh), stage="solve_envs")
        cuts_sh, masks_sh = jax.device_get((cuts_sh, masks_sh))
    return cuts_sh[plan.inverse][: plan.k], masks_sh[plan.inverse][: plan.k]
