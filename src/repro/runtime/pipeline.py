"""MCOP-driven pipeline execution over the ``pod`` mesh axis.

The placement mapper (repro.core.placement) turns an MCOP partition of the
layer graph into a *contiguous* stage split; this module executes that
split as a GPipe-style pipeline inside ``shard_map``:

* stage parameters are stacked on a leading ``n_stages`` axis and sharded
  ``P("pod")`` — each pod holds exactly its stage's weights;
* activations hop pods with ``jax.lax.ppermute`` (the cut edge of the WCG
  — the paper's `E_cut` — becomes exactly one collective-permute per
  microbatch per boundary, which is what the roofline's collective term
  charges);
* the schedule is the classic ``n_micro + n_stages − 1`` slot ramp; every
  pod computes every slot (SPMD) and validity is masked, so the HLO is
  identical across devices;
* outputs are only real on the last pod and are broadcast back with a
  masked ``psum`` over "pod" — one extra collective, charged to the
  roofline.

The paper's cost model maps 1:1: per-microbatch stage time = node weight
``w(v)`` of the merged stage vertex; the ppermute bytes = cut edge weight
``w(e)·B``; the pipeline bubble = the paper's "idle power while the cloud
computes" energy term (§4.3.2).

Within a stage, tensors stay sharded over ("data", "model") exactly as in
the non-pipelined path — shard_map only manages the "pod" axis; the body
re-enters the auto-sharding world for the other axes via
``jax.shard_map``'s ``check_vma=False`` escape.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["stack_stage_params", "pipeline_apply", "pipeline_spec_for"]


def stack_stage_params(layer_params: Any, n_stages: int) -> Any:
    """(L, …) stacked per-layer params → (n_stages, L/n_stages, …)."""

    def leaf(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])

    return jax.tree_util.tree_map(leaf, layer_params)


def pipeline_spec_for(params_stacked: Any) -> Any:
    """P("pod") on the stage axis for every stacked stage-param leaf."""
    return jax.tree_util.tree_map(lambda _: P("pod"), params_stacked)


def pipeline_apply(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    params_stacked: Any,          # (n_stages, L/S, …) leaves
    x: jnp.ndarray,               # (B, S, d) activations entering stage 0
    *,
    mesh: Mesh,
    n_micro: int,
    axis: str = "pod",
) -> jnp.ndarray:
    """Run ``x`` through the staged blocks as a microbatched pipeline.

    ``stage_fn(stage_params, x_micro) -> y_micro`` must preserve the
    activation shape (it is typically a ``lax.scan`` over the stage's
    layer group).  The batch axis of ``x`` must divide ``n_micro``.
    """
    n_stages = mesh.shape[axis]
    other_axes = tuple(a for a in mesh.axis_names if a != axis)

    # Activations: batch sharded over the remaining data axes, replicated
    # over "pod" (each pod sees the full microbatch stream; only pod 0's
    # copy is semantically the input — SPMD masking handles the rest).
    data_axes = tuple(a for a in ("data",) if a in other_axes)
    x_spec = P(data_axes if data_axes else None)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(pipeline_spec_for(params_stacked), x_spec),
        out_specs=x_spec,
        check_vma=False,
    )
    def run(stage_params, x_local):
        p_local = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        pod = jax.lax.axis_index(axis)
        b = x_local.shape[0]
        assert b % n_micro == 0, (b, n_micro)
        mb = b // n_micro
        micro = x_local.reshape(n_micro, mb, *x_local.shape[1:])

        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]
        n_slots = n_micro + n_stages - 1

        def slot(carry, t):
            in_buf, outs = carry
            my_idx = t - pod
            # stage 0 consumes fresh microbatches; later stages consume
            # whatever arrived over the wire last slot.
            feed_idx = jnp.clip(my_idx, 0, n_micro - 1)
            x_in = jnp.where(pod == 0, micro[feed_idx], in_buf)
            y = stage_fn(p_local, x_in)
            # hop pod i → i+1 (the WCG cut edge)
            in_buf = jax.lax.ppermute(y, axis, fwd_perm)
            # last pod banks its (valid) result
            valid = (my_idx >= 0) & (my_idx < n_micro) & (pod == n_stages - 1)
            write = jnp.where(valid, y, outs[feed_idx])
            outs = jax.lax.dynamic_update_slice(
                outs, write[None], (feed_idx,) + (0,) * y.ndim
            )
            return (in_buf, outs), None

        in_buf0 = jnp.zeros_like(micro[0])
        outs0 = jnp.zeros_like(micro)
        (_, outs), _ = jax.lax.scan(slot, (in_buf0, outs0), jnp.arange(n_slots))

        # results live on the last pod only — masked psum broadcasts them
        outs = jax.lax.psum(
            jnp.where(pod == n_stages - 1, outs, jnp.zeros_like(outs)), axis
        )
        return outs.reshape(b, *x_local.shape[1:])

    return run(params_stacked, x)
