"""Array-native pricing/telemetry: trace of placements → one fused report.

The paper frames partition quality as a cost/energy *trade-off report*
(§7, Figs. 17–19): for every environment the interesting numbers are the
cost of the chosen placement, the no-offloading baseline, the
full-offloading baseline, and the offloading gain between them.  The
adaptive loop's ``_emit`` used to produce those numbers with three
scalar graph evaluations per event — after PR 4 fused construction and
solving, that per-event host pricing was what dominated a sweep.

This module is the batched sibling: a whole trace of
``(environment, placement)`` pairs is priced in ONE vectorized
evaluation — one ``cost_model.build_batch`` (a single pass of array
arithmetic over the profile tensors) followed by one
:meth:`~repro.core.graph.WCGBatch.price_batch` call.  Results are
collected in a :class:`PriceReport`, a registered JAX pytree of (k,)
arrays, so downstream telemetry/dashboards can consume it without
touching Python objects.

Bit-identity contract: every number in the report equals the scalar
path (``g.total_cost`` + ``baselines.no_offloading`` /
``baselines.full_offloading`` + ``offloading_gain``) *bitwise*, because
host pricing batches are unpadded and both paths reduce in the same
order (see :meth:`repro.core.graph.WCG.total_cost`).  The parity suite
asserts ``==``, not ``approx``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import numpy as np

from repro.core.graph import WCGBatch

__all__ = [
    "PriceReport",
    "price_batch",
    "price_trace",
    "vector_gain",
]


def vector_gain(no_offload: np.ndarray, partial: np.ndarray) -> np.ndarray:
    """Vectorized §7.1 offloading gain: ``1 − partial/no_offload``.

    Matches :func:`repro.core.cost_models.offloading_gain` elementwise
    (a non-positive no-offloading cost yields 0.0, same guard).
    """
    no_offload = np.asarray(no_offload, dtype=np.float64)
    partial = np.asarray(partial, dtype=np.float64)
    out = np.zeros_like(no_offload)
    ok = no_offload > 0
    np.divide(partial, no_offload, out=out, where=ok)
    return np.where(ok, 1.0 - out, 0.0)


@dataclasses.dataclass
class PriceReport:
    """K priced placements as stacked (k,) arrays — the batched event.

    Attributes:
      partial_cost:      (k,) Eq.-2 cost of each placement at its own
                         environment's prices.
      no_offload_cost:   (k,) all-local baseline (paper §7.1).
      full_offload_cost: (k,) everything-offloadable-remote baseline.
      gain:              (k,) offloading gain ``1 − partial/no_offload``.

    A registered pytree (all leaves are arrays), so a report can cross
    ``jax.jit`` boundaries or be device_put for dashboard reduction.
    """

    partial_cost: Any
    no_offload_cost: Any
    full_offload_cost: Any
    gain: Any

    def __len__(self) -> int:
        return int(np.asarray(self.partial_cost).shape[0])

    def row(self, i: int) -> tuple[float, float, float, float]:
        """Scalar view of one trace step: (partial, no_off, full, gain)."""
        return (
            float(self.partial_cost[i]),
            float(self.no_offload_cost[i]),
            float(self.full_offload_cost[i]),
            float(self.gain[i]),
        )


jax.tree_util.register_pytree_node(
    PriceReport,
    lambda r: (
        (r.partial_cost, r.no_offload_cost, r.full_offload_cost, r.gain),
        None,
    ),
    lambda _, ch: PriceReport(*ch),
)


def price_batch(batch: WCGBatch, local_masks: np.ndarray) -> PriceReport:
    """Price K placements against an already-built :class:`WCGBatch`.

    Args:
      batch:       K stacked WCGs (one pricing evaluation regardless of K).
        For bit-identity with the scalar path the batch must be unpadded
        (``m == n``); padded batches are still numerically correct
        (padding contributes exactly 0.0) but may differ from the scalar
        path in the last ulp because numpy's pairwise summation groups
        by row length.
      local_masks: (k, m) bool placements (padding columns True).
    Returns:
      :class:`PriceReport` with (k,) rows in batch order.
    """
    partial, no_off, full = batch.price_batch(local_masks)
    return PriceReport(
        partial_cost=np.asarray(partial, dtype=np.float64),
        no_offload_cost=np.asarray(no_off, dtype=np.float64),
        full_offload_cost=np.asarray(full, dtype=np.float64),
        gain=vector_gain(no_off, partial),
    )


def price_trace(
    profile,
    model,
    trace: Sequence[tuple],
) -> PriceReport:
    """Price a trace of ``(environment, placement-mask)`` pairs in one pass.

    The array-native replacement for looping ``_emit``-style telemetry:
    the K WCGs are constructed by ONE vectorized
    ``model.build_batch`` call (rows bit-identical to the scalar
    ``model.build``) and all 3·K cost numbers come from ONE
    :meth:`~repro.core.graph.WCGBatch.price_batch` evaluation.

    Args:
      profile: :class:`~repro.core.cost_models.AppProfile` shared by the
        whole trace (one application, K environment points).
      model:   :class:`~repro.core.cost_models.CostModel` pricing the
        objective (time / energy / weighted).
      trace:   sequence of ``(Environment, local_mask)`` pairs; each
        mask is (n,) bool over the profile's vertices.
    Returns:
      :class:`PriceReport` with row ``i`` bit-identical to pricing
      ``trace[i]`` through the scalar path.
    """
    trace = list(trace)
    if not trace:
        empty = np.zeros(0, dtype=np.float64)
        return PriceReport(empty, empty.copy(), empty.copy(), empty.copy())
    envs = [env for env, _ in trace]
    masks = np.stack([np.asarray(m, dtype=bool) for _, m in trace])
    if masks.shape != (len(trace), profile.n):
        raise ValueError(
            f"trace masks must be (k, {profile.n}), got {masks.shape}"
        )
    batch = model.build_batch(profile, envs)  # unpadded: m == profile.n
    return price_batch(batch, masks)

