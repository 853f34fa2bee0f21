"""MCOP — the paper's Min-Cost Offloading Partitioning algorithm (§5).

All implementations share one contract: the minimum over phases of the
paper's Eq. 10 cut value

    C_cut(A−t, t) = C_local − [w_local(t) − w_cloud(t)] + Σ_{v∈A∖t} w(e(t,v))

together with the induced placement (True = execute locally).

Backend-selection story — when each wins:

* :func:`mcop_reference` (``backend="reference"``) — a line-by-line numpy
  transcription of Algorithms 1–3 (Merge / MinCut / MinCutPhase).  It keeps
  a full per-phase trace (induced vertex orderings, cut-of-the-phase
  values, merged memberships) so tests can check the paper's §5.5 case
  study *exactly*, phase by phase.  Use it for a single graph when you
  want the trace, f64 arithmetic, or are debugging; it is the semantic
  oracle everything else is tested against.

* :func:`mcop_batch` (``backend="jax"``, the default) — the throughput
  path, and the one every served flush takes.  Pads a heterogeneous list
  of graphs into static shape *buckets* (default 16/64/128/256 vertices)
  and solves each bucket as ONE device dispatch, with the solver that
  suits its shape, chosen by :func:`_solver_for` from what the code
  observes — the platform, the bucket and the solver dtype:

  - on a TPU, for float32 buckets of ``LOCKSTEP_MIN_BUCKET`` (64)
    vertices or more, ``_mcop_batch_impl_lockstep``: the Pallas kernel of
    ``repro.kernels.mcop_phase`` that solves all of a flush's graphs in
    lockstep, so the chain of Σ(n_alive − 1) dependent MTCV steps (~3,900
    for a 90-vertex graph) runs once per flush inside VMEM;
  - otherwise ``_mcop_batch_jit``, a ``vmap`` of the jitted dense masked
    Stoer–Wagner (XLA ``while`` loops): small buckets, whose chain is at
    most 120 steps (bucket 16), the CPU, and float64 under x64.

  Either way one program per (bucket, batch).  This is what
  ``AdaptiveController.sweep``, the placement tier sweep, the broker and
  the session engine call; a tracer's ``solve.wait`` span names the
  solver that ran (``solver="lockstep"`` or ``"xla"``).

* ``mcop_batch(..., backend="pallas")`` — the lockstep kernel for every
  bucket, on any platform (interpret mode off a TPU: correct but slow).
  Where the default path runs the kernel too, the two backends share one
  failure domain: the broker's circuit breaker (pallas → jax →
  reference) then serves a kernel fault from the numpy reference.

* :func:`mcop_batch` also accepts a :class:`~repro.core.graph.WCGBatch`
  directly — consumers that already hold stacked tensors (the cost
  models' ``build_batch``, the placement tier sweep, the broker's
  per-bucket flush) skip the per-graph Python packing entirely.

* :func:`solve_envs` — the fully fused environment→placement pipeline.
  Builds the K WCGs *and* runs Stoer–Wagner inside ONE jitted program per
  (cost model, shape bucket): the paper's Fig.-1 re-partitioning loop
  under a drifting environment becomes a single device dispatch with six
  scalars per environment crossing the host boundary, instead of K
  Python graph constructions followed by a packed solve.

Padding semantics: padded vertices carry zero weights, zero edges, and
are marked *pinned*, so the anchor fold absorbs them with no effect on
any phase cut; graphs with no unoffloadable vertex are anchored at vertex
0, matching :func:`mcop_reference`.
"""

from __future__ import annotations

import dataclasses
import functools
from collections import OrderedDict
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import WCG, WCGBatch
from repro.obs.trace import NULL_SPAN

__all__ = [
    "PhaseRecord",
    "MCOPResult",
    "mcop_reference",
    "mcop_batch",
    "solve_envs",
    "mcop",
    "DEFAULT_BUCKETS",
]

_NEG_INF = -1e30
_POS_INF = 1e30


@dataclasses.dataclass
class PhaseRecord:
    """Trace of one MinCutPhase run (paper Algorithm 3)."""

    order: list[str]          # induced ordering of current-graph nodes, by label
    s: str                    # second-to-last added
    t: str                    # last added
    cut_value: float          # Eq. 10 cut-of-the-phase
    cloud_members: frozenset  # original vertex indices inside t


@dataclasses.dataclass
class MCOPResult:
    min_cut: float
    local_mask: np.ndarray          # (n,) bool over original vertices
    phases: list[PhaseRecord]
    local_indices: tuple[int, ...] = ()
    cloud_indices: tuple[int, ...] = ()

    def __post_init__(self) -> None:
        mask = np.asarray(self.local_mask, dtype=bool)
        self.local_indices = tuple(int(i) for i in np.nonzero(mask)[0])
        self.cloud_indices = tuple(int(i) for i in np.nonzero(~mask)[0])


# ======================================================================
# Reference implementation — Algorithms 1, 2, 3 verbatim.
# ======================================================================


class _MutableGraph:
    """Dense mutable view used by the reference implementation.

    ``members[i]`` is the set of *original* vertex indices coalesced into
    current vertex ``i``; Algorithm 1's Merge adds edge weights and node
    weight tuples.
    """

    def __init__(self, g: WCG):
        self.adj = g.adj.copy()
        self.w_local = g.w_local.copy()
        self.w_cloud = g.w_cloud.copy()
        self.alive = np.ones(g.n, dtype=bool)
        self.members: list[set[int]] = [{i} for i in range(g.n)]
        self.names = list(g.names)

    @property
    def alive_indices(self) -> np.ndarray:
        return np.nonzero(self.alive)[0]

    def label(self, i: int) -> str:
        return "{" + "".join(sorted(self.names[j] for j in self.members[i])) + "}" \
            if len(self.members[i]) > 1 else self.names[next(iter(self.members[i]))]

    def merge(self, s: int, t: int) -> int:
        """Algorithm 1: fold t into s.  Returns the surviving index (s)."""
        if s == t or not (self.alive[s] and self.alive[t]):
            raise ValueError("merge requires two distinct alive vertices")
        # multiple edges resolved by adding edge weights (Alg. 1, line 4)
        self.adj[s, :] += self.adj[t, :]
        self.adj[:, s] += self.adj[:, t]
        self.adj[s, s] = 0.0
        self.adj[t, :] = 0.0
        self.adj[:, t] = 0.0
        # node weights resolved by adding tuples (Alg. 1, lines 5–7)
        self.w_local[s] += self.w_local[t]
        self.w_cloud[s] += self.w_cloud[t]
        self.w_local[t] = self.w_cloud[t] = 0.0
        self.members[s] |= self.members[t]
        self.members[t] = set()
        self.alive[t] = False
        return s


def _min_cut_phase(
    g: _MutableGraph, start: int, c_local_total: float
) -> tuple[float, int, int, list[str]]:
    """Algorithm 3: one phase.  Returns (cut value, s, t, induced order).

    Grows A from ``start``; at every step absorbs the most tightly
    connected vertex, where tightness is the paper's
    Δ(v) = w(e(A, v)) − [w_local(v) − w_cloud(v)].
    """
    alive = g.alive_indices
    in_a = np.zeros(g.adj.shape[0], dtype=bool)
    in_a[start] = True
    conn = g.adj[start].copy()  # w(e(A, v)) maintained incrementally
    order = [g.label(start)]
    added: list[int] = [start]
    gains = g.w_local - g.w_cloud

    for _ in range(len(alive) - 1):
        # strict '<' in Algorithm 3 line 11 → first maximum wins ties,
        # which reproduces the paper's induced orderings.
        best, best_v = _NEG_INF, -1
        for v in alive:
            if not in_a[v]:
                delta = conn[v] - gains[v]
                if best < delta:
                    best, best_v = delta, v
        in_a[best_v] = True
        conn += g.adj[best_v]
        order.append(g.label(best_v))
        added.append(best_v)

    t = added[-1]
    s = added[-2] if len(added) >= 2 else added[-1]
    # Eq. 10: Σ_{v∈A∖t} w(e(t, v)) is exactly conn over the full graph row.
    comm = float(g.adj[t, g.alive].sum())
    cut = c_local_total - float(gains[t]) + comm
    return cut, s, t, order


def mcop_reference(g: WCG, *, start: int | None = None) -> MCOPResult:
    """Algorithm 2 (MinCut): merge unoffloadables, run |V|−1 phases."""
    work = _MutableGraph(g)
    c_local_total = float(g.w_local.sum())  # invariant under merging

    # Step 1 (§5.1): merge all unoffloadable vertices into the source.
    pinned = np.nonzero(~g.offloadable)[0]
    if pinned.size == 0:
        source = 0 if start is None else start
    else:
        source = int(pinned[0])
        for other in pinned[1:]:
            work.merge(source, int(other))
    if start is not None:
        source = start  # test hook: explicit anchor

    best_cut = _POS_INF
    best_members: frozenset = frozenset()
    phases: list[PhaseRecord] = []

    # Step 2: coarse partitioning, |V|−1 phases (Algorithm 2 lines 6–13).
    while work.alive.sum() > 1:
        cut, s, t, order = _min_cut_phase(work, source, c_local_total)
        phases.append(
            PhaseRecord(
                order=order,
                s=work.label(s),
                t=work.label(t),
                cut_value=cut,
                cloud_members=frozenset(work.members[t]),
            )
        )
        if cut < best_cut:
            best_cut = cut
            best_members = frozenset(work.members[t])
        survivor = work.merge(s, t)
        if t == source:   # keep the anchor alive under merging
            source = survivor

    local_mask = np.ones(g.n, dtype=bool)
    for i in best_members:
        local_mask[i] = False
    return MCOPResult(min_cut=float(best_cut), local_mask=local_mask, phases=phases)


# ======================================================================
# Batched solver — dense masked Stoer–Wagner, static shape buckets, one
# XLA program per bucket.
# ======================================================================

DEFAULT_BUCKETS = (16, 64, 128, 256)


def _fold_pinned(adj, w_local, w_cloud, pinned):
    """Merge every pinned vertex into the first pinned one (masked fold)."""
    n = adj.shape[0]
    any_pinned = jnp.any(pinned)
    src = jnp.where(any_pinned, jnp.argmax(pinned), 0)
    others = pinned & (jnp.arange(n) != src)

    fold_row = (adj * others[:, None]).sum(axis=0)        # Σ rows being folded
    keep = ~others
    adj2 = adj * keep[:, None] * keep[None, :]            # drop folded rows/cols
    add = fold_row * keep
    adj2 = adj2.at[src, :].add(add)
    adj2 = adj2.at[:, src].add(add)
    adj2 = adj2.at[src, src].set(0.0)

    wl = jnp.where(others, 0.0, w_local).at[src].set((w_local * pinned).sum()
                                                     + w_local[src] * (~pinned[src]))
    wc = jnp.where(others, 0.0, w_cloud).at[src].set((w_cloud * pinned).sum()
                                                     + w_cloud[src] * (~pinned[src]))

    return adj2, wl, wc, ~others, src


@jax.jit
def _mcop_batch_impl(adj, w_local, w_cloud, pinned):
    """Single-graph solver, vmapped below.

    Vertices are never physically removed: a merge is a masked row/column
    fold and the inner most-tightly-connected-vertex scan a masked argmax.

    * ``lax.while_loop`` for both the phase loop and the inner MTCV scan —
      JAX's while batching rule masks finished lanes automatically, so
      each graph does exactly Σ(n_alive−1) absorptions instead of (n−1)²
      and padded vertices cost nothing (they are folded into the anchor
      before the first phase).
    * merged-group membership is a per-vertex representative *label*
      (union-find with full path compression: every merge relabels in
      O(n)) instead of an O(n²) boolean membership matrix, which would
      otherwise dominate the while-loop carry at n ≳ 128.
    """
    n = adj.shape[0]
    c_local_total = w_local.sum()
    adj, w_local, w_cloud, alive, src = _fold_pinned(
        adj, w_local, w_cloud, pinned
    )
    idx = jnp.arange(n)
    label = jnp.where(pinned | ~alive, src, idx)

    def phase_body(carry):
        adj, wl, wc, alive, label, src, best_cut, best_cloud = carry
        n_alive = alive.sum()
        gains = wl - wc

        # ---- inner MTCV scan (Algorithm 3), exactly n_alive−1 steps ----
        def acond(inner):
            return inner[0] < n_alive - 1

        def abody(inner):
            i, in_a, conn, s_reg, t_reg = inner
            cand = alive & ~in_a
            scores = jnp.where(cand, conn - gains, _NEG_INF)
            v = jnp.argmax(scores)
            return (i + 1, in_a | (idx == v), conn + adj[v], t_reg, v)

        in_a0 = alive & (idx == src)
        _, _, _, s_reg, t_reg = jax.lax.while_loop(
            acond, abody, (jnp.int32(0), in_a0, adj[src], src, src)
        )

        # ---- Eq. 10 cut-of-the-phase (outer cond guarantees validity) --
        comm = (adj[t_reg] * alive).sum()
        cut = c_local_total - gains[t_reg] + comm
        cloud_t = label == t_reg
        improved = cut < best_cut
        best_cut = jnp.where(improved, cut, best_cut)
        best_cloud = jnp.where(improved, cloud_t, best_cloud)

        # ---- Algorithm 1 merge of (s, t) -------------------------------
        t_row = adj[t_reg]
        adj2 = adj.at[s_reg, :].add(t_row)
        adj2 = adj2.at[:, s_reg].add(t_row)
        adj2 = adj2.at[s_reg, s_reg].set(0.0)
        tmask = idx == t_reg
        adj2 = adj2 * (~tmask[:, None]) * (~tmask[None, :])
        wl2 = wl.at[s_reg].add(wl[t_reg]).at[t_reg].set(0.0)
        wc2 = wc.at[s_reg].add(wc[t_reg]).at[t_reg].set(0.0)
        alive2 = alive & ~tmask
        label2 = jnp.where(cloud_t, s_reg, label)
        src = jnp.where(t_reg == src, s_reg, src)
        return adj2, wl2, wc2, alive2, label2, src, best_cut, best_cloud

    def pcond(carry):
        return carry[3].sum() > 1  # alive count

    carry0 = (
        adj, w_local, w_cloud, alive, label, src,
        jnp.asarray(_POS_INF, adj.dtype), jnp.zeros(n, dtype=bool),
    )
    out = jax.lax.while_loop(pcond, phase_body, carry0)
    best_cut, best_cloud = out[6], out[7]
    return best_cut, ~best_cloud  # local mask


# vmap over the batch-optimized solver; jit caches one executable per
# (bucket_n, batch) shape pair.
_mcop_batch_jit = jax.jit(jax.vmap(_mcop_batch_impl))


@functools.partial(jax.jit, static_argnames=("group", "interpret"))
def _mcop_batch_impl_lockstep(adj, w_local, w_cloud, pinned, *, group, interpret):
    """The lockstep Pallas solve of a (k, m[, m]) bucket, one program per
    (k, m): float32 conversion, inert all-pinned graphs padding the batch
    to whole groups, the pinned fold of :func:`_fold_pinned` (as the XLA
    path folds), ``kernels.mcop_phase.lockstep_phases`` and the crop, all
    inside this jit.  Returns ``(cuts (k,), local masks (k, m) bool)``."""
    from repro.kernels.mcop_phase import lockstep_phases  # deferred: kernel deps

    f32 = jnp.float32
    k, m = w_local.shape
    pad = ((0, (-k) % group), (0, 0))
    adj = jnp.pad(jnp.asarray(adj, f32), (*pad, (0, 0)))
    wl = jnp.pad(jnp.asarray(w_local, f32), pad)
    wc = jnp.pad(jnp.asarray(w_cloud, f32), pad)
    pin = jnp.pad(jnp.asarray(pinned).astype(bool), pad, constant_values=True)
    c_local_total = wl.sum(axis=1)
    adj, wl, wc, alive, src = jax.vmap(_fold_pinned)(adj, wl, wc, pin)
    label = jnp.where(pin | ~alive, src[:, None], jnp.arange(m))
    cuts, masks = lockstep_phases(
        adj, wl, wc, alive.astype(f32), label.astype(f32), src.astype(f32),
        c_local_total, group=group, interpret=interpret,
    )
    return cuts[:k], masks[:k] > 0.5


def mcop_stoer_wagner_kernel(
    adj, w_local, w_cloud, pinned, *, interpret: bool | None = None,
    block_graphs: int | None = None,
):
    """Solve a (B, n, n) batch with the lockstep Pallas kernel (the
    ``"pallas"`` backend, and ``"jax"`` where :func:`_solver_for` picks
    it): :func:`_mcop_batch_impl_lockstep` with groups of
    ``block_graphs`` graphs (``None`` = as many as VMEM holds,
    ``kernels.mcop_phase.lockstep_group``).  Returns ``(min_cuts (B,),
    local_masks (B, n) bool)``: the arithmetic of ``_mcop_batch_impl`` in
    float32 with the same tie-breaking, whatever the grouping.  Padded
    vertices must be pinned with zero weights and zero incident edges.
    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    from repro.kernels.mcop_phase import lockstep_group  # deferred: kernel deps
    from repro.kernels.ops import default_interpret

    assert adj.ndim == 3, f"expected (B, n, n) batch, got {adj.shape}"
    return _mcop_batch_impl_lockstep(
        adj, w_local, w_cloud, pinned,
        group=lockstep_group(adj.shape[0], adj.shape[-1], block_graphs),
        interpret=default_interpret() if interpret is None else interpret,
    )


# The smallest bucket whose chain of dependent MTCV steps (Σ(n_alive − 1):
# at most 120 at bucket 16, at least 2,016 at 64) is worth a Pallas kernel.
LOCKSTEP_MIN_BUCKET = 64


def _solver_for(platform: str, m: int, dtype) -> str:
    """The solver the default path (``backend="jax"``) runs for an
    m-vertex bucket in ``dtype`` on ``platform``: ``"lockstep"`` (the
    Pallas kernel) on a TPU for float32 buckets of ``LOCKSTEP_MIN_BUCKET``
    or more, ``"xla"`` (the vmapped ``_mcop_batch_impl``) otherwise, so
    x64 keeps float64 and small buckets keep their program."""
    lockstep = (
        platform == "tpu"
        and m >= LOCKSTEP_MIN_BUCKET
        and np.dtype(dtype) == np.float32
    )
    return "lockstep" if lockstep else "xla"


def _solver_name(backend: str, m: int, dtype) -> str:
    """What solves a bucket for ``backend``: the ``solver`` attribute of
    ``solve.wait`` and label of the dispatch metrics."""
    if backend == "jax":
        return _solver_for(jax.default_backend(), m, dtype)
    return {"pallas": "lockstep", "pallas_fused": "fused"}.get(backend, backend)


def _bucket_size(n: int, buckets: Sequence[int]) -> int:
    for b in sorted(buckets):
        if n <= b:
            return int(b)
    # beyond the largest bucket: 64-align so stragglers still share programs
    return int(-(-n // 64) * 64)


def _pack_bucket(
    graphs: Sequence[WCG], m: int, dtype
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Zero-pad a bucket of WCGs to m vertices in preallocated batch
    buffers; padding is pinned so the anchor fold absorbs it without
    touching any cut value (see module docstring)."""
    b = len(graphs)
    adj = np.zeros((b, m, m), dtype)
    wl = np.zeros((b, m), dtype)
    wc = np.zeros((b, m), dtype)
    pinned = np.ones((b, m), dtype=bool)
    for i, g in enumerate(graphs):
        n = g.n
        adj[i, :n, :n] = g.adj
        wl[i, :n] = g.w_local
        wc[i, :n] = g.w_cloud
        pinned[i, :n] = ~g.offloadable
        if not pinned[i, :n].any():
            pinned[i, 0] = True  # anchor at vertex 0, matching mcop_reference
    return adj, wl, wc, pinned


def _solver_dtype(backend: str):
    return (
        np.float64
        if backend == "jax" and jax.config.jax_enable_x64
        else np.float32
    )


def _dispatch_arrays(adj, wl, wc, pin, backend: str, interpret: bool | None):
    """One device dispatch over pre-packed (b, m[, m]) tensors."""
    if _solver_name(backend, adj.shape[-1], adj.dtype) == "xla":
        return _mcop_batch_jit(adj, wl, wc, pin)
    return mcop_stoer_wagner_kernel(adj, wl, wc, pin, interpret=interpret)


def _solve_wait(tracer, solver: str):
    """Span over the host's wait for a flush's device results
    (``solve.wait``, a child of the caller's ``stage.solve_flush``): it
    ends once the results are on the host, after the solve program has
    ended on the device.  ``solver`` names what solved it
    (:func:`_solver_name`)."""
    if tracer is None:
        return NULL_SPAN
    return tracer.span("solve.wait", solver=solver)


def _solve_wcg_batch(
    batch: WCGBatch,
    *,
    backend: str,
    interpret: bool | None,
    mesh=None,
    tracer=None,
) -> list[MCOPResult]:
    """Array-native entry: a WCGBatch is already one packed bucket."""
    if backend == "reference":
        return [mcop_reference(g) for g in batch.to_wcgs()]
    if backend not in ("jax", "pallas"):
        raise ValueError(f"unknown MCOP batch backend: {backend!r}")
    dtype = _solver_dtype(backend)
    from repro.core.mcop_shard import resolve_mesh  # deferred: cycle

    use_mesh = resolve_mesh(mesh)
    if use_mesh is not None:
        from repro.core.mcop_shard import sharded_dispatch_arrays

        cuts, masks = sharded_dispatch_arrays(
            np.asarray(batch.adj, dtype),
            np.asarray(batch.w_local, dtype),
            np.asarray(batch.w_cloud, dtype),
            batch.anchored_pinned(),
            mesh=use_mesh,
            backend=backend,
            interpret=interpret,
            tracer=tracer,
        )
    else:
        cuts, masks = _dispatch_arrays(
            jnp.asarray(np.asarray(batch.adj, dtype)),
            jnp.asarray(np.asarray(batch.w_local, dtype)),
            jnp.asarray(np.asarray(batch.w_cloud, dtype)),
            jnp.asarray(batch.anchored_pinned()),
            backend,
            interpret,
        )
        with _solve_wait(tracer, _solver_name(backend, batch.m, dtype)):
            cuts, masks = jax.device_get((cuts, masks))  # one host sync
    return [
        MCOPResult(
            min_cut=float(cuts[i]),
            local_mask=masks[i, : batch.n_valid[i]].copy(),
            phases=[],
        )
        for i in range(batch.k)
    ]


def mcop_batch(
    graphs: Sequence[WCG] | WCGBatch,
    *,
    backend: str = "jax",
    buckets: Sequence[int] = DEFAULT_BUCKETS,
    interpret: bool | None = None,
    mesh=None,
    tracer=None,
) -> list[MCOPResult]:
    """Solve many MCOP instances at once; results in input order.

    Args:
      graphs:   a sequence of :class:`~repro.core.graph.WCG` (arbitrary,
        heterogeneous sizes), or a single
        :class:`~repro.core.graph.WCGBatch` of K graphs padded to one
        static shape ``(k, m[, m])``.
      backend:  ``"jax"`` (the default solver for each bucket's shape,
        :func:`_solver_for`), ``"pallas"`` (the lockstep kernel for every
        bucket), or ``"reference"`` (loops the numpy oracle —
        testing/parity).
      buckets:  static shape buckets; each graph is zero-padded to the
        smallest bucket ≥ its vertex count and each bucket is ONE device
        dispatch.  Ignored for a ``WCGBatch`` (its padded shape *is* the
        bucket).
      interpret: Pallas-only — force interpret (True) / compiled (False)
        mode; ``None`` auto-detects (see ``kernels.ops.default_interpret``
        and the ``REPRO_PALLAS_INTERPRET`` env override).
      mesh:     solver-fleet routing (see ``repro.core.mcop_shard``):
        ``None`` auto-shards each bucket across the devices the process
        sees when there is more than one, ``False`` forces the
        single-device dispatch, a ``Mesh`` shards over exactly that
        fleet.  Results are bit-identical either way.
      tracer:   optional :class:`~repro.obs.trace.Tracer` — the wait for
        each bucket's results is a ``solve.wait`` span (attribute
        ``solver``: ``"lockstep"`` or ``"xla"``); on the sharded
        path a ``solve.shard_pack`` span covers the host's packing before
        it, and the wait holds one ``solve.shard`` span per device (shard
        index, device count, row count).
    Returns:
      ``list[MCOPResult]`` in input order; ``result[i].local_mask`` is
      ``(n_i,)`` bool over graph ``i``'s ORIGINAL vertices (padding
      cropped), True = execute locally.  ``min_cut`` is the Eq.-10
      optimum in solver precision (f64 when x64 is enabled on the jax
      backend, f32 otherwise).

    The WCGBatch form is the array-native path for callers that hold
    stacked tensors already (cost-model ``build_batch`` output, the
    placement tier sweep, the broker's bucket flush): the per-graph
    packing pass (``_pack_bucket``) is skipped entirely.
    """
    if isinstance(graphs, WCGBatch):
        return _solve_wcg_batch(
            graphs, backend=backend, interpret=interpret, mesh=mesh,
            tracer=tracer,
        )
    graphs = list(graphs)
    if backend == "reference":
        return [mcop_reference(g) for g in graphs]
    if backend not in ("jax", "pallas"):
        raise ValueError(f"unknown MCOP batch backend: {backend!r}")
    dtype = _solver_dtype(backend)

    by_bucket: dict[int, list[int]] = {}
    for i, g in enumerate(graphs):
        by_bucket.setdefault(_bucket_size(g.n, buckets), []).append(i)

    from repro.core.mcop_shard import resolve_mesh  # deferred: cycle

    use_mesh = resolve_mesh(mesh)
    results: list[MCOPResult | None] = [None] * len(graphs)
    for m, idxs in sorted(by_bucket.items()):
        packed = _pack_bucket([graphs[i] for i in idxs], m, dtype)
        if use_mesh is not None:
            from repro.core.mcop_shard import sharded_dispatch_arrays

            cuts, masks = sharded_dispatch_arrays(
                *packed,
                mesh=use_mesh,
                backend=backend,
                interpret=interpret,
                tracer=tracer,
            )
        else:
            adj, wl, wc, pin = (jnp.asarray(a) for a in packed)
            cuts, masks = _dispatch_arrays(adj, wl, wc, pin, backend, interpret)
            with _solve_wait(tracer, _solver_name(backend, m, dtype)):
                cuts, masks = jax.device_get((cuts, masks))  # one host sync
        for row, i in enumerate(idxs):
            results[i] = MCOPResult(
                min_cut=float(cuts[row]),
                local_mask=masks[row, : graphs[i].n].copy(),
                phases=[],
            )
    return results  # type: ignore[return-value]


# ======================================================================
# Fused environment→placement pipeline: build + solve, one XLA program.
# ======================================================================

# Compiled build+solve programs, keyed on (model class, model fingerprint,
# backend, interpret).  The fingerprint contract (see CostModel.fingerprint)
# guarantees equal-fingerprint models price identically, so reusing the
# first instance's closure is sound; jit itself re-specializes per input
# shape/dtype, so the bucket size never needs to appear in the key.  LRU
# bounded: a parametric-model sweep (e.g. many WeightedModel omegas) must
# not accumulate compiled executables for the process lifetime.
_FUSED_SOLVERS: OrderedDict = OrderedDict()
_FUSED_SOLVERS_CAP = 64


def _fused_solver(model, backend: str, interpret: bool | None, mesh=None):
    key = (type(model), model.fingerprint, backend, interpret, mesh)
    fn = _FUSED_SOLVERS.get(key)
    if fn is not None:
        _FUSED_SOLVERS.move_to_end(key)
    if fn is None:
        if backend == "pallas_fused":
            # VMEM-resident build+solve: the kernel constructs each
            # environment's WCG weights right before its phase loop runs
            # (no HBM round-trip for the (K, n, n) adjacency batch).
            from repro.kernels.mcop_phase import (
                FUSED_MODEL_KINDS,
                mcop_fused_solve_kernel,
            )

            kind = getattr(model, "name", None)
            if kind not in FUSED_MODEL_KINDS:
                raise ValueError(
                    f"backend='pallas_fused' implements the in-kernel weight "
                    f"build only for cost-model kinds {FUSED_MODEL_KINDS}; "
                    f"got model {model!r} (name={kind!r}) — use "
                    f"backend='pallas' for custom models"
                )
            omega = float(getattr(model, "omega", 0.5))

            def fused(t_local, data_in, data_out, pinned, env):
                env_mat = jnp.stack(list(env), axis=-1)  # EnvArrays → (k, 6)
                return mcop_fused_solve_kernel(
                    t_local, data_in, data_out, pinned, env_mat,
                    kind=kind, omega=omega, interpret=interpret,
                )

        else:

            def fused(t_local, data_in, data_out, pinned, env):
                wl, wc, adj = model.batch_weights(t_local, data_in, data_out, env)
                pin = jnp.broadcast_to(pinned[None, :], wl.shape)
                if _solver_name(backend, adj.shape[-1], adj.dtype) == "xla":
                    return jax.vmap(_mcop_batch_impl)(adj, wl, wc, pin)
                return mcop_stoer_wagner_kernel(adj, wl, wc, pin, interpret=interpret)

        if mesh is None:
            fn = jax.jit(fused)
        else:
            from repro.core.cost_models import EnvArrays
            from repro.core.mcop_shard import sharded_fused_solver

            env_struct = jax.tree_util.tree_structure(EnvArrays(*(0,) * 6))
            fn = sharded_fused_solver(fused, mesh, env_struct)
        _FUSED_SOLVERS[key] = fn
        while len(_FUSED_SOLVERS) > _FUSED_SOLVERS_CAP:
            _FUSED_SOLVERS.popitem(last=False)
    return fn


def solve_envs(
    profile,
    model,
    envs: Sequence,
    *,
    backend: str = "jax",
    buckets: Sequence[int] = DEFAULT_BUCKETS,
    interpret: bool | None = None,
    metrics=None,
    mesh=None,
    tracer=None,
) -> list[MCOPResult]:
    """Fused Fig.-1 pipeline: K environments → K placements, one dispatch.

    Args:
      profile: :class:`~repro.core.cost_models.AppProfile` — the
        environment-independent application description; its ``(n,)`` /
        ``(n, n)`` tensors are zero-padded once to the shape bucket.
      model:   :class:`~repro.core.cost_models.CostModel`; its
        ``batch_weights`` runs INSIDE the jitted program.  Compiled
        programs are cached per ``model.fingerprint`` (equal-fingerprint
        models must price identically).
      envs:    K :class:`~repro.core.cost_models.Environment` points, or
        an :class:`~repro.core.cost_models.EnvArrays` holding them as six
        (k,) columns (the batched session engine's form); six scalars per
        environment are all that crosses the host boundary.
      backend: ``"jax"`` (the default solver for the bucket, as in
        :func:`mcop_batch`) / ``"pallas"`` for the fused program,
        ``"pallas_fused"`` for the VMEM-resident kernel that builds each
        environment's WCG weights in-kernel immediately before its solve
        (built-in cost-model kinds only), or ``"reference"`` to route
        the vectorized host build through the numpy oracle
        (exact-parity testing).
      buckets: static shape buckets for the padded vertex count.
      interpret: Pallas-only interpret/compiled override.
      metrics: optional :class:`~repro.obs.metrics.MetricsRegistry` —
        when given, each call counts one ``solve_envs_dispatches`` and
        times the dispatch into ``solve_envs_duration_s``, both labeled
        ``(backend, bucket, devices, solver)``.  ``None`` (default) adds no work
        and no clock reads.
      mesh:    solver-fleet routing (``repro.core.mcop_shard``):
        ``None`` auto-shards the K environments across every device the
        process sees when there is more than one, ``False`` forces the
        single-device program, a ``Mesh`` shards over exactly that
        fleet.  Sharded results are bit-identical to unsharded.
      tracer:  optional :class:`~repro.obs.trace.Tracer` — the wait for
        the results is a ``solve.wait`` span; on the sharded path a
        ``solve.shard_pack`` span covers the host's packing before it,
        and the wait holds one ``solve_envs.shard`` span per device.
    Returns:
      ``list[MCOPResult]``, one per environment in input order, masks
      ``(n,)`` bool over the profile's vertices.

    ``model.batch_weights`` (WCG construction) and the batched
    Stoer–Wagner solver are jitted into ONE XLA program per (cost model,
    shape bucket) — no per-environment Python ``WCG`` objects, no
    separate packing pass.  Placements match the object path
    ``mcop_batch([model.build(profile, e) for e in envs])`` (asserted by
    the parity suite; note construction happens in the solver dtype
    here, so an *exact* tie between two cuts could in principle resolve
    differently than the build-f64-then-cast object path — equal-cost
    placements either way).
    """
    from repro.core.cost_models import (  # deferred: no import cycle
        EnvArrays,
        validate_env_finite,
    )

    if not isinstance(envs, EnvArrays):
        envs = EnvArrays.from_envs(list(envs))
    k = envs.k
    if k == 0:
        return []
    # corrupted environments must be named here, not silently solved
    # (NaN weights partition into garbage) — see NonFiniteWeightError
    validate_env_finite(envs)
    from repro.core.mcop_shard import resolve_mesh, solver_shards  # deferred

    if backend not in ("reference", "jax", "pallas", "pallas_fused"):
        raise ValueError(f"unknown MCOP batch backend: {backend!r}")
    use_mesh = None if backend == "reference" else resolve_mesh(mesh)
    devices = 1 if use_mesh is None else solver_shards(use_mesh)
    dtype = _solver_dtype(backend)
    n = profile.n
    m = _bucket_size(n, buckets)
    solver = _solver_name(backend, m, dtype)
    if metrics is not None:
        labels = dict(backend=backend, bucket=m, devices=devices, solver=solver)
        metrics.counter("solve_envs_dispatches", **labels).inc()
        timer = metrics.timer("solve_envs_duration_s", **labels)
    else:
        timer = NULL_SPAN
    if backend == "reference":
        with timer:
            return [
                mcop_reference(g)
                for g in model.build_batch(profile, envs).to_wcgs()
            ]

    # Environment-independent profile tensors, zero-padded to the bucket;
    # padding is pinned and a pin-free profile anchors at vertex 0 (the
    # same convention _pack_bucket applies per graph).
    t_local = np.zeros(m, dtype)
    data_in = np.zeros((m, m), dtype)
    data_out = np.zeros((m, m), dtype)
    pinned = np.ones(m, dtype=bool)
    t_local[:n] = profile.t_local
    data_in[:n, :n] = profile.data_in
    data_out[:n, :n] = profile.data_out
    pinned[:n] = ~profile.offloadable
    if not pinned[:n].any():
        pinned[0] = True

    fn = _fused_solver(model, backend, interpret, use_mesh)
    env_cols = (
        envs.astype(dtype)
        if isinstance(envs, EnvArrays)
        else EnvArrays.from_envs(envs, dtype)
    )
    with timer:
        if use_mesh is not None:
            from repro.core.mcop_shard import sharded_solve_envs_call

            cuts, masks = sharded_solve_envs_call(
                fn,
                jnp.asarray(t_local),
                jnp.asarray(data_in),
                jnp.asarray(data_out),
                jnp.asarray(pinned),
                env_cols,
                mesh=use_mesh,
                tracer=tracer,
                solver=solver,
            )
        else:
            cuts, masks = fn(
                jnp.asarray(t_local),
                jnp.asarray(data_in),
                jnp.asarray(data_out),
                jnp.asarray(pinned),
                env_cols,
            )
            with _solve_wait(tracer, solver):
                cuts, masks = jax.device_get((cuts, masks))  # one host sync
    return [
        MCOPResult(min_cut=float(cuts[i]), local_mask=masks[i, :n].copy(), phases=[])
        for i in range(k)
    ]


def mcop(g: WCG, *, backend: str = "reference") -> MCOPResult:
    """Front door used by the rest of the framework.

    Backends: ``"reference"`` (numpy oracle with per-phase trace), and
    ``"jax"`` / ``"pallas"`` (a batch of one through :func:`mcop_batch`).
    For many graphs per call use :func:`mcop_batch`.
    """
    if backend == "reference":
        return mcop_reference(g)
    if backend in ("jax", "pallas"):
        return mcop_batch([g], backend=backend)[0]
    raise ValueError(f"unknown MCOP backend: {backend!r}")
