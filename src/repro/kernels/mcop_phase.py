"""Pallas TPU kernels for MCOP (paper Algorithms 1–3) — full and fused solvers.

* :func:`lockstep_phases` — the FULL modified Stoer–Wagner for a batch
  of graphs, solved in lockstep.  Its caller, one jitted program per
  (B, n) (``core.mcop._mcop_batch_impl_lockstep``, entered through
  ``core.mcop.mcop_stoer_wagner_kernel``), folds the unoffloadable
  vertices into the anchor in XLA; then one ``pallas_call`` runs every
  phase and every Algorithm-1 merge of a *group* of graphs: each loop
  iteration is one Most-Tightly-Connected-Vertex step of every graph at
  once, so the chain of Σ(n_alive − 1) dependent steps (~3,900 for a
  90-vertex graph) runs once per group, not once per graph, and no step
  is an XLA loop iteration.  A group is as many graphs as VMEM holds (88
  at n = 128: a whole flush), so the group's adjacencies are loaded into
  VMEM once and merged there in place.  This is what the default path
  runs on a TPU for float32 buckets of 64 vertices or more (see
  ``core.mcop._solver_for``), and ``backend="pallas"`` everywhere.

* :func:`mcop_fused_solve_kernel` — one graph's solve at a time
  (:func:`_solve_graph`), preceded in VMEM by the build of its weights
  from its environment row (``backend="pallas_fused"`` of
  ``solve_envs``).

Lockstep layout: per-graph vectors (scores, ``conn``, ``in_a``, ``alive``,
the labels, the weights) are (g, n) tiles with one graph per sublane row,
and per-graph scalars (s, t, the anchor, the best cut) are (g, 1)
columns.  One step, for every graph at once:

    Δ(v)  = conn(v) − [w_local(v) − w_cloud(v)]   over v ∉ A   (g, n)
    v*    = first argmax Δ per row                 (lane max, then min index)
    conn += adj[j, v*_j, :]                        (one-hot row read)

Graphs of different true size in one bucket run to the group's largest
step count; a finished graph is masked, as vmap's while rule masks it.
The row read and the merge are the only per-graph work: a tile of 8
graphs at a time, each graph's (n, n) block masked by a one-hot row
selector and reduced over its rows on the VPU, with no dynamic gather
and no transpose (a row becomes a column through the identity mask
``Σ_j eye[i,j]·v[j]``, which the merge needs once per phase).

``interpret`` defaults to auto-detection (compiled on TPU, interpreter
elsewhere) via ``repro.kernels.ops.default_interpret``; pass an explicit
bool to override.

Padded/dead vertices are encoded ``pinned = 1`` with zero weights and
never selected (their score is −∞); everything stays 2-D (or a leading
batch axis over 2-D tiles) to keep the kernels TPU-lowering-friendly.

Backend selection cheat-sheet (see also ``repro.core.mcop``):

* one graph, need the per-phase trace        → ``mcop_reference`` (numpy)
* many graphs / env sweep                    → ``core.mcop.mcop_batch`` /
  ``solve_envs``: this file's lockstep kernel on a TPU at buckets ≥ 64
  in float32, the vmapped XLA solver otherwise (bucket 16, CPU, x64)
* many environments, weights built in VMEM   → the fused kernel
  (``backend="pallas_fused"``)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import default_interpret

__all__ = [
    "lockstep_phases",
    "lockstep_group",
    "mcop_fused_solve_kernel",
    "default_block_graphs",
    "FUSED_MODEL_KINDS",
]

# f32-representable sentinels matching the solver backends in core.mcop —
# graphs priced in FLOPs/bytes can have cuts far above 2**30, so a small
# sentinel would silently swallow every phase cut.
NEG_INF = -1e30
POS_INF = 1e30

# VMEM bound: adjacency + vectors must fit on-core alongside double-buffers.
_VMEM_BYTES = 12 * 2**20
# n²-sized f32 arrays a solve body keeps live besides its adjacency
# blocks (the index iotas, eye, members, the masks and temporaries of
# the row reads and merges).
_WORK_ARRAYS = 8
# The scoped-VMEM limit the kernels compile against: the budget above
# plus headroom for the vectors, the loop state and Mosaic's own scratch.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_BYTES + 4 * 2**20)


def _blocked_vmem_bytes(n: int, g: int) -> int:
    # every input block is double-buffered by the grid pipeline
    return (2 * g + _WORK_ARRAYS) * n * n * 4


def _resolve_interpret(interpret: bool | None) -> bool:
    return default_interpret() if interpret is None else interpret


# ======================================================================
# One graph's solve in VMEM — the fused kernel's body.
# ======================================================================


def _solve_graph(adj, wl, wc, pin, *, n: int):
    """One graph's full modified Stoer–Wagner, as pure kernel-body math.

    Args are VALUES already resident in VMEM (not refs): ``adj`` (n, n)
    f32, ``wl``/``wc`` (1, n) f32, ``pin`` (1, n) bool.  Returns
    ``(best_cut (1, 1) f32, local_mask (1, n) f32)``.  The fused kernel
    builds each graph's WCG weights in VMEM immediately before calling
    this, a block of graphs back to back per grid step.

    Everything stays a 2-D vector, as Mosaic requires: vertex indices are
    (1, 1) f32 vectors compared against f32 iotas (exact for n < 2**24),
    reductions keep their dims, argmax is a max followed by a min over the
    indices that reach it (first maximum wins, like ``jnp.argmax``), and
    loop-carried masks are 0/1 f32 rather than bool vectors.
    """
    f32 = jnp.float32

    row_i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0).astype(f32)
    col_i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1).astype(f32)
    col1 = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1).astype(f32)
    eye = (row_i == col_i).astype(f32)

    def total(v):
        return jnp.sum(v, axis=1, keepdims=True)  # (1, n) → (1, 1)

    def first_max(v):
        # index of the first maximum of a (1, n) row, as a (1, 1) vector
        hit = v == jnp.max(v, axis=1, keepdims=True)
        return jnp.min(jnp.where(hit, col1, f32(n)), axis=1, keepdims=True)

    def as_col(v):
        # (1, n) → (n, 1) without transpose/reshape: diagonal-mask reduce.
        return jnp.sum(eye * v, axis=1, keepdims=True)

    def as_row(c):
        # (n, 1) → (1, n), same gadget along the other axis.
        return jnp.sum(eye * c, axis=0, keepdims=True)

    def row_of(mat, v_idx):
        return jnp.sum(
            mat * (row_i == v_idx).astype(f32), axis=0, keepdims=True
        )  # (1, n)

    ctot = total(wl)  # C_local — invariant under merging

    # ---- fold all pinned vertices into the anchor (Algorithm 2 step 1) --
    pin_f = pin.astype(f32)
    any_p = jnp.max(pin_f, axis=1, keepdims=True) > 0.5
    src0 = jnp.where(any_p, first_max(pin_f), 0.0)              # (1, 1)
    others = pin & (col1 != src0)                               # (1, n)
    oth_f = others.astype(f32)
    # Σ of folded rows, as a column (symmetry: row-fold == col-fold).
    fold_col = jnp.sum(adj * oth_f, axis=1, keepdims=True)      # (n, 1)
    fold_row = as_row(fold_col)                                 # (1, n)
    keep_row = 1.0 - oth_f
    keep_col = as_col(keep_row)
    adj = adj * keep_row * keep_col
    s_rows = row_i == src0
    s_cols = col_i == src0
    adj = adj + s_rows.astype(f32) * (fold_row * keep_row)
    adj = adj + s_cols.astype(f32) * (fold_col * keep_col)
    adj = jnp.where(s_rows & s_cols, 0.0, adj)

    srcm = (col1 == src0).astype(f32)                           # (1, n)
    pin_src = total(pin_f * srcm)
    wl_src = total(wl * pin_f) + total(wl * srcm) * (1.0 - pin_src)
    wc_src = total(wc * pin_f) + total(wc * srcm) * (1.0 - pin_src)
    wl = jnp.where(others, 0.0, wl)
    wl = jnp.where(srcm > 0.5, wl_src, wl)
    wc = jnp.where(others, 0.0, wc)
    wc = jnp.where(srcm > 0.5, wc_src, wc)
    alive = 1.0 - oth_f                                         # (1, n) 0/1
    members = jnp.maximum(eye, s_rows.astype(f32) * pin_f)      # (n, n)

    # ---- Algorithm 2: |V|−1 phases, each followed by an Alg.-1 merge ----
    def phase(_, carry):
        adj, wl, wc, alive, members, src, best_cut, best_cloud = carry
        gains = wl - wc
        n_alive = total(alive)
        valid = n_alive >= 2.0

        in_a0 = alive * (col1 == src).astype(f32)
        conn0 = row_of(adj, src)

        def absorb(i, inner):
            in_a, conn, s_reg, t_reg = inner
            cand = (alive > 0.5) & (in_a < 0.5)
            v = first_max(jnp.where(cand, conn - gains, NEG_INF))
            do = (i + 1).astype(f32) < n_alive
            in_a = jnp.where(do, jnp.maximum(in_a, (col1 == v).astype(f32)), in_a)
            conn = jnp.where(do, conn + row_of(adj, v), conn)
            s_reg = jnp.where(do, t_reg, s_reg)
            t_reg = jnp.where(do, v, t_reg)
            return in_a, conn, s_reg, t_reg

        _, _, s_reg, t_reg = jax.lax.fori_loop(
            0, n - 1, absorb, (in_a0, conn0, src, src)
        )

        # Eq. 10 cut-of-the-phase.
        tm_f = (col1 == t_reg).astype(f32)
        t_row = row_of(adj, t_reg)                              # (1, n)
        comm = total(t_row * alive)
        gains_t = total(gains * tm_f)
        cut = jnp.where(valid, ctot - gains_t + comm, POS_INF)

        t_rows = row_i == t_reg
        cloud_t = jnp.sum(members * t_rows.astype(f32), axis=0, keepdims=True)
        improved = valid & (cut < best_cut)
        best_cut = jnp.where(improved, cut, best_cut)
        best_cloud = jnp.where(improved, cloud_t, best_cloud)

        # Algorithm 1: merge t into s (masked, symmetric).
        do_merge = valid & (s_reg != t_reg)
        s_rows_m = row_i == s_reg
        s_cols_m = col_i == s_reg
        t_cols = col_i == t_reg
        adj_m = adj + s_rows_m.astype(f32) * t_row
        adj_m = adj_m + s_cols_m.astype(f32) * as_col(t_row)
        adj_m = jnp.where(s_rows_m & s_cols_m, 0.0, adj_m)
        adj_m = jnp.where(t_rows | t_cols, 0.0, adj_m)
        sm_f = (col1 == s_reg).astype(f32)
        wl_m = jnp.where(tm_f > 0.5, 0.0, wl + sm_f * total(wl * tm_f))
        wc_m = jnp.where(tm_f > 0.5, 0.0, wc + sm_f * total(wc * tm_f))
        members_m = jnp.minimum(members + s_rows_m.astype(f32) * cloud_t, 1.0)
        members_m = jnp.where(t_rows, 0.0, members_m)
        alive_m = alive * (1.0 - tm_f)

        adj = jnp.where(do_merge, adj_m, adj)
        wl = jnp.where(do_merge, wl_m, wl)
        wc = jnp.where(do_merge, wc_m, wc)
        members = jnp.where(do_merge, members_m, members)
        alive = jnp.where(do_merge, alive_m, alive)
        src = jnp.where(do_merge & (t_reg == src), s_reg, src)
        return adj, wl, wc, alive, members, src, best_cut, best_cloud

    carry0 = (
        adj, wl, wc, alive, members, src0,
        jnp.full((1, 1), POS_INF, f32), jnp.zeros((1, n), f32),
    )
    out = jax.lax.fori_loop(0, n - 1, phase, carry0)
    best_cut, best_cloud = out[6], out[7]
    return best_cut, 1.0 - best_cloud


# ======================================================================
# Lockstep full solver — every graph of a group advances one MTCV step per
# loop iteration, so the chain of dependent steps runs once per group.
# ======================================================================


def _lockstep_body(
    nmax_ref,   # SMEM (groups,) i32: most alive vertices of any graph per group
    adj_ref,    # (g, m, m) f32 block: adjacencies, pinned folded; merged in place
    wl_ref,     # (g, m) f32 block: w_local, folded
    wc_ref,     # (g, m) f32 block: w_cloud, folded
    alive_ref,  # (g, m) f32 block: 1.0 = vertex alive after the fold
    label_ref,  # (g, m) f32 block: each vertex's representative
    src_ref,    # (g, 1) f32 block: the anchor
    ctot_ref,   # (g, 1) f32 block: C_local, invariant under merging
    cut_ref,    # (g, 1) f32 out: min over phases of Eq. 10
    mask_ref,   # (g, m) f32 out: 1.0 = execute locally
    idx_ref,    # VMEM scratch (g, m): a vertex index per graph, along lanes
    s_ref,      # VMEM scratch (g, m): a merge's survivor per graph, along lanes
    row_ref,    # VMEM scratch (g, m): adjacency rows read at idx_ref
    *,
    m: int,
    g: int,
):
    """Algorithm 2's phases for a group of g graphs, in lockstep.

    Per-graph vectors are (g, m) tiles, one graph per sublane row, and
    per-graph scalars (g, 1) columns, so one vector op serves every graph
    of the group.  Each loop iteration is one MTCV step of every graph:
    the masked score, a first-max argmax per row (ties broken as
    ``jnp.argmax`` breaks them) and ``conn += adj[j, v_j, :]``.  A graph
    with fewer alive vertices than the group's largest finishes early and
    is masked, as vmap's while rule masks finished lanes.  The only
    per-graph work is the row read and the Algorithm 1 merge, which visit
    the graphs a tile of 8 at a time: a one-hot mask over the graph's
    (m, m) block reduced over its rows (rows and columns are the same by
    symmetry), so no vertex index ever leaves the vector unit.
    """
    f32 = jnp.float32
    n_alive_max = nmax_ref[pl.program_id(0)]

    col = jax.lax.broadcasted_iota(jnp.int32, (g, m), 1).astype(f32)
    row_i = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0).astype(f32)
    col_i = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1).astype(f32)
    eye = row_i == col_i
    tile_row = jax.lax.broadcasted_iota(jnp.int32, (8, m), 0)

    def total(v):
        return jnp.sum(v, axis=1, keepdims=True)  # (g, m) → (g, 1)

    def pick(v, hit):
        # the one entry of each row where ``hit`` holds (exact: the rest are 0)
        return total(jnp.where(hit, v, 0.0))

    def first_max(v):
        hit = v == jnp.max(v, axis=1, keepdims=True)
        return jnp.min(jnp.where(hit, col, f32(m)), axis=1, keepdims=True)

    def tiles(fn):
        # fn(base) for each tile of 8 graphs base .. base + 7
        def tile(c, carry):
            fn(pl.multiple_of(c * 8, 8))
            return carry

        jax.lax.fori_loop(0, g // 8, tile, 0)

    def read_row(j):
        hit = row_i == idx_ref[pl.ds(j, 1), :]
        return jnp.sum(jnp.where(hit, adj_ref[j], 0.0), axis=0, keepdims=True)

    def read_rows(idx):
        """(g, 1) vertex indices → (g, m) rows ``adj[j, idx_j, :]``."""
        idx_ref[...] = jnp.broadcast_to(idx, (g, m))

        def tile(base):
            rows = jnp.zeros((8, m), f32)
            for r in range(8):
                rows = jnp.where(tile_row == r, read_row(base + r), rows)
            row_ref[pl.ds(base, 8), :] = rows

        tiles(tile)
        return row_ref[...]

    def merge_tile(base):
        for r in range(8):
            merge(base + r)

    def merge(j):
        """Algorithm 1 on graph j: fold t (idx_ref, its row in row_ref)
        into s (s_ref); both are −1 for a graph with no phase left."""
        s = s_ref[pl.ds(j, 1), :]
        t = idx_ref[pl.ds(j, 1), :]
        t_row = row_ref[pl.ds(j, 1), :]
        t_col = jnp.sum(jnp.where(eye, t_row, 0.0), axis=1, keepdims=True)
        s_rows, s_cols = row_i == s, col_i == s
        a = adj_ref[j]
        a = jnp.where(s_rows, a + t_row, a)
        a = jnp.where(s_cols, a + t_col, a)
        a = jnp.where(s_rows & s_cols, 0.0, a)
        adj_ref[j] = jnp.where((row_i == t) | (col_i == t), 0.0, a)

    def phase(p, carry):
        wl, wc, alive, label, src, n_alive, best_cut, best_cloud = carry
        gains = wl - wc
        valid = n_alive >= 2.0
        cand0 = alive > 0.5

        def step(i, inner):
            in_a, conn, s_reg, t_reg = inner
            do = (i + 1).astype(f32) < n_alive
            scores = jnp.where(cand0 & (in_a < 0.5), conn - gains, NEG_INF)
            v = first_max(scores)
            in_a = jnp.where(do & (col == v), 1.0, in_a)
            conn = jnp.where(do, conn + read_rows(v), conn)
            return in_a, conn, jnp.where(do, t_reg, s_reg), jnp.where(do, v, t_reg)

        in_a0 = jnp.where(col == src, alive, 0.0)
        _, _, s_reg, t_reg = jax.lax.fori_loop(
            0, n_alive_max - 1 - p, step, (in_a0, read_rows(src), src, src)
        )

        # Eq. 10 cut-of-the-phase; −1 marks a graph with no phase left
        s_reg = jnp.where(valid, s_reg, -1.0)
        t_reg = jnp.where(valid, t_reg, -1.0)
        t_row = read_rows(t_reg)
        t_hit = col == t_reg
        cut = ctot - pick(gains, t_hit) + total(t_row * alive)
        cloud_t = label == t_reg
        improved = valid & (cut < best_cut)
        best_cut = jnp.where(improved, cut, best_cut)
        best_cloud = jnp.where(improved, cloud_t.astype(f32), best_cloud)

        # Algorithm 1 merge of (s, t)
        s_ref[...] = jnp.broadcast_to(s_reg, (g, m))
        tiles(merge_tile)
        s_hit = col == s_reg
        wl = jnp.where(t_hit, 0.0, jnp.where(s_hit, wl + pick(wl, t_hit), wl))
        wc = jnp.where(t_hit, 0.0, jnp.where(s_hit, wc + pick(wc, t_hit), wc))
        alive = jnp.where(t_hit, 0.0, alive)
        label = jnp.where(cloud_t, s_reg, label)
        src = jnp.where(t_reg == src, s_reg, src)
        n_alive = n_alive - valid.astype(f32)
        return wl, wc, alive, label, src, n_alive, best_cut, best_cloud

    ctot = ctot_ref[...]
    alive0 = alive_ref[...]
    carry0 = (
        wl_ref[...], wc_ref[...], alive0, label_ref[...], src_ref[...],
        total(alive0), jnp.full((g, 1), POS_INF, f32), jnp.zeros((g, m), f32),
    )
    out = jax.lax.fori_loop(0, n_alive_max - 1, phase, carry0)
    cut_ref[...] = out[6]
    mask_ref[...] = 1.0 - out[7]


def lockstep_phases(adj, wl, wc, alive, label, src, ctot, *, group: int, interpret: bool):
    """The phases of B folded graphs, ``group`` graphs per lockstep chain.

    Traced inside the caller's jit (``core.mcop._mcop_batch_impl_lockstep``,
    which folds the pinned vertices first): ``adj`` (B, m, m), ``wl``,
    ``wc``, ``alive`` (1.0 = alive) and ``label`` (B, m), ``src`` and
    ``ctot`` (B,), all float32, B a multiple of ``group``, itself a
    multiple of 8.  Each group's adjacencies are loaded into VMEM once and
    merged there in place.  Returns ``(cuts (B,), masks (B, m) f32)``.
    """
    b, m = wl.shape
    g = group
    assert b % g == 0 and g % 8 == 0, (b, g)
    n_alive = jnp.sum(alive, axis=1).astype(jnp.int32)
    nmax = jnp.max(n_alive.reshape(b // g, g), axis=1)
    vec = pl.BlockSpec((g, m), lambda i, _: (i, 0))
    one = pl.BlockSpec((g, 1), lambda i, _: (i, 0))
    cut, mask = pl.pallas_call(
        functools.partial(_lockstep_body, m=m, g=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b // g,),
            in_specs=[
                pl.BlockSpec((g, m, m), lambda i, _: (i, 0, 0)),
                vec, vec, vec, vec, one, one,
            ],
            out_specs=[one, vec],
            scratch_shapes=[pltpu.VMEM((g, m), jnp.float32) for _ in range(3)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, m), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(nmax, adj, wl, wc, alive, label, src[:, None], ctot[:, None])
    return cut[:, 0], mask


def _block_override() -> int | None:
    """``REPRO_MCOP_BLOCK_GRAPHS``, the graphs-per-block override both
    kernels honour, or ``None`` when unset."""
    import os

    override = os.environ.get("REPRO_MCOP_BLOCK_GRAPHS")
    if override is None:
        return None
    g = int(override)
    if g < 1:
        raise ValueError(f"REPRO_MCOP_BLOCK_GRAPHS must be >= 1, got {g}")
    return g


def default_block_graphs(n: int) -> int:
    """Graphs per lockstep group for an n-vertex bucket.

    A group's chain of dependent steps costs the same whatever the group
    holds, so the group is as large as VMEM allows: whole tiles of 8
    graphs whose double-buffered adjacency block, with the n²-sized
    temporaries of the row reads and merges, fits the budget (88 graphs
    of 128 vertices, 16 of 256), capped at 256.  Batches smaller than a
    group run as one group of their own size.  ``REPRO_MCOP_BLOCK_GRAPHS``
    overrides it.
    """
    override = _block_override()
    if override is not None:
        return override
    g = 256
    while g > 8 and _blocked_vmem_bytes(n, g) > _VMEM_BYTES:
        g -= 8
    return g


def lockstep_group(b: int, n: int, block_graphs: int | None = None) -> int:
    """The lockstep group for a batch of b n-vertex graphs: ``block_graphs``
    (``None`` = :func:`default_block_graphs`) rounded up to a whole tile
    of 8, and no larger than the batch rounded up to one."""
    g = default_block_graphs(n) if block_graphs is None else int(block_graphs)
    g = min(-(-max(g, 1) // 8) * 8, -(-max(b, 1) // 8) * 8)
    assert _blocked_vmem_bytes(n, g) <= _VMEM_BYTES, (
        f"graph too large for single-core VMEM with kernel working set: "
        f"n={n}, block_graphs={g}"
    )
    return g


def _fused_block_graphs(n: int, interpret: bool) -> int:
    """Graphs per grid step of the fused kernel, which solves them one
    after another: ~2048 "vertex rows" of work per step, at most 8 graphs
    and within the VMEM budget when compiled, 1 in the interpreter (no
    per-step launch cost to amortize).  ``REPRO_MCOP_BLOCK_GRAPHS``
    overrides it."""
    override = _block_override()
    if override is not None:
        return override
    if interpret:
        return 1
    g = max(1, min(8, 2048 // max(n, 1)))
    while g > 1 and _blocked_vmem_bytes(n, g) > _VMEM_BYTES:
        g //= 2
    return g


def _pad_batch(b: int, g: int) -> int:
    return (-b) % g


# ======================================================================
# Fused build+solve kernel — WCG weights constructed in VMEM, no HBM
# round-trip for the (B, n, n) adjacency batch.
# ======================================================================

# cost-model kinds the in-kernel builder implements (Eqs. 4 / 6 / 8);
# core.mcop maps CostModel instances onto these.
FUSED_MODEL_KINDS = ("time", "energy", "weighted")


def _kernel_weights(kind, omega, t_loc, d_in, d_out, d_in_t, d_out_t, env_row):
    """Eqs. 4/6/8 on VMEM-resident profile tensors, transpose-free.

    ``env_row`` is (1, 6): [bandwidth_up, bandwidth_down, speedup,
    p_compute, p_idle, p_transfer].  Mirrors
    ``repro.core.cost_models.CostModel.batch_weights`` in f32, except the
    symmetrisation uses pre-transposed copies of the data matrices
    (``d_in_t``/``d_out_t``) instead of ``swapaxes`` — plain VPU adds, no
    in-kernel transpose.  Each parameter stays a (1, 1) vector that
    broadcasts, so no vector element is moved to a scalar register.
    Returns ``(wl (1, n), wc (1, n), adj (n, n))``.
    """
    b_up, b_down, speedup, p_c, p_i, p_tr = (
        env_row[:, c : c + 1] for c in range(6)
    )

    # Eq. 1, symmetrised: per_dir + per_dirᵀ via the transposed copies.
    # Two-term association matches _edge_time_batch exactly (per-element
    # float sums are order-sensitive; transposing a division result is
    # bitwise the division of the transposed operand).
    per_dir = d_in / b_up + d_out / b_down
    per_dir_t = d_in_t / b_up + d_out_t / b_down
    adj_t = per_dir + per_dir_t
    wl_t = t_loc                      # (1, n)
    wc_t = t_loc / speedup
    if kind == "time":
        return wl_t, wc_t, adj_t
    wl_e = p_c * t_loc
    wc_e = p_i * wc_t
    adj_e = p_tr * adj_t
    if kind == "energy":
        return wl_e, wc_e, adj_e
    # Eq. 8: ω·T/T_local + (1−ω)·E/E_local, normalised per graph.
    t_norm = jnp.maximum(jnp.sum(wl_t, axis=1, keepdims=True), 1e-30)
    e_norm = jnp.maximum(jnp.sum(wl_e, axis=1, keepdims=True), 1e-30)
    w = jnp.float32(omega)
    return (
        w * wl_t / t_norm + (1 - w) * wl_e / e_norm,
        w * wc_t / t_norm + (1 - w) * wc_e / e_norm,
        w * adj_t / t_norm + (1 - w) * adj_e / e_norm,
    )


def _fused_block_body(
    tl_ref,     # (1, n) f32 — profile t_local, replicated across the grid
    din_ref,    # (n, n) f32 — profile data_in
    dout_ref,   # (n, n) f32 — profile data_out
    dint_ref,   # (n, n) f32 — data_inᵀ (host-pre-transposed)
    doutt_ref,  # (n, n) f32 — data_outᵀ
    pin_ref,    # (1, n) f32 — profile pinned mask (anchor included)
    env_ref,    # (g, 1, 6) f32 — this block's environments
    cut_ref,    # (g, 1, 1) f32 out
    mask_ref,   # (g, 1, n) f32 out
    *,
    n: int,
    g: int,
    kind: str,
    omega: float,
):
    """Build each environment's WCG weights in VMEM, then solve it.

    The profile tensors are loaded once per program invocation and reused
    for all g graphs; only the (g, 1, 6) environment rows vary — the
    adjacency batch never exists in HBM at all.
    """
    t_loc = tl_ref[...]
    d_in = din_ref[...]
    d_out = dout_ref[...]
    d_in_t = dint_ref[...]
    d_out_t = doutt_ref[...]
    pin = pin_ref[...] > 0.5

    def solve_j(j, carry):
        wl, wc, adj = _kernel_weights(
            kind, omega, t_loc, d_in, d_out, d_in_t, d_out_t, env_ref[j]
        )
        cut, mask = _solve_graph(adj, wl, wc, pin, n=n)
        cut_ref[j] = cut
        mask_ref[j] = mask
        return carry

    jax.lax.fori_loop(0, g, solve_j, 0)


@functools.partial(
    jax.jit, static_argnames=("kind", "omega", "interpret", "block_graphs")
)
def _fused_call(
    t_local, data_in, data_out, pinned, env, *, kind, omega, interpret, block_graphs
):
    k = env.shape[0]
    n = t_local.shape[-1]
    g = block_graphs
    assert k % g == 0, (k, g)
    body = functools.partial(
        _fused_block_body, n=n, g=g, kind=kind, omega=omega
    )
    rep_row = pl.BlockSpec((1, n), lambda i: (0, 0))
    rep2 = pl.BlockSpec((n, n), lambda i: (0, 0))
    cut, mask = pl.pallas_call(
        body,
        grid=(k // g,),
        in_specs=[
            rep_row,
            rep2,
            rep2,
            rep2,
            rep2,
            rep_row,
            pl.BlockSpec((g, 1, 6), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((g, 1, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((g, 1, n), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((k, 1, n), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(
        t_local.reshape(1, n),
        data_in,
        data_out,
        data_in.T,
        data_out.T,
        pinned.reshape(1, n).astype(jnp.float32),
        env[:, None, :],
    )
    return cut[:, 0, 0], mask[:, 0, :] > 0.5


def mcop_fused_solve_kernel(
    t_local: jnp.ndarray,   # (n,) f32 — profile local execution times
    data_in: jnp.ndarray,   # (n, n) f32 — profile transfer-in bytes
    data_out: jnp.ndarray,  # (n, n) f32 — profile transfer-out bytes
    pinned: jnp.ndarray,    # (n,) bool/f32 — profile unoffloadable mask
    env: jnp.ndarray,       # (K, 6) f32 — per-graph environment columns
    *,
    kind: str,
    omega: float = 0.5,
    interpret: bool | None = None,
    block_graphs: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """VMEM-resident fused pipeline: env rows → WCG weights → min cut.

    The XLA-fused ``solve_envs`` path materializes the (K, n, n)
    adjacency batch in HBM between the build and the solve; this kernel
    builds each graph's weights in VMEM immediately before its phases
    run, so the only HBM traffic per graph is 6 environment scalars in
    and (1 + n) result floats out.  ``kind`` is one of
    ``FUSED_MODEL_KINDS`` (Eq. 4 / Eq. 6 / Eq. 8-with-``omega``).
    Returns ``(min_cuts (K,), local_masks (K, n) bool)``.
    """
    if kind not in FUSED_MODEL_KINDS:
        raise ValueError(
            f"unknown fused cost-model kind {kind!r}; expected one of "
            f"{FUSED_MODEL_KINDS}"
        )
    env = jnp.asarray(env, jnp.float32)
    assert env.ndim == 2 and env.shape[1] == 6, f"env must be (K, 6), got {env.shape}"
    k = env.shape[0]
    n = int(t_local.shape[-1])
    interp = _resolve_interpret(interpret)
    g = _fused_block_graphs(n, interp) if block_graphs is None else int(block_graphs)
    g = max(1, min(g, k if k else 1))
    # working set: 4 double-buffered n² profile blocks + the solver arrays
    assert _blocked_vmem_bytes(n, 4) <= _VMEM_BYTES, (
        f"graph too large for single-core VMEM with fused working set: n={n}"
    )
    pad = _pad_batch(k, g)
    if pad:
        env = jnp.concatenate([env, jnp.ones((pad, 6), jnp.float32)])
    cuts, masks = _fused_call(
        jnp.asarray(t_local, jnp.float32),
        jnp.asarray(data_in, jnp.float32),
        jnp.asarray(data_out, jnp.float32),
        jnp.asarray(pinned, jnp.float32),
        env,
        kind=kind,
        omega=float(omega),
        interpret=interp,
        block_graphs=g,
    )
    if pad:
        cuts, masks = cuts[:k], masks[:k]
    return cuts, masks
