"""Pallas TPU kernels for MCOP (paper Algorithms 1–3) — full and fused solvers.

Two kernels, one memory story:

* :func:`mcop_stoer_wagner_kernel` — the FULL modified Stoer–Wagner in a
  single kernel invocation, batched over graphs.  All |V|−1 phases, the
  Algorithm-1 merges of (s, t), and the initial fold of unoffloadable
  vertices into the anchor run inside the kernel body, so the adjacency is
  loaded into VMEM exactly once per solve.  A grid dimension over the
  batch lets one ``pallas_call`` partition B independent graphs — the
  throughput shape for the paper's §3.1 *real-time online* requirement
  when millions of users (or an environment sweep) need placements per
  scheduler tick.

* :func:`mcop_fused_solve_kernel` — the same solve, preceded in VMEM by
  the build of each graph's weights from its environment row
  (``backend="pallas_fused"`` of ``solve_envs``).

Dense adjacency is the TPU-native layout (the paper's graphs are small —
tens to a few thousand vertices — so a whole (n, n) matrix fits VMEM:
n = 1024 f32 is 4 MB against the ~16 MB/core budget; the wrappers enforce
the bound).  The phase hot loop is the Most-Tightly-Connected-Vertex scan:

    repeat |V|−1 times:
        Δ(v)  = conn(v) − [w_local(v) − w_cloud(v)]   over v ∉ A
        v*    = argmax Δ                               (VPU masked max)
        conn += adj[v*]                                (VPU row add)

The full kernel avoids dynamic row gathers and transposes entirely: rows
are extracted with one-hot masked reductions, and row↔column vector moves
use the identity-mask gadget ``Σ_j eye[i,j]·v[j]`` — both plain VPU work.

``interpret`` defaults to auto-detection (compiled on TPU, interpreter
elsewhere) via ``repro.kernels.ops.default_interpret``; pass an explicit
bool to override.

Padded/dead vertices are encoded ``pinned = 1`` with zero weights and
never selected (their score is −∞); scalars travel as (1, 1) or (1, n)
2-D arrays to keep the kernels TPU-lowering-friendly (2-D everywhere, no
0-D iota).

Backend selection cheat-sheet (see also ``repro.core.mcop``):

* one graph, need the per-phase trace        → ``mcop_reference`` (numpy)
* many graphs / env sweep, XLA               → ``core.mcop.mcop_batch``
* many graphs, adjacency resident in VMEM    → this file's full kernel
  (``mcop_batch(..., backend="pallas")``) — loads each adjacency into
  VMEM once per solve; how it compares with the XLA path is not measured
  on a chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import default_interpret

__all__ = [
    "mcop_stoer_wagner_kernel",
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
# n²-sized f32 arrays the solve body keeps live at once (adj, members,
# eye, the two index iotas and the merge temporaries).
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
# Full solver kernel — all phases + merges, one VMEM load, batch grid.
# ======================================================================


def _solve_graph(adj, wl, wc, pin, *, n: int):
    """One graph's full modified Stoer–Wagner, as pure kernel-body math.

    Args are VALUES already resident in VMEM (not refs): ``adj`` (n, n)
    f32, ``wl``/``wc`` (1, n) f32, ``pin`` (1, n) bool.  Returns
    ``(best_cut (1, 1) f32, local_mask (1, n) f32)``.  Factoring the
    solve out of the pallas body lets one program invocation solve a
    whole *block* of graphs (grid tuning) and lets the fused variant
    build the WCG weights in VMEM immediately before calling this.

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


def _sw_block_body(
    adj_ref,   # (g, n, n) f32 — a block of g graphs
    wl_ref,    # (g, 1, n) f32
    wc_ref,    # (g, 1, n) f32
    pin_ref,   # (g, 1, n) f32  1.0 = unoffloadable (pinned to local tier)
    cut_ref,   # (g, 1, 1) f32  out: min over phases of Eq. 10
    mask_ref,  # (g, 1, n) f32  out: 1.0 = execute locally
    *,
    n: int,
    g: int,
):
    """Solve the g graphs of this grid step back-to-back in VMEM.

    ``g == 1`` reproduces the historical one-graph-per-program grid
    bit-for-bit; ``g > 1`` amortizes per-invocation overhead (grid
    bookkeeping, output DMA turnaround) across g solves — the batch-grid
    tuning knob for small-bucket fleets where dispatch dominates.  Each
    graph's rows are read and written through the refs at a dynamic index
    on the untiled leading axis, so any g tiles.
    """

    def solve_j(j, carry):
        cut, mask = _solve_graph(
            adj_ref[j], wl_ref[j], wc_ref[j], pin_ref[j] > 0.5, n=n
        )
        cut_ref[j] = cut
        mask_ref[j] = mask
        return carry

    jax.lax.fori_loop(0, g, solve_j, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "block_graphs"))
def _sw_call(adj, wl, wc, pin, *, interpret: bool, block_graphs: int = 1):
    b, n, _ = adj.shape
    g = block_graphs
    assert b % g == 0, (b, g)
    body = functools.partial(_sw_block_body, n=n, g=g)
    row = pl.BlockSpec((g, 1, n), lambda i: (i, 0, 0))
    cut, mask = pl.pallas_call(
        body,
        grid=(b // g,),
        in_specs=[pl.BlockSpec((g, n, n), lambda i: (i, 0, 0)), row, row, row],
        out_specs=[pl.BlockSpec((g, 1, 1), lambda i: (i, 0, 0)), row],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, n), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(adj, wl[:, None, :], wc[:, None, :], pin[:, None, :])
    return cut[:, 0, 0], mask[:, 0, :] > 0.5


def default_block_graphs(n: int, interpret: bool) -> int:
    """Graphs per program invocation for an n-vertex bucket.

    Compiled kernels amortize per-invocation overhead by solving several
    graphs per grid step: target ~2048 "vertex rows" of work per program,
    capped at 8 graphs and by the VMEM budget (the double-buffered input
    block plus the n²-sized working arrays must fit).  The interpreter executes the
    grid serially with no per-step launch cost, so it keeps the
    historical 1-graph grid.  ``REPRO_MCOP_BLOCK_GRAPHS`` overrides both
    (the hillclimbing knob for real-TPU tuning).
    """
    import os

    override = os.environ.get("REPRO_MCOP_BLOCK_GRAPHS")
    if override is not None:
        g = int(override)
        if g < 1:
            raise ValueError(f"REPRO_MCOP_BLOCK_GRAPHS must be >= 1, got {g}")
        return g
    if interpret:
        return 1
    g = max(1, min(8, 2048 // max(n, 1)))
    while g > 1 and _blocked_vmem_bytes(n, g) > _VMEM_BYTES:
        g //= 2
    return g


def _pad_batch(b: int, g: int) -> int:
    return (-b) % g


def mcop_stoer_wagner_kernel(
    adj: jnp.ndarray,       # (B, n, n) f32 — a batch of WCG adjacencies
    w_local: jnp.ndarray,   # (B, n)
    w_cloud: jnp.ndarray,   # (B, n)
    pinned: jnp.ndarray,    # (B, n) bool/f32 — True = unoffloadable
    *,
    interpret: bool | None = None,
    block_graphs: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Solve a batch of MCOP instances entirely on-device.

    ``block_graphs`` graphs per grid step (``None`` = auto, see
    :func:`default_block_graphs`); within a step each adjacency lives in
    VMEM for its whole |V|−1-phase run (single HBM load per solve).
    Batches that don't divide the block are zero-padded with pinned
    dummy graphs and cropped after.  Returns ``(min_cuts (B,),
    local_masks (B, n) bool)`` — semantics match
    :func:`repro.core.mcop.mcop_reference` (same heuristic, same
    tie-breaking, f32 arithmetic), independent of ``block_graphs``.
    Dead/padded vertices must be encoded as pinned with zero weights and
    zero incident edges.
    """
    adj = jnp.asarray(adj, jnp.float32)
    assert adj.ndim == 3, f"expected (B, n, n) batch, got {adj.shape}"
    b, n = adj.shape[0], adj.shape[-1]
    interp = _resolve_interpret(interpret)
    g = default_block_graphs(n, interp) if block_graphs is None else int(block_graphs)
    g = max(1, min(g, b if b else 1))
    assert _blocked_vmem_bytes(n, g) <= _VMEM_BYTES, (
        f"graph too large for single-core VMEM with kernel working set: "
        f"n={n}, block_graphs={g}"
    )
    wl = jnp.asarray(w_local, jnp.float32).reshape(b, n)
    wc = jnp.asarray(w_cloud, jnp.float32).reshape(b, n)
    pin = jnp.asarray(pinned, jnp.float32).reshape(b, n)
    pad = _pad_batch(b, g)
    if pad:
        adj = jnp.concatenate([adj, jnp.zeros((pad, n, n), jnp.float32)])
        wl = jnp.concatenate([wl, jnp.zeros((pad, n), jnp.float32)])
        wc = jnp.concatenate([wc, jnp.zeros((pad, n), jnp.float32)])
        pin = jnp.concatenate([pin, jnp.ones((pad, n), jnp.float32)])
    cuts, masks = _sw_call(adj, wl, wc, pin, interpret=interp, block_graphs=g)
    if pad:
        cuts, masks = cuts[:b], masks[:b]
    return cuts, masks


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
    g = default_block_graphs(n, interp) if block_graphs is None else int(block_graphs)
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
