"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

The SSD ("state-space dual") formulation splits the selective-scan into an
*intra-chunk* quadratic term (an MXU-friendly (Q×Q)·(Q×P) matmul pair) and
an *inter-chunk* linear recurrence over per-chunk states.  That is exactly
the decomposition ``repro.models.ssm.mamba2_forward`` uses in pure jnp;
this kernel fuses one (batch, head) stream of it with the chunk loop kept
*sequential on the grid* so the running state h ∈ R^{P×N} lives in VMEM
scratch between chunks and never round-trips to HBM.

Grid: (batch, heads, num_chunks) — num_chunks is the innermost, sequential
("arbitrary") dimension.  Per step the VMEM working set is

    x (Q×P) + B,C (Q×N each) + decay tables (Q×Q) + h (P×N)

≈ 0.75 MB for the production Q=256, P=64, N=64 — far under VMEM budget,
leaving room for the compiler to double-buffer the HBM→VMEM streams of the
next chunk while the MXU works on this one.

Inputs are pre-projected (the surrounding jnp layer does conv/gating —
those are elementwise and XLA-fused); the kernel consumes:

    x   (B, H, NC, Q, P)   — per-head inputs
    dt  (B, H, NC, Q)      — softplus'd step sizes
    ld  (B, H, NC, Q)      — log-decay dt·a  (a < 0)
    Bm  (B, NC, Q, N)      — input projection (shared across heads)
    Cm  (B, NC, Q, N)      — output projection (shared across heads)
    h0  (B, H, P, N)       — initial state

and returns y (B, H, NC, Q, P) plus the final state (B, H, P, N).
The pure-jnp oracle is ``ref.mamba_chunk_scan_reference``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["mamba_chunk_scan_kernel"]


def _ssd_body(
    x_ref,     # (1, 1, 1, Q, P)
    dt_ref,    # (1, 1, 1, Q)
    ld_ref,    # (1, 1, 1, Q)
    b_ref,     # (1, 1, Q, N)
    c_ref,     # (1, 1, Q, N)
    h0_ref,    # (1, 1, P, N)
    y_ref,     # (1, 1, 1, Q, P)
    hout_ref,  # (1, 1, P, N)
    h_ref,     # VMEM scratch (P, N) f32 — carried across chunks
    *,
    num_chunks: int,
    q_len: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0, 0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)        # (Q,)
    ld = ld_ref[0, 0, 0].astype(jnp.float32)        # (Q,)
    bm = b_ref[0, 0].astype(jnp.float32)            # (Q, N)
    cm = c_ref[0, 0].astype(jnp.float32)            # (Q, N)
    h = h_ref[...]                                  # (P, N)

    cum = jnp.cumsum(ld)                            # (Q,)

    # ---- intra-chunk quadratic term --------------------------------
    # w[t, s] = exp(cum_t − cum_s) · (C_t·B_s) · dt_s   for s ≤ t
    row = jax.lax.broadcasted_iota(jnp.int32, (q_len, q_len), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q_len, q_len), 1)
    causal = col <= row
    decay = cum[:, None] - cum[None, :]             # (Q, Q)
    gate = jnp.where(causal, jnp.exp(decay), 0.0)
    scores = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                               # (Q, Q)
    w = scores * gate * dt[None, :]
    y_intra = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                               # (Q, P)

    # ---- inter-chunk: read state entering the chunk ------------------
    # y_inter[t] = exp(cum_t) · C_t · hᵀ
    ch = jax.lax.dot_general(
        cm, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                               # (Q, P)
    y = y_intra + jnp.exp(cum)[:, None] * ch
    y_ref[...] = y[None, None, None].astype(y_ref.dtype)

    # ---- state update -------------------------------------------------
    # h ← h·exp(cum_end) + Σ_s exp(cum_end − cum_s)·dt_s · x_s ⊗ B_s
    tail = jnp.exp(cum[-1] - cum) * dt              # (Q,)
    s_n = jax.lax.dot_general(
        x, bm * tail[:, None], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                               # (P, N)
    h_new = h * jnp.exp(cum[-1]) + s_n
    h_ref[...] = h_new

    @pl.when(ci == num_chunks - 1)
    def _final():
        hout_ref[...] = h_new[None, None].astype(hout_ref.dtype)


def mamba_chunk_scan_kernel(
    x: jnp.ndarray,    # (B, H, NC, Q, P) float32
    dt: jnp.ndarray,   # (B, H, NC, Q)
    ld: jnp.ndarray,   # (B, H, NC, Q)
    bm: jnp.ndarray,   # (B, NC, Q, N)
    cm: jnp.ndarray,   # (B, NC, Q, N)
    h0: jnp.ndarray,   # (B, H, P, N)
    *,
    interpret: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    b, h, nc, q, p = x.shape
    n = bm.shape[-1]
    body = functools.partial(_ssd_body, num_chunks=nc, q_len=q)
    y, h_final = pl.pallas_call(
        body,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda b_, h_, c_: (b_, h_, c_, 0, 0)),
            pl.BlockSpec((1, 1, 1, q), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, 1, q), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, q, n), lambda b_, h_, c_: (b_, c_, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda b_, h_, c_: (b_, c_, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b_, h_, c_: (b_, h_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda b_, h_, c_: (b_, h_, c_, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b_, h_, c_: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nc, q, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, dt, ld, bm, cm, h0)
    return y, h_final
