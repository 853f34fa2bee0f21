"""Pallas TPU flash attention (causal / full / sliding-window, GQA).

Target: TPU v5e.  The kernel follows the canonical TPU flash pattern:

* grid = (batch, q_heads, num_q_blocks, num_k_blocks) with the K dimension
  innermost and *sequential* ("arbitrary"), so the online-softmax
  accumulators can live in VMEM scratch across K iterations;
* BlockSpecs tile Q/K/V into (block_q × head_dim) / (block_k × head_dim)
  VMEM windows — the working set per grid step is
  block_q·hd + 2·block_k·hd + block_q·block_k floats, sized well under the
  ~16 MB/core VMEM budget for the default 512/512 blocks with hd ≤ 256;
* the MXU sees two matmuls per step (Q·Kᵀ and P·V) with dims that are
  multiples of 128 when hd ∈ {64, 128, 256} and block sizes are 128-aligned;
* GQA is expressed in the BlockSpec index map (KV head = Q head // group),
  so no repeated K/V materialisation in HBM.

Numerics are float32 in the accumulators regardless of input dtype,
matching ``ref.flash_reference`` (the pure-jnp oracle) to float32 rounding.

On this CPU-only container the kernel is validated with
``interpret=True``, which executes the same body in Python.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_kernel"]

NEG_INF = -2.0**30


def _flash_body(
    q_ref,      # (1, 1, block_q, hd)
    k_ref,      # (1, 1, block_k, hd)
    v_ref,      # (1, 1, block_k, hd)
    o_ref,      # (1, 1, block_q, hd)
    acc_ref,    # VMEM scratch (block_q, hd) f32
    m_ref,      # VMEM scratch (block_q, 1) f32
    l_ref,      # VMEM scratch (block_q, 1) f32
    *,
    scale: float,
    block_q: int,
    block_k: int,
    seq_q: int,
    seq_k: int,
    causal: bool,
    window: int | None,
    num_k_blocks: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                    # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                    # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                              # (bq, bk)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = k_pos < seq_k                                   # K padding
    mask &= q_pos < seq_q                                  # Q padding (harmless rows)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                    # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                                 # (bq, bk)
    # fully-masked rows: exp(NEG_INF − NEG_INF) = 1 — zero them explicitly
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)                         # (bq, 1)

    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l)[None, None].astype(o_ref.dtype)


def flash_attention_kernel(
    q: jnp.ndarray,   # (B, H, Sq, hd)
    k: jnp.ndarray,   # (B, Hkv, Sk, hd)
    v: jnp.ndarray,   # (B, Hkv, Sk, hd)
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    """pallas_call wrapper.  Head-major layout; returns (B, H, Sq, hd)."""
    b, h, sq, hd = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    assert h % hkv == 0, (h, hkv)
    rep = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    block_q = min(block_q, max(sq, 8))
    block_k = min(block_k, max(sk, 8))
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = q.shape[2] // block_q
    nk = k.shape[2] // block_k

    body = functools.partial(
        _flash_body,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        seq_q=sq,
        seq_k=sk,
        causal=causal,
        window=window,
        num_k_blocks=nk,
    )
    out = pl.pallas_call(
        body,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b_, h_, qi, ki: (b_, h_ // rep, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b_, h_, qi, ki: (b_, h_ // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, hd), lambda b_, h_, qi, ki: (b_, h_, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, nq * block_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq]
