"""Pure-jnp oracles for every Pallas kernel in this package.

These are deliberately naive — materialise-everything implementations whose
numerics define correctness.  tests/test_kernels.py sweeps shapes & dtypes
asserting the Pallas kernels (interpret=True) match these to tolerance.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = [
    "flash_reference",
    "mamba_chunk_scan_reference",
]

NEG_INF = -2.0**30


def flash_reference(
    q: jnp.ndarray,   # (B, H, Sq, hd)
    k: jnp.ndarray,   # (B, Hkv, Sk, hd)
    v: jnp.ndarray,   # (B, Hkv, Sk, hd)
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
) -> jnp.ndarray:
    """Naive attention with the full (Sq, Sk) score matrix."""
    b, h, sq, hd = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    rep = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    kr = jnp.repeat(k, rep, axis=1)
    vr = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kr.astype(jnp.float32))
    s = s * scale
    q_pos = jnp.arange(sq)[:, None]
    k_pos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vr.astype(jnp.float32))
    return out.astype(q.dtype)


def mamba_chunk_scan_reference(
    x: jnp.ndarray,    # (B, H, NC, Q, P)
    dt: jnp.ndarray,   # (B, H, NC, Q)
    ld: jnp.ndarray,   # (B, H, NC, Q)
    bm: jnp.ndarray,   # (B, NC, Q, N)
    cm: jnp.ndarray,   # (B, NC, Q, N)
    h0: jnp.ndarray,   # (B, H, P, N)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Token-by-token SSM recurrence — the slowest, most obviously-correct
    form:  h_t = exp(ld_t)·h_{t−1} + dt_t·(x_t ⊗ B_t);  y_t = C_t·h_tᵀ."""
    b, h, nc, q, p = x.shape
    n = bm.shape[-1]

    xf = x.reshape(b, h, nc * q, p).astype(jnp.float32)
    dtf = dt.reshape(b, h, nc * q).astype(jnp.float32)
    ldf = ld.reshape(b, h, nc * q).astype(jnp.float32)
    bf = bm.reshape(b, nc * q, n).astype(jnp.float32)
    cf = cm.reshape(b, nc * q, n).astype(jnp.float32)

    def step(hst, inputs):
        xt, dtt, ldt, bt, ct = inputs
        # hst: (B, H, P, N)
        hst = hst * jnp.exp(ldt)[..., None, None] + (
            dtt[..., None, None] * xt[..., :, None] * bt[:, None, None, :]
        )
        yt = jnp.einsum("bn,bhpn->bhp", ct, hst)
        return hst, yt

    hT, ys = jax.lax.scan(
        step,
        h0.astype(jnp.float32),
        (
            xf.transpose(2, 0, 1, 3),     # (T, B, H, P)
            dtf.transpose(2, 0, 1),
            ldf.transpose(2, 0, 1),
            bf.transpose(1, 0, 2),        # (T, B, N)
            cf.transpose(1, 0, 2),
        ),
    )
    y = ys.transpose(1, 2, 0, 3).reshape(b, h, nc, q, p)
    return y, hT
