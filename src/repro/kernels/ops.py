"""Jit'd public wrappers around the Pallas kernels.

The model code keeps its (B, S, H, hd) layout; these wrappers handle the
head-major transposes, GQA plumbing, chunk reshapes and interpret-mode
selection (interpret=True on CPU — this container — and compiled on TPU).

``flash_attention``     — drop-in for models.attention.chunked_attention.
``mamba_chunk_scan``    — drop-in for the scan core of ssm.mamba2_forward.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.mamba_scan import mamba_chunk_scan_kernel

__all__ = [
    "flash_attention",
    "mamba_chunk_scan",
    "on_tpu",
    "default_interpret",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=1)
def default_interpret() -> bool:
    """Pallas interpret-mode default, detected once from the JAX backend.

    Compiled kernels on TPU; the (slow but portable) interpreter everywhere
    else — CPU CI containers, GPU hosts.  Kernel wrappers take
    ``interpret=None`` to mean "use this".

    The ``REPRO_PALLAS_INTERPRET`` environment variable overrides the
    detection without code edits (the TPU-validation knob): ``1/true/
    yes/on`` forces interpret mode, ``0/false/no/off`` forces compiled
    kernels.  The value is read once per process (lru_cache); call
    ``default_interpret.cache_clear()`` after changing it.
    """
    override = os.environ.get("REPRO_PALLAS_INTERPRET")
    if override is not None:
        norm = override.strip().lower()
        if norm in ("1", "true", "yes", "on"):
            return True
        if norm in ("0", "false", "no", "off"):
            return False
        raise ValueError(
            f"REPRO_PALLAS_INTERPRET={override!r} is not a boolean "
            "(use 1/true/yes/on or 0/false/no/off)"
        )
    return not on_tpu()


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jnp.ndarray,   # (B, S, H, hd) — model layout
    k: jnp.ndarray,   # (B, S, Hkv, hd)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    out = flash_attention_kernel(
        qh, kh, vh,
        causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba_chunk_scan(
    x: jnp.ndarray,    # (B, S, H, P)
    dt: jnp.ndarray,   # (B, S, H)
    ld: jnp.ndarray,   # (B, S, H) — log decay dt·a
    bm: jnp.ndarray,   # (B, S, N)
    cm: jnp.ndarray,   # (B, S, N)
    h0: jnp.ndarray,   # (B, H, P, N)
    *,
    chunk: int = 256,
    interpret: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    b, s, h, p = x.shape
    n = bm.shape[-1]
    q = min(chunk, s)
    assert s % q == 0, (s, q)
    nc = s // q
    xk = x.reshape(b, nc, q, h, p).transpose(0, 3, 1, 2, 4)     # (B,H,NC,Q,P)
    dtk = dt.reshape(b, nc, q, h).transpose(0, 3, 1, 2)         # (B,H,NC,Q)
    ldk = ld.reshape(b, nc, q, h).transpose(0, 3, 1, 2)
    bmk = bm.reshape(b, nc, q, n)
    cmk = cm.reshape(b, nc, q, n)
    y, hT = mamba_chunk_scan_kernel(
        xk.astype(jnp.float32),
        dtk.astype(jnp.float32),
        ldk.astype(jnp.float32),
        bmk.astype(jnp.float32),
        cmk.astype(jnp.float32),
        h0.astype(jnp.float32),
        interpret=interpret,
    )
    y = y.transpose(0, 2, 3, 1, 4).reshape(b, s, h, p)
    return y, hT
