"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel has a pure-jnp oracle in ``ref.py`` and a jit'd public wrapper
in ``ops.py``:

* ``flash_attention`` — online-softmax attention (causal/full/window, GQA)
* ``mamba_chunk_scan`` — Mamba2 SSD chunked selective scan
* ``lockstep_phases`` — full batched MCOP: all phases + merges of a group
  of graphs in one lockstep chain (``core.mcop.mcop_stoer_wagner_kernel``
  folds the pins and calls it; see ``core.mcop.mcop_batch``)

``default_interpret`` picks interpret-vs-compiled once per process from the
JAX backend; all kernel wrappers accept ``interpret=None`` to mean "auto".
"""

from repro.kernels.ops import (
    default_interpret,
    flash_attention,
    mamba_chunk_scan,
    on_tpu,
)
from repro.kernels.mcop_phase import lockstep_phases
from repro.kernels import ref

__all__ = [
    "flash_attention",
    "mamba_chunk_scan",
    "lockstep_phases",
    "default_interpret",
    "on_tpu",
    "ref",
]
