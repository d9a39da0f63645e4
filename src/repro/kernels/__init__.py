"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel subpackage ships:
  * ``<name>.py`` — the ``pl.pallas_call`` kernel with explicit BlockSpec
    VMEM tiling (TPU is the target; Pallas interpret mode validates on
    the CPU, and :func:`interpret_mode` is the one place that decides it);
  * ``ops.py``    — the jit'd public wrapper (padding, dtype policy, block
    autotuning hooks);
  * ``ref.py``    — a pure-jnp oracle used by tests and as the XLA fallback
    path on CPU.

Kernels:
  * ``matmul``          — blocked MXU matmul; the paper's DGEMM, TPU-adapted:
    the tunables are the VMEM tile sizes (bm, bn, bk), which on TPU play the
    role the paper's (n, m, k) matrix dims played on CPU.
  * ``triad``           — STREAM TRIAD (C = A + g*B), HBM-streaming;
    the paper's low-intensity benchmark (I = 1/12 FLOP/byte).
  * ``flash_attention`` — online-softmax attention (GQA + causal + sliding
    window); its running (max, sum) rescaling is the same online-moment trick
    as the paper's Welford accumulation, applied to softmax.
  * ``ssd``             — Mamba2 SSD chunk scan (the SSM family's hot loop);
    the carried (P, N) state lives in VMEM scratch across the sequential
    chunk grid dimension.
"""

from . import flash_attention, matmul, ssd, triad  # noqa: F401
from .platform import interpret_mode  # noqa: F401
