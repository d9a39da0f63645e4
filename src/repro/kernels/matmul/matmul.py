"""Blocked MXU matmul Pallas kernel — the DGEMM benchmark, TPU-native.

The paper autotunes the DGEMM call's matrix dimensions (n, m, k) because on
CPU those decide cache/SIMD behavior. On TPU the analogous lever is the VMEM
tile shape fed to the MXU: (bm, bn, bk) decide the working set that must fit
in ~128 MiB of VMEM and the systolic-array utilization (multiples of 128
align with the 128x128 MXU). The tile sizes are this kernel's tunables and
form the search space of ``repro.benchsuite.matmul_bench``.

Grid layout: (m/bm, n/bn, k/bk) with the k dimension sequential
("arbitrary") so a float32 VMEM scratch accumulator carries partial sums
across k steps (output dtype may be bf16; accumulation is always f32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k_steps: int):
    """One (bm, bn) output tile; accumulates over the sequential k axis."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU op: (bm, bk) @ (bk, bn) accumulated in f32.
    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k_steps - 1)
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_pallas(a: jax.Array, b: jax.Array, *, bm: int = 512, bn: int = 512,
                  bk: int = 512, interpret: bool = False) -> jax.Array:
    """C = A @ B with explicit (bm, bn, bk) VMEM tiling.

    Requires shapes divisible by the tile sizes; ``ops.matmul`` handles
    padding. Interpret mode (``interpret``) runs the kernel body in
    Python on the CPU.
    """
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    if m % bm or n % bn or k % bk:
        raise ValueError(f"shape ({m},{n},{k}) not divisible by tiles "
                         f"({bm},{bn},{bk}); use ops.matmul for padding")
    n_k_steps = k // bk
    kernel = functools.partial(_matmul_kernel, n_k_steps=n_k_steps)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, n_k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)


def vmem_bytes(bm: int, bn: int, bk: int, dtype_bytes: int = 2) -> int:
    """Working-set estimate for one grid step: A-tile + B-tile + out-tile in
    input dtype, plus the f32 accumulator. Used by the search-space
    constraint (paper Sec. IV: constraint specification)."""
    return (bm * bk + bk * bn + bm * bn) * dtype_bytes + bm * bn * 4


def flops(m: int, n: int, k: int) -> float:
    """FLOPs of one C = A@B evaluation (the paper's DGEMM FLOP count)."""
    return 2.0 * m * n * k
