"""STREAM TRIAD Pallas kernel — the paper's low-intensity benchmark.

C <- A + gamma * B over double-word vectors: 2 FLOP per 24 bytes moved
(paper Sec. III-B, I = 1/12 FLOP/byte). On CPU the paper sweeps the vector
length N to land the working set in L3 vs DRAM; on TPU the same sweep moves
the stream between VMEM-resident (small N) and HBM-streaming (large N)
regimes — the v5e analog of the paper's L3/DRAM distinction.

TPU adaptation: vectors are viewed as (rows, 1024) 2D tiles so blocks are
lane-aligned (1024 = 8 sublanes * 128 lanes); the row-block size ``br`` is
the kernel's tunable.
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 1024  # elements per row: one (8, 128) f32 vreg tile


def _triad_kernel(a_ref, b_ref, o_ref, *, gamma: float):
    o_ref[...] = a_ref[...] + gamma * b_ref[...]


def triad_pallas(a: jax.Array, b: jax.Array, gamma: float, *, br: int = 256,
                 interpret: bool = False) -> jax.Array:
    """C = A + gamma*B over (rows, LANES)-shaped views.

    Args:
      a, b: equal-shape 2D arrays (rows, LANES); ``ops.triad`` reshapes/pads
        1D vectors into this layout.
      br: rows per block — the VMEM streaming-tile tunable.
    """
    if a.shape != b.shape or a.ndim != 2:
        raise ValueError(f"expected equal 2D shapes, got {a.shape} {b.shape}")
    rows, lanes = a.shape
    if rows % br:
        raise ValueError(f"rows {rows} not divisible by block {br}")
    kernel = functools.partial(_triad_kernel, gamma=gamma)
    return pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, lanes), lambda i: (i, 0)),
                  pl.BlockSpec((br, lanes), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(a, b)


def bytes_moved(n_elements: int, dtype_bytes: int) -> float:
    """3 words per element (load A, load B, store C) — paper Sec. III-B."""
    return 3.0 * n_elements * dtype_bytes


def flops(n_elements: int) -> float:
    """2 FLOP per element (mul + add)."""
    return 2.0 * n_elements
