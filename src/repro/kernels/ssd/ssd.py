"""Pallas TPU kernel for the Mamba2 SSD chunk scan (Dao & Gu 2024).

The hot loop of the SSM family (mamba2-130m, zamba2-2.7b): per (batch,
head), chunks of the sequence are processed with an attention-like
quadratic intra-chunk term while a (P, N) state carries across chunks.
The chunk axis is sequential ("arbitrary") and the running state lives in
a VMEM scratch accumulator — the same pattern as the flash-attention
kernel's (m, l, acc), i.e. the paper's online-statistics trick again, here
carrying a full state matrix instead of softmax moments.

Grid: (B, H, n_chunks). Per step the VMEM working set is
Q·P + 2·Q·N + Q + Q·Q + P·N floats — with Q=256, P=64, N=128 that is
~0.6 MiB, far under the ~128 MiB/core VMEM budget; Q is the tunable
(the autotuner's search dimension, see EXPERIMENTS §Perf cell 3).

Inputs (prepared by ``ops.ssd_chunk_scan``; f32):
  xdt (B, H, C, Q, P)   x * dt, head-major
  bm  (B, C, Q, N)      B projections (shared across heads, n_groups=1)
  cm  (B, C, Q, N)      C projections
  cum (B, H, C, Q)      within-chunk cumsum of a = dt * A  (<= 0)
Output: y (B, H, C, Q, P) = intra-chunk + inter-chunk contributions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xdt_ref, bm_ref, cm_ref, cum_col_ref, cum_row_ref, y_ref,
                h_ref, *, q_len: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    xdt = xdt_ref[0, 0, 0]                       # (Q, P)
    bm = bm_ref[0, 0]                            # (Q, N)
    cm = cm_ref[0, 0]                            # (Q, N)
    cum = cum_col_ref[0, 0, 0]                   # (Q, 1)
    cum_t = cum_row_ref[0, 0, 0]                 # (1, Q)

    # intra-chunk quadratic form: L[i,j] = exp(cum_i - cum_j) for i >= j
    diff = cum - cum_t                           # (Q, Q), <= 0 on tril
    mask = jax.lax.broadcasted_iota(jnp.int32, (q_len, q_len), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q_len, q_len), 1)
    decay = jnp.where(mask, jnp.exp(diff), 0.0)
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y_intra = jax.lax.dot((scores * decay).astype(jnp.float32), xdt,
                          preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state h (P, N)
    h = h_ref[...]
    y_inter = jax.lax.dot_general(cm, h, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_inter = y_inter * jnp.exp(cum)             # (Q, P)
    y_ref[0, 0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h' = exp(total) * h + (xdt * sd)^T @ bm
    # cum at the chunk's last position, as a (1, 1) reduction (a slice
    # at lane Q-1 gets a layout Mosaic cannot broadcast)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, q_len), 1) == q_len - 1
    total = jnp.sum(jnp.where(last, cum_t, 0.0), axis=1, keepdims=True)
    sd = jnp.exp(total - cum)                    # (Q, 1)
    contrib = jax.lax.dot_general(xdt * sd, bm,
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    h_ref[...] = jnp.exp(total) * h + contrib    # (P, N)


def ssd_chunk_scan_pallas(xdt: jax.Array, bm: jax.Array, cm: jax.Array,
                          cum: jax.Array, *,
                          interpret: bool = False) -> jax.Array:
    """Run the chunked SSD scan. Shapes as in the module docstring."""
    B, H, C, Q, P = xdt.shape
    N = bm.shape[-1]
    if bm.shape != (B, C, Q, N) or cm.shape != (B, C, Q, N):
        raise ValueError(f"bad B/C shapes: {bm.shape} {cm.shape}")
    if cum.shape != (B, H, C, Q):
        raise ValueError(f"bad cum shape: {cum.shape}")
    kernel = functools.partial(_ssd_kernel, q_len=Q)
    # cum rides in twice, as a column and as a row: a (.., 1, Q) or
    # (.., Q, 1) block matches its array's last two dims, which Mosaic
    # requires, and the kernel then needs no in-VMEM transpose
    cum = cum.astype(jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=(B, H, C),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, 1), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, Q), lambda b, h, c: (b, h, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, Q, P),
                               lambda b, h, c: (b, h, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(xdt.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xdt.astype(jnp.float32), bm.astype(jnp.float32),
      cm.astype(jnp.float32), cum[..., None], cum[..., None, :])


def flops(B: int, H: int, S: int, Q: int, P: int, N: int) -> float:
    """Per-forward FLOPs: scores QQN + intra QQP + inter QPN + state QPN
    per chunk per head."""
    n_chunks = S // Q
    per_chunk = 2.0 * (Q * Q * N + Q * Q * P + Q * P * N + Q * P * N)
    return B * H * n_chunks * per_chunk
