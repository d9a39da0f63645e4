"""Online-softmax (flash) attention Pallas kernel for TPU.

Supports GQA/MQA (kv head broadcast via BlockSpec index mapping), causal
masking, and sliding-window attention (Mixtral SWA) — the attention variants
required by the assigned architecture pool.

Thematic note: the running (max, normalizer) pair that online softmax carries
across kv blocks is the same single-pass online-moment pattern as the paper's
Welford accumulation — both replace a two-pass statistic with an
incrementally corrected one so the loop can stream.

Grid: (batch, q_heads, q_blocks, kv_blocks); the kv axis is sequential and
carries f32 VMEM scratch (m, l, acc). Causal/window skipping is done with
``pl.when`` so fully-masked kv blocks cost no MXU work (the block is still
visited — Pallas TPU grids are static — but its body is predicated out).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 sm_scale: float, causal: bool, window: int | None,
                 bq: int, bk: int, n_kv_steps: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-level skip: with a causal mask, kv blocks entirely in the future
    # contribute nothing; with a window, kv blocks entirely before the
    # horizon contribute nothing either.
    q_start = qi * bq
    k_start = kj * bk
    run = True
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + bq - 1)
    if window is not None:
        # newest q position in block is q_start + bq - 1; oldest visible
        # k position is q_pos - window + 1.
        run = jnp.logical_and(run, k_start + bk - 1 >= q_start - window + 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                              # (bq, bk)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        if window is not None:
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                           # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)               # rescale factor
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == n_kv_steps - 1)
    def _finalize():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           sm_scale: float | None = None, causal: bool = True,
                           window: int | None = None, bq: int = 512,
                           bk: int = 512,
                           interpret: bool = False) -> jax.Array:
    """Attention over (B, H, S, D) q and (B, Hkv, S, D) k/v.

    ``H % Hkv == 0``; query head h reads kv head ``h // (H // Hkv)`` (GQA).
    Sequence length must divide by the block sizes; ``ops.flash_attention``
    pads. Returns (B, H, S, D) in q's dtype.
    """
    b, h, s, d = q.shape
    _, hkv, sk, dk = k.shape
    if (sk, dk) != (s, d) or v.shape != k.shape:
        raise ValueError(f"shape mismatch q={q.shape} k={k.shape} v={v.shape}")
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    if s % bq or s % bk:
        raise ValueError(f"seq {s} not divisible by blocks ({bq},{bk})")
    group = h // hkv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    n_kv_steps = s // bk
    kernel = functools.partial(
        _attn_kernel, sm_scale=sm_scale, causal=causal, window=window,
        bq=bq, bk=bk, n_kv_steps=n_kv_steps)
    return pl.pallas_call(
        kernel,
        grid=(b, h, s // bq, n_kv_steps),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((bq, 1), jnp.float32),   # running normalizer l
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)


def flops(b: int, h: int, s: int, d: int, causal: bool) -> float:
    """Attention FLOPs: 2 matmuls of (s, d)x(d, s) and (s, s)x(s, d)."""
    full = 2.0 * b * h * (2.0 * s * s * d)
    return full / 2.0 if causal else full
