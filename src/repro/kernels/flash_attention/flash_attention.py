"""Online-softmax (flash) attention Pallas kernels for TPU: the forward,
and the two kernels of its backward.

Supports GQA/MQA (kv head broadcast via BlockSpec index mapping), causal
masking, and sliding-window attention (Mixtral SWA) — the attention variants
required by the assigned architecture pool.

Thematic note: the running (max, normalizer) pair that online softmax carries
across kv blocks is the same single-pass online-moment pattern as the paper's
Welford accumulation — both replace a two-pass statistic with an
incrementally corrected one so the loop can stream.

Forward grid: (batch, q_heads, q_blocks, kv_blocks); the kv axis is
sequential and carries f32 VMEM scratch (m, l, acc). Causal/window skipping
is done with ``pl.when`` so fully-masked kv blocks cost no MXU work (the
block is still visited — Pallas TPU grids are static — but its body is
predicated out). With ``return_lse`` the same kernel also writes each
query row's log-sum-exp ``m + log l``, lane-dense as (B, H, 1, S) f32:
4 bytes a row beside O's ``2 * D``.

Backward (FlashAttention-2): with ``delta = rowsum(dO * O)`` and the saved
log-sum-exp, each block recomputes ``P = exp(S - lse)`` and
``dS = P * (dP - delta)`` with ``dP = dO V^T``; no score matrix leaves VMEM.

* ``flash_bwd_dkv``, grid (batch, kv_heads, kv_blocks, group * q_blocks):
  the last axis is sequential and walks every q block of each of the kv
  head's ``group`` q heads, so dK += dS^T Q and dV += P^T dO sum over the
  group in f32 VMEM scratch and no per-q-head partial reaches HBM. The
  block works transposed, (bk, bq), so the log-sum-exp and delta rows
  broadcast as stored.
* ``flash_bwd_dq``, grid (batch, q_heads, q_blocks, kv_blocks): kv blocks
  sequential, dQ += dS K in f32 VMEM scratch.

Both skip the blocks the forward skips, with the same predicate, and their
index maps clamp a skipped block onto the nearest visible one so that no
copy from HBM starts for it. Every block is upcast to f32 and every matmul
accumulates in f32, as in the forward.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: the transposed contraction q k^T: contract the last dim of both
_NT = (((1,), (1,)), ((), ()))


def _visible(q_start, k_start, *, bq: int, bk: int, causal: bool,
             window: int | None):
    """Whether any (query, key) pair of the two blocks is unmasked.

    With a causal mask, kv blocks entirely in the future contribute
    nothing; with a window, kv blocks entirely before the horizon
    contribute nothing either."""
    run = True
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + bq - 1)
    if window is not None:
        # newest q position in block is q_start + bq - 1; oldest visible
        # k position is q_pos - window + 1.
        run = jnp.logical_and(run, k_start + bk - 1 >= q_start - window + 1)
    return run


def _keep(q_pos, k_pos, *, causal: bool, window: int | None):
    """Elementwise mask of the visible (query, key) pairs."""
    mask = jnp.ones(q_pos.shape, dtype=jnp.bool_)
    if causal:
        mask = jnp.logical_and(mask, q_pos >= k_pos)
    if window is not None:
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
    return mask


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

    q_start = qi * bq
    k_start = kj * bk

    @pl.when(_visible(q_start, k_start, bq=bq, bk=bk, causal=causal,
                      window=window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, d)
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                              # (bq, bk)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = _keep(q_pos, k_pos, causal=causal, window=window)
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


def _attn_lse_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                     acc_ref, **kw):
    """The forward kernel, also writing the row's log-sum-exp."""
    _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, **kw)

    @pl.when(pl.program_id(3) == kw["n_kv_steps"] - 1)
    def _lse():
        l = l_ref[...]
        lse = m_ref[...] + jnp.log(jnp.where(l == 0.0, 1.0, l))   # (bq, 1)
        lse_ref[0, 0] = lse.reshape(1, kw["bq"])


def _check(q, k, v, bq, bk):
    b, h, s, d = q.shape
    _, hkv, sk, dk = k.shape
    if (sk, dk) != (s, d) or v.shape != k.shape:
        raise ValueError(f"shape mismatch q={q.shape} k={k.shape} v={v.shape}")
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    if s % bq or s % bk:
        raise ValueError(f"seq {s} not divisible by blocks ({bq},{bk})")
    return h // hkv


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           sm_scale: float | None = None, causal: bool = True,
                           window: int | None = None, bq: int = 512,
                           bk: int = 512, interpret: bool = False,
                           return_lse: bool = False):
    """Attention over (B, H, S, D) q and (B, Hkv, S, D) k/v.

    ``H % Hkv == 0``; query head h reads kv head ``h // (H // Hkv)`` (GQA).
    Sequence length must divide by the block sizes; ``ops.flash_attention``
    pads. Returns (B, H, S, D) in q's dtype; with ``return_lse`` also each
    query row's log-sum-exp of its scaled, masked scores, (B, H, 1, S)
    f32, the residual :func:`flash_attention_bwd` needs (on the chip ``bq``
    is then a multiple of 128 or the whole sequence).
    """
    b, h, s, d = q.shape
    group = _check(q, k, v, bq, bk)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    n_kv_steps = s // bk
    kernel = functools.partial(
        _attn_lse_kernel if return_lse else _attn_kernel, sm_scale=sm_scale,
        causal=causal, window=window, bq=bq, bk=bk, n_kv_steps=n_kv_steps)
    out_spec = pl.BlockSpec((1, 1, bq, d),
                            lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    if return_lse:
        out_spec = [out_spec,
                    pl.BlockSpec((1, 1, 1, bq),
                                 lambda bi, hi, qi, ki: (bi, hi, 0, qi))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32)]
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
        out_specs=out_spec,
        out_shape=out_shape,
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


def _q_range(ki, *, bq, bk, nq, causal, window):
    """First and last q block that sees kv block ``ki``."""
    lo, hi = 0, nq - 1
    if causal:
        lo = (ki * bk) // bq
    if window is not None:
        hi = jnp.minimum(hi, (ki * bk + bk - 1 + window - 1) // bq)
    return lo, hi


def _k_range(qi, *, bq, bk, nk, causal, window):
    """First and last kv block that query block ``qi`` sees."""
    lo, hi = 0, nk - 1
    if causal:
        hi = (qi * bq + bq - 1) // bk
    if window is not None:
        lo = jnp.maximum(0, (qi * bq - window + 1) // bk)
    return lo, hi


def _clamp(i, lo, hi):
    return jnp.minimum(jnp.maximum(i, lo), hi)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, causal, window,
                bq, bk, nq, n_steps):
    ki = pl.program_id(2)
    j = pl.program_id(3)
    q_start = (j % nq) * bq
    k_start = ki * bk

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_visible(q_start, k_start, bq=bq, bk=bk, causal=causal,
                      window=window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)           # (bk, d)
        do = do_ref[0, 0].astype(jnp.float32)         # (bq, d)
        s = jax.lax.dot_general(k, q, _NT,
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                               # (bk, bq)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
        mask = _keep(q_pos, k_pos, causal=causal, window=window)
        p = jnp.exp(jnp.where(mask, s, NEG_INF) - lse_ref[0, 0])
        p = jnp.where(mask, p, 0.0)
        dv_acc[...] += jax.lax.dot(p, do, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0])                # (bk, bq)
        dk_acc[...] += jax.lax.dot(ds, q, preferred_element_type=jnp.float32)

    @pl.when(j == n_steps - 1)
    def _finalize():
        dk_ref[0, 0] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, sm_scale, causal, window, bq, bk, nk):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q_start = qi * bq
    k_start = ki * bk

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(_visible(q_start, k_start, bq=bq, bk=bk, causal=causal,
                      window=window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)           # (bk, d)
        do = do_ref[0, 0].astype(jnp.float32)         # (bq, d)
        lse = lse_ref[0, 0, 0][:, None]                # (bq, 1)
        delta = delta_ref[0, 0, 0][:, None]            # (bq, 1)
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                               # (bq, bk)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = _keep(q_pos, k_pos, causal=causal, window=window)
        p = jnp.exp(jnp.where(mask, s, NEG_INF) - lse)
        p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(do, v, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)                          # (bq, bk)
        dq_acc[...] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, sm_scale=None, causal=True,
                        window=None, bq=512, bk=512, interpret=False):
    """(dq, dk, dv) of :func:`flash_attention_pallas` at cotangent ``do``,
    from the forward's output ``o`` and log-sum-exp ``lse``
    (:func:`flash_attention_pallas` with ``return_lse``). Same shape rules
    as the forward; ``bq`` tiles the query blocks and ``bk`` the kv blocks
    of both kernels.
    """
    b, h, s, d = q.shape
    hkv = k.shape[1]
    group = _check(q, k, v, bq, bk)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    nq, nk = s // bq, s // bk
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)[:, :, None, :]               # (B, H, 1, S)
    masks = dict(causal=causal, window=window)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))

    # dK, dV: step j of the sequential axis is q head ``hk * group + j //
    # nq``, q block ``j % nq``
    def q_block(ki, j):
        lo, hi = _q_range(ki, bq=bq, bk=bk, nq=nq, **masks)
        return _clamp(j % nq, lo, hi)

    def q_map(bi, hk, ki, j):
        return bi, hk * group + j // nq, q_block(ki, j), 0

    def row_map(bi, hk, ki, j):
        return bi, hk * group + j // nq, 0, q_block(ki, j)

    def kv_map(bi, hk, ki, j):
        return bi, hk, ki, 0

    dkv = functools.partial(_dkv_kernel, sm_scale=sm_scale, bq=bq, bk=bk,
                            nq=nq, n_steps=group * nq, **masks)
    dk, dv = pl.pallas_call(
        dkv,
        grid=(b, hkv, nk, group * nq),
        in_specs=[pl.BlockSpec((1, 1, bq, d), q_map),
                  pl.BlockSpec((1, 1, bk, d), kv_map),
                  pl.BlockSpec((1, 1, bk, d), kv_map),
                  pl.BlockSpec((1, 1, bq, d), q_map),
                  pl.BlockSpec((1, 1, 1, bq), row_map),
                  pl.BlockSpec((1, 1, 1, bq), row_map)],
        out_specs=[pl.BlockSpec((1, 1, bk, d), kv_map),
                   pl.BlockSpec((1, 1, bk, d), kv_map)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),   # dK accumulator
                        pltpu.VMEM((bk, d), jnp.float32)],  # dV accumulator
        compiler_params=params,
        name="flash_bwd_dkv",
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    def k_block(qi, ki):
        lo, hi = _k_range(qi, bq=bq, bk=bk, nk=nk, **masks)
        return _clamp(ki, lo, hi)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, bq=bq, bk=bk,
                          nk=nk, **masks),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, qi, ki: (
                bi, hi // group, k_block(qi, ki), 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, qi, ki: (
                bi, hi // group, k_block(qi, ki), 0)),
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda bi, hi, qi, ki: (bi, hi, 0, qi)),
            pl.BlockSpec((1, 1, 1, bq), lambda bi, hi, qi, ki: (bi, hi, 0, qi)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],  # dQ accumulator
        compiler_params=params,
        name="flash_bwd_dq",
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def flops(b: int, h: int, s: int, d: int, causal: bool) -> float:
    """Attention FLOPs: 2 matmuls of (s, d)x(d, s) and (s, s)x(s, d)."""
    full = 2.0 * b * h * (2.0 * s * s * d)
    return full / 2.0 if causal else full
