"""Jit'd public wrapper for flash attention (padding + backend selection)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_bwd, flash_attention_pallas
from .ref import attention_ref


def _tiles(s: int, bq: int, bk: int) -> tuple[int, int, int]:
    """The blocks a length-``s`` sequence runs at, and its padding."""
    bq_ = min(bq, s) if s >= 128 else s
    bk_ = min(bk, s) if s >= 128 else s
    return bq_, bk_, (-s) % max(bq_, bk_)


def _pad(x, pad):
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x


@functools.partial(jax.jit, static_argnames=("sm_scale", "causal", "window",
                                             "bq", "bk", "use_pallas",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    sm_scale: float | None = None, causal: bool = True,
                    window: int | None = None, bq: int = 512, bk: int = 512,
                    use_pallas: bool = True,
                    interpret: bool = False) -> jax.Array:
    """Attention over (B, H, S, D); pads S to the block size.

    Padding correctness: padded *query* rows are sliced away; padded *key*
    rows can only attend forward of all real queries under the causal mask
    (pad positions are appended), so they never contribute. For non-causal
    use the reference path or pre-masked inputs.

    Differentiable: ``pallas_call`` defines no autodiff rule, so the
    kernel carries a custom VJP. Its forward rule runs the kernel that
    also saves each query row's log-sum-exp, and its backward runs the
    two Pallas backward kernels (``flash_bwd_dkv``, ``flash_bwd_dq``) at
    the same tiles, padding and masks, so no (S, S) score matrix is ever
    held. An undifferentiated call runs the plain forward kernel.
    """
    if not use_pallas:
        return attention_ref(q, k, v, sm_scale=sm_scale, causal=causal,
                             window=window)
    s = q.shape[2]
    bq_, bk_, pad = _tiles(s, bq, bk)
    kw = dict(sm_scale=sm_scale, causal=causal, window=window, bq=bq_,
              bk=bk_, interpret=interpret)

    @jax.custom_vjp
    def fa(q, k, v):
        out = flash_attention_pallas(_pad(q, pad), _pad(k, pad),
                                     _pad(v, pad), **kw)
        return out[:, :, :s, :]

    def fa_fwd(q, k, v):
        q, k, v = _pad(q, pad), _pad(k, pad), _pad(v, pad)
        out, lse = flash_attention_pallas(q, k, v, return_lse=True, **kw)
        return out[:, :, :s, :], (q, k, v, out, lse)

    def fa_bwd(res, g):
        dq, dk, dv = flash_attention_bwd(*res, _pad(g, pad), **kw)
        return dq[:, :, :s, :], dk[:, :, :s, :], dv[:, :, :s, :]

    fa.defvjp(fa_fwd, fa_bwd)
    return fa(q, k, v)
