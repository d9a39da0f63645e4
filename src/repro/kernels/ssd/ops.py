"""Jit'd wrapper: model-layout tensors -> kernel layout -> chunk scan."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .ref import ssd_chunk_scan_ref
from .ssd import ssd_chunk_scan_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas",
                                             "interpret"))
def ssd_chunk_scan(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
                   cm: jax.Array, *, chunk: int = 256,
                   use_pallas: bool = True,
                   interpret: bool = False) -> jax.Array:
    """SSD scan over model-layout inputs.

    Args:
      x:  (B, S, H, P)  inner activations (post-conv, post-silu)
      dt: (B, S, H)     softplus'd timestep
      a:  (H,)          negative decay rates (-exp(A_log))
      bm: (B, S, N)     B projections (n_groups=1)
      cm: (B, S, N)     C projections
      chunk: chunk length Q (S % Q == 0); the tunable.
    Returns (B, S, H, P) in f32.

    Differentiable: ``pallas_call`` defines no autodiff rule, so the
    kernel carries a custom VJP whose backward differentiates
    ``ssd_chunk_scan_ref`` on the same kernel-layout inputs, the pattern
    of ``kernels/flash_attention/ops.py``. The layout preparation around
    it (x·dt, the within-chunk cumsum) is differentiated by JAX.
    """
    B, S, H, P = x.shape
    N = bm.shape[-1]
    Q = min(chunk, S)
    C = S // Q
    xdt = (x * dt[..., None]).reshape(B, C, Q, H, P)
    xdt = jnp.moveaxis(xdt, 3, 1)                        # (B,H,C,Q,P)
    cum = jnp.cumsum((dt * a).reshape(B, C, Q, H), axis=2)
    cum = jnp.moveaxis(cum, 3, 1)                        # (B,H,C,Q)
    bm_c = bm.reshape(B, C, Q, N)
    cm_c = cm.reshape(B, C, Q, N)
    if use_pallas:
        @jax.custom_vjp
        def scan(xdt, bm_c, cm_c, cum):
            return ssd_chunk_scan_pallas(xdt, bm_c, cm_c, cum,
                                         interpret=interpret)

        def scan_fwd(*args):
            return scan(*args), args

        def scan_bwd(args, g):
            _, vjp = jax.vjp(ssd_chunk_scan_ref, *args)
            return vjp(g)

        scan.defvjp(scan_fwd, scan_bwd)
    else:
        scan = ssd_chunk_scan_ref
    y = scan(xdt, bm_c, cm_c, cum)
    y = jnp.moveaxis(y, 1, 3).reshape(B, S, H, P)        # back to (B,S,H,P)
    return y
