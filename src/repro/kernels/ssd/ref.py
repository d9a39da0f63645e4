"""Pure-jnp oracle for the SSD chunk-scan kernel (sequential over chunks)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_chunk_scan_ref(xdt: jax.Array, bm: jax.Array, cm: jax.Array,
                       cum: jax.Array) -> jax.Array:
    """Same contract as ``ssd_chunk_scan_pallas`` (see ssd.py docstring)."""
    B, H, C, Q, P = xdt.shape
    N = bm.shape[-1]
    xdt = xdt.astype(jnp.float32)
    bm = bm.astype(jnp.float32)
    cm = cm.astype(jnp.float32)
    cum = cum.astype(jnp.float32)

    def head_scan(xdt_h, bm_b, cm_b, cum_h):
        # xdt_h (C,Q,P), bm_b/cm_b (C,Q,N), cum_h (C,Q)
        def body(h, inputs):
            x_c, b_c, c_c, u_c = inputs
            diff = u_c[:, None] - u_c[None, :]
            mask = jnp.tril(jnp.ones((Q, Q), bool))
            # mask before exp: above the diagonal diff > 0 can overflow,
            # and where's gradient times an inf there is NaN
            decay = jnp.exp(jnp.where(mask, diff, -jnp.inf))
            scores = c_c @ b_c.T
            y = (scores * decay) @ x_c
            y = y + (c_c @ h.T) * jnp.exp(u_c)[:, None]
            total = u_c[-1]
            sd = jnp.exp(total - u_c)
            h_new = jnp.exp(total) * h + (x_c * sd[:, None]).T @ b_c
            return h_new, y

        h0 = jnp.zeros((P, N), jnp.float32)
        _, ys = jax.lax.scan(body, h0, (xdt_h, bm_b, cm_b, cum_h))
        return ys                                  # (C, Q, P)

    per_batch = jax.vmap(head_scan, in_axes=(0, None, None, 0))  # over H
    return jax.vmap(per_batch, in_axes=(0, 0, 0, 0))(xdt, bm, cm, cum)
