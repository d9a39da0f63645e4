"""Mamba2 SSD (state-space duality) layer — chunked scan, pure JAX.

Follows Dao & Gu (arXiv:2405.21060): within a chunk the recurrence is
evaluated as a masked attention-like quadratic form (MXU-friendly); across
chunks a (B, H, P, N) state is carried by ``lax.scan``. The chunk length is
``cfg.ssm_chunk`` — a tunable exposed to the autotuner (it trades VMEM-
resident (Q, Q) score tiles against scan sequentiality, exactly the kind of
knob the paper's CI-pruned search is for).

Projections are split per component (z/x/B/C/dt) rather than one fused
in_proj so the TP sharding of ``d_inner`` ("ssm_inner" -> model axis) never
crosses component boundaries (DESIGN.md §5). Decode carries
(ssm_state (B,H,P,N) f32, conv_state (B,W-1,dim)) — O(1) in context length,
which is why the SSM/hybrid archs run the long_500k cell.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..distributed.actctx import shard_act
from .config import ModelConfig
from .layers import xscan
from .params import ParamDef


def ssd_defs(cfg: ModelConfig, layers: int | None = None) -> dict:
    D, Din, N, H, W = (cfg.d_model, cfg.d_inner, cfg.ssm_state,
                       cfg.ssm_heads, cfg.conv_width)
    lead = (layers,) if layers else ()
    lax_ = ("layers",) if layers else ()

    def w(shape, logical, **kw):
        return ParamDef(shape=lead + shape, logical=lax_ + logical,
                        dtype=cfg.jdtype, **kw)

    def small(shape, **kw):
        return ParamDef(shape=lead + shape,
                        logical=lax_ + (None,) * len(shape),
                        dtype=jnp.float32, **kw)

    return {
        "in_z": w((D, Din), ("embed", "ssm_inner")),
        "in_x": w((D, Din), ("embed", "ssm_inner")),
        "in_b": w((D, N), ("embed", "ssm_state")),
        "in_c": w((D, N), ("embed", "ssm_state")),
        "in_dt": w((D, H), ("embed", "heads")),
        "conv_x": w((W, Din), ("conv", "ssm_inner"), scale=0.5),
        "conv_b": w((W, N), ("conv", "ssm_state"), scale=0.5),
        "conv_c": w((W, N), ("conv", "ssm_state"), scale=0.5),
        "dt_bias": small((H,), init="zeros"),
        "a_log": small((H,), init="ones"),
        "d_skip": small((H,), init="ones"),
        "norm": w((Din,), (None,), init="ones"),
        "out_proj": w((Din, D), ("ssm_inner", "embed")),
        **({"conv_bias_x": w((Din,), ("ssm_inner",), scale=0.1),
            "conv_bias_b": w((N,), ("ssm_state",), scale=0.1),
            "conv_bias_c": w((N,), ("ssm_state",), scale=0.1)}
           if cfg.conv_bias else {}),
    }


def _causal_conv(x: jax.Array, w: jax.Array,
                 bias: jax.Array | None = None) -> jax.Array:
    """Depthwise causal conv, width W (small): x (B, S, C), w (W, C),
    optional bias (C,)."""
    W = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(W):
        out = out + pad[:, i:i + x.shape[1]].astype(jnp.float32) * w[i].astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def _project(p: dict, u: jax.Array, cfg: ModelConfig):
    """Shared front half of train/decode: projections + conv + dt/A."""
    inner = ("batch", "act_seq", "ssm_inner")[:u.ndim - 1] + ("ssm_inner",) \
        if u.ndim == 3 else ("batch", "ssm_inner")
    z = shard_act(jnp.einsum("...d,di->...i", u, p["in_z"]), inner)
    x = shard_act(jnp.einsum("...d,di->...i", u, p["in_x"]), inner)
    b = jnp.einsum("...d,dn->...n", u, p["in_b"])
    c = jnp.einsum("...d,dn->...n", u, p["in_c"])
    dt = jnp.einsum("...d,dh->...h", u, p["in_dt"]).astype(jnp.float32)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["a_log"])                              # (H,), negative
    return z, x, b, c, dt, A


def ssd_forward(p: dict, u: jax.Array, cfg: ModelConfig,
                h0: jax.Array | None = None, return_state: bool = False, *,
                chunk: int | None = None, use_pallas: bool = False):
    """Full-sequence SSD. u: (B, S, D) -> (B, S, D).

    ``return_state=True`` additionally returns the decode cache
    {ssm (B,H,P,N) f32, conv (B,W-1,Din+2N)} after the last position
    (prefill path). ``chunk`` overrides ``cfg.ssm_chunk``;
    ``use_pallas`` runs the chunk scan in the Pallas kernel
    (``kernels.ssd.ssd_chunk_scan``, train only: no ``h0``, no state)."""
    import math
    B, S, D = u.shape
    H, P, N, Q = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                  min(chunk or cfg.ssm_chunk, S))
    if S % Q:
        Q = math.gcd(S, Q)  # odd test lengths: largest common chunk
    z, x, b, c, dt, A = _project(p, u, cfg)
    if return_state:
        W = cfg.conv_width
        conv_tail = jnp.concatenate([x, b, c], axis=-1)[:, S - (W - 1):, :]
    x = jax.nn.silu(_causal_conv(x, p["conv_x"], p.get("conv_bias_x")))
    b = jax.nn.silu(_causal_conv(b, p["conv_b"], p.get("conv_bias_b")))
    c = jax.nn.silu(_causal_conv(c, p["conv_c"], p.get("conv_bias_c")))
    if use_pallas:
        if return_state or h0 is not None:
            raise ValueError("the Pallas SSD scan carries no state in or out")
        from ..kernels.platform import interpret_mode
        from ..kernels.ssd import ssd_chunk_scan
        y = ssd_chunk_scan(x.reshape(B, S, H, P), dt, A, b, c, chunk=Q,
                           interpret=interpret_mode())
        return _gate_out(p, y, x, z, u, cfg)

    nc = S // Q
    xh = x.reshape(B, nc, Q, H, P).astype(jnp.float32)
    bh = b.reshape(B, nc, Q, N).astype(jnp.float32)
    ch = c.reshape(B, nc, Q, N).astype(jnp.float32)
    dth = dt.reshape(B, nc, Q, H)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def chunk_body(h, inputs):
        xc, bc, cc, dtc = inputs                # (B,Q,H,P) (B,Q,N) (B,Q,N) (B,Q,H)
        a = dtc * A                              # (B,Q,H), <= 0
        cum = jnp.cumsum(a, axis=1)              # (B,Q,H)
        total = cum[:, -1]                       # (B,H)
        # intra-chunk quadratic form
        cum_t = jnp.moveaxis(cum, 1, 2)          # (B,H,Q)
        diff = cum_t[:, :, :, None] - cum_t[:, :, None, :]   # (B,H,Q,Q)
        mask = jnp.tril(jnp.ones((Q, Q), bool))
        # mask before exp: above the diagonal diff > 0 can overflow, and
        # where's gradient times an inf there is NaN
        L = jnp.exp(jnp.where(mask, diff, -jnp.inf))
        scores = jnp.einsum("bin,bjn->bij", cc, bc)          # (B,Q,Q)
        xdt = xc * dtc[..., None]                            # (B,Q,H,P)
        y_intra = jnp.einsum("bij,bhij,bjhp->bihp", scores, L, xdt)
        # inter-chunk contribution from carried state
        y_inter = jnp.einsum("bin,bhpn->bihp", cc, h) * \
            jnp.exp(cum)[..., None]                          # (B,Q,H,1)
        # state update
        sd = jnp.exp(total[:, None, :] - cum)                # (B,Q,H)
        s_c = jnp.einsum("bjn,bjhp->bhpn", bc, xdt * sd[..., None])
        h_new = jnp.exp(total)[:, :, None, None] * h + s_c
        return h_new, y_intra + y_inter

    if h0 is None:
        h0 = jnp.zeros((B, H, P, N), jnp.float32)
    inputs = (jnp.moveaxis(xh, 1, 0), jnp.moveaxis(bh, 1, 0),
              jnp.moveaxis(ch, 1, 0), jnp.moveaxis(dth, 1, 0))
    h_final, ys = xscan(chunk_body, h0, inputs)       # (nc,B,Q,H,P)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S, H, P)
    out = _gate_out(p, y, x, z, u, cfg)
    if return_state:
        return out, {"ssm": h_final,
                     "conv": conv_tail.astype(cfg.jdtype)}
    return out


def _gate_out(p: dict, y: jax.Array, x: jax.Array, z: jax.Array,
              u: jax.Array, cfg: ModelConfig) -> jax.Array:
    """D skip, gated RMSNorm (y * silu(z), normalized) and out_proj of the
    scan's y (B, S, H, P)."""
    B, S, H, P = y.shape
    y = y + p["d_skip"][:, None] * x.reshape(B, S, H, P).astype(jnp.float32)
    y = y.reshape(B, S, cfg.d_inner)
    g = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    g = g * jax.lax.rsqrt(var + cfg.norm_eps) * p["norm"].astype(jnp.float32)
    return jnp.einsum("bsi,id->bsd", g.astype(u.dtype), p["out_proj"])


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def ssm_cache_shapes(cfg: ModelConfig, layers: int, batch: int) -> dict:
    H, P, N, W = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                  cfg.conv_width)
    dim = cfg.d_inner + 2 * N
    return {
        "ssm": jax.ShapeDtypeStruct((layers, batch, H, P, N), jnp.float32),
        "conv": jax.ShapeDtypeStruct((layers, batch, W - 1, dim), cfg.jdtype),
    }


def ssm_cache_logical() -> dict:
    return {"ssm": ("layers", "cache_batch", "heads", None, None),
            "conv": ("layers", "cache_batch", None, "ssm_inner")}


def ssm_cache_init(cfg: ModelConfig, layers: int, batch: int) -> dict:
    shapes = ssm_cache_shapes(cfg, layers, batch)
    return {k: jnp.zeros(v.shape, v.dtype) for k, v in shapes.items()}


def ssd_decode(p: dict, u: jax.Array, cache: dict,
               cfg: ModelConfig) -> tuple[jax.Array, dict]:
    """One-token decode. u: (B, 1, D); cache: {ssm (B,H,P,N) f32,
    conv (B,W-1,Din+2N)}. Returns (y (B,1,D), new_cache)."""
    B = u.shape[0]
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    z, x, b, c, dt, A = _project(p, u[:, 0], cfg)        # (B, ·)
    # conv over the rolling window of raw (pre-activation) projections
    xbc = jnp.concatenate([x, b, c], axis=-1)
    window = jnp.concatenate([cache["conv"], xbc[:, None, :]], axis=1)
    w_full = jnp.concatenate([p["conv_x"], p["conv_b"], p["conv_c"]], axis=1)
    conv_out = jnp.einsum("bwc,wc->bc", window.astype(jnp.float32),
                          w_full.astype(jnp.float32))
    if cfg.conv_bias:
        conv_out = conv_out + jnp.concatenate(
            [p["conv_bias_x"], p["conv_bias_b"], p["conv_bias_c"]]
        ).astype(jnp.float32)
    conv_out = jax.nn.silu(conv_out)
    x = conv_out[:, :cfg.d_inner]
    b = conv_out[:, cfg.d_inner:cfg.d_inner + N]
    c = conv_out[:, cfg.d_inner + N:]
    xh = x.reshape(B, H, P)
    decay = jnp.exp(dt * A)                               # (B, H)
    h = cache["ssm"] * decay[..., None, None] + jnp.einsum(
        "bn,bhp->bhpn", b, xh * dt[..., None])
    y = jnp.einsum("bn,bhpn->bhp", c, h)
    y = y + p["d_skip"][:, None] * xh
    y = y.reshape(B, cfg.d_inner)
    g = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    g = g * jax.lax.rsqrt(var + cfg.norm_eps) * p["norm"].astype(jnp.float32)
    out = jnp.einsum("bi,id->bd", g.astype(u.dtype), p["out_proj"])
    new_cache = {"ssm": h, "conv": window[:, 1:].astype(cache["conv"].dtype)}
    return out[:, None, :], new_cache


def ssd_reference_scan(p: dict, u: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Step-by-step recurrence oracle (O(S) sequential) used by tests to
    validate the chunked path."""
    B, S, D = u.shape
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    cache = {k: jnp.zeros(v.shape, v.dtype) for k, v in
             ssm_cache_shapes(cfg, 1, B).items()}
    cache = {"ssm": cache["ssm"][0], "conv": cache["conv"][0]}

    def body(carry, ut):
        y, new_cache = ssd_decode(p, ut[:, None, :], carry, cfg)
        return new_cache, y[:, 0]

    _, ys = jax.lax.scan(body, cache, jnp.moveaxis(u, 1, 0))
    return jnp.moveaxis(ys, 0, 1)
