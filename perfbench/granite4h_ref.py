"""Plain float32 reference of granite-4.0-h's training loss and its
gradients (``granitemoehybrid`` with no experts), computed one layer at a
time so that it fits beside the weights on one chip at 8192 positions.

It follows the published GraniteMoeHybrid equations as the configuration
file states them. Each layer is ``h + r * mixer(rms(h))`` then
``h + r * mlp(rms(h))`` with ``r`` the residual multiplier; the mixer is
a Mamba2 layer or a causal GQA attention layer, as ``layer_types`` says.

- Mamba2: z, x, B, C and dt projections (no bias); a depthwise causal
  convolution of width ``conv_width`` with bias over [x, B, C], then SiLU;
  dt = softplus(dt + dt_bias), A = -exp(A_log); the state-space scan
  (one group) computed as a chunked quadratic form at chunk 256, the
  state carried across chunks, each chunk checkpointed; y + D x; the gated
  RMSNorm of y * silu(z); the output projection.
- Attention: no position embedding (NoPE), scores scaled by the attention
  multiplier, a causal mask, computed over blocks of 512 queries, each
  checkpointed, so no (S, S) score matrix is held.
- The MLP: SiLU-gated, ``d_ff`` wide.
- The embedding is scaled by the embedding multiplier; the final RMSNorm's
  output goes to the head tied to the embedding table, and the logits are
  divided by the logits scaling. The loss is the mean next-token cross
  entropy over every position but the last, over the published
  vocabulary, computed over blocks of 1024 positions.

Every matmul and every einsum of the scan runs at the highest precision.
Weights come in as the program's tree (``embed/table``, ``pre``, ``attn``
and ``post`` stacked per period, ``ln_f``); the pattern of layers is read
from the configuration, and nothing of the program is used. ``low`` names
a floating-point format (float8_e4m3fn) whose exponent and mantissa bits
every matmul operand is rounded to in the forward pass, which turns the
reference into the lower-precision control.

``compare`` holds a gradient tree against the reference one layer at a
time, so that the reference's own float32 gradients are never held whole.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.granite_ref import _flatten

HIGHEST = jax.lax.Precision.HIGHEST
#: the reference's own chunk of the scan, the published ``mamba_chunk_size``
SSD_CHUNK = 256
#: queries per block of the attention, positions per block of the head
ATTN_BLOCK = 512
HEAD_BLOCK = 1024


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round(x, low):
    """``x`` rounded to the exponent and mantissa bits of the format
    ``low``, with one scale per tensor that maps its largest magnitude to
    the largest finite value of those bits. ``lax.reduce_precision`` does
    the rounding: on a TPU, XLA drops a round trip through
    ``astype(float8)`` as excess precision. The cotangent passes through
    unrounded, so the backward matmuls take the rounded operands of the
    forward."""
    info = jnp.finfo(low)
    exponent, mantissa = int(info.nexp), int(info.nmant)
    top = (2.0 - 2.0 ** -mantissa) * 2.0 ** (2 ** (exponent - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return jax.lax.reduce_precision(x / scale, exponent, mantissa) * scale


def _round_fwd(x, low):
    return _round(x, low), None


def _round_bwd(low, _, g):
    return (g,)


_round.defvjp(_round_fwd, _round_bwd)


def _ein(spec: str, *ops, low=None):
    if low is not None:
        ops = [_round(x, low) for x in ops]
    return jnp.einsum(spec, *ops, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def layer_kinds(model: dict) -> list[str]:
    return list(model["layer_types"][:model["n_layers"]])


def program_layout(model: dict) -> list[tuple[str, int, int | None]]:
    """Where each layer's weights sit in the program's tree, in layer
    order: (stack, period, index in the period's stack or None)."""
    kinds = layer_kinds(model)
    periods = kinds.count("attention")
    period = len(kinds) // periods
    pre = kinds[:period].index("attention")
    out = []
    for i in range(periods):
        out += [("pre", i, j) for j in range(pre)]
        out.append(("attn", i, None))
        out += [("post", i, j) for j in range(period - pre - 1)]
    return out


def ssd_scan(x, dt, a, b, c, *, chunk: int = SSD_CHUNK, low=None):
    """y[t] = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r a) dt_s x_s,
    chunk by chunk. x (B,S,H,P), dt (B,S,H), a (H,), b, c (B,S,N)."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, s)
    nc = s // q
    cum = jnp.cumsum((dt * a).reshape(bsz, nc, q, h), axis=2)
    xs = (x.reshape(bsz, nc, q, h, p), dt.reshape(bsz, nc, q, h),
          b.reshape(bsz, nc, q, n), c.reshape(bsz, nc, q, n), cum)
    xs = jax.tree.map(lambda v: jnp.moveaxis(v, 1, 0), xs)
    causal = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]

    @jax.checkpoint
    def chunk_step(state, inp):
        xq, dq, bq, cq, uq = inp         # (B,Q,H,P) (B,Q,H) (B,Q,N) (B,Q,N) (B,Q,H)
        seg = uq[:, :, None, :] - uq[:, None, :, :]        # (B,Qi,Qj,H)
        decay = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
        scores = _ein("bin,bjn->bij", cq, bq, low=low)
        y = _ein("bij,bijh,bjhp->bihp", scores, decay,
                 xq * dq[..., None], low=low)
        y = y + _ein("bin,bhpn->bihp", cq, state, low=low) \
            * jnp.exp(uq)[..., None]
        to_end = jnp.exp(uq[:, -1:, :] - uq)                # (B,Q,H)
        state = jnp.exp(uq[:, -1])[:, :, None, None] * state + _ein(
            "bjn,bjhp->bhpn", bq, xq * (dq * to_end)[..., None], low=low)
        return state, y

    state0 = jnp.zeros((bsz, h, p, n), jnp.float32)
    _, ys = jax.lax.scan(chunk_step, state0, xs)
    return jnp.moveaxis(ys, 0, 1).reshape(bsz, s, h, p)


def ssd_sequential(x, dt, a, b, c):
    """The same scan as a step-by-step recurrence of the (H, P, N) state."""
    bsz, _, h, p = x.shape
    n = b.shape[-1]

    def step(state, inp):
        xt, dtt, bt, ct = inp
        state = jnp.exp(dtt * a)[:, :, None, None] * state + jnp.einsum(
            "bn,bhp->bhpn", bt, xt * dtt[..., None], precision=HIGHEST)
        return state, jnp.einsum("bn,bhpn->bhp", ct, state,
                                 precision=HIGHEST)

    inputs = jax.tree.map(lambda v: jnp.moveaxis(v, 1, 0), (x, dt, b, c))
    _, ys = jax.lax.scan(step, jnp.zeros((bsz, h, p, n), jnp.float32),
                         inputs)
    return jnp.moveaxis(ys, 0, 1)


def mamba(x, p, model: dict, low=None):
    """The Mamba2 mixer of one layer on its normed input x (B, S, D)."""
    bsz, s, _ = x.shape
    heads = model["ssm_expand"] * model["d_model"] // model["ssm_head_dim"]
    z = _ein("bsd,di->bsi", x, p["in_z"], low=low)
    xbc = jnp.concatenate([_ein("bsd,di->bsi", x, p[k], low=low)
                           for k in ("in_x", "in_b", "in_c")], axis=-1)
    dt = _ein("bsd,dh->bsh", x, p["in_dt"], low=low)
    w = jnp.concatenate([p["conv_x"], p["conv_b"], p["conv_c"]], axis=-1)
    width = w.shape[0]
    padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + s] * w[i] for i in range(width))
    if model.get("conv_bias"):
        conv = conv + jnp.concatenate(
            [p["conv_bias_x"], p["conv_bias_b"], p["conv_bias_c"]])
    xbc = jax.nn.silu(conv)
    d_inner, n = p["in_x"].shape[-1], p["in_b"].shape[-1]
    xs = xbc[..., :d_inner].reshape(bsz, s, heads, -1)
    b, c = xbc[..., d_inner:d_inner + n], xbc[..., d_inner + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssd_scan(xs, dt, -jnp.exp(p["a_log"]), b, c, low=low)
    y = (y + p["d_skip"][:, None] * xs).reshape(bsz, s, d_inner)
    g = _rms(y * jax.nn.silu(z), p["norm"], model["norm_eps"])
    return _ein("bsi,id->bsd", g, p["out_proj"], low=low)


def attention(x, p, model: dict, low=None):
    """NoPE causal GQA attention of one layer on its normed input."""
    q = _ein("bsd,dhk->bshk", x, p["wq"], low=low)
    k = _ein("bsd,dhk->bshk", x, p["wk"], low=low)
    v = _ein("bsd,dhk->bshk", x, p["wv"], low=low)
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = x.shape[1]
    blk = min(ATTN_BLOCK, s)
    scale = model["attention_multiplier"]

    @jax.checkpoint
    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, blk, axis=1)
        scores = _ein("bqhk,bshk->bhqs", qb, k, low=low) * scale
        causal = (start + jnp.arange(blk))[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _ein("bhqs,bshk->bqhk", probs, v, low=low)

    out = jax.lax.map(block, jnp.arange(0, s, blk))       # (nb,B,blk,H,K)
    out = jnp.moveaxis(out, 0, 1).reshape(q.shape)
    return _ein("bshk,hkd->bsd", out, p["wo"], low=low)


def layer(h, p, *, kind: str, model: dict, low=None):
    """One layer; ``p`` holds its weights in float32."""
    eps, r = model["norm_eps"], model["residual_multiplier"]
    x = _rms(h, p["ln1"], eps)
    mixer = mamba(x, p["ssd"], model, low) if kind == "mamba" else \
        attention(x, p["attn"], model, low)
    h = h + r * mixer
    x = _rms(h, p["ln2"], eps)
    gate = jax.nn.silu(_ein("bsd,df->bsf", x, p["mlp"]["w_gate"], low=low))
    up = _ein("bsd,df->bsf", x, p["mlp"]["w_up"], low=low)
    return h + r * _ein("bsf,fd->bsd", gate * up, p["mlp"]["w_down"],
                        low=low)


def head_block_loss(h, ln_f, table, targets, *, model: dict, low=None):
    """Summed cross entropy of one block of positions against their next
    tokens; the final norm, the scaled tied head, the published vocabulary."""
    x = _rms(h, ln_f, model["norm_eps"]) / model["logits_scaling"]
    logits = _ein("bsd,vd->bsv", x, table[:model["vocab_size"]], low=low)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], -1))


def _backward(params: dict, tokens, model: dict, low, visit) -> float:
    """The loss. Each layer's float32 gradient goes to ``visit(stack, index,
    tree)`` as the backward pass reaches it (``index`` (period,) or
    (period, position) in the program's stack), then the final norm's
    (``"ln_f", None``) and the table's (``"embed", None, {"table": ...}``)."""
    kinds = layer_kinds(model)
    layout = [(stack, (i,) if j is None else (i, j))
              for stack, i, j in program_layout(model)]

    def run(kind):
        return functools.partial(layer, kind=kind, model=model, low=low)

    fwd = {k: jax.jit(run(k)) for k in set(kinds)}

    def vjp_of(kind):
        @jax.jit
        def layer_vjp(h, p, dh):
            _, vjp = jax.vjp(run(kind), h, p)
            return vjp(dh)
        return layer_vjp

    bwd = {k: vjp_of(k) for k in set(kinds)}

    @jax.jit
    def head_vjp(h, ln_f, table, targets):
        loss, vjp = jax.vjp(lambda h_, n_, t_: head_block_loss(
            h_, n_, t_, targets, model=model, low=low), h, ln_f, table)
        return (loss,) + vjp(jnp.ones((), jnp.float32))

    def layer_params(stack, idx):
        # float32 copies, so that the gradients are float32 too
        return jax.tree.map(lambda a: a[idx].astype(jnp.float32),
                            params[stack])

    table = params["embed"]["table"].astype(jnp.float32)
    ln_f = params["ln_f"].astype(jnp.float32)
    h = table[tokens] * model["embedding_multiplier"]
    inputs = []
    # each layer waits for the one before, so that the device holds one
    # layer's float32 copies and gradients at a time, not those of every
    # layer the host has dispatched ahead
    for kind, (stack, idx) in zip(kinds, layout):
        inputs.append(h)
        h = jax.block_until_ready(fwd[kind](h, layer_params(stack, idx)))
    # the head over blocks of positions; the last position has no target
    bsz, s = tokens.shape
    total, d_lnf, d_table, dh = 0.0, 0.0, 0.0, []
    for start in range(0, s - 1, HEAD_BLOCK):
        end = min(start + HEAD_BLOCK, s - 1)
        loss, dhb, dn, dt = head_vjp(h[:, start:end], ln_f, table,
                                     tokens[:, start + 1:end + 1])
        total, d_lnf, d_table = total + loss, d_lnf + dn, d_table + dt
        # one block's table gradient at a time, as for the layers
        jax.block_until_ready(d_table)
        dh.append(dhb)
    del table, h
    dh.append(jnp.zeros_like(dhb[:, -1:]))
    count = bsz * (s - 1)
    dh = jnp.concatenate(dh, axis=1) / count
    d_lnf, d_table = d_lnf / count, d_table / count
    for kind, (stack, idx) in reversed(list(zip(kinds, layout))):
        dh, dp = jax.block_until_ready(
            bwd[kind](inputs.pop(), layer_params(stack, idx), dh))
        visit(stack, idx, dp)
        del dp
    visit("ln_f", None, d_lnf)
    # the table's gradient: the head's plus the lookup's scatter
    visit("embed", None, {"table": d_table.at[tokens.reshape(-1)].add(
        model["embedding_multiplier"] * dh.reshape(-1, dh.shape[-1]))})
    return float(total / count)


def loss_and_grads(params: dict, tokens, model: dict, low=None,
                   ) -> tuple[float, dict]:
    """The loss and the whole gradient tree, each leaf in its parameter's
    dtype, as a training step returns them."""
    parts: dict = {}

    def keep(stack, idx, g):
        mine = params[stack] if idx is None else \
            jax.tree.map(lambda a: a[idx], params[stack])
        parts.setdefault(stack, []).append(jax.tree.map(
            lambda a, p: a.astype(p.dtype), g, mine))

    loss = _backward(params, tokens, model, low, keep)
    grads = {}
    for stack, got in parts.items():
        # the backward pass visits the layers last to first
        got.reverse()
        grads[stack] = got[0] if stack in ("embed", "ln_f") else \
            jax.tree.map(lambda p, *xs: jnp.stack(xs).reshape(p.shape),
                         params[stack], *got)
        del got[:]
    return loss, grads


@jax.jit
def _squares(ref, got):
    """Per leaf: the sum of squares of the reference, and of its difference
    from ``got`` (taken in float32)."""
    return (jax.tree.map(lambda r: jnp.sum(jnp.square(r)), ref),
            jax.tree.map(lambda r, g: jnp.sum(jnp.square(
                r - g.astype(jnp.float32))), ref, got))


def compare(params: dict, tokens, model: dict, grads: dict,
            ) -> tuple[float, dict[str, float], dict[str, float]]:
    """The reference loss, the L2 norm of each leaf's reference gradient,
    and the L2 norm of each leaf's difference ``grads`` - reference, keyed
    by the leaf's path in the program's tree (``pre/ssd/in_z``, ...) and
    summed over the layers a stack holds."""
    ref_sq: dict = {}
    diff_sq: dict = {}

    def take(stack, idx, g):
        mine = grads[stack] if idx is None else \
            jax.tree.map(lambda a: a[idx], grads[stack])
        r, d = _squares(g, mine)
        for out, tree in ((ref_sq, r), (diff_sq, d)):
            for path, v in _flatten(tree, stack).items():
                out[path] = out.get(path, 0.0) + v

    loss = _backward(params, tokens, model, None, take)
    return (loss, {p: float(jnp.sqrt(v)) for p, v in ref_sq.items()},
            {p: float(jnp.sqrt(v)) for p, v in diff_sq.items()})
