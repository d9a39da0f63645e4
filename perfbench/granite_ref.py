"""Plain float32 reference of the granite-3 dense decoder's training loss
and its gradients, computed one layer at a time so that it fits beside
the weights on one chip.

It follows the model as the configuration file states it: RMSNorm before
attention and before the GLU MLP, rotary embeddings (half-split rotation),
grouped-query attention with a causal mask, a SiLU-gated MLP, a final
RMSNorm, and an LM head tied to the embedding table. The loss is the mean
next-token cross entropy over every position but the last of each row,
over the published vocabulary (rows of the table past it are padding and
take no part). Every matmul runs at the highest precision.

Weights come in as the program's tree of stacked layers (``embed/table``,
``layers/{ln1,ln2,attn/{wq,wk,wv,wo},mlp/{w_gate,w_up,w_down}}``,
``ln_f``); nothing else of the program is used. ``low`` names a dtype that
every matmul operand is rounded through in the forward pass, with one
scale per tensor, which turns the reference into the lower-precision
control.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round(x, low):
    """``x`` rounded through ``low`` with one scale per tensor that maps its
    largest magnitude to the format's largest finite value (the usual way
    to run a matmul in float8). The cotangent passes through unrounded, so
    the backward matmuls take the rounded operands of the forward and a
    gradient is not lost to a cast of the cotangent through ``low``."""
    top = float(jnp.finfo(low).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(low).astype(jnp.float32) * scale


def _round_fwd(x, low):
    return _round(x, low), None


def _round_bwd(low, _, g):
    return (g,)


_round.defvjp(_round_fwd, _round_bwd)


def _mm(spec: str, x, w, low):
    if low is not None:
        x, w = _round(x, low), _round(w, low)
    return jnp.einsum(spec, x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x: (B, S, H, Dh); rotates the first half against the second."""
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(h, p, *, model: dict, low=None):
    """One decoder layer. ``p`` holds this layer's weights in float32."""
    eps, theta = model["norm_eps"], model["rope_theta"]
    x = _rms(h, p["ln1"], eps)
    q = _rope(_mm("bsd,dhk->bshk", x, p["attn"]["wq"], low), theta)
    k = _rope(_mm("bsd,dhk->bshk", x, p["attn"]["wk"], low), theta)
    v = _mm("bsd,dhk->bshk", x, p["attn"]["wv"], low)
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = h.shape[1]
    scores = _mm("bqhk,bshk->bhqs", q, k, low) / math.sqrt(q.shape[-1])
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = _mm("bhqs,bshk->bqhk", probs, v, low)
    h = h + _mm("bshk,hkd->bsd", att, p["attn"]["wo"], low)
    x = _rms(h, p["ln2"], eps)
    gate = jax.nn.silu(_mm("bsd,df->bsf", x, p["mlp"]["w_gate"], low))
    up = _mm("bsd,df->bsf", x, p["mlp"]["w_up"], low)
    return h + _mm("bsf,fd->bsd", gate * up, p["mlp"]["w_down"], low)


def head_loss(h, ln_f, table, tokens, *, model: dict, low=None):
    """Final norm, tied LM head over the published vocabulary, mean cross
    entropy of each position against the next token."""
    vocab = model["vocab_size"]
    x = _rms(h, ln_f, model["norm_eps"])
    logits = _mm("bsd,vd->bsv", x, table[:vocab], low)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(gold)


def loss_and_grad_norms(params: dict, tokens, model: dict, low=None,
                        ) -> tuple[float, dict[str, float]]:
    """The loss, and the L2 norm of each leaf's gradient, keyed by the
    leaf's path in the program's tree (``layers/attn/wq``, ...)."""
    n_layers = model["n_layers"]
    fwd = jax.jit(functools.partial(layer, model=model, low=low))

    @jax.jit
    def layer_vjp(h, p, dh):
        _, vjp = jax.vjp(functools.partial(layer, model=model, low=low), h, p)
        dh_in, dp = vjp(dh)
        return dh_in, jax.tree.map(lambda g: jnp.sum(jnp.square(g)), dp)

    @jax.jit
    def head_vjp(h, ln_f, table, tokens):
        loss, vjp = jax.vjp(lambda h_, n_, t_: head_loss(
            h_, n_, t_, tokens, model=model, low=low), h, ln_f, table)
        dh, dn, dt = vjp(jnp.ones((), jnp.float32))
        return loss, dh, dn, dt

    def layer_params(i):
        # float32 copies, so that the gradients are float32 too
        return jax.tree.map(lambda a: a[i].astype(jnp.float32),
                            params["layers"])

    table = params["embed"]["table"].astype(jnp.float32)
    h = table[tokens]
    inputs = []
    for i in range(n_layers):
        inputs.append(h)
        h = fwd(h, layer_params(i))
    loss, dh, d_lnf, d_table = head_vjp(
        h, params["ln_f"].astype(jnp.float32), table, tokens)
    sq = None
    for i in reversed(range(n_layers)):
        dh, dsq = layer_vjp(inputs[i], layer_params(i), dh)
        sq = dsq if sq is None else jax.tree.map(jnp.add, sq, dsq)
        inputs[i] = None
    # the table's gradient: the LM head's plus the lookup's scatter
    d_table = d_table.at[tokens.reshape(-1)].add(
        dh.reshape(-1, dh.shape[-1]))
    norms = {"embed/table": float(jnp.sqrt(jnp.sum(jnp.square(d_table)))),
             "ln_f": float(jnp.sqrt(jnp.sum(jnp.square(d_lnf))))}
    for path, v in _flatten(sq, "layers").items():
        norms[path] = float(jnp.sqrt(v))
    return float(loss), norms


def _flatten(tree, prefix: str) -> dict:
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out.update(_flatten(v, f"{prefix}/{k}"))
        return out
    return {prefix: tree}


def tree_norms(grads: dict) -> dict[str, float]:
    """L2 norm of each leaf of a gradient tree, in float32, keyed by path."""
    sq = jax.jit(lambda g: jax.tree.map(
        lambda a: jnp.sum(jnp.square(a.astype(jnp.float32))), g))(grads)
    return {path.lstrip("/"): float(jnp.sqrt(v))
            for path, v in _flatten(sq, "").items()}
