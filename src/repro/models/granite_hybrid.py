"""Granite-4.0-H assembly (``granitemoehybrid`` without experts): Mamba2
and GQA attention mixers on one residual stream, each layer's mixer
followed by its own GLU MLP.

The layer kinds come from ``cfg.layer_types[:cfg.n_layers]``, which has to
repeat with one attention layer per period (granite-4.0-h-micro: 5 Mamba,
1 attention, 4 Mamba in each period of 10). Parameters are stacked per
period, so the forward is one scan over periods, each an inner scan over
the Mamba blocks before the attention block, the attention block, and an
inner scan over the Mamba blocks after it:

    pre   (periods, n_pre, ...)    Mamba blocks before the attention layer
    attn  (periods, ...)           attention blocks
    post  (periods, n_post, ...)   Mamba blocks after it

Each block is ``h + r * mixer(norm(h))`` then ``h + r * mlp(norm(h))``,
with ``r = cfg.residual_multiplier``. The embedding is scaled by
``cfg.embedding_multiplier``; attention scores by
``cfg.attention_multiplier`` (q is scaled by it times sqrt(head_dim)
before the attention call, whose own scale is 1/sqrt(head_dim)); the
logits are divided by ``cfg.logits_scaling`` (the final hidden state is,
before the tied head). Attention is NoPE: no position embedding.

The dense decoder's pieces are reused only by calling them
(``layers.apply_norm``, ``apply_mlp``, ``_qkv``, ``_attend``,
``cross_entropy_loss``, ``xscan``, the flash kernel); none is adapted to
this model. With ``step.use_flash`` attention runs the Pallas flash
kernel forward and backward, as ``layers.attention_full`` does; its
backward holds no (S, S) scores, so it serves 8192 positions. Mamba
mixers run the SSD scan in the Pallas kernel when ``step.use_pallas_ssd``
is set, at chunk ``step.ssd_chunk`` (``models/ssd.py``).

Decode carries each Mamba layer's SSM and conv states and each attention
layer's KV cache, in the same per-period stacks as the parameters.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..distributed.actctx import shard_act
from ..kernels.flash_attention import flash_attention
from ..kernels.platform import interpret_mode
from . import layers as L
from . import ssd
from .config import ModelConfig
from .params import ParamDef
from .transformer import StepConfig, _maybe_remat, kv_cache_spec

MAMBA, ATTENTION = "mamba", "attention"


def pattern(cfg: ModelConfig) -> tuple[int, int, int]:
    """(periods, Mamba layers before the attention layer, after it) of
    ``cfg.layer_types[:cfg.n_layers]``."""
    kinds = tuple(cfg.layer_types[:cfg.n_layers])
    if len(kinds) != cfg.n_layers or set(kinds) - {MAMBA, ATTENTION}:
        raise ValueError(f"{cfg.name}: layer_types must name "
                         f"{cfg.n_layers} layers, each mamba or attention")
    periods = kinds.count(ATTENTION)
    if not periods or cfg.n_layers % periods:
        raise ValueError(f"{cfg.name}: no whole period of layers")
    period = cfg.n_layers // periods
    first = kinds[:period]
    if kinds != first * periods or first.count(ATTENTION) != 1:
        raise ValueError(f"{cfg.name}: layer_types does not repeat with one "
                         f"attention layer every {period}")
    pre = first.index(ATTENTION)
    return periods, pre, period - pre - 1


def n_mixers(cfg: ModelConfig) -> tuple[int, int]:
    """(Mamba layers, attention layers)."""
    periods, pre, post = pattern(cfg)
    return periods * (pre + post), periods


def count_build(cfg: ModelConfig, step: StepConfig) -> None:
    """Counters of one step program built: its Mamba and attention layers,
    and the Pallas SSD call sites its forward lowers (one per Mamba
    layer when ``step.use_pallas_ssd`` is set)."""
    from ..obs.metrics import metrics

    mamba, attention = n_mixers(cfg)
    reg = metrics()
    reg.inc("model.mamba_layers", mamba)
    reg.inc("model.attention_layers", attention)
    if step.use_pallas_ssd:
        reg.inc("ssd.pallas_calls", mamba)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _stack(tree, lead: tuple[int, ...]):
    if isinstance(tree, ParamDef):
        return ParamDef(shape=lead + tree.shape,
                        logical=("layers",) * len(lead) + tree.logical,
                        init=tree.init, scale=tree.scale, dtype=tree.dtype)
    return {k: _stack(v, lead) for k, v in tree.items()}


def _block_defs(cfg: ModelConfig, mixer: str) -> dict:
    mix = {"ssd": ssd.ssd_defs(cfg)} if mixer == MAMBA else \
        {"attn": L.attention_defs(cfg)}
    return {"ln1": L.norm_defs(cfg), **mix, "ln2": L.norm_defs(cfg),
            "mlp": L.mlp_defs(cfg)}


def param_defs(cfg: ModelConfig) -> dict:
    periods, pre, post = pattern(cfg)
    out = {"embed": L.embedding_defs(cfg)}
    if pre:
        out["pre"] = _stack(_block_defs(cfg, MAMBA), (periods, pre))
    out["attn"] = _stack(_block_defs(cfg, ATTENTION), (periods,))
    if post:
        out["post"] = _stack(_block_defs(cfg, MAMBA), (periods, post))
    out["ln_f"] = L.norm_defs(cfg)
    return out


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _q_scale(cfg: ModelConfig) -> float:
    """The attention multiplier over the attention call's own
    1/sqrt(head_dim)."""
    if cfg.attention_multiplier is None:
        raise ValueError(f"{cfg.name}: granite_hybrid needs "
                         f"attention_multiplier")
    return cfg.attention_multiplier * math.sqrt(cfg.head_dim_)


def _qkv(p: dict, x: jax.Array, cfg: ModelConfig):
    """q (scaled for the attention multiplier), k, v; no rotary (NoPE)."""
    q, k, v = L._qkv(p, x, x)
    return q * _q_scale(cfg), k, v


def _mlp(h: jax.Array, lp: dict, cfg: ModelConfig) -> jax.Array:
    with jax.named_scope("mlp"):
        y = L.apply_mlp(lp["mlp"], L.apply_norm(lp["ln2"], h, cfg), cfg)
    return h + cfg.residual_multiplier * y


def _mamba_block(h: jax.Array, lp: dict, cfg: ModelConfig,
                 step: StepConfig) -> jax.Array:
    with jax.named_scope("mamba"):
        y = ssd.ssd_forward(lp["ssd"], L.apply_norm(lp["ln1"], h, cfg), cfg,
                            chunk=step.ssd_chunk,
                            use_pallas=step.use_pallas_ssd)
    return _mlp(h + cfg.residual_multiplier * y, lp, cfg)


def _attention_block(h: jax.Array, lp: dict, cfg: ModelConfig,
                     step: StepConfig) -> jax.Array:
    with jax.named_scope("attention"):
        q, k, v = _qkv(lp["attn"], L.apply_norm(lp["ln1"], h, cfg), cfg)
        if step.use_flash:
            out = flash_attention(q, k, v, causal=True, window=cfg.window,
                                  bq=step.flash_block_q,
                                  bk=step.flash_block_k,
                                  interpret=interpret_mode())
        else:
            out = L._attend(q, k, v, causal=True, window=cfg.window)
        y = shard_act(jnp.einsum("bhsk,hkd->bsd", out, lp["attn"]["wo"]),
                      L.ACT_BSD)
    return _mlp(h + cfg.residual_multiplier * y, lp, cfg)


# ---------------------------------------------------------------------------
# Forward (train)
# ---------------------------------------------------------------------------


def _embed(params: dict, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    h = L.embed_tokens(params["embed"], tokens, cfg)
    if cfg.embedding_multiplier != 1.0:
        h = h * cfg.embedding_multiplier
    return h


def _final(params: dict, h: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Final norm, scaled so that the tied head gives logits / scaling."""
    h = L.apply_norm(params["ln_f"], h, cfg)
    if cfg.logits_scaling != 1.0:
        h = h * (1.0 / cfg.logits_scaling)
    return h


def _stacks(params: dict) -> dict:
    return {k: params[k] for k in ("pre", "attn", "post") if k in params}


def hidden(params: dict, tokens: jax.Array, cfg: ModelConfig,
           step: StepConfig) -> jax.Array:
    """Token ids -> final hidden states (B, S, D), scaled for the head."""
    mamba = _maybe_remat(
        lambda c, lp: (_mamba_block(c, lp, cfg, step), None), step)
    attention = _maybe_remat(
        lambda c, lp: _attention_block(c, lp, cfg, step), step)

    def period(c, xs):
        if "pre" in xs:
            c, _ = L.xscan(mamba, c, xs["pre"])
        c = attention(c, xs["attn"])
        if "post" in xs:
            c, _ = L.xscan(mamba, c, xs["post"])
        return c, None

    h, _ = L.xscan(period, _embed(params, tokens, cfg), _stacks(params))
    return _final(params, h, cfg)


def loss_fn(params: dict, batch: dict, cfg: ModelConfig,
            step: StepConfig) -> jax.Array:
    tokens = batch["tokens"]
    h = hidden(params, tokens, cfg, step)
    targets, mask = L.next_token_targets(tokens)
    return L.cross_entropy_loss(params["embed"], h, targets, cfg,
                                chunk=step.loss_chunk, mask=mask)


# ---------------------------------------------------------------------------
# Prefill & decode (jax.numpy throughout)
# ---------------------------------------------------------------------------


def prefill(params: dict, batch: dict, cfg: ModelConfig,
            step: StepConfig) -> tuple[jax.Array, dict]:
    """Full-sequence forward that also returns the decode cache; returns
    (last-position logits, cache)."""
    tokens = batch["tokens"]
    B, S = tokens.shape

    def mamba(c, lp):
        y, state = ssd.ssd_forward(lp["ssd"], L.apply_norm(lp["ln1"], c, cfg),
                                   cfg, return_state=True,
                                   chunk=step.ssd_chunk)
        return _mlp(c + cfg.residual_multiplier * y, lp, cfg), state

    def period(c, xs):
        out = {}
        if "pre" in xs:
            c, out["pre"] = L.xscan(mamba, c, xs["pre"])
        lp = xs["attn"]
        q, k, v = _qkv(lp["attn"], L.apply_norm(lp["ln1"], c, cfg), cfg)
        att = L._attend(q, k, v, causal=True, window=cfg.window)
        y = jnp.einsum("bhsk,hkd->bsd", att, lp["attn"]["wo"])
        c = _mlp(c + cfg.residual_multiplier * y, lp, cfg)
        out["attn"] = (k, v)
        if "post" in xs:
            c, out["post"] = L.xscan(mamba, c, xs["post"])
        return c, out

    h, states = L.xscan(period, _embed(params, tokens, cfg), _stacks(params))
    ks, vs = states.pop("attn")
    periods = ks.shape[0]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (periods, B, S))
    cache = {**states, "attn": {"k": ks, "v": vs, "pos": pos}}
    h = _final(params, h, cfg)
    return L.logits_fn(params["embed"], h[:, -1:], cfg), cache


def decode(params: dict, tokens: jax.Array, cache: dict, pos: jax.Array,
           cfg: ModelConfig, step: StepConfig) -> tuple[jax.Array, dict]:
    """One-token decode. tokens: (B, 1). Returns (logits, new cache)."""

    def mamba(c, xs):
        lp, lc = xs
        y, new_lc = ssd.ssd_decode(lp["ssd"], L.apply_norm(lp["ln1"], c, cfg),
                                   lc, cfg)
        return _mlp(c + cfg.residual_multiplier * y, lp, cfg), new_lc

    def period(c, xs):
        lps, lcs = xs
        out = {}
        if "pre" in lps:
            c, out["pre"] = L.xscan(mamba, c, (lps["pre"], lcs["pre"]))
        lp = lps["attn"]
        # the q scale folded into wq: exact when it is a power of two
        attn = dict(lp["attn"], wq=lp["attn"]["wq"] * _q_scale(cfg))
        y, out["attn"] = L.attention_decode(
            attn, L.apply_norm(lp["ln1"], c, cfg), lcs["attn"], pos, cfg,
            rope=False, window=cfg.window)
        c = _mlp(c + cfg.residual_multiplier * y, lp, cfg)
        if "post" in lps:
            c, out["post"] = L.xscan(mamba, c, (lps["post"], lcs["post"]))
        return c, out

    h, new_cache = L.xscan(period, _embed(params, tokens, cfg),
                           (_stacks(params), cache))
    h = _final(params, h, cfg)
    return L.logits_fn(params["embed"], h, cfg), new_cache


def cache_shapes(cfg: ModelConfig, batch: int, cache_length: int) -> dict:
    periods, pre, post = pattern(cfg)

    def states(n: int) -> dict:
        return {k: jax.ShapeDtypeStruct((periods,) + s.shape, s.dtype)
                for k, s in ssd.ssm_cache_shapes(cfg, n, batch).items()}

    out = {}
    if pre:
        out["pre"] = states(pre)
    out["attn"] = kv_cache_spec(cfg, batch, cache_length,
                                layers=periods).shape_tree()
    if post:
        out["post"] = states(post)
    return out


def cache_logical(cfg: ModelConfig) -> dict:
    _, pre, post = pattern(cfg)
    states = {k: ("layers",) + v for k, v in ssd.ssm_cache_logical().items()}
    out = {}
    if pre:
        out["pre"] = states
    out["attn"] = L.KVCacheSpec(1, 1, 1, 1, 1, jnp.bfloat16).logical
    if post:
        out["post"] = states
    return out
