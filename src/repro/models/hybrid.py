"""SSM (Mamba2) and hybrid (Zamba2-style) LM assemblies.

``ssm`` family: a pure stack of pre-norm Mamba2 blocks (mamba2-130m).
``hybrid`` family: Mamba2 backbone with ONE shared attention+MLP block
applied after every ``cfg.attn_every`` Mamba layers (Zamba2's shared block;
we apply the single shared block at each interval — the per-use LoRA deltas
of the real model are omitted, see DESIGN.md §4). The shared block's params
are closed over in the outer scan so gradients accumulate across all uses.

Decode carries per-layer SSM/conv states plus one KV cache *per shared-block
use* (same params, distinct caches).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from . import layers as L
from . import ssd
from .config import ModelConfig
from .params import ParamDef
from .transformer import StepConfig, _maybe_remat, kv_cache_spec


def _stacked_norm(cfg: ModelConfig, layers: int) -> ParamDef:
    return ParamDef(shape=(layers, cfg.d_model), logical=("layers", "embed_r"),
                    init="ones", dtype=cfg.jdtype)


def ssm_lm_defs(cfg: ModelConfig) -> dict:
    return {
        "embed": L.embedding_defs(cfg),
        "layers": {"ln": _stacked_norm(cfg, cfg.n_layers),
                   "ssd": ssd.ssd_defs(cfg, layers=cfg.n_layers)},
        "ln_f": L.norm_defs(cfg),
    }


def hybrid_lm_defs(cfg: ModelConfig) -> dict:
    return {
        "embed": L.embedding_defs(cfg),
        "layers": {"ln": _stacked_norm(cfg, cfg.n_layers),
                   "ssd": ssd.ssd_defs(cfg, layers=cfg.n_layers)},
        "shared": {
            "ln1": L.norm_defs(cfg),
            "attn": L.attention_defs(cfg),
            "ln2": L.norm_defs(cfg),
            "mlp": L.mlp_defs(cfg),
        },
        "ln_f": L.norm_defs(cfg),
    }


def n_shared_uses(cfg: ModelConfig) -> int:
    return cfg.n_layers // cfg.attn_every if cfg.attn_every else 0


# ---------------------------------------------------------------------------
# Forward (train) and prefill
# ---------------------------------------------------------------------------


def _mamba_block(h: jax.Array, lp: dict, cfg: ModelConfig, *,
                 collect_state: bool = False):
    x_in = L.apply_norm(lp["ln"], h, cfg)
    if collect_state:
        y, state = ssd.ssd_forward(lp["ssd"], x_in, cfg, return_state=True)
        return h + y, state
    return h + ssd.ssd_forward(lp["ssd"], x_in, cfg), None


def _shared_block(h: jax.Array, sp: dict, cfg: ModelConfig, step: StepConfig,
                  *, collect_kv: bool = False):
    a_in = L.apply_norm(sp["ln1"], h, cfg)
    if collect_kv:
        q = jnp.einsum("bsd,dhk->bhsk", a_in, sp["attn"]["wq"])
        k = jnp.einsum("bsd,dhk->bhsk", a_in, sp["attn"]["wk"])
        v = jnp.einsum("bsd,dhk->bhsk", a_in, sp["attn"]["wv"])
        pos = jnp.arange(h.shape[1])
        q = L.apply_rope(q, pos, cfg.rope_theta)
        k = L.apply_rope(k, pos, cfg.rope_theta)
        out = L._attend(q, k, v, causal=True, window=cfg.window)
        a = jnp.einsum("bhsk,hkd->bsd", out, sp["attn"]["wo"])
        kv = (k, v)
    else:
        a = L.attention_full(sp["attn"], a_in, cfg, causal=True,
                             window=cfg.window, use_flash=step.use_flash,
                             block_q=step.flash_block_q,
                             block_k=step.flash_block_k)
        kv = None
    h = h + a
    h = h + L.apply_mlp(sp["mlp"], L.apply_norm(sp["ln2"], h, cfg), cfg)
    return (h, kv) if collect_kv else h


def hidden(params: dict, tokens: jax.Array, cfg: ModelConfig,
           step: StepConfig) -> jax.Array:
    h = L.embed_tokens(params["embed"], tokens, cfg)
    if cfg.family == "ssm" or not cfg.attn_every:
        body = _maybe_remat(
            lambda c, lp: (_mamba_block(c, lp, cfg)[0], None), step)
        h, _ = L.xscan(body, h, params["layers"])
    else:
        uses = n_shared_uses(cfg)
        grouped = jax.tree.map(
            lambda a: a.reshape(uses, cfg.attn_every, *a.shape[1:]),
            params["layers"])
        inner = _maybe_remat(
            lambda c, lp: (_mamba_block(c, lp, cfg)[0], None), step)
        shared = _maybe_remat(
            lambda c: _shared_block(c, params["shared"], cfg, step), step)

        def group_body(c, lp):
            c, _ = L.xscan(inner, c, lp)
            return shared(c), None

        h, _ = L.xscan(group_body, h, grouped)
    return L.apply_norm(params["ln_f"], h, cfg)


def loss_fn(params: dict, batch: dict, cfg: ModelConfig,
            step: StepConfig) -> jax.Array:
    tokens = batch["tokens"]
    h = hidden(params, tokens, cfg, step)
    targets, mask = L.next_token_targets(tokens)
    return L.cross_entropy_loss(params["embed"], h, targets, cfg,
                                chunk=step.loss_chunk, mask=mask)


def prefill(params: dict, batch: dict, cfg: ModelConfig,
            step: StepConfig) -> tuple[jax.Array, dict]:
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = L.embed_tokens(params["embed"], tokens, cfg)
    if cfg.family == "ssm" or not cfg.attn_every:
        def body(c, lp):
            c, state = _mamba_block(c, lp, cfg, collect_state=True)
            return c, state

        h, states = L.xscan(body, h, params["layers"])
        cache = {"ssm": states["ssm"], "conv": states["conv"]}
    else:
        uses = n_shared_uses(cfg)
        grouped = jax.tree.map(
            lambda a: a.reshape(uses, cfg.attn_every, *a.shape[1:]),
            params["layers"])

        def inner(c, lp):
            c, state = _mamba_block(c, lp, cfg, collect_state=True)
            return c, state

        def group_body(c, lp):
            c, states = L.xscan(inner, c, lp)
            c, kv = _shared_block(c, params["shared"], cfg, step,
                                  collect_kv=True)
            return c, (states, kv)

        h, (states, kvs) = L.xscan(group_body, h, grouped)
        ssm_states = jax.tree.map(
            lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), states)
        ks, vs = kvs
        pos_tags = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                    (uses, B, S))
        cache = {"ssm": ssm_states["ssm"], "conv": ssm_states["conv"],
                 "attn": {"k": ks, "v": vs, "pos": pos_tags}}
    h = L.apply_norm(params["ln_f"], h, cfg)
    logits = L.logits_fn(params["embed"], h[:, -1:], cfg)
    return logits, cache


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def cache_shapes(cfg: ModelConfig, batch: int, cache_length: int) -> dict:
    shapes = ssd.ssm_cache_shapes(cfg, cfg.n_layers, batch)
    out = {"ssm": shapes["ssm"], "conv": shapes["conv"]}
    if cfg.family == "hybrid" and cfg.attn_every:
        out["attn"] = kv_cache_spec(cfg, batch, cache_length,
                                    layers=n_shared_uses(cfg)).shape_tree()
    return out


def cache_logical(cfg: ModelConfig) -> dict:
    out = dict(ssd.ssm_cache_logical())
    if cfg.family == "hybrid" and cfg.attn_every:
        out["attn"] = L.KVCacheSpec(1, 1, 1, 1, 1, jnp.bfloat16).logical
    return out


def decode(params: dict, tokens: jax.Array, cache: dict, pos: jax.Array,
           cfg: ModelConfig, step: StepConfig) -> tuple[jax.Array, dict]:
    h = L.embed_tokens(params["embed"], tokens, cfg)

    def mamba_body(c, xs):
        lp, lc = xs
        x_in = L.apply_norm(lp["ln"], c, cfg)
        y, new_lc = ssd.ssd_decode(lp["ssd"], x_in, lc, cfg)
        return c + y, new_lc

    if cfg.family == "ssm" or not cfg.attn_every:
        h, new_states = L.xscan(
            mamba_body, h, (params["layers"],
                            {"ssm": cache["ssm"], "conv": cache["conv"]}))
        new_cache = {**cache, **new_states}
    else:
        uses = n_shared_uses(cfg)
        grouped_lp = jax.tree.map(
            lambda a: a.reshape(uses, cfg.attn_every, *a.shape[1:]),
            params["layers"])
        grouped_state = jax.tree.map(
            lambda a: a.reshape(uses, cfg.attn_every, *a.shape[1:]),
            {"ssm": cache["ssm"], "conv": cache["conv"]})

        def group_body(c, xs):
            lp, st, attn_c = xs
            c, new_st = L.xscan(mamba_body, c, (lp, st))
            a_in = L.apply_norm(params["shared"]["ln1"], c, cfg)
            a, new_attn = L.attention_decode(params["shared"]["attn"], a_in,
                                             attn_c, pos, cfg,
                                             window=cfg.window)
            c = c + a
            c = c + L.apply_mlp(params["shared"]["mlp"],
                                L.apply_norm(params["shared"]["ln2"], c, cfg),
                                cfg)
            return c, (new_st, new_attn)

        h, (new_states, new_attn) = L.xscan(
            group_body, h, (grouped_lp, grouped_state, cache["attn"]))
        flat_states = jax.tree.map(
            lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), new_states)
        new_cache = {"ssm": flat_states["ssm"], "conv": flat_states["conv"],
                     "attn": new_attn}
    h = L.apply_norm(params["ln_f"], h, cfg)
    logits = L.logits_fn(params["embed"], h, cfg)
    return logits, new_cache
