"""Decoder-only LM assembly: dense GQA, MoE, and VLM (cross-attn) variants.

Layers are scanned (stacked params, leading "layers" axis) so the HLO stays
O(1) in depth — essential for compiling 66 dry-run cells quickly and for
remat-per-layer memory behavior. The VLM variant interleaves via a nested
scan: outer over (self-block-group + cross-block) groups, inner over the
self blocks of the group.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from . import layers as L
from . import moe as moe_lib
from .config import ModelConfig
from .params import ParamDef


@dataclasses.dataclass(frozen=True)
class StepConfig:
    """Execution knobs (all are autotuner search dimensions)."""

    use_flash: bool = False       # Pallas kernel (TPU) vs jnp reference (CPU)
    flash_block_q: int = 512      # Pallas flash-attention q tile
    flash_block_k: int = 512      # Pallas flash-attention k tile
    remat: bool = True
    remat_policy: str = "nothing_saveable"
    loss_chunk: int = 512
    microbatches: int = 1
    inference: bool = False       # prefill/decode: drop-free MoE routing
    grad_bf16: bool = False       # cast grads before sync (halves traffic)
    use_pallas_ssd: bool = False  # Pallas SSD chunk scan (granite_hybrid)
    ssd_chunk: int = 256          # SSD chunk length (granite_hybrid)

    def policy(self):
        if not self.remat:
            return None
        return getattr(jax.checkpoint_policies, self.remat_policy)


def _stacked_norm(cfg: ModelConfig, layers: int) -> ParamDef:
    return ParamDef(shape=(layers, cfg.d_model), logical=("layers", "embed_r"),
                    init="ones", dtype=cfg.jdtype)


def _block_defs(cfg: ModelConfig, layers: int) -> dict:
    d: dict = {"ln1": _stacked_norm(cfg, layers),
               "attn": L.attention_defs(cfg, layers=layers),
               "ln2": _stacked_norm(cfg, layers)}
    if cfg.n_experts:
        d["moe"] = moe_lib.moe_defs(cfg, layers=layers)
    else:
        d["mlp"] = L.mlp_defs(cfg, layers=layers)
    return d


def lm_defs(cfg: ModelConfig) -> dict:
    """Dense / MoE decoder-only parameter tree."""
    return {"embed": L.embedding_defs(cfg),
            "layers": _block_defs(cfg, cfg.n_layers),
            "ln_f": L.norm_defs(cfg)}


def vlm_defs(cfg: ModelConfig) -> dict:
    """Self blocks + every-Nth cross-attention blocks (Llama-3.2-Vision)."""
    n_cross = cfg.n_layers // cfg.cross_attn_every
    n_self = cfg.n_layers - n_cross
    return {
        "embed": L.embedding_defs(cfg),
        "self_layers": _block_defs(cfg, n_self),
        "cross_layers": {
            "ln1": _stacked_norm(cfg, n_cross),
            "attn": L.attention_defs(cfg, layers=n_cross),
            "ln2": _stacked_norm(cfg, n_cross),
            "mlp": L.mlp_defs(cfg, layers=n_cross),
        },
        "ln_f": L.norm_defs(cfg),
    }


# ---------------------------------------------------------------------------
# Block bodies
# ---------------------------------------------------------------------------


def _ffn(lp: dict, x: jax.Array, cfg: ModelConfig, step: StepConfig = StepConfig()) -> jax.Array:
    if cfg.n_experts:
        return moe_lib.apply_moe(lp["moe"], x, cfg, drop=not step.inference)
    return L.apply_mlp(lp["mlp"], x, cfg)


def _self_block(h: jax.Array, lp: dict, cfg: ModelConfig,
                step: StepConfig, *, collect_kv: bool = False):
    a_in = L.apply_norm(lp["ln1"], h, cfg)
    if collect_kv:
        # prefill: also emit this layer's roped K/V for the decode cache
        kv_src = a_in
        q = jnp.einsum("bsd,dhk->bhsk", a_in, lp["attn"]["wq"])
        k = jnp.einsum("bsd,dhk->bhsk", kv_src, lp["attn"]["wk"])
        v = jnp.einsum("bsd,dhk->bhsk", kv_src, lp["attn"]["wv"])
        pos = jnp.arange(h.shape[1])
        q = L.apply_rope(q, pos, cfg.rope_theta)
        k = L.apply_rope(k, pos, cfg.rope_theta)
        out = L._attend(q, k, v, causal=True, window=cfg.window)
        a = jnp.einsum("bhsk,hkd->bsd", out, lp["attn"]["wo"])
        kv = (k, v)
    else:
        a = L.attention_full(lp["attn"], a_in, cfg, causal=True,
                             window=cfg.window, use_flash=step.use_flash,
                             block_q=step.flash_block_q,
                             block_k=step.flash_block_k)
        kv = None
    h = h + a
    h = h + _ffn(lp, L.apply_norm(lp["ln2"], h, cfg), cfg, step)
    return (h, kv) if collect_kv else h


def _cross_block(h: jax.Array, lp: dict, ctx: jax.Array, cfg: ModelConfig,
                 step: StepConfig) -> jax.Array:
    a_in = L.apply_norm(lp["ln1"], h, cfg)
    a = L.attention_full(lp["attn"], a_in, cfg, kv_x=ctx, causal=False,
                         rope=False, use_flash=False)
    h = h + a
    h = h + L.apply_mlp(lp["mlp"], L.apply_norm(lp["ln2"], h, cfg), cfg)
    return h


def _maybe_remat(fn, step: StepConfig):
    if not step.remat:
        return fn
    return jax.checkpoint(fn, policy=step.policy())


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def lm_hidden(params: dict, tokens: jax.Array, cfg: ModelConfig,
              step: StepConfig,
              image_embeds: Optional[jax.Array] = None) -> jax.Array:
    """Token ids -> final hidden states (B, S, D)."""
    h = L.embed_tokens(params["embed"], tokens, cfg)
    if cfg.family == "vlm":
        h = _vlm_scan(params, h, image_embeds, cfg, step)
    else:
        body = _maybe_remat(
            lambda carry, lp: (_self_block(carry, lp, cfg, step), None), step)
        h, _ = L.xscan(body, h, params["layers"])
    return L.apply_norm(params["ln_f"], h, cfg)


def _vlm_scan(params: dict, h: jax.Array, image_embeds: jax.Array,
              cfg: ModelConfig, step: StepConfig) -> jax.Array:
    n_cross = cfg.n_layers // cfg.cross_attn_every
    per_group = cfg.cross_attn_every - 1
    grouped_self = jax.tree.map(
        lambda a: a.reshape(n_cross, per_group, *a.shape[1:]),
        params["self_layers"])

    self_body = _maybe_remat(
        lambda carry, lp: (_self_block(carry, lp, cfg, step), None), step)
    cross_body = _maybe_remat(
        lambda carry, lp: _cross_block(carry, lp, image_embeds, cfg, step),
        step)

    def group_body(carry, xs):
        self_lp, cross_lp = xs
        carry, _ = L.xscan(self_body, carry, self_lp)
        carry = cross_body(carry, cross_lp)
        return carry, None

    h, _ = L.xscan(group_body, h, (grouped_self, params["cross_layers"]))
    return h


def lm_loss(params: dict, batch: dict, cfg: ModelConfig,
            step: StepConfig) -> jax.Array:
    tokens = batch["tokens"]
    h = lm_hidden(params, tokens, cfg, step,
                  image_embeds=batch.get("image_embeds"))
    targets, mask = L.next_token_targets(tokens)
    loss = L.cross_entropy_loss(params["embed"], h, targets, cfg,
                                chunk=step.loss_chunk, mask=mask)
    if cfg.n_experts:
        # router load-balance aux loss on the first layer's activations is a
        # cheap proxy (full per-layer aux would need scan outputs); weight is
        # standard 0.01.
        h0 = L.embed_tokens(params["embed"], tokens, cfg)
        router0 = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
        loss = loss + 0.01 * moe_lib.aux_load_balance_loss(router0, h0, cfg)
    return loss


# ---------------------------------------------------------------------------
# Prefill & decode
# ---------------------------------------------------------------------------


def kv_cache_spec(cfg: ModelConfig, batch: int, length: int,
                  layers: Optional[int] = None) -> L.KVCacheSpec:
    return L.KVCacheSpec(layers=layers or cfg.n_layers, batch=batch,
                         kv_heads=cfg.n_kv_heads, length=length,
                         head_dim=cfg.head_dim_, dtype=cfg.jdtype)


def lm_prefill(params: dict, batch: dict, cfg: ModelConfig,
               step: StepConfig) -> tuple[jax.Array, dict]:
    """Full-sequence forward that also materializes the decode cache.
    Returns (last-position logits, cache)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = L.embed_tokens(params["embed"], tokens, cfg)

    def body(carry, lp):
        carry, kv = _self_block(carry, lp, cfg, step, collect_kv=True)
        return carry, kv

    body = jax.checkpoint(body, policy=step.policy()) if step.remat else body
    h, (ks, vs) = L.xscan(body, h, params["layers"])
    h = L.apply_norm(params["ln_f"], h, cfg)
    logits = L.logits_fn(params["embed"], h[:, -1:], cfg)
    pos_tags = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                (cfg.n_layers, B, S))
    cache = {"attn": {"k": ks, "v": vs, "pos": pos_tags}}
    return logits, cache


def vlm_prefill(params: dict, batch: dict, cfg: ModelConfig,
                step: StepConfig) -> tuple[jax.Array, dict]:
    """VLM prefill: self-layer KV collected through the nested scan."""
    tokens, image_embeds = batch["tokens"], batch["image_embeds"]
    B, S = tokens.shape
    n_cross = cfg.n_layers // cfg.cross_attn_every
    per_group = cfg.cross_attn_every - 1
    grouped_self = jax.tree.map(
        lambda a: a.reshape(n_cross, per_group, *a.shape[1:]),
        params["self_layers"])
    h = L.embed_tokens(params["embed"], tokens, cfg)

    def self_body(carry, lp):
        carry, kv = _self_block(carry, lp, cfg, step, collect_kv=True)
        return carry, kv

    def group_body(carry, xs):
        self_lp, cross_lp = xs
        carry, kvs = L.xscan(self_body, carry, self_lp)
        carry = _cross_block(carry, cross_lp, image_embeds, cfg, step)
        return carry, kvs

    h, (ks, vs) = L.xscan(group_body, h,
                               (grouped_self, params["cross_layers"]))
    n_self = n_cross * per_group
    ks = ks.reshape(n_self, *ks.shape[2:])
    vs = vs.reshape(n_self, *vs.shape[2:])
    h = L.apply_norm(params["ln_f"], h, cfg)
    logits = L.logits_fn(params["embed"], h[:, -1:], cfg)
    pos_tags = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                (n_self, B, S))
    return logits, {"attn": {"k": ks, "v": vs, "pos": pos_tags}}


def lm_decode(params: dict, tokens: jax.Array, cache: dict, pos: jax.Array,
              cfg: ModelConfig, step: StepConfig,
              image_embeds: Optional[jax.Array] = None,
              ) -> tuple[jax.Array, dict]:
    """One-token decode. tokens: (B, 1). Returns (logits, new cache)."""
    h = L.embed_tokens(params["embed"], tokens, cfg)
    if cfg.family == "vlm":
        h, new_attn = _vlm_decode_scan(params, h, cache, pos, cfg,
                                       image_embeds, step)
    else:
        def body(carry, xs):
            lp, lc = xs
            a_in = L.apply_norm(lp["ln1"], carry, cfg)
            a, new_lc = L.attention_decode(lp["attn"], a_in, lc, pos, cfg,
                                           window=cfg.window)
            carry = carry + a
            carry = carry + _ffn(lp, L.apply_norm(lp["ln2"], carry, cfg),
                                 cfg, step)
            return carry, new_lc

        h, new_attn = L.xscan(body, h, (params["layers"],
                                             cache["attn"]))
    h = L.apply_norm(params["ln_f"], h, cfg)
    logits = L.logits_fn(params["embed"], h, cfg)
    return logits, {**cache, "attn": new_attn}


def _vlm_decode_scan(params: dict, h: jax.Array, cache: dict, pos: jax.Array,
                     cfg: ModelConfig, image_embeds: jax.Array,
                     step: StepConfig = StepConfig(remat=False)):
    n_cross = cfg.n_layers // cfg.cross_attn_every
    per_group = cfg.cross_attn_every - 1
    grouped_self = jax.tree.map(
        lambda a: a.reshape(n_cross, per_group, *a.shape[1:]),
        params["self_layers"])
    grouped_cache = jax.tree.map(
        lambda a: a.reshape(n_cross, per_group, *a.shape[1:]), cache["attn"])
    step = dataclasses.replace(step, remat=False)

    def self_body(carry, xs):
        lp, lc = xs
        a_in = L.apply_norm(lp["ln1"], carry, cfg)
        a, new_lc = L.attention_decode(lp["attn"], a_in, lc, pos, cfg)
        carry = carry + a
        carry = carry + _ffn(lp, L.apply_norm(lp["ln2"], carry, cfg),
                             cfg, step)
        return carry, new_lc

    def group_body(carry, xs):
        self_lp, self_cache, cross_lp = xs
        carry, new_cache = L.xscan(self_body, carry,
                                        (self_lp, self_cache))
        carry = _cross_block(carry, cross_lp, image_embeds, cfg, step)
        return carry, new_cache

    h, new_cache = L.xscan(
        group_body, h, (grouped_self, grouped_cache, params["cross_layers"]))
    new_attn = jax.tree.map(
        lambda a: a.reshape(n_cross * per_group, *a.shape[2:]), new_cache)
    return h, new_attn
