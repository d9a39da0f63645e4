"""Unified model API: one entry point per step kind, dispatched by family.

    param_defs(cfg)                      -> ParamDef tree
    loss_fn(params, batch, cfg, step)    -> scalar loss            (train)
    prefill_fn(params, batch, cfg, step) -> (logits, cache)        (prefill)
    decode_fn(params, batch, cache, pos, cfg, step) -> (logits, cache)
    cache_shapes(cfg, shape)             -> ShapeDtypeStruct tree
    cache_logical(cfg)                   -> logical-axes tree (sharding)

All functions are pure and jit/pjit-compatible; ``batch`` is a dict of
arrays matching ``config.input_specs``.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from . import encdec, granite_hybrid, hybrid, layers, transformer
from .config import ModelConfig, WorkloadShape, cache_len
from .transformer import StepConfig


def param_defs(cfg: ModelConfig) -> dict:
    if cfg.family in ("dense", "moe"):
        return transformer.lm_defs(cfg)
    if cfg.family == "vlm":
        return transformer.vlm_defs(cfg)
    if cfg.family == "encdec":
        return encdec.encdec_defs(cfg)
    if cfg.family == "ssm":
        return hybrid.ssm_lm_defs(cfg)
    if cfg.family == "hybrid":
        return hybrid.hybrid_lm_defs(cfg)
    if cfg.family == "granite_hybrid":
        return granite_hybrid.param_defs(cfg)
    raise ValueError(f"unknown family {cfg.family!r}")


def loss_fn(params: dict, batch: dict, cfg: ModelConfig,
            step: StepConfig) -> jax.Array:
    if cfg.family == "encdec":
        return encdec.loss_fn(params, batch, cfg, step)
    if cfg.family == "granite_hybrid":
        return granite_hybrid.loss_fn(params, batch, cfg, step)
    if cfg.family in ("ssm", "hybrid"):
        return hybrid.loss_fn(params, batch, cfg, step)
    return transformer.lm_loss(params, batch, cfg, step)


def prefill_fn(params: dict, batch: dict, cfg: ModelConfig,
               step: StepConfig) -> tuple[jax.Array, dict]:
    import dataclasses
    step = dataclasses.replace(step, inference=True)
    if cfg.family == "encdec":
        return encdec.prefill(params, batch, cfg, step)
    if cfg.family == "granite_hybrid":
        return granite_hybrid.prefill(params, batch, cfg, step)
    if cfg.family in ("ssm", "hybrid"):
        return hybrid.prefill(params, batch, cfg, step)
    if cfg.family == "vlm":
        return transformer.vlm_prefill(params, batch, cfg, step)
    return transformer.lm_prefill(params, batch, cfg, step)


def decode_fn(params: dict, batch: dict, cache: dict, pos: jax.Array,
              cfg: ModelConfig, step: StepConfig) -> tuple[jax.Array, dict]:
    import dataclasses
    step = dataclasses.replace(step, inference=True)
    tokens = batch["tokens"]
    if cfg.family == "encdec":
        return encdec.decode(params, tokens, cache, pos, cfg, step)
    if cfg.family == "granite_hybrid":
        return granite_hybrid.decode(params, tokens, cache, pos, cfg, step)
    if cfg.family in ("ssm", "hybrid"):
        return hybrid.decode(params, tokens, cache, pos, cfg, step)
    return transformer.lm_decode(params, tokens, cache, pos, cfg, step,
                                 image_embeds=batch.get("image_embeds"))


def cache_shapes(cfg: ModelConfig, shape: WorkloadShape) -> dict:
    """ShapeDtypeStruct tree for the decode cache of one workload cell."""
    B = shape.global_batch
    length = cache_len(cfg, shape)
    if cfg.family == "encdec":
        return encdec.cache_shapes(cfg, B, length)
    if cfg.family == "granite_hybrid":
        return granite_hybrid.cache_shapes(cfg, B, length)
    if cfg.family in ("ssm", "hybrid"):
        return hybrid.cache_shapes(cfg, B, length)
    if cfg.family == "vlm":
        n_cross = cfg.n_layers // cfg.cross_attn_every
        n_self = cfg.n_layers - n_cross
        return {"attn": transformer.kv_cache_spec(
            cfg, B, length, layers=n_self).shape_tree()}
    return {"attn": transformer.kv_cache_spec(cfg, B, length).shape_tree()}


def cache_logical(cfg: ModelConfig) -> dict:
    kv_logical = layers.KVCacheSpec(1, 1, 1, 1, 1, jnp.bfloat16).logical
    if cfg.family == "encdec":
        return encdec.cache_logical(cfg)
    if cfg.family == "granite_hybrid":
        return granite_hybrid.cache_logical(cfg)
    if cfg.family in ("ssm", "hybrid"):
        return hybrid.cache_logical(cfg)
    return {"attn": kv_logical}


def extend_cache(cache: dict, extra: int) -> dict:
    """Grow every attention KV cache by ``extra`` slots (prefill allocates
    prompt-length caches; serving needs room for generated tokens)."""

    def walk(node):
        if isinstance(node, dict):
            if set(node) == {"k", "v", "pos"}:
                pad_kv = [(0, 0)] * node["k"].ndim
                pad_kv[-2] = (0, extra)
                pad_pos = [(0, 0)] * node["pos"].ndim
                pad_pos[-1] = (0, extra)
                return {
                    "k": jnp.pad(node["k"], pad_kv),
                    "v": jnp.pad(node["v"], pad_kv),
                    "pos": jnp.pad(node["pos"], pad_pos, constant_values=-1),
                }
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(cache)


def cache_init(cfg: ModelConfig, shape: WorkloadShape) -> dict:
    """Zero-initialized cache (smoke tests / real serving)."""
    shapes = cache_shapes(cfg, shape)

    def init_leaf(s: jax.ShapeDtypeStruct, path_is_pos: bool):
        if path_is_pos:
            return jnp.full(s.shape, -1, jnp.int32)
        return jnp.zeros(s.shape, s.dtype)

    def walk(node, key=""):
        if isinstance(node, dict):
            return {k: walk(v, k) for k, v in node.items()}
        return init_leaf(node, key == "pos")

    return walk(shapes)
