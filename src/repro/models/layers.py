"""Shared model layers (functional, param-tree based).

Conventions:
  * weights store contraction dims first: ``wq (D, H, Dh)``, ``wo (H, Dh, D)``;
  * every ParamDef carries logical axis names consumed by
    ``repro.distributed.sharding`` (TP on "heads"/"mlp"/"vocab", FSDP on
    "embed");
  * attention exposes a full-sequence path (train/prefill; flash kernel or
    jnp reference) and a one-token decode path over a position-tagged KV
    cache (supports both linear and rolling/sliding-window caches).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..distributed.actctx import shard_act
from ..kernels.flash_attention import flash_attention
from ..kernels.platform import interpret_mode
from .config import ModelConfig
from .params import ParamDef

# ---------------------------------------------------------------------------
# Scan-unroll context: ``cost_analysis`` counts a lax.scan body ONCE, so the
# roofline analysis lowers a small-depth model with every compute scan fully
# unrolled and extrapolates per-layer costs (launch/dryrun.py). All compute
# scans in the model zoo go through ``xscan`` so one flag flips them all.
# ---------------------------------------------------------------------------

_UNROLL_SCANS = False


class unroll_scans:
    """Context manager: trace with all model scans fully unrolled."""

    def __enter__(self):
        global _UNROLL_SCANS
        self._prev = _UNROLL_SCANS
        _UNROLL_SCANS = True

    def __exit__(self, *exc):
        global _UNROLL_SCANS
        _UNROLL_SCANS = self._prev


def xscan(body, init, xs, length=None):
    return jax.lax.scan(body, init, xs, length=length,
                        unroll=True if _UNROLL_SCANS else 1)

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def norm_defs(cfg: ModelConfig, d: int | None = None) -> ParamDef:
    return ParamDef(shape=(d or cfg.d_model,), logical=("embed_r",),
                    init="ones", dtype=cfg.jdtype)


def apply_norm(scale: jax.Array, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    x32 = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    x32 = x32 * jax.lax.rsqrt(var + cfg.norm_eps)
    return (x32 * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------


def _rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, H, S, Dh); positions: (S,) or (B, S)."""
    dh = x.shape[-1]
    freqs = _rope_freqs(dh, theta)                       # (Dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    if angles.ndim == 2:                                 # (S, Dh/2)
        angles = angles[None, None]                      # (1, 1, S, Dh/2)
    else:                                                # (B, S, Dh/2)
        angles = angles[:, None]                         # (B, 1, S, Dh/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    """Whisper-style sinusoidal table (computed, not learned)."""
    return sinusoidal_at(jnp.arange(n, dtype=jnp.float32), d)


def sinusoidal_at(positions: jax.Array, d: int) -> jax.Array:
    """Sinusoidal encoding for an arbitrary positions array -> (..., d)."""
    pos = positions.astype(jnp.float32)[..., None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)
    angle = pos / jnp.power(10000.0, dim / d)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def attention_defs(cfg: ModelConfig, layers: int | None = None,
                   kv_from: int | None = None) -> dict:
    """Param tree for one (stack of) attention layer(s).

    ``layers``: if given, stack with a leading "layers" axis for lax.scan.
    ``kv_from``: width of the kv source (cross-attention); default d_model.
    """
    D, H, Hk, Dh = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_)
    Dkv = kv_from or D
    lead = (layers,) if layers else ()
    lax_ = ("layers",) if layers else ()

    def w(shape, logical):
        return ParamDef(shape=lead + shape, logical=lax_ + logical,
                        dtype=cfg.jdtype)

    return {
        "wq": w((D, H, Dh), ("embed", "heads", "head_dim")),
        "wk": w((Dkv, Hk, Dh), ("embed", "kv_heads", "head_dim")),
        "wv": w((Dkv, Hk, Dh), ("embed", "kv_heads", "head_dim")),
        "wo": w((H, Dh, D), ("heads", "head_dim", "embed")),
    }


ACT_BSD = ("batch", "act_seq", "act_embed")
ACT_QHEADS = ("batch", "heads", "act_seq", "head_dim")
ACT_KVHEADS = ("batch", "kv_heads", "act_seq", "head_dim")


def _qkv(p: dict, x: jax.Array, kv_x: jax.Array):
    q = shard_act(jnp.einsum("bsd,dhk->bhsk", x, p["wq"]), ACT_QHEADS)
    k = shard_act(jnp.einsum("bsd,dhk->bhsk", kv_x, p["wk"]), ACT_KVHEADS)
    v = shard_act(jnp.einsum("bsd,dhk->bhsk", kv_x, p["wv"]), ACT_KVHEADS)
    return q, k, v


def attention_full(p: dict, x: jax.Array, cfg: ModelConfig, *,
                   kv_x: Optional[jax.Array] = None, causal: bool = True,
                   rope: bool = True, window: Optional[int] = None,
                   use_flash: bool = False, block_q: int = 512,
                   block_k: int = 512) -> jax.Array:
    """Full-sequence attention (train / prefill). x: (B, S, D)."""
    kv_src = x if kv_x is None else kv_x
    q, k, v = _qkv(p, x, kv_src)
    if rope:
        pos_q = jnp.arange(x.shape[1])
        pos_k = jnp.arange(kv_src.shape[1])
        q = apply_rope(q, pos_q, cfg.rope_theta)
        k = apply_rope(k, pos_k, cfg.rope_theta)
    if use_flash and kv_x is None:
        out = flash_attention(q, k, v, causal=causal, window=window,
                              bq=block_q, bk=block_k,
                              interpret=interpret_mode())
    else:
        out = _attend(q, k, v, causal=causal and kv_x is None, window=window)
    return shard_act(jnp.einsum("bhsk,hkd->bsd", out, p["wo"]), ACT_BSD)


def _expand_kv(k: jax.Array, h: int) -> jax.Array:
    """GQA: replicate kv heads up to the q-head count so every tensor in the
    attention math carries the TP-sharded "heads" dim (kv_heads rarely
    divides the model axis; q heads usually do — DESIGN.md §5)."""
    hkv = k.shape[1]
    if hkv == h:
        return k
    return shard_act(jnp.repeat(k, h // hkv, axis=1), ACT_QHEADS)


# Sequences at or above this length use the q-chunked online path so the
# (S, S) score matrix never materializes (the XLA analog of the Pallas
# flash kernel's VMEM tiling; on TPU the kernel path replaces this).
_CHUNK_THRESHOLD = 2048
_Q_CHUNK = 1024


def _attend(q, k, v, *, causal: bool, window: Optional[int]) -> jax.Array:
    """jnp attention with GQA head expansion; q-chunked for long sequences."""
    b, h, sq, dh = q.shape
    k = _expand_kv(k, h)
    v = _expand_kv(v, h)
    if sq >= _CHUNK_THRESHOLD and sq % _Q_CHUNK == 0:
        return _attend_chunked(q, k, v, causal=causal, window=window)
    return _attend_direct(q, k, v, causal=causal, window=window)


def _attend_direct(q, k, v, *, causal: bool, window: Optional[int],
                   q_offset=None) -> jax.Array:
    b, h, sq, dh = q.shape
    sk = k.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(dh)
    if causal or window is not None:
        if q_offset is None:
            q_pos = jnp.arange(sq)[:, None] + (sk - sq)  # align ends
        else:
            q_pos = jnp.arange(sq)[:, None] + q_offset
        k_pos = jnp.arange(sk)[None, :]
        mask = jnp.ones((sq, sk), dtype=bool)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32))
    return shard_act(out.astype(q.dtype), ACT_QHEADS)


def _attend_chunked(q, k, v, *, causal: bool,
                    window: Optional[int]) -> jax.Array:
    """Scan over query chunks: live score slab is (B, H, qc, S) instead of
    (B, H, S, S). The chunk body is checkpointed so the backward pass
    re-derives its probs instead of stashing them per chunk."""
    b, h, sq, dh = q.shape
    qc = _Q_CHUNK
    nq = sq // qc
    q_chunks = jnp.moveaxis(q.reshape(b, h, nq, qc, dh), 2, 0)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def body(_, inp):
        q_c, i = inp
        out = _attend_direct(q_c, k, v, causal=causal, window=window,
                             q_offset=i * qc)
        return None, out

    _, ys = xscan(body, None, (q_chunks, jnp.arange(nq)))
    return jnp.moveaxis(ys, 0, 2).reshape(b, h, sq, dh)


# ---- decode path ----------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KVCacheSpec:
    """Layout of one layer-stack's KV cache: (L, B, Hkv, S, Dh) k/v plus a
    position tag per slot (supports rolling window caches)."""

    layers: int
    batch: int
    kv_heads: int
    length: int
    head_dim: int
    dtype: object

    def shape_tree(self) -> dict:
        kv = jax.ShapeDtypeStruct(
            (self.layers, self.batch, self.kv_heads, self.length,
             self.head_dim), self.dtype)
        pos = jax.ShapeDtypeStruct((self.layers, self.batch, self.length),
                                   jnp.int32)
        return {"k": kv, "v": kv, "pos": pos}

    def init_tree(self) -> dict:
        shapes = self.shape_tree()
        return {
            "k": jnp.zeros(shapes["k"].shape, self.dtype),
            "v": jnp.zeros(shapes["v"].shape, self.dtype),
            "pos": jnp.full(shapes["pos"].shape, -1, jnp.int32),
        }

    @property
    def logical(self) -> dict:
        kv = ("layers", "cache_batch", "kv_heads", "cache_seq", "head_dim")
        return {"k": kv, "v": kv, "pos": ("layers", "cache_batch", "cache_seq")}


def attention_decode(p: dict, x: jax.Array, layer_cache: dict,
                     pos: jax.Array, cfg: ModelConfig, *,
                     rope: bool = True,
                     window: Optional[int] = None) -> tuple[jax.Array, dict]:
    """One-token decode. x: (B, 1, D); layer_cache holds this layer's
    {k, v, pos} slices (B, Hkv, S, Dh) / (B, S). Returns (y, new_cache)."""
    q, k_new, v_new = _qkv(p, x, x)                      # (B, *, 1, Dh)
    if rope:
        pos_b = jnp.full((x.shape[0], 1), pos, jnp.int32)
        q = apply_rope(q, pos_b, cfg.rope_theta)
        k_new = apply_rope(k_new, pos_b, cfg.rope_theta)
    length = layer_cache["k"].shape[2]
    # Linear cache (length == max seq): slot == pos. Rolling/window cache
    # (length == window): slot wraps; staleness is handled by the pos tags.
    slot = jnp.asarray(pos % length, jnp.int32)
    k = jax.lax.dynamic_update_slice_in_dim(layer_cache["k"],
                                            k_new.astype(layer_cache["k"].dtype),
                                            slot, axis=2)
    v = jax.lax.dynamic_update_slice_in_dim(layer_cache["v"],
                                            v_new.astype(layer_cache["v"].dtype),
                                            slot, axis=2)
    pos_tags = jax.lax.dynamic_update_slice_in_dim(
        layer_cache["pos"],
        jnp.full((x.shape[0], 1), pos, jnp.int32), slot, axis=1)

    b, h, _, dh = q.shape
    k_exp = _expand_kv(k, h)
    v_exp = _expand_kv(v, h)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k_exp.astype(jnp.float32)) / math.sqrt(dh)
    valid = (pos_tags >= 0) & (pos_tags <= pos)
    if window is not None:
        valid &= (pos - pos_tags) < window
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v_exp.astype(jnp.float32))
    out = out.astype(x.dtype)
    y = jnp.einsum("bhsk,hkd->bsd", out, p["wo"])
    return y, {"k": k, "v": v, "pos": pos_tags}


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / plain)
# ---------------------------------------------------------------------------


def mlp_defs(cfg: ModelConfig, layers: int | None = None) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    lead = (layers,) if layers else ()
    lax_ = ("layers",) if layers else ()

    def w(shape, logical):
        return ParamDef(shape=lead + shape, logical=lax_ + logical,
                        dtype=cfg.jdtype)

    if cfg.mlp_type == "plain":
        return {"w_up": w((D, F), ("embed", "mlp")),
                "w_down": w((F, D), ("mlp", "embed"))}
    return {"w_gate": w((D, F), ("embed", "mlp")),
            "w_up": w((D, F), ("embed", "mlp")),
            "w_down": w((F, D), ("mlp", "embed"))}


def _act(x: jax.Array, kind: str) -> jax.Array:
    if kind == "gelu":
        return jax.nn.gelu(x)
    return jax.nn.silu(x)


def apply_mlp(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    mlp_hidden = ("batch", "act_seq", "mlp")
    if cfg.mlp_type == "plain":
        h = _act(shard_act(jnp.einsum("bsd,df->bsf", x, p["w_up"]),
                           mlp_hidden), cfg.act)
        return shard_act(jnp.einsum("bsf,fd->bsd", h, p["w_down"]), ACT_BSD)
    g = _act(shard_act(jnp.einsum("bsd,df->bsf", x, p["w_gate"]),
                       mlp_hidden), cfg.act)
    u = shard_act(jnp.einsum("bsd,df->bsf", x, p["w_up"]), mlp_hidden)
    return shard_act(jnp.einsum("bsf,fd->bsd", g * u, p["w_down"]), ACT_BSD)


# ---------------------------------------------------------------------------
# Embedding + LM head + loss
# ---------------------------------------------------------------------------


def embedding_defs(cfg: ModelConfig) -> dict:
    # The table is 2D-sharded (vocab -> model TP, d_model -> data FSDP):
    # vocab-only sharding left a full-size f32 gradient all-reduce + table
    # all-gather in the HLO (12.6GB each for command-r; §Perf iteration 2).
    out = {"table": ParamDef(shape=(cfg.vocab_padded, cfg.d_model),
                             logical=("vocab", "embed"), init="embed",
                             scale=0.02, dtype=cfg.jdtype)}
    if not cfg.tie_embeddings:
        out["lm_head"] = ParamDef(shape=(cfg.d_model, cfg.vocab_padded),
                                  logical=("embed", "vocab"),
                                  dtype=cfg.jdtype)
    return out


def embed_tokens(p: dict, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    # One-hot-free lookup; GSPMD partitions the gather over the vocab-sharded
    # table via mask + all-reduce (verified in the dry-run HLO).
    return shard_act(jnp.take(p["table"], tokens, axis=0), ACT_BSD)


def logits_fn(p: dict, h: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", h, p["table"])
    return jnp.einsum("bsd,dv->bsv", h, p["lm_head"])


def cross_entropy_loss(p_embed: dict, h: jax.Array, targets: jax.Array,
                       cfg: ModelConfig, *, chunk: int = 512,
                       mask: jax.Array | None = None) -> jax.Array:
    """CE over (B, S) targets, chunked over the sequence so the
    (B, chunk, V) logits slab bounds activation memory (a hillclimbing
    lever; see §Perf). ``mask`` (B, S) in {0,1} weights positions."""
    b, s, _ = h.shape
    chunk = min(chunk, s)
    n_chunks = s // chunk
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    h_c = h[:, :n_chunks * chunk].reshape(b, n_chunks, chunk, -1)
    t_c = targets[:, :n_chunks * chunk].reshape(b, n_chunks, chunk)
    m_c = mask[:, :n_chunks * chunk].reshape(b, n_chunks, chunk)
    h_c = jnp.moveaxis(h_c, 1, 0)
    t_c = jnp.moveaxis(t_c, 1, 0)
    m_c = jnp.moveaxis(m_c, 1, 0)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def body(acc, xs):
        hc, tc, mc = xs                              # (B, chunk, D), (B, chunk)
        logits = shard_act(logits_fn(p_embed, hc, cfg),
                           ("batch", None, "vocab")).astype(jnp.float32)
        # mask padded vocab rows with an elementwise iota compare — an
        # .at[vocab_size:].set() would cross shard boundaries of the
        # vocab-sharded dim and force a full-logits all-gather (38.9GB for
        # granite train_4k; see EXPERIMENTS.md §Perf iteration 1)
        if cfg.vocab_padded != cfg.vocab_size:
            col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
            logits = jnp.where(col < cfg.vocab_size, logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return acc + jnp.sum((lse - gold) * mc.astype(jnp.float32)), None

    total, _ = xscan(body, jnp.zeros((), jnp.float32), (h_c, t_c, m_c))
    return total / jnp.maximum(jnp.sum(mask), 1.0)


def next_token_targets(tokens: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Keep S intact (chunking/sharding divisibility): targets are tokens
    rolled left; the final position is masked out of the loss."""
    b, s = tokens.shape
    targets = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones((b, s), jnp.float32).at[:, -1].set(0.0)
    return targets, mask
