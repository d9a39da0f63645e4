"""Whole-model workloads as jittable, costable callables.

The tuner's benchmarks (DGEMM, TRIAD) reproduce the paper; this module
turns the *models* already in the repo into the same shape of object: a
named, deterministic, jit-compatible callable with concrete example
arguments. That one handle feeds three consumers:

- ``benchmarks/common.py`` registers train/decode steps as audited,
  tunable benchmarks (the flash-attention tile sizes in
  :class:`~repro.models.transformer.StepConfig` are the search space);
- ``repro.obs.attribution`` lowers the callable, walks its optimized
  HLO per-op, and places every op on the empirical roofline;
- tests/CI smoke the whole path on CPU with the tiny default config.

Everything here is CPU-safe: the default config is a 2-layer toy model,
inputs come from a fixed PRNG key, and nothing allocates until
:func:`build_workload` is called.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

from .config import ModelConfig, WorkloadShape
from .transformer import StepConfig

__all__ = [
    "ModelWorkload",
    "TINY_CONFIG",
    "WORKLOAD_NAMES",
    "build_workload",
    "train_step_fn",
    "workload_static_cost",
]

# Small enough to compile in seconds on CPU, big enough that dot ops
# dominate the HLO (the attribution tables should not be all-reshape).
TINY_CONFIG = ModelConfig(
    name="tiny-dense",
    family="dense",
    n_layers=2,
    d_model=64,
    n_heads=4,
    n_kv_heads=2,
    d_ff=128,
    vocab_size=512,
    head_dim=16,
    norm="rmsnorm",
    dtype="float32",
)

_TINY_BATCH = 2
_TINY_SEQ = 64

WORKLOAD_NAMES = ("train_step", "prefill_step", "decode_step", "dgemm")


@dataclasses.dataclass(frozen=True)
class ModelWorkload:
    """One named workload: a pure jittable ``fn`` plus concrete ``args``.

    ``fn(*args)`` is what gets timed, lowered, and attributed; ``args``
    are real device arrays (deterministic — fixed PRNG key) so repeated
    builds of the same workload hash to the same executable.
    """

    name: str
    kind: str                    # train | prefill | decode | kernel
    fn: Callable
    args: tuple
    cfg: Optional[ModelConfig]   # None for raw-kernel workloads (dgemm)
    step: Optional[StepConfig]
    shape: Optional[WorkloadShape]
    declared_flops: Optional[float] = None  # analytic, when one exists

    def jit(self):
        import jax

        return jax.jit(self.fn)

    def compiled(self):
        """Lower + compile once (AOT); callers reuse for text and cost."""
        from repro.core.profiling import compiling

        with compiling("workload"):
            return self.jit().lower(*self.args).compile()


def _tiny_shape(kind: str, batch: int, seq: int) -> WorkloadShape:
    return WorkloadShape(name=f"tiny_{kind}", seq_len=seq,
                         global_batch=batch, kind=kind)


@functools.lru_cache(maxsize=None)
def _materialized(cfg: ModelConfig):
    import jax

    from . import api
    from .params import materialize

    return materialize(jax.random.PRNGKey(0), api.param_defs(cfg))


def _tokens(batch: int, seq: int, vocab: int):
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(1)
    return jax.random.randint(key, (batch, seq), 0, vocab, jnp.int32)


def _model_batch(cfg: ModelConfig, shape: WorkloadShape) -> dict:
    """Concrete input batch matching ``config.input_specs``."""
    import jax
    import jax.numpy as jnp

    seq = shape.seq_len if shape.kind != "decode" else 1
    batch: dict = {"tokens": _tokens(shape.global_batch, seq,
                                     cfg.vocab_size)}
    if cfg.family == "encdec":
        batch["frames"] = jnp.zeros(
            (shape.global_batch, cfg.n_frames, cfg.d_enc), cfg.jdtype)
    if cfg.family == "vlm":
        batch["image_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (shape.global_batch, cfg.n_image_tokens, cfg.d_model),
            cfg.jdtype)
    return batch


def train_step_fn(cfg: ModelConfig, step: StepConfig) -> Callable:
    """``(params, batch) -> (loss, grads)`` of one training step; takes
    arrays or, to compile without allocating, ``ShapeDtypeStruct``s."""
    import jax

    from . import api

    def train_step(params, batch):
        loss, grads = jax.value_and_grad(
            lambda p: api.loss_fn(p, batch, cfg, step))(params)
        return loss, grads

    return train_step


def _build_train(cfg: ModelConfig, step: StepConfig,
                 batch_size: int, seq: int) -> ModelWorkload:
    shape = _tiny_shape("train", batch_size, seq)
    params = _materialized(cfg)
    batch = _model_batch(cfg, shape)
    return ModelWorkload(name="train_step", kind="train",
                         fn=train_step_fn(cfg, step), args=(params, batch),
                         cfg=cfg, step=step, shape=shape)


def _build_prefill(cfg: ModelConfig, step: StepConfig,
                   batch_size: int, seq: int) -> ModelWorkload:
    from . import api

    shape = _tiny_shape("prefill", batch_size, seq)
    params = _materialized(cfg)
    batch = _model_batch(cfg, shape)

    def prefill_step(params, batch):
        return api.prefill_fn(params, batch, cfg, step)

    return ModelWorkload(name="prefill_step", kind="prefill",
                         fn=prefill_step, args=(params, batch), cfg=cfg,
                         step=step, shape=shape)


def _build_decode(cfg: ModelConfig, step: StepConfig,
                  batch_size: int, seq: int) -> ModelWorkload:
    import jax.numpy as jnp

    from . import api

    shape = _tiny_shape("decode", batch_size, seq)
    params = _materialized(cfg)
    batch = _model_batch(cfg, shape)
    cache = api.cache_init(cfg, shape)
    pos = jnp.int32(0)

    def decode_step(params, batch, cache, pos):
        return api.decode_fn(params, batch, cache, pos, cfg, step)

    return ModelWorkload(name="decode_step", kind="decode", fn=decode_step,
                         args=(params, batch, cache, pos), cfg=cfg,
                         step=step, shape=shape)


def _build_dgemm(m: int, n: int, k: int) -> ModelWorkload:
    """Square-ish DGEMM with an exact analytic FLOP count (2·m·n·k) —
    the calibration workload for attribution math (tests pin the
    attributed FLOPs to this declaration within 1%)."""
    import jax
    import jax.numpy as jnp

    a = jax.random.normal(jax.random.PRNGKey(3), (m, k), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(4), (k, n), jnp.float32)

    def dgemm(a, b):
        return jnp.dot(a, b)

    return ModelWorkload(name="dgemm", kind="kernel", fn=dgemm,
                         args=(a, b), cfg=None, step=None, shape=None,
                         declared_flops=2.0 * m * n * k)


def _count_flash_backward(cfg: ModelConfig) -> None:
    """Counter ``flash.bwd_pallas_calls`` of one train step program built
    with ``use_flash``: the attention layers whose backward lowers the
    Pallas flash kernels (every layer of the dense and MoE decoders, the
    attention layers of the granite-4.0-h assembly)."""
    from ..obs.metrics import metrics

    if cfg.family in ("dense", "moe"):
        layers = cfg.n_layers
    elif cfg.family == "granite_hybrid":
        from .granite_hybrid import n_mixers
        layers = n_mixers(cfg)[1]
    else:
        return
    metrics().inc("flash.bwd_pallas_calls", layers)


def build_workload(name: str, arch: "str | ModelConfig | None" = None, *,
                   step: Optional[StepConfig] = None,
                   batch_size: int = _TINY_BATCH, seq_len: int = _TINY_SEQ,
                   m: int = 128, n: int = 128, k: int = 128,
                   ) -> ModelWorkload:
    """Build one named workload with concrete inputs.

    ``arch`` selects a smoke-scale architecture from ``repro.configs``
    by name (e.g. ``"mixtral_8x22b"`` → its SMOKE config), or is a
    :class:`ModelConfig` built as given (``repro.configs.get(name)`` is
    the published size); the default is the in-module
    :data:`TINY_CONFIG` dense toy. ``step`` carries the
    execution knobs — including the Pallas flash-attention tile sizes —
    so a tuner can rebuild the same workload under different configs.
    """
    if name not in WORKLOAD_NAMES:
        raise ValueError(
            f"unknown workload {name!r}; expected one of {WORKLOAD_NAMES}")
    if name == "dgemm":
        return _build_dgemm(m, n, k)
    if arch is None:
        cfg = TINY_CONFIG
    elif isinstance(arch, ModelConfig):
        cfg = arch
    else:
        from repro.configs import get_smoke

        cfg = get_smoke(arch)
    step = step or StepConfig(remat=False)
    if cfg.family == "granite_hybrid":
        from .granite_hybrid import count_build
        count_build(cfg, step)
    if name == "train_step" and step.use_flash:
        _count_flash_backward(cfg)
    builder = {"train_step": _build_train, "prefill_step": _build_prefill,
               "decode_step": _build_decode}[name]
    return builder(cfg, step, batch_size, seq_len)


def workload_static_cost(workload: ModelWorkload):
    """Compiler-reported cost of one workload call (shared audit helper).

    This is the *same* number the benchmark registration declares as its
    work term and the GFLOP/s conversion divides by, so the workload
    audit (MS101) checks the shared formula against the trace rather
    than an analytic approximation that drifts on tiny models.
    """
    from repro.lint.workload import trace_cost

    return trace_cost(workload.fn, workload.args)
