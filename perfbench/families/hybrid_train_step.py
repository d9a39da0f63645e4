"""Sessions over execution knobs (the SSD chunk, flash tiles, remat) of
the granite-4.0-h hybrid's training step: the program's
``model_step_family("train_step", ...)`` at the configuration's widths,
depth, batch and sequence length, as ``train_step.py`` runs granite-3.

The verdict's worth is its model FLOP/s utilization: the FLOPs the step
requires (``perfbench.hybrid_counts.train_step_flops``) over consecutive
steps of the verdict's compiled step. The check compares its loss, and
the norm of each gradient leaf's difference from the reference's, on
weights and tokens drawn from the seed, with the plain float32 reference
(``perfbench.granite4h_ref``). The session machinery and the verdict's
timing are ``train_step.py``'s, by import.
"""

from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp

from perfbench import counts, granite4h_ref, hybrid_counts
from perfbench.check import Compared
from perfbench.families import train_step
from perfbench.families.kernel import _key


def model_config(model: dict):
    """The program's ``ModelConfig``; JSON lists become tuples, so that
    the configuration hashes as the program's caches require."""
    from repro.models.config import ModelConfig
    return ModelConfig(**{k: tuple(v) if isinstance(v, list) else v
                          for k, v in model.items()})


class Family(train_step.Family):

    def __init__(self, config: dict, traffic: dict):
        from benchmarks.common import model_step_family
        from repro.core import grid

        self.config = config
        self.traffic = traffic
        self.limits = traffic["limits"]
        self.model = model = config["model"]
        self.batch, self.seq = config["batch"], config["seq_len"]
        self.arch = model_config(model)
        self.space = grid(**{k: tuple(v)
                             for k, v in traffic["space"].items()})
        self.benchmark = model_step_family("train_step", self.arch,
                                           batch_size=self.batch,
                                           seq_len=self.seq)
        self.step_flops = hybrid_counts.train_step_flops(model, self.batch,
                                                         self.seq)
        # one call of each kernel: one attention layer's forward, one
        # Mamba layer's scan
        self.flash_flops_per_call = counts.flash_flops(
            self.batch, model["n_heads"], self.seq, model["head_dim"],
            causal=True)
        self.flash_bytes_per_call = counts.flash_bytes(
            self.batch, model["n_heads"], model["n_kv_heads"], self.seq,
            model["head_dim"])
        heads = hybrid_counts.ssm_heads(model)
        # the kernel is bound by its bytes at every chunk of the space, so
        # its roofline does not depend on the chunk a call ran at
        self.ssd_flops_per_call = hybrid_counts.ssd_flops(
            self.batch, self.seq, heads, model["ssm_head_dim"],
            model["ssm_state"], min(hybrid_counts.STEP_CHUNK, self.seq))
        self.ssd_bytes_per_call = hybrid_counts.ssd_bytes(
            self.batch, self.seq, heads, model["ssm_head_dim"],
            model["ssm_state"])
        self._compiled: dict[str, object] = {}

    def check(self, seed: int, sessions, rates, *, control: bool = False,
              steps: dict | None = None) -> list[Compared]:
        """Each verdict's step against the reference, on the seed's weights
        and tokens. ``loss_gap`` is the worst |loss - ref| / |ref|;
        ``grad_gap`` the worst leaf's ||g - g_ref|| over the larger of
        ||g_ref|| and the median leaf's, over the leaves that are not
        nought to rounding. ``control`` puts the reference one precision
        lower (float8 matmul operands) in the step's place, through the
        same comparison; ``steps`` (label -> ``step(params, batch)``)
        replaces the verdicts' compiled steps."""
        from repro.models import api
        from repro.models.params import materialize

        key = _key(seed)
        defs = api.param_defs(self.arch)
        params = jax.jit(lambda k: materialize(k, defs))(
            jax.random.fold_in(key, 1))
        tokens = jax.random.randint(jax.random.fold_in(key, 2),
                                    (self.batch, self.seq), 0,
                                    self.model["vocab_size"], jnp.int32)
        if control:
            steps = {"control": lambda p, b: granite4h_ref.loss_and_grads(
                p, b["tokens"], self.model, low=jnp.float8_e4m3fn)}
        elif steps is None:
            steps = {label: self._compiled[label]
                     for label in self._verdicts(sessions)}
        loss_gaps, grad_gaps = [], []
        for step in steps.values():
            loss, grads = step(params, {"tokens": tokens})
            loss = float(loss)
            ref_loss, ref_norms, gaps = granite4h_ref.compare(
                params, tokens, self.model, grads)
            del grads
            median = statistics.median(ref_norms.values())
            loss_gaps.append(abs(loss - ref_loss) / abs(ref_loss))
            grad_gaps += [gaps[p] / max(v, median)
                          for p, v in ref_norms.items()
                          if v >= train_step.NEGLIGIBLE_LEAF * median]
        del params
        return [Compared("loss_gap", train_step._worst(loss_gaps),
                         self.limits["loss_gap"]),
                Compared("grad_gap", train_step._worst(grad_gaps),
                         self.limits["grad_gap"])]
