"""Sessions over execution knobs (flash tiles, remat) of a whole-model
training step: the program's ``model_step_family("train_step", ...)`` at
the configuration's widths, depth, batch and sequence length.

The verdict's worth is its model FLOP/s utilization: the FLOPs the step
requires (``perfbench.counts.train_step_flops``) over consecutive steps of
the verdict's compiled step, ending in ``block_until_ready``.

The check drives the verdict's compiled step, the one the sessions timed,
on weights and tokens drawn from the seed, and compares its loss and the
norm of each gradient leaf with the plain float32 reference
(``perfbench.granite_ref``).
"""

from __future__ import annotations

import gc
import math
import statistics
import sys
import time

import jax
import jax.numpy as jnp

from perfbench import counts, granite_ref
from perfbench.check import Compared
from perfbench.families.kernel import _key

#: leaves whose reference gradient is under this share of the median
#: leaf's are nought to rounding and left out of the gradient comparison
NEGLIGIBLE_LEAF = 1e-3
#: steps timed back to back for the verdict's utilization
VERDICT_STEPS = 3


class Family:
    benchmark_name = "train_step"

    def __init__(self, config: dict, traffic: dict):
        from benchmarks.common import model_step_family
        from repro.core import grid
        from repro.models.config import ModelConfig

        self.config = config
        self.traffic = traffic
        self.limits = traffic["limits"]
        self.model = config["model"]
        self.batch, self.seq = config["batch"], config["seq_len"]
        self.arch = ModelConfig(**self.model)
        self.space = grid(**{k: tuple(v)
                             for k, v in traffic["space"].items()})
        self.benchmark = model_step_family("train_step", self.arch,
                                           batch_size=self.batch,
                                           seq_len=self.seq)
        self.step_flops = counts.train_step_flops(self.model, self.batch,
                                                  self.seq)
        self.flash_flops_per_call = counts.flash_flops(
            self.batch, self.model["n_heads"], self.seq,
            self.model["head_dim"], causal=True)
        self.flash_bytes_per_call = counts.flash_bytes(
            self.batch, self.model["n_heads"], self.model["n_kv_heads"],
            self.seq, self.model["head_dim"])
        self._compiled: dict[str, object] = {}

    def setup(self) -> None:
        """The program's weights on the device, then every config's step
        through the program's precompile hook (from the persistent compile
        cache after a cell's first run)."""
        from repro.models.workloads import _materialized
        jax.block_until_ready(_materialized(self.arch))
        for cfg in self.space.configs():
            self.benchmark.precompile(cfg)

    def _workload(self, cfg: dict):
        from benchmarks.common import model_step_workload
        return model_step_workload("train_step", self.arch, cfg,
                                   batch_size=self.batch, seq_len=self.seq)

    def _verdicts(self, sessions) -> dict[str, dict]:
        from perfbench.session import _label
        return {_label(s.result.best_config): s.result.best_config
                for s in sessions if not s.failed}

    def rates(self, seed: int) -> dict[str, float]:
        """Nothing to rank: the tiles' steps differ by less than the
        sessions' noise, so the verdict is not compared with a ranking."""
        return {}

    def verdict_metrics(self, sessions, rates, peaks) -> dict[str, float]:
        """Each verdict's step: compiled as the sessions compiled it, timed
        over consecutive steps on the program's own inputs."""
        from perfbench.session import _label
        mfu = {}
        for label, cfg in self._verdicts(sessions).items():
            w = self._workload(cfg)
            compiled = w.compiled()
            jax.block_until_ready(compiled(*w.args))
            times = []
            for _ in range(VERDICT_STEPS):
                t0 = time.perf_counter()
                jax.block_until_ready(compiled(*w.args))
                times.append(time.perf_counter() - t0)
            step_s = sum(times) / VERDICT_STEPS
            print(f"verdict {label}: step seconds {times}", file=sys.stderr)
            mfu[label] = 100.0 * self.step_flops / step_s / peaks["flops"]
            self._compiled[label] = compiled
            del w
        shares = [mfu[_label(s.result.best_config)]
                  for s in sessions if not s.failed]
        return {"verdict_mfu": sum(shares) / len(shares)} if shares else {}

    def release(self) -> None:
        """Free the program's weights before the check's own are made."""
        from repro.models.workloads import _materialized
        _materialized.cache_clear()
        gc.collect()

    def check(self, seed: int, sessions, rates, *, control: bool = False,
              steps: dict | None = None) -> list[Compared]:
        """Loss and per-leaf gradient norms of each verdict's step against
        the reference, on the seed's weights and tokens. ``control`` puts
        the reference one precision lower (float8 matmul operands) in the
        step's place; ``steps`` (label -> ``step(params, batch)``) replaces
        the verdicts' compiled steps."""
        from repro.models import api
        from repro.models.params import materialize

        key = _key(seed)
        defs = api.param_defs(self.arch)
        params = jax.jit(lambda k: materialize(k, defs))(
            jax.random.fold_in(key, 1))
        tokens = jax.random.randint(jax.random.fold_in(key, 2),
                                    (self.batch, self.seq), 0,
                                    self.model["vocab_size"], jnp.int32)
        readings = []
        if control:
            readings.append(granite_ref.loss_and_grad_norms(
                params, tokens, self.model, low=jnp.float8_e4m3fn))
        else:
            if steps is None:
                steps = {label: self._compiled[label]
                         for label in self._verdicts(sessions)}
            for step in steps.values():
                loss, grads = step(params, {"tokens": tokens})
                readings.append((float(loss), granite_ref.tree_norms(grads)))
                del grads
        ref_loss, ref_norms = granite_ref.loss_and_grad_norms(
            params, tokens, self.model)
        del params
        median = statistics.median(ref_norms.values())
        kept = [p for p, v in ref_norms.items()
                if v >= NEGLIGIBLE_LEAF * median]
        loss_gap = _worst(abs(loss - ref_loss) / abs(ref_loss)
                          for loss, _ in readings)
        grad_gap = _worst(abs(norms[p] - ref_norms[p])
                          / max(ref_norms[p], median)
                          for _, norms in readings for p in kept)
        return [Compared("loss_gap", loss_gap, self.limits["loss_gap"]),
                Compared("grad_gap", grad_gap, self.limits["grad_gap"])]


def _worst(values) -> float:
    """The largest value; infinite where any is not a number."""
    values = list(values)
    if any(math.isnan(v) for v in values):
        return math.inf
    return max(values)
