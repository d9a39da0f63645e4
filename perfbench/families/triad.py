"""TRIAD B_a sessions: the program's registered ``triad_benchmark`` over
the configuration's working sets, C = A + 3B in float32. The reference is
the same sum in float32; the control computes it in bfloat16."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench import counts
from perfbench.families.kernel import KernelFamily


class Family(KernelFamily):
    name = "triad"
    peak_key = "hbm_bytes_per_s"
    err_name = "triad_err"

    def __init__(self, config: dict, traffic: dict):
        super().__init__(config, traffic)
        from benchmarks.common import triad_benchmark, triad_kernel
        self.benchmark = triad_benchmark
        self.kernel = triad_kernel
        self.sizes = config["triad"]["n_bytes"]

    def setup(self) -> None:
        """Besides the executables, one invocation of each config: the
        factory draws its operands with ``jax.random`` on the device, which
        compiles once per shape."""
        super().setup()
        for cfg in self.space.configs():
            self.benchmark(cfg)()

    def axes(self) -> dict:
        return {"n_bytes": self.sizes}

    def shapes(self, cfg: dict):
        n = counts.triad_length(cfg["n_bytes"])
        return (jax.ShapeDtypeStruct((n,), jnp.float32),
                jax.ShapeDtypeStruct((n,), jnp.float32))

    def work(self, cfg: dict) -> float:
        return counts.triad_bytes(cfg["n_bytes"])

    def operands(self, key, cfg: dict):
        n = counts.triad_length(cfg["n_bytes"])
        ka, kb = jax.random.split(key)
        return (jax.random.normal(ka, (n,), jnp.float32),
                jax.random.normal(kb, (n,), jnp.float32))

    @staticmethod
    @jax.jit
    def reference(a, b):
        return a + jnp.float32(3.0) * b

    @staticmethod
    @jax.jit
    def control(a, b):
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
        return a + jnp.bfloat16(3.0) * b
