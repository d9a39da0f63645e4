"""DGEMM F_p sessions: the program's registered ``dgemm_benchmark`` over
the configuration's (n, m, k) grid.

The configuration states float32 operands at JAX's default matmul
precision, which on the v5e MXU is one bfloat16 pass with float32
accumulation (on the CPU it is float32 throughout). The reference computes
exactly that: on a TPU the operands are rounded to bfloat16, and their
products (exact in float32) are summed in float32 at the highest
precision. The control computes the same product one precision lower:
bfloat16 operands and a bfloat16 result.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench import counts
from perfbench.families.kernel import KernelFamily


class Family(KernelFamily):
    name = "dgemm"
    peak_key = "flops"
    err_name = "gemm_err"

    def __init__(self, config: dict, traffic: dict):
        super().__init__(config, traffic)
        from benchmarks.common import dgemm_benchmark
        self.benchmark = dgemm_benchmark
        self.kernel = jnp.dot
        self.dims = config["dgemm"]

    def axes(self) -> dict:
        return {"n": self.dims["n"], "m": self.dims["m"], "k": self.dims["k"]}

    def shapes(self, cfg: dict):
        return (jax.ShapeDtypeStruct((cfg["n"], cfg["k"]), jnp.float32),
                jax.ShapeDtypeStruct((cfg["k"], cfg["m"]), jnp.float32))

    def work(self, cfg: dict) -> float:
        return counts.dgemm_flops(cfg["n"], cfg["m"], cfg["k"])

    def operands(self, key, cfg: dict):
        ka, kb = jax.random.split(key)
        return (jax.random.normal(ka, (cfg["n"], cfg["k"]), jnp.float32),
                jax.random.normal(kb, (cfg["k"], cfg["m"]), jnp.float32))

    @staticmethod
    def reference(a, b):
        return _reference(a, b, jax.default_backend() == "tpu")

    @staticmethod
    @jax.jit
    def control(a, b):
        return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))


@functools.partial(jax.jit, static_argnames="one_bf16_pass")
def _reference(a, b, one_bf16_pass: bool):
    if one_bf16_pass:
        a = a.astype(jnp.bfloat16).astype(jnp.float32)
        b = b.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)
