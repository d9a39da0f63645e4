"""DGEMM F_p sessions in bfloat16: the program's DGEMM invocation factory
(``benchmarks.common.dgemm_invocation_factory``) driven with the traffic's
``dtype`` over the configuration's (n, m, k) grid. The operands are drawn
on the device in bfloat16 by the program's generator; the product is
summed in float32 on the MXU and returned in bfloat16.

The reference computes exactly that: the same bfloat16 operands, their
products (exact in float32) summed in float32 at the highest precision,
the sum rounded to bfloat16. The control computes the same product one
precision lower: operands rounded to float8_e4m3's 4 exponent and 3
mantissa bits, float32 sums, a bfloat16 result. The rounding is
``lax.reduce_precision``: on a TPU, XLA drops a round trip through
``astype(float8_e4m3fn)`` as excess precision, and such a control reads
exactly the program's answer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.families import dgemm

DTYPE = jnp.bfloat16


def benchmark(cfg: dict):
    from benchmarks.common import dgemm_invocation_factory
    return dgemm_invocation_factory(cfg["n"], cfg["m"], cfg["k"], dtype=DTYPE)


def _shapes(cfg: dict):
    return (jax.ShapeDtypeStruct((cfg["n"], cfg["k"]), DTYPE),
            jax.ShapeDtypeStruct((cfg["k"], cfg["m"]), DTYPE))


def precompile(cfg: dict) -> None:
    """The operand generator's and the dot's executables, as the program's
    ``dgemm_precompile`` makes them for float32."""
    from benchmarks.common import _dgemm_draw
    from repro.core import default_cache
    cache = default_cache()
    _dgemm_draw(cache, cfg["n"], cfg["m"], cfg["k"], DTYPE)
    cache.compile(jnp.dot, _shapes(cfg))


def audit_spec(cfg: dict):
    from benchmarks.common import dgemm_flops
    from repro.lint import WorkloadSpec
    n, m, k = cfg["n"], cfg["m"], cfg["k"]
    return WorkloadSpec(fn=jnp.dot, args=_shapes(cfg),
                        work=dgemm_flops(n, m, k), unit="flops",
                        dtype="bfloat16", name=f"dgemm_bf16[{n}x{m}x{k}]")


benchmark.precompile = precompile
benchmark.audit_spec = audit_spec


class Family(dgemm.Family):
    name = "dgemm_bf16"

    def __init__(self, config: dict, traffic: dict):
        super().__init__(config, traffic)
        if jnp.dtype(traffic["dtype"]) != DTYPE:
            raise ValueError(f"{self.name}: traffic dtype {traffic['dtype']}")
        self.benchmark = benchmark

    def shapes(self, cfg: dict):
        return _shapes(cfg)

    def operands(self, key, cfg: dict):
        ka, kb = jax.random.split(key)
        return (jax.random.normal(ka, (cfg["n"], cfg["k"]), DTYPE),
                jax.random.normal(kb, (cfg["k"], cfg["m"]), DTYPE))

    @staticmethod
    def reference(a, b):
        return _product(a, b, exponent_bits=8, mantissa_bits=7)

    @staticmethod
    def control(a, b):
        return _product(a, b, exponent_bits=4, mantissa_bits=3)


@functools.partial(jax.jit, static_argnames=("exponent_bits",
                                             "mantissa_bits"))
def _product(a, b, *, exponent_bits: int, mantissa_bits: int):
    """Operands rounded to the given exponent and mantissa bits (8 and 7
    leave bfloat16 as it is), products summed in float32 at the highest
    precision, the sum rounded to bfloat16."""
    a, b = (jax.lax.reduce_precision(x.astype(jnp.float32), exponent_bits,
                                     mantissa_bits) for x in (a, b))
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST).astype(DTYPE)
