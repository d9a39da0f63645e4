"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init).

Mesh layout (TPU v5e pods of 256 chips):
  single pod : (data=16, model=16)
  multi-pod  : (pod=2, data=16, model=16)  — "pod" is pure DP; the gradient
               all-reduce over "pod" is the only traffic that crosses the
               inter-pod links.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over however many devices this host actually has (tests,
    the CPU training example)."""
    n = len(jax.devices())
    model = max(1, min(model, n))
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
