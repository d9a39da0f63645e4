"""DGEMM operands drawn on the device by the precompiled generator in
``benchmarks.common``: shapes and dtype, seeding, distribution, no
compile after ``dgemm_precompile``, and the ``operands.device_draws``
counter."""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import benchmarks.common as common  # noqa: E402
from repro.core import ExecutableCache  # noqa: E402
from repro.obs.metrics import metrics  # noqa: E402


@pytest.mark.parametrize("n,m,k", [(16, 24, 8), (32, 8, 16)])
def test_draw_has_the_config_shapes_in_float32(n, m, k):
    cache = ExecutableCache(fingerprint="test")
    a, b = common._dgemm_data(n, m, k, seed=3, dtype=jnp.float32,
                              cache=cache)
    assert a.shape == (n, k) and b.shape == (k, m)
    assert a.dtype == jnp.float32 and b.dtype == jnp.float32


def test_same_seed_is_bitwise_equal_and_invocations_differ():
    cache = ExecutableCache(fingerprint="test")
    a1, b1 = common._dgemm_data(16, 16, 8, seed=11, dtype=jnp.float32,
                                cache=cache)
    a2, b2 = common._dgemm_data(16, 16, 8, seed=11, dtype=jnp.float32,
                                cache=cache)
    assert np.array_equal(np.asarray(a1), np.asarray(a2))
    assert np.array_equal(np.asarray(b1), np.asarray(b2))
    assert not np.array_equal(np.asarray(a1)[:8], np.asarray(b1)[:, :8])

    drawn = []
    real = common._dgemm_data

    def spy(*args, **kw):
        out = real(*args, **kw)
        drawn.append(out)
        return out

    factory = common.dgemm_invocation_factory(16, 16, 8, exec_cache=cache)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(common, "_dgemm_data", spy)
        factory()
        factory()
    (a_first, b_first), (a_second, b_second) = drawn
    assert not np.array_equal(np.asarray(a_first), np.asarray(a_second))
    assert not np.array_equal(np.asarray(b_first), np.asarray(b_second))


def test_large_seed_draws():
    cache = ExecutableCache(fingerprint="test")
    a, _ = common._dgemm_data(8, 8, 8, seed=2 ** 31 - 1, dtype=jnp.float32,
                              cache=cache)
    assert np.isfinite(np.asarray(a)).all()


@pytest.mark.parametrize("seed", [0, 12345, 2 ** 31 - 1])
def test_draw_is_standard_normal(seed):
    cache = ExecutableCache(fingerprint="test")
    a, b = common._dgemm_data(256, 256, 256, seed=seed, dtype=jnp.float32,
                              cache=cache)
    for x in (np.asarray(a), np.asarray(b)):
        assert abs(float(x.mean())) < 0.05
        assert abs(float(x.std()) - 1.0) < 0.05


def test_precompile_leaves_nothing_to_compile(monkeypatch):
    cache = ExecutableCache(fingerprint="test")
    monkeypatch.setattr(common, "default_cache", lambda: cache)
    cfg = {"n": 16, "m": 24, "k": 8}
    common.dgemm_precompile(cfg)
    assert cache.stats.compiles == 2
    sample = common.dgemm_benchmark(cfg)()
    assert sample() > 0.0
    assert cache.stats.compiles == 2


def test_each_invocation_counts_one_device_draw():
    cache = ExecutableCache(fingerprint="test")
    factory = common.dgemm_invocation_factory(16, 16, 8, exec_cache=cache)
    for _ in range(3):
        before = metrics().counter("operands.device_draws")
        factory()
        assert metrics().counter("operands.device_draws") == before + 1


def test_reused_operands_draw_once_per_config():
    cache = ExecutableCache(fingerprint="test")
    factory = common.dgemm_invocation_factory(16, 16, 8, exec_cache=cache,
                                              reuse_data=True)
    before = metrics().counter("operands.device_draws")
    for _ in range(3):
        factory()
    assert metrics().counter("operands.device_draws") == before + 1
