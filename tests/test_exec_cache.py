"""AOT executable cache, compile pipeline, phase profiler, and the
batched steady-state sampler's conformance with the classic timed one."""

import threading
import time

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import (BatchCalibration, CompilePipeline,  # noqa: E402
                        EvaluationSettings, ExecutableCache, PhaseProfiler,
                        Tuner, calibrate_batch, grid, phase, steady_sampler,
                        timed_sampler)


def _add(a, b):
    return a + b


def _scale(a, s):
    return a * s


# ---------------------------------------------------------------------------
# ExecutableCache keying
# ---------------------------------------------------------------------------

def test_same_key_hits_different_shape_misses():
    cache = ExecutableCache(fingerprint="test")
    a = jnp.ones((4, 4))
    exe1 = cache.compile(_add, (a, a))
    exe2 = cache.compile(_add, (a, a))
    assert exe1 is exe2
    s = cache.stats
    assert (s.misses, s.hits, s.compiles) == (1, 1, 1)

    wide = jnp.ones((4, 8))
    cache.compile(_add, (wide, wide))        # new shape -> new executable
    assert cache.stats.compiles == 2


def test_dtype_changes_the_key():
    cache = ExecutableCache(fingerprint="test")
    cache.compile(_add, (jnp.ones((4,), jnp.float32),) * 2)
    cache.compile(_add, (jnp.ones((4,), jnp.int32),) * 2)
    assert cache.stats.compiles == 2


def test_static_config_changes_the_key_and_the_code():
    cache = ExecutableCache(fingerprint="test")
    a = jnp.ones((3,))
    exe2 = cache.compile(_scale, (a,), static={"s": 2})
    exe3 = cache.compile(_scale, (a,), static={"s": 3})
    assert cache.stats.compiles == 2         # config is compiled in
    assert float(exe2(a)[0]) == 2.0
    assert float(exe3(a)[0]) == 3.0


def test_device_fingerprint_is_part_of_the_key():
    c1 = ExecutableCache(fingerprint="hw-a")
    c2 = ExecutableCache(fingerprint="hw-b")
    a = jnp.ones((2, 2))
    assert c1.key_for(_add, (a, a)) != c2.key_for(_add, (a, a))


def test_shape_dtype_struct_lowers_without_allocating():
    cache = ExecutableCache(fingerprint="test")
    spec = jax.ShapeDtypeStruct((4, 4), jnp.float32)
    exe = cache.compile(_add, (spec, spec))
    a = jnp.ones((4, 4))
    assert float(exe(a, a)[0, 0]) == 2.0
    # a concrete-array call with the same shapes is the same executable
    assert cache.compile(_add, (a, a)) is exe
    assert cache.stats.compiles == 1


def test_already_jitted_fn_routes_through_lower():
    cache = ExecutableCache(fingerprint="test")
    jitted = jax.jit(_add)
    a = jnp.ones((2,))
    exe = cache.compile(jitted, (a, a))
    assert float(exe(a, a)[0]) == 2.0
    assert cache.stats.compiles == 1


# ---------------------------------------------------------------------------
# Eviction + failure semantics
# ---------------------------------------------------------------------------

def test_lru_eviction_bounds_live_executables():
    cache = ExecutableCache(capacity=2, fingerprint="test")
    for n in (2, 3, 4):
        a = jnp.ones((n,))
        cache.compile(_add, (a, a))
    s = cache.stats
    assert len(cache) <= 2
    assert s.evictions >= 1
    assert s.compiles == 3
    # the evicted (oldest) key recompiles, the fresh ones hit
    cache.compile(_add, (jnp.ones((2,)),) * 2)
    assert cache.stats.compiles == 4


def test_failed_compile_is_not_cached():
    cache = ExecutableCache(fingerprint="test")

    def bad(a):
        raise ValueError("boom")

    a = jnp.ones((2,))
    for _ in range(2):                       # both attempts raise: no
        with pytest.raises(ValueError):      # poisoned entry is left behind
            cache.compile(bad, (a,))
    assert len(cache) == 0
    assert cache.stats.compiles == 0


def test_concurrent_compiles_dedup_to_one():
    cache = ExecutableCache(fingerprint="test")
    a = jnp.ones((8, 8))
    n_threads = 8
    barrier = threading.Barrier(n_threads)
    results, errors = [], []

    def worker():
        try:
            barrier.wait()
            results.append(cache.compile(_add, (a, a)))
        except BaseException as e:  # pragma: no cover - failure detail
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert cache.stats.compiles == 1         # one owner, n-1 waiters
    assert all(r is results[0] for r in results)
    assert float(results[0](a, a)[0, 0]) == 2.0


# ---------------------------------------------------------------------------
# CompilePipeline
# ---------------------------------------------------------------------------

def test_pipeline_counts_and_failures():
    done = []
    with CompilePipeline() as pipe:
        pipe.submit(lambda: done.append(1))
        pipe.submit(lambda: 1 / 0)
        pipe.submit(lambda: done.append(2))
        assert pipe.drain(timeout=5.0)
        assert pipe.counts == (3, 2, 1)      # failures recorded, not raised
    assert done == [1, 2]
    with pytest.raises(RuntimeError):
        pipe.submit(lambda: None)            # closed


def test_tuner_pipelines_precompiles_for_fresh_configs():
    space = grid(x=(1.0, 2.0))
    settings = EvaluationSettings(max_invocations=1, max_iterations=2,
                                  max_time_s=30.0)
    precompiled = []

    def benchmark(cfg):
        def factory():
            def sample():
                time.sleep(0.02)             # give the worker headroom
                return cfg["x"]
            return sample
        return factory

    benchmark.precompile = lambda cfg: precompiled.append(dict(cfg))
    result = Tuner(space, settings).tune(benchmark, validate="off")
    assert sorted(c["x"] for c in precompiled) == [1.0, 2.0]
    assert result.n_precompiled == 2


def test_tuner_pipeline_off_and_missing_hook():
    space = grid(x=(1.0,))
    settings = EvaluationSettings(max_invocations=1, max_iterations=1,
                                  max_time_s=30.0)

    def plain(cfg):
        return lambda: (lambda: cfg["x"])

    r = Tuner(space, settings).tune(plain, validate="off")
    assert r.n_precompiled == 0              # no precompile hook: no pipeline

    seen = []

    def hooked(cfg):
        return lambda: (lambda: cfg["x"])

    hooked.precompile = lambda cfg: seen.append(cfg)
    r = Tuner(space, settings).tune(hooked, validate="off", pipeline="off")
    assert r.n_precompiled == 0 and seen == []


def test_factory_compiles_once_across_invocations():
    """N invocations of one config compile the kernel and the operand
    generator exactly once each (factories that re-entered jax.jit per
    invocation compiled N times)."""
    from benchmarks.common import dgemm_invocation_factory, dgemm_operands

    cache = ExecutableCache(fingerprint="test")
    factory = dgemm_invocation_factory(16, 16, 8, exec_cache=cache)
    for _ in range(4):
        sample = factory()
        assert sample() > 0.0                # GFLOP/s
    s = cache.stats
    assert s.compiles == 2
    assert s.hits == 6
    assert cache.key_for(jnp.dot,
                         (jax.ShapeDtypeStruct((16, 8), jnp.float32),
                          jax.ShapeDtypeStruct((8, 16), jnp.float32))) in cache
    assert cache.key_for(dgemm_operands,
                         (jax.ShapeDtypeStruct((), jnp.uint32),),
                         {"n": 16, "m": 16, "k": 8, "dtype": "float32"}) in cache


# ---------------------------------------------------------------------------
# PhaseProfiler
# ---------------------------------------------------------------------------

def test_phase_is_noop_without_installed_profiler():
    with phase("anything"):
        pass                                 # must not raise or record


def test_profiler_buckets_count_and_accumulate():
    with PhaseProfiler() as prof:
        for _ in range(3):
            with phase("setup"):
                pass
        with phase("setup"):
            with phase("compile"):           # nesting: both buckets record
                pass
    doc = prof.to_json()
    assert doc["setup"]["count"] == 4
    assert doc["compile"]["count"] == 1
    assert doc["setup"]["seconds"] >= 0.0


def test_profiler_sees_spans_from_worker_threads():
    with PhaseProfiler() as prof:
        def work():
            with phase("compile"):
                pass
        t = threading.Thread(target=work)
        t.start()
        t.join()
    assert prof.to_json()["compile"]["count"] == 1


# ---------------------------------------------------------------------------
# steady_sampler vs timed_sampler conformance (deterministic virtual device)
# ---------------------------------------------------------------------------

class VirtualDevice:
    """Async device model on a virtual clock: dispatch enqueues free of
    charge, sync pays queued kernel time plus a fixed wake-up cost."""

    def __init__(self, t_exec_s: float, sync_overhead_s: float):
        self.t_exec_s = t_exec_s
        self.sync_overhead_s = sync_overhead_s
        self.now = 0.0
        self.pending = 0

    def clock(self):
        return self.now

    def dispatch(self):
        self.pending += 1
        return "handle"

    def sync(self, handle):
        self.now += self.pending * self.t_exec_s + self.sync_overhead_s
        self.pending = 0

    def blocking_call(self):
        self.sync(self.dispatch())


def test_steady_and_timed_conform_on_sync_light_workload():
    # per-call sync is 1% of kernel time: both samplers agree within the
    # paper's 2% budget, and batching tightens steady further
    dev = VirtualDevice(t_exec_s=10e-3, sync_overhead_s=0.1e-3)
    work = 1.0
    timed = timed_sampler(dev.blocking_call, work=work, clock=dev.clock)
    steady = steady_sampler(dev.dispatch, work=work, sync=dev.sync,
                            batch=8, clock=dev.clock)
    t, s = timed(), steady()
    true_rate = work / dev.t_exec_s
    assert abs(s - t) / t < 0.02
    assert abs(s - true_rate) < abs(t - true_rate)


def test_steady_recovers_rate_timed_cannot_on_tiny_kernels():
    # sync wake-up is 2x kernel time — the regime steady_sampler exists
    # for: the timed sampler is ~66% low, the batched one within 2%
    dev = VirtualDevice(t_exec_s=0.05e-3, sync_overhead_s=0.1e-3)
    work = 1.0
    timed = timed_sampler(dev.blocking_call, work=work, clock=dev.clock)
    steady = steady_sampler(dev.dispatch, work=work, sync=dev.sync,
                            batch=256, clock=dev.clock)
    true_rate = work / dev.t_exec_s
    assert timed() < 0.5 * true_rate
    assert abs(steady() - true_rate) / true_rate < 0.02


def test_calibrate_batch_fits_the_virtual_device_exactly():
    dev = VirtualDevice(t_exec_s=1e-3, sync_overhead_s=0.2e-3)
    cal = calibrate_batch(dev.dispatch, dev.sync, clock=dev.clock,
                          overhead_frac=0.02)
    assert cal.t_exec_s == pytest.approx(1e-3)
    assert cal.overhead_s == pytest.approx(0.2e-3)
    # smallest B with overhead/(B*t_exec) <= 2%: ceil(0.2/0.02) = 10
    assert cal.batch == 10

    free = VirtualDevice(t_exec_s=1e-3, sync_overhead_s=0.0)
    assert calibrate_batch(free.dispatch, free.sync,
                           clock=free.clock).batch == 1


def test_steady_sampler_autocalibrates_and_exposes_batch():
    dev = VirtualDevice(t_exec_s=1e-3, sync_overhead_s=0.2e-3)
    sample = steady_sampler(dev.dispatch, work=1.0, sync=dev.sync,
                            clock=dev.clock)
    assert sample.batch == 10
    assert sample() == pytest.approx(10.0 / (10 * 1e-3 + 0.2e-3))


def test_batch_calibration_dataclass_roundtrip():
    cal = BatchCalibration(batch=4, t_exec_s=1e-3, overhead_s=1e-4)
    assert (cal.batch, cal.t_exec_s, cal.overhead_s) == (4, 1e-3, 1e-4)
