"""Shared benchmark utilities: CSV emission, host matmul/triad objectives.

All benches print ``name,us_per_call,derived`` CSV rows (harness contract)
plus richer per-table output to stderr-safe stdout sections.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (Direction, EvaluationSettings, SearchSpace,
                        default_cache, grid, steady_sampler, timed_sampler)
from repro.core.profiling import trace_instant
from repro.core.searchspace import doubling_from, powers_of_two
from repro.lint import WorkloadSpec

CSV_ROWS: list[tuple[str, float, str]] = []


def emit(name: str, us_per_call: float, derived: str) -> None:
    CSV_ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.3f},{derived}")


def print_table(title: str, rows: list[dict]) -> None:
    print(f"\n## {title}")
    if not rows:
        print("(empty)")
        return
    keys = list(rows[0].keys())
    print(" | ".join(f"{k:>14s}" for k in keys))
    for r in rows:
        print(" | ".join(f"{str(r.get(k, '')):>14s}" for k in keys))


# ---------------------------------------------------------------------------
# Host benchmark objectives (the paper's DGEMM / TRIAD on this machine)
# ---------------------------------------------------------------------------
#
# Work terms are computed by the shared helpers below and declared to the
# workload audit (``repro.lint``) through each benchmark's ``audit_spec``
# attribute — the audit traces the *same kernel* with the *same formula*
# the invocation factory uses, so a drifted declaration cannot hide.


def dgemm_flops(n: int, m: int, k: int) -> float:
    """Raw FLOPs of one (n,k)x(k,m) matmul — the DGEMM work term."""
    return 2.0 * n * m * k


def triad_length(n_bytes: int, dtype=jnp.float32) -> int:
    """Vector length for a TRIAD working set of ~n_bytes (three arrays)."""
    return max(1024, n_bytes // (3 * jnp.dtype(dtype).itemsize))


def triad_moved_bytes(n_bytes: int, dtype=jnp.float32) -> float:
    """Raw bytes moved per TRIAD call (read A, read B, write C)."""
    return 3.0 * triad_length(n_bytes, dtype) * jnp.dtype(dtype).itemsize


def triad_kernel(x, y):
    """TRIAD C = A + 3B — shared between the timed factory and the audit."""
    return x + 3.0 * y


def _dgemm_data(n: int, m: int, k: int, seed: int, dtype):
    """Seeded operand generation on the host, then a device transfer.

    Deliberately *not* ``jax.random``: eager threefry compiles a fresh
    XLA kernel per operand shape (~150ms measured on host CPU), so a
    tuning campaign — where every trial visits a cold shape — would pay
    a data-generation compile it never amortizes. A seeded numpy
    Generator is deterministic, shape-oblivious and compile-free, and
    GEMM is data-oblivious, so operand provenance cannot shift the
    measurement."""
    rng = np.random.default_rng(seed)
    a = np.asarray(rng.standard_normal((n, k)), dtype=jnp.dtype(dtype))
    b = np.asarray(rng.standard_normal((k, m)), dtype=jnp.dtype(dtype))
    return jax.device_put(a), jax.device_put(b)


def dgemm_invocation_factory(n: int, m: int, k: int,
                             dtype=jnp.float32, *, exec_cache=None,
                             sampler: str = "timed", batch=None,
                             reuse_data: bool = False) -> Callable:
    """One 'program invocation' of the DGEMM benchmark: allocate fresh
    matrices, pre-heat the kernel (the paper pre-heats with one untimed
    call), return a GFLOP/s sampler.

    The kernel is served by the AOT
    :class:`~repro.core.exec_cache.ExecutableCache` (``exec_cache``,
    default the process-wide one): the first invocation of a config
    compiles, every later one reuses the executable — the pre-heat call
    stays, so first-timed-sample semantics are unchanged.

    ``sampler="steady"`` returns a batched
    :class:`~repro.core.evaluator.steady_sampler` (B async dispatches,
    one sync per observation); the auto-calibrated B is cached across
    invocations so calibration runs once per config. ``reuse_data=True``
    allocates operand data once per *config* instead of once per
    invocation — sound for GEMM on normal data because its runtime is
    data-oblivious, and it removes the dominant setup cost of short
    trials.

    The data seed is derived from the matrix dimensions plus an invocation
    counter — deterministic across reruns (reproducible cache keys and
    resumable sessions) while still varying between invocations."""
    flops = dgemm_flops(n, m, k)
    invocation = itertools.count()
    cache = exec_cache if exec_cache is not None else default_cache()
    state = {"batch": batch, "data": None}

    def factory():
        seed = (n * 1_000_003 + m * 10_007 + k * 101
                + next(invocation)) % (2 ** 31)
        if reuse_data and state["data"] is not None:
            a, b = state["data"]
        else:
            a, b = _dgemm_data(n, m, k, seed, dtype)
            if reuse_data:
                state["data"] = (a, b)
        f = cache.compile(jnp.dot, (a, b))
        jax.block_until_ready(f(a, b))      # pre-heat
        trace_instant("workload", kernel="dgemm", n=n, m=m, k=k,
                      flops=flops, dtype=str(jnp.dtype(dtype)))
        if sampler == "steady":
            s = steady_sampler(lambda: f(a, b), work=flops / 1e9,
                               sync=jax.block_until_ready,
                               batch=state["batch"])
            state["batch"] = s.batch       # calibrate once per config
            return s

        def run():
            jax.block_until_ready(f(a, b))

        return timed_sampler(run, work=flops / 1e9)  # GFLOP/s

    return factory


def triad_invocation_factory(n_bytes: int, dtype=jnp.float32, *,
                             exec_cache=None) -> Callable:
    """TRIAD C = A + 3B over vectors totalling ~n_bytes working set."""
    n = triad_length(n_bytes, dtype)
    moved = triad_moved_bytes(n_bytes, dtype)
    cache = exec_cache if exec_cache is not None else default_cache()

    def factory():
        key = jax.random.key(n % (2 ** 31))
        a = jax.random.normal(jax.random.fold_in(key, 1), (n,), dtype)
        b = jax.random.normal(jax.random.fold_in(key, 2), (n,), dtype)
        f = cache.compile(triad_kernel, (a, b))
        jax.block_until_ready(f(a, b))
        trace_instant("workload", kernel="triad", n=n, bytes=moved,
                      dtype=str(jnp.dtype(dtype)))

        def run():
            jax.block_until_ready(f(a, b))

        return timed_sampler(run, work=moved / 1e9)  # GB/s

    return factory


def dgemm_space(quick: bool = True) -> SearchSpace:
    """The paper's reduced DGEMM space (Sec. IV-A), scaled to this host:
    leading dims as multiples of 2 (500-doubling ladder) plus powers of 2."""
    if quick:
        return grid(n=(256, 512, 1024), m=(256, 512, 1024),
                    k=(64, 128, 256, 512))
    return grid(n=doubling_from(500, 4000) + powers_of_two(512, 2048),
                m=doubling_from(500, 4000) + powers_of_two(512, 2048),
                k=powers_of_two(64, 2048))


def paper_settings(quick: bool = True) -> EvaluationSettings:
    """Table I scaled for CI runtime: same structure, smaller budget."""
    if quick:
        return EvaluationSettings(max_invocations=4, max_iterations=60,
                                  max_time_s=1.5,
                                  direction=Direction.MAXIMIZE)
    return EvaluationSettings(max_invocations=10, max_iterations=200,
                              max_time_s=10.0,
                              direction=Direction.MAXIMIZE)


def session_settings(benchmark: str, quick: bool = True,
                     ) -> EvaluationSettings:
    """Settings of a CI-pruned tuning session (``scripts/tune.py``,
    ``chip_smoke.py``): the paper's budgets with CI convergence and both
    prunes on. TRIAD keeps convergence but never prunes: each working
    set probes its own memory subsystem, so the sizes are measurements,
    not competitors, and pruning a slow stream against a faster one
    would cache a truncated bandwidth and drop that subsystem from the
    roofline report."""
    prune = benchmark != "triad"
    return dataclasses.replace(paper_settings(quick),
                               use_ci_convergence=True,
                               use_inner_prune=prune,
                               use_outer_prune=prune)


def dgemm_benchmark(cfg: dict) -> Callable:
    return dgemm_invocation_factory(cfg["n"], cfg["m"], cfg["k"])


def triad_benchmark(cfg: dict) -> Callable:
    return triad_invocation_factory(cfg["n_bytes"])


# -- pipelined-compilation hooks (Tuner.tune submits these to a background
#    CompilePipeline so trial k+1 compiles while trial k measures) ----------

def dgemm_precompile(cfg: dict) -> None:
    """Warm the executable cache for one DGEMM config — ShapeDtypeStructs
    only, nothing is allocated or executed."""
    n, m, k = cfg["n"], cfg["m"], cfg["k"]
    cache = default_cache()
    cache.compile(jnp.dot,
                  (jax.ShapeDtypeStruct((n, k), jnp.float32),
                   jax.ShapeDtypeStruct((k, m), jnp.float32)))


def triad_precompile(cfg: dict) -> None:
    n = triad_length(cfg["n_bytes"])
    cache = default_cache()
    cache.compile(triad_kernel,
                  (jax.ShapeDtypeStruct((n,), jnp.float32),
                   jax.ShapeDtypeStruct((n,), jnp.float32)))


dgemm_benchmark.precompile = dgemm_precompile
triad_benchmark.precompile = triad_precompile


def synthetic_benchmark(cfg: dict) -> Callable:
    """Instant quadratic objective (optimum x=7, score 100) for
    smoke-testing session mechanics without timing noise.

    The three CLI benchmarks are module-level functions (not lambdas) so
    they pickle into ``ProcessPoolBackend`` workers. ``synthetic`` is
    deliberately *not* auditable (no device kernel to trace): it
    exercises the linter's MS100 info path.
    """
    mu = 100.0 - (cfg["x"] - 7) ** 2

    def factory():
        return lambda: mu

    return factory


# ---------------------------------------------------------------------------
# Shape-sweep families (repro.sweep): shape -> benchmark factory
# ---------------------------------------------------------------------------
#
# A *family* specializes the objective to one problem shape; the sweep
# campaign calls it once per grid point. Family closures capture the shape
# (not picklable) — drive campaigns with the serial or thread backend.

#: shared tile ladder of the sweep config space (powers of two, so every
#: k_chunk divides every power-of-two K)
SWEEP_TILES = (16, 32, 64, 128, 256, 512)


def gemm_shape_space(quick: bool = True) -> SearchSpace:
    """The (M, N[, K]) shape grid a sweep campaign tunes: a 3×3 grid of
    the paper's host-scaled DGEMM dims for CI, the full power-of-two
    ladder (with K) otherwise."""
    if quick:
        return grid(m=(256, 512, 1024), n=(256, 512, 1024))
    return grid(m=powers_of_two(256, 4096), n=powers_of_two(256, 4096),
                k=powers_of_two(64, 1024))


def sweep_config_space() -> SearchSpace:
    """Per-shape tunables shared by the sweep families."""
    return grid(bm=SWEEP_TILES, bn=SWEEP_TILES)


def synthetic_gemm_family(shape: dict) -> Callable:
    """Instant shape-conditioned objective for sweep mechanics tests.

    The optimal (bm, bn) tile *level* moves linearly with the shape's
    position on the (log-scale) 256..1024 ladder, and the score is
    quadratic around it — so in the joint encoder's features (config
    level index × log-normalized shape coordinate, both linear) the whole
    surface is exactly degree-2. The ridge surrogate can therefore
    represent it exactly, which makes oracle-interpolation acceptance
    tests sharp: any gap to the true optimum is a harness bug, not model
    bias. Peak score is 100 when the ideal tile lands on a ladder level.
    """
    levels = {v: i for i, v in enumerate(SWEEP_TILES)}
    top = len(SWEEP_TILES) - 1

    def ideal(dim_value: float, lo: float = 256.0, hi: float = 1024.0):
        t = (math.log(dim_value) - math.log(lo)) / (math.log(hi)
                                                    - math.log(lo))
        return top * min(max(t, 0.0), 1.0)

    ia, ib = ideal(shape["m"]), ideal(shape.get("n", shape["m"]))

    def bench(cfg: dict) -> Callable:
        mu = (100.0 - (levels[cfg["bm"]] - ia) ** 2
              - 0.5 * (levels[cfg["bn"]] - ib) ** 2)

        def factory():
            return lambda: mu

        return factory

    return bench


def chunked_dgemm_kernel(a3, b3):
    """DGEMM with the K axis pre-split into (chunks, k_chunk) — one
    einsum contracting both: identical 2·M·N·K flops to ``jnp.dot``,
    different loop/layout structure (the tunable). Shared between the
    timed factory and the workload audit."""
    return jnp.einsum("mck,ckn->mn", a3, b3)


def chunked_dgemm_family(shape: dict) -> Callable:
    """Real measured DGEMM family: C = A·B with A's K axis split into
    ``k_chunk``-wide chunks (snapped down to K when larger). Scores are
    GFLOP/s over the same useful work regardless of chunking, so configs
    compare on time alone."""
    m, n, k = shape["m"], shape["n"], shape.get("k", 256)
    flops = dgemm_flops(m, n, k)
    cache = default_cache()

    def bench(cfg: dict) -> Callable:
        kc = min(cfg["k_chunk"], k)
        chunks = k // kc
        invocation = itertools.count()

        def factory():
            seed = (m * 1_000_003 + n * 10_007 + k * 101 + kc * 13
                    + next(invocation)) % (2 ** 31)
            key = jax.random.key(seed)
            a = jax.random.normal(jax.random.fold_in(key, 1),
                                  (m, chunks, kc), jnp.float32)
            b = jax.random.normal(jax.random.fold_in(key, 2),
                                  (chunks, kc, n), jnp.float32)
            f = cache.compile(chunked_dgemm_kernel, (a, b))
            jax.block_until_ready(f(a, b))      # pre-heat
            trace_instant("workload", kernel="dgemm_sweep", m=m, n=n, k=k,
                          k_chunk=kc, flops=flops)

            def run():
                jax.block_until_ready(f(a, b))

            return timed_sampler(run, work=flops / 1e9)  # GFLOP/s

        return factory

    def sweep_audit_spec(cfg: dict) -> WorkloadSpec:
        kc = min(cfg["k_chunk"], k)
        chunks = k // kc
        return WorkloadSpec(
            fn=chunked_dgemm_kernel,
            args=(jax.ShapeDtypeStruct((m, chunks, kc), jnp.float32),
                  jax.ShapeDtypeStruct((chunks, kc, n), jnp.float32)),
            work=flops, unit="flops", dtype="float32",
            name=f"dgemm_sweep[{m}x{n}x{k}/kc{kc}]")

    def sweep_precompile(cfg: dict) -> None:
        kc = min(cfg["k_chunk"], k)
        chunks = k // kc
        cache.compile(chunked_dgemm_kernel,
                      (jax.ShapeDtypeStruct((m, chunks, kc), jnp.float32),
                       jax.ShapeDtypeStruct((chunks, kc, n), jnp.float32)))

    bench.audit_spec = sweep_audit_spec
    bench.precompile = sweep_precompile
    return bench


def sweep_chunk_space(k_max: int = 512) -> SearchSpace:
    """Config space of :func:`chunked_dgemm_family`."""
    return grid(k_chunk=powers_of_two(16, k_max))


# ---------------------------------------------------------------------------
# Whole-model workloads as tuning objectives (ROADMAP: models ∩ tuner)
# ---------------------------------------------------------------------------
#
# A model step is a benchmark like any other: the config carries the
# StepConfig execution knobs (Pallas flash-attention tiles, remat), the
# score is GFLOP/s over the step's *compiler-reported* work — the same
# helper the audit checks, so the declared-vs-traced lint (MS101) pins
# the conversion constant instead of trusting an analytic 6ND estimate
# that drifts on tiny configs.


def model_step_space(quick: bool = True) -> SearchSpace:
    """Execution-knob space of a whole-model step. ``use_flash`` gates
    the Pallas path (interpret mode on CPU), the tiles only bind when it
    is on — kept in one grid so the tuner sees the interaction."""
    if quick:
        return grid(use_flash=(0, 1), flash_block_q=(64, 128),
                    flash_block_k=(64, 128))
    return grid(use_flash=(0, 1), flash_block_q=(64, 128, 256, 512),
                flash_block_k=(64, 128, 256, 512), remat=(0, 1))


def model_step_workload(workload: str, arch, cfg: dict, *,
                batch_size: int, seq_len: int):
    """Build one workload under a tuner config (shared by the timed
    factory, the audit spec, and the precompile hook)."""
    from repro.models.transformer import StepConfig
    from repro.models.workloads import build_workload

    step = StepConfig(
        use_flash=bool(cfg.get("use_flash", 0)),
        flash_block_q=int(cfg.get("flash_block_q", 512)),
        flash_block_k=int(cfg.get("flash_block_k", 512)),
        remat=bool(cfg.get("remat", 0)))
    return build_workload(workload, arch, step=step,
                          batch_size=batch_size, seq_len=seq_len)


def model_step_family(workload: str, arch=None, *,
                      batch_size: int = 2, seq_len: int = 64) -> Callable:
    """Benchmark family for one whole-model step (train/prefill/decode).

    ``workload`` names a :mod:`repro.models.workloads` builder; ``arch``
    picks a smoke-scale architecture by name, or is a ``ModelConfig``
    built as given (default: the tiny dense toy). The
    returned ``bench(cfg)`` exposes ``audit_spec`` and ``precompile``
    like the microbenchmarks, so model steps ride the same lint, AOT
    cache, and pipelined-compile machinery.
    """
    from repro.models.workloads import workload_static_cost

    arch_name = getattr(arch, "name", arch) or "tiny-dense"

    def bench(cfg: dict) -> Callable:
        w = model_step_workload(workload, arch, cfg,
                        batch_size=batch_size, seq_len=seq_len)
        flops = workload_static_cost(w).flops
        state: dict = {"compiled": None}

        def factory():
            if state["compiled"] is None:
                state["compiled"] = w.compiled()
            f = state["compiled"]
            jax.block_until_ready(f(*w.args))   # pre-heat
            trace_instant("workload", kernel=workload,
                          arch=arch_name, flops=flops,
                          **{k: cfg[k] for k in sorted(cfg)})

            def run():
                jax.block_until_ready(f(*w.args))

            return timed_sampler(run, work=flops / 1e9)  # GFLOP/s

        return factory

    def model_audit_spec(cfg: dict) -> WorkloadSpec:
        w = model_step_workload(workload, arch, cfg,
                        batch_size=batch_size, seq_len=seq_len)
        return WorkloadSpec(
            fn=w.fn, args=w.args,
            work=workload_static_cost(w).flops, unit="flops",
            name=f"{workload}[{arch_name}"
                 f" b{batch_size} s{seq_len}]")

    def model_precompile(cfg: dict) -> None:
        w = model_step_workload(workload, arch, cfg,
                        batch_size=batch_size, seq_len=seq_len)
        w.compiled()

    bench.audit_spec = model_audit_spec
    bench.precompile = model_precompile
    bench.__name__ = f"model_step_{workload}"
    return bench


# -- workload audit declarations (repro.lint pass 1) ------------------------

def dgemm_audit_spec(cfg: dict) -> WorkloadSpec:
    n, m, k = cfg["n"], cfg["m"], cfg["k"]
    dtype = jnp.float32
    return WorkloadSpec(
        fn=jnp.dot,
        args=(jax.ShapeDtypeStruct((n, k), dtype),
              jax.ShapeDtypeStruct((k, m), dtype)),
        work=dgemm_flops(n, m, k), unit="flops", dtype="float32",
        name=f"dgemm[{n}x{m}x{k}]")


def triad_audit_spec(cfg: dict) -> WorkloadSpec:
    n_bytes = cfg["n_bytes"]
    dtype = jnp.float32
    n = triad_length(n_bytes, dtype)
    return WorkloadSpec(
        fn=triad_kernel,
        args=(jax.ShapeDtypeStruct((n,), dtype),
              jax.ShapeDtypeStruct((n,), dtype)),
        work=triad_moved_bytes(n_bytes, dtype), unit="bytes",
        dtype="float32", name=f"triad[{n_bytes}B]")


dgemm_benchmark.audit_spec = dgemm_audit_spec
triad_benchmark.audit_spec = triad_audit_spec

#: benchmarks `scripts/lint.py` audits (pass 1), with a sample config each
AUDITED_WORKLOADS: dict[str, tuple[Callable, dict]] = {
    "dgemm": (dgemm_benchmark, {"n": 256, "m": 256, "k": 64}),
    "triad": (triad_benchmark, {"n_bytes": 1 << 20}),
    "synthetic": (synthetic_benchmark, {"x": 7}),
    # one representative shape of the sweep family: the audit traces the
    # chunked kernel and must see exactly the 2mnk flops it declares
    "dgemm_sweep": (chunked_dgemm_family({"m": 256, "n": 256, "k": 256}),
                    {"k_chunk": 64}),
    # whole-model steps: work terms come from the compiler's own cost
    # analysis (shared helper), so the audit is a determinism check on
    # the GFLOP/s conversion rather than an analytic approximation
    "train_step": (model_step_family("train_step"),
                   {"use_flash": 0, "flash_block_q": 64,
                    "flash_block_k": 64}),
    "decode_step": (model_step_family("decode_step"),
                    {"use_flash": 0, "flash_block_q": 64,
                     "flash_block_k": 64}),
}
