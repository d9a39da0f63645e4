#!/usr/bin/env python
"""Harness self-benchmark: how fast is the measurement loop itself?

    PYTHONPATH=src python scripts/bench_harness.py            # measure + write
    PYTHONPATH=src python scripts/bench_harness.py --check    # validate baseline

The paper's search-time wins come from cutting sample counts; this
script watches the next term — *per-trial harness overhead*. A tuning
campaign evaluates each configuration for the first time, so trials are
compile-cold by nature: every session here runs over configs whose
shapes this process has never compiled, once through each harness
generation:

  legacy  the pre-PR idiom: ``jax.jit`` re-entered inside every
          invocation factory, operand data regenerated through eager
          ``jax.random`` every invocation, one blocking sync per timed
          sample (``timed_sampler``)
  fast    the shipping path: AOT ``ExecutableCache`` for kernels,
          pipelined compiles overlapping the previous trial's
          measurement, batched ``steady_sampler`` observations,
          host-side seeded data generation reused per config

and reports the **non-measured wall time per trial**::

    non_measured = session_wall - measured_s
    measured_s   = dispatch + sync phase-bucket seconds

where the *measured* seconds are exactly the samplers' own timed
windows, recorded by :class:`repro.core.PhaseProfiler` from inside
``timed_sampler``/``steady_sampler``. Everything else the session spent
— setup, tracing, compiling, data generation, pre-heats, bookkeeping —
is non-measured overhead. Both terms come from the same session, so the
accounting needs no external per-kernel reference time and no
cross-session subtraction (which would amplify run-to-run noise).

Each repetition draws a fresh set of cold shapes; legacy and fast get
interleaved, disjoint shape sets of the same size class so neither can
hit compilation caches warmed by the other. The per-mode result is the
median across repetitions.

The acceptance targets (ISSUE 8) are embedded in the JSON and enforced
by ``--check`` (schema + thresholds of the committed baseline — no
measurement, deterministic) and by the measuring run itself:

  * non-measured wall per trial: fast >= 3x lower than legacy on both
    the synthetic (tiny-kernel) and DGEMM families
  * batched ``steady_sampler`` agrees with unbatched ``timed_sampler``
    within 2% (the paper's error budget) on a DGEMM workload large
    enough that per-call sync wake-up (~0.1 ms on this host) is inside
    the budget for the unbatched sampler too
"""

from __future__ import annotations

import argparse
import itertools
import json
import pathlib
import statistics
import sys

_REPO = pathlib.Path(__file__).resolve().parent.parent
for p in (str(_REPO), str(_REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

BENCH_VERSION = 2
DEFAULT_JSON = "BENCH_harness.json"
MIN_SPEEDUP = 3.0        # ISSUE 8 acceptance: >=3x lower non-measured time
MAX_REL_DIFF = 0.02      # paper's 2% error budget for sampler agreement


# ---------------------------------------------------------------------------
# Measurement (imports jax lazily so --check stays dependency-free)
# ---------------------------------------------------------------------------

# Families: (name, fixed dims, k-generator params, steady batch).
# k varies per trial so every config is a genuinely cold shape; the two
# modes take interleaved k values from the same arithmetic progression,
# so their compile and kernel cost distributions match.
_FAMILIES = [
    # tiny kernel: measurement is ~15us/call, so the harness itself
    # dominates — the family that stresses overhead hardest
    ("synthetic", {"n": 64, "m": 64}, {"base": 32, "step": 4}, 64),
    # the paper's DGEMM at host scale: real measurement load per trial
    ("dgemm", {"n": 512, "m": 512}, {"base": 160, "step": 16}, 8),
]
_CONFIGS_PER_SESSION = 4


def _session_spaces(dims, kgen, rep):
    """Disjoint, interleaved cold-shape grids for (legacy, fast) at one
    repetition: 8 fresh k values, evens to legacy, odds to fast."""
    from repro.core import grid
    lo = rep * 2 * _CONFIGS_PER_SESSION
    ks = [kgen["base"] + kgen["step"] * (lo + j)
          for j in range(2 * _CONFIGS_PER_SESSION)]
    legacy = grid(n=(dims["n"],), m=(dims["m"],), k=tuple(ks[0::2]))
    fast = grid(n=(dims["n"],), m=(dims["m"],), k=tuple(ks[1::2]))
    return legacy, fast


def _legacy_benchmark(work_of):
    """The pre-PR invocation factory, verbatim idiom: fresh trace + fresh
    eagerly generated data every invocation, one sync per sample."""
    import jax
    import jax.numpy as jnp

    from repro.core import timed_sampler

    def benchmark(cfg):
        n, m, k = cfg["n"], cfg["m"], cfg["k"]
        flops = work_of(cfg)
        invocation = itertools.count()

        def factory():
            seed = (n * 1_000_003 + m * 10_007 + k * 101
                    + next(invocation)) % (2 ** 31)
            key = jax.random.key(seed)
            a = jax.random.normal(jax.random.fold_in(key, 1), (n, k))
            b = jax.random.normal(jax.random.fold_in(key, 2), (k, m))
            f = jax.jit(jnp.dot)   # lint: ok=MS207 — the legacy baseline under test
            jax.block_until_ready(f(a, b))      # pre-heat
            def run():
                jax.block_until_ready(f(a, b))
            return timed_sampler(run, work=flops / 1e9)

        return factory

    return benchmark


def _fast_benchmark(batch):
    """The shipping cached/batched factory (benchmarks.common)."""
    from benchmarks.common import dgemm_invocation_factory, dgemm_precompile

    def benchmark(cfg):
        return dgemm_invocation_factory(
            cfg["n"], cfg["m"], cfg["k"],
            sampler="steady", batch=batch, reuse_data=True)

    benchmark.precompile = dgemm_precompile
    return benchmark


def _session(benchmark, space, settings):
    """One profiled tuning session. The record is self-contained: wall
    and phase buckets come from the same run, and
    ``non_measured = wall - (dispatch + sync)`` subtracts exactly the
    samplers' own timed windows."""
    from repro.core import PhaseProfiler, Tuner

    prof = PhaseProfiler()
    with prof:
        result = Tuner(space, settings).tune(benchmark, validate="off")
    buckets = prof.to_json()
    measured = sum(buckets.get(p, {}).get("seconds", 0.0)
                   for p in ("dispatch", "sync"))
    wall = result.total_time_s
    trials = len(result.trials)
    return {
        "wall_s": round(wall, 6),
        "measured_s": round(measured, 6),
        "non_measured_s": round(max(wall - measured, 0.0), 6),
        "non_measured_per_trial_s": round(
            max(wall - measured, 0.0) / trials, 6),
        "trials": trials,
        "samples": result.total_samples,
        "n_precompiled": result.n_precompiled,
        "phases": buckets,
    }


def _run_family(name, dims, kgen, batch, settings, reps, work_of):
    runs = {"legacy": [], "fast": []}
    for rep in range(reps):
        legacy_space, fast_space = _session_spaces(dims, kgen, rep)
        order = [("legacy", _legacy_benchmark(work_of), legacy_space),
                 ("fast", _fast_benchmark(batch), fast_space)]
        if rep % 2:     # alternate order so drift cannot favour one mode
            order.reverse()
        for mode, benchmark, space in order:
            runs[mode].append(_session(benchmark, space, settings))

    def summarize(rs):
        med = statistics.median(r["non_measured_per_trial_s"] for r in rs)
        pick = min(rs, key=lambda r: abs(r["non_measured_per_trial_s"] - med))
        out = dict(pick)
        out["non_measured_per_trial_s"] = med   # median across repetitions
        out["reps"] = [r["non_measured_per_trial_s"] for r in rs]
        return out

    leg, fst = summarize(runs["legacy"]), summarize(runs["fast"])
    fst["batch"] = batch
    speedup = (leg["non_measured_per_trial_s"]
               / max(fst["non_measured_per_trial_s"], 1e-9))
    return {
        "configs_per_session": _CONFIGS_PER_SESSION,
        "sessions_per_mode": reps,
        "batch": batch,
        "legacy": leg,
        "fast": fst,
        "speedup_non_measured": round(speedup, 2),
    }


def _sampler_agreement(obs: int = 8, batch: int = 4) -> dict:
    """Batched vs unbatched score on a 2048^3 DGEMM: both samplers
    measure the same cached executable on the same data, observations
    interleaved in alternating order so frequency drift hits both
    streams alike. The kernel must be large enough for two reasons: the
    per-call sync wake-up the unbatched sampler necessarily includes
    (~0.1 ms on this host) must sit inside the 2% budget — on small
    kernels that wake-up *is* the divergence steady_sampler exists to
    remove — and single-observation frequency jitter (+-10% at ~15 ms
    on this host) must average out within one call (~140 ms here)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import _dgemm_data, dgemm_flops
    from repro.core import default_cache, steady_sampler, timed_sampler

    n = 2048
    a, b = _dgemm_data(n, n, n, seed=7, dtype=jnp.float32)
    f = default_cache().compile(jnp.dot, (a, b))
    jax.block_until_ready(f(a, b))      # warm
    work = dgemm_flops(n, n, n) / 1e9
    timed = timed_sampler(lambda: jax.block_until_ready(f(a, b)), work=work)
    steady = steady_sampler(lambda: f(a, b), work=work,
                            sync=jax.block_until_ready, batch=batch)
    timed(), steady()                   # one warm round each
    t_scores, s_scores = [], []
    for i in range(obs):
        if i % 2:
            s_scores.append(steady())
            t_scores.append(timed())
        else:
            t_scores.append(timed())
            s_scores.append(steady())
    t_med = statistics.median(t_scores)
    s_med = statistics.median(s_scores)
    rel = abs(s_med - t_med) / t_med
    return {"workload": f"dgemm[{n}x{n}x{n}]", "batch": batch,
            "observations": obs,
            "timed_gflops": round(t_med, 3),
            "steady_gflops": round(s_med, 3),
            "rel_diff": round(rel, 5)}


def measure(reps: int = 3) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.common import dgemm_flops
    from repro.core import Direction, EvaluationSettings

    # global first-use warmup on sacrificial shapes: pays jax's one-time
    # tracing/compilation machinery, attributed to neither mode
    key = jax.random.key(0)
    jax.block_until_ready(jax.random.normal(key, (48, 48)))
    jax.block_until_ready(jax.jit(jnp.dot)(jnp.ones((48, 40)),
                                           jnp.ones((40, 48))))

    def work_of(cfg):
        return dgemm_flops(cfg["n"], cfg["m"], cfg["k"])

    # fixed-count settings: both modes run the same trial structure
    settings = EvaluationSettings(max_invocations=3, max_iterations=8,
                                  max_time_s=60.0,
                                  direction=Direction.MAXIMIZE)
    families = {}
    for name, dims, kgen, batch in _FAMILIES:
        families[name] = _run_family(name, dims, kgen, batch,
                                     settings, reps, work_of)

    agreement = _sampler_agreement()
    ok = (all(f["speedup_non_measured"] >= MIN_SPEEDUP
              for f in families.values())
          and agreement["rel_diff"] <= MAX_REL_DIFF)
    return {
        "bench_version": BENCH_VERSION,
        "generated_by": "scripts/bench_harness.py",
        "protocol": ("cold-shape sessions (every trial compiles fresh, "
                     "the tuning-campaign regime); non_measured = wall - "
                     "(dispatch + sync phase buckets), i.e. wall minus "
                     "the samplers' own timed windows; median over "
                     "repetitions on disjoint interleaved shape sets"),
        "settings": {"max_invocations": settings.max_invocations,
                     "max_iterations": settings.max_iterations},
        "families": families,
        "agreement": agreement,
        "checks": {"min_speedup": MIN_SPEEDUP,
                   "max_rel_diff": MAX_REL_DIFF, "pass": ok},
    }


# ---------------------------------------------------------------------------
# Reporting / gating
# ---------------------------------------------------------------------------

def render(doc: dict) -> str:
    lines = ["harness self-benchmark:"]
    for name, fam in doc["families"].items():
        leg = fam["legacy"]["non_measured_per_trial_s"] * 1e3
        fst = fam["fast"]["non_measured_per_trial_s"] * 1e3
        lines.append(
            f"  {name:<10s} non-measured/trial: legacy {leg:8.3f} ms  "
            f"fast {fst:8.3f} ms  ({fam['speedup_non_measured']:.1f}x, "
            f"B={fam['batch']})")
    agr = doc["agreement"]
    lines.append(
        f"  agreement  timed {agr['timed_gflops']:.1f} vs steady "
        f"{agr['steady_gflops']:.1f} GFLOP/s on {agr['workload']} "
        f"(rel diff {agr['rel_diff'] * 100:.2f}%)")
    checks = doc["checks"]
    lines.append(
        f"  targets    >={checks['min_speedup']:g}x speedup, "
        f"<={checks['max_rel_diff'] * 100:g}% sampler divergence: "
        f"{'PASS' if checks['pass'] else 'FAIL'}")
    return "\n".join(lines)


def check(path: pathlib.Path) -> int:
    """Validate the committed baseline: schema + recorded thresholds.

    Deterministic (no measurement, no jax import) so it can block in
    ci.sh; the GitHub workflow re-measures fresh, non-blocking.
    """
    if not path.exists():
        print(f"error: no harness baseline at {path}", file=sys.stderr)
        return 2
    try:
        doc = json.loads(path.read_text(encoding="utf-8"))
    except json.JSONDecodeError as e:
        print(f"error: {path} is not valid JSON: {e}", file=sys.stderr)
        return 2
    problems = []
    if doc.get("bench_version") != BENCH_VERSION:
        problems.append(f"bench_version != {BENCH_VERSION}")
    fams = doc.get("families", {})
    for required in ("synthetic", "dgemm"):
        if required not in fams:
            problems.append(f"missing family {required!r}")
    for name, fam in fams.items():
        spd = fam.get("speedup_non_measured", 0.0)
        if spd < MIN_SPEEDUP:
            problems.append(
                f"{name}: speedup {spd} < required {MIN_SPEEDUP}")
        for mode in ("legacy", "fast"):
            if "non_measured_per_trial_s" not in fam.get(mode, {}):
                problems.append(f"{name}.{mode}: missing accounting")
    rel = doc.get("agreement", {}).get("rel_diff")
    if rel is None or rel > MAX_REL_DIFF:
        problems.append(f"sampler agreement rel_diff {rel} > {MAX_REL_DIFF}")
    if not doc.get("checks", {}).get("pass"):
        problems.append("checks.pass is not true")
    if problems:
        print(f"harness baseline {path}: FAIL")
        for p in problems:
            print(f"  - {p}")
        return 1
    spds = ", ".join(f"{n} {fam['speedup_non_measured']}x"
                     for n, fam in fams.items())
    print(f"harness baseline {path}: ok ({spds}; "
          f"agreement {rel * 100:.2f}%)")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--json", default=DEFAULT_JSON, metavar="PATH",
                    help=f"output path (default {DEFAULT_JSON})")
    ap.add_argument("--check", action="store_true",
                    help="validate an existing JSON instead of measuring")
    ap.add_argument("--reps", type=int, default=3,
                    help="cold-shape sessions per mode (median taken)")
    args = ap.parse_args()

    path = pathlib.Path(args.json)
    if args.check:
        return check(path)       # deterministic: no jax, no obs imports

    from repro.core.exec_cache import enable_compile_cache
    enable_compile_cache()

    # the measuring run is itself traced: every legacy/fast session's
    # trial/invocation/phase spans land in one JSONL + Perfetto artifact
    # next to the JSON (uploaded by CI) — the harness eating its own
    # observability dog food
    from repro.obs import TraceRecorder, write_chrome_trace
    trace_path = path.with_name(path.stem + ".trace.jsonl")
    trace_path.unlink(missing_ok=True)   # append-only file: one run per artifact
    with TraceRecorder(trace_path, session="bench-harness") as rec:
        doc = measure(reps=args.reps)
    perfetto = write_chrome_trace(
        path.with_name(path.stem + ".perfetto.json"), rec.events())
    path.write_text(json.dumps(doc, indent=2, sort_keys=False) + "\n",
                    encoding="utf-8")
    print(render(doc))
    print(f"wrote {path}")
    print(f"wrote {trace_path} and {perfetto}")
    return 0 if doc["checks"]["pass"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
