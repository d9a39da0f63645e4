"""Observability subsystem: span tracing, metrics, exports, device timing.

Covers the trace recorder's nesting/threading semantics, the program's
spans on the JAX profiler's trace, the compile counter, the JSONL and
Chrome-trace (Perfetto) exports, the per-session metrics/exec-cache delta
discipline, the GitHub Actions annotations emitted by the perf gate, and
the dashboard drill-down rendering (golden-pinned).
"""

import io
import json
import os
import pathlib
import subprocess
import sys
import threading

import pytest

from repro.core import (EvaluationSettings, ThreadPoolBackend, Tuner,
                        TuningSession, grid, welford)
from repro.core.exec_cache import ExecutableCache, default_cache
from repro.core import profiling
from repro.core.profiling import PhaseProfiler, phase
from repro.history import RunLedger, detect_regressions, render_html
from repro.history.ledger import RunRecord
from repro.obs import (MetricsRegistry, TraceRecorder, load_events, metrics,
                       recorder, to_chrome_trace, trial_summaries,
                       validate_chrome_trace)

REPO = pathlib.Path(__file__).resolve().parent.parent

SETTINGS = EvaluationSettings(max_invocations=2, max_iterations=10,
                              use_ci_convergence=True, use_inner_prune=True,
                              use_outer_prune=True)


def quadratic_benchmark(cfg):
    mu = 100.0 - (cfg["x"] - 5) ** 2
    return lambda: (lambda: mu)


# ---------------------------------------------------------------------------
# TraceRecorder mechanics
# ---------------------------------------------------------------------------


def test_recorder_nesting_and_jsonl_roundtrip(tmp_path):
    path = tmp_path / "t.trace.jsonl"
    seen = {}
    with TraceRecorder(path, session="s") as rec:
        assert recorder() is rec
        with rec.span("outer", cat="session", context=True) as outer:
            with rec.span("inner") as inner:
                rec.instant("mark", k=1)

            # a thread with an empty local span stack parents to the
            # context span — this is what attributes worker-thread trials
            # to the session
            def child():
                with rec.span("child") as c:
                    seen["parent"] = c.parent

            t = threading.Thread(target=child)
            t.start()
            t.join()
    assert recorder() is None
    assert seen["parent"] == outer.id

    events = load_events(path)
    assert events == rec.events()          # the file is the event stream
    spans = {e["id"]: e for e in events if e["type"] == "span"}
    assert spans[inner.id]["parent"] == outer.id
    assert spans[outer.id]["parent"] is None
    mark = next(e for e in events if e["type"] == "instant")
    assert mark["parent"] == inner.id and mark["attrs"] == {"k": 1}
    header = events[0]
    assert header["type"] == "meta" and header["session"] == "s"


def test_recorder_is_exclusive_per_process(tmp_path):
    with TraceRecorder(tmp_path / "a.jsonl"):
        other = TraceRecorder(tmp_path / "b.jsonl")
        with pytest.raises(RuntimeError):
            other.__enter__()
        other.close()
    # uninstalled cleanly: a fresh recorder installs fine
    with TraceRecorder(tmp_path / "c.jsonl") as rec:
        assert recorder() is rec
    assert recorder() is None


def test_phase_feeds_both_profiler_and_trace():
    now = [0.0]

    def clock():
        return now[0]

    prof = PhaseProfiler(clock=clock)
    with TraceRecorder(clock=clock) as rec, prof:
        with phase("work"):
            pass
        with phase("sync"):
            now[0] += 0.25
    buckets = prof.to_json()
    assert buckets["work"]["count"] == 1
    assert buckets["sync"]["seconds"] == pytest.approx(0.25)
    spans = [e for e in rec.events() if e["type"] == "span"]
    by_name = {s["name"]: s for s in spans}
    assert by_name["work"]["cat"] == "phase"
    assert by_name["sync"]["dur"] == pytest.approx(0.25)


def test_metrics_registry_snapshot_and_delta():
    reg = MetricsRegistry()
    reg.inc("a")
    reg.inc("a", 2)
    reg.gauge("g", 1.5)
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 3 and snap["gauges"]["g"] == 1.5
    reg.inc("b", 5)
    delta = reg.delta(snap)
    assert delta["counters"] == {"b": 5}          # only movement reported
    assert metrics() is metrics()                 # process-global accessor


# ---------------------------------------------------------------------------
# Traced tuning sessions: concurrency correctness + exports
# ---------------------------------------------------------------------------


def test_thread_backend_trace_attribution(tmp_path):
    """The concurrency acceptance check: a 4-worker threaded session's
    trace covers every persisted trial exactly once, every trial span
    hangs off the single session span, and the Chrome-trace export is
    structurally valid (balanced, per-tid monotone)."""
    session = TuningSession(
        "traced", Tuner(grid(x=tuple(range(12))), SETTINGS),
        quadratic_benchmark, cache_dir=tmp_path, fingerprint="fp",
        benchmark_name="bench", trace=True)
    reg = metrics()
    base = reg.snapshot()
    result = session.run(backend=ThreadPoolBackend(4))

    assert result.trace_path == str(tmp_path / "traced.trace.jsonl")
    events = load_events(result.trace_path)
    sessions = [e for e in events
                if e.get("type") == "span" and e.get("cat") == "session"]
    trials = [e for e in events
              if e.get("type") == "span" and e.get("cat") == "trial"]
    assert len(sessions) == 1
    assert len(trials) == len(result.trials) == 12
    assert sorted(t["attrs"]["index"] for t in trials) == list(range(12))
    assert all(t["parent"] == sessions[0]["id"] for t in trials)
    assert {t["attrs"]["worker"] for t in trials} <= set(range(4))
    # a trial span carries the tid of the worker thread that ran it, and
    # its nested invocation spans land on the same tid
    by_id = {e["id"]: e for e in events if e.get("type") == "span"}
    for inv in (e for e in events if e.get("cat") == "invocation"):
        assert by_id[inv["parent"]]["cat"] == "trial"
        assert inv["tid"] == by_id[inv["parent"]]["tid"]

    doc = to_chrome_trace(events)
    assert validate_chrome_trace(doc) == []
    assert any(e["ph"] == "M" for e in doc["traceEvents"])

    rows = trial_summaries(events)
    assert [r["index"] for r in rows] == list(range(12))
    assert all(r["invocations"] >= 1 for r in rows)

    # per-session result metrics: this session's activity, as a delta
    counters = result.metrics["counters"]
    assert counters["trials.started"] == 12
    assert counters["trials.completed"] == 12
    assert counters["cache.appends"] == 12
    # the ledger append happens in TuningSession.run, after tune()'s
    # delta closes — it lands in the global registry instead
    assert reg.delta(base)["counters"]["ledger.appends"] == 1


def test_cached_rerun_traces_cache_hits(tmp_path):
    def make(trace):
        return TuningSession(
            "hits", Tuner(grid(x=tuple(range(6))), SETTINGS),
            quadratic_benchmark, cache_dir=tmp_path, fingerprint="fp",
            benchmark_name="bench", trace=trace)

    make(False).run()
    result = make(tmp_path / "rerun.trace.jsonl").run()
    assert result.n_cached == 6
    assert result.metrics["counters"]["trials.cached"] == 6
    assert "trials.completed" not in result.metrics["counters"]

    events = load_events(tmp_path / "rerun.trace.jsonl")
    hits = [e for e in events
            if e.get("type") == "instant" and e["name"] == "cache_hit"]
    assert len(hits) == 6
    rows = trial_summaries(events)
    assert len(rows) == 6 and all(r["cached"] for r in rows)
    assert all(r["score"] is not None for r in rows)


def test_exec_cache_stats_report_per_session_deltas(tmp_path, monkeypatch):
    """Two sessions sharing the process-global executable cache must each
    report their own activity: the second session re-serves session 1's
    executables, so its delta shows hits and zero misses — cumulative
    reporting would repeat session 1's misses."""
    monkeypatch.setattr(
        ExecutableCache, "_lower_and_compile",
        staticmethod(lambda fn, args, static=None: lambda *a: None))
    np = pytest.importorskip("numpy")
    arrays = {x: np.zeros((x + 1,), dtype=np.float32) for x in range(4)}

    def bench(cfg):
        def factory():
            default_cache().compile(_kernel_stub, (arrays[cfg["x"]],),
                                    static={"x": cfg["x"]})
            return lambda: float(cfg["x"])
        return factory

    def run(name, benchmark_name):
        return TuningSession(
            name, Tuner(grid(x=tuple(range(4))), SETTINGS), bench,
            cache_dir=tmp_path, fingerprint="fp",
            benchmark_name=benchmark_name).run()

    r1 = run("s1", "b1")
    r2 = run("s2", "b2")
    assert r1.exec_cache["misses"] == 4
    assert r2.exec_cache["misses"] == 0 and r2.exec_cache["compiles"] == 0
    assert r2.exec_cache["hits"] >= 4


def _kernel_stub(x):
    return x


# ---------------------------------------------------------------------------
# Campaign tracing
# ---------------------------------------------------------------------------


def test_campaign_trace_spans(tmp_path):
    from repro.sweep import SweepCampaign

    def family(shape):
        def bench(cfg):
            mu = 100.0 - (cfg["bm"] - shape["m"]) ** 2
            return lambda: (lambda: mu)
        return bench

    camp = SweepCampaign(grid(bm=(1, 2)), grid(m=(1, 2)), family, SETTINGS,
                         name="camp", cache_dir=tmp_path, seed=0)
    result = camp.run(trace=True)
    assert result.trace_path == str(tmp_path / "camp.trace.jsonl")

    events = load_events(result.trace_path)
    spans = {e["id"]: e for e in events if e["type"] == "span"}
    campaigns = [s for s in spans.values() if s["cat"] == "session"
                 and s["name"] == "campaign"]
    shapes = [s for s in spans.values() if s["cat"] == "shape"]
    tunes = [s for s in spans.values() if s["name"] == "tune"]
    trials = [s for s in spans.values() if s["cat"] == "trial"]
    assert len(campaigns) == 1 and len(shapes) == 2 and len(tunes) == 2
    assert all(s["parent"] == campaigns[0]["id"] for s in shapes)
    assert {t["parent"] for t in tunes} == {s["id"] for s in shapes}
    assert trials and all(spans[t["parent"]]["name"] == "tune"
                          for t in trials)
    assert campaigns[0]["attrs"]["total_trials"] == len(trials)
    assert validate_chrome_trace(to_chrome_trace(events)) == []


# ---------------------------------------------------------------------------
# The program's spans on the JAX profiler's trace
# ---------------------------------------------------------------------------


def timed_benchmark(cfg):
    from repro.core import timed_sampler

    def factory():
        return timed_sampler(lambda: sum(range(2000)), work=float(cfg["x"]))
    return factory


def _perfetto_events(log_dir):
    import gzip

    path = sorted(pathlib.Path(log_dir).rglob("perfetto_trace.json.gz"))[-1]
    with gzip.open(path, "rt", encoding="utf-8") as fh:
        return json.load(fh)["traceEvents"]


def _inside(inner, outer):
    return (inner["tid"] == outer["tid"] and outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])


def test_tune_under_the_profiler_writes_program_spans(tmp_path):
    """Under ``jax.profiler.trace`` every span of a session lands on the
    host line of the profiler's own trace as ``repro.<name>``, nested
    under the caller's annotation, and the spans of one trial carry its
    index and config label."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path / "prof"),
                            create_perfetto_trace=True,
                            profiler_options=options):
        with jax.profiler.TraceAnnotation("session"):
            result = TuningSession(
                "prof", Tuner(grid(x=(3, 5)), SETTINGS), timed_benchmark,
                cache_dir=tmp_path, fingerprint="fp",
                benchmark_name="bench").run()
    assert profiling.trace_sink() is None and profiling._TRACE is None

    events = _perfetto_events(tmp_path / "prof")
    host = {e["pid"] for e in events
            if e.get("ph") == "M" and e.get("name") == "process_name"
            and e["args"]["name"].startswith("/host")}
    spans = [e for e in events if e.get("ph") == "X" and e["pid"] in host]
    program = [e for e in spans if e["name"].startswith("repro.")]
    assert {"repro.tune", "repro.audit", "repro.trial", "repro.build",
            "repro.invocation", "repro.setup", "repro.dispatch",
            "repro.stats", "repro.cache_io", "repro.ledger_io"} \
        <= {e["name"] for e in program}
    (session,) = [e for e in spans if e["name"] == "session"]
    assert all(_inside(e, session) for e in program)

    trials = sorted((e for e in program if e["name"] == "repro.trial"),
                    key=lambda e: e["ts"])
    assert len(trials) == len(result.trials) == 2
    assert [t["args"]["trial"] for t in trials] == ["0", "1"]
    assert [t["args"]["config"] for t in trials] == ["x=3", "x=5"]
    for t in trials:
        inner = [e for e in program if e is not t and _inside(e, t)]
        assert {"repro.build", "repro.invocation", "repro.dispatch"} \
            <= {e["name"] for e in inner}
        assert all(e["args"]["trial"] == t["args"]["trial"]
                   and e["args"]["config"] == t["args"]["config"]
                   for e in inner)


def test_untraced_tune_installs_nothing():
    """With no profiler collecting and no recorder, every seam returns
    the shared no-op while a session runs."""
    seen = []

    def bench(cfg):
        def factory():
            seen.append((profiling._TRACE, phase("dispatch"),
                         profiling.trace_span("trial"),
                         profiling.compiling("exec_cache")))
            return lambda: 1.0
        return factory

    Tuner(grid(x=(1, 2)), SETTINGS).tune(bench, validate="off")
    assert seen
    for sink, *handles in seen:
        assert sink is None
        assert all(h is profiling._NULL for h in handles)


def _compile_case(source):
    """A callable making one compile through ``source``'s path; returns
    the ``TuningResult`` where a session made it."""
    import jax
    import jax.numpy as jnp

    from repro.core.exec_cache import CompilePipeline
    from repro.lint import WorkloadSpec
    from repro.lint.workload import trace_cost
    from repro.models.workloads import ModelWorkload

    shape = (jax.ShapeDtypeStruct((8,), jnp.float32),)
    if source == "trace_cost":
        return lambda: trace_cost(jnp.sin, shape)
    if source == "workload":
        return lambda: ModelWorkload(
            name="w", kind="kernel", fn=jnp.sin, args=(jnp.ones(8),),
            cfg=None, step=None, shape=None).compiled()
    if source == "exec_cache":
        return lambda: ExecutableCache(fingerprint="fp").compile(jnp.cos,
                                                                 shape)
    if source == "pipeline":
        def run():
            with CompilePipeline() as pipeline:
                pipeline.submit(lambda: ExecutableCache(
                    fingerprint="fp").compile(jnp.tan, shape))
        return run

    def audited(cfg):
        return lambda: (lambda: 1.0)

    audited.audit_spec = lambda cfg: WorkloadSpec(
        fn=jnp.sin, args=shape, work=8.0, unit="flops")
    return lambda: Tuner(grid(x=(1,)), SETTINGS).tune(audited,
                                                      validate="warn")


@pytest.mark.parametrize("source,span_source", [
    ("trace_cost", "trace_cost"), ("workload", "workload"),
    ("exec_cache", "exec_cache"), ("pipeline", "pipeline"),
    ("audit", "trace_cost")])
def test_compile_calls_count_every_compile(source, span_source):
    """Every lowering plus compile passes one helper: it counts
    ``compile.calls`` and opens a ``compile`` span naming its source. The
    audit's compile lands in the session's own metrics."""
    import warnings

    reg = metrics()
    base = reg.snapshot()
    with TraceRecorder() as rec, warnings.catch_warnings():
        warnings.simplefilter("ignore")
        result = _compile_case(source)()
    assert reg.delta(base)["counters"]["compile.calls"] == 1
    compiles = [e for e in rec.events()
                if e["type"] == "span" and e["name"] == "compile"]
    assert [c["attrs"]["source"] for c in compiles] == [span_source]
    if source == "audit":
        assert result.metrics["counters"]["compile.calls"] == 1
        spans = {e["id"]: e for e in rec.events() if e["type"] == "span"}
        assert spans[compiles[0]["parent"]]["name"] == "audit"


# ---------------------------------------------------------------------------
# Device timing: graceful degradation off-GPU
# ---------------------------------------------------------------------------


def test_device_timing_degrades_gracefully():
    from repro.obs import device_timing_available, profile_sample
    from repro.obs.device_timing import DeviceTiming
    assert isinstance(device_timing_available(), bool)
    out = profile_sample(lambda: sum(range(100)))
    assert out is None or isinstance(out, DeviceTiming)


def test_device_busy_is_the_union_of_the_ops_line():
    """A TPU v5e trace of a 4-step scanned matmul (recorded on the chip):
    the device shows the program on "XLA Modules" and its ops on "XLA
    Ops", where the while op also spans its body ops. Busy time counts
    each instant once; summing every event would triple it."""
    import json

    from repro.obs.device_timing import parse_device_ops, union_length

    doc = json.loads((REPO / "tests" / "data" / "tpu_v5e_scan_trace.json")
                     .read_text())
    ops = parse_device_ops(doc["traceEvents"])
    device = [e for e in doc["traceEvents"]
              if e["ph"] == "X" and e["pid"] == 3]
    summed_us = sum(e["dur"] for e in device)
    module_us = next(e["dur"] for e in device if e["tid"] == 2)
    assert summed_us > 2.9 * module_us
    assert ops.total_s == pytest.approx(union_length(
        [(e["ts"], e["ts"] + e["dur"]) for e in device if e["tid"] == 3])
        * 1e-6)
    assert ops.total_s <= module_us * 1e-6
    assert ops.total_s > 0.99 * module_us * 1e-6
    assert ops.n_events == 13                  # the ops line only
    assert ops.by_name["while"] == pytest.approx(3610.96e-6)
    assert ops.by_name["fusion.8"] == pytest.approx(
        4 * 800.436e-6, rel=1e-3)               # four trips, summed


def test_union_length():
    from repro.obs.device_timing import union_length

    assert union_length([]) == 0.0
    assert union_length([(0, 2), (1, 3), (5, 6)]) == 4.0
    assert union_length([(0, 10), (2, 3)]) == 10.0


def test_profile_raises_on_accelerator_without_device_track(monkeypatch,
                                                            tmp_path):
    import jax

    from repro.obs import device_timing

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="no device track"):
        device_timing.profile_ops(lambda: sum(range(100)), log_dir=tmp_path)


# ---------------------------------------------------------------------------
# perf_gate: GitHub Actions annotations
# ---------------------------------------------------------------------------


def make_record(score, offsets=(0.5, 0.7, 0.4, 0.6, 0.5), run=0,
                benchmark="dgemm", fingerprint="fp", **kw):
    states = [welford.from_samples([score - o, score + o, score])
              for o in offsets]
    pooled = welford.tree_merge(states)
    return RunRecord(benchmark=benchmark, fingerprint=fingerprint, run=run,
                     config={"n": 512}, score=score,
                     count=float(pooled.count), mean=float(pooled.mean),
                     m2=float(pooled.m2),
                     invocation_means=tuple(float(s.mean) for s in states),
                     **kw)


def _run_gate(ledger_path, *argv, github=True):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    if github:
        env["GITHUB_ACTIONS"] = "1"
    else:
        env.pop("GITHUB_ACTIONS", None)
    return subprocess.run(
        [sys.executable, str(REPO / "scripts" / "perf_gate.py"),
         str(ledger_path), *map(str, argv)],
        capture_output=True, text=True, env=env, timeout=120)


def test_perf_gate_github_annotations(tmp_path):
    """Under GITHUB_ACTIONS=1 a confirmed regression emits an ::error
    workflow command whose file/line point at the candidate's exact
    ledger record; --dry-run downgrades it to ::warning."""
    sys.path.insert(0, str(REPO / "scripts"))
    try:
        import perf_gate
    finally:
        sys.path.pop(0)

    ledger_path = tmp_path / "history.jsonl"
    led = RunLedger(ledger_path)
    # a "%" in the name exercises workflow-command escaping end to end
    led.append(make_record(100.0, benchmark="dg%mm"))
    led.append(make_record(88.0, benchmark="dg%mm"))

    report = detect_regressions(RunLedger(ledger_path))
    assert not report.ok
    buf = io.StringIO()
    assert perf_gate.emit_annotations(report, ledger_path, out=buf) == 1
    expected = buf.getvalue().strip()
    assert expected.startswith("::error file=")
    assert f"file={perf_gate._esc_prop(str(ledger_path))},line=2," in expected
    assert "dg%25mm" in expected                 # % escaped, both segments
    assert "dg%mm" not in expected

    proc = _run_gate(ledger_path)
    assert proc.returncode == 1
    assert expected in proc.stdout.splitlines()

    proc = _run_gate(ledger_path, "--dry-run")
    assert proc.returncode == 0
    warning = "::warning " + expected[len("::error "):]
    assert warning in proc.stdout.splitlines()

    # outside GitHub Actions the same gate emits no workflow commands
    proc = _run_gate(ledger_path, github=False)
    assert proc.returncode == 1 and "::error" not in proc.stdout


def test_perf_gate_annotations_skip_clean_series(tmp_path):
    sys.path.insert(0, str(REPO / "scripts"))
    try:
        import perf_gate
    finally:
        sys.path.pop(0)
    ledger_path = tmp_path / "history.jsonl"
    led = RunLedger(ledger_path)
    led.append(make_record(100.0))
    led.append(make_record(100.0))
    buf = io.StringIO()
    n = perf_gate.emit_annotations(
        detect_regressions(RunLedger(ledger_path)), ledger_path, out=buf)
    assert n == 0 and buf.getvalue() == ""


# ---------------------------------------------------------------------------
# Dashboard drill-down (golden-pinned)
# ---------------------------------------------------------------------------


def test_dashboard_trial_drilldown_golden(golden):
    rows = [
        {"index": 0, "config": {"x": 0}, "score": 75.0, "pruned": False,
         "stop_reason": "converged", "samples": 30, "worker": 0,
         "thread": "w0", "tid": 1, "ts": 0.001, "dur_s": 0.0123,
         "invocations": 2, "phases": {"measure": 0.0101,
                                      "cache_io": 0.0004},
         "improved": True, "cached": False},
        {"index": 1, "config": {"x": 1}, "score": 84.0, "pruned": True,
         "stop_reason": "outer_pruned", "samples": 6, "worker": 1,
         "thread": "w1", "tid": 2, "ts": 0.002, "dur_s": 0.0042,
         "invocations": 1, "phases": {"measure": 0.0031},
         "improved": False, "cached": False},
        {"index": None, "config": {"x": 2}, "score": 91.0, "pruned": False,
         "stop_reason": "converged", "samples": 30, "worker": None,
         "thread": None, "tid": None, "ts": 0.003, "dur_s": 0.0,
         "invocations": 0, "phases": {}, "improved": False, "cached": True},
    ]
    html = render_html(trials=rows, subtitle="golden fixture")
    for needle in ("Trial drill-down", "3 traced trial(s)",
                   "trial-improved", "outer_pruned", "cached",
                   "measure 10.10ms"):
        assert needle in html, needle
    golden("dashboard_trials.html", html)


def test_trial_summaries_row_shape_from_live_trace(tmp_path):
    session = TuningSession(
        "rows", Tuner(grid(x=(3, 5)), SETTINGS), quadratic_benchmark,
        cache_dir=tmp_path, fingerprint="fp", benchmark_name="bench",
        trace=True)
    result = session.run()
    rows = trial_summaries(load_events(result.trace_path))
    assert len(rows) == 2
    for row in rows:
        assert {"index", "config", "score", "pruned", "stop_reason",
                "samples", "worker", "dur_s", "invocations", "phases",
                "improved", "cached"} <= set(row)
    assert any(r["improved"] for r in rows)
    # the best config's row carries the incumbent score
    best = max(rows, key=lambda r: r["score"])
    assert best["score"] == pytest.approx(result.best_score)


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_tune_cli_trace_and_live(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "tune.py"),
         "--session", "smoke", "--benchmark", "synthetic",
         "--cache-dir", str(tmp_path), "--trace", "--live"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    trace_path = tmp_path / "smoke.trace.jsonl"
    assert str(trace_path) in proc.stdout
    events = load_events(trace_path)
    trials = [e for e in events
              if e.get("type") == "span" and e.get("cat") == "trial"]
    assert len(trials) == 12                     # the synthetic grid
    assert validate_chrome_trace(to_chrome_trace(events)) == []
    assert "[live]" in proc.stderr
