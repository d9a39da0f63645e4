"""Whole-model roofline attribution: per-op cost vs the empirical roofs.

Covers the attribution math on synthetic modules (join, remainder,
%-of-roof formulas), the DGEMM calibration invariant (attributed FLOPs
== declared 2·m·n·k within 1%), off-GPU graceful degradation to static
HLO-only attribution, roofs recovery from a trial cache, the dashboard
section (golden-file), the trial-row cap threading, and the report CLI.

Regenerate the golden after an intentional rendering change with:

    PYTHONPATH=src python -m pytest tests/test_attribution.py -q \
        --update-golden
"""

import json
import math
import pathlib
import subprocess
import sys

import pytest

from repro.analysis.hlo import ModuleOps, OpCost
from repro.history.render import _trials_section, render_html
from repro.obs.attribution import (AttributionReport, Roofs, attribute,
                                   _attr_op, _attribution_from_device,
                                   attribution_from_static,
                                   roofs_from_trials)
from repro.obs.device_timing import DeviceOps, normalize_op_name

REPO = pathlib.Path(__file__).resolve().parent.parent

#: handmade roofs with easy arithmetic: ridge point at I* = 100/10 = 10
ROOFS = Roofs(peak_flops=100.0, bandwidths={"hbm": 10.0, "l2": 40.0},
              fingerprint="test-roofs")


# ---------------------------------------------------------------------------
# Roofs
# ---------------------------------------------------------------------------


def test_roofs_default_subsystem_is_slowest():
    assert ROOFS.default_subsystem == "hbm"


def test_roofs_ridge_and_attainable():
    assert ROOFS.ridge() == pytest.approx(10.0)          # F_p / B_hbm
    assert ROOFS.ridge("l2") == pytest.approx(2.5)
    # below the ridge the bandwidth slope rules, above it the flat roof
    assert ROOFS.attainable(2.0) == pytest.approx(20.0)
    assert ROOFS.attainable(50.0) == pytest.approx(100.0)
    assert ROOFS.attainable(2.0, "l2") == pytest.approx(80.0)


def test_roofs_classify_by_ridge():
    assert ROOFS.classify(20.0) == ("hbm", "compute")
    assert ROOFS.classify(1.0) == ("hbm", "memory")
    assert ROOFS.classify(10.0) == ("hbm", "compute")    # at the ridge


def test_roofs_model_time_is_max_of_terms():
    # 50 FLOPs / 100 FLOP/s = 0.5s vs 100 B / 10 B/s = 10s -> memory wins
    assert ROOFS.model_time(50.0, 100.0) == pytest.approx(10.0)
    # 80 FLOPs -> 0.8s vs 1 B -> 0.1s -> compute wins
    assert ROOFS.model_time(80.0, 1.0) == pytest.approx(0.8)


def test_roofs_json_round_trip():
    d = ROOFS.to_json()
    assert d == {"peak_flops": 100.0,
                 "bandwidths": {"hbm": 10.0, "l2": 40.0},
                 "fingerprint": "test-roofs"}
    assert json.loads(json.dumps(d)) == d


# ---------------------------------------------------------------------------
# Event-name normalization (trace join key)
# ---------------------------------------------------------------------------


def test_normalize_op_name_strips_scope_and_percent():
    assert normalize_op_name("jit_f/while/body/%fusion.1") == "fusion.1"
    assert normalize_op_name("%dot.4") == "dot.4"
    assert normalize_op_name("dot.4") == "dot.4"
    assert normalize_op_name(" %copy ") == "copy"


# ---------------------------------------------------------------------------
# Per-op attribution math
# ---------------------------------------------------------------------------


def _op(name, kind, flops, bytes_accessed, modeled=True):
    return OpCost(name=name, kind=kind, flops=flops,
                  bytes_accessed=bytes_accessed, modeled=modeled)


def test_attr_op_static_saturates_roof():
    a = _attr_op(_op("dot.1", "dot", 200.0, 10.0), 2.0, ROOFS, static=True)
    assert a.pct_of_roof == 100.0
    assert a.bound == "compute"            # I = 20 >= ridge 10
    assert a.subsystem == "hbm"


def test_attr_op_measured_pct_against_attainable():
    # I = 200/10 = 20 (compute-bound): roof = F_p = 100 FLOP/s;
    # achieved 200 FLOPs / 4 s = 50 FLOP/s -> 50% of roof
    a = _attr_op(_op("dot.1", "dot", 200.0, 10.0), 4.0, ROOFS, static=False)
    assert a.pct_of_roof == pytest.approx(50.0)
    # memory-bound op: I = 5/100 = 0.05, roof = 10 * 0.05 = 0.5 FLOP/s;
    # achieved 5/20 = 0.25 FLOP/s -> 50%
    b = _attr_op(_op("f.2", "fusion", 5.0, 100.0), 20.0, ROOFS, static=False)
    assert b.bound == "memory"
    assert b.pct_of_roof == pytest.approx(50.0)


def test_attr_op_flop_free_uses_bandwidth():
    # copy moves 50 B in 10 s = 5 B/s against B_hbm = 10 -> 50%
    a = _attr_op(_op("copy.1", "copy", 0.0, 50.0), 10.0, ROOFS, static=False)
    assert a.pct_of_roof == pytest.approx(50.0)
    assert a.bound == "memory"


def test_attr_op_without_time_or_roofs():
    no_time = _attr_op(_op("d", "dot", 8.0, 4.0), None, ROOFS, static=False)
    assert no_time.pct_of_roof is None
    assert no_time.subsystem == "hbm"      # still classified
    no_roofs = _attr_op(_op("d", "dot", 8.0, 4.0), 1.0, None, static=False)
    assert no_roofs.subsystem == "unclassified"
    assert no_roofs.bound == "unclassified"
    assert no_roofs.pct_of_roof is None


def test_attr_op_json_maps_inf_intensity_to_none():
    a = _attr_op(_op("x", "exponential", 4.0, 0.0), 1.0, ROOFS, static=False)
    assert math.isinf(a.intensity)
    assert a.to_json()["intensity"] is None
    assert json.dumps(a.to_json())         # must be valid JSON


# ---------------------------------------------------------------------------
# Measured-mode assembly: join + remainder
# ---------------------------------------------------------------------------


def _module():
    return ModuleOps(ops=(
        _op("dot.1", "dot", 200.0, 10.0),          # compute-bound
        _op("fusion.2", "fusion", 5.0, 100.0),     # memory-bound
        _op("copy.3", "copy", 0.0, 50.0),          # flop-free
    ), unhandled={"rng-bit-generator": 1})


def test_device_join_and_remainder():
    device = DeviceOps(total_s=10.0,
                       by_name={"dot.1": 4.0, "fusion.2": 2.0,
                                "unmatched-kernel": 1.0},
                       n_events=4, source="test")
    rep = _attribution_from_device("w", _module(), device, ROOFS)
    assert rep.mode == "measured"
    assert rep.device_total_s == 10.0
    assert rep.attributed_s == pytest.approx(6.0)    # only joined ops
    assert rep.unattributed_s == pytest.approx(4.0)  # incl. the unmatched
    assert rep.unattributed_frac == pytest.approx(0.4)
    by = {op.name: op for op in rep.ops}
    assert by["copy.3"].time_s is None               # no device event
    assert by["dot.1"].pct_of_roof == pytest.approx(50.0)
    assert rep.unhandled == {"rng-bit-generator": 1}
    # compute-bound time under "compute", memory-bound under its subsystem
    assert rep.subsystem_seconds == {"compute": pytest.approx(4.0),
                                     "hbm": pytest.approx(2.0)}


def test_device_remainder_clamped_at_zero():
    # more joined time than track total (overlapping streams) never goes
    # negative
    device = DeviceOps(total_s=1.0, by_name={"dot.1": 2.0}, n_events=1,
                       source="test")
    rep = _attribution_from_device("w", _module(), device, ROOFS)
    assert rep.unattributed_s == 0.0


def test_top_ops_orders_by_time_then_cost():
    device = DeviceOps(total_s=10.0,
                       by_name={"dot.1": 1.0, "fusion.2": 3.0},
                       n_events=2, source="test")
    rep = _attribution_from_device("w", _module(), device, ROOFS)
    assert [o.name for o in rep.top_ops(2)] == ["fusion.2", "dot.1"]


# ---------------------------------------------------------------------------
# Static fallback (off-GPU degradation)
# ---------------------------------------------------------------------------


def test_static_report_zero_remainder_and_full_labels():
    rep = attribution_from_static("w", _module(), ROOFS, fingerprint="fp")
    assert rep.mode == "static"
    assert rep.device_total_s is None
    assert rep.unattributed_s == 0.0
    assert rep.unattributed_frac == 0.0
    for op in rep.ops:
        assert op.subsystem == "hbm"
        assert op.bound in ("compute", "memory")
        assert op.pct_of_roof == 100.0
        # static time is the roofline lower bound
        assert op.time_s == pytest.approx(
            ROOFS.model_time(op.flops, op.bytes_accessed))
    assert rep.attributed_s == pytest.approx(
        sum(op.time_s for op in rep.ops))


def test_static_without_roofs_degrades_not_raises():
    rep = attribution_from_static("w", _module(), None)
    assert all(op.subsystem == "unclassified" for op in rep.ops)
    assert all(op.time_s is None for op in rep.ops)
    assert rep.to_markdown()               # renders without roofs too
    assert json.dumps(rep.to_json())


@pytest.mark.skipif(
    __import__("jax").default_backend() != "cpu",
    reason="degradation contract only guaranteed off-accelerator")
def test_attribute_off_gpu_degrades_to_static():
    """On a CPU backend the profiler emits no device tracks, so the
    measured path must silently fall back to static attribution."""
    from repro.models.workloads import build_workload

    w = build_workload("dgemm", m=32, n=32, k=32)
    rep = attribute(w, ROOFS)              # measured path attempted
    assert rep.mode == "static"
    assert rep.device_total_s is None
    assert rep.unattributed_s == 0.0
    assert rep.ops                         # every op still labeled
    assert all(op.subsystem != "" for op in rep.ops)


# ---------------------------------------------------------------------------
# DGEMM calibration: attributed FLOPs == declared 2mnk within 1%
# ---------------------------------------------------------------------------


def test_dgemm_attributed_flops_match_declared():
    from repro.models.workloads import build_workload

    w = build_workload("dgemm", m=64, n=48, k=32)
    assert w.declared_flops == 2.0 * 64 * 48 * 32
    rep = attribute(w, ROOFS, force_static=True)
    assert rep.total_flops == pytest.approx(w.declared_flops, rel=0.01)
    # the dot op itself carries the FLOPs (not scattered over reshapes)
    dot_flops = sum(op.flops for op in rep.ops if op.kind == "dot")
    assert dot_flops == pytest.approx(w.declared_flops, rel=0.01)


def test_train_step_every_op_labeled():
    """Acceptance shape: every HLO op of a whole-model workload carries a
    subsystem label, a %-of-roof figure, and the remainder is explicit
    (exactly 0 in static mode)."""
    from repro.models.workloads import build_workload

    w = build_workload("train_step")
    rep = attribute(w, ROOFS, force_static=True)
    assert rep.ops
    for op in rep.ops:
        assert op.subsystem in ("hbm", "l2")
        assert op.pct_of_roof is not None
    assert rep.unattributed_s == 0.0
    assert rep.total_flops > 0


# ---------------------------------------------------------------------------
# Roofs from the trial cache
# ---------------------------------------------------------------------------


def _seed_cache(path):
    from test_report import synthetic_trials, write_cache

    write_cache(path, synthetic_trials())


def test_roofs_from_trials_recovers_peaks(tmp_path):
    path = tmp_path / "c.jsonl"
    _seed_cache(path)
    roofs = roofs_from_trials([str(path)], fingerprint="fpB")
    assert roofs is not None
    assert roofs.fingerprint == "fpB"
    # scores are GFLOP/s / GB/s in the cache; machine peaks are SI
    assert roofs.peak_flops == pytest.approx(900.0e9)
    assert roofs.bandwidths
    assert all(v > 0 for v in roofs.bandwidths.values())
    assert roofs.ridge() > 0


def test_roofs_from_trials_falls_back_to_first_report(tmp_path):
    path = tmp_path / "c.jsonl"
    _seed_cache(path)
    # this host's fingerprint matches neither fpA nor fpB
    roofs = roofs_from_trials([str(path)])
    assert roofs is not None
    assert roofs.fingerprint in ("fpA", "fpB")


def test_roofs_from_trials_none_when_empty(tmp_path):
    assert roofs_from_trials([str(tmp_path / "missing.jsonl")]) is None
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert roofs_from_trials([str(empty)]) is None


# ---------------------------------------------------------------------------
# Dashboard rendering
# ---------------------------------------------------------------------------


def _deterministic_report():
    """Hand-built static report — no compiler in the loop, so the golden
    is stable across jax/XLA versions."""
    module = ModuleOps(ops=(
        _op("dot.1", "dot", 2.0e6, 4.0e4),
        _op("fusion.2", "fusion", 1.0e3, 2.0e5),
        _op("copy.3", "copy", 0.0, 8.0e4),
        _op("custom-call.4", "custom-call", 0.0, 0.0, modeled=False),
    ), unhandled={"custom-call": 1})
    roofs = Roofs(peak_flops=1.0e9, bandwidths={"hbm": 1.0e8, "l2": 4.0e8},
                  fingerprint="golden-fp")
    return attribution_from_static("train_step", module, roofs,
                                   fingerprint="golden-fp")


def test_attribution_html_matches_golden(golden):
    html = render_html(
        title="Attribution test dashboard",
        subtitle="fixed subtitle for golden stability",
        attribution=_deterministic_report())
    assert "Attribution — <code>train_step</code>" in html
    assert "attr-bar" in html              # stacked subsystem bar present
    assert "static HLO attribution" in html
    golden("attribution.html", html)


def test_attribution_markdown_sections():
    md = _deterministic_report().to_markdown(max_ops=2)
    assert "## Roofline attribution: `train_step` (static)" in md
    assert "### Subsystem shares" in md
    assert "2 further ops elided" in md
    assert "*unattributed* | 0µs" in md


def test_measured_report_renders_device_basis():
    device = DeviceOps(total_s=10.0, by_name={"dot.1": 4.0}, n_events=1,
                       source="test")
    rep = _attribution_from_device("w", _module(), device, ROOFS)
    html = render_html(attribution=rep)
    assert "device total" in html
    assert "unattributed 60.0%" in html


# ---------------------------------------------------------------------------
# Trial drill-down row cap
# ---------------------------------------------------------------------------


def _trial_rows(n):
    return [{"index": i, "config": {"x": i}, "score": float(i),
             "samples": 4, "invocations": 2, "stop_reason": "max",
             "dur_s": 0.01, "worker": 0, "phases": {}} for i in range(n)]


def test_trials_section_row_cap():
    html = _trials_section(_trial_rows(5), max_rows=2)
    assert "first 2 of 5" in html
    assert _trials_section(_trial_rows(2), max_rows=2).count("<tr>") >= 2
    assert "first" not in _trials_section(_trial_rows(2), max_rows=2)


def test_render_html_threads_max_trial_rows():
    html = render_html(trials=_trial_rows(7), max_trial_rows=3)
    assert "first 3 of 7" in html
    default = render_html(trials=_trial_rows(7))
    assert "first" not in default          # default cap is 200


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_attribute_static(tmp_path):
    out_json = tmp_path / "attr.json"
    out_html = tmp_path / "dash.html"
    cache = tmp_path / "roofs.jsonl"
    _seed_cache(cache)    # explicit roofs: this CPU has no published peaks
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "roofline_report.py"),
         str(cache), "--attribute", "dgemm", "--static",
         "--attribution-json", str(out_json), "--html", str(out_html),
         "--max-trial-rows", "5"],
        capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "PYTHONPATH": str(REPO / "src")})
    assert proc.returncode == 0, proc.stderr
    assert "attributed" in proc.stderr
    doc = json.loads(out_json.read_text())
    assert doc["mode"] == "static"
    assert doc["unattributed_s"] == 0.0
    assert doc["ops"]
    assert all(op["subsystem"] for op in doc["ops"])
    assert all(op["pct_of_roof"] is not None for op in doc["ops"])
    assert "Attribution —" in out_html.read_text(encoding="utf-8")


def test_cli_attribute_without_roofs_needs_published_peaks():
    """No cache: the peaks come from the device the workload runs on,
    and the CPU is not in the published-peaks table — an error, never a
    silent default."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "roofline_report.py"),
         "--attribute", "dgemm", "--static"],
        capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "PYTHONPATH": str(REPO / "src"),
             "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2
    assert "no published peaks for device kind 'cpu'" in proc.stderr
