"""Roofline model math (paper Eq. 1-2) and term analysis."""

import math

import pytest

from repro.analysis.hlo import parse_collectives
from repro.core.roofline import (TPU_V5E, TRIAD_INTENSITY, attainable,
                                 from_measurements, operational_intensity,
                                 published_peaks, ridge_point)


def test_attainable_eq2():
    # memory-bound region: F = B*I
    assert attainable(0.5, 100e12, 800e9) == 400e9
    # compute-bound region: F = Fp
    assert attainable(1000.0, 100e12, 800e9) == 100e12


def test_ridge_point():
    assert abs(ridge_point(100e12, 800e9) - 125.0) < 1e-9
    # v5e bf16 ridge: 197e12 / 819e9 ≈ 240 FLOP/byte
    assert 230 < ridge_point(TPU_V5E.peak_flops,
                             TPU_V5E.mem_bandwidths["hbm"]) < 250


def test_published_peaks_keyed_by_device_kind():
    v5e = published_peaks("TPU v5 lite")
    assert v5e is TPU_V5E
    assert (v5e.peak_flops, v5e.peak_int8_ops) == (197e12, 393e12)
    assert v5e.mem_bandwidths == {"hbm": 819e9}
    with pytest.raises(KeyError, match="no published peaks.*'cpu'"):
        published_peaks("cpu")


def test_triad_intensity():
    assert abs(TRIAD_INTENSITY - 1.0 / 12.0) < 1e-12


def test_operational_intensity():
    assert operational_intensity(24.0, 288.0) == 1.0 / 12.0
    assert operational_intensity(1.0, 0.0) == math.inf


def test_model_bound_classification():
    model = from_measurements("test", 100e12, {"dram": 800e9})
    assert model.bound(1.0, "dram") == "memory"
    assert model.bound(1e4, "dram") == "compute"


def test_curve_monotone_saturating():
    model = from_measurements("test", 100e12, {"dram": 800e9})
    pts = model.curve("dram")
    ys = [p[1] for p in pts]
    assert all(b >= a for a, b in zip(ys, ys[1:]))
    assert ys[-1] == 100e12


def test_csv_and_ascii():
    model = from_measurements("test", 1e12, {"l3": 1e11, "dram": 1e10})
    csv = model.to_csv()
    assert csv.splitlines()[0] == "subsystem,intensity_flop_per_byte,attainable_flops"
    assert "dram" in csv and "l3" in csv
    art = model.ascii_plot("dram", marks=[("x", 1.0, 1e10)])
    assert "roofline[test/dram]" in art


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

SAMPLE_HLO = """
  %ag = bf16[16,1024]{1,0} all-gather(%x), replica_groups=[32,16]<=[512], dimensions={0}
  %ar = f32[128]{0} all-reduce(%y), replica_groups={{0,1,2,3}}, to_apply=%add
  %ard = f32[128]{0} all-reduce-done(%ar)
  %cp = f32[64]{0} collective-permute(%z), source_target_pairs={{0,1}}
  %noise = f32[2,2]{1,0} add(%a, %b)
"""


def test_parse_collectives_counts_and_bytes():
    stats = parse_collectives(SAMPLE_HLO, n_devices=512)
    assert stats.count_by_op["all-gather"] == 1
    assert stats.count_by_op["all-reduce"] == 1      # -done not re-counted
    assert stats.count_by_op["collective-permute"] == 1
    ag = 16 * 1024 * 2 * (15 / 16)                    # group size 16
    ar = 2 * 128 * 4 * (3 / 4)                        # group size 4
    cp = 64 * 4
    assert abs(stats.bytes_by_op["all-gather"] - ag) < 1e-6
    assert abs(stats.bytes_by_op["all-reduce"] - ar) < 1e-6
    assert abs(stats.bytes_by_op["collective-permute"] - cp) < 1e-6


def test_parse_collectives_empty():
    stats = parse_collectives("%r = f32[4]{0} add(%a, %b)", 8)
    assert stats.total_bytes == 0 and stats.summary() == "none"


def test_percent_of_roof():
    model = from_measurements("test", 100e12, {"dram": 800e9})
    # memory-bound point: roof is B*I
    assert abs(model.percent_of_roof(1.0, 400e9, "dram") - 50.0) < 1e-9
    # compute-bound point: roof is Fp
    assert abs(model.percent_of_roof(1e4, 100e12, "dram") - 100.0) < 1e-9


def test_gap_table_rows():
    model = from_measurements("test", 100e12, {"l3": 8e12, "dram": 800e9})
    rows = model.gap_table([("dgemm", 1000.0, 90e12)])
    assert len(rows) == 2                      # one row per subsystem
    by_sub = {r["subsystem"]: r for r in rows}
    assert by_sub["dram"]["bound"] == "compute"
    assert abs(by_sub["dram"]["pct_of_roof"] - 90.0) < 1e-9
    assert by_sub["dram"]["attainable_flops"] == 100e12


def test_dashboard_multi_subsystem():
    model = from_measurements("test", 1e12, {"l3": 1e11, "dram": 1e10})
    art = model.dashboard(marks=[("dgemm", 64.0, 9e11)])
    assert "roofline[test]" in art
    assert "legend:" in art and "*=l3" in art and "+=dram" in art
    assert "D=dgemm" in art
    grid_lines = art.splitlines()[1:-1]
    assert any("D" in line for line in grid_lines)  # marker actually drawn
    # deterministic: same inputs, same art
    assert art == model.dashboard(marks=[("dgemm", 64.0, 9e11)])
