"""The command refuses a machine without a TPU; a run's last line has the
keys the contract names, checks last."""

from __future__ import annotations

import io
import json
import os
import shutil
import subprocess
import sys
import time
from contextlib import redirect_stdout
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def _run_py(cwd: Path) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "roofs.fp_cio",
         "--seed", "2147483700", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _run_py(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_keys(make_cell, v5e, trace):
    from perfbench import run

    cell = make_cell("roofs.fp_cio")
    line, compared = run.run_cell(cell, 2 ** 31 + 7, 0.01, bool(trace),
                                  time.perf_counter(), peaks=v5e)
    out = io.StringIO()
    with redirect_stdout(out):
        run.emit(line, compared)
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    keys = list(result)
    assert keys[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(keys)
    assert ("breakdown" in result) == bool(trace)
    assert isinstance(result["correct"], bool)
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    wanted = {m["name"] for m in (cell.per_layer if trace
                                  else cell.end_to_end)}
    # no trace-derived reading exists on the CPU: those readers stay silent
    assert set(result["metrics"]) <= wanted
    assert "setup_s" in result["metrics"] or trace
    assert set(result["checks"]) == {"gemm_err", "verdict_gap",
                                     "score_gap"}
    for check in result["checks"].values():
        assert set(check) == {"value", "limit"}
