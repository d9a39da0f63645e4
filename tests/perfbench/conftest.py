"""The benchmark's tests import ``perfbench`` from the repository root, and
build its cells at sizes a CPU test run can hold."""

import copy
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

#: budgets small enough for the CPU: the check, not the timing, is tested
TINY_SETTINGS = {"max_invocations": 2, "max_iterations": 8,
                 "max_time_s": 0.2}


def tiny_cell(name: str, **traffic):
    """The cell ``name`` of BENCHMARK.json, its traffic file as committed
    (limits included) with small budgets, on a configuration cut to CPU
    size."""
    from perfbench.cell import Cell

    cell = Cell.load(name, ROOT)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.traffic["settings"].update(TINY_SETTINGS)
    cell.traffic.update(traffic)
    family = cell.traffic["family"]
    if family == "dgemm":
        # work that differs 4096-fold, so that the CPU's ranking is clear
        cell.config = {"dgemm": {"n": [16, 256], "m": [16, 256],
                                 "k": [16, 256]}}
    elif family == "triad":
        cell.config = {"triad": {"n_bytes": [1 << 14, 1 << 22]}}
    else:
        model = json.loads((ROOT / "perfbench/configs/granite_3_2b.json")
                           .read_text())["model"]
        model.update(name="granite-3-2b-tiny", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                     vocab_size=500, dtype="float32")
        cell.config = {"model": model, "batch": 2, "seq_len": 64,
                       "remat": True}
        cell.traffic["space"] = {"use_flash": [1], "flash_block_q": [32, 64],
                                 "flash_block_k": [64], "remat": [1]}
    return cell


@pytest.fixture
def make_cell():
    return tiny_cell


@pytest.fixture
def v5e():
    from perfbench.peaks import PEAKS
    return PEAKS["TPU v5 lite"]
