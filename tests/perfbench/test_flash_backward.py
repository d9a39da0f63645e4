"""The flash kernel's Pallas backward as the benchmark sees it: the counter
``flash.bwd_pallas_calls`` in a model cell's ``TuningResult.metrics`` at
CPU size, and the trace readers ``flash_bwd_roofline`` and
``flash_roofline`` on a synthetic trace holding the forward's and the
backward's events."""

from __future__ import annotations

import json
import types
from pathlib import Path

import pytest

from perfbench.trace import Trace

ROOT = Path(__file__).resolve().parents[2]
GRANITE = "granite3_2b.flash_cio"
HYBRID = "granite4h_micro.ssd_cio"


def _tiny(make_cell, name: str, **space):
    """The cell at CPU size (the hybrid cut to one period of 10 layers,
    one of them attention), its space updated with ``space``."""
    cell = make_cell(name)
    if name == HYBRID:
        model = json.loads((ROOT / "perfbench/configs/granite_4h_micro.json")
                           .read_text())["model"]
        model.update(name="granite-4h-tiny", n_layers=10, d_model=64,
                     n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                     vocab_size=512, ssm_state=16, ssm_head_dim=16,
                     dtype="float32")
        cell.config = {"model": model, "batch": 1, "seq_len": 64,
                       "remat": True}
        cell.traffic["space"] = dict(cell.traffic["space"], ssd_chunk=[32],
                                     flash_block_q=[32], flash_block_k=[32])
    cell.traffic["space"] = dict(cell.traffic["space"], **space)
    return cell


def _session_counters(cell):
    from perfbench.run import settings_from
    from perfbench.session import run_session

    fam = cell.family()
    fam.cell_name = cell.name
    fam.settings = settings_from(cell.config, cell.traffic)
    fam.setup()
    record = run_session(0, fam)
    fam.release()
    assert not record.failed
    return record.result.metrics["counters"]


@pytest.mark.parametrize("name,layers", [(GRANITE, 2), (HYBRID, 1)])
def test_session_counts_the_flash_backward_layers(make_cell, name, layers):
    """Every train step program a session builds with ``use_flash`` adds
    its attention layers: 2 in the tiny granite-3, 1 in the tiny hybrid."""
    counters = _session_counters(_tiny(make_cell, name))
    calls = counters["flash.bwd_pallas_calls"]
    assert calls >= layers and calls % layers == 0
    if name == HYBRID:
        assert calls == layers * counters["model.attention_layers"]


def test_dense_build_counts_every_layer_only_with_flash():
    from repro.models.transformer import StepConfig
    from repro.models.workloads import TINY_CONFIG, build_workload
    from repro.obs.metrics import metrics

    reg = metrics()
    for use_flash in (True, False):
        before = reg.snapshot()
        build_workload("train_step", step=StepConfig(use_flash=use_flash))
        build_workload("prefill_step", step=StepConfig(use_flash=use_flash))
        got = reg.delta(before)["counters"].get("flash.bwd_pallas_calls")
        assert got == (TINY_CONFIG.n_layers if use_flash else None)


def _device(pid, ops=()):
    """A device process with an "XLA Ops" line."""
    meta = [{"ph": "M", "pid": pid, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": pid, "tid": 1, "name": "thread_name",
             "args": {"name": "XLA Ops"}}]
    return meta + [{"ph": "X", "pid": pid, "tid": 1, "name": name,
                    "ts": ts, "dur": dur} for name, ts, dur in ops]


def _run(ops):
    from perfbench.peaks import PEAKS
    # 3.94e9 FLOPs take 20 us at 197 TFLOP/s, 8.19e5 bytes 1 us at 819 GB/s
    family = types.SimpleNamespace(flash_flops_per_call=3.94e9,
                                   flash_bytes_per_call=8.19e5)
    return types.SimpleNamespace(trace=Trace.from_events(_device(9, ops)),
                                 span=(0.0, 30_000.0), family=family,
                                 peaks=PEAKS["TPU v5 lite"])


FORWARD = [("flash_attention.16", 100.0, 100.0),
           ("flash_attention.17", 300.0, 100.0)]
BACKWARD = [("flash_bwd_dkv.10", 500.0, 150.0),
            ("flash_bwd_dq.10", 700.0, 100.0),
            ("flash_bwd_dkv.10", 900.0, 150.0),
            ("flash_bwd_dq.10", 1100.0, 100.0)]


def test_backward_share_from_both_kernels_one_call_per_dq_event():
    from perfbench.cell import load_reader

    # a backward's least time is 2.5 x 20 us of FLOPs (its bytes, 2 us,
    # bound less): two calls in 500 us of kernel time are 20%
    run = _run(FORWARD + BACKWARD + [("fusion.1", 1300.0, 400.0)])
    assert load_reader("flash_bwd_roofline")(run) == pytest.approx(20.0)


def test_forward_share_counts_no_backward_event():
    from perfbench.cell import load_reader

    # two forward calls of 20 us in 200 us, whatever the backward ran
    assert load_reader("flash_roofline")(_run(FORWARD + BACKWARD)) == \
        pytest.approx(20.0)
    assert load_reader("flash_roofline")(_run(BACKWARD)) is None


@pytest.mark.parametrize("ops", [[], FORWARD,
                                 [("flash_bwd_dkv.10", 500.0, 150.0)]])
def test_backward_share_silent_without_a_dq_event(ops):
    from perfbench.cell import load_reader

    assert load_reader("flash_bwd_roofline")(_run(ops)) is None
