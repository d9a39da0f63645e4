"""The two cells added with granite-4.0-h: ``granite4h_micro.ssd_cio`` (the
hybrid's training step) and ``roofs.fp_bf16`` (DGEMM in bfloat16), run at
CPU size through the harness; their faults and controls; the SSD
kernel's roofline reader and counts."""

from __future__ import annotations

import copy
import json
import time
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
HYBRID = "granite4h_micro.ssd_cio"
BF16 = "roofs.fp_bf16"
#: budgets small enough for the CPU: the check, not the timing, is tested
TINY = {"max_invocations": 2, "max_iterations": 8, "max_time_s": 0.2}
SEED = 2 ** 31 + 13


def tiny_cell(name: str):
    """The cell of BENCHMARK.json with its committed limits, small budgets
    and a configuration cut to CPU size (one period of 10 layers for the
    hybrid)."""
    from perfbench.cell import Cell

    cell = Cell.load(name, ROOT)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.traffic["settings"].update(TINY)
    if name == BF16:
        cell.config = {"dgemm": {"n": [16, 256], "m": [16, 256],
                                 "k": [16, 256]}}
        return cell
    model = json.loads((ROOT / "perfbench/configs/granite_4h_micro.json")
                       .read_text())["model"]
    model.update(name="granite-4h-tiny", n_layers=10, d_model=64, n_heads=4,
                 n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512,
                 ssm_state=16, ssm_head_dim=16, dtype="float32")
    cell.config = {"model": model, "batch": 1, "seq_len": 64, "remat": True}
    cell.traffic["space"] = dict(cell.traffic["space"], ssd_chunk=[16, 32],
                                 flash_block_q=[32], flash_block_k=[32])
    return cell


def run_tiny(cell, v5e):
    from perfbench import run as harness
    line, compared = harness.run_cell(cell, SEED, 0.01, False,
                                      time.perf_counter(), peaks=v5e)
    return line, {c.name: c for c in compared}


def family_of(cell):
    from perfbench.run import settings_from
    fam = cell.family()
    fam.cell_name = cell.name
    fam.settings = settings_from(cell.config, cell.traffic)
    return fam


def test_sound_hybrid_run_is_correct(v5e):
    line, checks = run_tiny(tiny_cell(HYBRID), v5e)
    assert set(checks) == {"loss_gap", "grad_gap"}
    assert all(c.ok for c in checks.values()), checks
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert {"session_s", "setup_s", "verdict_mfu"} <= set(line["metrics"])


def test_hybrid_session_counts_its_layers_and_faults_fail(v5e):
    """A session's ``TuningResult.metrics`` holds the layers and Pallas SSD
    call sites of every step program it built (9 Mamba and 1 attention
    layer each at this size); a step that returns no change, one that
    leaves the second half of the sequence out, a doubled table gradient,
    and the float8 control each fail the check."""
    from perfbench.session import run_session
    from repro.models.transformer import StepConfig
    from repro.models.workloads import train_step_fn

    fam = family_of(tiny_cell(HYBRID))
    fam.setup()
    record = run_session(0, fam)
    assert not record.failed
    counters = record.result.metrics["counters"]
    builds = counters["model.attention_layers"]
    assert builds >= 1
    assert counters["model.mamba_layers"] == 9 * builds
    assert counters["ssd.pallas_calls"] == 9 * builds
    fam.release()

    step = jax.jit(train_step_fn(fam.arch, StepConfig(
        use_flash=True, flash_block_q=32, flash_block_k=32, remat=True,
        use_pallas_ssd=True, ssd_chunk=16)))

    def unchanged(p, b):
        loss, grads = step(p, b)
        return loss, jax.tree.map(jnp.zeros_like, grads)

    def half_sequence(p, b):
        return step(p, {"tokens": b["tokens"][:, :fam.seq // 2]})

    def altered(p, b):
        loss, grads = step(p, b)
        grads["embed"]["table"] = grads["embed"]["table"] * 2
        return loss, grads

    sound = {c.name: c for c in fam.check(SEED, [], {}, steps={"s": step})}
    assert all(c.ok for c in sound.values()), sound
    for fault in (unchanged, half_sequence, altered):
        checks = {c.name: c for c in fam.check(SEED, [], {},
                                               steps={"f": fault})}
        assert not checks["grad_gap"].ok, fault.__name__
    control = {c.name: c for c in fam.check(SEED, [], {}, control=True)}
    assert not control["grad_gap"].ok, control
    assert control["grad_gap"].value > 3 * sound["grad_gap"].value


def test_sound_bf16_gemm_run_is_correct(v5e):
    """On the CPU the host-timed score and the steady rate of a
    microsecond kernel are both set by dispatch: ``score_gap`` is left out
    here, as in the fp cell's fault tests."""
    cell = tiny_cell(BF16)
    cell.traffic["limits"].pop("score_gap")
    line, checks = run_tiny(cell, v5e)
    assert set(checks) == {"gemm_err", "verdict_gap"}
    assert all(c.ok for c in checks.values()), checks
    assert line["failed"] == 0
    assert "verdict_roof_share" in line["metrics"]


def test_bf16_gemm_control_and_altered_answer_fail():
    fam = family_of(tiny_cell(BF16))
    fam.setup()
    (program,) = fam.check(3, [], {"x": 1.0})
    (control,) = fam.check(3, [], {"x": 1.0}, control=True)
    (altered,) = fam.check(3, [], {"x": 1.0}, alter=lambda x: x.reshape(
        -1).at[0].add(1.0).reshape(x.shape))
    assert program.ok and program.value < program.limit / 3
    assert not control.ok and not altered.ok


def test_bf16_gemm_times_the_programs_bf16_executables():
    """The sessions' executables are the program's dot on bfloat16
    operands, found in its executable cache, and return bfloat16."""
    fam = family_of(tiny_cell(BF16))
    fam.setup()
    cfg = {"n": 16, "m": 256, "k": 16}
    a, b = fam.operands(jax.random.PRNGKey(0), cfg)
    assert a.dtype == b.dtype == jnp.bfloat16
    assert fam.executable(cfg)(a, b).dtype == jnp.bfloat16


def _device_trace(ops):
    from perfbench.trace import Trace
    meta = [{"ph": "M", "pid": 9, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 9, "tid": 1, "name": "thread_name",
             "args": {"name": "XLA Ops"}}]
    return Trace.from_events(meta + [
        {"ph": "X", "pid": 9, "tid": 1, "name": name, "ts": ts, "dur": dur}
        for name, ts, dur in ops])


def test_ssd_roofline_from_the_kernel_events(v5e):
    from perfbench.cell import load_reader

    family = types.SimpleNamespace(ssd_flops_per_call=1.97e9,
                                   ssd_bytes_per_call=8.19e6)
    ops = [("ssd_chunk_scan.21", 100.0, 40.0), ("ssd_chunk_scan.22", 200.0,
                                                 40.0),
           ("flash_attention.3", 300.0, 100.0)]
    run = types.SimpleNamespace(trace=_device_trace(ops), span=(0.0, 1e4),
                                family=family, peaks=v5e)
    # 8.19e6 bytes take 10 us at 819 GB/s (the FLOPs 10 us too): two calls
    # in 80 us of kernel time are 25% of the roofline
    assert load_reader("ssd_roofline")(run) == pytest.approx(25.0)
    silent = types.SimpleNamespace(trace=_device_trace(ops[2:]),
                                   span=(0.0, 1e4), family=family, peaks=v5e)
    assert load_reader("ssd_roofline")(silent) is None


def test_hybrid_counts():
    from perfbench import counts, hybrid_counts as H

    model = json.loads((ROOT / "perfbench/configs/granite_4h_micro.json")
                       .read_text())["model"]
    # 18 Mamba layers of 76.2 M matmul parameters, 2 attention layers of
    # 60.8 M, the tied head of 205.5 M: 6 x 8192 tokens x 1.697 G
    assert H.mamba_matmul_params(model) + counts.mlp_matmul_params(model) \
        == 2048 * (2 * 4096 + 2 * 128 + 64) + 4096 * 2048 + 3 * 2048 * 8192
    dense = 6.0 * 8192 * (18 * 76_152_832 + 2 * 60_817_408
                          + 2048 * 100352)
    flops = H.train_step_flops(model, 1, 8192)
    assert dense < flops < 1.05 * dense
    # one chunk of one head, every term written out
    q, p, n = 4, 2, 3
    assert H.ssd_flops(1, q, 1, p, n, q) == q * q * n + q * q * p \
        + 4 * q * p * n
    assert H.ssd_bytes(1, 8192, 64, 64, 128) == 4 * 8192 * (
        2 * 64 * 64 + 2 * 128 + 64)
