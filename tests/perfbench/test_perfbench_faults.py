"""Runs driven past the look for a chip, with the timed path broken
underneath the harness: ``correct`` has to come out false, once for each
fault a cell can have. Sound runs of the same tiny cells come out true.

On the CPU a microsecond kernel's host-timed score and its steady rate are
both set by dispatch, so their ratio says nothing here: the cells below
drop ``score_gap`` except where its fault is planted."""

from __future__ import annotations

import dataclasses
import io
import json
import time
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import pytest


def run(cell, v5e):
    from perfbench import run as harness
    line, compared = harness.run_cell(cell, 2 ** 31 + 11, 0.01, False,
                                      time.perf_counter(), peaks=v5e)
    failed = {c.name for c in compared if not c.ok}
    return line, failed


def kernel_cell(make_cell, name, keep_score=False):
    cell = make_cell(name)
    if not keep_score:
        cell.traffic["limits"].pop("score_gap")
    return cell


@pytest.mark.parametrize("name", ["roofs.fp_cio", "roofs.ba_cio"])
def test_sound_kernel_run_is_correct(make_cell, v5e, name):
    line, failed = run(kernel_cell(make_cell, name), v5e)
    assert failed == set() and line["failed"] == 0


@pytest.mark.parametrize("name,number", [("roofs.fp_cio", "gemm_err"),
                                         ("roofs.ba_cio", "triad_err")])
def test_answer_altered_where_produced(make_cell, v5e, monkeypatch, name,
                                       number):
    from repro.core.exec_cache import ExecutableCache

    compile_ = ExecutableCache._lower_and_compile

    def altered(fn, args, static):
        exe = compile_(fn, args, static)
        return lambda *a: exe(*a).reshape(-1).at[0].add(1.0).reshape(
            exe(*a).shape)

    monkeypatch.setattr(ExecutableCache, "_lower_and_compile",
                        staticmethod(altered))
    from repro.core import default_cache
    default_cache().clear()
    try:
        _, failed = run(kernel_cell(make_cell, name), v5e)
    finally:
        default_cache().clear()
    assert number in failed


@pytest.mark.parametrize("name", ["roofs.fp_cio", "roofs.ba_cio"])
def test_verdict_altered(make_cell, v5e, monkeypatch, name):
    """The session names the config with the least work as its verdict."""
    from repro.core.tuner import Tuner

    tune = Tuner.tune

    def slowest(self, *a, **kw):
        result = tune(self, *a, **kw)
        first = next(iter(self.space.configs()))
        return dataclasses.replace(result, best_config=first)

    monkeypatch.setattr(Tuner, "tune", slowest)
    _, failed = run(kernel_cell(make_cell, name), v5e)
    assert "verdict_gap" in failed


def test_score_counts_its_work_a_hundredfold(make_cell, v5e, monkeypatch):
    import benchmarks.common as common

    timed = common.timed_sampler
    monkeypatch.setattr(common, "timed_sampler",
                        lambda fn, work, **kw: timed(fn, 100.0 * work, **kw))
    _, failed = run(kernel_cell(make_cell, "roofs.fp_cio", keep_score=True),
                    v5e)
    assert "score_gap" in failed


def test_failed_session_is_not_correct(make_cell, v5e, monkeypatch):
    from repro.core.tuner import Tuner

    def broken(self, *a, **kw):
        raise RuntimeError("planted")

    monkeypatch.setattr(Tuner, "tune", broken)
    from perfbench import run as harness
    cell = kernel_cell(make_cell, "roofs.ba_cio")
    line, compared = harness.run_cell(cell, 5, 0.01, False,
                                      time.perf_counter(), peaks=v5e)
    assert line["failed"] == line["attempted"] == 1   # the window stops
    out = io.StringIO()
    with redirect_stdout(out):
        harness.emit(line, compared)
    assert json.loads(out.getvalue().splitlines()[-1])["correct"] is False


def _wrap_train_step(monkeypatch, wrap):
    import repro.models.workloads as workloads

    original = workloads.train_step_fn
    monkeypatch.setattr(workloads, "train_step_fn",
                        lambda cfg, step: wrap(original(cfg, step)))


def test_sound_train_run_is_correct(make_cell, v5e):
    _, failed = run(make_cell("granite3_2b.flash_cio"), v5e)
    assert failed == set()


def _unchanged(step):
    def fault(params, batch):
        loss, grads = step(params, batch)
        return loss, jax.tree.map(jnp.zeros_like, grads)
    return fault


def _half_batch(step):
    def fault(params, batch):
        tokens = batch["tokens"]
        return step(params, {"tokens": tokens[:tokens.shape[0] // 2]})
    return fault


def _answer_altered(step):
    def fault(params, batch):
        loss, grads = step(params, batch)
        grads["embed"]["table"] = grads["embed"]["table"] * 2
        return loss, grads
    return fault


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _answer_altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_train_step_faults(make_cell, v5e, monkeypatch, fault):
    _wrap_train_step(monkeypatch, fault)
    _, failed = run(make_cell("granite3_2b.flash_cio"), v5e)
    assert "grad_gap" in failed or "loss_gap" in failed
