"""The plain granite reference agrees with the program's training step at a
small size, and each cell's control, the reference one precision lower in
the program's place, fails the cell's limits."""

from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp
import pytest

from perfbench import granite_ref


def _program_step(model: dict, use_flash: bool):
    from repro.models import api
    from repro.models.config import ModelConfig
    from repro.models.params import materialize
    from repro.models.transformer import StepConfig
    from repro.models.workloads import train_step_fn

    arch = ModelConfig(**model)
    step = StepConfig(use_flash=use_flash, flash_block_q=32,
                      flash_block_k=32, remat=True)
    params = materialize(jax.random.PRNGKey(5), api.param_defs(arch))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 64), 0,
                                model["vocab_size"], jnp.int32)
    loss, grads = jax.jit(train_step_fn(arch, step))(params,
                                                     {"tokens": tokens})
    return params, tokens, float(loss), granite_ref.tree_norms(grads)


@pytest.mark.parametrize("use_flash", [False, True])
def test_reference_matches_the_program_in_float32(make_cell, use_flash):
    model = make_cell("granite3_2b.flash_cio").config["model"]
    params, tokens, loss, norms = _program_step(model, use_flash)
    ref_loss, ref_norms = granite_ref.loss_and_grad_norms(params, tokens,
                                                          model)
    assert set(norms) == set(ref_norms) and len(norms) == 11
    assert loss == pytest.approx(ref_loss, rel=1e-5)
    for path, value in ref_norms.items():
        assert value > 0, path
        assert norms[path] == pytest.approx(value, rel=1e-4), path


def test_reference_fp8_control_fails_the_limits(make_cell):
    """The bfloat16 program stays under the cell's limits, and the float8
    control reads ten times the program's gaps or more. At this size the
    control stays under the limits, which were set from its readings at
    the cell's own size on the chip (``PERF.md``); what holds at every
    size is that float8 operands part from the reference an order of
    magnitude further than the program does."""
    cell = make_cell("granite3_2b.flash_cio")
    model = dict(cell.config["model"], dtype="bfloat16")
    params, tokens, loss, norms = _program_step(model, True)
    limits = cell.traffic["limits"]
    ref_loss, ref_norms = granite_ref.loss_and_grad_norms(params, tokens,
                                                          model)
    ctl_loss, ctl_norms = granite_ref.loss_and_grad_norms(
        params, tokens, model, low=jnp.float8_e4m3fn)

    median = statistics.median(ref_norms.values())

    def gaps(l_, n_):
        return (abs(l_ - ref_loss) / abs(ref_loss),
                max(abs(n_[p] - v) / max(v, median)
                    for p, v in ref_norms.items()))

    program_loss_gap, program_grad_gap = gaps(loss, norms)
    control_loss_gap, control_grad_gap = gaps(ctl_loss, ctl_norms)
    assert program_loss_gap < limits["loss_gap"]
    assert program_grad_gap < limits["grad_gap"]
    assert control_loss_gap > 10 * program_loss_gap
    assert control_grad_gap > 10 * program_grad_gap


@pytest.mark.parametrize("name", ["roofs.fp_cio", "roofs.ba_cio"])
def test_kernel_control_fails_its_limit(make_cell, name):
    cell = make_cell(name)
    fam = cell.family()
    fam.setup()
    (program,) = fam.check(3, [], {"x": 1.0})
    (control,) = fam.check(3, [], {"x": 1.0}, control=True)
    assert program.ok and program.value < program.limit / 10
    assert not control.ok


def test_reference_rope_is_the_half_split_rotation():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 3, 2, 4))
    out = granite_ref._rope(x, 10000.0)
    assert jnp.allclose(out[:, 0], x[:, 0])        # position 0: identity
    ang = 1.0                                     # position 1, freq 1
    x1, x2 = x[:, 1, :, 0], x[:, 1, :, 2]
    assert jnp.allclose(out[:, 1, :, 0], x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                        atol=1e-6)
