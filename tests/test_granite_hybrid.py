"""granite-4.0-h (``granite_hybrid``): the program's training loss and every
gradient leaf against the plain float32 reference ``perfbench/granite4h_ref``
on seeded random weights, with the SSD scan in ``jax.numpy`` and in the
Pallas kernel (interpret mode), at two chunks; the layer pattern; the
reference's own chunked scan against the sequential recurrence; one
attention block's gradients through the flash kernel's backward against
the jnp attention; the counters a build adds.

The size: two periods of 10 layers, d_model 128, 4/2 attention heads of
32, d_inner 256 (8 SSM heads of 32), state 16, vocabulary 512, one
sequence of 256 tokens."""

from __future__ import annotations

import dataclasses
import statistics
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench import granite4h_ref, granite_ref  # noqa: E402
from repro import configs  # noqa: E402
from repro.configs.granite_4_0_h_micro import LAYER_TYPES  # noqa: E402
from repro.models import api, granite_hybrid  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.models.params import materialize  # noqa: E402
from repro.models.transformer import StepConfig  # noqa: E402
from repro.models.workloads import train_step_fn  # noqa: E402

MODEL = dict(name="granite-4h-test", family="granite_hybrid", n_layers=20,
             d_model=128, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=256,
             vocab_size=512, ssm_state=16, ssm_expand=2, ssm_head_dim=32,
             conv_width=4, conv_bias=True, layer_types=list(LAYER_TYPES),
             embedding_multiplier=12.0,
             attention_multiplier=0.015625, residual_multiplier=0.22,
             logits_scaling=8.0, norm_eps=1e-5, dtype="float32")
SEQ = 256

#: float32 program against float32 reference: they differ only in the
#: order of sums (the program's scan chunk, the kernel's, the head's
#: blocks), which moves the float32 loss by ~1e-7 and each gradient leaf
#: by ~1e-5 of its norm at this size. Float8 matmul operands move the
#: gradients by far more than GRAD_RTOL (~0.2 of a leaf's norm). The loss
#: of random weights sits near ln(vocab) whatever the precision (its
#: logits are small): the gradients are what a lower precision fails.
LOSS_RTOL = 1e-5
GRAD_RTOL = 1e-4


def _arch(model: dict) -> ModelConfig:
    return ModelConfig(**{k: tuple(v) if isinstance(v, list) else v
                          for k, v in model.items()})


@pytest.fixture(scope="module")
def setup():
    arch = _arch(MODEL)
    params = materialize(jax.random.PRNGKey(5), api.param_defs(arch))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, SEQ), 0,
                                MODEL["vocab_size"], jnp.int32)
    return arch, params, tokens


def _program(arch, params, tokens, *, pallas: bool, chunk: int):
    step = StepConfig(use_flash=pallas, flash_block_q=128, flash_block_k=128,
                      remat=True, use_pallas_ssd=pallas, ssd_chunk=chunk)
    loss, grads = jax.jit(train_step_fn(arch, step))(params,
                                                     {"tokens": tokens})
    return float(loss), grads


def _gaps(params, tokens, model, loss, grads):
    """(loss_gap, grad_gap) as the hybrid cell's check reads them."""
    ref_loss, ref_norms, gaps = granite4h_ref.compare(params, tokens, model,
                                                      grads)
    median = statistics.median(ref_norms.values())
    return (abs(loss - ref_loss) / abs(ref_loss),
            max(gaps[p] / max(v, median) for p, v in ref_norms.items()))


@pytest.mark.parametrize("chunk", [64, 128])
@pytest.mark.parametrize("pallas", [False, True])
def test_program_matches_reference(setup, pallas, chunk):
    arch, params, tokens = setup
    loss, grads = _program(arch, params, tokens, pallas=pallas, chunk=chunk)
    ref_loss, ref_norms, gaps = granite4h_ref.compare(params, tokens, MODEL,
                                                      grads)
    # a Mamba block's 21 leaves (two norms, 16 of the mixer, 3 of the MLP)
    # in each of pre and post, an attention block's 9, the table, ln_f
    assert set(granite_ref.tree_norms(grads)) == set(ref_norms) == set(gaps)
    assert len(ref_norms) == 2 * 21 + 9 + 2
    assert loss == pytest.approx(ref_loss, rel=LOSS_RTOL)
    for path, value in ref_norms.items():
        assert value > 0, path
        assert gaps[path] <= GRAD_RTOL * value, path


def test_float8_control_fails_the_gradient_tolerance(setup):
    """The reference with float8 matmul operands in the program's place
    parts from the float32 reference by more than GRAD_RTOL, and returns
    the program's gradient tree."""
    _, params, tokens = setup
    loss, grads = granite4h_ref.loss_and_grads(params, tokens, MODEL,
                                               low=jnp.float8_e4m3fn)
    assert jax.tree.structure(grads) == jax.tree.structure(params)
    _, grad_gap = _gaps(params, tokens, MODEL, loss, grads)
    assert grad_gap > 10 * GRAD_RTOL
    # at the full precision it is the reference itself
    loss, grads = granite4h_ref.loss_and_grads(params, tokens, MODEL)
    assert _gaps(params, tokens, MODEL, loss, grads) == (0.0, 0.0)


def test_bfloat16_program_within_the_cell_limits(setup):
    """The bfloat16 program stays under the hybrid cell's limits and the
    float8 control does not: its gradients part from the reference several
    times further than the program's (0.17-0.18 against 0.025 at this
    size)."""
    import json
    limits = json.loads((ROOT / "perfbench/traffic/ssd_cio.json")
                        .read_text())["limits"]
    model = dict(MODEL, dtype="bfloat16")
    arch = _arch(model)
    params = materialize(jax.random.PRNGKey(5), api.param_defs(arch))
    tokens = setup[2]
    program = _gaps(params, tokens, model, *_program(
        arch, params, tokens, pallas=True, chunk=64))
    control = _gaps(params, tokens, model, *granite4h_ref.loss_and_grads(
        params, tokens, model, low=jnp.float8_e4m3fn))
    assert program[0] < limits["loss_gap"] and program[1] < limits["grad_gap"]
    assert control[1] > limits["grad_gap"]
    assert control[1] > 3 * program[1]


@pytest.mark.parametrize("bq,bk", [(128, 128), (64, 128)])
def test_attention_block_gradients_flash_on_and_off(setup, bq, bk):
    """One attention block's parameter gradients through the flash
    kernel's Pallas backward (interpret mode) match those through the
    jnp attention, leaf by leaf."""
    arch, params, _ = setup
    lp = jax.tree.map(lambda a: a[0], params["attn"])
    h = jax.random.normal(jax.random.PRNGKey(8), (1, SEQ, MODEL["d_model"]))
    w = jax.random.normal(jax.random.PRNGKey(9), h.shape)

    def grads(use_flash):
        step = StepConfig(use_flash=use_flash, flash_block_q=bq,
                          flash_block_k=bk)
        return jax.grad(lambda p: jnp.sum(w * granite_hybrid._attention_block(
            h, p, arch, step)))(lp)

    flash, plain = grads(True), grads(False)
    for path, ref in jax.tree_util.tree_leaves_with_path(plain):
        got = _leaf(flash, path)
        gap = jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref)
        assert float(gap) < GRAD_RTOL, jax.tree_util.keystr(path)


def _leaf(tree, path):
    for key in path:
        tree = tree[key.key]
    return tree


def test_layer_pattern_puts_attention_at_the_published_indices():
    published = dataclasses.replace(configs.get("granite_4_0_h_micro"),
                                    n_layers=40)
    kinds = published.layer_types
    assert [i for i, k in enumerate(kinds) if k == "attention"] == \
        [5, 15, 25, 35]
    assert granite_hybrid.pattern(published) == (4, 5, 4)
    cut = configs.get("granite_4_0_h_micro")
    assert granite_hybrid.pattern(cut) == (2, 5, 4)
    assert granite_hybrid.n_mixers(cut) == (18, 2)
    # the program's stacks, read in layer order, are the published kinds
    periods, pre, post = granite_hybrid.pattern(cut)
    order = (["mamba"] * pre + ["attention"] + ["mamba"] * post) * periods
    assert tuple(order) == kinds[:cut.n_layers]
    # and the reference reads the same stacks in the same order
    layout = granite4h_ref.program_layout({"layer_types": list(kinds),
                                           "n_layers": cut.n_layers})
    assert [s == "attn" for s, _, _ in layout] == \
        [k == "attention" for k in order]


def test_pattern_without_a_whole_period_is_refused():
    # attention at 5 and 15 of 18 layers: no period of 9 repeats
    cfg = dataclasses.replace(configs.get_smoke("granite_4_0_h_micro"),
                              n_layers=18)
    with pytest.raises(ValueError, match="does not repeat"):
        granite_hybrid.pattern(cfg)


def test_published_config():
    cfg = configs.get("granite_4_0_h_micro")
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_,
            cfg.d_ff, cfg.vocab_size) == (2048, 32, 8, 64, 8192, 100352)
    assert (cfg.d_inner, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
            cfg.conv_width, cfg.conv_bias) == (4096, 64, 64, 128, 4, True)
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == (12, 1 / 64,
                                                             0.22, 8)
    assert len(cfg.layer_types) == 40 and cfg.n_layers == 20
    assert 1.65e9 < cfg.n_params() < 1.75e9


@pytest.mark.parametrize("chunk", [16, 64])
def test_reference_chunked_scan_matches_the_recurrence(chunk):
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 5)
    b, s, h, p, n = 2, 128, 3, 8, 16
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, s, n))
    cm = jax.random.normal(ks[4], (b, s, n))
    chunked = granite4h_ref.ssd_scan(x, dt, a, bm, cm, chunk=chunk)
    sequential = granite4h_ref.ssd_sequential(x, dt, a, bm, cm)
    assert jnp.allclose(chunked, sequential, rtol=1e-4, atol=1e-4)


def test_each_build_counts_its_layers():
    from repro.models.workloads import build_workload
    from repro.obs.metrics import metrics

    smoke = configs.get_smoke("granite_4_0_h_micro")
    reg = metrics()
    before = reg.snapshot()
    build_workload("train_step", smoke, step=StepConfig(use_pallas_ssd=True),
                   batch_size=1, seq_len=16)
    got = reg.delta(before)["counters"]
    assert got["model.mamba_layers"] == 9
    assert got["model.attention_layers"] == 1
    assert got["ssd.pallas_calls"] == 9
    assert "flash.bwd_pallas_calls" not in got
    before = reg.snapshot()
    build_workload("train_step", smoke, step=StepConfig(use_flash=True),
                   batch_size=1, seq_len=16)
    assert reg.delta(before)["counters"]["flash.bwd_pallas_calls"] == 1
    before = reg.snapshot()
    build_workload("train_step", smoke, step=StepConfig(), batch_size=1,
                   seq_len=16)
    got = reg.delta(before)["counters"]
    assert "ssd.pallas_calls" not in got
    assert "flash.bwd_pallas_calls" not in got
