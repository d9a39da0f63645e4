"""Compile the main path's kernels and the granite-3-2b train step for a
TPU v5e that is described, not attached.

Nothing runs: the chip's own compiler accepts or refuses each program,
and the compiled module must hold the Pallas kernel (``tpu_custom_call``).
The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the one that runs this
file loads the TPU compiler.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul import matmul_pallas
from repro.kernels.ssd import ssd_chunk_scan_pallas
from repro.kernels.triad import LANES, triad_pallas

#: one v5e chip's HBM
HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described chip's compile can be written to the persistent cache
    but never read back; keep it out of the cache entirely."""
    from jax.experimental.compilation_cache import compilation_cache

    previous = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", previous)


@pytest.fixture
def shapes(one_chip, no_persistent_cache):
    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("heads,kv_heads,dim,tile", [
    (32, 8, 64, 512),     # granite-3-2b heads, 512 tiles
    (32, 8, 64, 128),     # granite-3-2b heads, 128 tiles
    (8, 1, 256, 512),     # multi-query attention at head dim 256
])
def test_flash_attention_compiles(shapes, heads, kv_heads, dim, tile):
    q = shapes((2, heads, 2048, dim), jnp.bfloat16)
    kv = shapes((2, kv_heads, 2048, dim), jnp.bfloat16)
    _compile(lambda q_, k_, v_: flash_attention(
        q_, k_, v_, causal=True, bq=tile, bk=tile), q, kv, kv)


@pytest.mark.parametrize("batch,seq,tile", [
    (2, 2048, 256),   # granite-3-2b's attention, bq 256
    (1, 8192, 512),   # granite-4.0-h-micro's attention layers at 8k
])
def test_flash_attention_backward_compiles(shapes, batch, seq, tile):
    """The differentiated kernel at 32/8 heads of 64: the module holds the
    two Pallas backward kernels, and no (S, S) scores (f32 scores would
    be 8.6 GB at 8192) among its temporaries."""
    q = shapes((batch, 32, seq, 64), jnp.bfloat16)
    kv = shapes((batch, 8, seq, 64), jnp.bfloat16)

    def loss(q_, k_, v_):
        out = flash_attention(q_, k_, v_, causal=True, bq=tile, bk=512)
        return jnp.sum(out.astype(jnp.float32))

    compiled = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                        q, kv, kv)
    text = compiled.as_text()
    assert "flash_bwd_dkv" in text and "flash_bwd_dq" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_matmul_bf16_4096_compiles(shapes):
    a = shapes((4096, 4096), jnp.bfloat16)
    _compile(lambda x, y: matmul_pallas(x, y, bm=512, bn=512, bk=512), a, a)


def test_triad_1gib_compiles(shapes):
    rows = (2 ** 30 // (3 * 4 * LANES)) // 256 * 256
    a = shapes((rows, LANES))
    _compile(lambda x, y: triad_pallas(x, y, 3.0, br=256), a, a)


def test_ssd_mamba2_130m_compiles(shapes):
    b, h, c, q, p, n = 1, 24, 8, 256, 64, 128
    _compile(ssd_chunk_scan_pallas, shapes((b, h, c, q, p)),
             shapes((b, c, q, n)), shapes((b, c, q, n)),
             shapes((b, h, c, q)))


def test_granite_train_step_fits_one_chip(shapes, monkeypatch):
    """The published granite-3-2b (all 40 layers) at batch 2, seq 2048,
    with the flash kernel and remat: one chip's compiler takes it, and
    its arguments, outputs and temporaries fit in 16 GiB."""
    from repro import configs
    from repro.models import api, layers
    from repro.models.params import shape_tree
    from repro.models.transformer import StepConfig
    from repro.models.workloads import train_step_fn

    # the CPU backend would pick interpret mode; this compile is for the chip
    monkeypatch.setattr(layers, "interpret_mode", lambda: False)
    cfg = configs.get("granite_3_2b")
    step = StepConfig(use_flash=True, flash_block_q=512, flash_block_k=512,
                      remat=True)
    params = jax.tree.map(lambda s: shapes(s.shape, s.dtype),
                          shape_tree(api.param_defs(cfg)))
    batch = {"tokens": shapes((2, 2048), jnp.int32)}
    compiled = _compile(train_step_fn(cfg, step), params, batch)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES


@pytest.mark.parametrize("chunk", [128, 256])
def test_ssd_granite4h_train_compiles(shapes, chunk):
    """The SSD scan of one granite-4.0-h-micro Mamba2 layer at 8192 tokens
    (64 heads of 64, state 128), differentiated: the Pallas forward and the
    reference backward of its custom VJP."""
    from repro.kernels.ssd import ssd_chunk_scan

    b, s, h, p, n = 1, 8192, 64, 64, 128

    def loss(x, dt, a, bm, cm):
        return jnp.sum(ssd_chunk_scan(x, dt, a, bm, cm, chunk=chunk))

    # the value keeps the forward: jax.grad alone would drop the kernel
    _compile(jax.value_and_grad(loss, argnums=(0, 1, 3, 4)),
             shapes((b, s, h, p)),
             shapes((b, s, h)), shapes((h,)), shapes((b, s, n)),
             shapes((b, s, n)))
