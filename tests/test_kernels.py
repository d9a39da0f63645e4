"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.matmul import matmul, matmul_ref, vmem_bytes
from repro.kernels.triad import triad, triad_ref

KEY = jax.random.key(0)


def _tol(dtype):
    return dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


def _contraction_tol(dtype):
    # looser f32 bound: the blocked kernel's accumulation order differs from
    # the unblocked oracle over contraction dims of a few hundred
    return dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,k", [(128, 128, 128), (256, 512, 384),
                                   (300, 450, 200), (64, 64, 64),
                                   (1024, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_shapes_dtypes(m, n, k, dtype):
    a = jax.random.normal(jax.random.fold_in(KEY, m + n), (m, k), dtype)
    b = jax.random.normal(jax.random.fold_in(KEY, k), (k, n), dtype)
    out = matmul(a, b, bm=128, bn=128, bk=64, interpret=True)
    ref = matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               **_contraction_tol(dtype))


@pytest.mark.parametrize("bm,bn,bk", [(64, 64, 64), (128, 256, 64),
                                      (256, 128, 128)])
def test_matmul_block_sweep(bm, bn, bk):
    a = jax.random.normal(jax.random.fold_in(KEY, 1), (256, 256), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(KEY, 2), (256, 256), jnp.float32)
    out = matmul(a, b, bm=bm, bn=bn, bk=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(matmul_ref(a, b)),
                               rtol=2e-5, atol=2e-5)


def test_matmul_vmem_accounting():
    # (bm*bk + bk*bn + bm*bn)*2 + bm*bn*4 bytes
    assert vmem_bytes(128, 128, 128, 2) == (3 * 128 * 128) * 2 + 128 * 128 * 4


# ---------------------------------------------------------------------------
# triad
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1024, 4096, 100_000, 1_048_576 + 17])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_triad_sizes(n, dtype):
    a = jax.random.normal(jax.random.fold_in(KEY, n), (n,), dtype)
    b = jax.random.normal(jax.random.fold_in(KEY, n + 1), (n,), dtype)
    out = triad(a, b, gamma=3.0, br=8, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(triad_ref(a, b, 3.0), np.float32),
                               **_tol(dtype))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hq,hkv", [(8, 8), (8, 2), (8, 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_attention_gqa_causal(hq, hkv, causal):
    q = jax.random.normal(jax.random.fold_in(KEY, hq), (2, hq, 256, 64))
    k = jax.random.normal(jax.random.fold_in(KEY, hkv), (2, hkv, 256, 64))
    v = jax.random.normal(jax.random.fold_in(KEY, 9), (2, hkv, 256, 64))
    out = flash_attention(q, k, v, causal=causal, bq=64, bk=64,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [32, 96, 256])
def test_attention_sliding_window(window):
    q = jax.random.normal(jax.random.fold_in(KEY, window), (1, 4, 256, 32))
    k = jax.random.normal(jax.random.fold_in(KEY, window + 1), (1, 2, 256, 32))
    v = jax.random.normal(jax.random.fold_in(KEY, window + 2), (1, 2, 256, 32))
    out = flash_attention(q, k, v, causal=True, window=window, bq=64, bk=64,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("s", [100, 200, 250])
def test_attention_padded_lengths(s):
    q = jax.random.normal(jax.random.fold_in(KEY, s), (1, 4, s, 32))
    k = jax.random.normal(jax.random.fold_in(KEY, s + 1), (1, 4, s, 32))
    v = jax.random.normal(jax.random.fold_in(KEY, s + 2), (1, 4, s, 32))
    out = flash_attention(q, k, v, causal=True, bq=64, bk=64, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_attention_bf16():
    q = jax.random.normal(jax.random.fold_in(KEY, 77), (1, 4, 128, 64),
                          jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(KEY, 78), (1, 4, 128, 64),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(KEY, 79), (1, 4, 128, 64),
                          jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, bq=128, bk=128,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=3e-2,
                               atol=3e-2)


@pytest.mark.parametrize("hq,hkv,s,causal,window,bq,bk,dtype", [
    (4, 4, 256, True, None, 64, 64, jnp.float32),
    (8, 2, 256, True, None, 64, 128, jnp.float32),     # bq != bk
    (8, 1, 256, True, None, 128, 64, jnp.float32),     # MQA, bq > bk
    (4, 4, 256, False, None, 64, 64, jnp.float32),
    (8, 2, 256, False, None, 128, 64, jnp.float32),
    (8, 2, 256, True, 48, 64, 64, jnp.float32),        # sliding window
    (8, 1, 200, True, None, 64, 64, jnp.float32),      # S padded to 256
    (8, 2, 256, True, None, 128, 128, jnp.bfloat16),
])
def test_attention_gradients_match_reference(hq, hkv, s, causal, window, bq,
                                             bk, dtype):
    """dq, dk, dv of the kernel's Pallas backward against ``jax.vjp`` of
    the reference, in f32 at HIGHEST (bf16 inputs: the reference upcasts,
    the kernel's gradients are rounded to bf16)."""
    ks = jax.random.split(jax.random.fold_in(KEY, 1000 * hq + s), 4)
    q = jax.random.normal(ks[0], (1, hq, s, 32), dtype)
    k = jax.random.normal(ks[1], (1, hkv, s, 32), dtype)
    v = jax.random.normal(ks[2], (1, hkv, s, 32), dtype)
    g = jax.random.normal(ks[3], (1, hq, s, 32), dtype)
    _, kernel_vjp = jax.vjp(lambda *a: flash_attention(
        *a, causal=causal, window=window, bq=bq, bk=bk, interpret=True),
        q, k, v)
    with jax.default_matmul_precision("highest"):
        _, ref_vjp = jax.vjp(lambda *a: attention_ref(
            *a, causal=causal, window=window),
            *(x.astype(jnp.float32) for x in (q, k, v)))
        want = ref_vjp(g.astype(jnp.float32))
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    for name, got, ref in zip("qkv", kernel_vjp(g), want):
        assert got.dtype == dtype
        err = jnp.max(jnp.abs(got.astype(jnp.float32) - ref))
        assert float(err) <= tol * float(jnp.max(jnp.abs(ref))), name


def test_online_softmax_matches_xla_chunked():
    """The model zoo's XLA q-chunked path vs the kernel (same algorithm)."""
    from repro.models.layers import _attend
    q = jax.random.normal(jax.random.fold_in(KEY, 100), (1, 4, 2048, 32))
    k = jax.random.normal(jax.random.fold_in(KEY, 101), (1, 2, 2048, 32))
    v = jax.random.normal(jax.random.fold_in(KEY, 102), (1, 2, 2048, 32))
    chunked = _attend(q, k, v, causal=True, window=None)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
