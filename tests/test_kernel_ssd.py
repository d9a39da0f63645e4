"""Pallas SSD chunk-scan kernel vs jnp oracle and vs the model-zoo math."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ssd import ssd_chunk_scan, ssd_chunk_scan_pallas, ssd_chunk_scan_ref

KEY = jax.random.key(0)


def make_inputs(B=2, H=3, C=4, Q=16, P=8, N=16):
    xdt = jax.random.normal(jax.random.fold_in(KEY, 1), (B, H, C, Q, P)) * 0.5
    bm = jax.random.normal(jax.random.fold_in(KEY, 2), (B, C, Q, N)) * 0.5
    cm = jax.random.normal(jax.random.fold_in(KEY, 3), (B, C, Q, N)) * 0.5
    # cum must be a within-chunk cumsum of negatives (decays)
    a = -jax.random.uniform(jax.random.fold_in(KEY, 4), (B, H, C, Q),
                            minval=0.01, maxval=0.2)
    cum = jnp.cumsum(a, axis=-1)
    return xdt, bm, cm, cum


@pytest.mark.parametrize("shape", [dict(), dict(Q=32, P=16, N=8),
                                   dict(B=1, H=8, C=2), dict(C=8, Q=8)])
def test_kernel_matches_ref(shape):
    xdt, bm, cm, cum = make_inputs(**shape)
    out = ssd_chunk_scan_pallas(xdt, bm, cm, cum, interpret=True)
    ref = ssd_chunk_scan_ref(xdt, bm, cm, cum)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ops_wrapper_matches_model_math():
    """ssd_chunk_scan (kernel path) must equal the model zoo's chunked SSD
    core (ssd_forward's y before the D-skip/gate) on identical inputs."""
    from repro.models import params as P_
    from repro.models import ssd as model_ssd
    from repro.models.config import ModelConfig

    cfg = ModelConfig(name="t", family="ssm", n_layers=1, d_model=32,
                      n_heads=4, n_kv_heads=4, d_ff=0, vocab_size=64,
                      ssm_state=16, ssm_head_dim=8, ssm_chunk=8,
                      dtype="float32")
    p = P_.materialize(jax.random.key(0), model_ssd.ssd_defs(cfg))
    u = jax.random.normal(jax.random.key(1), (2, 32, 32)) * 0.5

    # reproduce the model's pre-scan tensors
    z, x, b, c, dt, A = model_ssd._project(p, u, cfg)
    x = jax.nn.silu(model_ssd._causal_conv(x, p["conv_x"]))
    b = jax.nn.silu(model_ssd._causal_conv(b, p["conv_b"]))
    c = jax.nn.silu(model_ssd._causal_conv(c, p["conv_c"]))
    B_, S, _ = u.shape
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    xh = x.reshape(B_, S, H, P)

    y_kernel = ssd_chunk_scan(xh, dt, A, b, c, chunk=cfg.ssm_chunk,
                              interpret=True)

    # reference: the full model layer minus (D-skip + gate + out_proj)
    # recomputed via the sequential oracle state recurrence
    y_full = model_ssd.ssd_forward(p, u, cfg)  # smoke that shapes agree
    assert y_full.shape == u.shape
    # direct check against the chunk-scan reference math
    xdt = (xh * dt[..., None]).reshape(B_, S // 8, 8, H, P)
    xdt = jnp.moveaxis(xdt, 3, 1)
    cum = jnp.cumsum((dt * A).reshape(B_, S // 8, 8, H), axis=2)
    cum = jnp.moveaxis(cum, 3, 1)
    ref = ssd_chunk_scan_ref(xdt, b.reshape(B_, S // 8, 8, N),
                             c.reshape(B_, S // 8, 8, N), cum)
    ref = jnp.moveaxis(ref, 1, 3).reshape(B_, S, H, P)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_state_carries_across_chunks():
    """With a single head and constant decay, later chunks must see earlier
    chunks' contributions (non-zero inter-chunk term)."""
    xdt, bm, cm, cum = make_inputs(B=1, H=1, C=3, Q=8, P=4, N=4)
    out = ssd_chunk_scan_pallas(xdt, bm, cm, cum, interpret=True)
    # zeroing the first chunk's inputs must change later chunks' outputs
    xdt0 = xdt.at[:, :, 0].set(0.0)
    out0 = ssd_chunk_scan_pallas(xdt0, bm, cm, cum, interpret=True)
    assert np.abs(np.asarray(out[:, :, 1:]) -
                  np.asarray(out0[:, :, 1:])).max() > 1e-6


@pytest.mark.parametrize("chunk", [8, 16])
def test_custom_vjp_matches_reference_gradients(chunk):
    """Gradients through the kernel path (Pallas forward, custom VJP) equal
    ``jax.grad`` through the reference scan, for every input."""
    B, S, H, P, N = 2, 32, 3, 8, 16
    ks = jax.random.split(jax.random.fold_in(KEY, 9), 6)
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)))
    bm = jax.random.normal(ks[3], (B, S, N)) * 0.5
    cm = jax.random.normal(ks[4], (B, S, N)) * 0.5
    w = jax.random.normal(ks[5], (B, S, H, P))

    def grads(use_pallas):
        def loss(*args):
            y = ssd_chunk_scan(*args, chunk=chunk, use_pallas=use_pallas,
                               interpret=True)
            return jnp.sum(y * w)
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(x, dt, a, bm, cm)

    for got, want in zip(grads(True), grads(False)):
        assert bool(jnp.all(jnp.isfinite(got)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
