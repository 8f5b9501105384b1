"""Pallas kernel validation: shape/dtype sweeps, interpret=True vs the
pure-jnp oracles in repro.kernels.ref."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as R

KEY = jax.random.PRNGKey(7)


def rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,Sq,Sk,hd,off,win", [
    (1, 4, 4, 32, 32, 32, 0, None),       # MHA causal
    (2, 4, 2, 64, 128, 32, 64, None),     # GQA + prefix offset
    (1, 8, 1, 32, 64, 16, 32, 24),        # MQA + sliding window
    (2, 6, 2, 96, 96, 64, 0, None),       # non-pow2 heads (G=3)
])
def test_flash_attention_sweep(dtype, B, H, KV, Sq, Sk, hd, off, win):
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (B, H, Sq, hd), dtype)
    k = rand(ks[1], (B, KV, Sk, hd), dtype)
    v = rand(ks[2], (B, KV, Sk, hd), dtype)
    a = ops.flash_attention(q, k, v, q_offset=off, window=win,
                            interpret=True)
    b = R.flash_attention_ref(q, k, v, q_offset=off, window=win)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,W,hd,nvalid", [
    (1, 4, 4, 64, 32, 64),
    (2, 8, 2, 256, 64, 100),
    (1, 4, 1, 128, 16, 1),
])
def test_decode_attention_sweep(dtype, B, H, KV, W, hd, nvalid):
    ks = jax.random.split(KEY, 3)
    q = rand(ks[0], (B, H, hd), dtype)
    kc = rand(ks[1], (B, KV, W, hd), dtype)
    vc = rand(ks[2], (B, KV, W, hd), dtype)
    valid = (jnp.arange(W) < nvalid).astype(jnp.int32)
    a = ops.decode_attention(q, kc, vc, valid, interpret=True)
    b = R.decode_attention_ref(q, kc, vc, valid)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_decode_ring_mask_matches_linear():
    """Ring-buffer valid mask == linear mask when no wraparound."""
    from repro.models.transformer import ring_kpos
    W, pos = 16, 9
    kpos = ring_kpos(W, jnp.asarray(pos))
    valid = ((kpos >= 0) & (kpos <= pos)).astype(jnp.int32)
    expect = (jnp.arange(W) <= pos).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(valid), np.asarray(expect))


@pytest.mark.parametrize("B,S,D,block", [(1, 16, 64, 64), (2, 33, 128, 64),
                                         (3, 8, 96, 32),
                                         (2, 512, 128, 64)])  # 2 time blocks
def test_rglru_sweep(B, S, D, block):
    ks = jax.random.split(KEY, 3)
    a = jax.random.uniform(ks[0], (B, S, D), minval=0.7, maxval=0.999)
    b = jax.random.normal(ks[1], (B, S, D)) * 0.1
    h0 = jax.random.normal(ks[2], (B, D))
    y1, h1 = ops.rglru_scan(a, b, h0, interpret=True)
    y2, h2 = R.rglru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-5)


@pytest.mark.parametrize("B,H,S,hd", [(1, 2, 16, 16), (2, 4, 32, 32),
                                      (1, 1, 64, 64)])
def test_wkv6_sweep(B, H, S, hd):
    ks = jax.random.split(KEY, 6)
    r = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, H, S, hd))
    v = jax.random.normal(ks[2], (B, H, S, hd))
    w = jax.random.uniform(ks[3], (B, H, S, hd), minval=0.8, maxval=0.999)
    u = jax.random.uniform(ks[4], (H, hd))
    s0 = jax.random.normal(ks[5], (B, H, hd, hd)) * 0.1
    y1, s1 = ops.wkv6(r, k, v, w, u, s0, interpret=True)
    y2, s2 = R.wkv6_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               atol=1e-4, rtol=1e-4)


def test_kernels_match_model_semantics():
    """The flash kernel reproduces the model's chunked attention path."""
    from repro.models.common import attention
    B, H, KV, Sq, hd = 1, 4, 2, 32, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd))
    k = jax.random.normal(ks[1], (B, Sq, KV, hd))
    v = jax.random.normal(ks[2], (B, Sq, KV, hd))
    model_out = attention(q, k, v)
    kern_out = ops.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), interpret=True)
    np.testing.assert_allclose(np.asarray(model_out),
                               np.asarray(kern_out.transpose(0, 2, 1, 3)),
                               atol=2e-5)
