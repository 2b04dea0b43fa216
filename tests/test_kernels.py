"""Pallas kernel allclose sweeps vs pure-jnp oracles (interpret mode on
CPU): shapes × dtypes per assignment requirement (c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.extract_pack.kernel import extract_pack
from repro.kernels.extract_pack.ref import extract_pack_ref
from repro.kernels.flash_attn.kernel import flash_attention
from repro.kernels.flash_attn.ref import flash_attention_ref
from repro.kernels.verify_attn.kernel import verify_attention
from repro.kernels.verify_attn.ref import verify_attention_ref

DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize(
    "b,s,hq,hk,d,causal,window",
    [(1, 128, 2, 1, 64, True, 0),
     (2, 256, 4, 2, 64, True, 0),
     (1, 128, 4, 4, 128, False, 0),
     (1, 256, 2, 2, 64, True, 96),
     (2, 128, 8, 2, 32, True, 0)])
def test_flash_attn_sweep(b, s, hq, hk, d, causal, window, dtype):
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hk, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hk, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_kv=64, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize(
    "b,t,hq,hk,d,s,window",
    [(2, 4, 4, 2, 64, 512, 0),
     (1, 4, 8, 8, 128, 256, 0),
     (3, 1, 2, 1, 64, 512, 0),          # plain decode T=1
     (2, 8, 4, 2, 32, 1024, 0),
     (2, 4, 4, 2, 64, 1024, 256)])      # sliding window
def test_verify_attn_sweep(b, t, hq, hk, d, s, window, dtype):
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(b, t, hq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, hk, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, hk, d)), dtype)
    lengths = jnp.asarray(rng.integers(t + 1, s - t, size=(b,)), jnp.int32)
    pad = jnp.minimum(jnp.asarray(rng.integers(0, s // 4, size=(b,)),
                                  jnp.int32), lengths - 1)
    out = verify_attention(q, k, v, lengths, pad, window=window,
                           block_kv=128, interpret=True)
    ref = verify_attention_ref(q, k, v, lengths, pad, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("b,t,f,p", [(2, 4, 512, 0.5), (3, 8, 1024, 0.25),
                                     (1, 4, 1536, 1.0), (2, 4, 512, 0.0)])
def test_extract_pack_sweep(b, t, f, p, dtype):
    rng = np.random.default_rng(3)
    feats = jnp.asarray(rng.normal(size=(b, t, f)), dtype)
    toks = jnp.asarray(rng.integers(0, 999, size=(b, t)), jnp.int32)
    mask = jnp.asarray(rng.random((b, t)) < p)
    pf, pt, cnt = extract_pack(feats, toks, mask, interpret=True)
    rf, rt, rc = extract_pack_ref(feats, toks, mask)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(pt), np.asarray(rt))
    # a selection, not arithmetic: bitwise equal in every dtype
    np.testing.assert_array_equal(np.asarray(pf).view(np.uint8),
                                  np.asarray(rf).view(np.uint8))


def test_ops_wrappers_dispatch():
    """CPU dispatch goes to the oracle; force_kernel runs interpret."""
    from repro.kernels.flash_attn.ops import flash_attn
    from repro.kernels.verify_attn.ops import verify_attn
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))
    a = flash_attn(q, k, v)
    b = flash_attn(q, k, v, force_kernel=True, block_q=64, block_kv=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)
    lengths = jnp.array([100], jnp.int32)
    out_ref = verify_attn(q[:, :4], k, v, lengths)
    out_ker = verify_attn(q[:, :4], k, v, lengths, force_kernel=True,
                          block_kv=128)
    np.testing.assert_allclose(np.asarray(out_ref), np.asarray(out_ker),
                               rtol=1e-5, atol=1e-5)
