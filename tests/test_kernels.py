"""Per-kernel validation: sweep shapes/dtypes, assert_allclose against the
pure-jnp oracle (pallas kernels run in interpret mode on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import (decode_attention_ref,
                                            paged_decode_attention_pallas,
                                            paged_decode_ref)
from repro.kernels.flash_attention import (attention_dense_ref,
                                           flash_attention_pallas,
                                           flash_attention_ref)
from repro.kernels.ssd_scan import ssd_chunked_ref, ssd_ref, ssd_scan_pallas


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,sq,skv,hq,hkv,d", [
    (1, 128, 128, 4, 4, 64),      # MHA square
    (2, 64, 64, 8, 2, 32),        # GQA
    (2, 128, 128, 8, 1, 64),      # MQA
    (1, 32, 128, 4, 4, 128),      # rectangular (chunked prefill q block)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_pallas_sweep(b, sq, skv, hq, hkv, d, dtype, causal):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, sq, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, skv, hkv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, skv, hkv, d)), dtype)
    off = skv - sq if causal else 0
    ref = attention_dense_ref(q, k, v, causal=causal, q_offset=off)
    out = flash_attention_pallas(q, k, v, causal=causal, q_offset=off,
                                 block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("kv_chunk", [16, 64, 256])
def test_flash_ref_chunk_invariance(kv_chunk):
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((2, 64, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 256, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 256, 2, 32)), jnp.float32)
    kvlen = jnp.array([100, 256])
    ref = attention_dense_ref(q, k, v, causal=True, q_offset=192, kv_len=kvlen)
    out = flash_attention_ref(q, k, v, causal=True, q_offset=192,
                              kv_len=kvlen, kv_chunk=kv_chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,hq,hkv,d,page,npages,maxp", [
    (2, 8, 2, 64, 16, 32, 4),
    (4, 4, 4, 32, 8, 16, 8),
    (1, 16, 1, 128, 32, 8, 2),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_sweep(b, hq, hkv, d, page, npages, maxp, dtype):
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((b, hq, d)), dtype)
    kp = jnp.asarray(rng.standard_normal((npages, hkv, page, d)), dtype)
    vp = jnp.asarray(rng.standard_normal((npages, hkv, page, d)), dtype)
    bt = jnp.asarray(rng.integers(0, npages, (b, maxp)), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, maxp * page + 1, (b,)), jnp.int32)
    ref = paged_decode_ref(q, kp, vp, bt, lengths)
    out = paged_decode_attention_pallas(q, kp, vp, bt, lengths,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_decode_ref_matches_flash_path():
    """Contiguous decode ref == dense attention on the same cache."""
    rng = np.random.default_rng(3)
    b, h, d, s = 2, 4, 32, 64
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    lengths = jnp.array([40, 64])
    out = decode_attention_ref(q, k, v, lengths)
    ref = attention_dense_ref(q[:, None], k, v, causal=False,
                              kv_len=lengths)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (2, 128, 4, 16, 2, 8, 32),
    (1, 64, 8, 32, 1, 16, 16),
    (2, 256, 2, 64, 2, 32, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_sweep(b, s, h, p, g, n, chunk, dtype):
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((b, s, h, p)), dtype)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (b, s, h)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((b, s, g, n)), dtype)
    Cm = jnp.asarray(rng.standard_normal((b, s, g, n)), dtype)
    D = jnp.asarray(rng.standard_normal((h,)), jnp.float32)
    st = jnp.asarray(rng.standard_normal((b, h, p, n)), jnp.float32) * 0.1
    y_ref, f_ref = ssd_ref(x, dt, A, Bm, Cm, D, st)
    y_c, f_c = ssd_chunked_ref(x, dt, A, Bm, Cm, D, st, chunk=chunk)
    y_p, f_p = ssd_scan_pallas(x, dt, A, Bm, Cm, D, st, chunk=chunk,
                               interpret=True)
    tol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(y_c, np.float32),
                               np.asarray(y_ref, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(y_p, np.float32),
                               np.asarray(y_ref, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(f_c), np.asarray(f_ref),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(f_p), np.asarray(f_ref),
                               rtol=1e-3, atol=1e-3)


def test_ssd_no_init_state():
    rng = np.random.default_rng(5)
    b, s, h, p, g, n = 2, 64, 4, 16, 1, 8
    x = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (b, s, h)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((b, s, g, n)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((b, s, g, n)), jnp.float32)
    D = jnp.asarray(rng.standard_normal((h,)), jnp.float32)
    y_ref, _ = ssd_ref(x, dt, A, Bm, Cm, D)
    y_p, _ = ssd_scan_pallas(x, dt, A, Bm, Cm, D, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)
