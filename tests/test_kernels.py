"""Per-kernel validation: sweep shapes/dtypes, assert_allclose against the
pure-jnp oracle (pallas kernels run in interpret mode on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.kernels.decode_attention import (decode_attention_ref,
                                            paged_decode_attention_pallas,
                                            paged_decode_ref)
from repro.kernels.decode_attention.decode_attention import (
    KV_BLOCK_VMEM_BYTES, pages_per_block)
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


# A block is P pages (``pages_per_block``): 16 for f32 and 32 for bf16 at
# the phi4 / nemo head shapes and page 16, 2 and 4 for the MHA case's page
# of 32. ``lengths`` None draws them at random.
@pytest.mark.parametrize("b,hq,hkv,d,page,npages,maxp,lengths", [
    pytest.param(2, 8, 2, 64, 16, 32, 4, None, id="2-8-2-64-16-32-4"),
    pytest.param(4, 4, 4, 32, 8, 16, 8, None, id="4-4-4-32-8-16-8"),
    pytest.param(1, 16, 1, 128, 32, 8, 2, None, id="1-16-1-128-32-8-2"),
    # phi4-mini's heads; 40 pages, not a multiple of P; lengths of one
    # token, a page, an f32 block, a bf16 block, the whole table, between
    pytest.param(6, 24, 8, 128, 16, 64, 40, (1, 16, 256, 512, 640, 300),
                 id="phi4-heads"),
    # mistral-nemo's heads; 33 pages; one past a page, short of a block
    pytest.param(5, 32, 8, 128, 16, 64, 33, (1, 17, 528, 255, 257),
                 id="nemo-heads"),
    # MHA with a page of 32 tokens: a smaller P; lengths on a page, on an
    # f32 and a bf16 block, the whole table of 7 pages
    pytest.param(5, 32, 32, 128, 32, 16, 7, (1, 32, 64, 128, 224),
                 id="mha-wide-page"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_sweep(b, hq, hkv, d, page, npages, maxp, lengths,
                            dtype):
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((b, hq, d)), dtype)
    kp = jnp.asarray(rng.standard_normal((npages, hkv, page, d)), dtype)
    vp = jnp.asarray(rng.standard_normal((npages, hkv, page, d)), dtype)
    bt = jnp.asarray(rng.integers(0, npages, (b, maxp)), jnp.int32)
    if lengths is None:
        lengths = rng.integers(1, maxp * page + 1, (b,))
    lengths = jnp.asarray(lengths, jnp.int32)
    ref = paged_decode_ref(q, kp, vp, bt, lengths)
    out = paged_decode_attention_pallas(q, kp, vp, bt, lengths,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_paged_decode_never_reads_past_the_length():
    """Block-table entries past each length point at a page of NaN: the
    kernel copies no such page, so its output is finite and equals the
    reference over a table whose dead entries point at a finite page."""
    rng = np.random.default_rng(4)
    b, hq, hkv, d, page, npages, maxp = 5, 24, 8, 128, 16, 48, 40
    lengths = np.array([1, 16, 255, 256, 300])
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    kp = rng.standard_normal((npages, hkv, page, d)).astype(np.float32)
    vp = rng.standard_normal((npages, hkv, page, d)).astype(np.float32)
    nan_page = npages - 1
    kp[nan_page] = vp[nan_page] = np.nan
    bt = rng.integers(0, nan_page, (b, maxp)).astype(np.int32)
    live = np.arange(maxp)[None, :] < -(-lengths[:, None] // page)
    out = paged_decode_attention_pallas(
        q, jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(np.where(live, bt, nan_page)), jnp.asarray(lengths),
        interpret=True)
    ref = paged_decode_ref(q, jnp.asarray(kp), jnp.asarray(vp),
                           jnp.asarray(bt), jnp.asarray(lengths))
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_tol(jnp.float32))


@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "mistral-nemo-12b",
                                  "llama2-7b"])
def test_pages_per_block_fits_the_vmem_budget(arch):
    """P is the largest power of two whose double-buffered f32 K and V
    blocks fit the budget, and never more than the table's pages."""
    a = get_arch(arch)
    page_bytes = a.n_kv_heads * 16 * a.resolved_head_dim * 4
    for maxp in (1, 3, 7, 320, 528, 4096):
        p = pages_per_block(a.n_kv_heads, 16, a.resolved_head_dim, 4, maxp)
        assert 1 <= p <= maxp
        assert 2 * 2 * p * page_bytes <= KV_BLOCK_VMEM_BYTES
        if p < maxp:        # not capped by the table: the budget's largest
            assert p & (p - 1) == 0
            assert 2 * 2 * (2 * p) * page_bytes > KV_BLOCK_VMEM_BYTES
    assert pages_per_block(a.n_kv_heads, 16, a.resolved_head_dim, 4,
                           4096) >= (16 if a.n_kv_heads == 8 else 4)


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
