"""Latent attention and the served expert layer at a small size on the CPU:
the absorbed decode against the expanded form, the latent paged-decode
kernel (interpret mode) against its jnp reference, flash attention with
v's own head dim, the sigmoid router, the expert layer that drops nothing,
and one chip's share of the experts against the whole layer."""
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.kernels.decode_attention import (mla_decode_attention_pallas,
                                            mla_decode_ref)
from repro.kernels.decode_attention.mla_decode_attention import (
    latent_pages_per_block, pool_width)
from repro.kernels.flash_attention import (attention_dense_ref,
                                           flash_attention_pallas,
                                           flash_attention_ref)
from repro.models import mla
from repro.models.common import rms_norm
from repro.models.model import LM
from repro.models.moe import held_experts_ffn, route
from repro.serving.engine import EngineConfig, PagedEngine

# float32 on the CPU: two forms of the same sums differ by their rounding
# order alone, ~1e-6 of values of order 1
F32_TOL = dict(rtol=1e-5, atol=1e-5)


def _arch(n_experts=16, top_k=4):
    a = reduced(get_arch("moonlight-16b-a3b"), n_layers=3, d_model=64)
    return dataclasses.replace(a, moe=dataclasses.replace(
        a.moe, n_experts=n_experts, top_k=top_k, d_expert=32, d_shared=64))


def _f32(tree):
    return jax.tree.map(lambda t: t.astype(jnp.float32), tree)


@pytest.fixture(scope="module")
def layer():
    """One expert layer's float32 weights of the small arch, with a
    correction bias."""
    arch = _arch()
    params = _f32(LM(arch).init(jax.random.key(0)))
    p = jax.tree.map(lambda t: t[0], params["seg1"])
    p["router_bias"] = 0.1 * jax.random.normal(jax.random.key(1),
                                               (arch.moe.n_experts,))
    return arch, p


def _norm(x, w):
    return rms_norm(x, w, 1e-5)


def test_absorbed_decode_matches_expanded(layer):
    """The last position's attention output: the expanded form (per-head k
    and v from W_kv_b, the flash reference) against the absorbed form
    (queries in the latent space scoring the latent rows themselves)."""
    arch, p = layer
    s = 13
    h = jax.random.normal(jax.random.key(2), (1, s, arch.d_model))
    pos = jnp.arange(s)
    expanded, rows = mla.attention_full(h, p, arch, pos, norm=_norm)
    q_nope, q_pe, rows2 = mla.project(h, p, arch, pos, _norm)
    np.testing.assert_allclose(rows, rows2, **F32_TOL)
    q = mla.absorb(q_nope[0, -1], q_pe[0, -1], p, arch)      # (H, C)
    sc = jnp.einsum("hc,sc->hs", q, rows[0]) * mla.scale(arch)
    o_lat = jax.nn.softmax(sc, -1) @ rows[0, :, :arch.mla.kv_lora_rank]
    absorbed = mla.absorbed_output(o_lat, p, arch)
    np.testing.assert_allclose(absorbed, expanded[0, -1], **F32_TOL)


def test_rope_pairs_rotate_interleaved_pairs():
    x = jnp.arange(8.0).reshape(1, 1, 8) + 1.0
    got = mla.rope_pairs(x[None], jnp.asarray([3]), 100.0)[0, 0, 0]
    j = np.arange(4)
    ang = 3 * 100.0 ** (-2 * j / 8)
    x0, x1 = np.asarray(x[0, 0, 0::2]), np.asarray(x[0, 0, 1::2])
    want = np.stack([x0 * np.cos(ang) - x1 * np.sin(ang),
                     x1 * np.cos(ang) + x0 * np.sin(ang)], -1).reshape(-1)
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("lengths", [[0, 1, 16, 17], [45, 3, 32, 1]],
                         ids=["empty-one-boundary", "ragged"])
def test_mla_decode_kernel_matches_reference(lengths, monkeypatch):
    """Pages out of order, a length of 0 and of 1, a page boundary (16,
    17); blocks of 2 pages, so a sequence spans several blocks."""
    b, h, c, value, page, n_pages, max_pages = 4, 4, 40, 32, 8, 32, 7
    width = pool_width(c)
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.standard_normal((n_pages, page, width)),
                       jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, h, c)), jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, n_pages))[
        :b * max_pages].reshape(b, max_pages), jnp.int32)
    ln = jnp.asarray(lengths, jnp.int32)
    mod = importlib.import_module(
        "repro.kernels.decode_attention.mla_decode_attention")
    monkeypatch.setattr(mod, "KV_BLOCK_VMEM_BYTES",
                        2 * 2 * page * width * 4)          # P = 2
    assert latent_pages_per_block(page, width, 4, max_pages) == 2
    got = mla_decode_attention_pallas.__wrapped__(    # not the jit cache
        q, pool, table, ln, value_dim=value, scale=0.3, interpret=True)
    want = mla_decode_ref(q, pool, table, ln, value_dim=value, scale=0.3)
    np.testing.assert_allclose(got, want, **F32_TOL)
    for i, n in enumerate(lengths):
        if n == 0:
            assert not np.asarray(got[i]).any()


def test_mla_decode_kernel_never_reads_past_the_length():
    """Rows past a sequence's length, NaN here, change nothing."""
    page, width = 8, 128
    pool = jnp.ones((6, page, width), jnp.float32)
    pool = pool.at[2, 3:].set(jnp.nan).at[5].set(jnp.nan)
    table = jnp.asarray([[1, 2, 5]], jnp.int32)
    q = jnp.ones((1, 2, 40), jnp.float32)
    out = mla_decode_attention_pallas(q, pool, table, jnp.asarray([11]),
                                      value_dim=32, scale=0.1,
                                      interpret=True)
    np.testing.assert_allclose(out, 1.0, rtol=1e-6)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_with_its_own_v_head_dim(causal):
    """q/k head dim 192 and v 128 (MLA's expanded prefill), GQA 4/2."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((1, 256, 4, 192)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 256, 2, 192)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 256, 2, 128)), jnp.float32)
    want = attention_dense_ref(q, k, v, causal=causal)
    got = flash_attention_pallas(q, k, v, causal=causal, interpret=True)
    assert got.shape == (1, 256, 4, 128)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    chunked = flash_attention_ref(q, k, v, causal=causal, kv_chunk=64)
    np.testing.assert_allclose(chunked, want, rtol=1e-4, atol=1e-4)


def test_router_bias_selects_but_does_not_weight():
    """A bias that lifts expert 3 past expert 0 changes the choice; the
    chosen weights are the unbiased sigmoid scores, normalised over the k,
    times the routed scaling factor."""
    moe = _arch(n_experts=4, top_k=2).moe
    w = jnp.eye(4, dtype=jnp.float32)
    x = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])       # scores: 0 > 1 > 2 > 3
    e0, _, _ = route(x, w, jnp.zeros(4), moe)
    assert sorted(np.asarray(e0[0]).tolist()) == [0, 1]
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.9])
    e1, w1, _ = route(x, w, bias, moe)
    assert sorted(np.asarray(e1[0]).tolist()) == [0, 3]
    s = jax.nn.sigmoid(x[0])
    want = {int(e): float(s[e] / (s[0] + s[3]) * moe.routed_scaling_factor)
            for e in (0, 3)}
    got = dict(zip(np.asarray(e1[0]).tolist(), np.asarray(w1[0]).tolist()))
    assert got == pytest.approx(want, rel=1e-6)


def _uncut(h, p, arch):
    """The expert layer as written: every routed expert, each computed on
    every token and weighted where the router chose it, plus the shared
    experts."""
    top_e, top_w, _ = route(h, p["router"], p["router_bias"], arch.moe)
    e = jnp.arange(arch.moe.n_experts)
    gate = jnp.sum(jnp.where(top_e[:, :, None] == e, top_w[:, :, None], 0.0),
                   axis=1)
    y = jax.vmap(lambda g, u, d: (jax.nn.silu(h @ g) * (h @ u)) @ d)(
        p["w_gate"], p["w_up"], p["w_down"])
    shared = (jax.nn.silu(h @ p["sh_gate"]) * (h @ p["sh_up"])) @ p["sh_down"]
    return jnp.einsum("etd,te->td", y, gate) + shared, shared


def _share(p, first, n):
    return {**p, **{k: p[k][first:first + n]
                    for k in ("w_gate", "w_up", "w_down")}}


@pytest.mark.parametrize("tile", [None, 8], ids=["decode", "prefill"])
def test_expert_shares_sum_to_the_uncut_layer(layer, tile):
    """Four chips of four experts each: their outputs, with the shared
    experts counted once, add up to the whole layer's; their counts to
    every assignment."""
    arch, p = layer
    h = jax.random.normal(jax.random.key(4), (37, arch.d_model))
    whole, shared = _uncut(h, p, arch)
    total, counts = 0.0, []
    for first in range(0, 16, 4):
        out, held = held_experts_ffn(h, _share(p, first, 4), arch,
                                     first=first, tile=tile)
        total = total + out - shared
        counts.append(np.asarray(held))
    np.testing.assert_allclose(total + shared, whole, **F32_TOL)
    assert np.concatenate(counts).sum() == 37 * arch.moe.top_k


@pytest.mark.parametrize("tile", [None, 8], ids=["decode", "prefill"])
def test_held_experts_drop_nothing(layer, tile):
    """Every token routed to held expert 1 (a bias that outweighs any
    score) is computed there, 37 of them with room for far fewer under a
    capacity; tokens marked invalid count for nothing."""
    arch, p = layer
    p = dict(p, router_bias=p["router_bias"].at[1].set(10.0))
    h = jax.random.normal(jax.random.key(5), (37, arch.d_model))
    here = _share(p, 0, 4)
    out, held = held_experts_ffn(h, here, arch, first=0, tile=tile)
    top_e, _, _ = route(h, p["router"], p["router_bias"], arch.moe)
    assert int(held[1]) == 37
    assert int(held.sum()) == int((np.asarray(top_e) < 4).sum())
    # the share's part of the whole: the uncut layer with experts 4..15
    # zeroed
    rest = {**p, **{k: p[k].at[4:].set(0.0)
                    for k in ("w_gate", "w_up", "w_down")}}
    np.testing.assert_allclose(out, _uncut(h, rest, arch)[0], **F32_TOL)
    valid = jnp.arange(37) < 20
    _, held = held_experts_ffn(h, here, arch, first=0, tile=tile,
                               valid=valid)
    assert int(held[1]) == 20


def test_engine_prices_the_latent_row():
    """The engine's KV bytes per token are one float32 latent row a layer
    at Moonlight's published widths."""
    arch = get_arch("moonlight-16b-a3b")
    eng = PagedEngine(arch, {}, EngineConfig(max_batch=1, page_size=16,
                                             n_pages=2, max_pages_per_seq=1))
    assert eng.kv_bytes_per_token == arch.kv_bytes_per_token(4) \
        == 27 * 576 * 4
    assert [p.shape for p in eng.kv_lat] == [(1, 2, 16, 640),
                                             (26, 2, 16, 640)]
