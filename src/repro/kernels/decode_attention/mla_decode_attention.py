"""Pallas TPU paged decode attention over latent rows (MLA, absorbed form).

The cache of latent-attention layers is one pool of latent rows
``(L, n_pages, page, W)``: per layer and token, the normed latent ``c``
(its first ``value_dim`` columns) and the rotary key ``k_pe`` after it,
shared by every head, then zeros up to ``W``, a multiple of 128 lanes. A decode
query is absorbed into the same space, ``q = [q_nope W_UK^T, q_pe]`` per
head, so every head scores against the whole row and accumulates over its
first ``value_dim`` columns: one shared "KV head" for all heads, as in
multi-query attention. A call attends over one layer of the pool, its
index a scalar-prefetched operand, so nothing slices a layer out.

MLA's row is 576 wide. The TPU's (8, 128) tiling pads it to 640 in HBM
whatever its logical width, and a page copy has to be tile-aligned, so the
pool holds 640 columns: the padding costs no memory that a 576-wide pool
would not, and the scores' matmul contracts over 640.

Grid: ``(batch,)``, as ``decode_attention.py``'s kernel. A grid step walks
its sequence's live pages only, in blocks of ``P`` pages, each page copied
by hand from HBM into a ``(P * page, W)`` VMEM tile, its id read from the
scalar-prefetched block table, double-buffered across blocks and into the
next sequence's first block. Each live page is read from HBM once a layer.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention.decode_attention import (
    KV_BLOCK_VMEM_BYTES, NEG_INF, _STAT_LANES, whole_pool)


LANES = 128


def pool_width(latent_dim: int) -> int:
    """A latent row's width in the pool: ``latent_dim`` up to 128 lanes."""
    return -(-latent_dim // LANES) * LANES


def latent_pages_per_block(page_size: int, width: int, itemsize: int,
                           max_pages: int) -> int:
    """The largest power of two P whose double-buffered block, ``2 x P x
    page x width x itemsize`` bytes, fits ``KV_BLOCK_VMEM_BYTES``, or 1
    where none does; at most ``max_pages``."""
    page_bytes = page_size * width * itemsize
    p = 1
    while 2 * (2 * p) * page_bytes <= KV_BLOCK_VMEM_BYTES:
        p *= 2
    return min(p, max_pages)


def _mla_kernel(block_table_ref, lengths_ref, layer_ref,  # scalar-prefetch
                q_ref, pool_hbm, o_ref,
                buf, sems, slot_ref, pending_ref,
                m_scr, l_scr, acc_scr, *,
                scale: float, page_size: int, pages: int, value_dim: int):
    bi = pl.program_id(0)
    batch, max_pages = block_table_ref.shape
    block = pages * page_size

    def n_blocks(b):
        return pl.cdiv(lengths_ref[b], block)

    def copies(b, j, slot):
        """Sequence b's block j: a copy of each page that holds a position
        below its length."""
        live = jnp.minimum(pages, pl.cdiv(lengths_ref[b], page_size)
                           - j * pages)
        layer = layer_ref[0]
        for i in range(pages):
            page_id = block_table_ref[b, jnp.minimum(j * pages + i,
                                                     max_pages - 1)]
            yield i < live, pltpu.make_async_copy(
                pool_hbm.at[layer, page_id],
                buf.at[slot, pl.ds(i * page_size, page_size), :],
                sems.at[slot])

    def start(b, j, slot):
        for live, cp in copies(b, j, slot):
            @pl.when(live)
            def _():
                cp.start()

    def wait(b, j, slot):
        for live, cp in copies(b, j, slot):
            @pl.when(live)
            def _():
                cp.wait()

    @pl.when(bi == 0)
    def _first():
        slot_ref[0] = 0
        pending_ref[0] = 0

    n = n_blocks(bi)

    @pl.when((n > 0) & (pending_ref[0] == 0))
    def _start_first():
        start(bi, 0, slot_ref[0])
    pending_ref[0] = 0

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    length = lengths_ref[bi]
    q = q_ref[0].astype(jnp.float32)                   # (H, W)

    def scores(slot):
        k = buf[slot].astype(jnp.float32)              # (block, W)
        v = buf[slot, :, :value_dim].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        return s, v                                    # (H, block)

    def mask_tail(j, s, v):
        """The last block: rows past the length, and pages never copied,
        hold anything, NaN included."""
        pos = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        vpos = j * block + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        return (jnp.where(pos < length, s, NEG_INF),
                jnp.where(vpos < length, v, 0.0))

    def accumulate(s, v):
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            l_prev * alpha + p.sum(axis=-1, keepdims=True), l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    def body(j, carry):
        slot = slot_ref[0]
        nxt = 1 - slot

        @pl.when(j + 1 < n)
        def _next_block():
            start(bi, j + 1, nxt)

        b_next = jnp.minimum(bi + 1, batch - 1)

        @pl.when((j + 1 == n) & (bi + 1 < batch) & (n_blocks(b_next) > 0))
        def _next_sequence():
            start(b_next, 0, nxt)
            pending_ref[0] = 1

        wait(bi, j, slot)

        @pl.when((j + 1) * block <= length)
        def _full():
            accumulate(*scores(slot))

        @pl.when((j + 1) * block > length)
        def _last():
            accumulate(*mask_tail(j, *scores(slot)))
        slot_ref[0] = nxt
        return carry

    jax.lax.fori_loop(0, n, body, 0)
    l = jnp.maximum(l_scr[:, :1], 1e-37)
    o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("value_dim", "scale",
                                             "interpret"))
def mla_decode_attention_pallas(q, pool, block_table, lengths, layer=0, *,
                                value_dim: int, scale: float,
                                interpret: bool = False) -> jnp.ndarray:
    """q: (B, H, C) absorbed queries; pool: (L, n_pages, page, W) latent
    rows, or one layer's (n_pages, page, W), W = ``pool_width(C)``;
    block_table: (B, max_pages) int32; lengths: (B,) int32; layer: int32
    scalar, the layer of the pool attended over -> (B, H, value_dim),
    attention over each row's first ``value_dim`` columns."""
    pool, layer = whole_pool(pool, layer=layer, layer_ndim=3)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    *_, page_size, c = pool.shape
    q = jnp.pad(q, ((0, 0), (0, 0), (0, c - q.shape[-1])))
    b, h, _ = q.shape
    max_pages = block_table.shape[1]
    pages = latent_pages_per_block(page_size, c, pool.dtype.itemsize,
                                   max_pages)
    kernel = functools.partial(_mla_kernel, scale=float(scale),
                               page_size=page_size, pages=pages,
                               value_dim=value_dim)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, c), lambda bi, bt, ln, ly: (bi, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, value_dim),
                               lambda bi, bt, ln, ly: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages * page_size, c), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),          # one per slot
            pltpu.SMEM((1,), jnp.int32),            # slot of the next block
            pltpu.SMEM((1,), jnp.int32),            # next sequence started
            pltpu.VMEM((h, _STAT_LANES), jnp.float32),
            pltpu.VMEM((h, _STAT_LANES), jnp.float32),
            pltpu.VMEM((h, value_dim), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, value_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="mla_decode_attention",
    )(block_table, lengths, layer, q, pool)


def mla_decode_ref(q, pool, block_table, lengths, layer=0, *,
                   value_dim: int, scale: float) -> jnp.ndarray:
    """The jnp reference: every sequence's pages of the layer gathered into
    one contiguous (B, S, C) block, masked by its length."""
    pool, layer = whole_pool(pool, layer=layer, layer_ndim=3)
    b, _, c = q.shape
    rows = pool[layer, block_table].reshape(b, -1, pool.shape[-1])[..., :c]
    rows = rows.astype(jnp.float32)
    s = jnp.einsum("bhc,bsc->bhs", q.astype(jnp.float32), rows,
                   preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(rows.shape[1])[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(valid[:, None, :], p, 0.0)
    out = jnp.einsum("bhs,bsv->bhv", p, rows[..., :value_dim],
                     preferred_element_type=jnp.float32)
    out = out / jnp.maximum(p.sum(-1, keepdims=True), 1e-37)
    return out.astype(q.dtype)


def mla_decode_attention(q, pool, block_table, lengths, layer=0, *,
                         value_dim: int, scale: float,
                         use_pallas: Optional[bool] = None,
                         interpret: bool = False) -> jnp.ndarray:
    """The Pallas kernel on a TPU (or in interpret mode), else the jnp
    reference."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        return mla_decode_attention_pallas(
            q, pool, block_table, lengths, layer, value_dim=value_dim,
            scale=scale, interpret=interpret)
    return mla_decode_ref(q, pool, block_table, lengths, layer,
                          value_dim=value_dim, scale=scale)
