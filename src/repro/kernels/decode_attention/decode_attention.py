"""Pallas TPU paged decode-attention kernel (flash-decoding over a page pool).

The KV cache lives in HBM as a global head-major page pool of every layer
``(L, n_pages, Hkv, page, D)``, so one page of every KV head is one
contiguous run (keep ``page`` a multiple of 8 for f32 pools, 16 for bf16:
the TPU tiling). Each sequence owns a list of pages (block table). A call
attends over one layer of the pool, its index a scalar-prefetched operand,
so the caller hands over the whole pool and nothing slices a layer out.

Grid: ``(batch,)``. A grid step walks its sequence's live pages only, in
blocks of ``P`` pages (``pages_per_block``): the pages of a block are
copied from HBM by hand, each page id read from the scalar-prefetched block
table (the TPU analogue of vLLM's gather inside the CUDA kernel), into a
``(Hkv, P * page, D)`` VMEM tile per K and V. The copies are
double-buffered: block ``j + 1``, or the next sequence's first block, is in
flight while block ``j`` is computed. A page that holds no position below
``lengths[b]`` is never copied, whatever its block-table entry holds, so
the work follows the live context, not the table's width. A block computes
the scores of every KV head at once, as a matmul batched over ``Hkv``, and
carries a running flash-softmax per head in VMEM scratch.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_STAT_LANES = 128
# VMEM for the double-buffered K and V blocks: 4 MiB of the 16 MiB a v5e
# kernel may scope, which leaves room for the f32 temporaries of a block
KV_BLOCK_VMEM_BYTES = 4 << 20


def pages_per_block(hkv: int, page_size: int, head_dim: int, itemsize: int,
                    max_pages: int) -> int:
    """The largest power of two P whose double-buffered K and V blocks,
    ``2 x 2 x P x Hkv x page x D x itemsize`` bytes, fit
    ``KV_BLOCK_VMEM_BYTES``, or 1 where none does; at most ``max_pages``."""
    page_bytes = hkv * page_size * head_dim * itemsize
    p = 1
    while 4 * (2 * p) * page_bytes <= KV_BLOCK_VMEM_BYTES:
        p *= 2
    return min(p, max_pages)


def whole_pool(*pools, layer, layer_ndim: int):
    """Pools of every layer and the layer to read: a pool of
    ``layer_ndim`` dims, one layer's, gains a leading layer axis of 1,
    which moves no data, and is read at its layer 0."""
    if pools[0].ndim == layer_ndim:
        return (*(p[None] for p in pools), 0)
    return (*pools, layer)


def _decode_kernel(block_table_ref, lengths_ref, layer_ref,  # scalar-prefetch
                   q_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sems, slot_ref, pending_ref,
                   m_scr, l_scr, acc_scr, *,
                   scale: float, page_size: int, pages: int):
    bi = pl.program_id(0)
    batch, max_pages = block_table_ref.shape
    block = pages * page_size

    def n_blocks(b):
        return pl.cdiv(lengths_ref[b], block)

    def copies(b, j, slot):
        """The K and V page copies of sequence b's block j: only the pages
        that hold a position below its length."""
        live = jnp.minimum(pages, pl.cdiv(lengths_ref[b], page_size)
                           - j * pages)
        layer = layer_ref[0]
        for i in range(pages):
            # a dead entry past the table's end is read, never copied from
            page_id = block_table_ref[b, jnp.minimum(j * pages + i,
                                                     max_pages - 1)]
            dst = pl.ds(i * page_size, page_size)
            yield i < live, [
                pltpu.make_async_copy(src.at[layer, page_id],
                                      buf.at[slot, :, dst, :],
                                      sems.at[kv, slot])
                for kv, (src, buf) in enumerate(((k_hbm, k_buf),
                                                 (v_hbm, v_buf)))]

    def start(b, j, slot):
        for live, cps in copies(b, j, slot):
            @pl.when(live)
            def _():
                for cp in cps:
                    cp.start()

    def wait(b, j, slot):
        for live, cps in copies(b, j, slot):
            @pl.when(live)
            def _():
                for cp in cps:
                    cp.wait()

    @pl.when(bi == 0)
    def _first():
        slot_ref[0] = 0
        pending_ref[0] = 0

    n = n_blocks(bi)

    # the previous sequence started this one's first block unless it was
    # the first sequence or had no blocks
    @pl.when((n > 0) & (pending_ref[0] == 0))
    def _start_first():
        start(bi, 0, slot_ref[0])
    pending_ref[0] = 0

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    length = lengths_ref[bi]
    q = q_ref[0].astype(jnp.float32)                   # (Hkv, group, D)

    def scores(slot):
        k = k_buf[slot].astype(jnp.float32)            # (Hkv, block, D)
        v = v_buf[slot].astype(jnp.float32)
        s = jnp.einsum("hgd,htd->hgt", q, k,
                       preferred_element_type=jnp.float32) * scale
        return s, v

    def mask_tail(j, s, v):
        """The last block: positions past the length, and pages never
        copied, hold anything, NaN included."""
        pos = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        vpos = j * block + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
        return (jnp.where(pos < length, s, NEG_INF),
                jnp.where(vpos < length, v, 0.0))

    def accumulate(s, v):                              # s: (Hkv, group, block)
        m_prev = m_scr[:, :, :1]
        l_prev = l_scr[:, :, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            l_prev * alpha + p.sum(axis=-1, keepdims=True), l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + jnp.einsum(
            "hgt,htd->hgd", p, v, preferred_element_type=jnp.float32)
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
    l = jnp.maximum(l_scr[:, :, :1], 1e-37)
    o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention_pallas(q, k_pages, v_pages, block_table, lengths,
                                  layer=0, *, scale: Optional[float] = None,
                                  interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, D); k/v_pages: (L, n_pages, Hkv, page, D), or one
    layer's (n_pages, Hkv, page, D); block_table: (B, max_pages) int32;
    lengths: (B,) int32; layer: int32 scalar, the layer of the pool
    attended over -> (B, Hq, D). ``interpret`` runs the kernel in the TPU
    interpreter (CPU tests)."""
    k_pages, v_pages, layer = whole_pool(k_pages, v_pages, layer=layer,
                                         layer_ndim=4)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    b, hq, d = q.shape
    _, n_pages, hkv, page_size, _ = k_pages.shape
    assert hq % hkv == 0
    group = hq // hkv
    max_pages = block_table.shape[1]
    scale = float(scale if scale is not None else d ** -0.5)
    pages = pages_per_block(hkv, page_size, d, k_pages.dtype.itemsize,
                            max_pages)
    # (B, Hkv, group, D): a grid step's q tile holds every kv head's group
    qg = q.reshape(b, hkv, group, d)
    kernel = functools.partial(_decode_kernel, scale=scale,
                               page_size=page_size, pages=pages)
    kv_block = (2, hkv, pages * page_size, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hkv, group, d),
                         lambda bi, bt, ln, ly: (bi, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hkv, group, d),
                               lambda bi, bt, ln, ly: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM(kv_block, k_pages.dtype),
            pltpu.VMEM(kv_block, v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),        # (K or V, slot)
            pltpu.SMEM((1,), jnp.int32),            # slot of the next block
            pltpu.SMEM((1,), jnp.int32),            # next sequence started
            pltpu.VMEM((hkv, group, _STAT_LANES), jnp.float32),
            pltpu.VMEM((hkv, group, _STAT_LANES), jnp.float32),
            pltpu.VMEM((hkv, group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        # the copy state carries from one sequence to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_decode_attention",
    )(block_table, lengths, layer, qg, k_pages, v_pages)
    return out.reshape(b, hq, d)
