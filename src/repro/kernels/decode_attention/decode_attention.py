"""Pallas TPU paged decode-attention kernel (flash-decoding over a page pool).

The KV cache lives in HBM as a global head-major page pool
``(n_pages, Hkv, page, D)``: one KV head's (page, D) tile is a block whose
last two dims equal the array's own, which the TPU tiling rule requires
(keep ``page`` a multiple of 8 for f32 pools, 16 for bf16). Each sequence
owns a list of pages (block table). The kernel walks a
sequence's pages (scalar-prefetched block table drives the BlockSpec index
map, i.e. page indirection happens at DMA-issue time, the TPU analogue of
vLLM's gather inside the CUDA kernel), computing a running flash-softmax
over the query-head group of each KV head in VMEM scratch.

Grid: (batch, kv_heads, max_pages) — pages minormost so (m, l, acc) scratch
carries across a sequence's pages. Pages past ``lengths[b]`` are skipped with
``pl.when`` (their block-table entries must alias a valid page id, e.g. 0).
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


def _decode_kernel(block_table_ref, lengths_ref,      # scalar-prefetch
                   q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *,
                   scale: float, page_size: int, max_pages: int, group: int):
    bi = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[bi]
    page_start = pi * page_size

    @pl.when(page_start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (group, D)
        k = k_ref[0, 0].astype(jnp.float32)            # (page, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)        # (group, page)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(l_prev * alpha
                                      + p.sum(axis=-1, keepdims=True),
                                      l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(pi == max_pages - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :1], 1e-37)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention_pallas(q, k_pages, v_pages, block_table, lengths,
                                  *, scale: Optional[float] = None,
                                  interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, D); k/v_pages: (n_pages, Hkv, page, D);
    block_table: (B, max_pages) int32; lengths: (B,) int32 -> (B, Hq, D)."""
    b, hq, d = q.shape
    n_pages, hkv, page_size, _ = k_pages.shape
    assert hq % hkv == 0
    group = hq // hkv
    max_pages = block_table.shape[1]
    scale = float(scale if scale is not None else d ** -0.5)

    # (B, Hkv, group, D) so a (group, D) q tile maps to one kv head.
    qg = q.reshape(b, hkv, group, d)
    # Pages laid out (Hkv, page, D); block index map picks (page_id, head).
    kernel = functools.partial(_decode_kernel, scale=scale,
                               page_size=page_size, max_pages=max_pages,
                               group=group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, group, d),
                         lambda bi, h, pi, bt, ln: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, d),
                         lambda bi, h, pi, bt, ln: (bt[bi, pi], h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, d),
                         lambda bi, h, pi, bt, ln: (bt[bi, pi], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, d),
                               lambda bi, h, pi, bt, ln: (bi, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, _STAT_LANES), jnp.float32),
            pltpu.VMEM((group, _STAT_LANES), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_table, lengths, qg, k_pages, v_pages)
    return out.reshape(b, hq, d)
