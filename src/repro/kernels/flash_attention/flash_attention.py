"""Pallas TPU flash-attention kernel (prefill/train hot-spot).

TPU-native adaptation: explicit VMEM tiling via BlockSpec, MXU-aligned
(block_q x head_dim) @ (head_dim x block_k) matmuls, fp32 running-softmax
carried in VMEM scratch across the innermost (KV) grid dimension. Causal
masking is applied per-tile and fully-masked tiles short-circuit via
``pl.when`` (the tile is still scheduled; the MXU work is skipped).

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks) — KV is the minormost
dimension so the (m, l, acc) scratch carries across it, matching the
multiple-visit accumulation pattern from the Pallas TPU docs. GQA is handled
in the K/V index_maps (each q head reads its kv head; no HBM replication).
V may have its own head dim (MLA's expanded prefill: q/k 192, v 128); the
output and the accumulator take v's.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_STAT_LANES = 128   # fp32 VMEM lane width for the m/l statistics tiles


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_k: int, causal: bool,
                  q_offset: int, num_kv_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q + q_offset
    k_start = ki * block_k
    live = jnp.bool_(True) if not causal else (q_start + block_q - 1 >= k_start)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (block_q, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (block_k, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_scr[:, :1]                        # lanes hold equal values
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                       # (block_q, block_k)
        alpha = jnp.exp(m_prev - m_new)              # (block_q, 1)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :1], 1e-37)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "q_offset", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q, k, v, *, scale: Optional[float] = None,
                           causal: bool = True, q_offset: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False) -> jnp.ndarray:
    """q, k: (B, Sq|Skv, Hq|Hkv, D); v: (B, Skv, Hkv, Dv). Returns
    (B, Sq, Hq, Dv)."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    dv = v.shape[-1]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = float(scale if scale is not None else d ** -0.5)
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0, (sq, skv, block_q, block_k)
    nq, nk = sq // block_q, skv // block_k

    qT = q.swapaxes(1, 2)        # (B, H, S, D): clean 2D VMEM tiles
    kT = k.swapaxes(1, 2)
    vT = v.swapaxes(1, 2)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, q_offset=q_offset, num_kv_blocks=nk)
    out = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, h, qi, ki: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, h, qi, ki, g=group: (bi, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda bi, h, qi, ki, g=group: (bi, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dv),
                               lambda bi, h, qi, ki: (bi, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qT, kT, vT)
    return out.swapaxes(1, 2)
