"""Pure-jnp oracle for causal/cross flash attention with GQA.

This is also the *production dry-run path*: it is memory-bounded (lax.scan over
KV chunks with a running-softmax carry), so 32k-token prefill never
materializes an (Sq, Skv) score matrix, and it is written in purely *logical*
terms so GSPMD can shard Sq over the `model` mesh axis (sequence-parallel
prefill) regardless of head-count divisibility.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B, S, Hkv, D) -> (B, S, Hkv*n_rep, D)."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d)) \
        .reshape(b, s, h * n_rep, d)


def attention_dense_ref(q, k, v, *, causal: bool = True,
                        q_offset: int = 0,
                        kv_len: Optional[jnp.ndarray] = None,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """O(Sq*Skv)-memory reference. Ground truth for both the pallas kernel and
    the chunked implementation below.

    q: (B, Sq, Hq, D); k: (B, Skv, Hkv, D); v: (B, Skv, Hkv, Dv);
    Hq % Hkv == 0.
    q_offset: global position of q[0] (for chunked prefill / decode).
    kv_len: optional (B,) valid KV lengths.
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    scale = scale if scale is not None else d ** -0.5
    k = _repeat_kv(k, hq // hkv)
    v = _repeat_kv(v, hq // hkv)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.ones((b, 1, sq, skv), dtype=bool)
    if causal:
        qpos = jnp.arange(sq)[:, None] + q_offset
        kpos = jnp.arange(skv)[None, :]
        mask &= (qpos >= kpos)[None, None]
    if kv_len is not None:
        mask &= (jnp.arange(skv)[None, :] < kv_len[:, None])[:, None, None, :]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "kv_chunk", "scale_none"))
def _flash_chunked(q, k, v, q_offset, kv_len, scale, *, causal, kv_chunk,
                   scale_none):
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    n_rep = hq // hkv
    if scale_none:
        scale = d ** -0.5
    n_chunks = skv // kv_chunk
    qpos = jnp.arange(sq)[:, None] + q_offset  # (Sq, 1) global positions

    def body(carry, inputs):
        m, l, acc = carry
        kc, vc, k0 = inputs          # kc: (B, Ckv, Hkv, D); k0: chunk start
        kc = _repeat_kv(kc, n_rep)
        vc = _repeat_kv(vc, n_rep)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kc,
                       preferred_element_type=jnp.float32) * scale
        kpos = k0 + jnp.arange(kv_chunk)[None, :]
        mask = jnp.ones((b, 1, sq, kv_chunk), dtype=bool)
        if causal:
            mask &= (qpos >= kpos)[None, None]
        if kv_len is not None:
            mask &= (kpos[None] < kv_len[:, None, None])[:, None]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vc.dtype), vc,
                        preferred_element_type=jnp.float32)
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hq, sq), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, hq, sq), dtype=jnp.float32)
    dv = v.shape[-1]
    acc0 = jnp.zeros((b, hq, sq, dv), dtype=jnp.float32)
    ks = k.reshape(b, n_chunks, kv_chunk, hkv, d).swapaxes(0, 1)
    vs = v.reshape(b, n_chunks, kv_chunk, hkv, dv).swapaxes(0, 1)
    k0s = jnp.arange(n_chunks) * kv_chunk
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (ks, vs, k0s))
    out = acc / jnp.maximum(l, 1e-37)[..., None]
    return out.swapaxes(1, 2).astype(q.dtype)   # (B, Sq, Hq, D)


def flash_attention_ref(q, k, v, *, causal: bool = True, q_offset=0,
                        kv_len: Optional[jnp.ndarray] = None,
                        scale: Optional[float] = None,
                        kv_chunk: int = 256) -> jnp.ndarray:
    """Memory-bounded flash attention (chunked over KV via lax.scan)."""
    skv = k.shape[1]
    kv_chunk = min(kv_chunk, skv)
    if skv % kv_chunk:                       # fall back for ragged chunking
        return attention_dense_ref(q, k, v, causal=causal, q_offset=q_offset,
                                   kv_len=kv_len, scale=scale)
    q_offset = jnp.asarray(q_offset)
    return _flash_chunked(q, k, v, q_offset, kv_len,
                          jnp.float32(scale if scale is not None else 0.0),
                          causal=causal, kv_chunk=kv_chunk,
                          scale_none=scale is None)
