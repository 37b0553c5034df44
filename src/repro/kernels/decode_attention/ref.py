"""Pure-jnp oracles for decode attention.

Three entry points:
  * ``decode_attention_ref``  — contiguous cache, masked by per-seq lengths.
  * ``paged_decode_ref``      — vLLM-style paged cache + block table.
  * ``attend_partial`` / ``merge_partials`` — flash-decoding building blocks
    (partial softmax states (m, l, o) and their associative merge), used by the
    model decode path to combine the seq-sharded "big" KV shard with the small
    replicated "recent" append buffer.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax.numpy as jnp

NEG_INF = -1e30


def _repeat_kv_heads(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B, S, Hkv, D) -> (B, S, Hq, D)."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d)) \
        .reshape(b, s, h * n_rep, d)


def attend_partial(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   valid: Optional[jnp.ndarray] = None,
                   scale: Optional[float] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Partial flash state over one KV segment.

    q: (B, Hq, D); k, v: (B, S, Hkv, D); valid: (B, S) bool or None.
    Returns m, l: (B, Hq); o: (B, Hq, D) — unnormalized (o = sum p*v).

    GQA is computed with a grouped einsum (q reshaped to (B, Hkv, G, D)) so
    the KV tensor is never head-broadcast: repeating KV heads of a
    sequence-sharded cache forces GSPMD to all-gather the whole cache
    (measured 64 GiB x layers in the baseline).  [§Perf iteration 5]
    """
    b, hq, d = q.shape
    _, s, hkv, _ = k.shape
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, hkv, g, d)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if valid is not None:
        logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    m = logits.max(axis=-1)                              # (B, Hkv, G)
    p = jnp.exp(logits - m[..., None])
    l = p.sum(axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return (m.reshape(b, hq), l.reshape(b, hq), o.reshape(b, hq, d))


def merge_partials(parts: Sequence[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]
                   ) -> jnp.ndarray:
    """Associative merge of flash states; returns normalized (B, Hq, D)."""
    m, l, o = parts[0]
    for m2, l2, o2 in parts[1:]:
        m_new = jnp.maximum(m, m2)
        a1 = jnp.exp(m - m_new)
        a2 = jnp.exp(m2 - m_new)
        l = l * a1 + l2 * a2
        o = o * a1[..., None] + o2 * a2[..., None]
        m = m_new
    return o / jnp.maximum(l, 1e-37)[..., None]


def decode_attention_ref(q: jnp.ndarray, k_cache: jnp.ndarray,
                         v_cache: jnp.ndarray, lengths: jnp.ndarray,
                         scale: Optional[float] = None) -> jnp.ndarray:
    """q: (B, Hq, D); caches: (B, S, Hkv, D); lengths: (B,). -> (B, Hq, D)."""
    s = k_cache.shape[1]
    valid = jnp.arange(s)[None, :] < lengths[:, None]
    part = attend_partial(q, k_cache, v_cache, valid, scale)
    return merge_partials([part]).astype(q.dtype)


def paged_decode_ref(q: jnp.ndarray, k_pages: jnp.ndarray,
                     v_pages: jnp.ndarray, block_table: jnp.ndarray,
                     lengths: jnp.ndarray,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """Paged decode attention.

    q:           (B, Hq, D)
    k/v_pages:   (n_pages, Hkv, page_size, D)  — head-major page pool
    block_table: (B, max_pages) int32          — page ids per sequence
    lengths:     (B,) int32                    — valid tokens per sequence
    """
    b, hq, d = q.shape
    _, hkv, page_size, _ = k_pages.shape
    max_pages = block_table.shape[1]

    def gather(pages):   # this batch's pages as contiguous (B, S, Hkv, D)
        return pages[block_table].swapaxes(2, 3).reshape(
            b, max_pages * page_size, hkv, d)
    return decode_attention_ref(q, gather(k_pages), gather(v_pages),
                                lengths, scale)
