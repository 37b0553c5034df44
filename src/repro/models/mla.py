"""Multi-head latent attention (MLA, DeepSeek-V2/V3), as Moonlight has it.

Per token and layer the cache holds one latent row, ``[RMSNorm(c),
rope(k_pe)]``: ``h W_kv_a`` gives the latent ``c`` (``kv_lora_rank`` wide)
and one rotary key ``k_pe`` (``qk_rope_head_dim``) shared by every head.
Queries are ``h W_q`` (``q_lora_rank`` null), per head ``qk_nope_head_dim``
then ``qk_rope_head_dim`` columns. ``c W_kv_b`` gives each head's
``k_nope`` and ``v``. The softmax scale is ``1/sqrt(qk_head_dim)``.

Two forms of the same attention:

- expanded (prefill, training): k = ``[k_nope, k_pe]`` with ``k_pe``
  broadcast over heads, v from ``W_kv_b``; q/k head dim 192, v 128,
  through the flash kernel;
- absorbed (decode): ``q_lat = q_nope W_UK^T`` per head, so ``[q_lat,
  q_pe]`` scores against the latent row itself; the output over the
  rows' ``c`` columns goes through ``W_UV`` per head, then ``W_o``.
  Nothing is expanded per token of the context.

Rotary embedding on the ``qk_rope_head_dim`` part rotates interleaved
pairs ``(2j, 2j+1)`` at frequency ``theta^(-2j/d)``: DeepSeek-V3's own code
de-interleaves q and k alike before a rotate-half, which gives the same
scores.

Leaves of one layer: ``wq`` (D, H*192), ``w_kv_a`` (D, 576), ``kv_ln``
(512,), ``w_kv_b`` (512, H*256: per head 128 k_nope then 128 v), ``wo``
(H*128, D).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Tuple

import jax.numpy as jnp

from repro.kernels.decode_attention import attend_partial, merge_partials
from repro.kernels.flash_attention import flash_attention


def rope_pairs(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: (..., S, H, d); positions: (S,) or (B, S). Rotates each pair
    ``(x[2j], x[2j+1])`` by ``position * theta^(-2j/d)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[..., None].astype(jnp.float32) * inv     # (.., S, d/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    xp = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    x0, x1 = xp[..., 0], xp[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def project(h, p, arch, positions, norm: Callable
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """h: (B, S, D) -> (q_nope (B, S, H, nope), q_pe (B, S, H, rope), the
    latent row (B, S, kv_lora_rank + rope)); rotary at ``positions``."""
    m = arch.mla
    b, s, _ = h.shape
    q = (h @ p["wq"]).reshape(b, s, arch.n_heads, m.qk_head_dim)
    q_nope = q[..., :m.qk_nope_head_dim]
    q_pe = rope_pairs(q[..., m.qk_nope_head_dim:], positions,
                      arch.rope_theta)
    kv = h @ p["w_kv_a"]
    c = norm(kv[..., :m.kv_lora_rank], p["kv_ln"])
    k_pe = rope_pairs(kv[..., None, m.kv_lora_rank:], positions,
                      arch.rope_theta)[..., 0, :]
    return q_nope, q_pe, jnp.concatenate([c, k_pe.astype(c.dtype)], -1)


def _kv_b(p, arch) -> jnp.ndarray:
    """``W_kv_b`` as (kv_lora_rank, H, nope + v)."""
    m = arch.mla
    return p["w_kv_b"].reshape(m.kv_lora_rank, arch.n_heads,
                               m.qk_nope_head_dim + m.v_head_dim)


def scale(arch) -> float:
    return arch.mla.qk_head_dim ** -0.5


def attention_full(h, p, arch, positions, *, norm: Callable,
                   use_pallas: bool = False, interpret: bool = False,
                   kv_chunk: int = 256):
    """Expanded causal attention over a whole sequence. h: (B, S, D) ->
    (output (B, S, D), latent rows (B, S, kv_lora_rank + rope))."""
    m = arch.mla
    b, s, _ = h.shape
    q_nope, q_pe, row = project(h, p, arch, positions, norm)
    kv = row[..., :m.kv_lora_rank] @ p["w_kv_b"]
    kv = kv.reshape(b, s, arch.n_heads, m.qk_nope_head_dim + m.v_head_dim)
    k_pe = jnp.broadcast_to(row[..., None, m.kv_lora_rank:],
                            (b, s, arch.n_heads, m.qk_rope_head_dim))
    k = jnp.concatenate([kv[..., :m.qk_nope_head_dim],
                         k_pe.astype(kv.dtype)], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    out = flash_attention(q, k.astype(q.dtype),
                          kv[..., m.qk_nope_head_dim:].astype(q.dtype),
                          causal=True, scale=scale(arch), kv_chunk=kv_chunk,
                          use_pallas=use_pallas, interpret=interpret)
    return out.reshape(b, s, -1) @ p["wo"], row


def absorb(q_nope, q_pe, p, arch) -> jnp.ndarray:
    """(..., H, nope), (..., H, rope) -> (..., H, kv_lora_rank + rope):
    queries in the latent row's space."""
    w_uk = _kv_b(p, arch)[..., :arch.mla.qk_nope_head_dim]
    q_lat = jnp.einsum("...hn,chn->...hc", q_nope, w_uk)
    return jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)], -1)


def absorbed_output(o_lat, p, arch) -> jnp.ndarray:
    """(..., H, kv_lora_rank) attention over the rows' ``c`` -> (..., D)."""
    w_uv = _kv_b(p, arch)[..., arch.mla.qk_nope_head_dim:]
    o = jnp.einsum("...hc,chv->...hv", o_lat, w_uv)
    return o.reshape(o.shape[:-2] + (-1,)) @ p["wo"]


def self_attention_decode(x, cache, p, arch, norm: Callable):
    """One token with ``LM``'s staged cache, absorbed. The cache's K and V
    both hold the latent rows (one KV head, ``kv_lora_rank + rope`` wide).
    x: (B, D) -> ((B, D), new cache)."""
    m = arch.mla
    pos = cache.big_len + cache.recent_len
    q_nope, q_pe, row = project(x[:, None], p, arch, pos[None], norm)
    q = absorb(q_nope[:, 0], q_pe[:, 0], p, arch)           # (B, H, C)
    w = cache.k_recent.shape[1]
    onehot = (jnp.arange(w) == cache.recent_len)[None, :, None, None]
    new = row[:, :, None, :].astype(cache.k_recent.dtype)    # (B, 1, 1, C)
    k_recent = jnp.where(onehot, new, cache.k_recent)
    b, s_max = x.shape[0], cache.k_big.shape[1]
    valid_big = (jnp.arange(s_max) < cache.big_len)[None].repeat(b, 0)
    valid_rec = (jnp.arange(w) <= cache.recent_len)[None].repeat(b, 0)
    q = q.astype(cache.k_big.dtype)
    o = merge_partials([
        attend_partial(q, cache.k_big, cache.v_big, valid_big, scale(arch)),
        attend_partial(q, k_recent, k_recent, valid_rec, scale(arch))])
    out = absorbed_output(o[..., :m.kv_lora_rank].astype(x.dtype), p, arch)
    return out, dataclasses.replace(cache, k_recent=k_recent,
                                    v_recent=k_recent,
                                    recent_len=cache.recent_len + 1)
