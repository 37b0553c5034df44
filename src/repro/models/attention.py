"""Attention blocks (self / cross) for train, prefill and decode.

Written in purely logical terms; all distribution comes from the Policy's
sharding constraints. Decode uses the staged KV cache: a large read-only
sequence-sharded segment ("big") plus a small replicated append buffer
("recent"); the two partial flash states are merged explicitly
(flash-decoding). ``flush`` moves recent -> big outside the hot step.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import NO_POLICY, Policy
from repro.kernels.decode_attention import attend_partial, merge_partials
from repro.kernels.flash_attention import flash_attention
from repro.models.common import rope

RECENT_WINDOW = 256     # decode append-buffer length between flushes


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class AttnCache:
    """Staged decode cache for ONE attention site."""
    k_big: jnp.ndarray        # (B, S_max, Hkv, D) — sequence-sharded
    v_big: jnp.ndarray
    k_recent: jnp.ndarray     # (B, W, Hkv, D)     — replicated
    v_recent: jnp.ndarray
    big_len: jnp.ndarray      # () int32  — filled length of the big segment
    recent_len: jnp.ndarray   # () int32


def make_attn_cache(batch: int, s_max: int, n_kv: int, head_dim: int,
                    dtype=jnp.bfloat16, window: int = RECENT_WINDOW) -> AttnCache:
    z = lambda s: jnp.zeros(s, dtype)
    return AttnCache(
        k_big=z((batch, s_max, n_kv, head_dim)),
        v_big=z((batch, s_max, n_kv, head_dim)),
        k_recent=z((batch, window, n_kv, head_dim)),
        v_recent=z((batch, window, n_kv, head_dim)),
        big_len=jnp.zeros((), jnp.int32),
        recent_len=jnp.zeros((), jnp.int32),
    )


def _qkv(x, p, arch, policy: Policy, *, prefix: str = ""):
    """Project x: (B, S, D) -> q (B,S,Hq,hd), k, v (B,S,Hkv,hd)."""
    b, s, _ = x.shape
    hd = arch.resolved_head_dim
    q = x @ p[prefix + "wq"]
    k = x @ p[prefix + "wk"]
    v = x @ p[prefix + "wv"]
    if arch.qkv_bias:
        q = q + p[prefix + "bq"]
        k = k + p[prefix + "bk"]
        v = v + p[prefix + "bv"]
    q = q.reshape(b, s, arch.n_heads, hd)
    k = k.reshape(b, s, arch.n_kv_heads, hd)
    v = v.reshape(b, s, arch.n_kv_heads, hd)
    q = policy.constrain(q, ("batch", "seq_q", "heads", None))
    # K/V must NOT be sequence-sharded: the flash scan slices KV chunks, and
    # a dynamic-slice over a sharded dim makes GSPMD re-gather the full KV
    # every chunk (measured 28-62s collective terms in the baseline roofline).
    # Constraining them replicated-over-model (heads-sharded when divisible)
    # gathers once per layer instead.  [§Perf iteration 1]
    k = policy.constrain(k, ("batch", None, "kv_heads", None))
    v = policy.constrain(v, ("batch", None, "kv_heads", None))
    return q, k, v


def _apply_rope(arch, q, k, positions):
    if arch.pos_emb.value == "rope":
        q = rope(q, positions, arch.rope_theta)
        if k is not None:
            k = rope(k, positions, arch.rope_theta)
    return q, k


def self_attention_full(x, p, arch, policy: Policy = NO_POLICY, *,
                        positions: Optional[jnp.ndarray] = None,
                        kv_chunk: int = 256, use_pallas: bool = False,
                        interpret: bool = False, return_kv: bool = False):
    """Causal full-sequence self-attention (train / prefill)."""
    b, s, d = x.shape
    q, k, v = _qkv(x, p, arch, policy)
    if positions is None:
        positions = jnp.arange(s)
    q, k = _apply_rope(arch, q, k, positions)
    out = flash_attention(q, k, v, causal=True, kv_chunk=kv_chunk,
                          use_pallas=use_pallas, interpret=interpret)
    out = policy.constrain(out, ("batch", "seq_q", "heads", None))
    out = out.reshape(b, s, -1) @ p["wo"]
    if return_kv:
        # storage sharding: the serve cache is sequence-sharded
        k = policy.constrain(k, ("batch", "kv_seq", None, None))
        v = policy.constrain(v, ("batch", "kv_seq", None, None))
        return out, (k, v)
    return out


def cross_attention_full(x, kv_src, p, arch, policy: Policy = NO_POLICY, *,
                         use_pallas: bool = False, return_kv: bool = False):
    """Cross-attention to frontend tokens (B, T, D_model)."""
    b, s, d = x.shape
    hd = arch.resolved_head_dim
    q = (x @ p["wq"]).reshape(b, s, arch.n_heads, hd)
    k = (kv_src @ p["wk"]).reshape(b, kv_src.shape[1], arch.n_kv_heads, hd)
    v = (kv_src @ p["wv"]).reshape(b, kv_src.shape[1], arch.n_kv_heads, hd)
    q = policy.constrain(q, ("batch", "seq_q", "heads", None))
    k = policy.constrain(k, ("batch", "frontend_seq", "kv_heads", None))
    v = policy.constrain(v, ("batch", "frontend_seq", "kv_heads", None))
    out = flash_attention(q, k, v, causal=False, use_pallas=use_pallas)
    out = out.reshape(b, s, -1) @ p["wo"]
    if return_kv:
        return out, (k, v)
    return out


def self_attention_decode(x, cache: AttnCache, p, arch,
                          policy: Policy = NO_POLICY
                          ) -> Tuple[jnp.ndarray, AttnCache]:
    """One-token decode with the staged cache. x: (B, D) -> (B, D)."""
    b, d = x.shape
    pos = cache.big_len + cache.recent_len              # scalar position
    q, k, v = _qkv(x[:, None, :], p, arch, policy)
    q, k = _apply_rope(arch, q, k, pos[None])
    q = q[:, 0]                                         # (B, Hq, hd)
    k_new, v_new = k[:, 0], v[:, 0]                     # (B, Hkv, hd)

    # append to the (small, replicated) recent buffer — one-hot update keeps
    # the write local regardless of sharding
    w = cache.k_recent.shape[1]
    onehot = (jnp.arange(w) == cache.recent_len)[None, :, None, None]
    k_recent = jnp.where(onehot, k_new[:, None], cache.k_recent)
    v_recent = jnp.where(onehot, v_new[:, None], cache.v_recent)

    # two partial flash states: big (seq-sharded) + recent (replicated)
    s_max = cache.k_big.shape[1]
    valid_big = (jnp.arange(s_max) < cache.big_len)[None].repeat(b, 0)
    part_big = attend_partial(q, cache.k_big, cache.v_big, valid_big)
    valid_rec = (jnp.arange(w) <= cache.recent_len)[None].repeat(b, 0)
    part_rec = attend_partial(q, k_recent, v_recent, valid_rec)
    out = merge_partials([part_big, part_rec]).astype(x.dtype)

    out = policy.constrain(out, ("batch", "heads", None))
    out = out.reshape(b, -1) @ p["wo"]
    new_cache = dataclasses.replace(
        cache, k_recent=k_recent, v_recent=v_recent,
        recent_len=cache.recent_len + 1)
    return out, new_cache


def cross_attention_decode(x, cross_kv, p, arch, policy: Policy = NO_POLICY):
    """Decode-time cross-attention against the fixed prefill-computed KV."""
    b, d = x.shape
    hd = arch.resolved_head_dim
    q = (x @ p["wq"]).reshape(b, arch.n_heads, hd)
    k, v = cross_kv
    part = attend_partial(q, k, v, None)
    out = merge_partials([part]).astype(x.dtype)
    return out.reshape(b, -1) @ p["wo"]


def flush_cache(cache: AttnCache) -> AttnCache:
    """Move the recent buffer into the big segment (amortized, outside the
    hot decode step). Dynamic-update-slice on the sequence-sharded big cache;
    runs once every RECENT_WINDOW tokens. Supports stacked (L, B, S, H, D)
    caches — the sequence dim is always -3."""
    nd = cache.k_big.ndim
    zero = jnp.zeros((), jnp.int32)
    starts = [zero] * nd
    starts[-3] = cache.big_len
    k_big = jax.lax.dynamic_update_slice(
        cache.k_big, cache.k_recent.astype(cache.k_big.dtype), starts)
    v_big = jax.lax.dynamic_update_slice(
        cache.v_big, cache.v_recent.astype(cache.v_big.dtype), starts)
    return dataclasses.replace(
        cache, k_big=k_big, v_big=v_big,
        big_len=cache.big_len + cache.recent_len,
        recent_len=jnp.zeros((), jnp.int32),
        k_recent=jnp.zeros_like(cache.k_recent),
        v_recent=jnp.zeros_like(cache.v_recent))
