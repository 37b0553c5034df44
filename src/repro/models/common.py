"""Shared model building blocks (pure functions over param pytrees)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5, *,
             use_pallas: bool = False, interpret: bool = False) -> jnp.ndarray:
    """RMSNorm; ``use_pallas`` runs the fused Pallas kernel (kernels/rmsnorm),
    otherwise the pure-jnp form below (identical math). The caller's kernel
    choice (``ExecConfig`` or the engine's) decides, never the backend."""
    if use_pallas:
        from repro.kernels.rmsnorm import rmsnorm_pallas
        return rmsnorm_pallas(x, w, eps=eps, interpret=interpret)
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(dtype)


def rope(x: jnp.ndarray, positions: jnp.ndarray,
         theta: float = 10000.0) -> jnp.ndarray:
    """Rotary embedding. x: (..., S, H, D); positions: (S,) or (B, S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., S, half)
    if angles.ndim == 2:          # (S, half) -> broadcast over batch
        angles = angles[None]
    angles = angles[..., :, None, :]                            # (B, S, 1, half)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(positions: jnp.ndarray, d_model: int) -> jnp.ndarray:
    """(S,) or (B,S) -> (..., S, d_model) sinusoidal embedding."""
    half = d_model // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], axis=-1)


def gated_mlp(x: jnp.ndarray, wi_gate: jnp.ndarray, wi_up: jnp.ndarray,
              wo: jnp.ndarray, act: str = "silu") -> jnp.ndarray:
    actf = jax.nn.silu if act == "silu" else jax.nn.gelu
    h = actf(x @ wi_gate) * (x @ wi_up)
    return h @ wo


def init_dense(key, shape, scale: Optional[float] = None,
               dtype=jnp.bfloat16) -> jnp.ndarray:
    scale = scale if scale is not None else (1.0 / math.sqrt(shape[0]))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def split_keys(key, n):
    return list(jax.random.split(key, n))
