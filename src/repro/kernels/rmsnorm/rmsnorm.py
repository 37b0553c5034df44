"""Pallas TPU fused RMSNorm (+ residual add).

The decode hot loop runs 2 norms per layer on (B, D) activations; fusing the
residual add + fp32 mean-square + scale into one VMEM pass saves two HBM
round-trips of the activation per call. Rows are tiled (block_rows, D) so a
row's full feature dim sits in VMEM (D <= ~16k fp32 fits easily)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _rmsnorm_res_kernel(x_ref, r_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows",
                                             "interpret"))
def rmsnorm_pallas(x, w, residual: Optional[jnp.ndarray] = None,
                   *, eps: float = 1e-5, block_rows: int = 128,
                   interpret: bool = False) -> jnp.ndarray:
    """x: (..., D); w: (D,). Rows flattened and tiled; a ragged last tile
    is zero-padded (a 1-row tile would break the TPU's 8-row tiling)."""
    shape = x.shape
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    block_rows = min(block_rows, rows)
    pad = -rows % block_rows

    def tiles(a):
        return jnp.pad(a.reshape(rows, d), ((0, pad), (0, 0)))
    x2 = tiles(x)
    grid = ((rows + pad) // block_rows,)
    w2 = w.reshape(1, d)
    if residual is None:
        out = pl.pallas_call(
            functools.partial(_rmsnorm_kernel, eps=eps),
            grid=grid,
            in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                      pl.BlockSpec((1, d), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((rows + pad, d), x.dtype),
            interpret=interpret,
            name="rmsnorm",
        )(x2, w2)
    else:
        out = pl.pallas_call(
            functools.partial(_rmsnorm_res_kernel, eps=eps),
            grid=grid,
            in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                      pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                      pl.BlockSpec((1, d), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((rows + pad, d), x.dtype),
            interpret=interpret,
            name="rmsnorm_residual",
        )(x2, tiles(residual), w2)
    return out[:rows].reshape(shape)
