"""Public fused-RMSNorm op with backend dispatch."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.rmsnorm.rmsnorm import rmsnorm_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def rmsnorm(x, w, residual: Optional[jnp.ndarray] = None, *,
            eps: float = 1e-5, use_pallas: Optional[bool] = None,
            interpret: bool = False) -> jnp.ndarray:
    use_pallas = _on_tpu() if use_pallas is None else use_pallas
    if use_pallas:
        return rmsnorm_pallas(x, w, residual, eps=eps, interpret=interpret)
    return rmsnorm_ref(x, w, residual, eps)


__all__ = ["rmsnorm", "rmsnorm_pallas", "rmsnorm_ref"]
