"""jit'd public wrapper: picks the Pallas kernel on TPU, the memory-bounded
jnp reference elsewhere (CPU dry-run / tests use ref or interpret mode)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import (     # noqa: F401 (re-export)
    attention_dense_ref, flash_attention_ref)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, causal: bool = True, q_offset=0,
                    kv_len: Optional[jnp.ndarray] = None,
                    scale: Optional[float] = None,
                    kv_chunk: int = 256,
                    use_pallas: Optional[bool] = None,
                    interpret: bool = False) -> jnp.ndarray:
    """Causal (or cross) batched GQA attention. See ref.py for semantics."""
    use_pallas = _on_tpu() if use_pallas is None else use_pallas
    if use_pallas and kv_len is None and isinstance(q_offset, int):
        return flash_attention_pallas(q, k, v, scale=scale, causal=causal,
                                      q_offset=q_offset, interpret=interpret)
    return flash_attention_ref(q, k, v, causal=causal, q_offset=q_offset,
                               kv_len=kv_len, scale=scale, kv_chunk=kv_chunk)


__all__ = ["flash_attention", "flash_attention_pallas", "flash_attention_ref",
           "attention_dense_ref"]
