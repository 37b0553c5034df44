"""Public decode-attention ops: paged (engine path) + partial/merge helpers
(model dry-run path)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import (
    paged_decode_attention_pallas)
from repro.kernels.decode_attention.ref import (    # noqa: F401 (re-export)
    attend_partial, decode_attention_ref, merge_partials, paged_decode_ref)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def paged_decode_attention(q, k_pages, v_pages, block_table, lengths,
                           layer=0, *, scale: Optional[float] = None,
                           use_pallas: Optional[bool] = None,
                           interpret: bool = False) -> jnp.ndarray:
    """Attention of ``q`` over layer ``layer`` of the page pools (L,
    n_pages, Hkv, page, D), or over a one-layer pool (n_pages, Hkv, page,
    D): the Pallas kernel on a TPU (or in interpret mode), else the jnp
    reference."""
    use_pallas = _on_tpu() if use_pallas is None else use_pallas
    if use_pallas:
        return paged_decode_attention_pallas(
            q, k_pages, v_pages, block_table, lengths, layer, scale=scale,
            interpret=interpret)
    return paged_decode_ref(q, k_pages, v_pages, block_table, lengths,
                            layer, scale)


__all__ = ["paged_decode_attention", "paged_decode_attention_pallas",
           "paged_decode_ref", "decode_attention_ref", "attend_partial",
           "merge_partials"]
