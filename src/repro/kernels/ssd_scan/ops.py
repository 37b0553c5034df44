"""Public SSD ops: backend dispatch + single-token recurrent step."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.ref import ssd_chunked_ref, ssd_ref, _expand_groups
from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def ssd_scan(x, dt, A, Bm, Cm, D, init_state=None, *, chunk: int = 64,
             use_pallas: Optional[bool] = None,
             interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence SSD (prefill/train). See ref.py for shapes."""
    use_pallas = _on_tpu() if use_pallas is None else use_pallas
    chunk = min(chunk, x.shape[1])
    if x.shape[1] % chunk:
        return ssd_ref(x, dt, A, Bm, Cm, D, init_state)
    if use_pallas:
        return ssd_scan_pallas(x, dt, A, Bm, Cm, D, init_state, chunk=chunk,
                               interpret=interpret)
    return ssd_chunked_ref(x, dt, A, Bm, Cm, D, init_state, chunk=chunk)


def ssd_decode_step(state, x, dt, A, Bm, Cm, D
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One-token recurrence. state: (B,H,P,N); x: (B,H,P); dt: (B,H);
    Bm/Cm: (B,G,N). Returns (y: (B,H,P), new_state)."""
    h = x.shape[1]
    Bh = _expand_groups(Bm[:, None], h)[:, 0]          # (B,H,N)
    Ch = _expand_groups(Cm[:, None], h)[:, 0]
    dA = jnp.exp(dt * A)                               # (B,H)
    dBx = (dt[..., None, None] * x[..., None]) * Bh[:, :, None, :]
    new_state = state * dA[..., None, None] + dBx
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch) + x * D[None, :, None]
    return y.astype(x.dtype), new_state


__all__ = ["ssd_scan", "ssd_scan_pallas", "ssd_ref", "ssd_chunked_ref",
           "ssd_decode_step"]
