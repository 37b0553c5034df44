"""Production mesh construction.

Defined as functions (not module-level constants) so importing never touches
jax device state. The dry-run launcher forces 512 host platform devices
*before* any jax import; everything else sees the real device count."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — run "
            "via launch.dryrun (it forces XLA host device count) or on a pod")
    import numpy as np
    dev_array = np.asarray(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(dev_array, axes)

