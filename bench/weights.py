"""Model weights from the seed, made on the device in one jitted call.

The leaves, their shapes and standard deviations come from the
configuration's model family (``bench/families/<name>.py``, ``shapes``),
in the order it gives them: leaf ``i`` takes the ``i``-th key split from
the seed. Each is normal with its standard deviation, or ones where that is
0 (a norm), in bfloat16, the type the served path takes. The reference
makes the same tree again from the same seed once the program is gone, so
it takes nothing from the program.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

_DTYPE = jnp.bfloat16


def seed_key(seed: int) -> jax.Array:
    """A key from any whole seed: 64 bits, so large seeds do not collide."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnums=0)
def _make(spec: Tuple[Tuple[str, Tuple[int, ...], float], ...], key):
    keys = jax.random.split(key, len(spec))
    out = {}
    for k, (name, shape, std) in zip(keys, spec):
        if std == 0.0:
            out[name] = jnp.ones(shape, _DTYPE)
        else:
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * std).astype(_DTYPE)
    return out


def make(leaves: Dict[str, Tuple[Tuple[int, ...], float]], seed: int
         ) -> dict:
    """The nested weight tree of ``leaves`` (name -> (shape, std), a
    family's ``shapes``) for ``seed``; a ``/`` in a name nests it."""
    spec = tuple((n, s, float(std)) for n, (s, std) in leaves.items())
    flat = _make(spec, seed_key(seed))
    tree: dict = {}
    for name, leaf in flat.items():
        *outer, last = name.split("/")
        node = tree
        for part in outer:
            node = node.setdefault(part, {})
        node[last] = leaf
    return tree
