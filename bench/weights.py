"""Model weights from the seed, made on the device in one jitted call.

The tree is the one the served path takes (``embed``, ``final_ln``, an
untied ``head``, and the layers stacked under ``seg0``), in bfloat16, the
type they are served in. Each matrix is normal with standard deviation
1/sqrt(fan-in), the embedding 0.02, the norms ones. The reference makes the
same tree again from the same seed once the program is gone, so it takes
nothing from the program. A configuration may set the embedding's
standard deviation (``init.embedding_std``, default 0.02).
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

_DTYPE = jnp.bfloat16


def shapes(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Leaf name -> (shape, std); std 0 marks a norm (ones)."""
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    n_l, hd = cfg["num_hidden_layers"], cfg["head_dim"]
    qd, kvd = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    out = {
        "embed": ((v, d), cfg.get("init", {}).get("embedding_std", 0.02)),
        "final_ln": ((d,), 0.0),
        "seg0/ln1": ((n_l, d), 0.0),
        "seg0/ln2": ((n_l, d), 0.0),
        "seg0/wq": ((n_l, d, qd), d ** -0.5),
        "seg0/wk": ((n_l, d, kvd), d ** -0.5),
        "seg0/wv": ((n_l, d, kvd), d ** -0.5),
        "seg0/wo": ((n_l, qd, d), qd ** -0.5),
        "seg0/wg": ((n_l, d, ff), d ** -0.5),
        "seg0/wu": ((n_l, d, ff), d ** -0.5),
        "seg0/wd": ((n_l, ff, d), ff ** -0.5),
    }
    if not cfg["tie_word_embeddings"]:
        out["head"] = ((d, v), d ** -0.5)
    return out


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


def make(cfg: dict, seed: int) -> dict:
    """The nested weight tree of configuration ``cfg`` for ``seed``."""
    spec = tuple((n, s, float(std)) for n, (s, std) in shapes(cfg).items())
    flat = _make(spec, seed_key(seed))
    tree: dict = {}
    for name, leaf in flat.items():
        *outer, last = name.split("/")
        node = tree
        for part in outer:
            node = node.setdefault(part, {})
        node[last] = leaf
    return tree
