"""Model family ``dense_gqa_qkv_bias``: ``dense_gqa`` with a bias on the
query, key and value projections, as ``PagedEngine`` serves an architecture
with ``qkv_bias`` set (Qwen2's attention). A family added with files only:
it takes the dense family by name for what it shares, and writes its own
leaves, check and float32 reference.

The biases are normal with standard deviation 1/sqrt(hidden_size), stacked
under ``seg0`` as ``bq``, ``bk`` and ``bv`` after the dense leaves, and are
added after the projection, before the rotary embedding.
"""
from __future__ import annotations

import functools
from pathlib import Path

import jax
import jax.numpy as jnp

from bench import harness
from bench.reference import _attention, _mm, _norm, _rope

_dense = harness.family("dense_gqa", Path(__file__).resolve().parents[2])
warm_up, programs = _dense.warm_up, _dense.programs


def check(cfg: dict, arch) -> None:
    _dense.check(cfg, arch)
    if cfg["qkv_bias"] is not arch.qkv_bias:
        raise ValueError(f"program arch differs from the file: qkv_bias "
                         f"{cfg['qkv_bias']} in the file, {arch.qkv_bias} "
                         "in the program")


def shapes(cfg: dict):
    d, hd, n_l = cfg["hidden_size"], cfg["head_dim"], cfg["num_hidden_layers"]
    qd, kvd = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return {**_dense.shapes(cfg),
            "seg0/bq": ((n_l, qd), d ** -0.5),
            "seg0/bk": ((n_l, kvd), d ** -0.5),
            "seg0/bv": ((n_l, kvd), d ** -0.5)}


def logit_rows(params, tokens, rows, *, cfg: dict, mode: str, q_block: int):
    """Logits (len(rows), V) at positions ``rows`` of ``tokens`` (T,)."""
    return _logit_rows(params, tokens, rows, dm=_dense.dims(cfg), mode=mode,
                       q_block=q_block)


@functools.partial(jax.jit, static_argnames=("dm", "mode", "q_block"))
def _logit_rows(params, tokens, rows, *, dm, mode: str, q_block: int):
    t = tokens.shape[0]
    pos = jnp.arange(t)
    x = params["embed"][tokens].astype(jnp.float32) * dm.emb_mult

    def proj(h, p, w, heads):
        y = _mm(h, p["w" + w], mode) + p["b" + w].astype(jnp.float32)
        return y.reshape(t, heads, dm.hd)

    def layer(x, p):
        h = _norm(x, p["ln1"], dm.eps)
        q = _rope(proj(h, p, "q", dm.hq), pos, dm.theta, dm.rot)
        k = _rope(proj(h, p, "k", dm.hkv), pos, dm.theta, dm.rot)
        v = proj(h, p, "v", dm.hkv)
        x = x + _mm(_attention(q, k, v, q_block), p["wo"], mode)
        h = _norm(x, p["ln2"], dm.eps)
        g = _mm(h, p["wg"], mode)
        x = x + _mm(jax.nn.silu(g) * _mm(h, p["wu"], mode), p["wd"], mode)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["seg0"])
    x = _norm(x[rows], params["final_ln"], dm.eps)
    head = params["embed"].T if dm.tied else params["head"]
    return _mm(x, head, mode)
