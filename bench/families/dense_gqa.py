"""Model family ``dense_gqa``: a dense decoder of grouped-query attention and
a SwiGLU MLP, as ``PagedEngine`` serves it.

- ``check``: the registered architecture, with the configuration's
  overrides, has the file's sizes.
- ``shapes``: the tree the served path takes (``embed``, ``final_ln``, an
  untied ``head``, and the layers stacked under ``seg0``). Each matrix is
  normal with standard deviation 1/sqrt(fan-in), the embedding 0.02, the
  norms ones. A configuration may set the embedding's standard deviation
  (``init.embedding_std``, default 0.02).
- ``logit_rows``: the plain float32 reference, written from the
  configuration file alone (it imports nothing of the program): token
  embedding (times ``embedding_multiplier``), then per layer RMSNorm,
  grouped-query attention with rotary embeddings (rotate-half, the first
  ``partial_rotary_factor`` of each head), a residual, RMSNorm and a SwiGLU
  MLP, then a final RMSNorm and the output head (the embedding, transposed,
  when tied). Every product runs at float32 ``HIGHEST`` precision on
  weights upcast from bfloat16, layer by layer under ``lax.scan``, and
  attention in blocks of queries, so a whole prompt fits on one chip once
  the program is gone. ``mode="int8"`` is the control's lower precision
  (``reference._mm``).
- ``warm_up`` and ``programs``: what the engine's own ``warmup`` leaves
  cold, and the engine's prefill and decode programs, lowered.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import _attention, _mm, _norm, _rope


def check(cfg: dict, arch) -> None:
    """Raises where the program's architecture differs from the file."""
    pairs = {"hidden_size": arch.d_model, "intermediate_size": arch.d_ff,
             "num_attention_heads": arch.n_heads,
             "num_key_value_heads": arch.n_kv_heads,
             "head_dim": arch.resolved_head_dim,
             "num_hidden_layers": arch.n_layers, "vocab_size": arch.vocab,
             "rope_theta": arch.rope_theta, "rms_norm_eps": arch.norm_eps,
             "tie_word_embeddings": arch.tie_embeddings}
    bad = {k: (cfg[k], v) for k, v in pairs.items() if cfg[k] != v}
    if bad:
        raise ValueError(f"program arch differs from the file: {bad}")


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


class Dims(NamedTuple):
    d: int
    layers: int
    hq: int
    hkv: int
    hd: int
    vocab: int
    eps: float
    theta: float
    rot: int            # rotary dims per head
    emb_mult: float
    tied: bool


def dims(cfg: dict) -> Dims:
    hd = cfg["head_dim"]
    rot = int(hd * cfg.get("partial_rotary_factor", 1.0))
    return Dims(cfg["hidden_size"], cfg["num_hidden_layers"],
                cfg["num_attention_heads"], cfg["num_key_value_heads"], hd,
                cfg["vocab_size"], float(cfg["rms_norm_eps"]),
                float(cfg["rope_theta"]), rot - rot % 2,
                float(cfg.get("embedding_multiplier", 1.0)),
                bool(cfg["tie_word_embeddings"]))


def logit_rows(params, tokens, rows, *, cfg: dict, mode: str, q_block: int):
    """Logits (len(rows), V) at positions ``rows`` of ``tokens`` (T,)."""
    return _logit_rows(params, tokens, rows, dm=dims(cfg), mode=mode,
                       q_block=q_block)


@functools.partial(jax.jit, static_argnames=("dm", "mode", "q_block"))
def _logit_rows(params, tokens, rows, *, dm: Dims, mode: str, q_block: int):
    t = tokens.shape[0]
    pos = jnp.arange(t)
    x = params["embed"][tokens].astype(jnp.float32) * dm.emb_mult

    def layer(x, p):
        h = _norm(x, p["ln1"], dm.eps)
        q = _mm(h, p["wq"], mode).reshape(t, dm.hq, dm.hd)
        k = _mm(h, p["wk"], mode).reshape(t, dm.hkv, dm.hd)
        v = _mm(h, p["wv"], mode).reshape(t, dm.hkv, dm.hd)
        q = _rope(q, pos, dm.theta, dm.rot)
        k = _rope(k, pos, dm.theta, dm.rot)
        x = x + _mm(_attention(q, k, v, q_block), p["wo"], mode)
        h = _norm(x, p["ln2"], dm.eps)
        g = _mm(h, p["wg"], mode)
        x = x + _mm(jax.nn.silu(g) * _mm(h, p["wu"], mode), p["wd"], mode)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["seg0"])
    x = _norm(x[rows], params["final_ln"], dm.eps)
    head = params["embed"].T if dm.tied else params["head"]
    return _mm(x, head, mode)


def warm_up(eng, lengths) -> None:
    """Compile (or load) every program one engine runs for prompts of
    ``lengths``: the prefill buckets and decode step (``eng.warmup``), the
    prompt uploads and cache writes of every prompt length, and the argmax
    of each step."""
    from repro.serving.engine import prompt_bucket
    eng.warmup(lengths)
    a = eng.arch
    slot = eng.slots.index(None)            # its block table is all 0:
    for s in lengths:                       # writes land on null page 0
        b = prompt_bucket(s)
        jnp.asarray([[0] * b])              # the prompt's upload
        ks = jnp.zeros((a.n_layers, b, a.n_kv_heads, a.resolved_head_dim),
                       jnp.float32, device=eng.device)
        eng._write_kv(slot, 0, ks[:, :s], ks[:, :s])
    for shape in ((a.vocab,), (eng.cfg.max_batch, a.vocab)):
        np.asarray(jnp.argmax(jnp.zeros(shape, jnp.float32,
                                        device=eng.device), -1))
    jax.block_until_ready(eng.kv_k)


def programs(eng, prompt_len: int) -> Dict[str, object]:
    """The engine's prefill program for the bucket of ``prompt_len`` and
    its decode step, lowered at the engine's shapes."""
    from repro.serving.engine import decode_step, prefill_step, prompt_bucket
    kw = dict(arch=eng.arch, use_pallas=eng.use_pallas,
              interpret=eng.cfg.interpret)
    s = prompt_bucket(prompt_len)
    pre = prefill_step.lower(eng.params, jnp.zeros((1, s), jnp.int32), s - 1,
                             **kw)
    b = eng.cfg.max_batch
    dec = decode_step.lower(
        eng.params, eng.kv_k, eng.kv_v, jnp.asarray(eng.block_tables),
        jnp.asarray(eng.lengths), jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), bool), page_size=eng.cfg.page_size, **kw)
    return {"prefill": pre, "decode": dec}
