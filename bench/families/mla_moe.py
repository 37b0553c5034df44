"""Model family ``mla_moe``: DeepSeek-V3-style latent attention (MLA) with
sparse experts, as ``PagedEngine`` serves it: leading dense layers, then
expert layers with a sigmoid router over every routed expert, of which
this chip holds a share, and shared experts.

- ``check``: the registered architecture, with the configuration's
  overrides, has the file's sizes, and the chip holds the experts the file
  says (``program.held_experts``; the run's engine holds them from expert
  0, so ``first`` has to be 0).
- ``shapes``: the tree the served path takes: ``embed``, ``final_ln``, an
  untied ``head``, the dense layers under ``seg0`` and the expert layers
  under ``seg1``, each stacked. ``n_routed_experts`` is how many experts
  the chip holds (their weights' leading size); the router has the
  published count's outputs (``published.n_routed_experts``). Each matrix
  is normal with standard deviation 1/sqrt(fan-in), the embedding
  ``init.embedding_std`` (default 0.02), the router's correction bias
  ``init.router_bias_std``, the norms ones.
- ``logit_rows``: the plain float32 reference in the expanded form,
  written from the configuration file alone (it imports nothing of the
  program). Per layer: RMSNorm; q = h W_q, per head ``qk_nope_head_dim``
  then ``qk_rope_head_dim``; h W_kv_a gives the latent c, RMS-normed, and
  one rotary key shared by the heads; c W_kv_b gives each head's k_nope and
  v; rotary on the rope dims rotates interleaved pairs (2j, 2j+1) at
  theta^(-2j/d); causal attention in blocks of queries at scale
  1/sqrt(nope + rope); a residual; RMSNorm; then the dense SwiGLU, or the
  expert layer: sigmoid scores of every routed expert, the top-k of score
  + correction bias, weighted by the unbiased scores normalised over the k
  and times ``routed_scaling_factor``; every held expert computed on every
  token and masked by that choice; plus the shared experts, one SwiGLU of
  ``n_shared_experts`` x ``moe_intermediate_size``. A final RMSNorm and
  the head. Every product at float32 ``HIGHEST`` on weights upcast from
  bfloat16; ``mode="int8"`` is the control's lower precision
  (``reference._mm``), the router's product included.
- ``warm_up`` and ``programs``: the engine's own ``warmup`` and
  ``programs``.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from bench.reference import HIGHEST, _mm, _norm


def check(cfg: dict, arch) -> None:
    """Raises where the program's architecture differs from the file."""
    moe, mla = arch.moe, arch.mla
    held = cfg["program"]["held_experts"]
    pairs = {"hidden_size": arch.d_model, "num_attention_heads": arch.n_heads,
             "num_hidden_layers": arch.n_layers, "vocab_size": arch.vocab,
             "rms_norm_eps": arch.norm_eps, "rope_theta": arch.rope_theta,
             "tie_word_embeddings": arch.tie_embeddings,
             "kv_lora_rank": mla.kv_lora_rank,
             "q_lora_rank": mla.q_lora_rank,
             "qk_nope_head_dim": mla.qk_nope_head_dim,
             "qk_rope_head_dim": mla.qk_rope_head_dim,
             "v_head_dim": mla.v_head_dim,
             "intermediate_size": moe.d_dense,
             "moe_intermediate_size": moe.d_expert,
             "n_shared_experts": moe.n_shared_experts,
             "num_experts_per_tok": moe.top_k,
             "first_k_dense_replace": moe.n_dense_layers,
             "routed_scaling_factor": moe.routed_scaling_factor,
             "norm_topk_prob": moe.norm_topk_prob,
             "scoring_func": moe.router,
             "n_routed_experts": held["count"]}
    bad = {k: (cfg[k], v) for k, v in pairs.items() if cfg[k] != v}
    if cfg["published"]["n_routed_experts"] != moe.n_experts:
        bad["published.n_routed_experts"] = (
            cfg["published"]["n_routed_experts"], moe.n_experts)
    if (cfg["topk_method"] == "noaux_tc") != moe.router_bias:
        bad["topk_method"] = (cfg["topk_method"], moe.router_bias)
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        bad["n_group"] = (cfg["n_group"], cfg["topk_group"])
    if moe.d_shared != moe.n_shared_experts * moe.d_expert:
        bad["d_shared"] = moe.d_shared
    if held["first"] != 0:
        bad["held_experts.first"] = (held["first"], 0)
    if bad:
        raise ValueError(f"program arch differs from the file: {bad}")


class Dims(NamedTuple):
    d: int
    heads: int
    latent: int         # kv_lora_rank
    nope: int
    rope: int
    v: int
    dense_ff: int
    expert_ff: int
    shared_ff: int
    router: int         # routed experts of the whole layer
    held: int           # of them, this chip's
    top_k: int
    dense_layers: int
    expert_layers: int
    vocab: int
    eps: float
    theta: float
    scale: float        # routed_scaling_factor
    norm_topk: bool


def dims(cfg: dict) -> Dims:
    dense = cfg["first_k_dense_replace"]
    return Dims(cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                cfg["intermediate_size"], cfg["moe_intermediate_size"],
                cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
                cfg["published"]["n_routed_experts"],
                cfg["n_routed_experts"], cfg["num_experts_per_tok"], dense,
                cfg["num_hidden_layers"] - dense, cfg["vocab_size"],
                float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
                float(cfg["routed_scaling_factor"]),
                bool(cfg["norm_topk_prob"]))


def shapes(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Leaf name -> (shape, std); std 0 marks a norm (ones)."""
    m = dims(cfg)
    init = cfg.get("init", {})

    def attention(n: int) -> dict:
        qk, kv = m.heads * (m.nope + m.rope), m.heads * (m.nope + m.v)
        return {"ln1": ((n, m.d), 0.0), "ln2": ((n, m.d), 0.0),
                "wq": ((n, m.d, qk), m.d ** -0.5),
                "w_kv_a": ((n, m.d, m.latent + m.rope), m.d ** -0.5),
                "kv_ln": ((n, m.latent), 0.0),
                "w_kv_b": ((n, m.latent, kv), m.latent ** -0.5),
                "wo": ((n, m.heads * m.v, m.d), (m.heads * m.v) ** -0.5)}

    def swiglu(lead: tuple, ff: int, gate: str, up: str, down: str) -> dict:
        return {gate: (lead + (m.d, ff), m.d ** -0.5),
                up: (lead + (m.d, ff), m.d ** -0.5),
                down: (lead + (ff, m.d), ff ** -0.5)}

    n_d, n_e = m.dense_layers, m.expert_layers
    seg0 = {**attention(n_d), **swiglu((n_d,), m.dense_ff, "wg", "wu", "wd")}
    seg1 = {**attention(n_e),
            "router": ((n_e, m.d, m.router), m.d ** -0.5),
            "router_bias": ((n_e, m.router), init["router_bias_std"]),
            **swiglu((n_e, m.held), m.expert_ff, "w_gate", "w_up", "w_down"),
            **swiglu((n_e,), m.shared_ff, "sh_gate", "sh_up", "sh_down")}
    out = {"embed": ((m.vocab, m.d), init.get("embedding_std", 0.02)),
           "final_ln": ((m.d,), 0.0),
           "head": ((m.d, m.vocab), m.d ** -0.5)}
    out.update({f"seg0/{k}": v for k, v in seg0.items()})
    out.update({f"seg1/{k}": v for k, v in seg1.items()})
    return out


def logit_rows(params, tokens, rows, *, cfg: dict, mode: str, q_block: int):
    """Logits (len(rows), V) at positions ``rows`` of ``tokens`` (T,)."""
    return _logit_rows(params, tokens, rows, dm=dims(cfg), mode=mode,
                       q_block=q_block)


def _rope(x, pos, theta):
    """x: (T, H, d): rotate each pair (x[2j], x[2j+1]) by pos theta^(-2j/d)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v, q_block):
    """Causal attention of q, k (T, H, dk) and v (T, H, dv), scale
    1/sqrt(dk), in blocks of ``q_block`` queries."""
    t, h, dk = q.shape
    kpos = jnp.arange(t)

    def block(args):
        i, qb = args                                       # (Bq, H, dk)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * dk ** -0.5
        qpos = i * q_block + jnp.arange(q_block)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khv->qhv", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(t // q_block),
                              q.reshape(t // q_block, q_block, h, dk)))
    return out.reshape(t, h * v.shape[-1])


def _swiglu(h, wg, wu, wd, mode):
    return _mm(jax.nn.silu(_mm(h, wg, mode)) * _mm(h, wu, mode), wd, mode)


@functools.partial(jax.jit, static_argnames=("dm", "mode", "q_block"))
def _logit_rows(params, tokens, rows, *, dm: Dims, mode: str, q_block: int):
    t = tokens.shape[0]
    pos = jnp.arange(t)
    x = params["embed"][tokens].astype(jnp.float32)

    def attention(x, p):
        h = _norm(x, p["ln1"], dm.eps)
        q = _mm(h, p["wq"], mode).reshape(t, dm.heads, dm.nope + dm.rope)
        q = jnp.concatenate([q[..., :dm.nope],
                             _rope(q[..., dm.nope:], pos, dm.theta)], -1)
        kv_a = _mm(h, p["w_kv_a"], mode)
        c = _norm(kv_a[:, :dm.latent], p["kv_ln"], dm.eps)
        k_pe = _rope(kv_a[:, None, dm.latent:], pos, dm.theta)
        kv = _mm(c, p["w_kv_b"], mode).reshape(t, dm.heads, dm.nope + dm.v)
        k = jnp.concatenate(
            [kv[..., :dm.nope],
             jnp.broadcast_to(k_pe, (t, dm.heads, dm.rope))], -1)
        out = _attention(q, k, kv[..., dm.nope:], q_block)
        return x + _mm(out, p["wo"], mode)

    def dense(x, p):
        x = attention(x, p)
        h = _norm(x, p["ln2"], dm.eps)
        return x + _swiglu(h, p["wg"], p["wu"], p["wd"], mode), None

    def experts(x, p):
        x = attention(x, p)
        h = _norm(x, p["ln2"], dm.eps)
        score = jax.nn.sigmoid(_mm(h, p["router"], mode))       # (T, E)
        _, top = jax.lax.top_k(
            score + p["router_bias"].astype(jnp.float32), dm.top_k)
        w = jnp.take_along_axis(score, top, axis=-1)
        if dm.norm_topk:
            w = w / w.sum(-1, keepdims=True)
        w = w * dm.scale
        # this chip holds experts 0 .. held - 1: each one on every token,
        # weighted where the router chose it
        chosen = top[:, :, None] == jnp.arange(dm.held)[None, None, :]
        gate = jnp.sum(jnp.where(chosen, w[:, :, None], 0.0), axis=1)
        y = jax.vmap(lambda wg, wu, wd: _swiglu(h, wg, wu, wd, mode))(
            p["w_gate"], p["w_up"], p["w_down"])             # (held, T, D)
        out = jnp.einsum("etd,te->td", y, gate, precision=HIGHEST)
        out = out + _swiglu(h, p["sh_gate"], p["sh_up"], p["sh_down"], mode)
        return x + out, None

    x, _ = jax.lax.scan(dense, x, params["seg0"])
    x, _ = jax.lax.scan(experts, x, params["seg1"])
    x = _norm(x[rows], params["final_ln"], dm.eps)
    return _mm(x, params["head"], mode)


def warm_up(eng, lengths) -> None:
    """Compile (or load) every program one engine runs for prompts of
    ``lengths`` (``PagedEngine.warmup``)."""
    eng.warmup(lengths)


def programs(eng, prompt_len: int) -> Dict[str, object]:
    """The engine's prefill program for the bucket of ``prompt_len`` and
    its decode step, lowered (``PagedEngine.programs``)."""
    return eng.programs(prompt_len)
