"""Unified LM builder: one class covering all 10 assigned architectures.

A model is a sequence of *segments*; each segment is a stack of identical
layers run under ``lax.scan`` (keeps HLO small for 100-layer configs), with
heterogeneous patterns expressed as superblocks:

  dense/audio:  [dense x L]
  moe:          [dense x n_dense, moe x (L - n_dense)]
  ssm:          [mamba x L]
  hybrid:       [hyb_super x n_super (inner mamba + one SHARED attn block),
                 mamba x trailing]
  vlm:          [vlm_super x n_super (inner dense + one cross-attn layer)]

Three entry points (all pure functions over the param pytree):
  train_loss   — full causal pass + chunked softmax-xent (vocab TP)
  prefill      — full pass, returns last-position logits + staged KV caches
  decode_step  — one token through all layers (staged cache, flash-decoding)

Distribution is injected via a ``Policy`` (logical-axis constraints); params
carry logical axes in the template so the dry-run can derive in_shardings
without materializing anything.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, Family, PosEmb
from repro.distributed.sharding import NO_POLICY, Policy
from repro.models.attention import (AttnCache, cross_attention_decode,
                                    cross_attention_full, flush_cache,
                                    self_attention_decode,
                                    self_attention_full)
from repro.models.common import gated_mlp, rms_norm, sinusoidal_pos
from repro.models import mla
from repro.models.mamba2 import (MambaCache, make_mamba_cache,
                                 mamba_block_decode, mamba_block_full)
from repro.models.moe import moe_ffn, padded_experts, shared_expert_ffn


@dataclasses.dataclass(frozen=True)
class ExecConfig:
    use_pallas: bool = False
    interpret: bool = False        # Pallas interpret mode (CPU tests only)
    kv_chunk: int = 256
    scan_layers: bool = True
    remat: bool = False
    loss_chunk: int = 512          # seq chunk for the vocab-TP xent
    recent_window: int = 256       # decode append-buffer length
    capacity_factor: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class SegmentSpec:
    kind: str                      # dense | moe | mamba | hyb_super | vlm_super
    n: int                         # scan length
    inner: int = 1                 # inner plain layers per superblock


# =============================================================================
# parameter templates: leaf = (shape, logical_axes, scale)
# =============================================================================
Leaf = Tuple[Tuple[int, ...], Tuple[Optional[str], ...], float]


def _mla_leaves(arch: ArchConfig) -> Dict[str, Leaf]:
    """Latent attention (``models/mla.py``): q, the latent and rotary key,
    the latent's norm, its expansion to per-head k_nope and v, output."""
    d, h, m = arch.d_model, arch.n_heads, arch.mla
    c = m.kv_lora_rank
    return {
        "wq": ((d, h * m.qk_head_dim), ("p_fsdp", "p_tp"), d ** -0.5),
        "w_kv_a": ((d, m.latent_dim), ("p_fsdp", None), d ** -0.5),
        "kv_ln": ((c,), (None,), -1.0),
        "w_kv_b": ((c, h * (m.qk_nope_head_dim + m.v_head_dim)),
                   (None, "p_tp"), c ** -0.5),
        "wo": ((h * m.v_head_dim, d), ("p_tp", "p_fsdp"),
               (h * m.v_head_dim) ** -0.5),
    }


def _attn_leaves(arch: ArchConfig, prefix: str = "") -> Dict[str, Leaf]:
    if arch.mla is not None:
        return _mla_leaves(arch)
    d = arch.d_model
    hd = arch.resolved_head_dim
    qd, kvd = arch.n_heads * hd, arch.n_kv_heads * hd
    s = 1.0 / math.sqrt(d)
    leaves = {
        prefix + "wq": ((d, qd), ("p_fsdp", "p_tp"), s),
        prefix + "wk": ((d, kvd), ("p_fsdp", "p_tp"), s),
        prefix + "wv": ((d, kvd), ("p_fsdp", "p_tp"), s),
        prefix + "wo": ((qd, d), ("p_tp", "p_fsdp"), 1.0 / math.sqrt(qd)),
    }
    if arch.qkv_bias:
        leaves.update({
            prefix + "bq": ((qd,), ("p_tp",), 0.0),
            prefix + "bk": ((kvd,), ("p_tp",), 0.0),
            prefix + "bv": ((kvd,), ("p_tp",), 0.0),
        })
    return leaves


def _mlp_leaves(arch: ArchConfig, d_ff: int) -> Dict[str, Leaf]:
    d = arch.d_model
    return {
        "wg": ((d, d_ff), ("p_fsdp", "p_tp"), 1.0 / math.sqrt(d)),
        "wu": ((d, d_ff), ("p_fsdp", "p_tp"), 1.0 / math.sqrt(d)),
        "wd": ((d_ff, d), ("p_tp", "p_fsdp"), 1.0 / math.sqrt(d_ff)),
    }


def _dense_layer_leaves(arch: ArchConfig) -> Dict[str, Leaf]:
    d = arch.d_model
    out = {"ln1": ((d,), (None,), -1.0), "ln2": ((d,), (None,), -1.0)}
    out.update(_attn_leaves(arch))
    out.update(_mlp_leaves(arch, arch.d_ff))
    return out


def _moe_layer_leaves(arch: ArchConfig, ep: int) -> Dict[str, Leaf]:
    d = arch.d_model
    m = arch.moe
    e_pad = padded_experts(m.n_experts, ep)
    out = {"ln1": ((d,), (None,), -1.0), "ln2": ((d,), (None,), -1.0)}
    out.update(_attn_leaves(arch))
    s = 1.0 / math.sqrt(d)
    out.update({
        "router": ((d, m.n_experts), (None, None), s),
        **({"router_bias": ((m.n_experts,), (None,), 0.0)}
           if m.router_bias else {}),
        "w_gate": ((e_pad, d, m.d_expert), ("experts", "p_fsdp", None), s),
        "w_up": ((e_pad, d, m.d_expert), ("experts", "p_fsdp", None), s),
        "w_down": ((e_pad, m.d_expert, d), ("experts", None, "p_fsdp"),
                   1.0 / math.sqrt(m.d_expert)),
    })
    if m.n_shared_experts:
        d_sh = m.d_shared or m.d_expert * m.n_shared_experts
        out.update({
            "sh_gate": ((d, d_sh), ("p_fsdp", "p_tp"), s),
            "sh_up": ((d, d_sh), ("p_fsdp", "p_tp"), s),
            "sh_down": ((d_sh, d), ("p_tp", "p_fsdp"), 1.0 / math.sqrt(d_sh)),
        })
    return out


def _mamba_layer_leaves(arch: ArchConfig) -> Dict[str, Leaf]:
    d = arch.d_model
    s_cfg = arch.ssm
    di = arch.d_inner
    nh = arch.n_ssm_heads
    gn = s_cfg.ngroups * s_cfg.d_state
    s = 1.0 / math.sqrt(d)
    return {
        "ln": ((d,), (None,), -1.0),
        "w_z": ((d, di), ("p_fsdp", "p_tp"), s),
        "w_x": ((d, di), ("p_fsdp", "p_tp"), s),
        "w_bc": ((d, 2 * gn), ("p_fsdp", None), s),
        "w_dt": ((d, nh), ("p_fsdp", "p_tp"), s),
        "dt_bias": ((nh,), ("p_tp",), 0.0),
        "conv_wx": ((s_cfg.d_conv, di), (None, "p_tp"), 0.5),
        "conv_bx": ((di,), ("p_tp",), 0.0),
        "conv_wbc": ((s_cfg.d_conv, 2 * gn), (None, None), 0.5),
        "conv_bbc": ((2 * gn,), (None,), 0.0),
        "A_log": ((nh,), ("p_tp",), -2.0),       # special init: log-uniform
        "D": ((nh,), ("p_tp",), -1.0),           # special init: ones
        "norm_w": ((di,), ("p_tp",), -1.0),
        "w_out": ((di, d), ("p_tp", "p_fsdp"), 1.0 / math.sqrt(di)),
    }


def _cross_layer_leaves(arch: ArchConfig) -> Dict[str, Leaf]:
    d = arch.d_model
    out = {"ln1": ((d,), (None,), -1.0), "ln2": ((d,), (None,), -1.0),
           "gate_attn": ((1,), (None,), 0.0), "gate_mlp": ((1,), (None,), 0.0)}
    out.update(_attn_leaves(arch))
    out.update(_mlp_leaves(arch, arch.d_ff))
    return out


@functools.partial(jax.jit, static_argnums=(2, 3))
def _normal_leaf(key, scale, shape, dtype):
    """One scaled-normal weight, generated straight into ``dtype``: fused
    under jit, so a (32, 4096, 11008) bf16 leaf never exists in f32.
    The no-op ``reduce_precision`` stops XLA from reassociating ``* scale``
    with the sampler's own ``* sqrt(2)``, which would round differently
    from the eager product and change the weights of a fixed seed."""
    n = jax.lax.reduce_precision(jax.random.normal(key, shape, jnp.float32),
                                 exponent_bits=8, mantissa_bits=23)
    return (n * scale).astype(dtype)


def _stack(leaves: Dict[str, Leaf], *ns: int) -> Dict[str, Leaf]:
    out = {}
    for k, (shape, axes, scale) in leaves.items():
        out[k] = (tuple(ns) + shape, ("p_layers",) * len(ns) + axes, scale)
    return out


# =============================================================================
# the model
# =============================================================================
class LM:
    def __init__(self, arch: ArchConfig, policy: Policy = NO_POLICY,
                 exec_cfg: ExecConfig = ExecConfig()):
        self.arch = arch
        self.policy = policy
        self.cfg = exec_cfg
        self.dtype = jnp.bfloat16 if arch.param_dtype == "bfloat16" \
            else jnp.float32
        self.segments = self._build_segments()

    # -- segment layout -------------------------------------------------------
    def _build_segments(self) -> List[SegmentSpec]:
        a = self.arch
        if a.family in (Family.DENSE, Family.AUDIO):
            return [SegmentSpec("dense", a.n_layers)]
        if a.family == Family.MOE:
            nd = a.moe.n_dense_layers
            segs = []
            if nd:
                segs.append(SegmentSpec("dense_mlp", nd))
            segs.append(SegmentSpec("moe", a.n_layers - nd))
            return segs
        if a.family == Family.SSM:
            return [SegmentSpec("mamba", a.n_layers)]
        if a.family == Family.HYBRID:
            per = a.attn_every
            n_super = a.n_layers // per
            trailing = a.n_layers - n_super * per
            segs = [SegmentSpec("hyb_super", n_super, inner=per - 1)]
            if trailing:
                segs.append(SegmentSpec("mamba", trailing))
            return segs
        if a.family == Family.VLM:
            per = a.cross_attn_every
            n_super = a.n_layers // per
            assert n_super * per == a.n_layers, "vlm layers % cross_every != 0"
            return [SegmentSpec("vlm_super", n_super, inner=per - 1)]
        raise ValueError(a.family)

    # -- parameter template ---------------------------------------------------
    def param_template(self) -> Dict[str, Any]:
        a = self.arch
        ep = self.policy.axis_size("experts")
        d = a.d_model
        t: Dict[str, Any] = {
            # std 0.02 (GPT-2 convention); tied archs re-scale inputs by
            # sqrt(d), giving unit-variance residual streams either way
            "embed": ((a.vocab, d), ("p_fsdp", None), 0.02),
            "final_ln": ((d,), (None,), -1.0),
        }
        if not a.tie_embeddings:
            t["head"] = ((d, a.vocab), ("p_fsdp", "vocab"), 1.0 / math.sqrt(d))
        for i, seg in enumerate(self.segments):
            key = f"seg{i}"
            if seg.kind in ("dense", "dense_mlp"):
                if a.family == Family.MOE:   # leading dense layers of a MoE
                    leaves = {"ln1": ((d,), (None,), -1.0),
                              "ln2": ((d,), (None,), -1.0)}
                    leaves.update(_attn_leaves(a))
                    dff = a.moe.d_dense or a.moe.d_shared \
                        or a.moe.d_expert * 8
                    leaves.update(_mlp_leaves(a, dff))
                else:
                    leaves = _dense_layer_leaves(a)
                t[key] = _stack(leaves, seg.n)
            elif seg.kind == "moe":
                t[key] = _stack(_moe_layer_leaves(a, ep), seg.n)
            elif seg.kind == "mamba":
                t[key] = _stack(_mamba_layer_leaves(a), seg.n)
            elif seg.kind == "hyb_super":
                t[key] = {
                    "mamba": _stack(_mamba_layer_leaves(a), seg.n, seg.inner),
                    "attn": {**{k: v for k, v in _dense_layer_leaves(a).items()}},
                }
            elif seg.kind == "vlm_super":
                t[key] = {
                    "dense": _stack(_dense_layer_leaves(a), seg.n, seg.inner),
                    "cross": _stack(_cross_layer_leaves(a), seg.n),
                }
        return t

    def param_specs(self):
        """PartitionSpec tree matching init()'s structure (shape-aware: axes
        that do not divide a dim are dropped, as jit in_shardings requires)."""
        pol = self.policy
        return jax.tree.map(lambda leaf: pol.spec_for_shape(leaf[1], leaf[0]),
                            self.param_template(),
                            is_leaf=lambda x: isinstance(x, tuple)
                            and len(x) == 3 and isinstance(x[0], tuple))

    def init(self, key) -> Dict[str, Any]:
        tmpl = self.param_template()
        leaves, treedef = jax.tree.flatten(
            tmpl, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3
            and isinstance(x[0], tuple))
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, axes, scale) in zip(keys, leaves):
            if scale == -1.0:       # norm weights / D -> ones
                out.append(jnp.ones(shape, self.dtype))
            elif scale == -2.0:     # A_log -> log U[1, 16]
                u = jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0)
                out.append(jnp.log(u).astype(jnp.float32))
            elif scale == 0.0:
                out.append(jnp.zeros(shape, self.dtype))
            else:
                out.append(_normal_leaf(k, jnp.float32(scale), shape,
                                        self.dtype))
        return jax.tree.unflatten(treedef, out)

    # =========================================================================
    # layer bodies
    # =========================================================================
    def _norm(self, x, w):
        c = self.cfg
        return rms_norm(x, w, self.arch.norm_eps, use_pallas=c.use_pallas,
                        interpret=c.interpret)

    def _self_attention_full(self, h, p, positions, return_cache):
        a, c = self.arch, self.cfg
        if a.mla is None:
            return self_attention_full(h, p, a, self.policy,
                                       positions=positions,
                                       kv_chunk=c.kv_chunk,
                                       use_pallas=c.use_pallas,
                                       interpret=c.interpret,
                                       return_kv=return_cache)
        out, row = mla.attention_full(h, p, a, positions, norm=self._norm,
                                      use_pallas=c.use_pallas,
                                      interpret=c.interpret,
                                      kv_chunk=c.kv_chunk)
        # the staged cache keeps the latent row as K and as V: one head
        return (out, (row[:, :, None], row[:, :, None])) if return_cache \
            else out

    def _self_attention_decode(self, h, p, cache: AttnCache):
        if self.arch.mla is None:
            return self_attention_decode(h, cache, p, self.arch, self.policy)
        return mla.self_attention_decode(h, cache, p, self.arch, self._norm)

    def _kv_shape(self) -> Tuple[int, int]:
        """(KV heads, width) of one cached token: the latent row for MLA."""
        a = self.arch
        if a.mla is not None:
            return 1, a.mla.latent_dim
        return a.n_kv_heads, a.resolved_head_dim

    def _dense_layer_full(self, x, p, positions, return_cache):
        a, pol = self.arch, self.policy
        h = self._norm(x, p["ln1"])
        res = self._self_attention_full(h, p, positions, return_cache)
        if return_cache:
            res, kv = res
        x = x + res
        h = self._norm(x, p["ln2"])
        h = gated_mlp(h, p["wg"], p["wu"], p["wd"], a.act)
        h = pol.constrain(h, ("batch", "seq_q", None))
        x = x + h
        return (x, kv) if return_cache else (x, None)

    def _moe_layer_full(self, x, p, positions, return_cache):
        a, pol = self.arch, self.policy
        h = self._norm(x, p["ln1"])
        res = self._self_attention_full(h, p, positions, return_cache)
        if return_cache:
            res, kv = res
        x = x + res
        h = self._norm(x, p["ln2"])
        out, aux = moe_ffn(h, p, a, pol, self.cfg.capacity_factor)
        if a.moe.n_shared_experts:
            out = out + shared_expert_ffn(h, p, a, pol)
        x = x + out
        return (x, kv, aux) if return_cache else (x, aux)

    def _moe_layer_decode(self, x, p, cache: AttnCache):
        a, pol = self.arch, self.policy
        h = self._norm(x, p["ln1"])
        res, cache = self._self_attention_decode(h, p, cache)
        x = x + res
        h = self._norm(x, p["ln2"])
        out, _ = moe_ffn(h[:, None, :], p, a, pol, self.cfg.capacity_factor)
        out = out[:, 0]
        if a.moe.n_shared_experts:
            out = out + shared_expert_ffn(h, p, a, pol)
        return x + out, cache

    def _dense_layer_decode(self, x, p, cache: AttnCache):
        a = self.arch
        h = self._norm(x, p["ln1"])
        res, cache = self._self_attention_decode(h, p, cache)
        x = x + res
        h = self._norm(x, p["ln2"])
        x = x + gated_mlp(h, p["wg"], p["wu"], p["wd"], a.act)
        return x, cache

    def _cross_layer_full(self, x, p, frontend, return_cache):
        a, pol = self.arch, self.policy
        h = self._norm(x, p["ln1"])
        res = cross_attention_full(h, frontend, p, a, pol,
                                   use_pallas=self.cfg.use_pallas,
                                   return_kv=return_cache)
        if return_cache:
            res, kv = res
        x = x + jnp.tanh(p["gate_attn"].astype(jnp.float32)).astype(x.dtype) * res
        h = self._norm(x, p["ln2"])
        h = gated_mlp(h, p["wg"], p["wu"], p["wd"], a.act)
        x = x + jnp.tanh(p["gate_mlp"].astype(jnp.float32)).astype(x.dtype) * h
        return (x, kv) if return_cache else (x, None)

    def _cross_layer_decode(self, x, p, cross_kv):
        a = self.arch
        h = self._norm(x, p["ln1"])
        res = cross_attention_decode(h, cross_kv, p, a, self.policy)
        x = x + jnp.tanh(p["gate_attn"].astype(jnp.float32)).astype(x.dtype) * res
        h = self._norm(x, p["ln2"])
        h = gated_mlp(h, p["wg"], p["wu"], p["wd"], a.act)
        return x + jnp.tanh(p["gate_mlp"].astype(jnp.float32)).astype(x.dtype) * h

    def _mamba_layer_full(self, x, p, return_cache):
        a = self.arch
        h = self._norm(x, p["ln"])
        res = mamba_block_full(h, p, a, self.policy,
                               use_pallas=self.cfg.use_pallas,
                               return_cache=return_cache)
        if return_cache:
            res, cache = res
            return x + res, cache
        return x + res, None

    def _mamba_layer_decode(self, x, p, cache: MambaCache):
        a = self.arch
        h = self._norm(x, p["ln"])
        res, cache = mamba_block_decode(h, cache, p, a, self.policy)
        return x + res, cache

    # =========================================================================
    # scan machinery
    # =========================================================================
    def _scan(self, body: Callable, carry, xs, length: int):
        if self.cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        if self.cfg.scan_layers and length > 1:
            return jax.lax.scan(body, carry, xs)
        ys = []
        for i in range(length):
            xi = jax.tree.map(lambda t: t[i], xs) if xs is not None else None
            carry, y = body(carry, xi)
            ys.append(y)
        ys = jax.tree.map(lambda *t: jnp.stack(t), *ys) \
            if ys and ys[0] is not None else None
        return carry, ys

    # =========================================================================
    # full-sequence forward (train / prefill)
    # =========================================================================
    def _embed_inputs(self, params, tokens=None, embeds=None):
        a = self.arch
        if embeds is None:
            embeds = params["embed"][tokens] * (1.0 if not a.tie_embeddings
                                                else math.sqrt(a.d_model))
        x = embeds.astype(self.dtype)
        if a.pos_emb == PosEmb.SINUSOIDAL:
            s = x.shape[1]
            x = x + sinusoidal_pos(jnp.arange(s), a.d_model).astype(x.dtype)
        return self.policy.constrain(x, ("batch", None, None))

    def _forward_full(self, params, x, frontend=None, return_cache=False):
        """x: (B, S, D) -> (hidden (B,S,D), caches, aux)."""
        b, s, _ = x.shape
        positions = jnp.arange(s)
        caches: List[Any] = []
        aux_sum = jnp.zeros((2,), jnp.float32)

        for i, seg in enumerate(self.segments):
            p = params[f"seg{i}"]
            if seg.kind in ("dense", "dense_mlp"):
                def body(carry, lp):
                    y, kv = self._dense_layer_full(carry, lp, positions,
                                                   return_cache)
                    return y, kv
                x, kvs = self._scan(body, x, p, seg.n)
                caches.append(kvs)
            elif seg.kind == "moe":
                def body(carry, lp):
                    out = self._moe_layer_full(carry, lp, positions,
                                               return_cache)
                    if return_cache:
                        y, kv, aux = out
                        return y, (kv, aux)
                    y, aux = out
                    return y, (None, aux)
                x, ys = self._scan(body, x, p, seg.n)
                kvs, auxs = ys
                caches.append(kvs)
                aux_sum = aux_sum + jax.tree.reduce(
                    lambda a_, b_: a_ + b_, jax.tree.map(
                        lambda t: t.sum(0) if t.ndim > 1 else t, auxs))
            elif seg.kind == "mamba":
                def body(carry, lp):
                    y, c = self._mamba_layer_full(carry, lp, return_cache)
                    return y, c
                x, cs = self._scan(body, x, p, seg.n)
                caches.append(cs)
            elif seg.kind == "hyb_super":
                shared = p["attn"]

                def body(carry, lp):
                    y = carry

                    def inner(c2, lp2):
                        y2, cc = self._mamba_layer_full(c2, lp2, return_cache)
                        return y2, cc
                    y, mcs = self._scan(inner, y, lp, seg.inner)
                    y, kv = self._dense_layer_full(y, shared, positions,
                                                   return_cache)
                    return y, (mcs, kv)
                x, ys = self._scan(body, x, p["mamba"], seg.n)
                caches.append(ys)
            elif seg.kind == "vlm_super":
                def body(carry, lp):
                    dense_p, cross_p = lp
                    y = carry

                    def inner(c2, lp2):
                        y2, kv = self._dense_layer_full(c2, lp2, positions,
                                                        return_cache)
                        return y2, kv
                    y, kvs = self._scan(inner, y, dense_p, seg.inner)
                    y, ckv = self._cross_layer_full(y, cross_p, frontend,
                                                    return_cache)
                    return y, (kvs, ckv)
                x, ys = self._scan(body, x, (p["dense"], p["cross"]), seg.n)
                caches.append(ys)
            else:
                raise ValueError(seg.kind)
        x = self._norm(x, params["final_ln"])
        return x, caches, aux_sum

    # -- losses ----------------------------------------------------------------
    def _head_weight(self, params):
        if self.arch.tie_embeddings:
            return params["embed"].T
        return params["head"]

    def train_loss(self, params, batch) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        """batch: {"tokens" (B,S) | "embeds" (B,S,D), "labels" (B,S),
        optional "frontend" (B,T,D)}. labels < 0 are masked."""
        x = self._embed_inputs(params, batch.get("tokens"),
                               batch.get("embeds"))
        h, _, aux = self._forward_full(params, x,
                                       frontend=batch.get("frontend"))
        h = self.policy.constrain(h, ("batch", None, None))
        labels = batch["labels"]
        w = self._head_weight(params)
        b, s, d = h.shape
        chunk = self.cfg.loss_chunk or s
        chunk = min(chunk, s)
        if s % chunk:
            chunk = s
        nc = s // chunk

        def body(carry, inputs):
            hc, lc = inputs                    # (nc axis leading)
            # keep w in bf16 through the (FSDP-gathered) matmul; accumulate
            # in f32 via preferred_element_type — casting w to f32 first
            # would double the gather traffic.  [§Perf iteration 4]
            logits = jax.lax.dot_general(
                hc, w, (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            logits = self.policy.constrain(logits, ("batch", None, "vocab"))
            lse = jax.nn.logsumexp(logits, axis=-1)
            onehot = lc[..., None] == jnp.arange(logits.shape[-1])[None, None]
            tgt = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
            mask = (lc >= 0)
            tok_loss = jnp.where(mask, lse - tgt, 0.0)
            return (carry[0] + tok_loss.sum(), carry[1] + mask.sum()), None

        hc = h.reshape(b, nc, chunk, d).swapaxes(0, 1)
        lc = labels.reshape(b, nc, chunk).swapaxes(0, 1)
        (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32),
                                            jnp.zeros((), jnp.int32)),
                                     (hc, lc))
        loss = tot / jnp.maximum(cnt, 1)
        metrics = {"xent": loss, "lb_loss": aux[0], "moe_drops": aux[1]}
        if self.arch.moe is not None:
            loss = loss + 0.01 * aux[0] / max(self.arch.n_layers, 1)
        return loss, metrics

    # -- prefill ---------------------------------------------------------------
    def prefill(self, params, tokens=None, embeds=None, frontend=None,
                s_max: Optional[int] = None,
                logit_pos: Optional[int] = None):
        """Returns (logits (B, V) at logit_pos (default: last), cache).

        ``logit_pos`` supports length-bucketed prefill: causal attention makes
        tail padding inert for positions <= logit_pos."""
        x = self._embed_inputs(params, tokens, embeds)
        b, s, _ = x.shape
        s_max = s_max or s
        h, raw_caches, _ = self._forward_full(params, x, frontend=frontend,
                                              return_cache=True)
        pos = s - 1 if logit_pos is None else logit_pos
        logits = (h[:, pos].astype(jnp.float32)
                  @ self._head_weight(params).astype(jnp.float32))
        cache = self._package_cache(raw_caches, b, s, s_max)
        return logits, cache

    def _pad_kv(self, kv, s, s_max):
        k, v = kv
        # kv from scan: (L, B, S, Hkv, hd)
        pad = [(0, 0)] * k.ndim
        pad[-3] = (0, s_max - s)
        k = jnp.pad(k.astype(self.dtype), pad)
        v = jnp.pad(v.astype(self.dtype), pad)
        return k, v

    def _attn_cache_from_kv(self, kv, b, s, s_max):
        w = self.cfg.recent_window
        k, v = self._pad_kv(kv, s, s_max)
        lead = k.shape[:-4] if k.ndim > 4 else ()
        zr = jnp.zeros(lead + (b, w) + self._kv_shape(), self.dtype)
        return {"k_big": k, "v_big": v, "k_rec": zr, "v_rec": zr + 0,
                "big_len": jnp.asarray(s, jnp.int32),
                "rec_len": jnp.zeros((), jnp.int32)}

    def _package_cache(self, raw, b, s, s_max):
        out = []
        for seg, c in zip(self.segments, raw):
            if seg.kind in ("dense", "dense_mlp", "moe"):
                out.append(self._attn_cache_from_kv(c, b, s, s_max))
            elif seg.kind == "mamba":
                out.append(c)
            elif seg.kind == "hyb_super":
                mcs, kv = c
                out.append({"mamba": mcs,
                            "attn": self._attn_cache_from_kv(kv, b, s, s_max)})
            elif seg.kind == "vlm_super":
                kvs, ckv = c
                out.append({"dense": self._attn_cache_from_kv(kvs, b, s, s_max),
                            "cross_kv": ckv})
        return out

    def init_cache(self, batch: int, s_max: int, frontend_tokens: int = 0):
        """Zero cache (for dry-run decode cells and fresh generation)."""
        a = self.arch
        hd = a.resolved_head_dim
        w = self.cfg.recent_window
        dt = self.dtype
        kv_shape = self._kv_shape()

        def attn_cache(*lead):
            zb = jnp.zeros(lead + (batch, s_max) + kv_shape, dt)
            zr = jnp.zeros(lead + (batch, w) + kv_shape, dt)
            return {"k_big": zb, "v_big": zb + 0, "k_rec": zr, "v_rec": zr + 0,
                    "big_len": jnp.zeros((), jnp.int32),
                    "rec_len": jnp.zeros((), jnp.int32)}

        def mamba_cache(*lead):
            c = make_mamba_cache(batch, a)
            return jax.tree.map(
                lambda t: jnp.broadcast_to(t, lead + t.shape), c)

        out = []
        for seg in self.segments:
            if seg.kind in ("dense", "dense_mlp", "moe"):
                out.append(attn_cache(seg.n))
            elif seg.kind == "mamba":
                out.append(mamba_cache(seg.n))
            elif seg.kind == "hyb_super":
                out.append({"mamba": mamba_cache(seg.n, seg.inner),
                            "attn": attn_cache(seg.n)})
            elif seg.kind == "vlm_super":
                nf = frontend_tokens or a.n_frontend_tokens
                out.append({
                    "dense": attn_cache(seg.n, seg.inner),
                    "cross_kv": (jnp.zeros((seg.n, batch, nf, a.n_kv_heads,
                                            hd), dt),
                                 jnp.zeros((seg.n, batch, nf, a.n_kv_heads,
                                            hd), dt))})
        return out

    def cache_specs(self, batch: int, s_max: int, frontend_tokens: int = 0):
        """PartitionSpec tree matching init_cache(batch, s_max) (shape-aware
        so it is valid for jit in_shardings)."""
        a = self.arch
        pol = self.policy
        hd = a.resolved_head_dim
        w = self.cfg.recent_window

        def P_(logical, shape):
            return pol.spec_for_shape(logical, shape)

        def attn_spec(*lead):
            nl = (None,) * len(lead)
            big_shape = lead + (batch, s_max) + self._kv_shape()
            rec_shape = lead + (batch, w) + self._kv_shape()
            big = P_(nl + ("batch", "kv_seq", None, None), big_shape)
            rec = P_(nl + ("batch", None, None, None), rec_shape)
            return {"k_big": big, "v_big": big, "k_rec": rec, "v_rec": rec,
                    "big_len": P_((), ()), "rec_len": P_((), ())}

        def mamba_spec(*lead):
            nl = (None,) * len(lead)
            s_cfg = a.ssm
            nh = self.n_ssm_heads_like()
            return MambaCache(
                ssm_state=P_(nl + ("batch", "ssm_heads", None, None),
                             lead + (batch, nh, s_cfg.head_dim,
                                     s_cfg.d_state)),
                conv_x=P_(nl + ("batch", None, "d_inner"),
                          lead + (batch, s_cfg.d_conv - 1, a.d_inner)),
                conv_bc=P_(nl + ("batch", None, None),
                           lead + (batch, s_cfg.d_conv - 1,
                                   2 * s_cfg.ngroups * s_cfg.d_state)))

        out = []
        for seg in self.segments:
            if seg.kind in ("dense", "dense_mlp", "moe"):
                out.append(attn_spec(seg.n))
            elif seg.kind == "mamba":
                out.append(mamba_spec(seg.n))
            elif seg.kind == "hyb_super":
                out.append({"mamba": mamba_spec(seg.n, seg.inner),
                            "attn": attn_spec(seg.n)})
            elif seg.kind == "vlm_super":
                nf = frontend_tokens or a.n_frontend_tokens
                ckv = P_((None, "batch", "frontend_seq", None, None),
                         (seg.n, batch, nf, a.n_kv_heads, hd))
                out.append({"dense": attn_spec(seg.n, seg.inner),
                            "cross_kv": (ckv, ckv)})
        return out

    def n_ssm_heads_like(self) -> int:
        return self.arch.n_ssm_heads

    # -- decode ------------------------------------------------------------
    def _unpack_attn(self, c, idx=None):
        sel = (lambda t: t if idx is None else t[idx])
        return AttnCache(k_big=sel(c["k_big"]), v_big=sel(c["v_big"]),
                         k_recent=sel(c["k_rec"]), v_recent=sel(c["v_rec"]),
                         big_len=c["big_len"], recent_len=c["rec_len"])

    def decode_step(self, params, cache, tokens):
        """tokens: (B,) int32 -> (logits (B, V), new cache)."""
        a = self.arch
        x = params["embed"][tokens].astype(self.dtype)
        if a.tie_embeddings:
            x = x * math.sqrt(a.d_model)
        if a.pos_emb == PosEmb.SINUSOIDAL:
            c0 = cache[0]
            pos = c0["big_len"] + c0["rec_len"]
            x = x + sinusoidal_pos(pos[None], a.d_model)[0].astype(x.dtype)
        x = self.policy.constrain(x, ("batch", None))
        new_cache = []

        for i, seg in enumerate(self.segments):
            p = params[f"seg{i}"]
            c = cache[i]
            if seg.kind in ("dense", "dense_mlp", "moe"):
                step = self._moe_layer_decode if seg.kind == "moe" \
                    else self._dense_layer_decode

                def body(carry, inp):
                    lp, lc = inp
                    ac = AttnCache(k_big=lc[0], v_big=lc[1], k_recent=lc[2],
                                   v_recent=lc[3], big_len=c["big_len"],
                                   recent_len=c["rec_len"])
                    y, nc_ = step(carry, lp, ac)
                    return y, (nc_.k_recent, nc_.v_recent)
                xs = (p, (c["k_big"], c["v_big"], c["k_rec"], c["v_rec"]))
                x, recs = self._scan(body, x, xs, seg.n)
                new_cache.append({**c, "k_rec": recs[0], "v_rec": recs[1],
                                  "rec_len": c["rec_len"] + 1})
            elif seg.kind == "mamba":
                def body(carry, inp):
                    lp, lc = inp
                    y, nc_ = self._mamba_layer_decode(carry, lp, lc)
                    return y, nc_
                x, ncs = self._scan(body, x, (p, c), seg.n)
                new_cache.append(ncs)
            elif seg.kind == "hyb_super":
                shared = p["attn"]

                def body(carry, inp):
                    (mp, mc), lc = inp

                    def inner(c2, inp2):
                        lp2, lc2 = inp2
                        y2, nc2 = self._mamba_layer_decode(c2, lp2, lc2)
                        return y2, nc2
                    y, nmc = self._scan(inner, carry, (mp, mc), seg.inner)
                    ac = AttnCache(k_big=lc[0], v_big=lc[1], k_recent=lc[2],
                                   v_recent=lc[3],
                                   big_len=c["attn"]["big_len"],
                                   recent_len=c["attn"]["rec_len"])
                    y, nac = self._dense_layer_decode(y, shared, ac)
                    return y, (nmc, (nac.k_recent, nac.v_recent))
                ca = c["attn"]
                xs = ((p["mamba"], c["mamba"]),
                      (ca["k_big"], ca["v_big"], ca["k_rec"], ca["v_rec"]))
                x, ys = self._scan(body, x, xs, seg.n)
                nmc, recs = ys
                new_cache.append({
                    "mamba": nmc,
                    "attn": {**ca, "k_rec": recs[0], "v_rec": recs[1],
                             "rec_len": ca["rec_len"] + 1}})
            elif seg.kind == "vlm_super":
                cd = c["dense"]

                def body(carry, inp):
                    (dp, cp), (dc, ckv) = inp

                    def inner(c2, inp2):
                        lp2, lc2 = inp2
                        ac2 = AttnCache(k_big=lc2[0], v_big=lc2[1],
                                        k_recent=lc2[2], v_recent=lc2[3],
                                        big_len=cd["big_len"],
                                        recent_len=cd["rec_len"])
                        y2, nc2 = self._dense_layer_decode(c2, lp2, ac2)
                        return y2, (nc2.k_recent, nc2.v_recent)
                    y, recs = self._scan(
                        inner, carry,
                        ((dp), (dc[0], dc[1], dc[2], dc[3])), seg.inner)
                    y = self._cross_layer_decode(y, cp, ckv)
                    return y, recs
                xs = ((p["dense"], p["cross"]),
                      ((cd["k_big"], cd["v_big"], cd["k_rec"], cd["v_rec"]),
                       c["cross_kv"]))
                x, recs = self._scan(body, x, xs, seg.n)
                new_cache.append({
                    "dense": {**cd, "k_rec": recs[0], "v_rec": recs[1],
                              "rec_len": cd["rec_len"] + 1},
                    "cross_kv": c["cross_kv"]})
        x = self._norm(x, params["final_ln"])
        logits = (x.astype(jnp.float32)
                  @ self._head_weight(params).astype(jnp.float32))
        logits = self.policy.constrain(logits, ("batch", "vocab"))
        return logits, new_cache

    def maybe_flush(self, cache):
        """Flush recent->big on every attention cache (call every
        recent_window steps from the serving loop)."""
        def flush_attn(c):
            ac = self._unpack_attn(c)
            nc = flush_cache(ac)
            return {"k_big": nc.k_big, "v_big": nc.v_big,
                    "k_rec": nc.k_recent, "v_rec": nc.v_recent,
                    "big_len": nc.big_len, "rec_len": nc.recent_len}

        out = []
        for seg, c in zip(self.segments, cache):
            if seg.kind in ("dense", "dense_mlp", "moe"):
                out.append(flush_attn(c))
            elif seg.kind == "mamba":
                out.append(c)
            elif seg.kind == "hyb_super":
                out.append({"mamba": c["mamba"], "attn": flush_attn(c["attn"])})
            elif seg.kind == "vlm_super":
                out.append({"dense": flush_attn(c["dense"]),
                            "cross_kv": c["cross_kv"]})
        return out
