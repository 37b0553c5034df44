"""Mixture-of-Experts FFN with TP-style expert parallelism.

Experts are sharded over the ``model`` mesh axis; activations are replicated
across it (they are only batch-sharded). Each model-rank computes the routed
assignments that land on *its* experts (sort -> truncate to static capacity ->
gather -> expert GEMMs -> scatter-add) and the rank outputs are combined with
a single ``psum`` — the same one all-reduce per layer a dense Megatron MLP
pays, but with only the top-k expert FLOPs. Capacity overflow drops tokens
(standard GShard semantics); the drop fraction is returned for monitoring.

Expert counts that do not divide the model axis (qwen2-moe's 60 over 16) are
padded with dummy experts whose router logits are -inf; they cost capacity
buffers but receive no tokens.

When no mesh is active (CPU smoke tests / the serving engine's tiny models)
the identical inner function runs with a single rank and no collectives.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import NO_POLICY, Policy
from repro.models.common import gated_mlp

NEG_INF = -1e30


def padded_experts(n_experts: int, ep: int) -> int:
    """Number of expert slots after padding to a multiple of the EP degree."""
    return ((n_experts + ep - 1) // ep) * ep


def _moe_local(x_flat, router_w, w_gate, w_up, w_down, *, top_k: int,
               n_real: int, n_pad: int, e_lo: int, capacity: int, act: str):
    """Routed-expert compute for experts [e_lo, e_lo + E_loc) held locally.

    x_flat: (T, D); router_w: (D, n_real); w_*: (E_loc, D, F) / (E_loc, F, D).
    Returns (out: (T, D) partial sum, aux: (2,) [load-balance loss, drops]).
    """
    t, d = x_flat.shape
    e_loc = w_gate.shape[0]
    logits = x_flat.astype(jnp.float32) @ router_w              # (T, n_real)
    if n_pad > n_real:
        logits = jnp.concatenate(
            [logits, jnp.full((t, n_pad - n_real), NEG_INF)], axis=-1)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, top_k)                  # (T, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(-1)                                  # (T*k,)
    flat_t = jnp.repeat(jnp.arange(t), top_k)
    flat_w = top_w.reshape(-1)
    local = (flat_e >= e_lo) & (flat_e < e_lo + e_loc)
    # sort so local assignments come first, grouped by expert
    sort_key = jnp.where(local, flat_e - e_lo, e_loc)
    order = jnp.argsort(sort_key, stable=True)
    k_max = e_loc * capacity
    order = order[:k_max]
    se = sort_key[order]                                        # (k_max,)
    st = flat_t[order]
    sw = flat_w[order]
    # rank within expert = index - first index of this expert
    first = jnp.searchsorted(se, jnp.arange(e_loc + 1))
    pos_in_e = jnp.arange(se.shape[0]) - first[jnp.clip(se, 0, e_loc)]
    valid = (se < e_loc) & (pos_in_e < capacity)
    slot = jnp.where(valid, se * capacity + pos_in_e, k_max)    # OOB -> drop

    gathered = x_flat[jnp.where(valid, st, 0)]                  # (k_max, D)
    disp = jnp.zeros((k_max + 1, d), x_flat.dtype).at[slot].set(
        jnp.where(valid[:, None], gathered, 0))[:k_max]
    disp = disp.reshape(e_loc, capacity, d)

    actf = jax.nn.silu if act == "silu" else jax.nn.gelu
    h = actf(jnp.einsum("ecd,edf->ecf", disp, w_gate)) * \
        jnp.einsum("ecd,edf->ecf", disp, w_up)
    eo = jnp.einsum("ecf,efd->ecd", h, w_down).reshape(k_max, d)

    contrib = eo[jnp.where(valid, slot, 0)] * \
        jnp.where(valid, sw, 0.0)[:, None].astype(eo.dtype)
    out = jnp.zeros((t, d), eo.dtype).at[jnp.where(valid, st, t - 1)].add(
        jnp.where(valid[:, None], contrib, 0))

    # aux: load-balance loss (Switch-style) over global router state + drops
    frac_tokens = jnp.zeros((n_pad,), jnp.float32) \
        .at[flat_e].add(1.0) / (t * top_k)
    frac_probs = probs.mean(0)
    lb_loss = n_real * jnp.sum(frac_tokens * frac_probs)
    n_local = local.sum()
    drops = jnp.maximum(n_local - valid.sum(), 0).astype(jnp.float32)
    return out, jnp.stack([lb_loss, drops])


def moe_ffn(x: jnp.ndarray, p: dict, arch, policy: Policy = NO_POLICY,
            capacity_factor: Optional[float] = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out, aux[2])."""
    moe = arch.moe
    b, s, d = x.shape
    cf = capacity_factor if capacity_factor is not None else moe.capacity_factor

    mesh = policy.mesh
    ep = policy.axis_size("experts")
    n_pad = padded_experts(moe.n_experts, max(ep, 1))
    assert p["w_gate"].shape[0] == n_pad, (p["w_gate"].shape, n_pad)
    if mesh is not None and ep > 1:
        e_loc = n_pad // ep
        t_loc = (b // max(policy.axis_size("batch"), 1)) * s
        capacity = max(int(t_loc * moe.top_k / moe.n_experts * cf), 4)

        def ranked(xb, rw, wg, wu, wd):
            t_ = xb.shape[0] * xb.shape[1]
            idx = jax.lax.axis_index("model")
            out, aux = _moe_local(
                xb.reshape(t_, d), rw, wg, wu, wd, top_k=moe.top_k,
                n_real=moe.n_experts, n_pad=n_pad, e_lo=idx * e_loc,
                capacity=capacity, act=arch.act)
            out = jax.lax.psum(out, "model")
            aux = jax.lax.psum(aux * jnp.array([1.0 / ep, 1.0]), "model")
            return out.reshape(xb.shape), aux

        batch_spec = policy.spec(("batch",))[0]
        out, aux = jax.shard_map(
            ranked, mesh=mesh,
            in_specs=(P(batch_spec, None, None), P(),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=(P(batch_spec, None, None), P()),
            check_vma=False,
        )(x, p["router"].astype(jnp.float32), p["w_gate"], p["w_up"],
          p["w_down"])
        return out.astype(x.dtype), aux

    # single-rank path (no mesh / tiny models)
    capacity = max(int(b * s * moe.top_k / moe.n_experts * cf), 4)
    out, aux = _moe_local(
        x.reshape(b * s, d), p["router"].astype(jnp.float32),
        p["w_gate"], p["w_up"], p["w_down"], top_k=moe.top_k,
        n_real=moe.n_experts, n_pad=n_pad, e_lo=0, capacity=capacity,
        act=arch.act)
    return out.reshape(b, s, d).astype(x.dtype), aux


def shared_expert_ffn(x, p, arch, policy: Policy = NO_POLICY):
    """Always-on shared experts = one dense TP MLP of width d_shared."""
    return gated_mlp(x, p["sh_gate"], p["sh_up"], p["sh_down"], arch.act)
