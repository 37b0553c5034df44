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

``held_experts_ffn`` is the served expert layer (``PagedEngine``): one
chip's share of an expert-parallel deployment. It routes over every
expert, computes the assignments to the experts it holds and drops none,
and adds the shared experts; the other chips' shares, and the exchange that
would sum them, are not here.
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


def route(x_flat, router_w, bias, moe, n_pad: Optional[int] = None):
    """Top-k routing of x_flat (T, D) over every expert, in float32.

    ``softmax``: top-k of the softmax. ``sigmoid`` (DeepSeek-V3
    ``noaux_tc``, one group): top-k of sigmoid score + ``bias``, the
    correction bias, which selects and does not weight. The chosen scores
    are normalised over the k (``norm_topk_prob``) and times
    ``routed_scaling_factor``. Experts past ``moe.n_experts`` (up to
    ``n_pad``) are never chosen. Returns (top_e (T, k) int32, top_w (T, k)
    float32, probs (T, n_pad), each row summing to 1, for a balance loss).
    """
    t = x_flat.shape[0]
    logits = jnp.matmul(x_flat.astype(jnp.float32),
                        router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    n_pad = n_pad or moe.n_experts
    if moe.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choose = scores if bias is None else scores + bias.astype(jnp.float32)
        pad = jnp.zeros((t, n_pad - moe.n_experts), jnp.float32)
        scores = jnp.concatenate([scores, pad], axis=-1)
        choose = jnp.concatenate([choose, pad + NEG_INF], axis=-1)
        _, top_e = jax.lax.top_k(choose, moe.top_k)
        top_w = jnp.take_along_axis(scores, top_e, axis=-1)
        probs = scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-9)
    elif moe.router == "softmax":
        logits = jnp.concatenate(
            [logits, jnp.full((t, n_pad - moe.n_experts), NEG_INF)], axis=-1)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, moe.top_k)
    else:
        raise ValueError(f"no router {moe.router!r}")
    if moe.norm_topk_prob:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    return top_e, top_w * moe.routed_scaling_factor, probs


def _moe_local(x_flat, router_w, router_bias, w_gate, w_up, w_down, *,
               moe, n_pad: int, e_lo: int, capacity: int, act: str):
    """Routed-expert compute for experts [e_lo, e_lo + E_loc) held locally.

    x_flat: (T, D); router_w: (D, n_real); w_*: (E_loc, D, F) / (E_loc, F, D).
    Returns (out: (T, D) partial sum, aux: (2,) [load-balance loss, drops]).
    """
    t, d = x_flat.shape
    e_loc = w_gate.shape[0]
    top_k, n_real = moe.top_k, moe.n_experts
    top_e, top_w, probs = route(x_flat, router_w, router_bias, moe, n_pad)

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

        def ranked(xb, rw, rb, wg, wu, wd):
            t_ = xb.shape[0] * xb.shape[1]
            idx = jax.lax.axis_index("model")
            out, aux = _moe_local(
                xb.reshape(t_, d), rw, rb, wg, wu, wd, moe=moe, n_pad=n_pad,
                e_lo=idx * e_loc, capacity=capacity, act=arch.act)
            out = jax.lax.psum(out, "model")
            aux = jax.lax.psum(aux * jnp.array([1.0 / ep, 1.0]), "model")
            return out.reshape(xb.shape), aux

        batch_spec = policy.spec(("batch",))[0]
        out, aux = jax.shard_map(
            ranked, mesh=mesh,
            in_specs=(P(batch_spec, None, None), P(), P(),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=(P(batch_spec, None, None), P()),
            check_vma=False,
        )(x, p["router"].astype(jnp.float32), _router_bias(p, moe),
          p["w_gate"], p["w_up"], p["w_down"])
        return out.astype(x.dtype), aux

    # single-rank path (no mesh / tiny models)
    capacity = max(int(b * s * moe.top_k / moe.n_experts * cf), 4)
    out, aux = _moe_local(
        x.reshape(b * s, d), p["router"].astype(jnp.float32),
        _router_bias(p, moe), p["w_gate"], p["w_up"], p["w_down"], moe=moe,
        n_pad=n_pad, e_lo=0, capacity=capacity, act=arch.act)
    return out.reshape(b, s, d).astype(x.dtype), aux


def _router_bias(p, moe):
    """The correction bias in float32, zeros where the router has none."""
    if moe.router_bias:
        return p["router_bias"].astype(jnp.float32)
    return jnp.zeros((moe.n_experts,), jnp.float32)


def shared_expert_ffn(x, p, arch, policy: Policy = NO_POLICY):
    """Always-on shared experts = one dense TP MLP of width d_shared."""
    return gated_mlp(x, p["sh_gate"], p["sh_up"], p["sh_down"], arch.act)


def held_experts_ffn(h, p, arch, *, first: int, valid=None,
                     tile: Optional[int] = None):
    """The served expert layer: routed experts ``first .. first + E_h - 1``
    of this chip (``p["w_gate"]``: (E_h, D, F)) and the shared experts.

    h: (T, D); ``valid`` (T,) marks the tokens that count (a decode step's
    active slots, a prefill's real positions). Every token is routed over
    all ``moe.n_experts`` (``route``); each assignment of a valid token to
    a held expert is computed, none dropped, and weighted by its router
    weight. With ``tile`` None every row of ``h`` goes through every held
    expert, the weight 0 where it was not routed there (a decode batch: at
    most the batch's rows per expert, each expert's weights read once).
    With ``tile`` the assignments are grouped by expert and computed
    ``tile`` rows at a time, so an expert computes its routed rows and at
    most one partial tile (prefill). Returns (out (T, D), the assignments
    of valid tokens to each held expert (E_h,) int32).
    """
    moe = arch.moe
    t = h.shape[0]
    n_held = p["w_gate"].shape[0]
    top_e, top_w, _ = route(h, p["router"], p.get("router_bias"), moe)
    here = (top_e >= first) & (top_e < first + n_held)
    if valid is not None:
        here &= valid[:, None]
    e_here = jnp.where(here, top_e - first, n_held)           # (T, k)
    counts = jnp.zeros((n_held,), jnp.int32).at[e_here.reshape(-1)].add(
        1, mode="drop")
    if tile is None:
        w = jnp.zeros((t, n_held + 1), jnp.float32).at[
            jnp.arange(t)[:, None], e_here].add(jnp.where(here, top_w, 0.0))
        actf = jax.nn.silu if arch.act == "silu" else jax.nn.gelu
        g = jnp.einsum("td,edf->etf", h, p["w_gate"])
        u = jnp.einsum("td,edf->etf", h, p["w_up"])
        y = jnp.einsum("etf,efd->etd", actf(g) * u, p["w_down"])
        out = jnp.einsum("etd,te->td", y, w[:, :n_held].astype(y.dtype))
    else:
        out = _grouped(h, e_here.reshape(-1), top_w.reshape(-1), counts, p,
                       tile, arch.act)
    if moe.n_shared_experts:
        out = out + gated_mlp(h, p["sh_gate"], p["sh_up"], p["sh_down"],
                              arch.act)
    return out, counts


def _grouped(h, flat_e, flat_w, counts, p, tile: int, act: str):
    """Assignments sorted by held expert (the rest, ``flat_e == E_h``,
    last), computed ``tile`` rows at a time: tile i belongs to one expert,
    and a loop of as many tiles as the counts need runs them."""
    t, d = h.shape
    k = flat_e.shape[0] // t
    order = jnp.argsort(flat_e, stable=True)
    tok = (order // k).astype(jnp.int32)                  # token of each row
    wgt = flat_w[order]
    starts = jnp.cumsum(counts) - counts
    tiles = (counts + tile - 1) // tile
    ends = jnp.cumsum(tiles)                              # tiles up to e
    last = flat_e.shape[0] - 1

    def body(i, out):
        e = jnp.searchsorted(ends, i, side="right")
        r0 = starts[e] + (i - (ends[e] - tiles[e])) * tile
        rows = r0 + jnp.arange(tile)
        ok = rows < starts[e] + counts[e]
        rows = jnp.minimum(rows, last)
        y = gated_mlp(h[tok[rows]], p["w_gate"][e], p["w_up"][e],
                      p["w_down"][e], act)
        w = jnp.where(ok, wgt[rows], 0.0).astype(y.dtype)
        return out.at[tok[rows]].add(y * w[:, None])

    out = jnp.zeros((t, d), jnp.result_type(h.dtype, p["w_down"].dtype))
    return jax.lax.fori_loop(0, ends[-1], body, out)
