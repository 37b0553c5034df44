"""Per-worker continuous-batching engine with a paged KV cache (vLLM-style).

Slot-based execution over a page pool: each running request owns a slot and a
list of pages (block table). Iteration-level scheduling (Orca-style): new
requests run a prefill iteration (preempting decode, as vLLM does — the
paper's constraint (d) budgets exactly this), otherwise all running slots
advance one decode step via paged attention. The engine picks its attention
kernels once, from the device it runs on: on a TPU, prefill runs the compiled
Pallas flash-attention kernel, decode the compiled Pallas paged kernel, and
both the Pallas RMSNorm; elsewhere all run the jnp references.
``EngineConfig.interpret`` runs the same Pallas kernels in interpret mode
(CPU tests).

Serves dense/GQA transformer archs (the paper's Llama-2 family), with K and
V page pools, and latent-attention archs with sparse experts
(Moonlight-16B-A3B), with one pool of latent rows: a dense segment of layers
then a segment of expert layers, each a ``lax.scan``, the experts those of
this worker's share (``EngineConfig.first_expert`` and the leading size of
its expert weights). Execution is real JAX compute — iteration wall-times
feed the TraceBuffer that fits the paper's performance models
(Eqs. 1-3)."""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Callable, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, Family, PosEmb
from repro.core.perf_model import TraceBuffer
from repro.core.request import ReqState, Request
from repro.kernels.decode_attention import (mla_decode_attention,
                                            paged_decode_attention)
from repro.kernels.decode_attention.mla_decode_attention import pool_width
from repro.models import mla
from repro.models.common import gated_mlp, rms_norm, rope, sinusoidal_pos
from repro.models.model import LM, ExecConfig
from repro.models.moe import held_experts_ffn
from repro.serving.spans import ServeStats, span


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 8
    page_size: int = 16             # multiple of 8 (the f32 pool's TPU tile)
    n_pages: int = 512
    max_pages_per_seq: int = 64
    max_new_tokens: int = 2048
    interpret: bool = False         # Pallas kernels in interpret mode (CPU
                                    # tests only); a TPU always compiles them
    prefill_chunk: int = 0          # >0: Sarathi-style chunked prefill — at
                                    # most this many prompt tokens per
                                    # iteration, bounding decode preemption
                                    # stalls (shrinks constraint (d) pressure)
    first_expert: int = 0           # expert parallel: the first routed
                                    # expert this worker holds; how many is
                                    # the leading size of its expert weights


def prompt_bucket(n_tokens: int) -> int:
    """Power-of-two prefill length bucket (one compiled program each)."""
    return max(8, 1 << (n_tokens - 1).bit_length())


# ---- jitted model math (module-level: engines on one device share the
# compiled programs) ----------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("arch", "use_pallas",
                                             "interpret"))
def prefill_step(params, tokens, logit_pos, *, arch: ArchConfig,
                 use_pallas: bool, interpret: bool):
    """tokens: (1, S_bucket) -> (logits (V,), ks, vs (L, S, Hkv, hd)).
    S is a power-of-two bucket; real length = logit_pos + 1 (causal
    attention makes the tail padding inert)."""
    model = LM(arch, exec_cfg=ExecConfig(scan_layers=True,
                                         use_pallas=use_pallas,
                                         interpret=interpret))
    logits, cache = model.prefill(params, tokens=tokens,
                                  s_max=tokens.shape[1], logit_pos=logit_pos)
    c0 = cache[0]
    return (logits[0], c0["k_big"][:, 0].astype(jnp.float32),
            c0["v_big"][:, 0].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("arch", "page_size",
                                             "use_pallas", "interpret"))
def decode_step(params, kv_k, kv_v, block_tables, lengths, tokens, active, *,
                arch: ArchConfig, page_size: int, use_pallas: bool,
                interpret: bool):
    """One decode iteration for every slot (inactive ones masked).
    kv_k/kv_v: (L, n_pages, Hkv, page, hd). Returns (logits, new kv_k,
    new kv_v)."""
    a = arch
    hd = a.resolved_head_dim
    x = params["embed"][tokens].astype(jnp.float32)
    if a.tie_embeddings:
        x = x * math.sqrt(a.d_model)
    if a.pos_emb == PosEmb.SINUSOIDAL:
        x = x + sinusoidal_pos(lengths, a.d_model).astype(x.dtype)
    page_ids = jnp.take_along_axis(
        block_tables, (lengths // page_size)[:, None], axis=1)[:, 0]
    offs = lengths % page_size
    msk = active[:, None, None]
    norm = functools.partial(rms_norm, eps=a.norm_eps, use_pallas=use_pallas,
                             interpret=interpret)

    def layer(x, inp):
        p, kk, vv = inp   # one layer's weights, (n_pages, Hkv, page, hd) pools
        h = norm(x, p["ln1"])
        q = (h @ p["wq"]).reshape(-1, a.n_heads, hd)
        k = (h @ p["wk"]).reshape(-1, a.n_kv_heads, hd)
        v = (h @ p["wv"]).reshape(-1, a.n_kv_heads, hd)
        if a.qkv_bias:
            q = q + p["bq"].reshape(a.n_heads, hd)
            k = k + p["bk"].reshape(a.n_kv_heads, hd)
            v = v + p["bv"].reshape(a.n_kv_heads, hd)
        if a.pos_emb == PosEmb.ROPE:
            q = rope(q[:, None], lengths[:, None], a.rope_theta)[:, 0]
            k = rope(k[:, None], lengths[:, None], a.rope_theta)[:, 0]
        # (page_ids, :, offs) selects (B, Hkv, hd): one token slot per seq
        kk = kk.at[page_ids, :, offs].set(
            jnp.where(msk, k, kk[page_ids, :, offs]))
        vv = vv.at[page_ids, :, offs].set(
            jnp.where(msk, v, vv[page_ids, :, offs]))
        att = paged_decode_attention(q, kk, vv, block_tables, lengths + 1,
                                     use_pallas=use_pallas,
                                     interpret=interpret)
        x = x + att.reshape(x.shape[0], -1) @ p["wo"]
        h = norm(x, p["ln2"])
        x = x + gated_mlp(h, p["wg"], p["wu"], p["wd"], a.act)
        return x, (kk, vv)

    x, (kv_k, kv_v) = jax.lax.scan(layer, x, (params["seg0"], kv_k, kv_v))
    x = norm(x, params["final_ln"])
    head = params["embed"].T if a.tie_embeddings else params["head"]
    return x @ head.astype(x.dtype), kv_k, kv_v


# ---- latent attention with sparse experts (Moonlight) -----------------------
# rows a prefill's expert layer computes at a time, per expert
PREFILL_EXPERT_TILE = 128
# Matmul precision of these programs. The router chooses experts from the
# residual stream, and products rounded to one bfloat16 pass (the TPU's
# default) move the near-tied choices, each of which swaps a whole
# expert's output in or out: over 26 expert layers that moves logits by up
# to ~0.7 where the dense models move by ~0.001. At float32 products the
# served choices, and logits, are the float32 model's.
MLA_MOE_PRECISION = "highest"


@functools.partial(jax.jit, static_argnames=("n",), donate_argnums=0)
def _scatter_latent(pool, rows, pages, offs, *, n: int):
    """The first ``n`` of ``rows`` (L_seg, S, W) into ``pages``, ``offs``
    (n,) of ``pool`` (L_seg, n_pages, page, W), in place: the pool is
    donated, so a prompt's write needs no second pool."""
    # adjacent index arrays: the indexed view is (L_seg, n, W)
    return pool.at[:, pages, offs].set(rows[:, :n])


def _pool_row(row):
    """Latent rows (..., C) in float32, zero-padded to the pool's width."""
    pad = pool_width(row.shape[-1]) - row.shape[-1]
    return jnp.pad(row.astype(jnp.float32),
                   [(0, 0)] * (row.ndim - 1) + [(0, pad)])


@functools.partial(jax.jit, static_argnames=("arch", "first_expert",
                                             "use_pallas", "interpret"))
def mla_moe_prefill_step(params, tokens, logit_pos, *, arch: ArchConfig,
                         first_expert: int, use_pallas: bool,
                         interpret: bool):
    """tokens: (1, S_bucket) -> (logits (V,), latent rows per segment
    [(L_seg, S, W)], held (expert layers, held experts) int32: the real
    prompt's assignments to each held expert). Real length = logit_pos + 1;
    padding positions are routed to no expert."""
    with jax.default_matmul_precision(MLA_MOE_PRECISION):
        return _mla_moe_prefill(params, tokens, logit_pos, arch, first_expert,
                                use_pallas, interpret)


def _mla_moe_prefill(params, tokens, logit_pos, arch, first_expert,
                     use_pallas, interpret):
    a = arch
    s = tokens.shape[1]
    positions = jnp.arange(s)
    valid = positions <= logit_pos
    norm = functools.partial(rms_norm, eps=a.norm_eps, use_pallas=use_pallas,
                             interpret=interpret)
    x = params["embed"][tokens].astype(jnp.float32)

    def attention(x, p):
        out, row = mla.attention_full(norm(x, p["ln1"]), p, a, positions,
                                      norm=norm, use_pallas=use_pallas,
                                      interpret=interpret)
        return x + out, _pool_row(row[0])

    def dense(x, p):
        x, row = attention(x, p)
        h = norm(x, p["ln2"])
        return x + gated_mlp(h, p["wg"], p["wu"], p["wd"], a.act), row

    def expert_layer(x, p):
        x, row = attention(x, p)
        out, held = held_experts_ffn(norm(x, p["ln2"])[0], p, a,
                                     first=first_expert, valid=valid,
                                     tile=PREFILL_EXPERT_TILE)
        return x + out[None], (row, held)

    x, rows0 = jax.lax.scan(dense, x, params["seg0"])
    x, (rows1, held) = jax.lax.scan(expert_layer, x, params["seg1"])
    h = norm(x[0, logit_pos], params["final_ln"])
    return h @ params["head"].astype(h.dtype), [rows0, rows1], held


@functools.partial(jax.jit, static_argnames=("arch", "page_size",
                                             "first_expert", "use_pallas",
                                             "interpret"))
def mla_moe_decode_step(params, kv_lat, block_tables, lengths, tokens, active,
                        *, arch: ArchConfig, page_size: int,
                        first_expert: int, use_pallas: bool, interpret: bool):
    """One decode iteration for every slot (inactive ones masked), absorbed
    latent attention over the latent pool. kv_lat: per segment (L_seg,
    n_pages, page, W). Returns (logits, new kv_lat, held (expert layers,
    held experts) int32: the active slots' assignments to each held
    expert)."""
    with jax.default_matmul_precision(MLA_MOE_PRECISION):
        return _mla_moe_decode(params, kv_lat, block_tables, lengths, tokens,
                               active, arch, page_size, first_expert,
                               use_pallas, interpret)


def _mla_moe_decode(params, kv_lat, block_tables, lengths, tokens, active,
                    arch, page_size, first_expert, use_pallas, interpret):
    a, m = arch, arch.mla
    x = params["embed"][tokens].astype(jnp.float32)
    page_ids = jnp.take_along_axis(
        block_tables, (lengths // page_size)[:, None], axis=1)[:, 0]
    offs = lengths % page_size
    norm = functools.partial(rms_norm, eps=a.norm_eps, use_pallas=use_pallas,
                             interpret=interpret)

    def attention(x, p, pool):          # pool: (n_pages, page, W)
        q_nope, q_pe, row = mla.project(norm(x, p["ln1"])[:, None], p, a,
                                        lengths[:, None], norm)
        q = mla.absorb(q_nope[:, 0], q_pe[:, 0], p, a)        # (B, H, C)
        pool = pool.at[page_ids, offs].set(
            jnp.where(active[:, None], _pool_row(row[:, 0]),
                      pool[page_ids, offs]))
        o = mla_decode_attention(q, pool, block_tables, lengths + 1,
                                 value_dim=m.kv_lora_rank,
                                 scale=mla.scale(a), use_pallas=use_pallas,
                                 interpret=interpret)
        return x + mla.absorbed_output(o, p, a), pool

    def dense(x, inp):
        x, pool = attention(x, *inp)
        h = norm(x, inp[0]["ln2"])
        return x + gated_mlp(h, inp[0]["wg"], inp[0]["wu"], inp[0]["wd"],
                             a.act), pool

    def expert_layer(x, inp):
        x, pool = attention(x, *inp)
        out, held = held_experts_ffn(norm(x, inp[0]["ln2"]), inp[0], a,
                                     first=first_expert, valid=active)
        return x + out, (pool, held)

    x, pool0 = jax.lax.scan(dense, x, (params["seg0"], kv_lat[0]))
    x, (pool1, held) = jax.lax.scan(expert_layer, x,
                                    (params["seg1"], kv_lat[1]))
    x = norm(x, params["final_ln"])
    return x @ params["head"].astype(x.dtype), [pool0, pool1], held


class PagedEngine:
    """One worker's execution engine."""

    def __init__(self, arch: ArchConfig, params, cfg: EngineConfig,
                 time_fn: Callable[[], float] = time.perf_counter,
                 device: Optional[jax.Device] = None,
                 stats: Optional[ServeStats] = None):
        self.mla = arch.mla is not None
        assert arch.family in (Family.DENSE, Family.AUDIO) or (
            self.mla and arch.family == Family.MOE
            and arch.moe.n_dense_layers), \
            "engine path supports dense GQA archs and latent attention " \
            "with sparse experts after leading dense layers"
        assert not (self.mla and cfg.prefill_chunk), \
            "chunked prefill is for K/V pools"
        self.arch = arch
        self.cfg = cfg
        self.time_fn = time_fn
        self.traces = TraceBuffer()
        self.device = device if device is not None else jax.devices()[0]
        self.use_pallas = self.device.platform == "tpu" or cfg.interpret
        self.params = jax.device_put(params, self.device)
        L = arch.n_layers
        hd = arch.resolved_head_dim
        if self.mla:
            # one pool of latent rows, kept per segment of layers (dense,
            # then expert layers) as the weights are
            pages = (cfg.n_pages, cfg.page_size,
                     pool_width(arch.mla.latent_dim))
            nd = arch.moe.n_dense_layers
            self.kv_lat = [jnp.zeros((n,) + pages, jnp.float32,
                                     device=self.device)
                           for n in (nd, L - nd)]
        else:
            pool = (L, cfg.n_pages, arch.n_kv_heads, cfg.page_size, hd)
            self.kv_k = jnp.zeros(pool, jnp.float32, device=self.device)
            self.kv_v = jnp.zeros(pool, jnp.float32, device=self.device)
        self.block_tables = np.zeros((cfg.max_batch, cfg.max_pages_per_seq),
                                     np.int32)
        self.lengths = np.zeros((cfg.max_batch,), np.int32)
        self.free_pages = list(range(cfg.n_pages - 1, 0, -1))  # page 0 = null
        self.slots: List[Optional[Request]] = [None] * cfg.max_batch
        self.waiting: List[Request] = []
        self.stats = stats if stats is not None else ServeStats()
        # requests the cluster still queues after its placement pass; a
        # decode step counts its empty slots while this is nonzero
        self.backlog = 0
        kernels = dict(arch=arch, use_pallas=self.use_pallas,
                       interpret=cfg.interpret)
        if self.mla:
            kernels["first_expert"] = cfg.first_expert
            prefill, decode = mla_moe_prefill_step, mla_moe_decode_step
        else:
            prefill, decode = prefill_step, decode_step
        self._prefill_jit = functools.partial(prefill, **kernels)
        self._decode_jit = functools.partial(
            decode, page_size=cfg.page_size, **kernels)
        self._chunk_jit = jax.jit(self._chunk_fn)
        # float32 pools: K and V of every KV head, or one latent row
        self.kv_bytes_per_token = arch.kv_bytes_per_token(dtype_bytes=4)
        # optional observer of every logits row the engine samples from:
        # called as on_logits(request, logits (V,)) after prefill and after
        # each decode step
        self.on_logits: Optional[Callable[[Request, jax.Array], None]] = None

    def warmup(self, prompt_lens: Iterable[int]) -> float:
        """Compile (or load), by running once, every program serving runs
        for prompts of these lengths: the prefill program of each length's
        bucket and the decode step, each length's prompt upload and
        cache-write scatter, and the argmax of either step's logits, so
        serving never waits on the compiler. Touches no request, page or
        trace: the writes put zeros on the null page 0. Returns seconds."""
        t0 = time.perf_counter()
        lengths = sorted(set(prompt_lens))
        shapes = {}                     # each bucket's prefill outputs
        for s in sorted({prompt_bucket(n) for n in lengths}):
            out = self._prefill_jit(self.params,
                                    jnp.zeros((1, s), jnp.int32), s - 1)
            self._sample(out[0], out[2] if self.mla else None)
            shapes[s] = jax.tree.map(
                lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype), out[1:])
            del out
        null = np.zeros((self.cfg.max_pages_per_seq,), np.int32)
        for n in lengths:
            b = prompt_bucket(n)
            jnp.asarray([[0] * b])                  # the prompt's upload
            self._write_prompt(null, jax.tree.map(
                lambda t: jnp.zeros(t.shape, t.dtype, device=self.device),
                shapes[b]), n)
        b = self.cfg.max_batch
        logits, held = self._launch(np.zeros((b,), np.int64),
                                    np.zeros((b,), bool))   # all masked
        self._sample(logits, held)
        jax.block_until_ready(self._pools())
        return time.perf_counter() - t0

    def programs(self, prompt_len: int) -> dict:
        """The prefill program of ``prompt_len``'s bucket and the decode
        step, lowered at this engine's shapes (for the Pallas kernels they
        call)."""
        s = prompt_bucket(prompt_len)
        b = self.cfg.max_batch
        return {
            "prefill": self._prefill_jit.func.lower(
                self.params, jnp.zeros((1, s), jnp.int32), s - 1,
                **self._prefill_jit.keywords),
            "decode": self._decode_jit.func.lower(
                self.params, *self._pools(), jnp.asarray(self.block_tables),
                jnp.asarray(self.lengths), jnp.zeros((b,), jnp.int32),
                jnp.zeros((b,), bool), **self._decode_jit.keywords)}

    def _pools(self) -> tuple:
        """The cache the decode step takes and returns."""
        return (self.kv_lat,) if self.mla else (self.kv_k, self.kv_v)

    def _launch(self, tokens, active):
        """Dispatch one decode step and keep its pools. Returns (logits,
        the held experts' assignments (expert layers, held) or None)."""
        args = (jnp.asarray(self.block_tables), jnp.asarray(self.lengths),
                jnp.asarray(tokens), jnp.asarray(active))
        if self.mla:
            logits, self.kv_lat, held = self._decode_jit(
                self.params, self.kv_lat, *args)
            return logits, held
        logits, self.kv_k, self.kv_v = self._decode_jit(
            self.params, self.kv_k, self.kv_v, *args)
        return logits, None

    @staticmethod
    def _sample(logits, held):
        """Argmax of the logits, read back with the held experts' counts
        in one transfer."""
        nxt = jnp.argmax(logits, -1)
        if held is None:
            return np.asarray(nxt), None
        return jax.device_get((nxt, held))

    def _count_experts(self, sp, held: np.ndarray, tokens: int) -> None:
        """Expert counters of one step, and its span's ``held``,
        ``held_max`` stats."""
        self.stats.expert_assignments += \
            tokens * self.arch.moe.top_k * held.shape[0]
        n, busiest = int(held.sum()), int(held.max(axis=1).sum())
        self.stats.held_assignments += n
        self.stats.held_expert_max += busiest
        sp.set_metadata(held=n, held_max=busiest)

    # ---- admission / state --------------------------------------------------
    def can_admit(self, n_tokens_total: int) -> bool:
        pages_needed = n_tokens_total // self.cfg.page_size + 2
        return (any(s is None for s in self.slots)
                and len(self.free_pages) >= pages_needed)

    def submit(self, req: Request) -> None:
        self.waiting.append(req)

    @property
    def running(self) -> List[Request]:
        return [r for r in self.slots if r is not None]

    # ---- page management ----------------------------------------------------
    def _alloc_slot(self, req: Request, n_tokens: int) -> int:
        slot = self.slots.index(None)
        pages = (n_tokens + self.cfg.page_size - 1) // self.cfg.page_size
        assert len(self.free_pages) >= pages
        tbl = np.zeros((self.cfg.max_pages_per_seq,), np.int32)
        for j in range(pages):
            tbl[j] = self.free_pages.pop()
        self.block_tables[slot] = tbl
        self.lengths[slot] = 0
        self.slots[slot] = req
        return slot

    def _ensure_page(self, slot: int) -> bool:
        pos = int(self.lengths[slot])
        pi = pos // self.cfg.page_size
        if pi >= self.cfg.max_pages_per_seq:
            return False
        if self.block_tables[slot, pi] == 0:
            if not self.free_pages:
                return False
            self.block_tables[slot, pi] = self.free_pages.pop()
        return True

    def _free_slot(self, slot: int) -> None:
        for pid in self.block_tables[slot]:
            if pid > 0:
                self.free_pages.append(int(pid))
        self.block_tables[slot] = 0
        self.lengths[slot] = 0
        self.slots[slot] = None

    # ---- iteration-level scheduling -----------------------------------------
    def step(self, now: Optional[float] = None) -> List[Request]:
        """Run ONE iteration (a prefill batch or a decode batch). Returns the
        requests that finished."""
        finished: List[Request] = []
        with span("serve.step"):
            t0 = self.time_fn()
            if self.waiting and self.can_admit(self.waiting[0].l_in + 8):
                total_in, batch = 0, []
                while self.waiting and \
                        self.can_admit(self.waiting[0].l_in + 8):
                    r = self.waiting.pop(0)
                    batch.append(r)
                    total_in += r.l_in
                    self._run_prefill(r)
                t1 = self.time_fn()
                self.traces.record_prefill(total_in, t1 - t0)
                for r in batch:
                    r.t_first_token = now if now is not None else t1
                    r.state = ReqState.DECODING
                return finished
            if all(r is None for r in self.slots):
                return finished
            b = self.cfg.max_batch
            with span("serve.decode") as sp:
                with span("serve.pages"):
                    active_slots, preempted = [], 0
                    for i, r in enumerate(self.slots):
                        if r is None:
                            continue
                        if self._ensure_page(i):
                            active_slots.append(i)
                        else:                   # out of pages: preempt it
                            self._free_slot(i)
                            r.l_out = 0
                            r.state = ReqState.QUEUED
                            self.waiting.insert(0, r)
                            preempted += 1
                n = len(active_slots)
                empty = b - n if n and self.backlog else 0
                # the pages the decode kernel reads: each slot's context
                # with the token this step writes
                ps = self.cfg.page_size
                pages = int(((self.lengths[active_slots] + ps) // ps).sum())
                sp.set_metadata(active=n, slots=b, preempted=preempted,
                                empty=empty, pages=pages)
                self.stats.preemptions += preempted
                if not n:
                    return finished
                with span("serve.launch"):
                    tokens = np.zeros((b,), np.int64)
                    for i in active_slots:
                        tokens[i] = self.slots[i].tokens[-1]
                    active = np.zeros((b,), bool)
                    active[active_slots] = True
                    logits, held = self._launch(tokens, active)
                with span("serve.sample"):
                    nxt, held = self._sample(logits, held)
                t1 = self.time_fn()
                if held is not None:
                    self._count_experts(sp, held, n)
                with span("serve.bookkeep"):
                    if self.on_logits is not None:
                        for i in active_slots:
                            self.on_logits(self.slots[i], logits[i])
                    total_ctx = int(self.lengths[active_slots].sum()) + n
                    self.traces.record_decode(n, total_ctx, t1 - t0)
                    for i in active_slots:
                        r = self.slots[i]
                        self.lengths[i] += 1
                        r.l_out += 1
                        r.t_decode_spent += (t1 - t0)
                        r.tokens.append(int(nxt[i]))
                        self.traces.record_kv(
                            r.context,
                            r.context * self.kv_bytes_per_token / 2)
                        if r.l_out >= min(r.l_real or self.cfg.max_new_tokens,
                                          self.cfg.max_new_tokens):
                            r.state = ReqState.FINISHED
                            r.t_finish = now if now is not None else t1
                            finished.append(r)
                            self._free_slot(i)
                self.stats.decode_steps += 1
                self.stats.tokens_out += n
                self.stats.empty_slot_steps += empty
                self.stats.decode_kv_pages += pages
        return finished

    def _chunk_fn(self, params, chunk_toks, k_ctx, v_ctx, ctx_len,
                  logit_pos):
        """One chunked-prefill step: chunk tokens attend to the gathered
        context KV (q_offset = ctx) + causally within the chunk.
        Returns (logits at logit_pos, chunk ks, vs: (L, C, Hkv, hd))."""
        import math as _m
        from repro.kernels.flash_attention import flash_attention_ref
        a = self.arch
        hd = a.resolved_head_dim
        x = params["embed"][chunk_toks].astype(jnp.float32)[None]  # (1,C,D)
        if a.tie_embeddings:
            x = x * _m.sqrt(a.d_model)
        c = x.shape[1]
        positions = ctx_len + jnp.arange(c)
        ks_out, vs_out = [], []
        norm = functools.partial(rms_norm, eps=a.norm_eps,
                                 use_pallas=self.use_pallas,
                                 interpret=self.cfg.interpret)
        for i in range(a.n_layers):
            p = jax.tree.map(lambda t: t[i], params["seg0"])
            h = norm(x, p["ln1"])
            q = (h @ p["wq"]).reshape(1, c, a.n_heads, hd)
            k = (h @ p["wk"]).reshape(1, c, a.n_kv_heads, hd)
            v = (h @ p["wv"]).reshape(1, c, a.n_kv_heads, hd)
            if a.qkv_bias:
                q = q + p["bq"].reshape(a.n_heads, hd)
                k = k + p["bk"].reshape(a.n_kv_heads, hd)
                v = v + p["bv"].reshape(a.n_kv_heads, hd)
            if a.pos_emb == PosEmb.ROPE:
                q = rope(q, positions, a.rope_theta)
                k = rope(k, positions, a.rope_theta)
            ks_out.append(k[0])
            vs_out.append(v[0])
            k_all = jnp.concatenate([k_ctx[i][None], k], axis=1)
            v_all = jnp.concatenate([v_ctx[i][None], v], axis=1)
            kv_len = (ctx_len + c) * jnp.ones((1,), jnp.int32)
            att = flash_attention_ref(q, k_all, v_all, causal=True,
                                      q_offset=ctx_len, kv_len=kv_len)
            x = x + att.reshape(1, c, -1) @ p["wo"]
            h = norm(x, p["ln2"])
            x = x + gated_mlp(h, p["wg"], p["wu"], p["wd"], a.act)
        x = norm(x, params["final_ln"])
        head = params["embed"].T if a.tie_embeddings else params["head"]
        logits = x[0, logit_pos] @ head.astype(x.dtype)
        return logits, jnp.stack(ks_out), jnp.stack(vs_out)

    def _gather_ctx_kv(self, slot: int, ctx: int):
        """Contiguous (L, ctx_pad, Hkv, hd) copies of this slot's pages."""
        n_pages = (ctx + self.cfg.page_size - 1) // self.cfg.page_size
        n_pages = max(n_pages, 1)
        pages = self.block_tables[slot][:n_pages]

        def gather(pool):       # (L, n, Hkv, page, hd) -> (L, n*page, Hkv, hd)
            return pool[:, pages].swapaxes(2, 3).reshape(
                self.arch.n_layers, n_pages * self.cfg.page_size,
                self.arch.n_kv_heads, -1)
        return gather(self.kv_k), gather(self.kv_v)

    def _token_slots(self, table, start: int, n: int):
        """(page, offset) of positions start .. start + n - 1 under a
        block-table row."""
        pos = np.arange(start, start + n)
        return table[pos // self.cfg.page_size], pos % self.cfg.page_size

    def _write_kv(self, slot: int, start: int, ks, vs) -> None:
        """ks, vs: (L, n, Hkv, hd) for positions start .. start + n - 1."""
        self._scatter_kv(self.block_tables[slot], start, ks, vs)

    def _scatter_kv(self, table, start: int, ks, vs) -> None:
        pages, offs = self._token_slots(table, start, ks.shape[1])
        # the two index arrays straddle a slice, so the indexed view is
        # (n, L, Hkv, hd): token-major
        self.kv_k = self.kv_k.at[:, pages, :, offs].set(
            ks.swapaxes(0, 1).astype(self.kv_k.dtype))
        self.kv_v = self.kv_v.at[:, pages, :, offs].set(
            vs.swapaxes(0, 1).astype(self.kv_v.dtype))

    def _write_prompt(self, table, rows, n: int) -> None:
        """A prefill's cache of its first ``n`` positions into the pages of
        ``table``: K and V (L, S, Hkv, hd), or latent rows per segment
        [(L_seg, S, W)]."""
        if not self.mla:
            ks, vs = rows
            self._scatter_kv(table, 0, ks[:, :n], vs[:, :n])
            return
        pages, offs = self._token_slots(table, 0, n)
        self.kv_lat = [_scatter_latent(pool, r, pages, offs, n=n)
                       for pool, r in zip(self.kv_lat, rows[0])]

    def _run_prefill(self, req: Request) -> None:
        s = req.l_in
        cchunk = self.cfg.prefill_chunk
        chunked = bool(cchunk) and s > cchunk
        if req.t_prefill_start is None:
            req.t_prefill_start = self.time_fn()
            if req.t_submit is not None:
                self.stats.queue_wait_s += req.t_prefill_start - req.t_submit
                self.stats.queue_waits += 1
        with span("serve.prefill", req=req.id, tokens=s,
                  bucket=prompt_bucket(cchunk if chunked else s)) as sp:
            slot = self._alloc_slot(req, s + 8)
            toks = list(req.tokens[:s]) if req.tokens else \
                list(np.random.default_rng(req.id).integers(
                    2, self.arch.vocab, s))
            req.tokens = [int(t) for t in toks]
            if chunked:
                # Sarathi-style: process the prompt in fixed-size chunks,
                # each attending to the already-written context pages
                logits = None
                done = 0
                while done < s:
                    n = min(cchunk, s - done)
                    bucket = prompt_bucket(n)
                    chunk = toks[done:done + n] + [0] * (bucket - n)
                    with span("serve.prefill_program"):
                        k_ctx, v_ctx = self._gather_ctx_kv(slot,
                                                           max(done, 1))
                        # slice to exactly the valid context so chunk
                        # positions in the concatenated KV line up with
                        # their logical positions
                        logits, ks, vs = self._chunk_jit(
                            self.params, jnp.asarray(chunk),
                            k_ctx[:, :done], v_ctx[:, :done], done, n - 1)
                    with span("serve.write_kv"):
                        self._write_kv(slot, done, ks[:, :n], vs[:, :n])
                    done += n
            else:
                bucket = prompt_bucket(s)
                padded = toks + [0] * (bucket - s)
                with span("serve.prefill_program"):
                    logits, *rows = self._prefill_jit(
                        self.params, jnp.asarray([padded]), s - 1)
                with span("serve.write_kv"):
                    self._write_prompt(self.block_tables[slot], rows, s)
            self.lengths[slot] = s
            with span("serve.first_token"):
                if self.on_logits is not None:
                    self.on_logits(req, logits)
                first, held = self._sample(
                    logits, rows[1] if self.mla and not chunked else None)
                req.tokens.append(int(first))
            if held is not None:
                self._count_experts(sp, held, s)
        req.l_out = 1      # the prefill emits the first token (TTFT)
        self.stats.prefills += 1
        self.stats.prompt_tokens += s
        self.stats.tokens_out += 1
