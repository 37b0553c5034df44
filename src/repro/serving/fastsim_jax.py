"""Compiled colocated simulation core (the ``engine="jax"`` path).

The whole heartbeat loop — admission, FIFO placement, per-worker prefill /
decode-segment advancement — runs as ONE ``jax.jit``-compiled
``lax.while_loop`` over beats, with the per-worker advance ``vmap``-ped
across the fleet and (for ``optimize``) the entire simulation ``vmap``-ped
across a batch of candidate worker counts, so a whole bracket of the
binary search evaluates in a single compiled call
(:func:`run_candidate_batch`).

Scope: this is the throughput engine, not the oracle. It compiles the
semantics of :mod:`repro.serving.fastsim` (itself bit-for-bit against the
Python reference) for the whole colocated envelope: ``aladdin``/``jsq``/
``po2`` placement, live KV pressure (constraint-(e) peak admission,
overflow preemption, FIFO resume), and fixed or policy-scaled fleets with
or without a spot market. Two compiled cores share the lane layout:

* the *legacy* whole-trace kernel (``_make_simulate``) — inert-KV, fixed
  ``aladdin``/``jsq`` fleets, the original single-``while_loop`` path that
  ``run_candidate_batch`` vmaps across fleet sizes;
* the *chunked* kernel (``_make_chunk``) — everything else. The host
  splits the beat grid at fleet-mutation boundaries (scaling epochs, boot
  completions, market events, notice deadlines) and runs each
  fixed-fleet-configuration span as one compiled call; between chunks the
  REAL :class:`repro.serving.forecast.ManagedPool` /
  :class:`repro.serving.lifecycle.WorkerLifecycle` state machines make
  every boot/drain/kill decision on numpy mirrors of the lane state (so
  reclaim victim draws consume the same numpy Generator stream as the
  reference). Fleet membership enters the kernel as **lane activation
  masks**: per-lane ``mode`` (off / online / draining) plus serving-order
  ``rank`` arrays, rebuilt host-side per chunk. Lane rows stay resident
  across chunks — scaling never bulk-scatters per beat; a booted or
  recycled lane costs one O(B) row reset at the boundary.

Performance contract: the beat body touches only O(W·B) lane-resident
state (request clocks live in per-worker row arrays, not in trace-sized
arrays), because on CPU XLA a bulk scatter into a trace-sized carry costs
~50 ns *per update element* per beat while single-element updates and
fused masked reductions are ~0.1 µs. In the legacy kernel, finished rows
are drained into the per-request output arrays one finisher at a time (a
few per beat); the still-running remainder is flushed with one bulk
scatter after the loop. The chunked kernel goes further: its while-loop
carry holds NO trace-sized array at all. Finished rows park in their
slot as state 5 (finished, undrained) and the host fans them out from
the returned row arrays between chunks; the (n,)-sized re-entrant sinks
are read-only loop operands; the admission queue is host-presized per
chunk from the arrival trace. The lean carry is what makes the vmapped
candidate batch viable — under ``vmap``, the batched-``while_loop``
masking rule re-selects every carried byte on every iteration of every
nested loop, so each candidate pays the carry size each beat.
KV-preempted rows likewise park in the lane (slot-state 3) rather than
in any trace-sized structure, and the only trace-sized arrays the beat
body touches are single-element ``.at[rid]`` gathers against the sink
operands at placement and kill boundaries.

Numerics: each request's clock arithmetic keeps the reference's
*sequential* add order (decode segments advance through an inner
``while_loop`` of dependent adds on lane-local rows). Worker aggregates
(context sums, batch counts) are reduced in slot order rather than
admission order — exact anyway, because they are sums of integers (and
integer multiples of ``gamma``) well below 2^52. XLA may still contract
multiply-add chains, so agreement with the oracle is to the last few ulps
rather than bit-for-bit — the equivalence tests pin the integer outputs
exactly and the float outputs at ``rtol=1e-12``.

``jax`` is an optional dependency: importing this module without it
raises ``ImportError`` (``api.run`` only imports it on ``engine="jax"``).
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.request import ReqState
from repro.serving.fastsim import (DEFAULT_TAIL, check_colocated_envelope,
                                   check_trace_session_free)

_BIG_I = 1 << 50


def check_jax_envelope(scenario) -> List:
    """The vectorized-engine envelope (the compiled cores now cover all of
    it: live KV, po2, policy-scaled fleets, spot markets). po2 placement
    draws from the jax PRNG instead of the reference's numpy Generator
    stream, so po2 cells are deterministic but only tolerance-comparable
    to the other engines; everything else tracks the reference within the
    pinned equivalence tolerances."""
    specs = check_colocated_envelope(scenario)
    for s in specs:
        if s.kv_capacity <= 0:
            raise ValueError("kv_capacity must be positive")
    market = scenario.market
    if market is not None and market.spec is not None \
            and market.spec.kv_capacity <= 0:
        raise ValueError("kv_capacity must be positive")
    return specs


def _legacy_ok(scenario, specs) -> bool:
    """True when the original whole-trace kernel applies (fixed fleet, no
    market, inert KV, aladdin/jsq) — the fast path ``run_candidate_batch``
    vmaps across fleet sizes."""
    from repro.serving import api

    return (isinstance(scenario.scaling, api.FixedScale)
            and scenario.market is None
            and scenario.topology.policy in ("aladdin", "jsq")
            and all(s.perf.kv.h == 0.0 and s.perf.kv.j == 0.0
                    for s in specs))


# ---- the compiled kernel -----------------------------------------------------


def _advance_lane(t0, active0, started0, li, lr, lo, tds, tf1, tfn,
                  k1, c1, k2, c2, c3, t_end):
    """One worker's ``advance_to(t_end)``: alternate prefill / decode
    segments until the local clock reaches the beat end. Membership is a
    pair of masks (``active`` rows hold a request; ``started`` ones have
    been prefilled) so finished slots become reusable holes without any
    compaction; all row state is lane-local, keeping every request's
    sequential add order. vmapped across the fleet."""

    def cond(st):
        return st[0] < t_end

    def body(st):
        t, active, started, lo, tds, tf1, tfn = st
        newm = active & ~started
        has_new = jnp.any(newm)
        n_on = jnp.sum(active & started)
        # --- prefill branch: joint new-batch prefill, decode stalls -------
        tot_in = jnp.sum(jnp.where(newm, li, 0))
        dur_p = k1 * tot_in + c1
        t_pre = t + dur_p
        tds_pre = tds + jnp.where(active & started, dur_p, 0.0)
        tf1_pre = jnp.where(newm, t_pre, tf1)
        lo_pre = jnp.where(newm, jnp.int64(1), lo)
        # --- decode branch: batch fixed until the next finish boundary ----
        do_dec = ~has_new & (n_on > 0)
        b = n_on
        C0 = jnp.sum(jnp.where(active, li + lo, 0))
        n_fin = jnp.min(jnp.where(active, jnp.maximum(lr - lo, 1), _BIG_I))
        n_fin = jnp.where(do_dec, n_fin, 0)
        cb = c2 * b

        def dcond(dst):
            k, td, _seg = dst
            return (k < n_fin) & (td < t_end)

        def dbody(dst):
            k, td, seg = dst
            dur = k2 * (C0 + k * b) + cb + c3
            return k + 1, td + dur, seg + dur

        k, t_dec, seg = lax.while_loop(
            dcond, dbody, (jnp.int64(0), t, jnp.float64(0.0)))
        lo_dec = lo + jnp.where(active, k, 0)
        tds_dec = tds + jnp.where(active, seg, 0.0)
        done = active & (lo_dec >= lr)
        tfn_dec = jnp.where(done, t_dec, tfn)
        # --- select: prefill > decode > idle ------------------------------
        t_new = jnp.where(has_new, t_pre, jnp.where(do_dec, t_dec, t_end))
        return (t_new,
                jnp.where(has_new, active, active & ~done),
                jnp.where(has_new, active, started),
                jnp.where(has_new, lo_pre,
                          jnp.where(do_dec, lo_dec, lo)),
                jnp.where(has_new, tds_pre,
                          jnp.where(do_dec, tds_dec, tds)),
                jnp.where(has_new, tf1_pre, tf1),
                jnp.where(do_dec, tfn_dec, tfn))

    return lax.while_loop(cond, body,
                          (t0, active0, started0, lo, tds, tf1, tfn))


def _make_simulate(n: int, W: int, B: int, hb: float, horizon: float,
                   theta: float, gamma: float, ttft: float, atgt: float,
                   policy: str,
                   coefs: Tuple[Tuple[float, ...], ...],
                   maxb: Tuple[int, ...],
                   maxb_norm: Tuple[float, ...],
                   cmax_norm: Tuple[float, ...],
                   edf: bool = False, tagged: bool = False):
    """Close over the static configuration and return the whole-trace
    simulation ``fn(arrival, l_in, l_real, n_active, rank_r, ttft_r,
    atgt_r)`` (jit/vmap-able). ``rank_r``/``ttft_r``/``atgt_r`` are
    read-only per-request operands for multi-tenant scenarios: ``rank_r``
    is the host-computed total queue order (priority desc, deadline asc,
    arrival index) that ``edf=True`` sorts the admission queue by each
    beat, and the raw per-request SLO budgets drive the tagged
    constraint-(b)/(c)/(d) math when ``tagged=True`` (``inf`` falls back
    to the planning SLO, like the reference). With both flags False the
    operands are ignored and the compiled graph is unchanged."""
    K1, C1, K2, C2, C3 = (jnp.asarray(c) for c in coefs)
    MAXB = jnp.asarray(maxb, dtype=jnp.int64)
    MAXBN = jnp.asarray(maxb_norm)
    CMAXN = jnp.asarray(cmax_norm)
    is_aladdin = policy == "aladdin"
    tag_a = tagged and is_aladdin

    def simulate(arrival, l_in, l_real, n_active, rank_r, ttft_r, atgt_r):
        alive = jnp.arange(W) < n_active

        def place_pass(qlen, q, mem, active, started, lane_li, lane_lr,
                       lane_lo, lane_tds, lane_tf1, lane_tfn):
            on = active & started
            if is_aladdin:
                # constraint (d) slack over *ongoing* members: fixed for
                # the whole pass (placement only adds new_batch entries)
                slack = jnp.min(jnp.where(
                    on, atgt * jnp.maximum(lane_lo - 1, 0) - lane_tds,
                    jnp.inf), axis=1)
                d_budget = theta * jnp.maximum(slack, 0.0)
                if tag_a:
                    # per-member budgets: each ongoing row's own tenant
                    # ATGT (inf -> planning SLO), selected per candidate
                    am = atgt_r[mem]
                    am = jnp.where(jnp.isinf(am), atgt, am)
                    slack_t = jnp.min(jnp.where(
                        on, am * jnp.maximum(lane_lo - 1, 0) - lane_tds,
                        jnp.inf), axis=1)
                    d_budget_t = theta * jnp.maximum(slack_t, 0.0)
            else:
                d_budget = jnp.zeros(W)
            if tag_a:
                # running raw-budget mins over members (b: ongoing + new
                # batch; c: new batch only), updated as placements land
                amin0 = jnp.min(jnp.where(active, atgt_r[mem], jnp.inf),
                                axis=1)
                tmin0 = jnp.min(jnp.where(active & ~started,
                                          ttft_r[mem], jnp.inf), axis=1)
            # l_pred == l_real inside the envelope (no predictor); sums of
            # integers (x gamma), so slot order cannot perturb them
            wctx0 = jnp.sum(jnp.where(
                active, lane_li + gamma * lane_lr, 0.0), axis=1)
            newsum0 = jnp.sum(jnp.where(active & ~started, lane_li, 0),
                              axis=1)
            cnt0 = jnp.sum(active, axis=1)

            def pbody(st):
                (i, keep, q, mem, active, started, lane_li, lane_lr,
                 lane_lo, lane_tds, lane_tf1, lane_tfn, cnt, newsum,
                 wctx) = st[:15]
                if tag_a:
                    amin, tmin = st[15], st[16]
                rid = q[i]
                liv = l_in[rid]
                lrv = l_real[rid]
                v = liv + gamma * lrv
                bpost = cnt + 1
                if is_aladdin:
                    if tag_a:
                        # an untagged candidate takes the scalar branch
                        # even among tagged members (reference _tagged)
                        ct = jnp.isfinite(atgt_r[rid])
                        a0 = jnp.minimum(amin, atgt_r[rid])
                        a_eff = jnp.where(
                            ct, jnp.where(jnp.isinf(a0), atgt, a0), atgt)
                        t0_ = jnp.minimum(tmin, ttft_r[rid])
                        t_eff = jnp.where(
                            ct, jnp.where(jnp.isinf(t0_), ttft, t0_),
                            ttft)
                        d_eff = jnp.where(ct, d_budget_t, d_budget)
                    else:
                        a_eff, t_eff, d_eff = atgt, ttft, d_budget
                    budget = jnp.where(
                        K2 > 0,
                        jnp.maximum(((a_eff - C3) - C2 * bpost)
                                    / jnp.where(K2 > 0, K2, 1.0), 0.0),
                        jnp.inf)
                    pre_t = K1 * (newsum + liv) + C1
                    ok = ((bpost <= MAXB)
                          & (wctx + v <= theta * budget)
                          & (pre_t <= t_eff) & (pre_t <= d_eff) & alive)
                    # best-fit: max capacity_norm, ties to the lowest index
                    # (argmax returns the first maximum, like stable sort)
                    norm = jnp.hypot(cnt / MAXBN, wctx / CMAXN)
                    w = jnp.argmax(jnp.where(ok, norm, -jnp.inf))
                else:
                    # jsq: min batch, ties to the lowest index; inert KV
                    # makes _admit_naive's occupancy test vacuous
                    ok = (bpost <= MAXB) & alive
                    w = jnp.argmin(jnp.where(ok, cnt, _BIG_I))
                placed = jnp.any(ok)
                # placed implies cnt[w] < max_batch <= B, so the row has a
                # hole; out-of-range updates drop, so B is a safe no-op
                wslot = jnp.where(placed, jnp.argmin(active[w]), B)
                mem = mem.at[w, wslot].set(rid, mode="drop")
                active = active.at[w, wslot].set(True, mode="drop")
                started = started.at[w, wslot].set(False, mode="drop")
                lane_li = lane_li.at[w, wslot].set(liv, mode="drop")
                lane_lr = lane_lr.at[w, wslot].set(lrv, mode="drop")
                lane_lo = lane_lo.at[w, wslot].set(0, mode="drop")
                lane_tds = lane_tds.at[w, wslot].set(0.0, mode="drop")
                lane_tf1 = lane_tf1.at[w, wslot].set(jnp.nan, mode="drop")
                lane_tfn = lane_tfn.at[w, wslot].set(jnp.nan, mode="drop")
                cnt = cnt.at[w].add(jnp.where(placed, 1, 0))
                newsum = newsum.at[w].add(jnp.where(placed, liv, 0))
                wctx = wctx.at[w].add(jnp.where(placed, v, 0.0))
                # unplaced requests stay queued, FIFO order preserved
                qslot = jnp.where(placed, jnp.int64(n), keep)
                q = q.at[qslot].set(rid, mode="drop")
                keep = keep + jnp.where(placed, 0, 1)
                out = (i + 1, keep, q, mem, active, started, lane_li,
                       lane_lr, lane_lo, lane_tds, lane_tf1, lane_tfn,
                       cnt, newsum, wctx)
                if tag_a:
                    amin = amin.at[w].min(
                        jnp.where(placed, atgt_r[rid], jnp.inf))
                    tmin = tmin.at[w].min(
                        jnp.where(placed, ttft_r[rid], jnp.inf))
                    out = out + (amin, tmin)
                return out

            st0p = (jnp.int64(0), jnp.int64(0), q, mem, active, started,
                    lane_li, lane_lr, lane_lo, lane_tds, lane_tf1,
                    lane_tfn, cnt0, newsum0, wctx0)
            if tag_a:
                st0p = st0p + (amin0, tmin0)
            st = lax.while_loop(lambda st: st[0] < qlen, pbody, st0p)
            return st[1:12]

        def beat_body(st):
            (t, idx, qlen, q, mem, active, started, t_w, lane_li, lane_lr,
             lane_lo, lane_tds, lane_tf1, lane_tfn, out_lo, out_tds,
             out_tf1, out_tfn, beats) = st

            # admit arrivals <= t (the trace is sorted by arrival)
            def adm_body(ast):
                i2, qlen2, q2 = ast
                return i2 + 1, qlen2 + 1, q2.at[qlen2].set(i2)

            idx, qlen, q = lax.while_loop(
                lambda ast: (ast[0] < n) & (arrival[ast[0]] <= t),
                adm_body, (idx, qlen, q))
            if edf:
                # priority-then-EDF admission order: sort the backlog by
                # the host-computed total rank (stable because ranks are
                # unique); entries past qlen sort to the tail
                ii = jnp.arange(q.shape[0])
                keys = jnp.where(ii < qlen, rank_r[q], _BIG_I)
                q = jnp.take(q, jnp.argsort(keys))
            (qlen, q, mem, active, started, lane_li, lane_lr, lane_lo,
             lane_tds, lane_tf1, lane_tfn) = place_pass(
                qlen, q, mem, active, started, lane_li, lane_lr, lane_lo,
                lane_tds, lane_tf1, lane_tfn)
            # Event skip: with an empty queue, placement is a no-op at
            # every beat until the next arrival is admitted, and decode
            # segments continue across beat boundaries unchanged (lane
            # clocks persist and overshoot; segments end at finishes, not
            # beats).  So step the beat clock with the *same sequential
            # t += hb adds* as stepwise execution (bit-identical grid)
            # until the first beat whose admission check would fire, and
            # cover the whole gap with one advance call.  A backlogged
            # queue forces single-beat stepping, because placement must
            # retry every beat.
            can_skip = qlen == 0
            next_arr = jnp.where(idx < n,
                                 arrival[jnp.minimum(idx, n - 1)], jnp.inf)

            def jcond(jst):
                j, tt = jst
                return ((tt < horizon) & (tt < next_arr)
                        & ((j == 0) | can_skip))

            k_steps, t_next = lax.while_loop(
                jcond, lambda jst: (jst[0] + 1, jst[1] + hb),
                (jnp.int64(0), t))
            # advance every worker on its lane-resident rows
            pre_active = active
            t_w, active, started, lane_lo, lane_tds, lane_tf1, lane_tfn = \
                jax.vmap(_advance_lane,
                         in_axes=(0,) * 14 + (None,))(
                    t_w, active, started, lane_li, lane_lr, lane_lo,
                    lane_tds, lane_tf1, lane_tfn, K1, C1, K2, C2, C3,
                    t_next)
            # drain this step's finishers into the per-request outputs one
            # at a time (bulk scatters into trace-sized arrays are the
            # dominant cost on CPU XLA; single-element updates are free)
            fin = pre_active & ~active

            def ext_body(_j, es):
                fm, o_lo, o_tds, o_tf1, o_tfn, mf = es
                fl = jnp.argmax(fm.reshape(-1))
                w, s = fl // B, fl % B
                rid = mem[w, s]
                o_lo = o_lo.at[rid].set(lane_lo[w, s])
                o_tds = o_tds.at[rid].set(lane_tds[w, s])
                o_tf1 = o_tf1.at[rid].set(lane_tf1[w, s])
                o_tfn = o_tfn.at[rid].set(lane_tfn[w, s])
                mf = jnp.maximum(mf, lane_tfn[w, s])
                return fm.at[w, s].set(False), o_lo, o_tds, o_tf1, o_tfn, mf

            _fm, out_lo, out_tds, out_tf1, out_tfn, maxfin = lax.fori_loop(
                0, jnp.sum(fin), ext_body,
                (fin, out_lo, out_tds, out_tf1, out_tfn, -jnp.inf))
            # Stepwise execution exits once the last request finishes; the
            # final drain jump runs all the way to the horizon, so clamp
            # its beat count to the last finish (exact to within the final
            # decode segment's span -- nothing downstream consumes beats
            # beyond the benchmark rate).
            emptied = ~jnp.any(active)
            k_fin = jnp.ceil((maxfin - t) / hb).astype(jnp.int64)
            k_used = jnp.where((idx >= n) & emptied & (k_steps > 1),
                               jnp.clip(k_fin, 1, k_steps), k_steps)
            return (t_next, idx, qlen, q, mem, active, started, t_w,
                    lane_li, lane_lr, lane_lo, lane_tds, lane_tf1,
                    lane_tfn, out_lo, out_tds, out_tf1, out_tfn,
                    beats + k_used)

        def beat_cond(st):
            t, idx, qlen, active = st[0], st[1], st[2], st[5]
            drained = (idx >= n) & (qlen == 0) & ~jnp.any(active)
            return (t < horizon) & ~drained

        st0 = (jnp.float64(0.0), jnp.int64(0), jnp.int64(0),
               jnp.zeros((max(n, 1),), dtype=jnp.int64),
               jnp.full((W, B), -1, dtype=jnp.int64),
               jnp.zeros((W, B), dtype=bool),
               jnp.zeros((W, B), dtype=bool),
               jnp.zeros((W,)),
               jnp.zeros((W, B), dtype=jnp.int64),
               jnp.zeros((W, B), dtype=jnp.int64),
               jnp.zeros((W, B), dtype=jnp.int64),
               jnp.zeros((W, B)),
               jnp.full((W, B), jnp.nan), jnp.full((W, B), jnp.nan),
               jnp.zeros((n,), dtype=jnp.int64),
               jnp.zeros((n,)),
               jnp.full((n,), jnp.nan), jnp.full((n,), jnp.nan),
               jnp.int64(0))
        st = lax.while_loop(beat_cond, beat_body, st0)
        mem, active = st[4], st[5]
        lane_lo, lane_tds, lane_tf1, lane_tfn = st[10], st[11], st[12], \
            st[13]
        out_lo, out_tds, out_tf1, out_tfn, beats = st[14], st[15], st[16], \
            st[17], st[18]
        # flush still-running rows (partial clocks) once, after the loop
        sink = jnp.where(active, mem, n).reshape(-1)
        out_lo = out_lo.at[sink].set(lane_lo.reshape(-1), mode="drop")
        out_tds = out_tds.at[sink].set(lane_tds.reshape(-1), mode="drop")
        out_tf1 = out_tf1.at[sink].set(lane_tf1.reshape(-1), mode="drop")
        out_tfn = out_tfn.at[sink].set(lane_tfn.reshape(-1), mode="drop")
        return out_lo, out_tds, out_tf1, out_tfn, beats

    return simulate


# ---- the chunked kernel (live KV / po2 / pooled fleets) ---------------------
#
# Slot states (``sst``): 0 empty, 1 placed awaiting prefill, 2 ongoing,
# 3 KV-preempted (parked in-lane), 4 popped for resume (transient within one
# advance iteration), 5 finished but not yet drained to the host output
# mirrors (slots are only recycled between chunks — every mask below is an
# equality test, so 5 behaves like empty for placement aggregates while
# still blocking the slot). Row ordering is carried by three per-slot
# counters:
# ``rnsq`` (global placement sequence — new-batch list order), ``rjsq``
# (lane join sequence — the reference's ongoing-list append order, which
# decides the KV-evict victim tie-break), ``rpsq`` (lane preemption
# sequence — FIFO resume order and kill extraction order).


def _advance_lane_kv(t0, t_start, t_end, sst0, rli, rlr, rnsq, rarr, lo0,
                     tds0, tf10, tpe0, tfn0, jsq0, psq0, jc0, pc0,
                     k1, c1, k2, c2, c3, h, jv, M):
    """One worker's ``advance_to(t_end)`` with the full KV semantics of
    ``fastsim._Engine._advance``: FIFO head-blocking resume against the
    pre-pop occupancy, joint prefill (new batch + resumed victims, stalls
    charged to everyone else), KV-overflow eviction of the youngest
    arrival, and decode segments that break on finish/overflow/beat end.
    All state is lane-resident; vmapped across the fleet."""
    resume_thr = 0.9 * M
    # a lane that sat booting/idle clamps to the beat start before any
    # pending work runs (the reference's advance_to t_start clamp)
    t_in = jnp.where(jnp.any((sst0 == 1) | (sst0 == 3)) & (t0 < t_start)
                     & (t0 < t_end), t_start, t0)

    def cond(st):
        return st[0] < t_end

    def body(st):
        t, sst, lo, tds, tf1, tpe, tfn, jsq, psq, jc, pc = st
        on0 = sst == 2
        n_on = jnp.sum(on0)
        base = h * jnp.sum(jnp.where(on0, rli + lo, 0)) + jv * n_on

        # --- FIFO head-blocking resume (admission tested against the
        # pre-pop occupancy for every pop, like the oracle) ---------------
        def rcond(rst):
            sst2 = rst
            pm = sst2 == 3
            head = jnp.argmin(jnp.where(pm, psq, _BIG_I))
            occ = base + h * (rli[head] + lo[head]) + jv
            return jnp.any(pm) & (occ <= resume_thr)

        def rbody(rst):
            sst2 = rst
            pm = sst2 == 3
            head = jnp.argmin(jnp.where(pm, psq, _BIG_I))
            return sst2.at[head].set(4)

        sst_r = lax.while_loop(rcond, rbody, sst)
        newm = sst_r == 1
        resm = sst_r == 4
        has_work = jnp.any(newm | resm)

        # --- prefill branch ----------------------------------------------
        tot_in = jnp.sum(jnp.where(newm | resm, rli + lo, 0))
        dur_p = k1 * tot_in + c1
        t_pre = t + dur_p
        stall = on0 | (sst_r == 3) | resm
        tds_p = tds + jnp.where(stall, dur_p, 0.0)
        fresh = newm & jnp.isnan(tf1)
        reent = newm & ~jnp.isnan(tf1) & ~jnp.isnan(tpe)
        tds_p = tds_p + jnp.where(
            reent, jnp.maximum(t_pre - tpe, 0.0), 0.0)
        tf1_p = jnp.where(fresh, t_pre, tf1)
        lo_p = jnp.where(fresh, jnp.int64(1), lo)
        tpe_p = jnp.where(newm, jnp.nan, tpe)
        # join order: new rows by placement sequence, then resumed rows by
        # preemption sequence — the ongoing-list append order
        nj = jnp.sum(newm)
        rank_new = jnp.sum(newm[None, :]
                           & (rnsq[None, :] < rnsq[:, None]), axis=1)
        rank_res = jnp.sum(resm[None, :]
                           & (psq[None, :] < psq[:, None]), axis=1)
        jsq_p = jnp.where(newm, jc + rank_new,
                          jnp.where(resm, jc + nj + rank_res, jsq))
        jc_p = jc + nj + jnp.sum(resm)
        sst_p = jnp.where(newm | resm, jnp.int64(2), sst_r)

        # --- KV overflow -> evict the youngest arrival (ties: earliest
        # joiner), then a decode segment ----------------------------------
        do_dec = ~has_work & (n_on > 0)

        def econd(est):
            sst2, _psq2, _pc2 = est
            on2 = sst2 == 2
            b2 = jnp.sum(on2)
            C2_ = jnp.sum(jnp.where(on2, rli + lo, 0))
            return do_dec & (h * C2_ + jv * b2 > M) & (b2 > 1)

        def ebody(est):
            sst2, psq2, pc2 = est
            on2 = sst2 == 2
            ma = jnp.max(jnp.where(on2, rarr, -jnp.inf))
            vic = jnp.argmin(jnp.where(on2 & (rarr == ma), jsq, _BIG_I))
            return (sst2.at[vic].set(3), psq2.at[vic].set(pc2), pc2 + 1)

        sst_e, psq_e, pc_e = lax.while_loop(econd, ebody, (sst_r, psq, pc))
        on_e = sst_e == 2
        b = jnp.sum(on_e)
        C0 = jnp.sum(jnp.where(on_e, rli + lo, 0))
        n_fin = jnp.min(jnp.where(on_e, jnp.maximum(rlr - lo, 1), _BIG_I))
        n_fin = jnp.where(do_dec, n_fin, 0)
        cb = c2 * b

        def dcond(dst):
            k, td, _seg = dst
            kv_break = (k > 0) & (h * (C0 + k * b) + jv * b > M) & (b > 1)
            return (k < n_fin) & (td < t_end) & ~kv_break

        def dbody(dst):
            k, td, seg = dst
            dur = k2 * (C0 + k * b) + cb + c3
            return k + 1, td + dur, seg + dur

        k, t_dec, seg = lax.while_loop(
            dcond, dbody, (jnp.int64(0), t, jnp.float64(0.0)))
        lo_d = lo + jnp.where(on_e, k, 0)
        # preempted rows' ATGT clocks stall through the segment too
        tds_d = tds + jnp.where(on_e | (sst_e == 3), seg, 0.0)
        done = on_e & (lo_d >= rlr)
        tfn_d = jnp.where(done, t_dec, tfn)
        # finished rows park as 5 (finished, undrained) so the beat loop
        # never touches (n,)-sized output arrays; the host fans them out
        # from the row state after the chunk returns
        sst_d = jnp.where(done, jnp.int64(5), sst_e)

        # --- select: prefill > decode > idle -----------------------------
        t_n = jnp.where(has_work, t_pre, jnp.where(do_dec, t_dec, t_end))
        sel_i = jnp.where(has_work, sst_p, jnp.where(do_dec, sst_d, sst_r))
        return (t_n, sel_i,
                jnp.where(has_work, lo_p, jnp.where(do_dec, lo_d, lo)),
                jnp.where(has_work, tds_p,
                          jnp.where(do_dec, tds_d, tds)),
                jnp.where(has_work, tf1_p, tf1),
                jnp.where(has_work, tpe_p, tpe),
                jnp.where(do_dec, tfn_d, tfn),
                jnp.where(has_work, jsq_p, jsq),
                jnp.where(do_dec, psq_e, psq),
                jnp.where(has_work, jc_p, jc),
                jnp.where(do_dec, pc_e, pc))

    return lax.while_loop(cond, body, (t_in, sst0, lo0, tds0, tf10, tpe0,
                                       tfn0, jsq0, psq0, jc0, pc0))


def _make_chunk(n: int, W: int, B: int, Q: int, hb: float,
                gamma: float, ttft: float, atgt: float, policy: str,
                edf: bool = False, tagged: bool = False):
    """Close over the static shape/config and return the chunk kernel
    ``fn(st, arrival, l_in, l_real, s_lo, s_tds, s_tf1, s_tpe, rank_r,
    ttft_r, atgt_r) -> st``
    advancing up to ``st['K']`` beats of a FIXED fleet configuration.
    The three trailing operands are the multi-tenant per-request arrays
    (see :func:`_tenant_arrays`): ``edf=True`` sorts the backlog by
    ``rank_r`` each beat, ``tagged=True`` swaps the aladdin constraint
    budgets for the per-request ones; both False ignores them and leaves
    the compiled graph unchanged.
    Fleet composition is traced state (activation masks + per-lane
    coefficient arrays), so boots, drains and reclaims never recompile;
    only lane-capacity growth does. ``st['theta']`` is traced too, which
    lets ``run_policy_candidate_batch`` vmap a whole theta bracket
    through one compiled call.

    The while-loop carry is kept deliberately lean — ``Q``-capped queue,
    finished rows parked in-slot as state 5 (the host drains them from
    the row arrays after the chunk) instead of (n,) output arrays, and
    the re-entrant sinks passed as loop-invariant
    operands — because under ``vmap`` every carried byte is re-selected
    each iteration of every loop (the batched while_loop masking rule),
    which is what the candidate-batch throughput lives or dies on."""
    is_aladdin = policy == "aladdin"
    is_jsq = policy == "jsq"
    tag_a = tagged and is_aladdin
    lane_ids = jnp.arange(W)

    def chunk(st, arrival, l_in, l_real, s_lo, s_tds, s_tf1, s_tpe,
              rank_r, ttft_r, atgt_r):

        def place_pass(st):
            theta = st["theta"]
            sst, rlo, rtds = st["sst"], st["rlo"], st["rtds"]
            rli, rlr = st["rli"], st["rlr"]
            online = st["mode"] == 2
            rank = st["rank"]
            on = sst == 2
            members = on | (sst == 1)
            # aggregates in the reference's cache roles: cnt=bsz (ongoing
            # + new batch), ctx0 over ongoing only, newctx over new batch
            # (re-entrants count their retained l_out — what kv_now sees)
            cnt0 = jnp.sum(members, axis=1)
            wctx0 = jnp.sum(jnp.where(members, rli + gamma * rlr, 0.0),
                            axis=1)
            newsum0 = jnp.sum(jnp.where(sst == 1, rli, 0), axis=1)
            newctx0 = jnp.sum(jnp.where(sst == 1, rli + rlo, 0), axis=1)
            ctx0 = jnp.sum(jnp.where(on, rli + rlo, 0), axis=1)
            if is_aladdin:
                slack = jnp.min(jnp.where(
                    on, atgt * jnp.maximum(rlo - 1, 0) - rtds,
                    jnp.inf), axis=1)
                d_budget = theta * jnp.maximum(slack, 0.0)
                if tag_a:
                    # per-member ATGT budgets (inf -> planning SLO),
                    # selected per candidate like the reference
                    am = atgt_r[st["rid"]]
                    am = jnp.where(jnp.isinf(am), atgt, am)
                    slack_t = jnp.min(jnp.where(
                        on, am * jnp.maximum(rlo - 1, 0) - rtds,
                        jnp.inf), axis=1)
                    d_budget_t = theta * jnp.maximum(slack_t, 0.0)
            else:
                d_budget = jnp.zeros(W)
            if tag_a:
                # running raw-budget mins over members (b: ongoing + new
                # batch; c: new batch only), updated as placements land
                amin0 = jnp.min(jnp.where(members, atgt_r[st["rid"]],
                                          jnp.inf), axis=1)
                tmin0 = jnp.min(jnp.where(sst == 1, ttft_r[st["rid"]],
                                          jnp.inf), axis=1)
            nserv = jnp.sum(online)

            def pbody(ps):
                (i, keep, q, sst, rid, rli, rlr, rlo, rtds, rtf1, rtpe,
                 rtfn, rarr, rnsq, rjsq, rpsq, cnt, newsum, newctx, wctx,
                 seqc, key, ovf) = ps[:23]
                if tag_a:
                    amin, tmin = ps[23], ps[24]
                r = q[i]
                liv = l_in[r]
                lrv = l_real[r]
                lov = s_lo[r]               # re-entrant retained l_out
                v = liv + gamma * lrv
                bpost = cnt + 1
                if is_aladdin:
                    K2a = st["K2"]
                    if tag_a:
                        # an untagged candidate takes the scalar branch
                        # even among tagged members (reference _tagged)
                        ct = jnp.isfinite(atgt_r[r])
                        a0 = jnp.minimum(amin, atgt_r[r])
                        a_eff = jnp.where(
                            ct, jnp.where(jnp.isinf(a0), atgt, a0), atgt)
                        t0_ = jnp.minimum(tmin, ttft_r[r])
                        t_eff = jnp.where(
                            ct, jnp.where(jnp.isinf(t0_), ttft, t0_),
                            ttft)
                        d_eff = jnp.where(ct, d_budget_t, d_budget)
                    else:
                        a_eff, t_eff, d_eff = atgt, ttft, d_budget
                    budget = jnp.where(
                        K2a > 0,
                        jnp.maximum(((a_eff - st["C3"]) - st["C2"] * bpost)
                                    / jnp.where(K2a > 0, K2a, 1.0), 0.0),
                        jnp.inf)
                    pre_t = st["K1"] * (newsum + liv) + st["C1"]
                    ok = ((bpost <= st["MAXB"])
                          & (wctx + v <= theta * budget)
                          & (pre_t <= t_eff) & (pre_t <= d_eff) & online)
                    norm = jnp.hypot(cnt / st["MAXBN"], wctx / st["CMAXN"])
                    # lazy best-fit: walk candidates by (norm desc, serving
                    # order), testing constraint (e)'s KV peak per lane
                    rem_c = jnp.maximum(lrv - lov, 0)
                    ctx_c = liv + lov

                    def kcond(ks):
                        m_, _w, found = ks
                        return jnp.any(m_) & ~found

                    def kbody(ks):
                        m_, _w, _f = ks
                        mn = jnp.max(jnp.where(m_, norm, -jnp.inf))
                        w_ = jnp.argmin(jnp.where(m_ & (norm == mn),
                                                  rank, _BIG_I))
                        memb = (sst[w_] == 1) | (sst[w_] == 2)
                        remv = jnp.concatenate([
                            jnp.where(memb,
                                      jnp.maximum(rlr[w_] - rlo[w_], 0), 0),
                            rem_c[None]])
                        ctxv = jnp.concatenate([
                            jnp.where(memb, rli[w_] + rlo[w_], 0),
                            ctx_c[None]])
                        mv = jnp.concatenate(
                            [memb, jnp.ones((1,), dtype=bool)])
                        hk, jk = st["H"][w_], st["J"][w_]
                        kiv = jnp.maximum(remv, 1)
                        aliveM = mv[None, :] & (remv[None, :]
                                                >= kiv[:, None])
                        cnt_a = jnp.sum(aliveM, axis=1)
                        sum_c = jnp.sum(
                            jnp.where(aliveM, ctxv[None, :], 0), axis=1)
                        tot = hk * (sum_c + cnt_a * kiv) + jk * cnt_a
                        valid = mv & (cnt_a > 0)
                        peak = jnp.maximum(
                            hk * jnp.sum(jnp.where(mv, ctxv, 0))
                            + jk * jnp.sum(mv),
                            jnp.max(jnp.where(valid, tot, -jnp.inf)))
                        return (m_.at[w_].set(False), w_,
                                peak <= theta * st["M"][w_])

                    _m, w, placed = lax.while_loop(
                        kcond, kbody, (ok, jnp.int64(0), jnp.bool_(False)))
                    key2 = key
                else:
                    # kv_now admission shared by jsq and po2 (_admit_naive)
                    kv_now = (st["H"] * (ctx0 + newctx) + st["J"] * cnt) \
                        + (st["H"] * liv + st["J"])
                    admit = (kv_now <= st["M"]) & (bpost <= st["MAXB"]) \
                        & online
                    if is_jsq:
                        # min batch, ties to serving-list order
                        w = jnp.argmin(jnp.where(
                            admit, cnt * (W + 1) + rank, _BIG_I))
                        placed = jnp.any(admit)
                        key2 = key
                    else:
                        # po2: two uniform draws (jax PRNG — deterministic
                        # but a different stream than the numpy oracle)
                        key2, ka, kb = jax.random.split(key, 3)
                        m = nserv
                        r1 = jax.random.randint(
                            ka, (), 0, jnp.maximum(m, 1))
                        r2 = jax.random.randint(
                            kb, (), 0, jnp.maximum(m - 1, 1))
                        jj = r2 + (r2 >= r1)
                        c1_ = st["p2l"][r1]
                        c2_ = st["p2l"][jj]
                        swap = wctx[c2_] < wctx[c1_]
                        c1_, c2_ = (jnp.where(swap, c2_, c1_),
                                    jnp.where(swap, c1_, c2_))
                        use1 = (m >= 1) & admit[c1_]
                        use2 = (m >= 2) & ~use1 & admit[c2_]
                        fb = admit & ~((lane_ids == c1_) & (m >= 1)) \
                            & ~((lane_ids == c2_) & (m >= 2))
                        mw = jnp.min(jnp.where(fb, wctx, jnp.inf))
                        wf = jnp.argmin(jnp.where(fb & (wctx == mw),
                                                  rank, _BIG_I))
                        w = jnp.where(use1, c1_,
                                      jnp.where(use2, c2_, wf))
                        placed = use1 | use2 | jnp.any(fb)
                slot = jnp.argmin(sst[w])
                has_free = sst[w, slot] == 0
                ovf = ovf | (placed & ~has_free)
                do = placed & has_free
                wslot = jnp.where(do, slot, B)   # B: out-of-range no-op
                sst = sst.at[w, wslot].set(1, mode="drop")
                rid = rid.at[w, wslot].set(r, mode="drop")
                rli = rli.at[w, wslot].set(liv, mode="drop")
                rlr = rlr.at[w, wslot].set(lrv, mode="drop")
                rlo = rlo.at[w, wslot].set(lov, mode="drop")
                rtds = rtds.at[w, wslot].set(s_tds[r], mode="drop")
                rtf1 = rtf1.at[w, wslot].set(s_tf1[r], mode="drop")
                rtpe = rtpe.at[w, wslot].set(s_tpe[r], mode="drop")
                rtfn = rtfn.at[w, wslot].set(jnp.nan, mode="drop")
                rarr = rarr.at[w, wslot].set(arrival[r], mode="drop")
                rnsq = rnsq.at[w, wslot].set(seqc, mode="drop")
                rjsq = rjsq.at[w, wslot].set(0, mode="drop")
                rpsq = rpsq.at[w, wslot].set(0, mode="drop")
                cnt = cnt.at[w].add(jnp.where(do, 1, 0))
                newsum = newsum.at[w].add(jnp.where(do, liv, 0))
                newctx = newctx.at[w].add(jnp.where(do, liv + lov, 0))
                wctx = wctx.at[w].add(jnp.where(do, v, 0.0))
                seqc = seqc + jnp.where(do, 1, 0)
                # unplaced requests stay queued, FIFO order preserved
                qslot = jnp.where(do, jnp.int64(Q), keep)
                q = q.at[qslot].set(r, mode="drop")
                keep = keep + jnp.where(do, 0, 1)
                out = (i + 1, keep, q, sst, rid, rli, rlr, rlo, rtds,
                       rtf1, rtpe, rtfn, rarr, rnsq, rjsq, rpsq, cnt,
                       newsum, newctx, wctx, seqc, key2, ovf)
                if tag_a:
                    amin = amin.at[w].min(
                        jnp.where(do, atgt_r[r], jnp.inf))
                    tmin = tmin.at[w].min(
                        jnp.where(do, ttft_r[r], jnp.inf))
                    out = out + (amin, tmin)
                return out

            ps0 = (jnp.int64(0), jnp.int64(0), st["q"], sst, st["rid"],
                   rli, rlr, rlo, rtds, st["rtf1"], st["rtpe"],
                   st["rtfn"], st["rarr"], st["rnsq"], st["rjsq"],
                   st["rpsq"], cnt0, newsum0, newctx0, wctx0, st["seqc"],
                   st["key"], st["ovf"])
            if tag_a:
                ps0 = ps0 + (amin0, tmin0)
            ps = lax.while_loop(lambda ps: ps[0] < st["qlen"], pbody, ps0)
            out = dict(st)
            (out["qlen"], out["q"], out["sst"], out["rid"], out["rli"],
             out["rlr"], out["rlo"], out["rtds"], out["rtf1"],
             out["rtpe"], out["rtfn"], out["rarr"], out["rnsq"],
             out["rjsq"], out["rpsq"]) = ps[1:16]
            out["seqc"], out["key"], out["ovf"] = ps[20], ps[21], ps[22]
            return out

        def beat_body(st):
            t = st["t"]

            # admit arrivals <= t (the trace is sorted by arrival): one
            # masked scatter append — the host pre-sizes Q so the whole
            # chunk's arrivals always fit (no in-kernel overflow path)
            hi = jnp.maximum(
                jnp.searchsorted(arrival, t, side="right"), st["idx"])
            na = hi - st["idx"]
            ii = jnp.arange(Q, dtype=jnp.int64)
            q = st["q"].at[jnp.where(ii < na, st["qlen"] + ii, Q)].set(
                st["idx"] + ii, mode="drop")
            st = dict(st)
            st["idx"], st["qlen"], st["q"] = hi, st["qlen"] + na, q
            if edf:
                # priority-then-EDF admission order: sort the backlog by
                # the host-computed total rank (unique per request);
                # entries past qlen sort to the tail
                keys = jnp.where(ii < st["qlen"], rank_r[st["q"]],
                                 _BIG_I)
                st["q"] = jnp.take(st["q"], jnp.argsort(keys))
            st = place_pass(st)
            t_next = t + hb
            adv = (st["mode"] == 2) | (st["mode"] == 3)
            t_end_w = jnp.where(adv, t_next, st["t_w"])
            sst_pp = st["sst"]
            ax = (0, None) + (0,) * 23
            (t_w, sst, rlo, rtds, rtf1, rtpe, rtfn, rjsq, rpsq, jc, pc) = \
                jax.vmap(_advance_lane_kv, in_axes=ax)(
                    st["t_w"], t, t_end_w, sst_pp, st["rli"], st["rlr"],
                    st["rnsq"], st["rarr"], st["rlo"], st["rtds"],
                    st["rtf1"], st["rtpe"], st["rtfn"], st["rjsq"],
                    st["rpsq"], st["jc"], st["pc"], st["K1"], st["C1"],
                    st["K2"], st["C2"], st["C3"], st["H"], st["J"],
                    st["M"])
            (st["t_w"], st["sst"], st["rlo"], st["rtds"], st["rtf1"],
             st["rtpe"], st["rtfn"], st["rjsq"], st["rpsq"], st["jc"],
             st["pc"]) = (t_w, sst, rlo, rtds, rtf1, rtpe, rtfn, rjsq,
                          rpsq, jc, pc)
            # busy/retirement stats for the host's billing replay: a lane
            # is busy with ongoing or new-batch rows (preempted rows are
            # not load); a draining lane that empties retires before its
            # beat is billed, so record the first-empty beat index.
            # Finished-undrained rows (5) are semantically gone: they
            # neither load a lane nor block its retirement
            loaded = jnp.any((sst == 1) | (sst == 2), axis=1)
            busy = jnp.sum((st["mode"] == 2) & loaded)
            st["busy_pk"] = jnp.maximum(st["busy_pk"], busy)
            st["busy_fin"] = busy
            occ_lane = jnp.any((sst > 0) & (sst < 5), axis=1)
            st["empty_at"] = jnp.where(
                (st["mode"] == 3) & ~occ_lane
                & (st["empty_at"] == _BIG_I),
                st["j"], st["empty_at"])
            st["j"] = st["j"] + 1
            st["t"] = t_next
            return st

        def beat_cond(st):
            drained = (st["idx"] >= n) & (st["qlen"] == 0) \
                & ~jnp.any((st["sst"] > 0) & (st["sst"] < 5))
            return (st["j"] < st["K"]) & ~drained

        return lax.while_loop(beat_cond, beat_body, st)

    return chunk


# compiled kernels are cached per static configuration; the jit wrapper on
# top caches its traces too, so repeated runs/batches recompile nothing
_KERNELS: Dict[Tuple, object] = {}


def _kernel_for(scenario, specs, trace, batched: bool,
                edf: bool = False, tagged: bool = False):
    from repro.serving import api

    topo = scenario.topology
    W = len(specs)
    B = max(max(int(s.max_batch) for s in specs), 1)
    arrival = np.array(sorted(r.arrival for r in trace))
    n = len(trace)
    horizon = (float(arrival[-1]) if n else 0.0) + DEFAULT_TAIL
    cmax_norm = []
    for s in specs:
        cmax = s.perf.decode.max_total_context(1, scenario.slo.atgt) or 1.0
        cmax_norm.append(max(cmax, 1.0))
    key = (n, W, B, float(topo.heartbeat), horizon, float(topo.theta),
           float(topo.gamma), float(scenario.slo.ttft),
           float(scenario.slo.atgt), topo.policy,
           tuple((float(s.perf.prefill.k1), float(s.perf.prefill.c1),
                  float(s.perf.decode.k2), float(s.perf.decode.c2),
                  float(s.perf.decode.c3), int(s.max_batch)) for s in specs),
           batched, edf, tagged)
    fn = _KERNELS.get(key)
    if fn is None:
        coefs = tuple(tuple(getattr(s.perf.prefill, a) for s in specs)
                      for a in ("k1", "c1")) + \
            tuple(tuple(getattr(s.perf.decode, a) for s in specs)
                  for a in ("k2", "c2", "c3"))
        sim = _make_simulate(
            n, W, B, float(topo.heartbeat), horizon, float(topo.theta),
            float(topo.gamma), float(scenario.slo.ttft),
            float(scenario.slo.atgt), topo.policy, coefs,
            tuple(int(s.max_batch) for s in specs),
            tuple(max(int(s.max_batch), 1) for s in specs),
            tuple(cmax_norm), edf, tagged)
        if batched:
            fn = jax.jit(jax.vmap(sim, in_axes=(None, None, None, 0,
                                                None, None, None)))
        else:
            fn = jax.jit(sim)
        _KERNELS[key] = fn
    return fn


def _trace_arrays(trace):
    order = sorted(range(len(trace)), key=lambda i: trace[i].arrival)
    ordered = [trace[i] for i in order]
    arrival = np.array([r.arrival for r in ordered])
    l_in = np.array([r.l_in for r in ordered], dtype=np.int64)
    l_real = np.array([r.l_real for r in ordered], dtype=np.int64)
    return ordered, arrival, l_in, l_real


def _tenant_arrays(ordered):
    """Per-request multi-tenant operands for the kernels: the total queue
    rank (priority desc, deadline asc, arrival index — the order a stable
    reference sort converges to; after a requeue an exact-key tie can
    differ, which the tolerance pins absorb) and the RAW per-request SLO
    budgets (``inf`` = untagged; the kernels resolve the fallback to the
    planning SLO in-branch, like the reference). ``tagged`` mirrors the
    reference's trace-level gate (any finite ATGT budget)."""
    n = len(ordered)
    prio = np.array([int(r.priority) for r in ordered], dtype=np.int64)
    dl = np.array([r.deadline for r in ordered])
    rank = np.empty(n, dtype=np.int64)
    rank[np.lexsort((dl, -prio))] = np.arange(n, dtype=np.int64)
    ttft_r = np.array([r.slo_ttft for r in ordered])
    atgt_r = np.array([r.slo_atgt for r in ordered])
    tagged = bool(np.isfinite(atgt_r).any()) if n else False
    return rank, ttft_r, atgt_r, tagged


def _report_from_arrays(scenario, specs, n_active, arrival, l_real, l_out,
                        tds, t_first, t_fin):
    """Replicate ``api._percentiles`` over the result arrays (requests in
    finish order, like the reference's finished list)."""
    from repro.serving import api

    slo = scenario.slo
    n = len(arrival)
    fin = ~np.isnan(t_fin)
    order = np.lexsort((np.arange(n)[fin], t_fin[fin]))
    idx = np.nonzero(fin)[0][order]
    ttfts = t_first[idx] - arrival[idx]
    has_atgt = l_real[idx] > 1
    atgts = tds[idx][has_atgt] / np.maximum(l_real[idx][has_atgt] - 1, 1)
    ok = (ttfts <= slo.ttft)
    ok_atgt = np.ones(len(idx), dtype=bool)
    ok_atgt[has_atgt] = atgts <= slo.atgt
    rep = api.RunReport(
        topology="colocated", scaling="fixed",
        attainment=float(np.sum(ok & ok_atgt)) / max(n, 1),
        p99_atgt=float(np.percentile(atgts, 99)) if len(atgts)
        else float("nan"),
        p99_ttft=float(np.percentile(ttfts, 99)) if len(ttfts)
        else float("nan"),
        mean_atgt=float(np.mean(atgts)) if len(atgts) else float("nan"),
        finished=int(len(idx)), total=n)
    rep.peak_workers = int(n_active)
    rep.gpu_cost = sum(s.n_accelerators for s in specs[:n_active])
    rep.moves = 0
    return rep


def _chunk_kernel(n: int, W: int, B: int, Q: int, hb: float,
                  gamma: float, ttft: float, atgt: float, policy: str,
                  batched: bool, edf: bool = False, tagged: bool = False):
    key = ("chunk", n, W, B, Q, hb, gamma, ttft, atgt, policy, batched,
           edf, tagged)
    fn = _KERNELS.get(key)
    if fn is None:
        sim = _make_chunk(n, W, B, Q, hb, gamma, ttft, atgt, policy,
                          edf, tagged)
        if batched:
            fn = jax.jit(jax.vmap(sim,
                                  in_axes=(0, None, None, None, 0, 0, 0, 0,
                                           None, None, None)))
        else:
            fn = jax.jit(sim)
        _KERNELS[key] = fn
    return fn


# mirror layout: per-lane coefficient/clock arrays and per-slot row arrays
# (grown by doubling; rows are recycled once a lane leaves every pool list)
_LANE_KEYS = ("t_w", "jc", "pc", "K1", "C1", "K2", "C2", "C3", "H", "J",
              "M", "MAXB", "MAXBN", "CMAXN")
_ROW_KEYS = ("sst", "rid", "rli", "rlr", "rlo", "rtds", "rtf1", "rtpe",
             "rtfn", "rarr", "rnsq", "rjsq", "rpsq")
_NAN_KEYS = ("rtf1", "rtpe", "rtfn")
_ONE_KEYS = ("MAXB", "MAXBN", "CMAXN")
# host-resident mirrors the kernel never carries: the (n,) request outputs
# (fed by the staged finisher ring) and the re-entrant sinks (loop-invariant
# kernel operands, written only between chunks by the lane adapters)
_HOST_KEYS = ("o_lo", "o_tds", "o_tf1", "o_tfn",
              "s_lo", "s_tds", "s_tf1", "s_tpe")


class _PooledSim:
    """Host half of the chunked compiled engine.

    The kernel advances beats inside a fixed fleet configuration; this
    class owns everything between chunks: numpy mirrors of the lane state,
    the REAL ``ManagedPool``/``_FixedLanes``/``WorkerLifecycle`` state
    machines (driven through the same adapter protocol the numpy engine
    uses, so every scaling/reclaim decision — including the victim rng
    draws — is made by reference code on the reference's numpy Generator),
    and the beat-grid bookkeeping that cuts chunks at fleet-mutation
    boundaries: scaling epochs, boot completions, market events, notice
    deadlines, and the horizon."""

    def __init__(self, scenario, seed: Optional[int] = None,
                 tail: float = DEFAULT_TAIL):
        from repro.serving import api
        from repro.serving.fastsim import (_FixedLanes, _managed_policy,
                                           _managed_scfg)
        from repro.serving.forecast import ManagedPool

        scenario = api.resolve_scenario(scenario)
        self.scenario = scenario
        self.specs0 = check_jax_envelope(scenario)
        topo = scenario.topology
        self.policy_name = topo.policy
        self.hb = float(topo.heartbeat)
        self.gamma = float(topo.gamma)
        self.theta = float(topo.theta)
        self.slo = scenario.slo
        s = seed if seed is not None else scenario.seed
        self.rng = np.random.default_rng(s)
        trace = scenario.materialize()
        check_trace_session_free(trace)
        self.trace, self.arrival, self.l_in, self.l_real = \
            _trace_arrays(trace)
        self.n = len(self.trace)
        self.rank_r, self.ttft_r, self.atgt_r, self.tagged = \
            _tenant_arrays(self.trace)
        self.edf = (scenario.tenants is not None
                    and len(scenario.tenants) > 1 and self.n > 0)
        horizon = (float(self.arrival[-1]) if self.n else 0.0) + tail
        grid = [0.0]
        while grid[-1] < horizon:    # the reference's sequential t += hb
            grid.append(grid[-1] + self.hb)
        self.G = np.array(grid)
        self.total_beats = len(grid) - 1
        market = scenario.market
        self.notice = float(market.notice_s) if market is not None else 0.0
        self.events = sorted(market.events, key=lambda e: e.t) \
            if market is not None and market.events else []
        self.managed = not isinstance(scenario.scaling, api.FixedScale)
        cand_specs = list(self.specs0)
        if market is not None and market.spec is not None:
            cand_specs.append(market.spec)
        maxb = max(max(int(sp.max_batch) for sp in cand_specs), 1)
        live_kv = any(sp.perf.kv.h != 0.0 or sp.perf.kv.j != 0.0
                      for sp in cand_specs)
        # live KV parks preempted rows in-lane, and finished rows park
        # in-slot as state 5 until the host drains them between chunks:
        # slots are transient scratch, not a capacity model.  Start
        # small — every while-loop carry in the kernel drags the (W, B)
        # row arrays, so an oversized B taxes every beat.  The kernel
        # flags slot exhaustion (ovf) and the drivers regrow B and
        # re-run the chunk; n rows is the absolute ceiling.
        self.Bmax = max(2 * maxb + 8 if live_kv else maxb, self.n, 1)
        self.B = max(min(2 * maxb + 8 if live_kv else maxb, 64), 1)
        # queue capacity is host-presized per chunk (arrivals are known)
        self.qcap = max(1, min(self.n, 64))
        self.W_cap = 8
        self.specs: List = []
        self._wid = 0
        n = self.n
        W, B = self.W_cap, self.B
        self.m = {
            "t_w": np.zeros(W), "jc": np.zeros(W, np.int64),
            "pc": np.zeros(W, np.int64),
            "K1": np.zeros(W), "C1": np.zeros(W), "K2": np.zeros(W),
            "C2": np.zeros(W), "C3": np.zeros(W), "H": np.zeros(W),
            "J": np.zeros(W), "M": np.zeros(W),
            "MAXB": np.ones(W, np.int64), "MAXBN": np.ones(W),
            "CMAXN": np.ones(W),
            "sst": np.zeros((W, B), np.int64),
            "rid": np.zeros((W, B), np.int64),
            "rli": np.zeros((W, B), np.int64),
            "rlr": np.zeros((W, B), np.int64),
            "rlo": np.zeros((W, B), np.int64),
            "rtds": np.zeros((W, B)),
            "rtf1": np.full((W, B), np.nan),
            "rtpe": np.full((W, B), np.nan),
            "rtfn": np.full((W, B), np.nan),
            "rarr": np.zeros((W, B)),
            "rnsq": np.zeros((W, B), np.int64),
            "rjsq": np.zeros((W, B), np.int64),
            "rpsq": np.zeros((W, B), np.int64),
            "o_lo": np.zeros(n, np.int64), "o_tds": np.zeros(n),
            "o_tf1": np.full(n, np.nan), "o_tfn": np.full(n, np.nan),
            "s_lo": np.zeros(n, np.int64), "s_tds": np.zeros(n),
            "s_tf1": np.full(n, np.nan), "s_tpe": np.full(n, np.nan),
        }
        self.h_pn = np.zeros(n, np.int64)   # preempt_count deltas
        self._queue: List[int] = []
        self.idx = 0
        self.eidx = 0
        self.beat = 0
        self.seqc = 0
        self.key = jax.random.PRNGKey(int(scenario.seed))
        self.done = False
        self.pool = None
        if self.managed:
            scfg = _managed_scfg(scenario)
            pol = _managed_policy(scenario, scfg)
            self.scaling_policy = pol
            self.pool = ManagedPool(
                scenario.fleet.for_role("serve")[0].spec, scfg, pol,
                self.hb, self.rng, new_worker=self._new_lane,
                on_spawn=self._spawn_lane, on_kill=self._kill_lane,
                load=self._lane_load, idle=self._lane_idle,
                mark=self._mark_rid,
                spot_spec=market.spec if market is not None else None,
                notice_s=self.notice, name="serve")
        else:
            lanes = [self._new_lane(sp) for sp in self.specs0]
            self.init_W = len(lanes)
            self.pool = _FixedLanes(self, lanes, self.rng, self.notice)

    # ---- lane allocation (grow-only mirrors, recycled rows) ----------------

    def _ensure_cap(self, need: int) -> None:
        if need <= self.W_cap:
            return
        cap = self.W_cap
        while cap < need:
            cap *= 2
        ext = cap - self.W_cap
        for k in _LANE_KEYS:
            fill = np.ones(ext, self.m[k].dtype) if k in _ONE_KEYS \
                else np.zeros(ext, self.m[k].dtype)
            self.m[k] = np.concatenate([self.m[k], fill])
        for k in _ROW_KEYS:
            fill = np.full((ext, self.B), np.nan) if k in _NAN_KEYS \
                else np.zeros((ext, self.B), self.m[k].dtype)
            self.m[k] = np.vstack([self.m[k], fill])
        self.W_cap = cap

    def _ensure_rows(self, need: int) -> None:
        """Grow the per-lane row dimension (slot exhaustion recovery)."""
        need = min(need, self.Bmax)
        if need <= self.B:
            return
        B = self.B
        while B < need:
            B = min(B * 2, self.Bmax)
        ext = B - self.B
        for k in _ROW_KEYS:
            fill = np.full((self.W_cap, ext), np.nan) if k in _NAN_KEYS \
                else np.zeros((self.W_cap, ext), self.m[k].dtype)
            self.m[k] = np.hstack([self.m[k], fill])
        self.B = B

    def _live_idx(self) -> set:
        if self.pool is None:       # pool ctor is mid-boot: nothing retired
            return set(range(len(self.specs)))
        live = {ln.idx for ln in self.pool.active()}
        if self.managed:
            live |= {b[1].idx for b in self.pool.booting}
        return live

    def _new_lane(self, spec):
        from repro.serving.fastsim import _Lane

        live = self._live_idx()
        free = [i for i in range(len(self.specs)) if i not in live]
        if free:
            idx = free[0]
            self.specs[idx] = spec
        else:
            idx = len(self.specs)
            self._ensure_cap(idx + 1)
            self.specs.append(spec)
        m = self.m
        m["t_w"][idx] = 0.0
        m["jc"][idx] = 0
        m["pc"][idx] = 0
        m["K1"][idx] = spec.perf.prefill.k1
        m["C1"][idx] = spec.perf.prefill.c1
        m["K2"][idx] = spec.perf.decode.k2
        m["C2"][idx] = spec.perf.decode.c2
        m["C3"][idx] = spec.perf.decode.c3
        m["H"][idx] = spec.perf.kv.h
        m["J"][idx] = spec.perf.kv.j
        m["M"][idx] = spec.kv_capacity
        m["MAXB"][idx] = int(spec.max_batch)
        m["MAXBN"][idx] = max(int(spec.max_batch), 1)
        cmax = spec.perf.decode.max_total_context(1, self.slo.atgt) or 1.0
        m["CMAXN"][idx] = max(cmax, 1.0)
        for k in _ROW_KEYS:
            m[k][idx] = np.nan if k in _NAN_KEYS else 0
        self._wid += 1
        return _Lane(self._wid, spec, idx)

    # ---- pool/lifecycle adapters (mirror-backed) ---------------------------

    def _spawn_lane(self, lane, t: float) -> None:
        self.m["t_w"][lane.idx] = t

    def _kill_lane(self, lane) -> List[int]:
        """Extraction in the reference's order: ongoing (join order), new
        batch (placement order), KV-preempted (preemption order). Row
        state is parked in the re-entrant sinks; the lifecycle's mark
        callback then stamps ``s_tpe``."""
        wi = lane.idx
        m = self.m
        sst = m["sst"][wi]
        parts = []
        for state, okey in ((2, "rjsq"), (1, "rnsq"), (3, "rpsq")):
            slots = np.nonzero(sst == state)[0]
            parts.append(slots[np.argsort(m[okey][wi][slots],
                                          kind="stable")])
        lost = []
        for slot in np.concatenate(parts):
            r = int(m["rid"][wi, slot])
            m["s_lo"][r] = m["rlo"][wi, slot]
            m["s_tds"][r] = m["rtds"][wi, slot]
            m["s_tf1"][r] = m["rtf1"][wi, slot]
            m["s_tpe"][r] = m["rtpe"][wi, slot]
            lost.append(r)
        m["sst"][wi] = 0
        return lost

    def _mark_rid(self, rid: int, t: float) -> None:
        self.m["s_tpe"][rid] = t
        self.h_pn[rid] += 1

    def _lane_load(self, lane) -> int:
        sst = self.m["sst"][lane.idx]
        return int(np.sum((sst == 1) | (sst == 2)))

    def _lane_idle(self, lane) -> bool:
        return not (self.m["sst"][lane.idx] > 0).any()

    # ---- the ColocatedTopology shim the pools call back into ---------------

    def requeue(self, rids, side: str = "serve") -> None:
        self._queue.extend(int(r) for r in rids)

    def backlog_len(self, side: str = "serve") -> int:
        return len(self._queue)

    def slo_window(self, side: str, t_now: float, window: float,
                   metric: str = "both") -> tuple:
        m = self.m
        t0 = t_now - window
        tfn = m["o_tfn"]
        inw = ~np.isnan(tfn) & (tfn >= t0)
        ids = np.nonzero(inw)[0]
        total = int(ids.size)
        ok = 0
        if total:
            ttft_ok = (m["o_tf1"][ids] - self.arrival[ids]) \
                <= self.slo.ttft
            has_dec = self.l_real[ids] > 1
            atgt_ok = np.ones(total, dtype=bool)
            d = ids[has_dec]
            atgt_ok[has_dec] = (m["o_tds"][d] / (self.l_real[d] - 1)) \
                <= self.slo.atgt
            if metric == "both":
                okm = ttft_ok & atgt_ok
            elif metric == "ttft":
                okm = ttft_ok
            elif metric == "atgt":
                okm = atgt_ok
            else:
                raise ValueError(f"unknown SLO metric {metric!r}")
            ok = int(okm.sum())
        if metric != "atgt":
            for rid in self._queue:
                if math.isnan(m["s_tf1"][rid]) \
                        and t_now - float(self.arrival[rid]) \
                        > self.slo.ttft:
                    total += 1
        return ok, total

    # ---- chunk orchestration -----------------------------------------------

    def _grid_beat(self, x: float) -> int:
        """First beat index b with G[b] >= x (the beat at which a
        time-armed transition fires under the reference's ``<= t`` test)."""
        return int(np.searchsorted(self.G, x, side="left"))

    def _boundary(self) -> None:
        """The host-side slice of one beat start: admit arrivals, fire
        market events, run ``begin_beat`` (boot onlining + reaps) — the
        reference's exact per-beat order. In-chunk beats run the admission
        step in-kernel; everything else is a no-op off-boundary by
        construction of the chunk cuts."""
        t = self.G[self.beat]
        while self.idx < self.n and self.arrival[self.idx] <= t:
            self._queue.append(self.idx)
            self.pool.note_arrival()
            self.idx += 1
        while self.eidx < len(self.events) \
                and self.events[self.eidx].t <= t:
            self.requeue(self.pool.on_reclaim(t, self.events[self.eidx]))
            self.eidx += 1
        self.pool.begin_beat(self, t)

    def _chunk_len(self) -> int:
        """Beats until the next fleet-mutation boundary (always >= 1: the
        boundary processing above already consumed everything due now)."""
        b = self.beat
        cands = [self.total_beats - b]
        if self.eidx < len(self.events):
            cands.append(self._grid_beat(self.events[self.eidx].t) - b)
        for dl in self.pool.life.condemned.values():
            cands.append(self._grid_beat(dl) - b)
        if self.managed:
            bpe = self.pool.beats_per_epoch
            cands.append(bpe - (self.pool.acc["beat"] % bpe))
            for bt in self.pool.booting:
                cands.append(self._grid_beat(bt[0]) - b)
        return max(min(cands), 1)

    def _pack(self, K: int) -> Dict:
        m = self.m
        W = self.W_cap
        mode = np.zeros(W, np.int64)
        rank = np.full(W, _BIG_I, np.int64)
        p2l = np.zeros(W, np.int64)
        serving = [ln for ln in self.pool.serving()
                   if ln.alive and not ln.draining]
        sset = {id(ln) for ln in serving}
        for p, ln in enumerate(serving):
            mode[ln.idx] = 2
            rank[ln.idx] = p
            p2l[p] = ln.idx
        for ln in self.pool.active():
            if id(ln) not in sset:
                mode[ln.idx] = 3
        q = np.zeros(self.qcap, np.int64)
        if self._queue:
            q[:len(self._queue)] = self._queue
        st = {k: v for k, v in m.items() if k not in _HOST_KEYS}
        st.update(
            mode=mode, rank=rank, p2l=p2l, q=q,
            t=np.float64(self.G[self.beat]), K=np.int64(K),
            idx=np.int64(self.idx), qlen=np.int64(len(self._queue)),
            seqc=np.int64(self.seqc), key=self.key, j=np.int64(0),
            busy_pk=np.int64(0), busy_fin=np.int64(0),
            empty_at=np.full(W, _BIG_I, np.int64), ovf=np.bool_(False),
            theta=np.float64(self.theta))
        return st

    def _pull(self, out) -> Tuple[int, int, int, np.ndarray]:
        for k in list(self.m):
            if k in _HOST_KEYS:
                continue
            # np.array(): device output buffers are read-only as views and
            # the mirrors are mutated by the lane adapters between chunks
            self.m[k] = np.array(out[k])
        # drain finished-undrained rows (state 5) from the row arrays to
        # the per-request output mirrors and recycle their slots; each
        # rid finishes exactly once, so the scatter is collision-free
        wf, sf = np.nonzero(self.m["sst"] == 5)
        if len(wf):
            r = self.m["rid"][wf, sf]
            self.m["o_lo"][r] = self.m["rlo"][wf, sf]
            self.m["o_tds"][r] = self.m["rtds"][wf, sf]
            self.m["o_tf1"][r] = self.m["rtf1"][wf, sf]
            self.m["o_tfn"][r] = self.m["rtfn"][wf, sf]
            self.m["sst"][wf, sf] = 0
        qlen = int(out["qlen"])
        q = np.asarray(out["q"])
        self._queue = [int(r) for r in q[:qlen]]
        self.idx = int(out["idx"])
        self.seqc = int(out["seqc"])
        self.key = out["key"]
        if bool(out["ovf"]):
            raise RuntimeError(
                "jax engine lane-slot overflow at the Bmax ceiling "
                "(KV-preempted backlog exceeded slot headroom); "
                "use engine='vectorized'")
        return (int(out["j"]), int(out["busy_pk"]), int(out["busy_fin"]),
                np.asarray(out["empty_at"]))

    def _settle(self, executed: int, busy_pk: int, busy_fin: int,
                empty_at: np.ndarray, arrivals: int) -> None:
        b0 = self.beat
        if self.managed:
            dts = [float(self.G[b0 + i + 1] - self.G[b0 + i])
                   for i in range(executed)]
            retiring: Dict[int, List] = {}
            for ln in list(self.pool.draining):
                ea = int(empty_at[ln.idx])
                if ea < executed:
                    retiring.setdefault(ea, []).append(ln)
            self.pool.absorb_chunk(self, self.G[b0 + executed], dts,
                                   retiring, busy_fin, busy_pk, arrivals,
                                   len(self._queue))
        self.beat = b0 + executed

    def _host_drained(self) -> bool:
        return (self.idx >= self.n and not self._queue
                and not (self.m["sst"] > 0).any())

    def _ensure_queue(self, K: int) -> None:
        """Pre-size the queue for every request that can be queued during
        the next K beats: the current backlog plus the chunk window's
        arrivals (the trace is known, so in-kernel overflow is impossible
        and the kernel needs no queue-growth path)."""
        hi = int(np.searchsorted(self.arrival,
                                 self.G[min(self.beat + K,
                                            self.total_beats)],
                                 side="right")) if self.n else 0
        need = len(self._queue) + max(hi - self.idx, 0)
        while self.qcap < need:
            self.qcap = min(self.qcap * 2, max(self.n, 1))

    def step_prepare(self):
        """One lockstep round's host half: process the boundary and return
        the packed state + chunk length (0 when this sim is finished)."""
        if self.done:
            return self._pack(0), 0
        self._boundary()
        K = self._chunk_len()
        self._ensure_queue(K)
        self._arr0 = self.idx
        return self._pack(K), K

    def step_absorb(self, out) -> None:
        if self.done:
            return
        executed, busy_pk, busy_fin, empty_at = self._pull(out)
        if executed == 0:
            raise RuntimeError("chunked kernel made no progress")
        self._settle(executed, busy_pk, busy_fin, empty_at,
                     self.idx - self._arr0)
        if self.beat >= self.total_beats or self._host_drained():
            self.done = True

    def run(self) -> None:
        def mk_kern():
            return _chunk_kernel(self.n, self.W_cap, self.B, self.qcap,
                                 self.hb, self.gamma,
                                 float(self.slo.ttft),
                                 float(self.slo.atgt), self.policy_name,
                                 batched=False, edf=self.edf,
                                 tagged=self.tagged)

        def call(kern, st):
            m = self.m
            return kern(st, self.arrival, self.l_in, self.l_real,
                        m["s_lo"], m["s_tds"], m["s_tf1"], m["s_tpe"],
                        self.rank_r, self.ttft_r, self.atgt_r)

        sig = None
        kern = None
        with jax.enable_x64(True):
            while not self.done:
                st, K = self.step_prepare()
                cur = (self.W_cap, self.B, self.qcap)
                if cur != sig:    # shape growth: new compiled variant
                    kern, sig = mk_kern(), cur
                out = call(kern, st)
                # slot exhaustion: regrow and re-run the chunk — the
                # kernel is pure and mirrors are untouched until absorb,
                # so re-execution replays the identical decision stream
                while bool(out["ovf"]) and self.B < self.Bmax:
                    self._ensure_rows(self.B * 2)
                    kern = mk_kern()
                    sig = (self.W_cap, self.B, self.qcap)
                    st = self._pack(K)
                    out = call(kern, st)
                self.step_absorb(out)

    # ---- results -----------------------------------------------------------

    def finish(self):
        """Flush lane-resident and queued re-entrant rows into the
        per-request outputs; returns (l_out, tds, t_first, t_fin,
        t_preempted) arrays."""
        m = self.m
        t_pre = np.full(self.n, np.nan)
        for w, slot in zip(*np.nonzero(m["sst"] > 0)):
            r = int(m["rid"][w, slot])
            m["o_lo"][r] = m["rlo"][w, slot]
            m["o_tds"][r] = m["rtds"][w, slot]
            m["o_tf1"][r] = m["rtf1"][w, slot]
            t_pre[r] = m["rtpe"][w, slot]
        for r in self._queue:
            m["o_lo"][r] = m["s_lo"][r]
            m["o_tds"][r] = m["s_tds"][r]
            m["o_tf1"][r] = m["s_tf1"][r]
            t_pre[r] = m["s_tpe"][r]
        return m["o_lo"], m["o_tds"], m["o_tf1"], m["o_tfn"], t_pre


def _pooled_report(sim: _PooledSim, writeback: bool):
    o_lo, o_tds, o_tf1, o_tfn, t_pre = sim.finish()
    if writeback:
        for pos, r in enumerate(sim.trace):
            r.l_pred = int(sim.l_real[pos])
            r.l_out = int(o_lo[pos])
            r.t_decode_spent = float(o_tds[pos])
            tf = o_tf1[pos]
            r.t_first_token = None if math.isnan(tf) else float(tf)
            tp = t_pre[pos]
            r.t_preempted = None if math.isnan(tp) else float(tp)
            pn = int(sim.h_pn[pos])
            if pn:
                r.preempt_count += pn
            te = o_tfn[pos]
            if not math.isnan(te):
                r.t_finish = float(te)
                r.state = ReqState.FINISHED
    rep = _report_from_arrays(sim.scenario, sim.specs0, len(sim.specs0),
                              sim.arrival, sim.l_real, o_lo, o_tds, o_tf1,
                              o_tfn)
    pool = sim.pool
    if sim.managed:
        pol = sim.scaling_policy
        rep.scaling = getattr(pol, "name", type(pol).__name__)
        rep.peak_workers = pool.peak
        rep.gpu_seconds = pool.gpu_s
        rep.gpu_cost = pool.gpu_s
        rep.spot_gpu_seconds = pool.spot_gpu_s
        rep.epochs = {"serve": pool.epochs}
    else:
        rep.peak_workers = sim.init_W
        # every worker that served counts, including reclaimed ones
        rep.gpu_cost = sum(ln.spec.n_accelerators
                           for ln in pool.workers) + pool.retired_cost
    rep.preempted_workers = pool.killed
    rep.drained_ok = pool.drained_ok
    rep.requeued = pool.requeued
    rep.moves = 0
    rep.beats = sim.beat        # benchmark side channel (not in row())
    if writeback and sim.scenario.tenants is not None:
        from repro.serving.tenants import tenant_attainment, tenant_rows
        rep.attainment = tenant_attainment(sim.trace)
        rep.tenant_rows = tenant_rows(sim.trace,
                                      list(sim.scenario.tenants),
                                      rep.gpu_cost)
    return rep


def _run_pooled(scenario, seed: Optional[int] = None):
    sim = _PooledSim(scenario, seed)
    sim.run()
    return _pooled_report(sim, writeback=True)


def run_colocated_jax(scenario, seed: Optional[int] = None):
    """Run a colocated ``Scenario`` on the compiled engine, mutate the
    trace's ``Request`` objects with the outcome (the same contract as the
    other engines) and return the ``RunReport``. Also returns the executed
    beat count via the report-side channel ``rep.beats`` attribute used by
    the benchmarks."""
    from repro.serving import api

    scenario = api.resolve_scenario(scenario)
    specs = check_jax_envelope(scenario)
    trace = scenario.materialize()
    check_trace_session_free(trace)
    ordered, arrival, l_in, l_real = _trace_arrays(trace)
    multi = scenario.tenants is not None and len(scenario.tenants) > 1
    if len(ordered) == 0:
        if not _legacy_ok(scenario, specs):
            # pooled fleets still accrue billing/epochs on an empty trace;
            # the bit-for-bit numpy engine handles that without a kernel
            from repro.serving.fastsim import run_colocated_vectorized
            return run_colocated_vectorized(scenario, seed)
        # nothing to simulate: XLA rejects gathers into a size-0 trace
        # axis, and the reference drains immediately anyway
        empty = np.array([])
        rep = _report_from_arrays(scenario, specs, len(specs), empty,
                                  empty, empty, empty, empty, empty)
        rep.beats = 0
        return rep
    if not _legacy_ok(scenario, specs):
        # KV pressure / po2 / managed fleets / spot markets: the chunked
        # kernel with the host-side pool driver
        return _run_pooled(scenario, seed)
    rank_r, ttft_r, atgt_r, tagged = _tenant_arrays(ordered)
    # x64 is scoped, not a process-global flag: the serving models run in
    # jax's default 32-bit mode and must not see this engine's precision
    with jax.enable_x64(True):
        fn = _kernel_for(scenario, specs, trace, batched=False,
                         edf=multi, tagged=tagged)
        l_out, tds, t_first, t_fin, beats = (
            np.asarray(x) for x in fn(arrival, l_in, l_real, len(specs),
                                      rank_r, ttft_r, atgt_r))
    for pos, r in enumerate(ordered):
        r.l_pred = int(l_real[pos])
        r.l_out = int(l_out[pos])
        r.t_decode_spent = float(tds[pos])
        tf = t_first[pos]
        r.t_first_token = None if math.isnan(tf) else float(tf)
        te = t_fin[pos]
        if not math.isnan(te):
            r.t_finish = float(te)
            r.state = ReqState.FINISHED
    rep = _report_from_arrays(scenario, specs, len(specs), arrival, l_real,
                              l_out, tds, t_first, t_fin)
    rep.beats = int(beats)      # benchmark side channel (not in row())
    if scenario.tenants is not None:
        from repro.serving.tenants import tenant_attainment, tenant_rows
        rep.attainment = tenant_attainment(ordered)
        rep.tenant_rows = tenant_rows(ordered, list(scenario.tenants),
                                      rep.gpu_cost)
    return rep


def run_candidate_batch(scenarios) -> List:
    """Evaluate a batch of fleet-size candidates of the SAME workload /
    spec / policy in one vmapped compiled call — the whole bracket of
    ``optimize``'s search at once. Returns one ``RunReport`` per scenario
    (candidate traces are not mutated; the search only reads reports —
    which is also why multi-tenant candidates keep the planning-SLO
    headline attainment and carry no per-tenant rows: ``optimize``
    evaluates multi-tenant scenarios sequentially instead)."""
    from repro.serving import api

    if not scenarios:
        return []
    scenarios = [api.resolve_scenario(sc) for sc in scenarios]
    spec_lists = [check_jax_envelope(sc) for sc in scenarios]
    if not all(_legacy_ok(sc, sl)
               for sc, sl in zip(scenarios, spec_lists)):
        # pooled candidates carry host-side fleet state machines that the
        # fleet-size vmap cannot batch; run them through the chunked
        # driver one at a time (each still amortizes its kernel)
        return [run_colocated_jax(sc) for sc in scenarios]
    base = scenarios[0]
    base_spec = spec_lists[0][0]

    def coef_key(s):
        return (s.perf.prefill.k1, s.perf.prefill.c1, s.perf.decode.k2,
                s.perf.decode.c2, s.perf.decode.c3, s.max_batch,
                s.n_accelerators)

    for sl in spec_lists:
        if any(coef_key(s) != coef_key(base_spec) for s in sl):
            # vmap shares one coefficient set across the batch
            raise ValueError("run_candidate_batch needs homogeneous "
                             "candidates of one worker spec")
    W_max = max(len(sl) for sl in spec_lists)
    trace = base.materialize()
    check_trace_session_free(trace)
    _ordered, arrival, l_in, l_real = _trace_arrays(trace)
    multi = base.tenants is not None and len(base.tenants) > 1
    rank_r, ttft_r, atgt_r, tagged = _tenant_arrays(_ordered)
    padded = [base_spec] * W_max
    n_active = np.array([len(sl) for sl in spec_lists], dtype=np.int64)
    with jax.enable_x64(True):
        fn = _kernel_for(base, padded, trace, batched=True,
                         edf=multi, tagged=tagged)
        l_out, tds, t_first, t_fin, beats = (
            np.asarray(x) for x in fn(arrival, l_in, l_real, n_active,
                                      rank_r, ttft_r, atgt_r))
    reps = []
    for i in range(len(scenarios)):
        rep = _report_from_arrays(base, padded, int(n_active[i]), arrival,
                                  l_real, l_out[i], tds[i], t_first[i],
                                  t_fin[i])
        rep.beats = int(beats[i])   # benchmark side channel
        reps.append(rep)
    return reps


def run_policy_candidate_batch(scenarios) -> List:
    """Evaluate a batch of policy-knob candidates (same workload and spec
    family, differing theta / scaling parameters) in lockstep: each round
    advances every live candidate's next chunk through ONE vmapped
    compiled call, then settles each candidate's fleet boundary on the
    host. Finished candidates ride along with zero-length chunks until the
    batch drains. Candidate traces are never mutated; the policy search
    only reads the returned reports."""
    if not scenarios:
        return []
    if len(scenarios) == 1:
        sim = _PooledSim(scenarios[0])
        sim.run()
        return [_pooled_report(sim, writeback=False)]
    sims = [_PooledSim(sc) for sc in scenarios]
    s0 = sims[0]
    homog = all(
        s.n == s0.n and s.B == s0.B and s.Bmax == s0.Bmax
        and s.hb == s0.hb
        and s.gamma == s0.gamma and s.policy_name == s0.policy_name
        and float(s.slo.ttft) == float(s0.slo.ttft)
        and float(s.slo.atgt) == float(s0.slo.atgt)
        and s.edf == s0.edf and s.tagged == s0.tagged
        for s in sims[1:])
    if not homog:
        # heterogeneous statics cannot share one compiled kernel
        for s in sims:
            s.run()
        return [_pooled_report(s, writeback=False) for s in sims]
    with jax.enable_x64(True):
        while not all(s.done for s in sims):
            lens = []
            for s in sims:
                if s.done:
                    lens.append(0)
                    continue
                s._boundary()
                lens.append(s._chunk_len())
                s._arr0 = s.idx
            cap = max(s.W_cap for s in sims)
            for s, k in zip(sims, lens):  # lockstep: one shared lane axis
                s._ensure_cap(cap)
                s._ensure_queue(k)
            qc = max(s.qcap for s in sims)
            for s in sims:                # ...and a shared queue axis
                s.qcap = qc

            def round_out():
                sts = [s._pack(k) for s, k in zip(sims, lens)]
                stb = {k: np.stack([np.asarray(st[k]) for st in sts])
                       for k in sts[0]}
                ops = {k: np.stack([s.m[k] for s in sims])
                       for k in ("s_lo", "s_tds", "s_tf1", "s_tpe")}
                kern = _chunk_kernel(s0.n, cap, s0.B, s0.qcap,
                                     s0.hb, s0.gamma,
                                     float(s0.slo.ttft),
                                     float(s0.slo.atgt),
                                     s0.policy_name, batched=True,
                                     edf=s0.edf, tagged=s0.tagged)
                out = kern(stb, s0.arrival, s0.l_in, s0.l_real,
                           ops["s_lo"], ops["s_tds"], ops["s_tf1"],
                           ops["s_tpe"], s0.rank_r, s0.ttft_r, s0.atgt_r)
                return {k: np.asarray(v) for k, v in out.items()}

            outs = round_out()
            # slot exhaustion in any candidate: regrow every sim to the
            # shared larger capacity and re-run the round
            while outs["ovf"].any() and s0.B < s0.Bmax:
                newB = min(s0.B * 2, s0.Bmax)
                for s in sims:
                    s._ensure_rows(newB)
                outs = round_out()
            for ci, s in enumerate(sims):
                s.step_absorb({k: v[ci] for k, v in outs.items()})
    return [_pooled_report(s, writeback=False) for s in sims]
