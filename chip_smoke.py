#!/usr/bin/env python3
"""Smoke test of the served path on TPU chips, at full model width.

    python chip_smoke.py               # one chip: phases 1-4 below
    python chip_smoke.py --chips 4     # four workers on four chips, only

One chip serves phi4-mini-3.8b at its published widths (all 32 layers, bf16
weights drawn from ``--seed``) on one worker, through ``serve_trace``, the
loop ``repro.launch.serve`` runs (submit -> heartbeat -> run_until_drained):

  1. served: 8 requests (prompts of 100-1000 tokens in three power-of-two
     buckets, 32 output tokens each) must all finish with every token, the
     compiled prefill program must call the flash-attention and RMSNorm
     Pallas kernels, and the decode program the paged-decode and RMSNorm
     ones;
  2. kernel: the compiled paged-decode kernel against ``paged_decode_ref``
     at the engine's shapes;
  3. oracle: the engine's prefill and first-decode logits of two served
     requests against the model's own ``LM.prefill`` / ``LM.decode_step``;
  4. planner: README cell 9's fleet over a few simulated minutes,
     ``engine="jax"`` against ``engine="reference"`` on the same trace.

``--chips 4`` runs four workers, one per chip, behind Aladdin placement on
16 requests, and checks that each worker served requests from its own
device with first-token logits equal to a one-worker run's.

Every phase prints its result. The last stdout line is
``{"ok": true, "device": {...}}`` only when every phase passed on a TPU;
otherwise the script exits nonzero without printing it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from collections import Counter
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402

from repro.configs import get_arch                             # noqa: E402
from repro.configs.base import ArchConfig                      # noqa: E402
from repro.core import (A100_80G, PAPER_SLOS, PerfModel,      # noqa: E402
                        make_worker_spec)
from repro.core.request import ReqState, Request               # noqa: E402
from repro.core.slo import SLO                                 # noqa: E402
from repro.kernels import compiled_kernels                     # noqa: E402
from repro.kernels.decode_attention import (                   # noqa: E402
    paged_decode_attention_pallas, paged_decode_ref)
from repro.launch.serve import serve_trace, setup_compile_cache  # noqa: E402
from repro.models.model import LM                              # noqa: E402
from repro.serving import (Colocated, FleetSpec, PoolSpec,     # noqa: E402
                           Scenario, WorkloadConfig, clone_trace,
                           diurnal_trace, run)
from repro.serving.cluster import ClusterConfig, ServingCluster  # noqa: E402
from repro.serving.engine import (EngineConfig, PagedEngine,   # noqa: E402
                                  decode_step, prefill_step, prompt_bucket)

# tolerances, fixed before the first chip run (PERF.md, Findings):
KERNEL_TOL = 1e-2    # |kernel - ref| <= tol * (1 + |ref|): the MXU may run
                     # f32 matmuls as bf16 passes; a layout bug is O(1)
LOGIT_TOL = 1e-1     # ||engine - model|| / ||model|| over the vocab: the
                     # engine keeps f32 activations and KV, the model bf16
SHARD_TOL = 1e-4     # same program on another chip of the same kind
CLOCK_REL = 1e-12    # README envelope of the jax planning core: clocks
REPORT_REL = 1e-9    # ... and report floats; integer outputs exact
CLOCK_REL_TPU = 5e-12  # clocks on a TPU, whose float64 is not IEEE double:
                       # between a sound run (2.085e-12) and a planted 1e-11
                       # heartbeat drift (PERF.md, Findings)
KERNELS = {"prefill": "flash_attention", "decode": "paged_decode_attention"}


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    arch: ArchConfig
    engine: EngineConfig
    prompt_lens: Tuple[int, ...]
    n_out: int
    four_chip_lens: Tuple[int, ...]
    four_chip_out: int
    planner_seconds: float
    seed: int = 0

    @classmethod
    def full(cls, seed: int = 0) -> "SmokeConfig":
        return cls(
            arch=get_arch("phi4-mini-3.8b"),
            engine=EngineConfig(max_batch=8, page_size=16, n_pages=512,
                                max_pages_per_seq=128),
            prompt_lens=(130, 200, 300, 450, 600, 750, 900, 1000),
            n_out=32,
            four_chip_lens=tuple(range(140, 501, 24)),      # 16 prompts
            four_chip_out=8, planner_seconds=240.0, seed=seed)


@dataclasses.dataclass
class Check:
    name: str
    ok: bool
    detail: str

    def report(self) -> "Check":
        print(f"[{self.name}] {'PASS' if self.ok else 'FAIL'} {self.detail}",
              flush=True)
        return self


def make_requests(lens: Sequence[int], n_out: int, vocab: int,
                  seed: int) -> List[Request]:
    rng = np.random.default_rng(seed)
    out = []
    for n in lens:
        r = Request(l_in=n, l_pred=0, l_real=n_out, arrival=0.0)
        r.tokens = [int(t) for t in rng.integers(2, vocab, n)]
        out.append(r)
    return out


def _tap(engines: Sequence[PagedEngine], keep: int
         ) -> Dict[int, List[np.ndarray]]:
    """Record the first ``keep`` logits rows each request is sampled from,
    by request id, whichever engine serves it."""
    seen: Dict[int, List[np.ndarray]] = {}

    def on_logits(req, logits):
        rows = seen.setdefault(req.id, [])
        if len(rows) < keep:
            rows.append(np.asarray(logits, np.float32))
    for e in engines:
        e.on_logits = on_logits
    return seen


def _cluster(cfg: SmokeConfig, params, engine_cfg: EngineConfig,
             n_workers: int) -> ServingCluster:
    return ServingCluster(cfg.arch, params, SLO(10.0, 2.0),
                          engine_cfg=engine_cfg,
                          cfg=ClusterConfig(policy="aladdin"),
                          n_workers=n_workers)


def _served_ok(reqs: Sequence[Request], n_out: int) -> bool:
    return all(r.state == ReqState.FINISHED and r.l_out == n_out
               and len(r.tokens) == r.l_in + n_out for r in reqs)


def _peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def kernel_calls(engine: PagedEngine, bucket: int) -> Dict[str, Counter]:
    """Pallas kernels, by name, that the engine's compiled prefill and
    decode programs call."""
    kw = dict(arch=engine.arch, use_pallas=engine.use_pallas,
              interpret=engine.cfg.interpret)
    pre = prefill_step.lower(engine.params, jnp.zeros((1, bucket), jnp.int32),
                             bucket - 1, **kw)
    b = engine.cfg.max_batch
    dec = decode_step.lower(
        engine.params, engine.kv_k, engine.kv_v,
        jnp.asarray(engine.block_tables), jnp.asarray(engine.lengths),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool),
        page_size=engine.cfg.page_size, **kw)
    return {step: compiled_kernels(lowered.compile().as_text())
            for step, lowered in (("prefill", pre), ("decode", dec))}


# ---- phase 1 ----------------------------------------------------------------
def served_phase(cfg: SmokeConfig, params, expect_kernels: bool
                 ) -> Tuple[Check, List[Request], Dict[int, List]]:
    cluster = _cluster(cfg, params, cfg.engine, n_workers=1)
    eng = next(iter(cluster.workers.values())).engine
    taps = _tap([eng], keep=2)
    reqs = make_requests(cfg.prompt_lens, cfg.n_out, cfg.arch.vocab,
                         cfg.seed)
    pool = eng.kv_k.nbytes + eng.kv_v.nbytes
    print(f"[served] kv pool {pool} bytes (f32, {cfg.engine.n_pages} pages "
          f"x {cfg.engine.page_size}) on {eng.device}; buckets "
          f"{sorted({prompt_bucket(n) for n in cfg.prompt_lens})}",
          flush=True)
    t0 = time.perf_counter()
    warm = serve_trace(cluster, reqs)
    wall = time.perf_counter() - t0 - warm
    ok = _served_ok(reqs, cfg.n_out) and len(cluster.finished) == len(reqs)
    calls = kernel_calls(eng, prompt_bucket(cfg.prompt_lens[0]))
    if expect_kernels:      # the attention kernel and RMSNorm in each step
        ok = ok and all(calls[step][name] > 0 and calls[step]["rmsnorm"] > 0
                        for step, name in KERNELS.items())
    detail = (f"{len(cluster.finished)}/{len(reqs)} finished, "
              f"{sum(r.l_out for r in reqs)} tokens; compile (warm-up) "
              f"{warm:.2f}s; serve {wall:.3f}s host clock; Pallas kernels "
              + "; ".join(f"{step} {dict(sorted(c.items()))}"
                          for step, c in calls.items())
              + f"; use_pallas={eng.use_pallas}; "
              f"peak_bytes_in_use={_peak_bytes(eng.device)}")
    return Check("served", ok, detail), reqs, taps


# ---- phase 2 ----------------------------------------------------------------
def kernel_phase(cfg: SmokeConfig) -> Check:
    a, e = cfg.arch, cfg.engine
    hd = a.resolved_head_dim
    kq, kk, kv, kb, kl = jax.random.split(jax.random.key(cfg.seed + 1), 5)
    pool = (e.n_pages, a.n_kv_heads, e.page_size, hd)
    q = jax.random.normal(kq, (e.max_batch, a.n_heads, hd), jnp.float32)
    kp = jax.random.normal(kk, pool, jnp.float32)
    vp = jax.random.normal(kv, pool, jnp.float32)
    bt = jax.random.randint(kb, (e.max_batch, e.max_pages_per_seq), 1,
                            e.n_pages, jnp.int32)
    lengths = jax.random.randint(kl, (e.max_batch,), 1,
                                 e.max_pages_per_seq * e.page_size + 1,
                                 jnp.int32)
    out = np.asarray(paged_decode_attention_pallas(
        q, kp, vp, bt, lengths, interpret=e.interpret))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(paged_decode_ref(q, kp, vp, bt, lengths))
    err = float(np.max(np.abs(out - ref) / (1.0 + np.abs(ref))))
    ok = bool(np.isfinite(out).all()) and err <= KERNEL_TOL
    return Check("kernel", ok,
                 f"q {q.shape} pool {pool} f32, block table "
                 f"{tuple(bt.shape)}: max |kernel-ref|/(1+|ref|) = {err:.3e} "
                 f"(tol {KERNEL_TOL})")


# ---- phase 3 ----------------------------------------------------------------
def oracle_phase(cfg: SmokeConfig, params, reqs: Sequence[Request],
                 taps: Dict[int, List[np.ndarray]]) -> Check:
    """Engine logits of the shortest and longest served request against the
    model's own prefill / decode_step (fed the engine's first token)."""
    model = LM(cfg.arch)
    s_max = max(r.l_in for r in reqs) + 8
    prefill = jax.jit(lambda p, t: model.prefill(p, tokens=t, s_max=s_max))
    step = jax.jit(model.decode_step)
    worst, lines = 0.0, []
    by_len = sorted(reqs, key=lambda r: r.l_in)
    for r in (by_len[0], by_len[-1]):
        got = taps.get(r.id, [])
        if len(got) < 2:
            return Check("oracle", False, f"request {r.id}: engine logits "
                         f"not captured ({len(got)} rows)")
        logits, cache = prefill(params, jnp.asarray([r.tokens[:r.l_in]]))
        want = [np.asarray(logits[0], np.float32)]
        logits, _ = step(params, cache, jnp.asarray([r.tokens[r.l_in]]))
        want.append(np.asarray(logits[0], np.float32))
        for tag, g, w in zip(("prefill", "decode1"), got, want):
            rel = float(np.linalg.norm(g - w) / np.linalg.norm(w))
            cos = float(g @ w / (np.linalg.norm(g) * np.linalg.norm(w)))
            worst = max(worst, rel) if np.isfinite(rel) else np.inf
            lines.append(f"l_in={r.l_in} {tag}: rel_l2={rel:.3e} "
                         f"cos={cos:.6f}")
    return Check("oracle", worst <= LOGIT_TOL,
                 "; ".join(lines) + f" (tol {LOGIT_TOL})")


# ---- phase 4 ----------------------------------------------------------------
def cell9_trace(seconds: float) -> List[Request]:
    """README cell 9's diurnal trace cut to ``seconds`` of simulated time."""
    return diurnal_trace(WorkloadConfig(mean_rate=11.574, duration=seconds,
                                        seed=5), period=8640.0)


def planner_run(trace: Sequence[Request], engine: str,
                heartbeat: float = 0.02):
    """README cell 9's fleet (24 llama2-70b workers, 20 ms heartbeat) on a
    copy of ``trace``. Returns (its requests, report, host seconds)."""
    slo = PAPER_SLOS["llama2-70b"]
    spec = make_worker_spec(get_arch("llama2-70b"), A100_80G, slo,
                            mean_context=450.0)
    fast = dataclasses.replace(
        spec, max_batch=32,
        perf=PerfModel(prefill=spec.perf.prefill, decode=spec.perf.decode))
    reqs = clone_trace(trace)
    t0 = time.perf_counter()
    rep = run(Scenario(workload=reqs, fleet=FleetSpec([PoolSpec(fast, 24)]),
                       slo=slo, topology=Colocated(heartbeat=heartbeat),
                       engine=engine))
    return reqs, rep, time.perf_counter() - t0


def planner_errors(ref, got) -> Tuple[int, float, float]:
    """(integer mismatches, worst relative per-request clock error, worst
    relative report-float error) of run ``got`` against run ``ref``."""
    (ref_reqs, ref_rep, _), (got_reqs, got_rep, _) = ref, got
    bad_int, worst = 0, 0.0

    def rel(a, b):
        return abs(a - b) / max(abs(a), 1e-300)
    key = lambda r: r.arrival                                   # noqa: E731
    for a, b in zip(sorted(ref_reqs, key=key), sorted(got_reqs, key=key)):
        bad_int += (a.l_out != b.l_out) + ((a.t_finish is None)
                                           != (b.t_finish is None))
        for x, y in ((a.t_first_token, b.t_first_token),
                     (a.t_finish, b.t_finish),
                     (a.t_decode_spent, b.t_decode_spent)):
            if x is not None and y is not None:
                worst = max(worst, rel(x, y))
    ra, ga = ref_rep.row(), got_rep.row()
    worst_row = 0.0
    for k, v in ra.items():
        if isinstance(v, float):
            if not (np.isnan(v) and np.isnan(ga[k])):
                worst_row = max(worst_row, rel(v, ga[k])
                                if abs(v) > 1e-12 else abs(ga[k]))
        elif v != ga[k]:
            bad_int += 1
    return bad_int, worst, worst_row


def planner_phase(seconds: float) -> Check:
    """README cell 9 cut to ``seconds`` of simulated time: the jax core on
    the default device against the reference."""
    trace = cell9_trace(seconds)
    ref, got = planner_run(trace, "reference"), planner_run(trace, "jax")
    bad_int, worst, worst_row = planner_errors(ref, got)
    tpu = jax.devices()[0].platform == "tpu"
    clock_tol = CLOCK_REL_TPU if tpu else CLOCK_REL
    ok = bad_int == 0 and worst <= clock_tol and worst_row <= REPORT_REL
    return Check("planner", ok,
                 f"{len(trace)} requests, {seconds:.0f}s simulated: "
                 f"integer mismatches {bad_int}; worst clock rel "
                 f"{worst:.3e} (tol {clock_tol:.0e}); worst report rel "
                 f"{worst_row:.3e} (tol {REPORT_REL:.0e}); attainment "
                 f"{got[1].row()['attainment']}; wall reference "
                 f"{ref[2]:.2f}s jax {got[2]:.2f}s (host clock, jax incl. "
                 "compile)")


# ---- --chips 4 --------------------------------------------------------------
def four_chip_phase(cfg: SmokeConfig, params, n_chips: int) -> Check:
    """``n_chips`` workers, one per device, against a one-worker run of the
    same requests in this process."""
    ecfg = dataclasses.replace(cfg.engine, max_batch=len(cfg.four_chip_lens)
                               // n_chips)
    first: Dict[str, List[np.ndarray]] = {}
    placed: Dict[int, int] = {}
    devices_ok = True
    for label, n in (("one", 1), ("many", n_chips)):
        cluster = _cluster(cfg, params, ecfg, n_workers=n)
        reqs = make_requests(cfg.four_chip_lens, cfg.four_chip_out,
                             cfg.arch.vocab, cfg.seed)
        taps = _tap([w.engine for w in cluster.workers.values()], keep=1)
        warm = serve_trace(cluster, reqs)
        first[label] = [taps.get(r.id, [None])[0] for r in reqs]
        if not _served_ok(reqs, cfg.four_chip_out):
            return Check("four_chip", False, f"{label}-worker run: not every "
                         "request finished")
        if label == "many":
            workers = list(cluster.workers.values())
            devs = [w.engine.device for w in workers]
            for w in workers:
                arrays = jax.tree.leaves(w.engine.params) + [w.engine.kv_k,
                                                              w.engine.kv_v]
                devices_ok &= all(x.devices() == {w.engine.device}
                                  for x in arrays)
                placed[w.id] = sum(r.worker == w.id for r in reqs)
            print(f"[four_chip] devices {[str(d) for d in devs]}; requests "
                  f"per worker {placed}; peak_bytes_in_use "
                  f"{[_peak_bytes(d) for d in devs]}; warm-up {warm:.2f}s",
                  flush=True)
            distinct = len(set(devs)) == n_chips
        del cluster, taps
        gc.collect()
    diffs = [float(np.max(np.abs(a - b)) / np.max(np.abs(a)))
             if a is not None and b is not None else np.inf
             for a, b in zip(first["one"], first["many"])]
    all_served = all(v > 0 for v in placed.values())
    ok = distinct and devices_ok and all_served and max(diffs) <= SHARD_TOL
    return Check("four_chip", ok,
                 f"{n_chips} workers on distinct devices={distinct}, arrays "
                 f"on own device={devices_ok}, every worker served="
                 f"{all_served}; first-token "
                 f"logits vs one worker: max rel diff {max(diffs):.3e} "
                 f"(tol {SHARD_TOL})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax sees {devices[0].platform}); "
              "nothing measured", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    print(f"compile cache: {setup_compile_cache()}")
    cfg = SmokeConfig.full(args.seed)
    a = cfg.arch
    print(f"model {a.name}: {a.n_layers} layers, d_model {a.d_model}, heads "
          f"{a.n_heads}/{a.n_kv_heads} x {a.resolved_head_dim}, d_ff "
          f"{a.d_ff}, vocab {a.vocab}, {a.param_dtype} weights (seed "
          f"{cfg.seed}); {len(devices)} x {devices[0].device_kind}",
          flush=True)
    t0 = time.perf_counter()
    params = jax.block_until_ready(LM(a).init(jax.random.key(cfg.seed)))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"params {n_bytes} bytes, init {time.perf_counter() - t0:.2f}s",
          flush=True)

    if args.chips > 1:
        checks = [four_chip_phase(cfg, params, args.chips).report()]
    else:
        served, reqs, taps = served_phase(cfg, params, expect_kernels=True)
        checks = [served.report(), kernel_phase(cfg).report(),
                  oracle_phase(cfg, params, reqs, taps).report()]
        del params
        gc.collect()
        checks.append(planner_phase(cfg.planner_seconds).report())
    if not all(c.ok for c in checks):
        print("chip_smoke: FAILED " + ", ".join(c.name for c in checks
                                               if not c.ok), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}))     # the devices the phases used
    return 0


if __name__ == "__main__":
    sys.exit(main())
