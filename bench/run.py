#!/usr/bin/env python3
"""Chip benchmark of the served path: one run of one cell.

    python3 bench/run.py --workload phi4-conv --seed 7 --seconds 51 --trace 0

The cell's configuration, its model family, traffic mix and per-layer
metrics are found by name (``harness.py``). A run makes the weights from
``--seed`` on the device, builds a ``ServingCluster`` of one worker per
chip, warms up every program the mix uses, fills a closed loop, and then
drives ``submit`` and ``heartbeat`` for ``--seconds``. Token times are
stamped after every engine step. With ``--trace 1`` the window runs under
the profiler and the result's metrics are the per-layer ones. After the
window the program is freed and the served tokens of a sample of finished
requests are checked against the family's float32 reference
(``reference.py``).

The last stdout line is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced). A run
that finds no TPU, or fewer chips than the cell asks for, exits 3 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                                # noqa: E402
import dataclasses                                             # noqa: E402
import gc                                                      # noqa: E402
import json                                                    # noqa: E402
import math                                                    # noqa: E402
import os                                                      # noqa: E402
import shutil                                                  # noqa: E402
import sys                                                     # noqa: E402
from pathlib import Path                                       # noqa: E402
from typing import Dict, List, Optional                        # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np                                             # noqa: E402

from bench import harness, weights                             # noqa: E402
from bench.reference import Served, served_readings            # noqa: E402
from bench.traffic import Traffic                              # noqa: E402

NO_CHIP = 3


# ---- the program under test -------------------------------------------------
def program_arch(cfg: dict, family):
    """The registered architecture the configuration runs, with its
    overrides; the family refuses it where a size differs from the
    configuration file."""
    from repro.configs import get_arch
    prog = cfg["program"]
    arch = dataclasses.replace(get_arch(prog["arch"]),
                               **prog.get("overrides", {}))
    family.check(cfg, arch)
    return arch


def build_cluster(cell: harness.Cell, arch, params, traffic: Traffic):
    """One worker per chip. The KV pool is the configuration's byte budget
    in pages of the engine's own size; its slots are as many as the pool
    holds requests of the mix at their mean final context."""
    from repro.core.slo import SLO
    from repro.serving.cluster import ClusterConfig, ServingCluster
    from repro.serving.engine import EngineConfig, PagedEngine
    e, cfg = cell.config["engine"], cell.config
    probe = PagedEngine(arch, params, EngineConfig(
        max_batch=1, page_size=e["page_size"], n_pages=2,
        max_pages_per_seq=1))
    per_token = probe.kv_bytes_per_token      # the engine's own KV bytes
    del probe
    n_pages = int(e["kv_pool_bytes"] // (e["page_size"] * per_token))
    max_batch = int((n_pages - 1)
                    // traffic.mean_context_pages(e["page_size"]))
    ecfg = EngineConfig(max_batch=max_batch, page_size=e["page_size"],
                        n_pages=n_pages,
                        max_pages_per_seq=e["max_pages_per_seq"],
                        max_new_tokens=e["max_new_tokens"])
    c = cfg["cluster"]
    return ServingCluster(
        arch, params, SLO(cfg["slo"]["ttft_s"], cfg["slo"]["atgt_s"]),
        engine_cfg=ecfg,
        cfg=ClusterConfig(policy=c["policy"],
                          heartbeat_iters=c["heartbeat_iters"],
                          enable_rebalance=c["enable_rebalance"]),
        n_workers=cell.chips)


# ---- what the benchmark records around the program --------------------------
@dataclasses.dataclass
class Step:
    t0: float
    prompts: List[int]       # prompt lengths prefilled in this step
    contexts: List[int]      # context each decoded sequence attended over

    @property
    def kind(self) -> str:
        return "prefill" if self.prompts else (
            "decode" if self.contexts else "idle")


class Recorder:
    """Token times and step records, from wrappers around each worker's
    ``engine.step``, and placement refusals by constraint."""

    def __init__(self, cluster, annotate):
        self.stamps: Dict[int, List[float]] = {}
        self.steps: List[Step] = []
        self.preempted = 0
        self.refused = {"b": 0, "c": 0, "d": 0, "e": 0}
        self.counting = False
        for w in cluster.workers.values():
            w.engine.step = self._wrap_step(w.engine, annotate)
            for c in self.refused:
                name = f"_constraint_{c}"
                setattr(w.state, name,
                        self._wrap_constraint(c, getattr(w.state, name)))

    def _wrap_constraint(self, c, fn):
        def check(reqs):
            ok = fn(reqs)
            if not ok and self.counting:
                self.refused[c] += 1
            return ok
        return check

    def _wrap_step(self, eng, annotate):
        inner = eng.step

        def step(now=None):
            cands = [r for r in eng.slots if r is not None] + eng.waiting
            before = [(r, r.l_out) for r in cands]
            with annotate("bench.engine_step"):
                t0 = time.perf_counter()
                done = inner(now)
                t1 = time.perf_counter()
            prompts, contexts = [], []
            for r, b in before:
                a = r.l_out
                stamps = self.stamps.setdefault(r.id, [])
                if a > b:
                    (prompts if b == 0 else contexts).append(
                        r.l_in if b == 0 else r.l_in + b)
                    stamps.extend([t1] * (a - b))
                elif a < b:             # preempted: its tokens come again
                    self.preempted += 1
                    del stamps[a:]
            self.steps.append(Step(t0, prompts, contexts))
            return done
        return step


# ---- warm-up and fill -------------------------------------------------------
def warm_up(cluster, lengths, family) -> None:
    """Compile (or load) every program the mix uses, on every worker, as
    the configuration's family asks of its engine (``warm_up``)."""
    for w in cluster.workers.values():
        family.warm_up(w.engine, lengths)


def all_decoding(cluster) -> bool:
    return all(all(r is not None and r.l_out >= 2 for r in w.engine.slots)
               for w in cluster.workers.values())


def busy(cluster) -> bool:
    return bool(cluster.queued) or any(
        w.engine.waiting or w.engine.running or w.state.new_batch
        for w in cluster.workers.values())


# ---- the window -------------------------------------------------------------
@dataclasses.dataclass
class Issued:
    req: object
    due: float
    client: int


def serve_window(cluster, traffic: Traffic, rec: Recorder, seconds: float,
                 annotate, fill_beats: int, on_open=None) -> dict:
    """Fill (closed loop), then drive the cluster for ``seconds``;
    ``on_open`` runs just before the window opens. Returns the issued
    requests, the window bounds, lateness and counts."""
    from repro.core.request import Request
    sizes = traffic.sizes()
    issued: List[Issued] = []
    late: List[float] = []
    beats = 0

    def make(due: float, client: int) -> Issued:
        l_in, l_out = next(sizes)
        r = Request(l_in=l_in, l_pred=0, l_real=l_out, arrival=due)
        r.tokens = traffic.prompt_tokens(l_in)
        it = Issued(r, due, client)
        issued.append(it)
        return it

    def submit(it: Issued) -> None:
        with annotate("bench.submit"):
            cluster.submit(it.req)
        late.append(time.perf_counter() - it.due)

    pending: List[Issued] = []
    by_id: Dict[int, Issued] = {}

    def reissue(done) -> None:
        """Closed loop: each finished request's client sends its next one,
        due when the last token came."""
        for r in done:
            it = make(rec.stamps[r.id][-1], by_id[r.id].client)
            by_id[it.req.id] = it
            pending.append(it)

    if traffic.closed:
        # set-up: fill until every slot decodes or, where outputs are too
        # short for that, every client has had a first token
        now = time.perf_counter()
        clients = traffic.clients(sum(w.engine.cfg.max_batch
                                      for w in cluster.workers.values()))
        for c in range(clients):
            it = make(now, c)
            by_id[it.req.id] = it
            submit(it)
        for _ in range(fill_beats):
            beats += 1
            reissue(cluster.heartbeat())
            for it in pending:
                submit(it)
            pending.clear()
            served = sum(1 for st in rec.stamps.values() if st)
            if all_decoding(cluster) or served >= clients:
                break
    filled = sum(r is not None for w in cluster.workers.values()
                 for r in w.engine.slots)
    late.clear()
    fill, beats = beats, 0
    if on_open is not None:
        on_open()
    t_open = time.perf_counter()
    t_end = t_open + seconds
    arrivals = None if traffic.closed else traffic.arrivals()
    nxt = None if traffic.closed else make(t_open + next(arrivals), -1)
    rec.counting = True
    with annotate("bench.window"):
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            with annotate("bench.generator"):
                due = [it for it in pending if it.due <= now]
                pending = [it for it in pending if it.due > now]
                while nxt is not None and nxt.due <= now:
                    due.append(nxt)
                    nxt = make(t_open + next(arrivals), -1)
            for it in due:
                submit(it)
            if not busy(cluster) and nxt is not None:
                time.sleep(max(0.0, min(nxt.due, t_end) - now))
                continue
            with annotate("bench.heartbeat"):
                done = cluster.heartbeat()
            beats += 1
            if traffic.closed:
                reissue(done)
    rec.counting = False
    if nxt is not None:
        issued.remove(nxt)                # never due inside the window
    for it in pending:
        issued.remove(it)
    return {"issued": issued, "t_open": t_open, "t_end": t_end,
            "late": late, "beats": beats, "fill": fill, "filled": filled}


# ---- end-to-end metrics -----------------------------------------------------
def p95(xs: List[float]) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), 95))


def beyond_p95(n: int) -> int:
    return n - math.ceil(0.95 * n)


def end_to_end(w: dict, rec: Recorder) -> dict:
    lo, hi = w["t_open"], w["t_end"]
    tokens, tpot, ttft, attempted = 0, [], [], 0
    for it in w["issued"]:
        st = rec.stamps.get(it.req.id, [])
        inside = [t for t in st if lo <= t <= hi]
        tokens += len(inside)
        if len(inside) >= 2:
            tpot.append((inside[-1] - inside[0]) / (len(inside) - 1))
        if lo <= it.due <= hi:
            attempted += 1
            first = st[0] if st and st[0] <= hi else hi
            ttft.append(first - it.due)
    return {"tokens": tokens, "tpot": tpot, "ttft": ttft,
            "attempted": attempted,
            "output_tok_s": tokens / (hi - lo),
            "tpot_p95_ms": 1e3 * p95(tpot) if tpot else None,
            "ttft_p95_s": p95(ttft) if ttft else None}


def failures(w: dict) -> int:
    """Finished requests that came back with a wrong token count."""
    from repro.core.request import ReqState
    return sum(1 for it in w["issued"]
               if it.req.state == ReqState.FINISHED
               and (it.req.l_out != it.req.l_real
                    or len(it.req.tokens) != it.req.l_in + it.req.l_real))


# ---- correctness ------------------------------------------------------------
def check_sample(w: dict, n: int, seed: int) -> List[Served]:
    """Finished requests drawn from the seed, with the longest served one
    and at least one from every worker."""
    from repro.core.request import ReqState
    done = [it.req for it in w["issued"] if it.req.state == ReqState.FINISHED]
    if not done:
        return []
    rng = np.random.default_rng([seed, 7])
    longest = max(done, key=lambda r: r.l_out)
    pick = {longest.id: longest}
    for wid in sorted({r.worker for r in done}):
        own = [r for r in done if r.worker == wid and r.id not in pick]
        if own:
            r = own[int(rng.integers(len(own)))]
            pick[r.id] = r
    rest = [r for r in done if r.id not in pick]
    for i in rng.permutation(len(rest))[:max(0, n - len(pick))]:
        pick[rest[i].id] = rest[i]
    return [Served(list(r.tokens), r.l_in) for r in pick.values()]


# ---- traced run -------------------------------------------------------------
class TracedRun:
    """What a per-layer metric reader sees: the reduced trace, the window's
    engine steps matched to their host spans, the configuration, peaks."""

    def __init__(self, trace, steps: List[Step], cfg: dict, peaks: dict):
        self.trace, self.cfg, self.peaks = trace, cfg, peaks
        spans = trace.spans("bench.engine_step")
        if len(spans) != len(steps):
            raise ValueError(f"{len(spans)} engine-step spans in the trace, "
                             f"{len(steps)} steps recorded")
        self.spans = list(zip(spans, steps))
        self.prompts = [s for _, st in self.spans for s in st.prompts]
        self.decode_contexts = [st.contexts for _, st in self.spans
                                if st.kind == "decode"]

    def step_spans(self, kind: str):
        return [sp for sp, st in self.spans if st.kind == kind]


def _no_annotation(name):
    import contextlib
    return contextlib.nullcontext()


# ---- main -------------------------------------------------------------------
def main(argv=None, root: Path = ROOT, require_chip: bool = True,
         peaks: Optional[dict] = None, control: Optional[str] = None) -> int:
    """One run. Tests pass ``require_chip=False`` and their own ``peaks``
    to drive the rest of a run on the CPU. ``control.py`` passes a lower
    precision of the reference (the ``mode`` of the family's
    ``logit_rows``): the tokens it puts first then stand in for the served
    ones in the verdict, which has to come out not correct."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload, root)

    import jax
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); jax "
              f"sees {len(devices)} {devices[0].platform} device(s). Nothing "
              "measured.", file=sys.stderr)
        return NO_CHIP
    if require_chip:       # a fixed path in the checkout: only the first
        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
            or str(root / ".jax_cache")              # run of a cell compiles
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = _compile_counter(jax)
    compiles.update(programs=[], backend=0, counting=False)

    cfg = cell.config
    peaks = peaks or harness.peaks(devices[0].device_kind, root)
    used = devices[:cell.chips]
    annotate = jax.profiler.TraceAnnotation if args.trace else \
        _no_annotation
    family = harness.family(cfg["family"], root)
    arch = program_arch(cfg, family)
    params = jax.block_until_ready(
        weights.make(family.shapes(cfg), args.seed))
    traffic = Traffic(cell.traffic, arch.vocab, args.seed)
    cluster = build_cluster(cell, arch, params, traffic)
    ecfg = cluster.engine_cfg
    rec = Recorder(cluster, annotate)
    warm_up(cluster, traffic.prompt_lengths(), family)
    print(f"[setup] {cell.name}: {arch.name} ({arch.n_layers} layers) on "
          f"{cell.chips} x {devices[0].device_kind}; engine max_batch "
          f"{ecfg.max_batch}, pages {ecfg.n_pages} x {ecfg.page_size}, "
          f"max_pages_per_seq {ecfg.max_pages_per_seq}; "
          f"{len(traffic.prompt_lengths())} prompt lengths warmed",
          file=sys.stderr, flush=True)

    trace_dir = root / ".bench_trace" / f"{cell.name}-{args.seed}"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)

    def on_open():
        if args.trace:
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=_profile_options(jax))
        compiles["counting"] = True

    w = serve_window(cluster, traffic, rec, args.seconds, annotate,
                     fill_beats=cfg["cluster"]["fill_beats"], on_open=on_open)
    if args.trace:
        jax.profiler.stop_trace()
    compiles["counting"] = False
    setup_s = w["t_open"] - T_START
    e2e = end_to_end(w, rec)
    n_failed = failures(w)
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in used]
    kernels = pallas_kernels(cluster, traffic, family) if require_chip \
        else {}
    window_steps = [s for s in rec.steps
                    if s.t0 >= w["t_open"] and s.t0 < w["t_end"]]
    n_steps = {k: sum(s.kind == k for s in window_steps)
               for k in ("prefill", "decode", "idle")}
    print(f"[window] {args.seconds:g}s: {e2e['attempted']} requests "
          f"arrived/issued, {e2e['tokens']} tokens out, "
          f"{len(e2e['tpot'])} tpot samples "
          f"({beyond_p95(len(e2e['tpot']))} beyond p95), "
          f"{len(e2e['ttft'])} ttft samples "
          f"({beyond_p95(len(e2e['ttft']))} beyond p95); "
          f"{w['beats']} heartbeats, steps {n_steps}; slots filled at open "
          f"{w['filled']} after {w['fill']} fill heartbeats; generator late "
          f"max "
          f"{max(w['late'], default=0.0):.4f}s mean "
          f"{float(np.mean(w['late'])) if w['late'] else 0.0:.4f}s; "
          f"placement refusals {rec.refused}; preemptions {rec.preempted}; "
          f"programs built in window {compiles['programs']} (backend "
          f"compiles {compiles['backend']}); peak_bytes_in_use {mem}; "
          f"Pallas kernels {kernels}; ttft_p95_s {e2e['ttft_p95_s']}; "
          f"tpot_p95_ms {e2e['tpot_p95_ms']}",
          file=sys.stderr, flush=True)

    traced = None
    if args.trace:
        from bench.trace_reduce import Trace, load_xplane
        xplane = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))[-1]
        record = load_xplane(xplane)
        ids = sorted(record["devices"], key=int)[:cell.chips]
        if not ids:
            raise RuntimeError(f"no device planes in {xplane}")
        trace = Trace(record, args.seconds, ids)
        traced = TracedRun(trace, window_steps, cfg, peaks)

    # free the program before the reference runs
    sample = check_sample(w, cfg["correct"]["sample_requests"], args.seed)
    del cluster, params, rec.stamps, w
    gc.collect()
    readings = served_readings(
        family.logit_rows, weights.make(family.shapes(cfg), args.seed), cfg,
        sample, (control,) if control else ())
    n_served = int(len(readings["served"]))
    if control:
        print(f"[control] {control} in the program's place; the program's "
              f"own widest gap {float(readings['served'].max())!r}",
              file=sys.stderr)
    judged = readings[control] if control else readings["served"]
    gap = float(judged.max()) if n_served else None
    limit = cfg["correct"]["served_gap_max"]
    correct = bool(n_served and gap <= limit and n_failed == 0)
    compared = {"served_gap_max": {"value": gap, "limit": limit},
                "failed_requests": {"value": n_failed, "limit": 0}}
    print(f"[correct] {len(sample)} finished requests, {n_served} served "
          f"tokens against the float32 reference", file=sys.stderr)

    if args.trace:
        metrics = {}
        for m in cell.per_layer:
            v = harness.metric_reader(m["name"], root)(traced)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = {"setup_s": setup_s, **e2e}
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end
                   if values.get(m["name"]) is not None}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": int(max(mem))}
    result = {"correct": correct, "attempted": e2e["attempted"],
              "failed": n_failed, "metrics": metrics, "device": device}
    if traced is not None:
        device["busy_s"] = traced.trace.mean_busy_s()
        device["window_s"] = traced.trace.window_s
        result["breakdown"] = {
            "device_ops": [list(x) for x in traced.trace.top_ops()],
            "idle_gaps": [list(x) for x in traced.trace.idle_gaps()]}
    result["compared"] = compared
    for k, v in compared.items():
        print(f"{k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def pallas_kernels(cluster, traffic, family) -> Dict[str, Dict[str, int]]:
    """Pallas kernels, by name, in the compiled programs the window ran:
    the family's ``programs`` of the first worker's engine."""
    from repro.kernels import compiled_kernels
    eng = next(iter(cluster.workers.values())).engine
    lowered = family.programs(eng, max(traffic.prompt_lengths()))
    return {step: dict(compiled_kernels(lw.compile().as_text()))
            for step, lw in lowered.items()}


_COUNTER: dict = {}


def _compile_counter(jax) -> dict:
    """Programs built and compiled while ``counting`` is set; one listener
    for the process, since JAX keeps every listener it is given."""
    if not _COUNTER:
        def on_event(event, duration, **kw):
            if _COUNTER["counting"]:
                if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                    _COUNTER["programs"].append(kw.get("fun_name", "?"))
                elif event == "/jax/core/compile/backend_compile_duration":
                    _COUNTER["backend"] += 1
        _COUNTER["counting"] = False
        jax.monitoring.register_event_duration_secs_listener(on_event)
    return _COUNTER


def _profile_options(jax):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


if __name__ == "__main__":
    sys.exit(main())
