"""Serving launcher.

Each Aladdin worker is a ``PagedEngine`` on one device (worker k on
``jax.devices()[k % n]``); this launcher assembles the cluster, runs the
Aladdin control loop, and serves a synthetic Poisson workload.

  PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b \
      --rate 2 --duration 30 [--policy aladdin|jsq] [--workers 2]

The model runs at its published size (phi4-mini fits one 16 GiB v5e chip
with its KV pool; Llama-2-7B does not). ``--reduce`` swaps in a 2-layer,
d_model-64 variant of the same family, for CPU runs.
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path
from typing import List, Sequence

import jax
import numpy as np

from repro.configs import get_arch, reduced
from repro.core.request import Request
from repro.core.slo import SLO
from repro.core.worker_config import TPU_V5E, optimal_worker_config
from repro.models.model import LM
from repro.serving.cluster import ClusterConfig, ServingCluster
from repro.serving.engine import EngineConfig

REPO_ROOT = Path(__file__).resolve().parents[3]


def setup_compile_cache() -> str:
    """Persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR`` says
    (JAX reads the variable itself), else the fixed ``<repo>/.jax_cache``
    (the path is part of the cache key, so it never moves)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def serve_trace(cluster: ServingCluster, requests: Sequence[Request],
                max_beats: int = 500) -> float:
    """Serve ``requests`` on ``cluster`` as a live trace. Every worker first
    compiles its programs for the trace's prompt lengths; then each request
    is submitted once its ``arrival`` (seconds from the start) has passed,
    re-stamped with the real submit time, with heartbeats in between, and
    the cluster is drained. Returns the warm-up (compile) seconds."""
    warm = sum(w.engine.warmup([r.l_in for r in requests])
               for w in cluster.workers.values())
    pending = sorted(requests, key=lambda r: r.arrival)
    t0 = time.perf_counter()
    i = 0
    while i < len(pending):
        now = time.perf_counter()
        while i < len(pending) and now - t0 >= pending[i].arrival:
            pending[i].arrival = now
            cluster.submit(pending[i])
            i += 1
        cluster.heartbeat()
    cluster.run_until_drained(max_beats)
    return warm


def poisson_requests(rate: float, duration: float, vocab: int,
                     seed: int = 0) -> List[Request]:
    """Poisson arrivals over ``duration`` s; prompts of 8-47 random tokens,
    4-15 output tokens."""
    rng = np.random.default_rng(seed)
    out: List[Request] = []
    t = rng.exponential(1.0 / rate)
    while t < duration:
        r = Request(l_in=int(rng.integers(8, 48)), l_pred=0,
                    l_real=int(rng.integers(4, 16)), arrival=t)
        r.tokens = [int(x) for x in rng.integers(2, vocab, r.l_in)]
        out.append(r)
        t += rng.exponential(1.0 / rate)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi4-mini-3.8b")
    ap.add_argument("--policy", default="aladdin")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--rate", type=float, default=2.0)
    ap.add_argument("--duration", type=float, default=20.0)
    ap.add_argument("--ttft", type=float, default=10.0)
    ap.add_argument("--atgt", type=float, default=2.0)
    ap.add_argument("--reduce", action="store_true",
                    help="serve a 2-layer d_model-64 variant (CPU runs)")
    ap.add_argument("--autoscale", action="store_true")
    args = ap.parse_args()

    setup_compile_cache()
    arch = get_arch(args.arch)
    try:
        cfg = optimal_worker_config(arch, TPU_V5E, SLO(args.ttft, args.atgt))
        print(f"[serve] Eq.5-6 optimal worker: {cfg.n_accelerators} chips "
              f"({cfg.bound}-bound)")
    except ValueError as e:
        print(f"[serve] worker config: {e}")
    if args.reduce:
        arch = reduced(arch, n_layers=2, d_model=64, vocab=256)
    params = LM(arch).init(jax.random.key(0))
    cluster = ServingCluster(
        arch, params, SLO(args.ttft, args.atgt),
        engine_cfg=EngineConfig(max_batch=4, page_size=8, n_pages=256,
                                max_pages_per_seq=32),
        cfg=ClusterConfig(policy=args.policy, autoscale=args.autoscale,
                          max_workers=max(args.workers * 2, 4)),
        n_workers=args.workers)
    reqs = poisson_requests(args.rate, args.duration, arch.vocab)
    warm = serve_trace(cluster, reqs)
    print(f"[serve] {arch.name} on {jax.devices()[0].device_kind} | "
          f"warm-up {warm:.1f}s | {len(cluster.finished)}/{len(reqs)} "
          f"finished | attainment {cluster.attainment():.2f} | "
          f"workers={len(cluster.workers)} | decode fit err="
          f"{cluster.perf.max_rel_err.get('decode', -1):.3f}")
    print(f"[serve] {cluster.stats}")


if __name__ == "__main__":
    main()
