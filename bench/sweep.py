#!/usr/bin/env python3
"""Rate sweep of an open-loop cell, to find its knee once, on the chip.

    python3 bench/sweep.py --workload <open-loop cell> --seed 5 --seconds 40 \
        --rates 0.5,1,1.5,2

One process makes the weights once; for each rate it builds a fresh
cluster, warms it up, and drives the cell's mix at that rate for
``--seconds``. Each rate prints the requests that arrived and finished,
the backlog left at the close, and the TTFT tail. The knee is the highest
rate whose backlog stays flat; the cell's mix file then fixes its rate at
0.8 of it (PERF.md). The benchmark's own runs do not run this.
"""
import argparse
import gc
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run  # noqa: E402  (puts the program on the path)
from bench import harness, weights  # noqa: E402
from bench.traffic import Traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import jax
    cell = harness.load_cell(args.workload, run.ROOT)
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return run.NO_CHIP
    jax.config.update("jax_compilation_cache_dir",
                      str(run.ROOT / ".jax_cache"))
    family = harness.family(cell.config["family"], run.ROOT)
    arch = run.program_arch(cell.config, family)
    params = jax.block_until_ready(
        weights.make(family.shapes(cell.config), args.seed))
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(cell.traffic, rate_per_s=rate)
        traffic = Traffic(mix, arch.vocab, args.seed)
        cluster = run.build_cluster(cell, arch, params, traffic)
        rec = run.Recorder(cluster, run._no_annotation)
        run.warm_up(cluster, traffic.prompt_lengths(), family)
        w = run.serve_window(cluster, traffic, rec, args.seconds,
                             run._no_annotation, fill_beats=0)
        e2e = run.end_to_end(w, rec)
        backlog = len(cluster.queued) + sum(
            len(x.engine.waiting) + len(x.state.new_batch)
            for x in cluster.workers.values())
        done = sum(1 for it in w["issued"]
                   if it.req.state.value == "finished")
        print(f"[sweep] rate {rate:g}/s: {e2e['attempted']} arrived, {done} "
              f"finished, backlog {backlog} at close, running "
              f"{sum(len(x.engine.running) for x in cluster.workers.values())}"
              f", ttft p95 {e2e['ttft_p95_s']!r} s, tokens/s "
              f"{e2e['output_tok_s']!r}, refusals {rec.refused}",
              flush=True)
        del cluster, rec, w
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
