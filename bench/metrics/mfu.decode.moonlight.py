"""Share of the chip's bf16 peak the decode steps of latent attention with
sparse experts reach, in %: the logical operations of every decode step,
the routed ones from the assignments the steps' ``serve.decode`` spans
count (``held``), over the decode programs' device time."""
from pathlib import Path

from bench import serve_spans
from bench.work import mla_moe_step


def read(run):
    t, n = run.trace.module_seconds(lambda m: m == "mla_moe_decode_step")
    spans = serve_spans.of(run, Path(__file__).resolve().parents[2])
    if not n or not spans:
        return None
    held = [st["held"] for _, _, st in spans.named("serve.decode")
            if "held" in st]
    if not held:
        return None
    flops = sum(mla_moe_step.decode_flops(ctx, 0, run.cfg)
                for ctx in run.decode_contexts) \
        + 2.0 * mla_moe_step.expert_params(run.cfg) * sum(held)
    return 100.0 * flops / (t * run.peaks["bf16_flops_per_s"])
