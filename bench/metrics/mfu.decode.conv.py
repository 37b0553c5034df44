"""Share of the chip's bf16 peak the decode steps reach, in %: the logical
operations of every decode step over the decode programs' device time."""
from bench.work import model_step


def read(run):
    t, n = run.trace.module_seconds(lambda m: m == "decode_step")
    if not n:
        return None
    flops = sum(model_step.decode_flops(ctx, run.cfg)
                for ctx in run.decode_contexts)
    return 100.0 * flops / (t * run.peaks["bf16_flops_per_s"])
