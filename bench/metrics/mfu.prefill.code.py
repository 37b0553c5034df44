"""Share of the chip's bf16 peak the prefill steps reach, in %: the logical
operations of every prefill over the device time of the prefill steps."""
from bench.work import model_step


def read(run):
    t, n = run.trace.module_seconds(lambda m: True,
                                    within=run.step_spans("prefill"))
    if not n or not run.prompts:
        return None
    flops = model_step.prefill_flops(run.prompts, run.cfg)
    return 100.0 * flops / (t * run.peaks["bf16_flops_per_s"])
