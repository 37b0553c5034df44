"""Share of its roofline the flash-attention kernel reaches in prefill,
in %: the least time for causal attention over the real prompt lengths at
the chip's peaks, over the kernel's device time in the prefill steps."""
from bench.work import flash_attention


def read(run):
    t, n = run.trace.kernel_seconds("flash_attention",
                                    within=run.step_spans("prefill"))
    if not n or not run.prompts:
        return None
    flops, nbytes = flash_attention.work(run.prompts, run.cfg)
    least = max(flops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
