"""Share of its roofline the latent paged-decode kernel reaches, in %: the
least time for the live context's logical work at the chip's peaks, over
the kernel's device time in the decode steps."""
from bench.work import mla_decode_attention


def read(run):
    t, n = run.trace.kernel_seconds("mla_decode_attention",
                                    within=run.step_spans("decode"))
    if not n:
        return None
    flops = nbytes = 0.0
    for ctx in run.decode_contexts:
        f, b = mla_decode_attention.work(ctx, run.cfg)
        flops, nbytes = flops + f, nbytes + b
    least = max(flops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
