"""Device time of one decode-step program of latent attention with sparse
experts (``mla_moe_decode_step``), in ms."""


def read(run):
    t, n = run.trace.module_seconds(lambda m: m == "mla_moe_decode_step")
    return 1e3 * t / n if n else None
