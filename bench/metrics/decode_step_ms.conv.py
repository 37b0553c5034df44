"""Device time of one decode-step program, in ms."""


def read(run):
    t, n = run.trace.module_seconds(lambda m: m == "decode_step")
    return 1e3 * t / n if n else None
