"""Device time of prefill steps (the prefill programs and the cache writes
after them) per 1,000 real prompt tokens, in ms."""


def read(run):
    tokens = sum(run.prompts)
    t, n = run.trace.module_seconds(lambda m: True,
                                    within=run.step_spans("prefill"))
    return 1e3 * t / (tokens / 1e3) if n and tokens else None
