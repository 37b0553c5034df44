"""Host time of the scheduler per heartbeat of the four workers, in ms: the
mean over the window's ``bench.heartbeat`` spans of each one's time less
the ``bench.engine_step`` spans inside it (placement, re-balance,
hand-off, refit, upkeep). It reads the benchmark's own spans, so a program
with or without ``serve.*`` spans reads the same."""
import bisect


def read(run):
    beats = run.trace.spans("bench.heartbeat")
    if not beats:
        return None
    steps = sorted((s, s + d) for n, s, d in run.trace.host
                   if n == "bench.engine_step")
    starts = [s for s, _ in steps]
    own = 0.0
    for a, b in beats:
        inside = steps[bisect.bisect_left(starts, a):
                       bisect.bisect_right(starts, b)]
        own += (b - a) - sum(e - s for s, e in inside if e <= b)
    return 1e-6 * own / len(beats)
