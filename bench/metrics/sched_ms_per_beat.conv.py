"""Host time of the scheduler per heartbeat, in ms: the mean over the
window's ``serve.heartbeat`` spans of each one's time less the
``serve.step`` spans inside it (placement, re-balance, hand-off, refit,
upkeep)."""
from pathlib import Path

from bench import serve_spans


def read(run):
    spans = serve_spans.of(run, Path(__file__).resolve().parents[2])
    return spans.sched_ms_per_beat() if spans else None
