"""The program's own host spans in a traced run, for the metrics that read
them.

The served path marks its phases with ``serve.*`` host spans
(``src/repro/serving/spans.py``), which carry integer stats.
``trace_reduce.load_xplane`` keeps the benchmark's ``bench.*`` spans
only, so ``of`` reads the ``serve.*`` spans from the same profile: the
newest ``.xplane.pb`` under ``<root>/.bench_trace`` whose ``bench.window``
span starts where the run's window does. It does so once a run, and adds
the spans to the run's trace, so that the breakdown names each idle gap by
the innermost span of either kind. A program without these spans gives
none: ``of`` returns ``None`` and the metrics read nothing.
"""
from __future__ import annotations

import bisect
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench.trace_reduce import union

Span = Tuple[str, float, float, Dict[str, int]]  # name, start, duration ns


def load_xplane(path: Path) -> Tuple[Optional[float], List[Span]]:
    """The start of the ``bench.window`` span and every ``serve.*`` span,
    with its stats, in one profile."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    window, spans = None, []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    spans.append((e.name, e.start_ns, e.duration_ns,
                                  dict(e.stats)))
                elif e.name == "bench.window" and window is None:
                    window = e.start_ns
    return window, spans


class ServeSpans:
    """The ``serve.*`` spans of one traced window, against the device's
    busy time (the union of every chip's ops)."""

    def __init__(self, spans: List[Span], trace):
        self.lo, self.hi = trace.lo, trace.hi
        self.spans = sorted((s, s + d, n, st) for n, s, d, st in spans)
        self.busy = union((a, b) for ops in trace.ops.values()
                          for _, a, b in ops)
        self._starts = [s for s, _ in self.busy]
        self._cum = [0.0]
        for s, e in self.busy:
            self._cum.append(self._cum[-1] + e - s)

    def named(self, name: str, window: bool = True
              ) -> List[Tuple[float, float, Dict[str, int]]]:
        """(start, end ns, stats) of the spans ``name``; with ``window``
        only those that start inside it."""
        return [(s, e, st) for s, e, n, st in self.spans if n == name
                and (not window or self.lo <= s < self.hi)]

    def _busy_in(self, a: float, b: float) -> float:
        """Device-busy ns inside [a, b]."""
        i = bisect.bisect_right(self._starts, a) - 1
        j = bisect.bisect_left(self._starts, b)
        if i < 0:
            i = 0
        total = self._cum[j] - self._cum[i]
        if j > i:                       # cut the first and last interval
            s, e = self.busy[i]
            total -= max(0.0, min(a, e) - s)
            s, e = self.busy[j - 1]
            total -= max(0.0, e - max(b, s))
        return total

    def sched_ms_per_beat(self) -> Optional[float]:
        """Mean of each heartbeat's time less its engine steps, in ms."""
        beats = self.named("serve.heartbeat")
        if not beats:
            return None
        steps = self.named("serve.step", window=False)
        starts = [s for s, _, _ in steps]
        own = 0.0
        for a, b, _ in beats:
            lo = bisect.bisect_left(starts, a)
            hi = bisect.bisect_right(starts, b)
            own += (b - a) - sum(e - s for s, e, _ in steps[lo:hi]
                                 if e <= b)
        return 1e-6 * own / len(beats)

    def idle_ms(self, name: str, when=lambda stats: True
                ) -> Optional[float]:
        """Mean device-idle time inside the spans ``name`` whose stats pass
        ``when``, each cut to the window, in ms."""
        spans = [(max(s, self.lo), min(e, self.hi))
                 for s, e, st in self.named(name) if when(st)]
        if not spans:
            return None
        idle = sum((b - a) - self._busy_in(a, b) for a, b in spans)
        return 1e-6 * idle / len(spans)

    def empty_slot_share(self) -> Optional[float]:
        """Empty slot-steps while a request waited unplaced, over the
        slot-steps of the window's decode steps, in %."""
        steps = [st for _, _, st in self.named("serve.decode")
                 if st["active"]]
        slots = sum(st["slots"] for st in steps)
        return 100.0 * sum(st["empty"] for st in steps) / slots \
            if slots else None


def attach(run, spans: List[Span]) -> Optional[ServeSpans]:
    """Keep ``spans`` on ``run`` and add them to its trace's host spans;
    ``None`` where there are none."""
    found = None
    if spans:
        found = ServeSpans(spans, run.trace)
        run.trace.host.extend((n, s, d) for n, s, d, _ in spans)
    run.serve_spans = found
    return found


def of(run, root: Path) -> Optional[ServeSpans]:
    """The ``serve.*`` spans of ``run``'s window, or ``None`` where its
    profile holds none; read once and kept on the run."""
    if hasattr(run, "serve_spans"):
        return run.serve_spans
    paths = sorted((root / ".bench_trace").glob(
        "*/plugins/profile/*/*.xplane.pb"),
        key=lambda p: p.stat().st_mtime, reverse=True)
    for path in paths:
        window, spans = load_xplane(path)
        if window == run.trace.lo:
            return attach(run, spans)
    return attach(run, [])
