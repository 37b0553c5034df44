"""Profiler trace -> device intervals, time by name, host spans, idle gaps.

``load_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain record: per device the ``XLA Ops`` and ``XLA Modules`` events, and
the host spans this benchmark writes (names starting ``bench.``), all as
``[name, start_ns, duration_ns]`` on the profiler's one clock. ``Trace``
reduces such a record; the tests check it on a small recorded one.
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start ns, duration ns

_SUFFIX = re.compile(r"(\.\d+)+$")
_MODULE = re.compile(r"^(jit_)?(?P<name>[^(]*)(\(.*\))?$")
_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_CONTAINERS = {"while", "conditional", "call"}


def op_name(raw: str) -> str:
    """An op event's instruction name without its number:
    ``%paged_decode_attention.4 = f32[...] custom-call(...)`` and
    ``paged_decode_attention.4`` -> ``paged_decode_attention``."""
    return _SUFFIX.sub("", raw.split(" = ", 1)[0].lstrip("%"))


def module_name(raw: str) -> str:
    """``jit_decode_step(1234)`` -> ``decode_step``."""
    return _MODULE.match(raw).group("name")


def load_xplane(path: Path) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices: Dict[str, dict] = {}
    host: List[Event] = []
    for plane in data.planes:
        chip = _TPU_PLANE.match(plane.name)
        if chip:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:             # an op's name is its HLO text: keep
                    dev[key].extend(    # the instruction name alone
                        [e.name.split(" = ", 1)[0], e.start_ns,
                         e.duration_ns] for e in line.events)
            devices[chip.group(1)] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"devices": devices, "host": host}


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Merged, sorted [start, end] intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(ev: Sequence[Event], lo: float, hi: float
          ) -> List[Tuple[str, float, float]]:
    """Events as (name, start, end), cut to [lo, hi]."""
    out = []
    for name, s, d in ev:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


class Trace:
    """One traced window: it opens where the ``bench.window`` host span
    does and lasts ``seconds`` (the span itself runs on to the end of the
    heartbeat in flight)."""

    def __init__(self, record: dict, seconds: float,
                 devices: Optional[Sequence[str]] = None):
        self.host: List[Event] = [tuple(e) for e in record["host"]]
        win = [e for e in self.host if e[0] == "bench.window"]
        if not win:
            raise ValueError("trace holds no bench.window span")
        self.lo = win[0][1]
        self.hi = self.lo + seconds * 1e9
        ids = devices if devices is not None else sorted(record["devices"])
        self.ops = {i: _clip(record["devices"][i]["ops"], self.lo, self.hi)
                    for i in ids}
        self.modules = {i: _clip(record["devices"][i]["modules"], self.lo,
                                 self.hi) for i in ids}

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def spans(self, name: str) -> List[Tuple[float, float]]:
        """(start, end) ns of the host spans ``name`` inside the window."""
        return sorted((s, s + d) for n, s, d in self.host
                      if n == name and s >= self.lo and s < self.hi)

    def busy_s(self, dev: str) -> float:
        return sum(e - s for s, e in union((a, b) for _, a, b
                                           in self.ops[dev])) * 1e-9

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(d) for d in self.ops) / len(self.ops)

    def idle_share(self) -> float:
        """1 - busy / window, averaged over the devices."""
        return 1.0 - self.mean_busy_s() / self.window_s

    def op_seconds(self) -> Dict[str, float]:
        """Device seconds by ``module:op`` name, summed over devices. Loops
        and calls are left out: their events span the ops inside them."""
        out: Dict[str, float] = {}
        for dev, ops in self.ops.items():
            mods = sorted(self.modules[dev], key=lambda m: m[1])
            starts = [m[1] for m in mods]
            for name, s, e in ops:
                if op_name(name) in _CONTAINERS:
                    continue
                i = bisect.bisect_right(starts, s) - 1
                mod = module_name(mods[i][0]) if i >= 0 and \
                    mods[i][2] >= e else "?"
                key = f"{mod}:{op_name(name)}"
                out[key] = out.get(key, 0.0) + (e - s) * 1e-9
        return out

    def kernel_seconds(self, kernel: str,
                       within: Optional[Sequence[Tuple[float, float]]] = None
                       ) -> Tuple[float, int]:
        """Device seconds and count of ops named ``kernel`` (any suffix),
        optionally only those starting inside the host spans ``within``."""
        return self._sum(self.ops, lambda n: op_name(n) == kernel, within)

    def module_seconds(self, pred, within=None) -> Tuple[float, int]:
        """Device seconds and count of programs whose name passes
        ``pred``, optionally only those starting inside ``within``."""
        return self._sum(self.modules, lambda n: pred(module_name(n)),
                         within)

    def _sum(self, table, pred, within):
        starts = [a for a, _ in within] if within is not None else None
        total, count = 0.0, 0
        for evs in table.values():
            for name, s, e in evs:
                if not pred(name):
                    continue
                if starts is not None:
                    i = bisect.bisect_right(starts, s) - 1
                    if i < 0 or s > within[i][1]:
                        continue
                total += (e - s) * 1e-9
                count += 1
        return total, count

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """The ``top`` longest device-idle gaps in the window, each named by
        the innermost ``bench.*`` host span around its midpoint."""
        spans = [(s, s + d, n) for n, s, d in self.host
                 if n != "bench.window"]
        gaps = []
        for ops in self.ops.values():
            t = self.lo
            for s, e in union((a, b) for _, a, b in ops) + [[self.hi,
                                                              self.hi]]:
                if s > t:
                    gaps.append((s - t, (s + t) / 2))
                t = max(t, e)
        out = []
        for length, mid in sorted(gaps, key=lambda g: -g[0])[:top]:
            around = [(b - a, n) for a, b, n in spans if a <= mid <= b]
            out.append((min(around)[1] if around else "none", length * 1e-9))
        return out

    def top_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:top]
