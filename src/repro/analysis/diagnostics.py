"""Diagnostic model: stable codes, inline suppressions, tracked baseline.

Every finding is a :class:`Diagnostic` carrying one of the stable ``SIM00x``
codes from :data:`CODES`.  Two opt-out channels exist, with different jobs:

* ``# simlint: ignore[SIM003]`` on (or immediately above) the offending
  line — for idioms that are *correct by design* and should stay exempt
  next to the code they annotate.  A bare ``# simlint: ignore`` suppresses
  every code on that line.
* a baseline file (``scripts/simlint_baseline.json``) — for pre-existing
  findings accepted as-is when a checker lands.  Entries match on
  ``(code, path, stripped line text)`` so ordinary line drift does not
  invalidate them, and entries that no longer match anything fail the run
  (a stale allowlist is itself a finding: the debt it tracked is gone).
"""
from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

# The stable diagnostic registry. Codes are append-only: a retired checker
# keeps its code reserved so old suppressions/baselines never silently
# re-bind to a new rule.
CODES: Dict[str, str] = {
    "SIM001": ("jit purity / performance contract: no bulk scatters, "
               "Python branching, or tracer coercions inside compiled "
               "beat-loop bodies and Pallas kernels"),
    "SIM002": ("x64 scope: jax 64-bit precision may only be enabled via a "
               "scoped `with jax.enable_x64(True):` block, never "
               "process-globally"),
    "SIM003": ("unit safety: additions/comparisons must not mix dimensions "
               "(seconds vs tokens vs GPU-seconds vs price) inferred from "
               "the repo's naming conventions"),
    "SIM004": ("clock monotonicity: request/worker clock fields are "
               "stamped only by the blessed simulation helpers"),
    "SIM005": ("shim freeze: no new src/ importers of the deprecated "
               "simulate/min_workers_for_slo/simulate_disaggregated/"
               "min_cost_disagg entry points"),
    "SIM006": ("envelope coverage: every Scenario/topology/scaling field "
               "must be inspected by a check_*_envelope validator before "
               "a compiled core may run the scenario"),
}

_IGNORE_RE = re.compile(
    r"#\s*simlint:\s*ignore(?:\[\s*([A-Z0-9,\s]+?)\s*\])?")


@dataclasses.dataclass(frozen=True)
class Diagnostic:
    """One finding: a stable code anchored to a source line."""
    code: str
    path: str                  # repo-relative posix path
    line: int                  # 1-indexed
    col: int                   # 0-indexed (ast convention)
    message: str
    line_text: str = ""        # stripped source line (baseline fingerprint)

    def format(self) -> str:
        return f"{self.path}:{self.line}:{self.col + 1}: " \
               f"{self.code} {self.message}"

    @property
    def fingerprint(self) -> Tuple[str, str, str]:
        return (self.code, self.path, self.line_text)


def parse_suppressions(lines: Sequence[str]) -> Dict[int, Optional[Set[str]]]:
    """Map 1-indexed line number -> suppressed codes (``None`` = all codes).

    A suppression comment governs its own line; when it sits on a
    comment-only line it also governs the next line (annotate-above style).
    """
    out: Dict[int, Optional[Set[str]]] = {}

    def merge(lineno: int, codes: Optional[Set[str]]) -> None:
        if codes is None or out.get(lineno, set()) is None:
            out[lineno] = None if codes is None else codes
        else:
            out.setdefault(lineno, set()).update(codes)

    for i, text in enumerate(lines, start=1):
        m = _IGNORE_RE.search(text)
        if not m:
            continue
        codes = None if m.group(1) is None else {
            c.strip() for c in m.group(1).split(",") if c.strip()}
        merge(i, codes)
        if text.lstrip().startswith("#"):       # comment-only line: applies
            merge(i + 1, codes)                 # to the line it annotates
    return out


def is_suppressed(diag: Diagnostic,
                  suppressions: Dict[int, Optional[Set[str]]]) -> bool:
    codes = suppressions.get(diag.line, set())
    return codes is None or diag.code in (codes or set())


class Baseline:
    """The tracked allowlist of accepted pre-existing findings."""

    def __init__(self, entries: Optional[List[Dict]] = None):
        self.entries: List[Dict] = entries or []
        self._matched = [False] * len(self.entries)

    @classmethod
    def load(cls, path: Path) -> "Baseline":
        with open(path) as f:
            data = json.load(f)
        if data.get("version") != 1:
            raise ValueError(f"{path}: unsupported baseline version "
                             f"{data.get('version')!r}")
        return cls(list(data.get("entries", [])))

    @classmethod
    def from_diagnostics(cls, diags: Sequence[Diagnostic],
                         reason: str = "accepted pre-existing finding") \
            -> "Baseline":
        seen = set()
        entries = []
        for d in sorted(diags, key=lambda d: (d.path, d.line, d.code)):
            if d.fingerprint in seen:
                continue
            seen.add(d.fingerprint)
            entries.append({"code": d.code, "path": d.path,
                            "text": d.line_text, "reason": reason})
        return cls(entries)

    def save(self, path: Path) -> None:
        payload = {"version": 1, "entries": self.entries}
        path.write_text(json.dumps(payload, indent=2) + "\n")

    def accepts(self, diag: Diagnostic) -> bool:
        for i, e in enumerate(self.entries):
            if (e["code"], e["path"], e["text"]) == diag.fingerprint:
                self._matched[i] = True
                return True
        return False

    def stale_entries(self) -> List[Dict]:
        """Entries that matched no finding this run — debt that no longer
        exists and must be removed from the allowlist."""
        return [e for i, e in enumerate(self.entries) if not self._matched[i]]
