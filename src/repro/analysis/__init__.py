"""simlint — repo-specific static analysis for the simulator's invariants.

The three simulation engines (reference / vectorized / jax) agree only
because a set of invariants holds that ordinary linters cannot see: the
``fastsim_jax`` performance contract (never bulk-scatter into trace-sized
carries inside the beat loop), the scoped-``jax.enable_x64(True)`` precision
discipline, dimensional consistency of the second/token/GPU-second
arithmetic, monotone causal clocks stamped only by blessed helpers, frozen
deprecation shims, and envelope validators that must inspect every scenario
knob before a compiled core is allowed to run it.  ``simlint`` enforces
those invariants at diff time — an AST pass over the tree instead of a 90s
smoke bench.

Run it as ``python -m repro.analysis [paths...]`` (or
``scripts/simlint.py``); CI runs it as a hard gate with the tracked
allowlist ``scripts/simlint_baseline.json``.  Diagnostics carry stable
``SIM00x`` codes (see ``--list-codes`` or the README); individual lines
can opt out with ``# simlint: ignore[SIM00x]``.
"""
from repro.analysis.core import (Checker, Project, SourceFile,  # noqa: F401
                                 run_checkers)
from repro.analysis.diagnostics import (CODES, Baseline,        # noqa: F401
                                        Diagnostic)

__all__ = ["Baseline", "Checker", "CODES", "Diagnostic", "Project",
           "SourceFile", "run_checkers"]
