#!/usr/bin/env python3
"""The control of ``correct``: one run of a cell in which the tokens that
the reference puts first with int8 products (activations per row, weights
per output column) stand in for the served ones, at the same prompts and
positions, and go through the same verdict. It has to print ``correct``
false.

    python3 bench/control.py --workload phi4-conv --seed 5 --seconds 51

The program's own widest gap is printed on an earlier stderr line, so one
run gives both readings that a configuration's limit lies between
(PERF.md). The benchmark's own runs do not run this.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run.main(control="int8"))
