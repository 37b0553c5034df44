"""Finds every part of a cell by the name ``BENCHMARK.json`` gives it.

A cell (an entry of ``workloads``) names a configuration, a traffic mix and
a chip count. Its parts live in files of their own, found by name:

  configuration   the ``file`` of its ``configs`` entry (sizes, engine,
                  cluster, SLO and the limits of ``correct``)
  model family    ``bench/families/<family>.py``, named by the
                  configuration's ``"family"``: the weight tree, the check
                  of the program's architecture against the file, the
                  float32 reference, and what the engine needs warmed
  traffic mix     ``bench/traffic/<traffic>.json``, read by ``traffic.py``
  per-layer metric ``bench/metrics/<metric name>.py``, a reader with
                  ``read(run) -> float | None``
  peaks           ``bench/peaks.json``, keyed by ``device_kind``

So a later change adds a configuration, a model family, a mix, a cell or a
metric by adding files and entries, with no edit to the code here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]      # the end-to-end metrics this cell reports
    per_layer: List[dict]       # the per-layer metrics this cell reports


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files read."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    file = configs[w["config"]]["file"]
    config = json.loads((root / file).read_text())
    if "family" not in config:
        raise KeyError(f"configuration {file} names no \"family\" (the "
                       "name of a file under bench/families/)")
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name=name, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


def metric_reader(name: str, root: Path
                  ) -> Callable[[object], Optional[float]]:
    """``read`` of ``<root>/bench/metrics/<name>.py``."""
    return _module(root / "bench" / "metrics" / f"{name}.py",
                   "bench_metric_" + name).read


def family(name: str, root: Path):
    """The module ``<root>/bench/families/<name>.py``: ``check(cfg,
    arch)``, ``shapes(cfg)``, ``logit_rows(params, tokens, rows, *, cfg,
    mode, q_block)``, ``warm_up(engine, prompt_lengths)`` and
    ``programs(engine, prompt_len)``."""
    path = root / "bench" / "families" / f"{name}.py"
    if not path.is_file():
        have = sorted(p.stem for p in path.parent.glob("*.py"))
        raise FileNotFoundError(f"no model family {name!r}: {path} is not "
                                f"a file (have {have})")
    return _module(path, "bench_family_" + name)


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str, root: Path) -> Dict[str, float]:
    """The chip's peaks; a device that is not in the table is an error."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]
