"""The harness finds every part of a cell by name, so a configuration, a
cell or a metric is added with files and BENCHMARK.json entries alone;
and the benchmark in the repository keeps to its own shape."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from benchroot import make_root

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_finds_fixture_cell_by_name(tmp_path):
    root = make_root(tmp_path)
    cell = harness.load_cell("tiny-conv", root)
    assert (cell.config_name, cell.traffic_name, cell.chips) == \
        ("tiny", "tiny-closed", 1)
    assert cell.config["hidden_size"] == 64
    assert cell.traffic["loop"] == "closed"
    assert [m["name"] for m in cell.end_to_end] == \
        ["setup_s", "output_tok_s", "tpot_p95_ms"]
    assert [m["name"] for m in harness.load_cell("tiny-code",
                                                 root).end_to_end][-1] == \
        "ttft_p95_s"
    assert callable(harness.metric_reader("idle_share.conv", root))


def test_new_config_cell_and_metric_need_files_only(tmp_path):
    root = make_root(tmp_path)
    cfg = json.loads((root / "bench/configs/tiny.json").read_text())
    cfg["name"] = "tiny-b"
    (root / "bench/configs/tiny-b.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tiny-burst.json").write_text(json.dumps(
        {"loop": "open", "rate_per_s": 5.0, "set_size": 4, "strata": 2,
         "prompt": {"median": 9, "sigma": 0.1, "min": 8, "max": 12},
         "output": {"median": 3, "sigma": 0.1, "min": 2, "max": 4}}))
    (root / "bench/metrics/answer.b.py").write_text(
        "def read(run):\n    return 42.0 if run else None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-b",
                             "file": "bench/configs/tiny-b.json"})
    bench["workloads"].append({"name": "tiny-b-burst", "config": "tiny-b",
                               "traffic": "tiny-burst", "chips": 1})
    bench["per_layer"].append({"name": "answer.b", "unit": "%",
                               "workloads": ["tiny-b-burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("tiny-b-burst", root)
    assert cell.config["name"] == "tiny-b"
    assert cell.traffic["rate_per_s"] == 5.0
    assert [m["name"] for m in cell.per_layer] == ["answer.b"]
    assert harness.metric_reader("answer.b", root)(object()) == 42.0
    assert harness.load_cell("tiny-conv", root).per_layer[0]["name"] == \
        "idle_share.conv"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.peaks("TPU v99", ROOT)
    assert harness.peaks("TPU v5 lite", ROOT)["bf16_flops_per_s"] == 197e12


def test_repository_benchmark_resolves():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    ends = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"], ROOT)
        got = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in got and len(got) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in got and m["moves"] in ends
            assert callable(harness.metric_reader(m["name"], ROOT))
        assert len(w["why"]) <= 200


def test_run_without_tpu_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "phi4-conv", "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
