"""A benchmark root in a temporary directory, made of files only:
``BENCHMARK.json`` naming two tiny configurations, their model families,
their mixes and a metric. The second configuration's family,
``dense_gqa_qkv_bias``, is a fixture that no file of ``bench/`` names."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

FIXTURES = Path(__file__).resolve().parent / "fixtures"
BENCH = Path(__file__).resolve().parents[1]
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def make_root(tmp: Path, metric: str = "idle_share.conv") -> Path:
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir()
    (tmp / "bench" / "metrics").mkdir()
    (tmp / "bench" / "families").mkdir()
    for cfg in ("tiny", "tiny-qkv-bias"):
        shutil.copy(FIXTURES / f"{cfg}.json",
                    tmp / "bench" / "configs" / f"{cfg}.json")
    shutil.copy(BENCH / "families" / "dense_gqa.py",
                tmp / "bench/families/dense_gqa.py")
    shutil.copy(FIXTURES / "dense_gqa_qkv_bias.py",
                tmp / "bench/families/dense_gqa_qkv_bias.py")
    for mix in ("tiny-closed", "tiny-open"):
        shutil.copy(FIXTURES / f"{mix}.json",
                    tmp / "bench" / "traffic" / f"{mix}.json")
    shutil.copy(BENCH / "metrics" / f"{metric}.py",
                tmp / f"bench/metrics/{metric}.py")
    bench = {
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"},
                    {"name": "tiny-qkv-bias",
                     "file": "bench/configs/tiny-qkv-bias.json"}],
        "workloads": [
            {"name": "tiny-conv", "config": "tiny", "traffic": "tiny-closed",
             "chips": 1},
            {"name": "tiny-code", "config": "tiny", "traffic": "tiny-open",
             "chips": 1},
            {"name": "tiny-qkv-conv", "config": "tiny-qkv-bias",
             "traffic": "tiny-closed", "chips": 1}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s"},
            {"name": "output_tok_s", "unit": "tokens/s"},
            {"name": "tpot_p95_ms", "unit": "ms"},
            {"name": "ttft_p95_s", "unit": "s", "workloads": ["tiny-code"]}],
        "per_layer": [{"name": metric, "unit": "%",
                       "workloads": ["tiny-conv"]}],
    }
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
