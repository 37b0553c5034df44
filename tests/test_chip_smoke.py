"""CPU rehearsal of ``chip_smoke.py``: each phase at a reduced width, with
the Pallas kernels in interpret mode, and the script's refusal to report
success anywhere but on a TPU."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_arch, reduced
from repro.models.model import LM
from repro.serving.engine import EngineConfig

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _load_smoke():
    if "chip_smoke" not in sys.modules:   # dataclasses look the module up
        spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
        sys.modules["chip_smoke"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules["chip_smoke"])
    return sys.modules["chip_smoke"]


def small_config(cs):
    """phi4-mini's family and head layout at toy width."""
    return cs.SmokeConfig(
        arch=reduced(get_arch("phi4-mini-3.8b"), n_layers=2, d_model=128,
                     n_heads=4, n_kv_heads=2, vocab=512, d_ff=256),
        engine=EngineConfig(max_batch=4, page_size=8, n_pages=64,
                            max_pages_per_seq=16, interpret=True),
        prompt_lens=(10, 20, 40), n_out=4,
        four_chip_lens=tuple(range(9, 41, 4)), four_chip_out=3,
        planner_seconds=20.0)


@pytest.fixture(scope="module")
def smoke():
    cs = _load_smoke()
    cfg = small_config(cs)
    params = LM(cfg.arch).init(jax.random.key(cfg.seed))
    served, reqs, taps = cs.served_phase(cfg, params, expect_kernels=False)
    return cs, cfg, params, served, reqs, taps


def test_served_phase_finishes_every_request(smoke):
    cs, cfg, _, served, reqs, taps = smoke
    assert served.ok, served.detail
    assert all(r.l_out == cfg.n_out for r in reqs)
    # prefill and first decode logits captured for every request
    assert all(len(taps[r.id]) == 2 for r in reqs)


def test_kernel_phase_matches_reference(smoke):
    cs, cfg = smoke[:2]
    check = cs.kernel_phase(cfg)
    assert check.ok, check.detail


def test_oracle_phase_matches_model(smoke):
    cs, cfg, params, _, reqs, taps = smoke
    check = cs.oracle_phase(cfg, params, reqs, taps)
    assert check.ok, check.detail


def test_oracle_phase_catches_wrong_logits(smoke):
    cs, cfg, params, _, reqs, taps = smoke
    bad = {k: [v[0], v[0][::-1].copy()] for k, v in taps.items()}
    assert not cs.oracle_phase(cfg, params, reqs, bad).ok


def test_planner_phase_matches_reference(smoke):
    cs, cfg = smoke[:2]
    check = cs.planner_phase(cfg.planner_seconds)
    assert check.ok, check.detail


def test_planner_check_catches_heartbeat_drift(smoke):
    """A planted 1e-10 drift of the heartbeat reads far above both clock
    limits, while a rerun of the same engine reads exactly 0."""
    cs, cfg = smoke[:2]
    trace = cs.cell9_trace(cfg.planner_seconds)
    ref = cs.planner_run(trace, "reference")
    assert cs.planner_errors(ref, cs.planner_run(trace, "reference")) == (
        0, 0.0, 0.0)
    _, clock, _ = cs.planner_errors(
        ref, cs.planner_run(trace, "reference", heartbeat=0.02 * (1 + 1e-10)))
    assert clock > 10 * cs.CLOCK_REL_TPU


def _run(args, env_extra, cwd, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(args, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=timeout)


def test_four_chip_phase_on_virtual_devices():
    """Four workers on four virtual CPU devices against one worker."""
    code = ("import sys; sys.path.insert(0, %r); import test_chip_smoke as t;"
            "import jax; from repro.models.model import LM;"
            "cs = t._load_smoke(); cfg = t.small_config(cs);"
            "p = LM(cfg.arch).init(jax.random.key(0));"
            "c = cs.four_chip_phase(cfg, p, 4).report();"
            "sys.exit(0 if c.ok else 1)") % str(ROOT / "tests")
    out = _run([sys.executable, "-c", code], {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": str(ROOT / "src")}, cwd=ROOT)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "distinct devices=True" in out.stdout


def _assert_refused(out):
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_smoke_refuses_without_tpu():
    _assert_refused(_run([sys.executable, str(SCRIPT)], {}, cwd=ROOT,
                         timeout=120))


def test_smoke_refuses_outside_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    _assert_refused(_run([sys.executable, SCRIPT.name],
                         {"PYTHONPATH": ""}, cwd=tmp_path, timeout=120))
