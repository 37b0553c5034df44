"""Fixture tests for simlint: each SIM00x checker is pinned by at least
one true positive and one true negative, plus suppression/baseline
mechanics and the repo-wide exit-0 acceptance gate."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import Baseline, Diagnostic, Project, run_checkers
from repro.analysis.checkers import (ALL_CHECKERS, ClockMonotonicity,
                                     EnvelopeCoverage, JitPurity,
                                     ShimFreeze, UnitSafety, X64Scope)
from repro.analysis.core import SourceFile

REPO_ROOT = Path(__file__).resolve().parent.parent


def _check(checker, source, rel):
    src = SourceFile.from_source(textwrap.dedent(source), rel)
    proj = Project([src], REPO_ROOT)
    return run_checkers(proj, [checker])


def _codes(diags):
    return [d.code for d in diags]


# ---- SIM001 jit purity / performance contract -------------------------------

JAX_REL = "src/repro/serving/fastsim_jax.py"


def test_sim001_flags_bulk_scatter_in_loop_body():
    diags = _check(JitPurity(), """
        from jax import lax
        import jax.numpy as jnp

        def run(out, vals, n):
            def body(st):
                t, out = st
                sink = jnp.where(vals > 0)[0].reshape(-1)
                out = out.at[sink].set(vals)
                return t + 1, out
            def cond(st):
                return st[0] < n
            return lax.while_loop(cond, body, (0, out))
        """, JAX_REL)
    assert _codes(diags) == ["SIM001"]
    assert "bulk scatter" in diags[0].message


def test_sim001_allows_single_element_update_and_post_loop_flush():
    diags = _check(JitPurity(), """
        from jax import lax
        import jax.numpy as jnp

        def run(out, vals, n, active, mem):
            def body(st):
                t, out = st
                i = jnp.argmin(vals)
                out = out.at[i].set(vals[i], mode="drop")
                return t + 1, out
            def cond(st):
                return st[0] < n
            t, out = lax.while_loop(cond, body, (0, out))
            sink = jnp.where(active, mem, n).reshape(-1)
            return out.at[sink].set(vals)
        """, JAX_REL)
    assert diags == []


def test_sim001_flags_python_branch_on_traced_value():
    diags = _check(JitPurity(), """
        from jax import lax

        def run(x, n):
            def body(i, x):
                if x > 0:
                    x = x - 1
                return x
            return lax.fori_loop(0, n, body, x)
        """, JAX_REL)
    assert _codes(diags) == ["SIM001"]
    assert "Python `if`" in diags[0].message


def test_sim001_allows_static_branch_in_pallas_kernel():
    # keyword-only params are static configuration (the Pallas idiom):
    # branching on them is compile-time specialization, not impurity
    diags = _check(JitPurity(), """
        import functools
        from jax.experimental import pallas as pl

        def _kernel(q_ref, o_ref, *, causal, block_q):
            if causal:
                o_ref[...] = q_ref[...] * 2
            else:
                o_ref[...] = q_ref[...]

        def call(q):
            kernel = functools.partial(_kernel, causal=True, block_q=64)
            return pl.pallas_call(kernel, out_shape=None)(q)
        """, "src/repro/kernels/attn/attn.py")
    assert diags == []


def test_sim001_flags_tracer_coercion():
    diags = _check(JitPurity(), """
        import numpy as np
        from jax import lax

        def run(x, n):
            def body(i, x):
                return x + float(x) + np.exp(x)
            return lax.fori_loop(0, n, body, x)
        """, JAX_REL)
    assert sorted(_codes(diags)) == ["SIM001", "SIM001"]


def test_sim001_ignores_files_outside_scope():
    diags = _check(JitPurity(), """
        from jax import lax
        def run(x, n):
            def body(i, x):
                if x > 0:
                    return x - 1
                return x
            return lax.fori_loop(0, n, body, x)
        """, "src/repro/serving/simulator.py")
    assert diags == []


# ---- SIM002 x64 scope --------------------------------------------------------


def test_sim002_flags_global_config_update():
    diags = _check(X64Scope(), """
        import jax
        jax.config.update("jax_enable_x64", True)
        """, "src/repro/serving/foo.py")
    assert _codes(diags) == ["SIM002"]


def test_sim002_flags_unscoped_enable_x64_call():
    diags = _check(X64Scope(), """
        import jax
        ctx = jax.enable_x64(True)
        """, "src/repro/serving/foo.py")
    assert _codes(diags) == ["SIM002"]


def test_sim002_allows_scoped_with_block():
    diags = _check(X64Scope(), """
        import jax

        def run():
            with jax.enable_x64(True):
                return 1
        """, "src/repro/serving/foo.py")
    assert diags == []


def test_sim002_repo_fastsim_jax_is_scoped():
    src = SourceFile.parse(
        REPO_ROOT / "src/repro/serving/fastsim_jax.py", REPO_ROOT)
    proj = Project([src], REPO_ROOT)
    assert run_checkers(proj, [X64Scope()]) == []


# ---- SIM003 unit safety ------------------------------------------------------


def test_sim003_flags_seconds_plus_tokens():
    diags = _check(UnitSafety(), """
        def f(r, t):
            return t + r.l_out
        """, "src/repro/serving/foo.py")
    assert _codes(diags) == ["SIM003"]
    assert "seconds" in diags[0].message and "tokens" in diags[0].message


def test_sim003_flags_mixed_comparison_and_augassign():
    diags = _check(UnitSafety(), """
        def f(r, price):
            if r.t_finish > r.l_real:
                price += r.gpu_s
        """, "src/repro/serving/foo.py")
    assert sorted(_codes(diags)) == ["SIM003", "SIM003"]


def test_sim003_allows_same_dimension_and_wildcards():
    diags = _check(UnitSafety(), """
        def f(r, t, self):
            r.t_decode_spent += max(self.t - r.t_preempted, 0.0)
            dur = t - r.arrival + 0.25
            total = r.l_in + r.l_out
            cost = price_per_s * dur    # mult changes dimension: wildcard
            return dur, total, cost
        """, "src/repro/serving/foo.py")
    assert diags == []


def test_sim003_out_of_scope_dirs_not_checked():
    diags = _check(UnitSafety(), "x = t_end + l_out\n",
                   "benchmarks/bench_foo.py")
    assert diags == []


# ---- SIM004 clock monotonicity ----------------------------------------------


def test_sim004_flags_adhoc_clock_stamp():
    diags = _check(ClockMonotonicity(), """
        def sneak(r, t):
            r.t_finish = t
        """, "src/repro/serving/router.py")
    assert _codes(diags) == ["SIM004"]
    assert "t_finish" in diags[0].message


def test_sim004_allows_blessed_helper_and_array_setup():
    diags = _check(ClockMonotonicity(), """
        import numpy as np

        class SimWorker:
            def __init__(self, n):
                self.t_w = np.zeros(n)   # allocation, not a stamp

            def advance_to(self, r, t):
                r.t_first_token = t
                r.t_finish = t
        """, "src/repro/serving/simulator.py")
    assert diags == []


def test_sim004_flags_clock_array_element_write_elsewhere():
    diags = _check(ClockMonotonicity(), """
        def hack(eng, t):
            eng.t_w[0] = t
        """, "src/repro/serving/router.py")
    assert _codes(diags) == ["SIM004"]


# ---- SIM005 shim freeze ------------------------------------------------------

SHIM_SRC = '''
def simulate(trace):
    """Old entry point.

    .. deprecated:: use api.run
    """

def run_heartbeat_loop(trace):
    """The real engine."""
'''


def _shim_project(client_src, client_rel):
    shim = SourceFile.from_source(SHIM_SRC, "src/repro/serving/simulator.py")
    client = SourceFile.from_source(textwrap.dedent(client_src), client_rel)
    return Project([shim, client], REPO_ROOT)


def test_sim005_flags_new_src_importer_of_deprecated_shim():
    proj = _shim_project(
        "from repro.serving.simulator import simulate\n",
        "src/repro/serving/router.py")
    diags = run_checkers(proj, [ShimFreeze()])
    assert _codes(diags) == ["SIM005"]
    assert "simulate" in diags[0].message


def test_sim005_flags_module_attribute_use():
    proj = _shim_project(
        "from repro.serving import simulator\n"
        "plan = simulator.min_workers_for_slo\n",
        "src/repro/serving/router.py")
    # min_workers_for_slo is in the fallback set only when no shim module
    # is in the project; here the fixture module defines just `simulate`,
    # so use `simulate` for the attribute path instead
    proj2 = _shim_project(
        "from repro.serving import simulator\n"
        "plan = simulator.simulate\n",
        "src/repro/serving/router.py")
    assert run_checkers(proj, [ShimFreeze()]) == []
    assert _codes(run_checkers(proj2, [ShimFreeze()])) == ["SIM005"]


def test_sim005_allows_hub_reexport_and_fresh_entry_points():
    hub = _shim_project(
        "from repro.serving.simulator import simulate\n",
        "src/repro/serving/__init__.py")
    assert run_checkers(hub, [ShimFreeze()]) == []
    fresh = _shim_project(
        "from repro.serving.simulator import run_heartbeat_loop\n",
        "src/repro/serving/router.py")
    assert run_checkers(fresh, [ShimFreeze()]) == []
    test_file = _shim_project(
        "from repro.serving.simulator import simulate\n",
        "tests/test_old_api.py")
    assert run_checkers(test_file, [ShimFreeze()]) == []


# ---- SIM006 envelope coverage ------------------------------------------------

API_SRC = """
class Scenario:
    workload: object = None
    seed: int = 0

class Colocated:
    heartbeat: float = 0.25
    policy: str = "aladdin"

class FixedScale:
    n: int = None
"""


def _envelope_project(validator_src):
    api = SourceFile.from_source(API_SRC, "src/repro/serving/api.py")
    val = SourceFile.from_source(textwrap.dedent(validator_src),
                                 "src/repro/serving/fastsim.py")
    return Project([api, val], REPO_ROOT)


def test_sim006_flags_uninspected_field():
    proj = _envelope_project("""
        def check_colocated_envelope(sc):
            if sc.workload is None:
                raise ValueError("no workload")
            _ = sc.topology.heartbeat, sc.topology.policy, sc.scaling.n
        """)
    diags = run_checkers(proj, [EnvelopeCoverage()])
    assert _codes(diags) == ["SIM006"]
    assert "Scenario.seed" in diags[0].message


def test_sim006_passes_when_every_field_is_inspected():
    proj = _envelope_project("""
        def check_colocated_envelope(sc):
            _ = (sc.workload, sc.seed, sc.topology.heartbeat,
                 sc.topology.policy, sc.scaling.n)
        """)
    assert run_checkers(proj, [EnvelopeCoverage()]) == []


def test_sim006_repo_api_is_fully_covered():
    proj = Project.collect([REPO_ROOT / "src"], REPO_ROOT)
    assert run_checkers(proj, [EnvelopeCoverage()]) == []


# ---- suppressions / baseline mechanics --------------------------------------


def test_inline_suppression_same_line_and_annotate_above():
    src = """
        def sneak(r, t):
            r.t_finish = t  # simlint: ignore[SIM004]
            # simlint: ignore[SIM004]
            r.t_first_token = t
            r.t_preempted = t
        """
    diags = _check(ClockMonotonicity(), src, "src/repro/serving/x.py")
    assert len(diags) == 1          # only the unsuppressed third stamp
    assert diags[0].line_text == "r.t_preempted = t"


def test_inline_suppression_wrong_code_does_not_apply():
    diags = _check(ClockMonotonicity(), """
        def sneak(r, t):
            r.t_finish = t  # simlint: ignore[SIM001]
        """, "src/repro/serving/x.py")
    assert _codes(diags) == ["SIM004"]


def test_bare_suppression_covers_all_codes():
    diags = _check(ClockMonotonicity(), """
        def sneak(r, t):
            r.t_finish = t  # simlint: ignore
        """, "src/repro/serving/x.py")
    assert diags == []


def test_baseline_accepts_by_fingerprint_and_reports_stale():
    d = Diagnostic(code="SIM004", path="src/x.py", line=3, col=4,
                   message="m", line_text="r.t_finish = t")
    b = Baseline.from_diagnostics([d])
    moved = Diagnostic(code="SIM004", path="src/x.py", line=99, col=0,
                       message="m", line_text="r.t_finish = t")
    assert b.accepts(moved)          # line drift tolerated
    assert b.stale_entries() == []
    b2 = Baseline.from_diagnostics([d])
    other = Diagnostic(code="SIM004", path="src/x.py", line=3, col=4,
                       message="m", line_text="r.t_finish = now")
    assert not b2.accepts(other)     # text changed: no longer accepted
    assert len(b2.stale_entries()) == 1


def test_baseline_roundtrip(tmp_path):
    d = Diagnostic(code="SIM001", path="src/a.py", line=1, col=0,
                   message="m", line_text="x = 1")
    p = tmp_path / "base.json"
    Baseline.from_diagnostics([d]).save(p)
    loaded = Baseline.load(p)
    assert loaded.accepts(d)
    data = json.loads(p.read_text())
    assert data["version"] == 1 and len(data["entries"]) == 1


# ---- the acceptance gate: the repo itself is clean --------------------------


def test_registry_has_six_active_checkers():
    assert len(ALL_CHECKERS) >= 6
    assert len({c.code for c in ALL_CHECKERS}) == len(ALL_CHECKERS)


def test_repo_simlint_exits_zero():
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "src", "scripts",
         "benchmarks", "--baseline", "scripts/simlint_baseline.json"],
        cwd=REPO_ROOT, capture_output=True, text=True,
        env={**__import__("os").environ,
             "PYTHONPATH": str(REPO_ROOT / "src")})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


def test_cli_reports_findings_with_nonzero_exit(tmp_path):
    bad = tmp_path / "src" / "repro" / "serving" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("def sneak(r, t):\n    r.t_finish = t\n")
    (tmp_path / "pyproject.toml").write_text("")   # repo-root marker
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "src"],
        cwd=tmp_path, capture_output=True, text=True,
        env={**__import__("os").environ,
             "PYTHONPATH": str(REPO_ROOT / "src")})
    assert proc.returncode == 1
    assert "SIM004" in proc.stdout


def test_cli_list_codes():
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--list-codes"],
        cwd=REPO_ROOT, capture_output=True, text=True,
        env={**__import__("os").environ,
             "PYTHONPATH": str(REPO_ROOT / "src")})
    assert proc.returncode == 0
    for code in ("SIM001", "SIM002", "SIM003", "SIM004", "SIM005",
                 "SIM006"):
        assert code in proc.stdout


def test_stale_baseline_entry_fails(tmp_path):
    (tmp_path / "pyproject.toml").write_text("")
    srcdir = tmp_path / "src"
    srcdir.mkdir()
    (srcdir / "clean.py").write_text("x = 1\n")
    base = tmp_path / "base.json"
    base.write_text(json.dumps({"version": 1, "entries": [
        {"code": "SIM004", "path": "src/gone.py",
         "text": "r.t_finish = t", "reason": "old"}]}))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "src",
         "--baseline", str(base)],
        cwd=tmp_path, capture_output=True, text=True,
        env={**__import__("os").environ,
             "PYTHONPATH": str(REPO_ROOT / "src")})
    assert proc.returncode == 1
    assert "stale" in proc.stdout
