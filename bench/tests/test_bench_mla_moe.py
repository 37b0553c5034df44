"""The ``mla_moe`` family and the two cells of this configuration: a
three-layer cut of Moonlight-16B-A3B served through ``bench/run.py`` on the
CPU against the family's float32 reference (and not against its int8
control), the engine's logits through the latent pool against the
reference's, the cut's parameters against the tree, the harness finding
both new cells, and the new metric readers on a small synthetic trace."""
import dataclasses
import json
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness, run, serve_spans, weights
from bench.run import Step, TracedRun
from bench.trace_reduce import Trace
from bench.traffic import Traffic
from bench.work import mla_decode_attention, mla_moe_step
from benchroot import PEAKS

ROOT = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).resolve().parent / "fixtures"
TINY = json.loads((FIXTURES / "tiny-mla-moe.json").read_text())
SEED = 3000000019
MS = 1_000_000
NEW_METRICS = ["decode_step_ms.moonlight",
               "mla_decode_attention_roofline.moonlight",
               "mfu.decode.moonlight", "expert_tokens_per_step.moonlight",
               "idle_share.moonlight", "sched_ms_per_beat.x4",
               "idle_share.x4"]


@pytest.fixture(scope="module")
def tiny_arch():
    """The fixture's program arch, ``moonlight-tiny``: the registered
    Moonlight with the fixture's sizes (a router of 16 experts, of which
    the fixture's chip holds 4), registered for this module only."""
    from repro.configs import MLAConfig, get_arch, register
    from repro.configs.base import _REGISTRY
    base = get_arch("moonlight-16b-a3b")
    arch = register(dataclasses.replace(
        base, name="moonlight-tiny", n_layers=3, d_model=64, n_heads=4,
        n_kv_heads=4, vocab=1024,
        moe=dataclasses.replace(base.moe, n_experts=16, top_k=4,
                                d_expert=32, d_shared=64, d_dense=128),
        mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16)))
    yield arch
    del _REGISTRY["moonlight-tiny"]


@pytest.fixture(scope="module")
def family():
    return harness.family("mla_moe", ROOT)


def make_root(tmp: Path) -> Path:
    """A benchmark root of files only: the fixture's configuration under
    the ``mla_moe`` family and the tiny closed mix."""
    for d in ("configs", "traffic", "metrics", "families"):
        (tmp / "bench" / d).mkdir(parents=True)
    shutil.copy(FIXTURES / "tiny-mla-moe.json",
                tmp / "bench/configs/tiny-mla-moe.json")
    shutil.copy(ROOT / "bench/families/mla_moe.py",
                tmp / "bench/families/mla_moe.py")
    shutil.copy(FIXTURES / "tiny-closed.json",
                tmp / "bench/traffic/tiny-closed.json")
    (tmp / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny-mla-moe",
                     "file": "bench/configs/tiny-mla-moe.json"}],
        "workloads": [{"name": "tiny-mla-conv", "config": "tiny-mla-moe",
                       "traffic": "tiny-closed", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "output_tok_s", "unit": "tokens/s"}],
        "per_layer": []}))
    return tmp


@pytest.fixture(scope="module")
def root(tmp_path_factory, tiny_arch):
    return make_root(tmp_path_factory.mktemp("bench-mla"))


def one_run(root, capsys, control=None):
    rc = run.main(["--workload", "tiny-mla-conv", "--seed", str(SEED),
                   "--seconds", "0.5"], root=root, require_chip=False,
                  peaks=PEAKS, control=control)
    out, err = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), err


def test_served_through_run_is_correct(root, capsys):
    res, err = one_run(root, capsys)
    assert res["correct"] is True, err[-2000:]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "programs built in window [] (backend compiles 0)" in err


def test_int8_control_is_not_correct(root, capsys):
    res, _ = one_run(root, capsys, control="int8")
    gap = res["compared"]["served_gap_max"]
    assert res["correct"] is False
    assert gap["value"] > gap["limit"]


def test_engine_logits_match_the_reference(tiny_arch, family):
    """Prefill, then 10 decode steps through the latent pool, for three
    interleaved requests: every logits row the engine sampled from against
    the reference's (expanded attention, every held expert on every
    token). Float32 on the CPU on both sides, so they differ by rounding
    order alone: 1e-4 on logits of order 1-5. The reference's rows agree
    with the int8 control's far less (checked beside)."""
    from repro.core.request import ReqState, Request
    from repro.serving.engine import EngineConfig, PagedEngine
    params = weights.make(family.shapes(TINY), 11)
    eng = PagedEngine(tiny_arch, params, EngineConfig(
        max_batch=3, page_size=16, n_pages=32, max_pages_per_seq=8,
        interpret=True))
    seen = {}
    eng.on_logits = lambda r, lg: seen.setdefault(r.id, []).append(
        np.asarray(lg))
    rng = np.random.default_rng(0)
    reqs = []
    for n in (40, 17, 9):
        r = Request(l_in=n, l_pred=11, l_real=11)
        r.tokens = [int(x) for x in rng.integers(2, 1024, n)]
        reqs.append(r)
        eng.submit(r)
        eng.step()
    while not all(r.state == ReqState.FINISHED for r in reqs):
        eng.step()
    for r in reqs:
        toks = np.zeros((64,), np.int32)
        toks[:len(r.tokens) - 1] = r.tokens[:-1]
        rows = np.zeros((16,), np.int32)
        rows[:11] = np.arange(r.l_in - 1, r.l_in + 10)
        ref, low = (np.asarray(family.logit_rows(
            params, toks, rows, cfg=TINY, mode=m, q_block=32))[:11]
            for m in ("f32", "int8"))
        got = np.stack(seen[r.id])
        np.testing.assert_allclose(got, ref, rtol=0, atol=1e-4)
        assert np.abs(low - ref).max() > 100 * np.abs(got - ref).max()
    # 66 prompt and 30 decoded tokens, top-4, two expert layers
    assert eng.stats.expert_assignments == (66 + 30) * 4 * 2
    assert 0 < eng.stats.held_assignments < eng.stats.expert_assignments


def test_param_count_of_the_cut_arch(tiny_arch, family):
    """The arch's parameters with the chip's share of experts are the
    served tree's elements, less the final norm's weight, which
    ``param_count`` leaves out for every arch."""
    tree = weights.make(family.shapes(TINY), 1)
    n = sum(x.size for x in jax.tree.leaves(tree))
    assert tiny_arch.param_count(held_experts=4) + tiny_arch.d_model == n
    arch = run.program_arch(
        json.loads((ROOT / "bench/configs/moonlight-16b-a3b-ep8.json")
                   .read_text()), family)
    assert arch.param_count(held_experts=8) == 3_364_613_248
    assert arch.param_count() == 15_960_108_160


def test_check_refuses_what_differs(tiny_arch, family):
    family.check(TINY, tiny_arch)
    with pytest.raises(ValueError, match="kv_lora_rank"):
        family.check(dict(TINY, kv_lora_rank=64), tiny_arch)
    with pytest.raises(ValueError, match="n_routed_experts"):
        family.check(dict(TINY, n_routed_experts=8), tiny_arch)
    moved = dict(TINY, program=dict(TINY["program"], held_experts={
        "first": 4, "count": 4}))
    with pytest.raises(ValueError, match="held_experts.first"):
        family.check(moved, tiny_arch)


def test_warm_up_leaves_nothing_to_build_while_serving(root):
    """After the family's warm-up, serving a prompt of each length the mix
    sends builds no program."""
    from repro.core.request import ReqState, Request
    cell = harness.load_cell("tiny-mla-conv", root)
    fam = harness.family(cell.config["family"], root)
    arch = run.program_arch(cell.config, fam)
    params = weights.make(fam.shapes(cell.config), SEED)
    traffic = Traffic(cell.traffic, arch.vocab, SEED)
    cluster = run.build_cluster(cell, arch, params, traffic)
    lengths = traffic.prompt_lengths()
    counter = run._compile_counter(jax)
    run.warm_up(cluster, lengths, fam)
    reqs = [Request(l_in=n, l_pred=0, l_real=3, arrival=0.0)
            for n in lengths]
    for r in reqs:
        r.tokens = traffic.prompt_tokens(r.l_in)
    counter.update(programs=[], backend=0, counting=True)
    try:
        for r in reqs:
            cluster.submit(r)
        for _ in range(10 * len(reqs)):
            cluster.heartbeat()
            if all(r.state == ReqState.FINISHED for r in reqs):
                break
    finally:
        counter["counting"] = False
    assert counter["programs"] == []
    assert all(r.state == ReqState.FINISHED for r in reqs)
    eng = next(iter(cluster.workers.values())).engine
    lowered = fam.programs(eng, max(lengths))
    assert list(lowered) == ["prefill", "decode"]


def test_harness_finds_both_new_cells():
    moon = harness.load_cell("moonlight-conv", ROOT)
    assert moon.chips == 1 and moon.config["family"] == "mla_moe"
    assert moon.config["n_routed_experts"] == 8
    assert moon.config["published"] == {"n_routed_experts": 64}
    x4 = harness.load_cell("phi4-conv-x4", ROOT)
    assert x4.chips == 4 and x4.config["family"] == "dense_gqa"
    conv = harness.load_cell("phi4-conv", ROOT)
    assert x4.traffic["prompt"] == conv.traffic["prompt"]
    assert x4.traffic["output"] == conv.traffic["output"]
    assert {m["name"] for m in moon.end_to_end} == {"setup_s",
                                                    "output_tok_s"}
    assert {m["name"] for m in x4.end_to_end} == {"setup_s", "output_tok_s"}
    names = {m["name"] for m in moon.per_layer + x4.per_layer}
    assert names == set(NEW_METRICS)
    for cell in (moon, x4):
        harness.family(cell.config["family"], ROOT)
        for m in cell.per_layer:
            assert callable(harness.metric_reader(m["name"], ROOT))
    assert harness.peaks("TPU v5 lite", ROOT)["hbm_bytes_per_s"] == 819e9


def synthetic_run():
    """A 100 ms window on one chip: two decode steps of the Moonlight
    program (10-30 ms, 50-70 ms), each with 4 ms of the latent kernel, the
    program's two ``serve.decode`` spans with their ``held`` stats, and
    one heartbeat."""
    cfg = json.loads((ROOT / "bench/configs/moonlight-16b-a3b-ep8.json")
                     .read_text())
    record = {
        "host": [["bench.window", 0, 100 * MS],
                 ["bench.heartbeat", 5 * MS, 70 * MS],
                 ["bench.engine_step", 9 * MS, 22 * MS],
                 ["bench.engine_step", 49 * MS, 22 * MS]],
        "devices": {"0": {
            "modules": [["jit_mla_moe_decode_step(3)", 10 * MS, 20 * MS],
                        ["jit_mla_moe_decode_step(3)", 50 * MS, 20 * MS]],
            "ops": [["mla_decode_attention.2", 10 * MS, 4 * MS],
                    ["fusion.1", 14 * MS, 16 * MS],
                    ["mla_decode_attention.2", 50 * MS, 4 * MS],
                    ["fusion.1", 54 * MS, 16 * MS]]}}}
    steps = [Step(9 * MS, [], [1000, 2000]), Step(49 * MS, [], [1001, 2001])]
    tr = TracedRun(Trace(record, 0.1), steps, cfg, PEAKS)
    spans = [("serve.heartbeat", 5 * MS, 70 * MS, {"beat": 1}),
             ("serve.step", 9 * MS, 22 * MS, {}),
             ("serve.decode", 9 * MS, 22 * MS,
              {"active": 2, "slots": 32, "empty": 0, "pages": 190,
               "held": 312, "held_max": 100}),
             ("serve.step", 49 * MS, 22 * MS, {}),
             ("serve.decode", 49 * MS, 22 * MS,
              {"active": 2, "slots": 32, "empty": 0, "pages": 190,
               "held": 104, "held_max": 52})]
    serve_spans.attach(tr, spans)
    return tr, cfg


def test_new_readers_by_hand():
    tr, cfg = synthetic_run()
    got = {m: harness.metric_reader(m, ROOT)(tr) for m in NEW_METRICS}
    assert got["decode_step_ms.moonlight"] == pytest.approx(20.0)
    # 416 assignments over 8 experts x 26 layers x 2 steps
    assert got["expert_tokens_per_step.moonlight"] == pytest.approx(1.0)
    f, b = mla_decode_attention.work([1000, 2000, 1001, 2001], cfg)
    least = max(f / PEAKS["bf16_flops_per_s"], b / PEAKS["hbm_bytes_per_s"])
    assert got["mla_decode_attention_roofline.moonlight"] == \
        pytest.approx(100 * least / 0.008)
    flops = mla_moe_step.decode_flops([1000, 2000], 312, cfg) \
        + mla_moe_step.decode_flops([1001, 2001], 104, cfg)
    assert got["mfu.decode.moonlight"] == \
        pytest.approx(100 * flops / (0.04 * PEAKS["bf16_flops_per_s"]))
    assert got["idle_share.moonlight"] == got["idle_share.x4"] == \
        pytest.approx(60.0)
    assert got["sched_ms_per_beat.x4"] == pytest.approx(70 - 44)


def test_new_readers_read_nothing_without_the_programs_spans():
    """A program without the expert counts (the parent's) gives the
    readers that need them nothing to read, and no error."""
    tr, _ = synthetic_run()
    serve_spans.attach(tr, [])
    assert harness.metric_reader("expert_tokens_per_step.moonlight",
                                 ROOT)(tr) is None
    assert harness.metric_reader("mfu.decode.moonlight", ROOT)(tr) is None


def test_work_at_published_widths():
    """Hand counts at Moonlight's widths: attention in the absorbed form
    13,762,560 parameters a layer (6,291,456 W_q + 1,179,648 W_kv_a +
    2 x 1,048,576 per-head W_UK and W_UV + 4,194,304 W_o), the dense
    layer 69,206,016, router and shared experts 131,072 + 17,301,504 in
    each of 26 layers, the head 335,544,320; an expert 8,650,752."""
    cfg = json.loads((ROOT / "bench/configs/moonlight-16b-a3b-ep8.json")
                     .read_text())
    assert mla_moe_step.token_params(cfg) == (
        13_762_560 * 27 + 69_206_016 + 26 * (131_072 + 17_301_504)
        + 335_544_320)
    assert mla_moe_step.expert_params(cfg) == 8_650_752
    f, b = mla_decode_attention.work([100, 300], cfg)
    assert f == 2.0 * 400 * 16 * (576 + 512) * 27
    assert b == (400 * 576 + 2 * 16 * (576 + 512)) * 2 * 27
