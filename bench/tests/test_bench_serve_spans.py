"""The program's ``serve.*`` spans in the benchmark: the readers that take
them, the idle gaps they name, the metrics that stay as they were without
them, and the program's own counters against the benchmark's outside
counts."""
import copy
import itertools
import json
from pathlib import Path

import jax
import pytest

import repro.serving.engine as engine
from bench import harness, run, serve_spans
from bench.run import Step, TracedRun
from bench.trace_reduce import Trace
from benchroot import PEAKS, make_root

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).resolve().parent / "fixtures" / "trace_small.json"
MS = 1_000_000
NEW = ["sched_ms_per_beat.conv", "sched_ms_per_beat.code",
       "decode_host_gap_ms.conv", "prefill_host_gap_ms.code",
       "empty_slot_share.code"]


def synthetic():
    """Window 0-100 ms on one chip, busy 10-40 ms (a decode step) and
    60-90 ms (a prefill); one heartbeat of the program at 3-97 ms, with a
    decode step, a step whose only request was preempted, and a prefill
    step inside it, and one after the window."""
    record = {
        "host": [["bench.window", 0, 200 * MS],
                 ["bench.heartbeat", 2 * MS, 96 * MS],
                 ["bench.engine_step", 8 * MS, 34 * MS],
                 ["bench.engine_step", 42 * MS, 1.5 * MS],
                 ["bench.engine_step", 55 * MS, 37 * MS]],
        "devices": {"0": {
            "modules": [["jit_decode_step(7)", 10 * MS, 30 * MS],
                        ["jit_prefill_step(9)", 60 * MS, 30 * MS]],
            "ops": [["fusion.1", 10 * MS, 30 * MS],
                    ["flash_attention", 60 * MS, 30 * MS]]}}}
    decode = {"active": 2, "slots": 4, "empty": 2, "preempted": 0}
    spans = [
        ("serve.heartbeat", 3 * MS, 94 * MS, {"beat": 1}),
        ("serve.place", 3 * MS, 4 * MS, {"placed": 0, "left": 1}),
        ("serve.step", 8.5 * MS, 33 * MS, {}),
        ("serve.decode", 9 * MS, 32 * MS, decode),
        ("serve.launch", 9 * MS, 1 * MS, {}),
        ("serve.sample", 10 * MS, 30 * MS, {}),
        ("serve.step", 42.2 * MS, 1 * MS, {}),
        ("serve.decode", 42.5 * MS, 0.5 * MS,
         {"active": 0, "slots": 4, "empty": 0, "preempted": 1}),
        ("serve.step", 55.5 * MS, 36 * MS, {}),
        ("serve.prefill", 56 * MS, 35 * MS,
         {"req": 1, "tokens": 100, "bucket": 128}),
        ("serve.prefill_program", 56 * MS, 4 * MS, {}),
        ("serve.write_kv", 90 * MS, 0.5 * MS, {}),
        ("serve.refit", 92 * MS, 2 * MS, {"worker": 1}),
        ("serve.upkeep", 94 * MS, 2.5 * MS, {}),
        ("serve.heartbeat", 150 * MS, 10 * MS, {"beat": 2}),
    ]
    return record, spans


class Run:
    """What a reader sees, reduced to the trace."""

    def __init__(self, record, seconds):
        self.trace = Trace(record, seconds)


def test_readers_by_hand():
    record, spans = synthetic()
    r = Run(record, 0.1)
    serve_spans.attach(r, spans)
    got = {m: harness.metric_reader(m, ROOT)(r) for m in NEW}
    # 94 ms less the steps inside (33 + 1 + 36 ms); idle in the decode
    # step that ran, 32 - 30 ms, and in the prefill, 35 - 30 ms; 2 of 4
    # slots empty in the one decode step that ran
    assert got == {"sched_ms_per_beat.conv": pytest.approx(24.0),
                   "sched_ms_per_beat.code": pytest.approx(24.0),
                   "decode_host_gap_ms.conv": pytest.approx(2.0),
                   "prefill_host_gap_ms.code": pytest.approx(5.0),
                   "empty_slot_share.code": pytest.approx(50.0)}


def test_idle_gaps_named_by_the_program_s_spans():
    # gaps 0-10, 40-60, 90-100 ms, midpoints 5, 50, 95 ms
    record, spans = synthetic()
    r = Run(record, 0.1)
    before = r.trace.idle_gaps()
    assert [n for n, _ in before] == ["bench.heartbeat"] * 3
    serve_spans.attach(r, spans)
    after = r.trace.idle_gaps()
    assert [(n, round(s * 1e3, 6)) for n, s in after] == [
        ("serve.heartbeat", 20.0), ("serve.place", 10.0),
        ("serve.upkeep", 10.0)]


@pytest.mark.parametrize("name", NEW)
def test_readers_read_nothing_without_the_program_s_spans(name):
    record, _ = synthetic()
    r = Run(record, 0.1)
    serve_spans.attach(r, [])
    assert harness.metric_reader(name, ROOT)(r) is None


def fixture_run():
    """The recorded window (three decode steps of phi4-conv on one v5e),
    as the readers see it."""
    rec = json.loads(FIXTURE.read_text())
    trace = Trace(rec, rec["seconds"])
    cfg = harness.load_cell("phi4-conv", ROOT).config
    steps = [Step(s, [], [1500 + 100 * i for i in range(6)])
             for s, _ in trace.spans("bench.engine_step")]
    return TracedRun(trace, steps, cfg, harness.peaks("TPU v5 lite", ROOT))


def existing_metrics():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [m["name"] for m in bench["per_layer"] if m["name"] not in NEW]


def test_recorded_trace_reads_as_before_with_and_without_serve_spans():
    """Every metric that was there reads the same on the recorded window,
    with no ``serve.*`` spans (as from a program without them) and with
    them added; the new ones read only where the spans are."""
    rec = json.loads(FIXTURE.read_text())
    plain = fixture_run()
    assert plain.trace.idle_share() == \
        pytest.approx(rec["expect"]["idle_share"], rel=1e-9)
    gaps = plain.trace.idle_gaps()
    serve_spans.attach(plain, [])
    assert plain.trace.idle_gaps() == gaps
    before = {m: harness.metric_reader(m, ROOT)(plain)
              for m in existing_metrics()}
    assert before["decode_step_ms.conv"] == pytest.approx(
        1e3 * rec["expect"]["decode_step"][0] / 3, rel=1e-9)
    assert all(harness.metric_reader(m, ROOT)(plain) is None for m in NEW)

    spanned = fixture_run()
    steps = spanned.trace.spans("bench.engine_step")
    beat = [e for e in spanned.trace.host if e[0] == "bench.heartbeat"][0]
    decode = {"active": 6, "slots": 6, "empty": 0, "preempted": 0}
    extra = [("serve.heartbeat", beat[1], beat[2], {"beat": 1})] + [
        x for a, b in steps for x in (
            ("serve.step", a + 1000, b - a - 2000, {}),
            ("serve.decode", a + 2000, b - a - 4000, decode))]
    serve_spans.attach(spanned, extra)
    for m, v in before.items():
        got = harness.metric_reader(m, ROOT)(spanned)
        assert got == (None if v is None else pytest.approx(v, rel=1e-9)), m
    assert harness.metric_reader("decode_host_gap_ms.conv",
                                 ROOT)(spanned) > 0.0
    assert [n for n, _ in spanned.trace.idle_gaps()] != [n for n, _ in gaps]
    assert [s for _, s in spanned.trace.idle_gaps()] == \
        [s for _, s in gaps]


def test_finds_its_profile_by_the_window(tmp_path):
    """``of`` reads the profile whose window starts where the run's does,
    and reads nothing from a profile without the program's spans."""
    from jax.profiler import TraceAnnotation
    for name, program in (("with", True), ("without", False)):
        jax.profiler.start_trace(str(tmp_path / ".bench_trace" / name))
        with TraceAnnotation("bench.window"):
            if program:
                with TraceAnnotation("serve.heartbeat", beat=1):
                    pass
        jax.profiler.stop_trace()
    runs = {}
    for name in ("with", "without"):
        path = next((tmp_path / ".bench_trace" / name).glob(
            "plugins/profile/*/*.xplane.pb"))
        window, _ = serve_spans.load_xplane(path)
        runs[name] = Run({"host": [["bench.window", window, 1e9]],
                          "devices": {"0": {"ops": [], "modules": []}}},
                         1.0)
    found = serve_spans.of(runs["with"], tmp_path)
    assert [s for _, _, s in found.named("serve.heartbeat")] == [{"beat": 1}]
    assert serve_spans.of(runs["without"], tmp_path) is None


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", ["tiny-conv", "tiny-code"])
def test_program_counts_equal_the_benchmark_s(tiny_root, capsys, monkeypatch,
                                              cell):
    """Placement refusals in the window by constraint, and preemptions
    over the run, as the program counts them and as ``Recorder`` counts
    them from outside. Every seventh page check fails, so that requests
    are preempted; the closed loop's clients outnumber the slots, so that
    there placement refuses."""
    seen = {}
    window = run.serve_window

    def counted(cluster, traffic, rec, *args, on_open=None, **kw):
        def opened():
            seen["open"] = copy.deepcopy(cluster.stats)
            on_open()
        w = window(cluster, traffic, rec, *args, on_open=opened, **kw)
        seen.update(stats=copy.deepcopy(cluster.stats), rec=rec)
        return w
    checks = itertools.count(1)
    has_page = engine.PagedEngine._ensure_page

    def scarce(self, slot):
        return has_page(self, slot) and next(checks) % 7 != 0
    monkeypatch.setattr(run, "serve_window", counted)
    monkeypatch.setattr(engine.PagedEngine, "_ensure_page", scarce)
    assert run.main(["--workload", cell, "--seed", "5", "--seconds", "0.5"],
                    root=tiny_root, require_chip=False, peaks=PEAKS) == 0
    capsys.readouterr()
    stats, rec = seen["stats"], seen["rec"]
    refused = {c: n - seen["open"].refused[c]
               for c, n in stats.refused.items()}
    assert refused == rec.refused
    assert stats.preemptions == rec.preempted > 0
    if cell == "tiny-conv":
        assert sum(refused.values()) > 0
