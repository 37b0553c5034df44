"""The trace reduction: busy union, idle share, time by name, and idle
gaps named by the host span around them."""
import json
from pathlib import Path

import pytest

from bench.trace_reduce import Trace, module_name, op_name, union

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "trace_small.json"

MS = 1_000_000


def synthetic():
    """Window 0-100 ms on one chip: a decode program at 10-40 ms with two
    kernel calls and an overlapping fusion (busy 10-25 and 30-40), a
    prefill program at 60-90 (busy throughout)."""
    return {
        "host": [["bench.window", 0, 200 * MS],
                 ["bench.heartbeat", 5 * MS, 90 * MS],
                 ["bench.engine_step", 8 * MS, 34 * MS],
                 ["bench.engine_step", 55 * MS, 37 * MS],
                 ["bench.submit", 95 * MS, 3 * MS]],
        "devices": {"0": {
            "modules": [["jit_decode_step(7)", 10 * MS, 30 * MS],
                        ["jit_prefill_step(9)", 60 * MS, 30 * MS]],
            "ops": [["paged_decode_attention.1", 10 * MS, 10 * MS],
                    ["fusion.3", 15 * MS, 10 * MS],
                    ["paged_decode_attention.2", 30 * MS, 10 * MS],
                    ["flash_attention", 60 * MS, 20 * MS],
                    ["fusion.9", 80 * MS, 10 * MS]]}}}


def test_names():
    assert op_name("paged_decode_attention.12") == "paged_decode_attention"
    assert op_name("copy-start.2.1") == "copy-start"
    assert module_name("jit_decode_step(1234)") == "decode_step"
    assert module_name("prefill_step") == "prefill_step"


def test_union_merges_overlaps():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_busy_idle_and_time_by_name():
    t = Trace(synthetic(), 0.1)
    assert t.window_s == pytest.approx(0.1)
    assert t.busy_s("0") == pytest.approx(0.055)
    assert t.idle_share() == pytest.approx(0.45)
    ops = t.op_seconds()
    assert ops["decode_step:paged_decode_attention"] == pytest.approx(0.02)
    assert ops["decode_step:fusion"] == pytest.approx(0.01)
    assert ops["prefill_step:flash_attention"] == pytest.approx(0.02)
    decode = [(8 * MS, 42 * MS)]
    assert t.kernel_seconds("paged_decode_attention", decode) == \
        (pytest.approx(0.02), 2)
    assert t.kernel_seconds("flash_attention", decode) == (0.0, 0)
    assert t.module_seconds(lambda m: m == "prefill_step") == \
        (pytest.approx(0.03), 1)


def test_idle_gaps_named_by_host_span():
    # gaps 0-10, 25-30, 40-60, 90-100 ms; each named by the shortest host
    # span around its midpoint (5, 27.5, 50, 95 ms)
    got = [(n, round(s * 1e3, 6)) for n, s in
           Trace(synthetic(), 0.1).idle_gaps()]
    assert got == [("bench.heartbeat", 20.0), ("bench.heartbeat", 10.0),
                   ("bench.submit", 10.0), ("bench.engine_step", 5.0)]


def test_recorded_trace():
    """0.3 s of a phi4-conv window on one v5e: three decode steps, the
    paged-decode kernel once per layer in each."""
    rec = json.loads(FIXTURE.read_text())
    t = Trace(rec, rec["seconds"])
    want = rec["expect"]
    assert t.window_s == pytest.approx(rec["seconds"])
    assert t.idle_share() == pytest.approx(want["idle_share"], rel=1e-9)
    assert 0.0 < t.idle_share() < 0.1
    top = dict(t.top_ops())
    for name, secs in want["top_ops"].items():
        assert top[name] == pytest.approx(secs, rel=1e-9)
    assert next(iter(top)) == "decode_step:paged_decode_attention"
    assert len(t.spans("bench.engine_step")) == want["engine_steps"] == 3
    secs, n = t.kernel_seconds("paged_decode_attention")
    assert (secs, n) == (pytest.approx(want["paged_decode_attention"][0]),
                         want["paged_decode_attention"][1])
    lo, hi = t.lo, t.hi
    by_hand = sum(min(s + d, hi) - max(s, lo) for name, s, d in
                  rec["devices"]["0"]["ops"]
                  if name.startswith("%paged_decode_attention")
                  and s < hi and s + d > lo) * 1e-9
    assert secs == pytest.approx(by_hand)
    assert t.module_seconds(lambda m: m == "decode_step")[1] == 3
