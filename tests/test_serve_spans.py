"""The served path's spans and counters: a tiny cluster served under the
profiler on the CPU, its profile read by the benchmark's reader. The
spans nest, carry their request's id, sum to the cluster's counters, and
leave the served tokens as they are."""
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.core.request import Request
from repro.core.slo import SLO
from repro.models.model import LM
from repro.serving.cluster import ClusterConfig, ServingCluster
from repro.serving.engine import EngineConfig
from repro.serving.spans import NAMES

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from bench.serve_spans import load_xplane                      # noqa: E402

PARENT = {
    "serve.place": "serve.heartbeat", "serve.rebalance": "serve.heartbeat",
    "serve.handoff": "serve.heartbeat", "serve.refit": "serve.heartbeat",
    "serve.upkeep": "serve.heartbeat", "serve.step": "serve.heartbeat",
    "serve.prefill": "serve.step", "serve.decode": "serve.step",
    "serve.prefill_program": "serve.prefill",
    "serve.write_kv": "serve.prefill", "serve.first_token": "serve.prefill",
    "serve.pages": "serve.decode", "serve.launch": "serve.decode",
    "serve.sample": "serve.decode", "serve.bookkeep": "serve.decode",
}


def serve(profile_dir=None):
    """Eight requests on one worker of three slots and 15 pages, the KV
    budget overcommitted (theta 4) so that placement refuses and the
    engine preempts. Also returns the block tables, lengths and active
    masks of every decode launch, as the engine handed them over."""
    arch = reduced(get_arch("phi4-mini-3.8b"), n_layers=2, d_model=64,
                   vocab=256)
    cluster = ServingCluster(
        arch, LM(arch).init(jax.random.key(0)), SLO(1000.0, 1000.0),
        engine_cfg=EngineConfig(max_batch=3, page_size=8, n_pages=16,
                                max_pages_per_seq=16),
        cfg=ClusterConfig(heartbeat_iters=2, theta=4.0), n_workers=1)
    launches = []
    for w in cluster.workers.values():
        def spy(params, kv_k, kv_v, tables, lengths, tokens, active,
                _decode=w.engine._decode_jit):
            # copies: on the CPU an array may share the engine's buffers
            launches.append(tuple(np.array(x)
                                  for x in (tables, lengths, active)))
            return _decode(params, kv_k, kv_v, tables, lengths, tokens,
                           active)
        w.engine._decode_jit = spy
    rng = np.random.default_rng(0)
    reqs = []
    for _ in range(8):
        r = Request(l_in=int(rng.integers(8, 40)), l_pred=0,
                    l_real=int(rng.integers(4, 30)))
        r.tokens = [int(x) for x in rng.integers(2, arch.vocab, r.l_in)]
        reqs.append(r)
    if profile_dir is not None:
        jax.profiler.start_trace(str(profile_dir))
    for r in reqs:
        r.arrival = time.perf_counter()
        cluster.submit(r)
    cluster.run_until_drained()
    if profile_dir is not None:
        jax.profiler.stop_trace()
    return cluster, reqs, launches


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    out = tmp_path_factory.mktemp("profile")
    cluster, reqs, launches = serve(out)
    _, spans = load_xplane(
        sorted(out.glob("plugins/profile/*/*.xplane.pb"))[-1])
    by_name = {}
    for name, s, d, stats in spans:
        by_name.setdefault(name, []).append((s, s + d, stats))
    return cluster, reqs, by_name, launches


def test_every_span_is_listed_and_recorded(traced):
    _, _, spans, _ = traced
    assert set(spans) == set(NAMES)


def test_children_nest_inside_their_parent(traced):
    _, _, spans, _ = traced
    for child, parent in PARENT.items():
        outer = spans[parent]
        for s, e, _ in spans[child]:
            assert any(a <= s and e <= b for a, b, _ in outer), child


def test_submit_and_prefill_share_the_request_id(traced):
    _, reqs, spans, _ = traced
    ids = {r.id for r in reqs}
    assert {st["req"] for _, _, st in spans["serve.submit"]} == ids
    assert {st["req"] for _, _, st in spans["serve.prefill"]} == ids
    tokens = {r.id: r.l_in for r in reqs}
    assert all(st["tokens"] == tokens[st["req"]]
               for _, _, st in spans["serve.prefill"])


def test_span_stats_sum_to_the_counters(traced):
    cluster, _, spans, _ = traced
    st = cluster.stats

    def total(name, key):
        return sum(x[key] for _, _, x in spans[name])
    decode = [x for _, _, x in spans["serve.decode"] if x["active"]]
    assert st.submitted == len(spans["serve.submit"]) == 8
    assert st.heartbeats == len(spans["serve.heartbeat"])
    assert st.placed == total("serve.place", "placed") == 8
    for c in "bcde":
        assert st.refused[c] == total("serve.place", f"refused_{c}") \
            + total("serve.rebalance", f"refused_{c}")
    assert st.prefills == len(spans["serve.prefill"])
    assert st.prompt_tokens == total("serve.prefill", "tokens")
    assert st.decode_steps == len(decode)
    assert st.preemptions == total("serve.decode", "preempted")
    assert st.tokens_out == st.prefills + sum(x["active"] for x in decode)
    assert st.empty_slot_steps == total("serve.decode", "empty")
    assert st.decode_kv_pages == total("serve.decode", "pages") > 0
    assert st.queue_waits == 8 and st.queue_wait_s > 0.0
    # the run exercises what it counts
    assert st.refused["b"] and st.preemptions and st.empty_slot_steps


def test_decode_pages_are_the_block_tables_live_pages(traced):
    """Each decode span's ``pages`` is the sum, over the slots its launch
    marks active, of the pages up to and with the written token; the
    block table handed to the kernel holds a page for every one."""
    cluster, _, spans, launches = traced
    page = cluster.engine_cfg.page_size
    live = []
    for tables, lengths, active in launches:
        if not active.any():
            continue
        n = -(-(lengths[active] + 1) // page)
        assert all((row[:k] > 0).all()
                   for row, k in zip(tables[active], n))
        live.append(int(n.sum()))
    decode = sorted((s, x["pages"]) for s, _, x in spans["serve.decode"]
                    if x["active"])
    assert [p for _, p in decode] == live
    assert sum(live) == cluster.stats.decode_kv_pages


@pytest.fixture(scope="module")
def plain():
    return serve()


def test_tokens_same_with_the_profiler_on_and_off(traced, plain):
    assert [r.tokens for r in plain[1]] == [r.tokens for r in traced[1]]


def test_attainment_counts_an_unfinished_request_as_missed(plain):
    cluster, _, _ = plain
    assert len(cluster.finished) == 8
    assert cluster.attainment() == 1.0
    late = Request(l_in=8, l_pred=0, l_real=4, arrival=time.perf_counter())
    late.tokens = list(range(2, 10))
    cluster.submit(late)
    assert cluster.attainment() == pytest.approx(8 / 9)
