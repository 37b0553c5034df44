"""The traffic generator: one seed gives one schedule, every seed the same
sizes, and the medians of the mixes land where their source puts them."""
import itertools
import json
from pathlib import Path

import numpy as np
import pytest

from bench.traffic import Traffic, quantile_gaps, quantile_lengths

MIXES = Path(__file__).resolve().parents[1] / "traffic"
FIXTURES = Path(__file__).resolve().parent / "fixtures"


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def take(it, n):
    return list(itertools.islice(it, n))


@pytest.mark.parametrize("name", ["conversation-closed", "coding-closed"])
def test_same_seed_same_schedule(name):
    a, b = Traffic(mix(name), 1000, 2 ** 33 + 5), Traffic(mix(name), 1000,
                                                          2 ** 33 + 5)
    assert take(a.sizes(), 200) == take(b.sizes(), 200)
    assert a.prompt_tokens(50) == b.prompt_tokens(50)
    if not a.closed:
        assert take(a.arrivals(), 100) == take(b.arrivals(), 100)


@pytest.mark.parametrize("name", ["conversation-closed", "coding-closed"])
def test_seeds_share_sizes_in_another_order(name):
    m = mix(name)
    n = m["set_size"]
    a, b = Traffic(m, 1000, 1), Traffic(m, 1000, 2 ** 33 + 1)
    sa, sb = take(a.sizes(), n), take(b.sizes(), n)
    assert sa != sb
    assert sorted(p for p, _ in sa) == sorted(p for p, _ in sb)
    assert sorted(o for _, o in sa) == sorted(o for _, o in sb)
    assert a.prompt_tokens(20) != b.prompt_tokens(20)


@pytest.mark.parametrize("name,prompt,output", [
    ("conversation-closed", 1020, 129), ("coding-closed", 1500, 13)])
def test_medians_within_two_percent(name, prompt, output):
    """Splitwise's medians (arXiv:2311.18677), within 2% (one token for
    outputs of a dozen)."""
    m = mix(name)
    p = quantile_lengths(m["prompt"], m["set_size"])
    o = quantile_lengths(m["output"], m["set_size"])
    assert abs(np.median(p) - prompt) <= 0.02 * prompt
    assert abs(np.median(o) - output) <= max(1, 0.02 * output)
    assert p.min() >= m["prompt"]["min"] and p.max() <= m["prompt"]["max"]


def test_open_loop_rate_within_five_percent():
    m = dict(json.loads((FIXTURES / "tiny-open.json").read_text()),
             set_size=64, strata=8)
    gaps = quantile_gaps(m["rate_per_s"], m["set_size"])
    assert abs(1 / gaps.mean() - m["rate_per_s"]) <= 0.05 * m["rate_per_s"]
    t = take(Traffic(m, 1000, 9).arrivals(), 3 * m["set_size"])
    assert np.all(np.diff(t) > 0)
    assert abs(t[-1] - 3 * gaps.sum()) < 1e-6 * t[-1]


@pytest.mark.parametrize("name,pages", [("conversation-closed", 102.703125),
                                        ("coding-closed", 141.78125)])
def test_slot_size_and_clients_follow_the_mix(name, pages):
    """A slot holds a request's prompt and output in 16-token pages, on
    average over the mix's sizes; a closed loop has clients per slot."""
    t = Traffic(mix(name), 1000, 3)
    assert t.mean_context_pages(16) == pages
    assert t.clients(6) == 12


def test_every_block_takes_one_size_from_each_stratum():
    m = mix("conversation-closed")
    t = Traffic(m, 1000, 77)
    s = m["strata"]
    edges = np.sort(t.prompts).reshape(s, -1)
    sizes = take(t.sizes(), 2 * m["set_size"])
    for b in range(0, len(sizes), s):
        block = sorted(p for p, _ in sizes[b:b + s])
        for j, p in enumerate(block):
            assert edges[j].min() <= p <= edges[j].max()
