"""Paged engine correctness: continuous batching must reproduce the staged-
cache model path token-for-token, and page accounting must hold."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.core.request import ReqState, Request
from repro.models.model import LM, ExecConfig
from repro.serving.engine import EngineConfig, PagedEngine


def _setup(max_batch=4):
    arch = reduced(get_arch("granite-3-8b"), n_layers=2, d_model=64,
                   vocab=128)
    model = LM(arch, exec_cfg=ExecConfig(recent_window=8))
    params = model.init(jax.random.key(0))
    eng = PagedEngine(arch, params, EngineConfig(
        max_batch=max_batch, page_size=8, n_pages=128, max_pages_per_seq=16,
        max_new_tokens=64))
    return arch, model, params, eng


def _reference_generate(model, params, prompt, n_new):
    logits, cache = jax.jit(
        lambda p, t: model.prefill(p, tokens=t,
                                   s_max=len(prompt) + n_new + 8))(
        params, jnp.asarray([prompt]))
    out = [int(np.asarray(jnp.argmax(logits, -1))[0])]
    step = jax.jit(model.decode_step)
    for _ in range(n_new - 1):
        lg, cache = step(params, cache, jnp.asarray([out[-1]]))
        out.append(int(np.asarray(jnp.argmax(lg, -1))[0]))
    return out


def test_engine_matches_model_single():
    arch, model, params, eng = _setup()
    rng = np.random.default_rng(0)
    prompt = [int(x) for x in rng.integers(2, arch.vocab, 12)]
    n_new = 8
    ref = _reference_generate(model, params, prompt, n_new)
    req = Request(l_in=len(prompt), l_pred=n_new, l_real=n_new)
    req.tokens = list(prompt)
    eng.submit(req)
    while req.state != ReqState.FINISHED:
        eng.step()
    got = req.tokens[len(prompt):]
    assert got == ref, (got, ref)


def test_engine_continuous_batching_isolation():
    """Two interleaved requests must each match their solo generation."""
    arch, model, params, eng = _setup()
    rng = np.random.default_rng(1)
    p1 = [int(x) for x in rng.integers(2, arch.vocab, 10)]
    p2 = [int(x) for x in rng.integers(2, arch.vocab, 17)]
    ref1 = _reference_generate(model, params, p1, 6)
    ref2 = _reference_generate(model, params, p2, 6)
    r1 = Request(l_in=len(p1), l_pred=6, l_real=6)
    r1.tokens = list(p1)
    r2 = Request(l_in=len(p2), l_pred=6, l_real=6)
    r2.tokens = list(p2)
    eng.submit(r1)
    eng.step()                      # prefill r1
    eng.step()                      # decode r1 once
    eng.submit(r2)                  # r2 arrives mid-flight
    for _ in range(40):
        eng.step()
        if r1.state == ReqState.FINISHED and r2.state == ReqState.FINISHED:
            break
    assert r1.tokens[len(p1):] == ref1
    assert r2.tokens[len(p2):] == ref2


def test_engine_page_accounting():
    arch, model, params, eng = _setup()
    free0 = len(eng.free_pages)
    rng = np.random.default_rng(2)
    reqs = []
    for i in range(3):
        p = [int(x) for x in rng.integers(2, arch.vocab, 9 + i)]
        r = Request(l_in=len(p), l_pred=5, l_real=5)
        r.tokens = list(p)
        reqs.append(r)
        eng.submit(r)
    for _ in range(60):
        eng.step()
        if all(r.state == ReqState.FINISHED for r in reqs):
            break
    assert all(r.state == ReqState.FINISHED for r in reqs)
    assert len(eng.free_pages) == free0, "pages leaked"
    assert eng.traces.decode_batches, "decode traces recorded"
    assert eng.traces.prefill_inputs, "prefill traces recorded"


def test_warmup_leaves_engine_state_untouched():
    arch, model, params, eng = _setup()
    # copies: a numpy view would keep the donated buffers alive on the CPU
    k0, v0 = np.array(eng.kv_k), np.array(eng.kv_v)
    free0 = len(eng.free_pages)
    assert eng.warmup([5, 12, 40]) > 0
    np.testing.assert_array_equal(np.asarray(eng.kv_k), k0)
    np.testing.assert_array_equal(np.asarray(eng.kv_v), v0)
    assert len(eng.free_pages) == free0
    assert not eng.traces.prefill_inputs and not eng.traces.decode_batches


def test_kernel_choice_follows_device():
    arch, _, params, eng = _setup()
    assert eng.device == jax.devices()[0]
    assert eng.use_pallas == (eng.device.platform == "tpu")
    interp = PagedEngine(arch, params, EngineConfig(
        max_batch=2, page_size=8, n_pages=16, max_pages_per_seq=4,
        interpret=True))
    assert interp.use_pallas        # interpret mode runs the Pallas kernels
    assert eng.kv_k.shape == (arch.n_layers, 128, arch.n_kv_heads, 8,
                              arch.resolved_head_dim)   # head-major pool


def _small(name, max_batch=3):
    """An engine of a three-layer arch at a tiny width, pages of 8."""
    arch = reduced(get_arch(name), n_layers=3, d_model=64, vocab=128)
    return PagedEngine(arch, LM(arch).init(jax.random.key(0)), EngineConfig(
        max_batch=max_batch, page_size=8, n_pages=16, max_pages_per_seq=4))


def _random_pools(eng, rng) -> list:
    """Fills the engine's pools with noise, so that any write shows;
    returns host copies of them (copies: a numpy view would keep the
    buffers from being donated on the CPU)."""
    pools = [rng.standard_normal(p.shape).astype(np.float32)
             for p in jax.tree.leaves(eng._pools())]
    if eng.mla:
        eng.kv_lat = [jnp.asarray(p) for p in pools]
    else:
        eng.kv_k, eng.kv_v = (jnp.asarray(p) for p in pools)
    return pools


def _host(eng) -> list:
    return [np.array(p) for p in jax.tree.leaves(eng._pools())]


def _at(pool, pages, offs):
    """The index of positions (pages, offs) of every layer: (L, n, ...,
    W) for a latent pool, (n, L, Hkv, hd) for K or V."""
    if pool.ndim == 5:
        return np.s_[:, pages, :, offs]
    return np.s_[:, pages, offs]


@pytest.mark.parametrize("name", ["granite-3-8b", "moonlight-16b-a3b"],
                         ids=["dense", "latent-experts"])
def test_decode_step_writes_its_rows_in_place(name):
    """A decode step writes each active slot's row of every layer and
    nothing else: the masked slot's row, the rest of each written page and
    every other page stay bit-identical. The pools it was given are
    donated (deleted), and the engine holds the ones it returned."""
    eng = _small(name)
    old = _random_pools(eng, np.random.default_rng(5))
    eng.block_tables[:] = [[3, 5, 0, 0], [7, 2, 0, 0], [9, 0, 0, 0]]
    eng.lengths[:] = [11, 4, 0]              # rows (5, 3), (7, 4), (9, 0)
    active = np.array([True, False, True])
    given = jax.tree.leaves(eng._pools())
    eng._launch(np.array([5, 6, 7]), active)
    assert all(p.is_deleted() for p in given)
    for o, new in zip(old, _host(eng)):
        rows = _at(o, np.array([5, 9]), np.array([3, 0]))
        assert (new[rows] != o[rows]).any()
        new[rows] = o[rows]
        np.testing.assert_array_equal(new, o)


@pytest.mark.parametrize("start,n", [(5, 13), (3, 4)],
                         ids=["across-pages", "inside-a-page"])
@pytest.mark.parametrize("name", ["granite-3-8b", "moonlight-16b-a3b"],
                         ids=["dense", "latent-experts"])
def test_prompt_write_touches_only_its_rows(name, start, n):
    """Rows written from a start off a page boundary (positions 5..17:
    the end of page 4, all of page 9, the head of page 2; or 3..6 inside
    page 4, fewer rows than a page) land where the block table says, and
    every other row of every page and layer stays bit-identical. The
    pools written are donated."""
    eng = _small(name)
    rng = np.random.default_rng(6)
    old = _random_pools(eng, rng)
    table = np.array([4, 9, 2, 0], np.int32)
    rows = [rng.standard_normal(p.shape[:1] + (n,) + p.shape[2:-2]
                                + p.shape[-1:]).astype(np.float32)
            for p in old]
    given = jax.tree.leaves(eng._pools())
    if eng.mla:
        eng._write(table, start, [jnp.asarray(r) for r in rows], n)
    else:
        eng.block_tables[1] = table
        eng._write_kv(1, start, *map(jnp.asarray, rows))
    assert all(p.is_deleted() for p in given)
    pos = np.arange(start, start + n)
    for o, r, new in zip(old, rows, _host(eng)):
        want = o.copy()
        want[_at(o, table[pos // 8], pos % 8)] = \
            r.swapaxes(0, 1) if o.ndim == 5 else r
        np.testing.assert_array_equal(new, want)


def _builds(fn) -> list:
    """Names of the programs ``fn`` builds (lowers to MLIR)."""
    built, on = [], [True]

    def listen(event, duration, **kw):
        if on[0] and \
                event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            built.append(kw.get("fun_name", "?"))
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        fn()
    finally:
        on[0] = False
    return built


@pytest.mark.parametrize("name", ["granite-3-8b", "moonlight-16b-a3b"],
                         ids=["dense", "latent-experts"])
def test_warmup_leaves_nothing_to_build_while_serving(name):
    """After ``warmup`` of the lengths served, prefill (its upload, its
    cache write, its first token's argmax) and decode (its argmax) build
    no program, with K/V pools and with a latent pool and held experts."""
    arch = reduced(get_arch(name), n_layers=3, d_model=64, vocab=128)
    eng = PagedEngine(arch, LM(arch).init(jax.random.key(0)), EngineConfig(
        max_batch=2, page_size=8, n_pages=64, max_pages_per_seq=8))
    lengths = [5, 12, 23]
    eng.warmup(lengths)
    rng = np.random.default_rng(4)
    reqs = []
    for n in lengths:
        r = Request(l_in=n, l_pred=4, l_real=4)
        r.tokens = [int(x) for x in rng.integers(2, arch.vocab, n)]
        reqs.append(r)

    def serve():
        for r in reqs:
            eng.submit(r)
        while not all(r.state == ReqState.FINISHED for r in reqs):
            eng.step()
    assert _builds(serve) == []
    assert list(eng.programs(23)) == ["prefill", "decode"]


def test_programs_lower_prefill_and_decode():
    """``programs`` lowers the prefill program of a length's bucket and
    the decode step at the engine's shapes; the decode one runs."""
    arch, model, params, eng = _setup()
    lowered = eng.programs(12)
    assert list(lowered) == ["prefill", "decode"]
    assert all("stablehlo" in lw.as_text() for lw in lowered.values())
    b = eng.cfg.max_batch
    logits = lowered["decode"].compile()(
        eng.params, eng.kv_k, eng.kv_v, jnp.asarray(eng.block_tables),
        jnp.asarray(eng.lengths), jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), bool))[0]
    assert logits.shape == (b, arch.vocab)
