"""Ahead-of-time compiles of the served path's kernels and step programs for
a described (not attached) TPU v5e, at real widths.

The chip's own compiler refuses what interpret mode accepts: blocks that
break the (8, 128) tiling, kernels that overflow VMEM, programs that do not
fit the device. Nothing runs here, so these say nothing about results or
times. The topology is described inside a fixture, never at import, so
every pytest-xdist worker collects the same tests and only the one that
runs this file loads the TPU compiler.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import compiled_kernels
from repro.kernels.decode_attention import (mla_decode_attention_pallas,
                                            paged_decode_attention_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.models.model import LM
from repro.serving.engine import (EngineConfig, _write_pages, decode_step,
                                  mla_moe_decode_step, prefill_step)

GIB = 1 << 30
SMOKE = EngineConfig(max_batch=8, page_size=16, n_pages=512,
                     max_pages_per_seq=128)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args, **static):
    return jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).compile()


def _footprint(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def _pool_moves(compiled, pool_shape) -> list:
    """Instructions of the optimized program that copy, slice or scatter
    a whole pool or a whole layer of it (``pool_shape``, leading 1s aside),
    named ops or fusions of them: a pool written in place has none. An
    in-place ``dynamic-update-slice`` of the pool is not among them."""
    sizes = {tuple(pool_shape)[1:], tuple(pool_shape)}
    moves = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([\w-]+)\(", line)
        if not m:
            continue
        name, shape, op = m.groups()
        dims = {tuple(int(d) for d in s.split(",") if d)
                for s in re.findall(r"\[([\d,]*)\]", shape)}
        if op == "fusion":
            op = name
        if re.search(r"copy|dynamic-slice|scatter", op) and \
                any(d[next((i for i, x in enumerate(d) if x != 1),
                           len(d)):] in sizes for d in dims):
            moves.append(line.strip()[:160])
    return moves


def _in_place(compiled, pool_bytes: int) -> None:
    """The pools alias the program's outputs, its temporaries stay under
    1% of them, and nothing copies or slices a pool or a layer of one."""
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= pool_bytes
    assert m.temp_size_in_bytes < pool_bytes / 100


# block-table width where it is not the smoke's: the benchmark's nemo cell
MAX_PAGES = {"mistral-nemo-12b": 528}


@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "llama2-7b",
                                  "mistral-nemo-12b"])
def test_paged_decode_kernel_compiles(one_chip, arch):
    a = get_arch(arch)
    hd, e = a.resolved_head_dim, SMOKE
    max_pages = MAX_PAGES.get(arch, e.max_pages_per_seq)
    pool = _sds(one_chip, (e.n_pages, a.n_kv_heads, e.page_size, hd),
                jnp.float32)
    compiled = _compile(
        paged_decode_attention_pallas,
        _sds(one_chip, (e.max_batch, a.n_heads, hd), jnp.float32), pool, pool,
        _sds(one_chip, (e.max_batch, max_pages), jnp.int32),
        _sds(one_chip, (e.max_batch,), jnp.int32))
    assert compiled_kernels(compiled.as_text()) == {
        "paged_decode_attention": 1}


def test_flash_attention_kernel_compiles(one_chip):
    q = _sds(one_chip, (1, 512, 24, 128), jnp.bfloat16)
    kv = _sds(one_chip, (1, 512, 8, 128), jnp.bfloat16)
    compiled = _compile(flash_attention_pallas, q, kv, kv)
    assert compiled_kernels(compiled.as_text()) == {"flash_attention": 1}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_kernel_compiles(one_chip, dtype):
    compiled = _compile(rmsnorm_pallas, _sds(one_chip, (8, 3072), dtype),
                        _sds(one_chip, (3072,), dtype))
    assert compiled_kernels(compiled.as_text()) == {"rmsnorm": 1}


def _phi4_params(one_chip):
    arch = get_arch("phi4-mini-3.8b")
    shapes = jax.eval_shape(LM(arch).init, jax.random.key(0))
    return arch, jax.tree.map(
        lambda s: _sds(one_chip, s.shape, s.dtype), shapes)


def test_engine_decode_step_fits_one_chip(one_chip):
    """The engine's decode step at phi4-mini's published widths (32 layers,
    bf16 weights) with the smoke's 512-page f32 pool and the Pallas kernel."""
    arch, params = _phi4_params(one_chip)
    e = SMOKE
    pool = _sds(one_chip, (arch.n_layers, e.n_pages, arch.n_kv_heads,
                           e.page_size, arch.resolved_head_dim), jnp.float32)
    b = e.max_batch
    compiled = decode_step.lower(
        params, pool, pool,
        _sds(one_chip, (b, e.max_pages_per_seq), jnp.int32),
        _sds(one_chip, (b,), jnp.int32), _sds(one_chip, (b,), jnp.int32),
        _sds(one_chip, (b,), jnp.bool_), arch=arch, page_size=e.page_size,
        use_pallas=True, interpret=False).compile()
    # RMSNorm twice in the layer scan's body and once before the head
    assert compiled_kernels(compiled.as_text()) == {
        "paged_decode_attention": 1, "rmsnorm": 3}
    assert _footprint(compiled) < 15 * GIB


def test_engine_prefill_step_fits_one_chip(one_chip):
    """Prefill at the smoke's largest (1024-token) bucket on the flash
    kernel."""
    arch, params = _phi4_params(one_chip)
    compiled = prefill_step.lower(
        params, _sds(one_chip, (1, 1024), jnp.int32), 1023, arch=arch,
        use_pallas=True, interpret=False).compile()
    assert compiled_kernels(compiled.as_text()) == {
        "flash_attention": 1, "rmsnorm": 3}
    assert _footprint(compiled) < 15 * GIB


def test_mla_decode_kernel_compiles(one_chip):
    """The latent paged-decode kernel at Moonlight's widths: 16 heads, a
    576-wide row in a 640-wide pool, 32 sequences of 320 pages."""
    compiled = _compile(
        mla_decode_attention_pallas,
        _sds(one_chip, (32, 16, 576), jnp.float32),
        _sds(one_chip, (3014, 16, 640), jnp.float32),
        _sds(one_chip, (32, 320), jnp.int32), _sds(one_chip, (32,), jnp.int32),
        value_dim=512, scale=192 ** -0.5)
    assert compiled_kernels(compiled.as_text()) == {"mla_decode_attention": 1}


def _moonlight_params(one_chip):
    """One chip's share of Moonlight-16B-A3B: 8 of 64 experts a layer,
    bf16 weights."""
    arch = get_arch("moonlight-16b-a3b")
    shapes = jax.eval_shape(LM(arch).init, jax.random.key(0))
    experts = ("w_gate", "w_up", "w_down")
    shapes["seg1"] = {k: jax.ShapeDtypeStruct(
        (v.shape[0], 8) + v.shape[2:] if k in experts else v.shape, v.dtype)
        for k, v in shapes["seg1"].items()}
    return arch, jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype),
                              shapes)


def test_moonlight_decode_step_fits_one_chip(one_chip):
    """The latent engine's decode step with one chip's share of
    Moonlight-16B-A3B (8 of 64 experts a layer, bf16 weights) and a
    3,014-page float32 latent pool for 32 slots: the kernel once per
    segment of layers (dense, experts), and the step's arguments, outputs
    and temporaries inside the chip."""
    arch, params = _moonlight_params(one_chip)
    b, pages = 32, 3014
    pools = [_sds(one_chip, (n, pages, 16, 640), jnp.float32)
             for n in (1, 26)]
    compiled = mla_moe_decode_step.lower(
        params, pools, _sds(one_chip, (b, 320), jnp.int32),
        _sds(one_chip, (b,), jnp.int32), _sds(one_chip, (b,), jnp.int32),
        _sds(one_chip, (b,), jnp.bool_), arch=arch, page_size=16,
        first_expert=0, use_pallas=True, interpret=False).compile()
    kernels = compiled_kernels(compiled.as_text())
    assert kernels["mla_decode_attention"] == 2
    assert _footprint(compiled) < 15 * GIB


# the benchmark's phi4-conv engine: 715 pages of 16 for 6 slots of 320
PHI4_CONV = EngineConfig(max_batch=6, page_size=16, n_pages=715,
                         max_pages_per_seq=320)


def test_phi4_decode_step_writes_the_pool_in_place(one_chip):
    """The dense decode step at phi4-conv's engine: the donated K and V
    pools (3.0 GB) come back as the same buffers, each layer's rows written
    in place and the kernel reading its layer of the whole pool, with no
    pool- or layer-sized copy or slice."""
    arch, params = _phi4_params(one_chip)
    e = PHI4_CONV
    shape = (arch.n_layers, e.n_pages, arch.n_kv_heads, e.page_size,
             arch.resolved_head_dim)
    pool = _sds(one_chip, shape, jnp.float32)
    b = e.max_batch
    compiled = decode_step.lower(
        params, pool, pool,
        _sds(one_chip, (b, e.max_pages_per_seq), jnp.int32),
        _sds(one_chip, (b,), jnp.int32), _sds(one_chip, (b,), jnp.int32),
        _sds(one_chip, (b,), jnp.bool_), arch=arch, page_size=e.page_size,
        use_pallas=True, interpret=False).compile()
    assert compiled_kernels(compiled.as_text()) == {
        "paged_decode_attention": 1, "rmsnorm": 3}
    _in_place(compiled, 2 * 4 * math.prod(shape))
    assert _pool_moves(compiled, shape) == []
    assert _footprint(compiled) < 15 * GIB


def test_moonlight_decode_step_writes_the_pool_in_place(one_chip):
    """The latent decode step at moonlight-conv's engine (29 slots, 3,014
    pages): both segments' donated pools written in place, each layer's
    kernel reading its layer of its segment's pool."""
    arch, params = _moonlight_params(one_chip)
    b, pages = 29, 3014
    shapes = [(n, pages, 16, 640) for n in (1, 26)]
    compiled = mla_moe_decode_step.lower(
        params, [_sds(one_chip, s, jnp.float32) for s in shapes],
        _sds(one_chip, (b, 320), jnp.int32),
        _sds(one_chip, (b,), jnp.int32), _sds(one_chip, (b,), jnp.int32),
        _sds(one_chip, (b,), jnp.bool_), arch=arch, page_size=16,
        first_expert=0, use_pallas=True, interpret=False).compile()
    assert compiled_kernels(compiled.as_text())["mla_decode_attention"] == 2
    _in_place(compiled, sum(4 * math.prod(s) for s in shapes))
    for s in shapes:
        assert _pool_moves(compiled, s) == []
    assert _footprint(compiled) < 15 * GIB


@pytest.mark.parametrize("pools,rows", [
    pytest.param([(32, 715, 8, 16, 128)] * 2, [(32, 4096, 8, 128)] * 2,
                 id="phi4-kv"),
    pytest.param([(26, 3014, 16, 640)], [(26, 4096, 640)],
                 id="moonlight-experts-latent"),
])
def test_prompt_write_is_in_place(one_chip, pools, rows):
    """A 4,096-token prompt's cache write into phi4-conv's K and V pools
    and into Moonlight's expert-layer latent pool: page by page into the
    donated pools, no copy of a pool, a layer or the prompt's rows."""
    compiled = _write_pages.lower(
        [_sds(one_chip, s, jnp.float32) for s in pools],
        [_sds(one_chip, s, jnp.float32) for s in rows],
        _sds(one_chip, (4096 // 16 + 1,), jnp.int32),
        _sds(one_chip, (), jnp.int32), _sds(one_chip, (), jnp.int32)
    ).compile()
    _in_place(compiled, sum(4 * math.prod(s) for s in pools))
    for s in pools:
        assert _pool_moves(compiled, s) == []
