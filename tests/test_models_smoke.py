"""Per-architecture smoke tests: reduced same-family configs, one forward /
train / prefill+decode step on CPU, asserting shapes and finiteness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_arch, reduced
from repro.models.model import LM, ExecConfig


def _batch_for(arch, b=2, s=16):
    rng = np.random.default_rng(0)
    batch = {"labels": jnp.asarray(rng.integers(0, arch.vocab, (b, s)))}
    if arch.family.value == "audio":
        batch["embeds"] = jnp.asarray(
            rng.standard_normal((b, s, arch.d_model)), jnp.float32)
    else:
        batch["tokens"] = jnp.asarray(rng.integers(0, arch.vocab, (b, s)))
    if arch.family.value == "vlm":
        batch["frontend"] = jnp.asarray(
            rng.standard_normal((b, arch.n_frontend_tokens, arch.d_model)),
            jnp.float32)
    return batch


@pytest.fixture(scope="module")
def models():
    return {}


@pytest.mark.parametrize("name", ASSIGNED_ARCHS)
def test_train_step_smoke(name):
    arch = reduced(get_arch(name))
    model = LM(arch, exec_cfg=ExecConfig(loss_chunk=8, scan_layers=True))
    params = model.init(jax.random.key(0))
    batch = _batch_for(arch)
    loss, metrics = jax.jit(model.train_loss)(params, batch)
    assert np.isfinite(float(loss)), (name, loss)
    assert float(loss) > 0
    # gradients exist and are finite
    grads = jax.grad(lambda p: model.train_loss(p, batch)[0])(params)
    flat = jax.tree.leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g, np.float32))) for g in flat), name


@pytest.mark.parametrize("name", ASSIGNED_ARCHS)
def test_prefill_decode_smoke(name):
    arch = reduced(get_arch(name))
    model = LM(arch, exec_cfg=ExecConfig(recent_window=8))
    params = model.init(jax.random.key(1))
    b, s = 2, 16
    batch = _batch_for(arch, b, s)
    logits, cache = jax.jit(lambda p: model.prefill(
        p, tokens=batch.get("tokens"), embeds=batch.get("embeds"),
        frontend=batch.get("frontend"), s_max=s + 8))(params)
    assert logits.shape == (b, arch.vocab)
    assert np.all(np.isfinite(np.asarray(logits)))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    step = jax.jit(model.decode_step)
    for i in range(3):
        logits, cache = step(params, cache, tok)
        assert logits.shape == (b, arch.vocab)
        assert np.all(np.isfinite(np.asarray(logits))), (name, i)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)


def test_decode_matches_prefill_dense():
    """Teacher-forcing consistency: decoding token t must reproduce the
    prefill logits at position t (dense arch)."""
    arch = reduced(get_arch("granite-3-8b"))
    model = LM(arch, exec_cfg=ExecConfig(recent_window=8))
    params = model.init(jax.random.key(2))
    rng = np.random.default_rng(3)
    b, s = 2, 12
    toks = jnp.asarray(rng.integers(0, arch.vocab, (b, s)))
    # full prefill logits at the last position
    logits_full, _ = jax.jit(lambda p, t: model.prefill(p, tokens=t,
                                                        s_max=s + 4))(
        params, toks)
    # prefill on the prefix, then decode the remaining tokens one by one
    cut = 8
    logits, cache = jax.jit(lambda p, t: model.prefill(p, tokens=t,
                                                       s_max=s + 4))(
        params, toks[:, :cut])
    step = jax.jit(model.decode_step)
    for t in range(cut, s):
        logits, cache = step(params, cache, toks[:, t])
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_full),
                               rtol=5e-2, atol=1e-1)


def test_decode_matches_prefill_ssm():
    arch = reduced(get_arch("mamba2-1.3b"))
    model = LM(arch, exec_cfg=ExecConfig(recent_window=8))
    params = model.init(jax.random.key(4))
    rng = np.random.default_rng(5)
    b, s, cut = 2, 12, 8
    toks = jnp.asarray(rng.integers(0, arch.vocab, (b, s)))
    logits_full, _ = jax.jit(lambda p, t: model.prefill(p, tokens=t))(
        params, toks)
    logits, cache = jax.jit(lambda p, t: model.prefill(p, tokens=t))(
        params, toks[:, :cut])
    step = jax.jit(model.decode_step)
    for t in range(cut, s):
        logits, cache = step(params, cache, toks[:, t])
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_full),
                               rtol=5e-2, atol=1e-1)


def test_flush_preserves_decode():
    """Flushing recent->big must not change subsequent logits."""
    arch = reduced(get_arch("mistral-nemo-12b"))
    model = LM(arch, exec_cfg=ExecConfig(recent_window=8))
    params = model.init(jax.random.key(6))
    rng = np.random.default_rng(7)
    b, s = 2, 8
    toks = jnp.asarray(rng.integers(0, arch.vocab, (b, s)))
    _, cache = jax.jit(lambda p, t: model.prefill(p, tokens=t, s_max=32))(
        params, toks)
    step = jax.jit(model.decode_step)
    tok = jnp.zeros((b,), jnp.int32)
    for _ in range(4):
        l1, cache = step(params, cache, tok)
    flushed = jax.jit(model.maybe_flush)(cache)
    l_a, _ = step(params, cache, tok)
    l_b, _ = step(params, flushed, tok)
    np.testing.assert_allclose(np.asarray(l_a), np.asarray(l_b),
                               rtol=5e-2, atol=1e-1)


@pytest.mark.parametrize("name", ASSIGNED_ARCHS)
def test_init_matches_eager_draw(name):
    """``LM.init`` draws each scaled-normal leaf straight into the param
    dtype, with the values of the eager ``normal * scale`` for a seed."""
    model = LM(reduced(get_arch(name)))
    tmpl = model.param_template()
    leaves = jax.tree.leaves(tmpl, is_leaf=lambda x: isinstance(x, tuple)
                             and len(x) == 3 and isinstance(x[0], tuple))
    keys = jax.random.split(jax.random.key(7), len(leaves))
    got = jax.tree.leaves(model.init(jax.random.key(7)))
    for k, (shape, _, scale), g in zip(keys, leaves, got):
        if scale > 0:
            want = (jax.random.normal(k, shape, jnp.float32)
                    * scale).astype(model.dtype)
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(want, np.float32))
