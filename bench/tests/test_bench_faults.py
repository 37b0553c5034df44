"""A whole run at a tiny size on the CPU, the chip check skipped: sound, it
is correct; with the timed path broken underneath, or with the reference
in a lower precision put in the program's place (the control), ``correct``
comes out false."""
import json
import re

import jax.numpy as jnp
import pytest

import repro.serving.engine as engine
from bench import run
from benchroot import PEAKS, make_root

SEED = "11"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def one_run(root, capsys, cell="tiny-conv", control=None):
    rc = run.main(["--workload", cell, "--seed", SEED, "--seconds", "0.5"],
                  root=root, require_chip=False, peaks=PEAKS,
                  control=control)
    out, err = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("cell", ["tiny-conv", "tiny-code"])
def test_sound_run_is_correct(root, capsys, cell):
    res, err = one_run(root, capsys, cell)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "compared"
    assert err.strip().splitlines()[-1].startswith("failed_requests 0 limit")


def _roll_token(logits, kk, vv, k0, v0):
    return jnp.roll(logits, 1, axis=-1), kk, vv        # another token


def _state_unchanged(logits, kk, vv, k0, v0):
    return logits, k0, v0                              # KV never written


def _half_batch(logits, kk, vv, k0, v0):
    b = logits.shape[0]
    return logits.at[b // 2:].set(0.0), kk, vv         # rows not computed


@pytest.mark.parametrize("fault", [_roll_token, _state_unchanged,
                                   _half_batch])
def test_broken_decode_step_is_not_correct(root, capsys, monkeypatch, fault):
    sound = engine.decode_step

    def broken(params, kv_k, kv_v, *args, **kw):
        logits, kk, vv = sound(params, kv_k, kv_v, *args, **kw)
        return fault(logits, kk, vv, kv_k, kv_v)
    monkeypatch.setattr(engine, "decode_step", broken)
    res, err = one_run(root, capsys)
    assert res["correct"] is False
    gap = res["compared"]["served_gap_max"]
    assert gap["value"] > gap["limit"]


def test_control_in_lower_precision_is_not_correct(root, capsys):
    """The tokens that int8 products in the reference put first, in the
    served tokens' place, lie further below the float32 best than the
    limit allows; the program's own gap, printed beside, stays under it."""
    res, err = one_run(root, capsys, control="int8")
    gap = res["compared"]["served_gap_max"]
    assert res["correct"] is False
    assert gap["value"] > gap["limit"]
    own = float(re.search(r"the program's own widest gap (\S+)",
                          err).group(1))
    assert own <= gap["limit"]
