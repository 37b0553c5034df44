"""Model families: a configuration names its family, the harness finds it
by name, and a family brings the weight tree, the check of the program's
architecture and the float32 reference. The dense family holds the code
that ``weights.py``, ``reference.py`` and ``run.py`` held before families
existed, and gives the same numbers; a family is added with files alone;
and the warm-up it asks of the engine leaves nothing to build while
serving."""
import json
import re
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference, run, weights
from bench.traffic import Traffic
from benchroot import PEAKS, make_root

ROOT = Path(__file__).resolve().parents[2]
TINY = json.loads(
    (Path(__file__).parent / "fixtures" / "tiny.json").read_text())
SEED = 11

# Read at commit 16476ed, before the dense model moved into
# bench/families/dense_gqa.py: ``weights.shapes`` and ``weights.make`` of
# the tiny fixture at seed 11, tied and untied (leaf: float64 sum and sum of
# squares), and ``reference.logit_rows`` over the sequence below, its 8
# served rows (sum, sum of squares, argmax of each row).
DENSE_LEAVES = ["embed", "final_ln", "seg0/ln1", "seg0/ln2", "seg0/wq",
                "seg0/wk", "seg0/wv", "seg0/wo", "seg0/wg", "seg0/wu",
                "seg0/wd"]
PINNED_LEAVES = {
    "embed": (2.105508263222873, 26.029806108595757),
    "final_ln": (64.0, 64.0),
    "seg0/ln1": (128.0, 128.0),
    "seg0/ln2": (128.0, 128.0),
    "seg0/wq": (0.07345247268676758, 127.40589618161289),
    "seg0/wk": (-4.729827880859375, 64.22013561299127),
    "seg0/wv": (1.0468111038208008, 64.26795466845442),
    "seg0/wo": (14.437678396701813, 128.29789431834377),
    "seg0/wg": (-13.73448882997036, 257.3754300773176),
    "seg0/wu": (12.920878887176514, 257.9997162586727),
    "seg0/wd": (-16.22603076696396, 129.43994637842695),
    "head": (33.01050880551338, 1022.8379258223193),
}
SEQUENCE = [686, 824, 23, 827, 480, 527, 645, 292, 1003, 55, 284, 392, 584,
            418, 134, 46, 1, 49, 152, 1023, 195, 668, 768, 240, 289, 445,
            269, 997, 181, 919, 816, 864, 118, 401, 643, 504, 681, 692, 678,
            62, 982, 568, 925, 277, 370, 900, 191, 65]
N_PROMPT = 40
PINNED_ROWS = {
    (True, "f32"): (-28.592809039731947, 211.93170797215035,
                    [111, 383, 683, 209, 864, 1010, 447, 785]),
    (True, "int8"): (-28.348947404779164, 211.9221221115563,
                     [111, 383, 683, 209, 864, 1010, 447, 785]),
    (False, "f32"): (36.16425084322691, 8095.3991787191535,
                     [161, 81, 268, 659, 970, 145, 109, 669]),
    (False, "int8"): (36.46403616270982, 8075.950102996263,
                      [161, 81, 268, 659, 970, 145, 109, 669]),
}


def tiny(tied: bool) -> dict:
    return dict(TINY, tie_word_embeddings=tied)


def flat(tree, prefix=""):
    for k, v in tree.items():
        if isinstance(v, dict):
            yield from flat(v, prefix + k + "/")
        else:
            yield prefix + k, np.asarray(v, np.float64)


@pytest.fixture(scope="module")
def dense():
    return harness.family("dense_gqa", ROOT)


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_dense_weights_are_the_parents(dense, tied):
    """Same leaves in the same order, so one seed gives the same weights
    and the limits of ``correct`` keep their calibration."""
    cfg = tiny(tied)
    order = list(dense.shapes(cfg))
    assert order == DENSE_LEAVES + ([] if tied else ["head"])
    got = dict(flat(weights.make(dense.shapes(cfg), SEED)))
    assert sorted(got) == sorted(order)
    for name, leaf in got.items():
        assert leaf.shape == dense.shapes(cfg)[name][0]
        np.testing.assert_allclose(
            (leaf.sum(), (leaf * leaf).sum()), PINNED_LEAVES[name],
            rtol=1e-7, atol=1e-9, err_msg=name)


@pytest.mark.parametrize("mode", ["f32", "int8"])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_dense_reference_is_the_parents(dense, tied, mode):
    cfg = tiny(tied)
    ref = cfg["reference"]
    params = weights.make(dense.shapes(cfg), SEED)
    t = reference._bucket(len(SEQUENCE) - 1, ref["max_tokens"],
                          ref["q_block"])
    toks = np.zeros((t,), np.int32)
    toks[:len(SEQUENCE) - 1] = SEQUENCE[:-1]
    n = len(SEQUENCE) - N_PROMPT
    rows = np.zeros((reference._bucket(n, ref["max_served"], 8),), np.int32)
    rows[:n] = np.arange(N_PROMPT - 1, N_PROMPT - 1 + n)
    out = np.asarray(dense.logit_rows(params, toks, rows, cfg=cfg, mode=mode,
                                      q_block=ref["q_block"]),
                     np.float64)[:n]
    total, squares, argmax = PINNED_ROWS[(tied, mode)]
    np.testing.assert_allclose((out.sum(), (out * out).sum()),
                               (total, squares), rtol=1e-6)
    assert out.argmax(1).tolist() == argmax


def test_dense_check_refuses_a_size_that_differs(dense):
    cfg = json.loads((ROOT / "bench/configs/mistral-nemo-12b-d10.json")
                     .read_text())
    arch = run.program_arch(cfg, dense)
    dense.check(cfg, arch)
    with pytest.raises(ValueError, match="intermediate_size"):
        dense.check(dict(cfg, intermediate_size=14335), arch)


def test_run_and_weights_name_no_dense_leaf_or_cache():
    """The dense model lives in its family file alone."""
    dense_names = re.compile(
        r"n_kv_heads|_write_kv|kv_k|kv_v|intermediate_size|wq")
    for f in ("run.py", "weights.py"):
        text = (ROOT / "bench" / f).read_text()
        assert not dense_names.findall(text), f


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def one_run(root, capsys, cell, control=None):
    rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                   "0.5"], root=root, require_chip=False, peaks=PEAKS,
                  control=control)
    out, err = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), err


def test_family_added_with_files_only_is_correct(root, capsys):
    """``dense_gqa_qkv_bias`` lives in the temporary root alone: its
    weights, check and reference reach the run by name."""
    assert not (ROOT / "bench/families/dense_gqa_qkv_bias.py").exists()
    fam = harness.family("dense_gqa_qkv_bias", root)
    cfg = harness.load_cell("tiny-qkv-conv", root).config
    assert list(fam.shapes(cfg))[-3:] == ["seg0/bq", "seg0/bk", "seg0/bv"]
    res, _ = one_run(root, capsys, "tiny-qkv-conv")
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0


def test_family_added_with_files_only_control_is_not_correct(root, capsys):
    res, err = one_run(root, capsys, "tiny-qkv-conv", control="int8")
    gap = res["compared"]["served_gap_max"]
    assert res["correct"] is False
    assert gap["value"] > gap["limit"]
    own = float(re.search(r"the program's own widest gap (\S+)",
                          err).group(1))
    assert own <= gap["limit"]


def test_family_reference_without_its_biases_is_not_correct(
        root, capsys, monkeypatch):
    """The biases reach the served tokens: the dense reference, which
    leaves them out, does not agree with them."""
    found = harness.family

    def biasless(name, at):
        fam = found(name, at)
        if name == "dense_gqa_qkv_bias":
            fam.logit_rows = found("dense_gqa", at).logit_rows
        return fam
    monkeypatch.setattr(harness, "family", biasless)
    res, _ = one_run(root, capsys, "tiny-qkv-conv")
    assert res["correct"] is False


def test_unknown_family_is_an_error(tmp_path):
    root = make_root(tmp_path)
    path = root / "bench/configs/tiny.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    family="no_such_family")))
    with pytest.raises(FileNotFoundError,
                       match=r"no_such_family.*families/no_such_family\.py"):
        run.main(["--workload", "tiny-conv", "--seed", "1", "--seconds",
                  "0.1"], root=root, require_chip=False, peaks=PEAKS)


def test_missing_family_key_is_an_error(tmp_path):
    root = make_root(tmp_path)
    path = root / "bench/configs/tiny.json"
    cfg = json.loads(path.read_text())
    del cfg["family"]
    path.write_text(json.dumps(cfg))
    with pytest.raises(KeyError, match=r"bench/configs/tiny\.json.*family"):
        harness.load_cell("tiny-conv", root)


def built_while(fn, counter) -> list:
    counter.update(programs=[], backend=0, counting=True)
    try:
        fn()
    finally:
        counter["counting"] = False
    return counter["programs"]


def tiny_cluster(root):
    """The ``tiny-conv`` cell's family, traffic and cluster, as a run
    builds them."""
    cell = harness.load_cell("tiny-conv", root)
    fam = harness.family(cell.config["family"], root)
    arch = run.program_arch(cell.config, fam)
    params = weights.make(fam.shapes(cell.config), SEED)
    traffic = Traffic(cell.traffic, arch.vocab, SEED)
    return fam, traffic, run.build_cluster(cell, arch, params, traffic)


def test_warm_up_leaves_nothing_to_build_while_serving(root):
    """After the family's warm-up, serving a prompt of each length the mix
    sends, to its last token, builds no program."""
    import jax
    from repro.core.request import ReqState, Request
    fam, traffic, cluster = tiny_cluster(root)
    lengths = traffic.prompt_lengths()
    counter = run._compile_counter(jax)
    assert built_while(lambda: jax.jit(lambda x: x + 1)(1.0), counter)
    run.warm_up(cluster, lengths, fam)
    reqs = [Request(l_in=n, l_pred=0, l_real=3, arrival=0.0)
            for n in lengths]
    for r in reqs:
        r.tokens = traffic.prompt_tokens(r.l_in)

    def serve():
        for r in reqs:
            cluster.submit(r)
        for _ in range(10 * len(reqs)):
            cluster.heartbeat()
            if all(r.state == ReqState.FINISHED for r in reqs):
                break
    assert built_while(serve, counter) == []
    assert all(r.state == ReqState.FINISHED for r in reqs)


def test_family_programs_lower_on_the_cpu(root):
    """The engine's prefill and decode programs, as the family lists them
    for the count of Pallas kernels, lower here."""
    fam, traffic, cluster = tiny_cluster(root)
    eng = next(iter(cluster.workers.values())).engine
    lowered = fam.programs(eng, max(traffic.prompt_lengths()))
    assert list(lowered) == ["prefill", "decode"]
    for step, lw in lowered.items():
        assert "stablehlo" in lw.as_text(), step
    logits = lowered["decode"].compile()(
        eng.params, eng.kv_k, eng.kv_v, jnp.asarray(eng.block_tables),
        jnp.asarray(eng.lengths), jnp.zeros((eng.cfg.max_batch,), jnp.int32),
        jnp.zeros((eng.cfg.max_batch,), bool))[0]
    assert logits.shape == (eng.cfg.max_batch, eng.arch.vocab)
