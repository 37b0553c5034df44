"""Logical FLOPs and bytes of ``bench/work`` against sums worked out by
hand at a small shape."""
import pytest

from bench.work import flash_attention, model_step, paged_decode_attention

CFG = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 2, "num_hidden_layers": 3,
       "vocab_size": 10}


def test_paged_decode_work_by_hand():
    # two sequences of 3 and 5 live tokens: q.k and p.v are 4 FLOPs per
    # (token, q head, dim): 4 * 8 * 4 * 2 per layer, 3 layers; K and V of
    # 8 tokens x 2 kv heads x 2 dims, q and out 2 x 2 seqs x 4 heads x 2
    # dims, 2 bytes each, 3 layers
    flops, nbytes = paged_decode_attention.work([3, 5], CFG)
    assert flops == 4 * 8 * 4 * 2 * 3 == 768
    assert nbytes == (2 * 8 * 2 * 2 + 2 * 2 * 4 * 2) * 2 * 3 == 576


def test_flash_attention_work_by_hand():
    # a 3-token prompt: 6 causal pairs x 4 heads x (2 + 2) x 2 dims per
    # layer; q, k, v, out: 3 tokens x 2 dims x (4 + 2 + 2 + 4) heads x 2 B
    flops, nbytes = flash_attention.work([3], CFG)
    assert flops == 6 * 4 * 4 * 2 * 3 == 576
    assert nbytes == 3 * 2 * 12 * 2 * 3 == 432


def test_model_step_flops_by_hand():
    per_layer = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16
    assert model_step.matmul_params_per_layer(CFG) == per_layer == 576
    assert model_step.head_params(CFG) == 80
    # decode: 2 FLOPs per parameter per sequence, plus attention
    assert model_step.decode_flops([3, 5], CFG) == \
        2 * (576 * 3 + 80) * 2 + 768 == 8000
    # prefill: every real token through the layers, one head row
    assert model_step.prefill_flops([3], CFG) == \
        2 * 576 * 3 * 3 + 2 * 80 + 576 == 11104


@pytest.mark.parametrize("prompts", [[1], [7, 9], [64]])
def test_flash_work_grows_with_real_tokens_only(prompts):
    f, b = flash_attention.work(prompts, CFG)
    f2, b2 = flash_attention.work(prompts + [0], CFG)
    assert (f, b) == (f2, b2)
