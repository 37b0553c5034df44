"""Operations of whole model steps: matrix products and attention."""
from __future__ import annotations

from typing import Sequence

from . import flash_attention, paged_decode_attention


def matmul_params_per_layer(cfg: dict) -> int:
    d, ff, hd = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    qd = cfg["num_attention_heads"] * hd
    kvd = cfg["num_key_value_heads"] * hd
    return d * qd + 2 * d * kvd + qd * d + 3 * d * ff


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def decode_flops(contexts: Sequence[int], cfg: dict) -> float:
    """One decode step of ``len(contexts)`` sequences: every layer's
    products and the head for each, plus attention over live context."""
    per_token = matmul_params_per_layer(cfg) * cfg["num_hidden_layers"] \
        + head_params(cfg)
    return 2.0 * per_token * len(contexts) \
        + paged_decode_attention.work(contexts, cfg)[0]


def prefill_flops(prompts: Sequence[int], cfg: dict) -> float:
    """Prefills of ``prompts``: every layer's products for each real prompt
    token, the head for the one position sampled, causal attention."""
    layers = matmul_params_per_layer(cfg) * cfg["num_hidden_layers"]
    return sum(2.0 * layers * s + 2.0 * head_params(cfg) for s in prompts) \
        + flash_attention.work(prompts, cfg)[0]
