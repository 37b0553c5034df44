"""Operations of a decode step of latent attention with sparse experts."""
from __future__ import annotations

from typing import Sequence

from . import mla_decode_attention


def token_params(cfg: dict) -> int:
    """Matrix parameters every token's decode multiplies by, the routed
    experts left out: per layer the latent attention in its absorbed form
    (W_q, W_kv_a, q_nope W_UK and o W_UV per head, W_o); the dense layers'
    MLP; per expert layer the router and the shared experts; the head."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    c, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rope, v = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    attention = d * h * (nope + rope) + d * (c + rope) + h * nope * c \
        + h * c * v + h * v * d
    dense = cfg["first_k_dense_replace"]
    experts = cfg["num_hidden_layers"] - dense
    shared = 3 * d * cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    router = d * cfg["published"]["n_routed_experts"]
    return (attention * cfg["num_hidden_layers"]
            + dense * 3 * d * cfg["intermediate_size"]
            + experts * (router + shared) + d * cfg["vocab_size"])


def expert_params(cfg: dict) -> int:
    """Matrix parameters of one routed expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def decode_flops(contexts: Sequence[int], held: int, cfg: dict) -> float:
    """One decode step of ``len(contexts)`` sequences whose tokens made
    ``held`` assignments to this chip's experts, summed over the expert
    layers: each token's products, the assignments' expert products, and
    latent attention over the live context."""
    return 2.0 * token_params(cfg) * len(contexts) \
        + 2.0 * expert_params(cfg) * held \
        + mla_decode_attention.work(contexts, cfg)[0]
