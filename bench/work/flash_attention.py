"""Causal prefill attention over whole prompts, all layers."""
from __future__ import annotations

from typing import Sequence, Tuple

BYTES = 2      # bfloat16


def work(prompts: Sequence[int], cfg: dict) -> Tuple[float, float]:
    """(FLOPs, bytes) of causal attention over prompts of ``prompts``
    tokens: S(S+1)/2 query-key pairs per query head, each 2 hd FLOPs for
    q.k and 2 hd for p.v; q, k, v read and the output written once."""
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, layers = cfg["head_dim"], cfg["num_hidden_layers"]
    flops = sum(2.0 * hq * hd * s * (s + 1) for s in prompts) * layers
    nbytes = sum(s * hd * (2.0 * hq + 2.0 * hkv) for s in prompts) \
        * BYTES * layers
    return flops, nbytes
