"""One decode step's attention over the paged cache, all layers."""
from __future__ import annotations

from typing import Sequence, Tuple

BYTES = 2      # bfloat16


def work(contexts: Sequence[int], cfg: dict) -> Tuple[float, float]:
    """(FLOPs, bytes) for active sequences attending over ``contexts``
    tokens each: q.k and p.v per query head; K and V of the live context
    read once per kv head, q read and the output written once."""
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, layers = cfg["head_dim"], cfg["num_hidden_layers"]
    ctx = sum(contexts)
    flops = 4.0 * ctx * hq * hd * layers
    nbytes = (2.0 * ctx * hkv * hd + 2.0 * len(contexts) * hq * hd) \
        * BYTES * layers
    return flops, nbytes
