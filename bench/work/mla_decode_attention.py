"""One decode step's latent attention (MLA, absorbed) over the paged
latent rows, all layers."""
from __future__ import annotations

from typing import Sequence, Tuple

BYTES = 2      # bfloat16


def work(contexts: Sequence[int], cfg: dict) -> Tuple[float, float]:
    """(FLOPs, bytes) for active sequences attending over ``contexts``
    latent rows each: every head scores against the whole row
    (``kv_lora_rank + qk_rope_head_dim``) and accumulates over its first
    ``kv_lora_rank`` columns; the live rows read once per layer, the
    absorbed q read and the output written once."""
    h, c = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    row, layers = c + cfg["qk_rope_head_dim"], cfg["num_hidden_layers"]
    ctx = sum(contexts)
    flops = 2.0 * ctx * h * (row + c) * layers
    nbytes = (ctx * row + len(contexts) * h * (row + c)) * BYTES * layers
    return flops, nbytes
