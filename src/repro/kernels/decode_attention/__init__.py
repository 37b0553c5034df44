from repro.kernels.decode_attention.ops import (                     # noqa: F401
    attend_partial, decode_attention_ref, merge_partials,
    paged_decode_attention, paged_decode_attention_pallas, paged_decode_ref)
from repro.kernels.decode_attention.mla_decode_attention import (  # noqa: F401
    mla_decode_attention, mla_decode_attention_pallas, mla_decode_ref)
