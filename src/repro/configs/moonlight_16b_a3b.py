"""moonlight-16b-a3b (Moonlight-16B-A3B), a DeepSeek-V3 model.

Published config (hf:moonshotai/Moonlight-16B-A3B, ``model_type``
``deepseek_v3``): 27 layers, hidden 2048, 16 heads, vocabulary 163,840,
untied head, ``rms_norm_eps`` 1e-5, ``rope_theta`` 50,000, no
``rope_scaling``.

- Attention is MLA with ``q_lora_rank`` null: ``q = h W_q`` gives 16 x (128
  nope + 64 rope); ``h W_kv_a`` gives a 512-wide latent, RMS-normed, and
  one 64-wide rotary key shared by every head; the latent times ``W_kv_b``
  gives 16 x (128 k_nope + 128 v). Softmax scale 1/sqrt(192).
- Layer 0 is dense, d_ff 11,264 (``first_k_dense_replace`` 1). Layers 1-26
  hold 64 routed experts of width 1,408 and 2 shared ones, one SwiGLU of
  width 2,816. The router is ``sigmoid`` with ``noaux_tc``: top-6 of score
  + ``e_score_correction_bias`` (one group), weighted by the unbiased
  scores normalised over the six, times ``routed_scaling_factor`` 2.446.
"""
from repro.configs.base import (ArchConfig, Family, MLAConfig, MoEConfig,
                                register)

MOONLIGHT_16B_A3B = register(ArchConfig(
    name="moonlight-16b-a3b",
    family=Family.MOE,
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=0,                      # the dense layer's width is moe.d_dense
    vocab=163840,
    head_dim=128,                # v_head_dim; q/k heads are 192 (mla)
    rope_theta=50000.0,
    norm_eps=1e-5,
    moe=MoEConfig(n_experts=64, top_k=6, n_shared_experts=2, d_expert=1408,
                  d_shared=2816, n_dense_layers=1, d_dense=11264,
                  router="sigmoid", router_bias=True, norm_topk_prob=True,
                  routed_scaling_factor=2.446),
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, q_lora_rank=None),
    source="hf:moonshotai/Moonlight-16B-A3B (config.json)",
))
