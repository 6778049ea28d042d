"""DeepSeek-V2 236B [arXiv:2405.04434], as its config.json states it
(huggingface.co/deepseek-ai/DeepSeek-V2): 60 layers, d=5120, RMSNorm eps
1e-6, vocabulary 102,400 with an untied head.

- Attention: multi-head latent attention on every layer, 128 heads; the
  query through a rank-1536 LoRA with an RMSNorm (128 nope + 64 rope dims a
  head), K/V from one 512-wide normed latent plus one 64-wide rope key
  shared by all heads, expanded to a 128 nope key and a 128 value a head.
  Rotary embedding with YaRN (factor 40 over 4096 positions, beta 32/1,
  mscale = mscale_all_dim = 0.707).
- FFN: layer 0 dense SwiGLU of width 12,288 (`first_k_dense_replace` 1);
  layers 1-59 MoE with 160 routed SwiGLU experts of width 1536 and 2
  shared ones (one SwiGLU of 3072). The router scores with a float32
  softmax, keeps the best 3 of 8 groups of 20 (a group scored by its best
  expert), takes the top 6 experts among those, and gates them by their
  scores times 16, without renormalising (`group_limited_greedy`).
"""
from repro.configs.base import (MLA, MOE, MLAConfig, MoEConfig, ModelConfig,
                                YarnScaling)

CONFIG = ModelConfig(
    name="deepseek-v2-236b",
    family="moe",
    num_layers=60,
    d_model=5120,
    num_heads=128,
    num_kv_heads=128,   # MLA: every head its own K/V (no GQA grouping)
    d_ff=1536,          # routed-expert width
    vocab_size=102_400,
    head_dim=128,       # nope head dim
    pattern=(MLA,),
    ffn_pattern=(MOE,),
    first_k_dense=1,    # layer 0: MLA + dense FFN
    dense_d_ff=12_288,
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=1536, rope_head_dim=64,
                  nope_head_dim=128, v_head_dim=128, absorb_decode=True),
    moe=MoEConfig(num_experts=160, top_k=6, d_ff_expert=1536,
                  num_shared_experts=2, capacity_factor=1.0,
                  n_group=8, topk_group=3, norm_topk_prob=False,
                  routed_scaling_factor=16.0),
    rope_theta=10_000.0,
    rope_scaling=YarnScaling(factor=40.0,
                             original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    norm_eps=1e-6,
    sub_quadratic=False,   # MLA compresses KV but attention is full
    opt_state_dtype="bfloat16",
    remat_policy="nothing",  # §Perf B4: memory headroom
    train_microbatch=32,      # §Perf: memory-feasibility frontier (opt4)
    fsdp_over_pod=True,
)

# small widths, the same mechanisms: groups, held experts and YaRN all
# divide or fit its 8 experts and 64 positions
SMOKE = CONFIG.scaled(
    num_layers=3, d_model=128, num_heads=4, num_kv_heads=4, head_dim=32,
    d_ff=64, dense_d_ff=256, vocab_size=256, first_k_dense=1,
    mla=MLAConfig(kv_lora_rank=32, q_lora_rank=48, rope_head_dim=16,
                  nope_head_dim=32, v_head_dim=32),
    moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=64,
                  num_shared_experts=1, dispatch="dense", n_group=4,
                  topk_group=2, norm_topk_prob=False,
                  routed_scaling_factor=16.0),
    rope_scaling=YarnScaling(factor=40.0,
                             original_max_position_embeddings=16,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    opt_state_dtype="float32")
