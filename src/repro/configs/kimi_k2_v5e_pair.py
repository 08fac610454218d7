"""Kimi-K2 target + Moonlight-16B-A3B draft at published widths, as one
TPU v5e's share of a deployment.

Sources: huggingface.co/moonshotai/Kimi-K2-Instruct (config.json,
model_type kimi_k2, the DeepSeek-V3 block) and
huggingface.co/moonshotai/Moonlight-16B-A3B (config.json, deepseek_v3).
Both are latent-attention (MLA) MoE models: a leading dense layer, then
layers of sigmoid-routed (noaux_tc, one group) fine-grained experts beside
always-on shared experts.

Kept as published: every width (hidden 7168 / 2048, dense FFN 18432 /
11264, expert FFN 2048 / 1408; MLA q_lora_rank 1536 / none, kv_lora_rank
512, nope/rope/v head dims 128/64/128, 64 / 16 heads), the router's width
(384 / 64) and experts per token (8 / 6), the shared experts (1 / 2),
routed scaling 2.827 / 2.446, RoPE theta 50000 (Kimi-K2 with YaRN, factor
32 over 4096, beta_fast = beta_slow = 1, mscale = mscale_all_dim = 1),
RMSNorm eps 1e-6 / 1e-5, bfloat16.

Changed keys (the cut, model-configs §4):
  * target ``n_layers`` 61 -> 5 (the dense layer and 4 MoE layers);
  * target ``n_experts`` 384 -> 8 held (``router_experts`` stays 384,
    ``expert_offset`` 0): each MoE layer spread over 48 chips;
  * draft ``n_layers`` 27 -> 5 (the dense layer and 4 MoE layers);
  * ``vocab_size`` 163840 -> 20480 for both: one eighth, as the head split
    over 8 chips; a draft proposes in its target's vocabulary;
  * ``moe_dropless`` True: the published models route every token to its
    top-k with no capacity limit.

Assumed: the draft shares the target's Moonshot tokenizer (both
vocabularies are 163840).

Deployment: attention, the dense layer and the shared expert are whole on
every chip; the layers cut away lie on further pipeline stages.  The
draft is resident and whole in width and experts (64).  Weights: target
2.79e9 parameters (5.58 GB in bf16), draft 2.51e9 (5.01 GB).  A held
expert sees only this chip's verify tokens: about 1/48 of what the 48
chips sharing its layer would send it, which the chip's memory cannot
batch; attention sees its whole share.
"""
from repro.configs.base import MLA, ModelConfig

_MLA = dict(arch_type="moe", layer_pattern=(MLA,), qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
            head_dim=192, rope_theta=50_000.0, router="sigmoid_bias",
            first_dense_layers=1, moe_dropless=True,
            vocab_size=20_480, n_layers=5, norm="rmsnorm",
            activation="swiglu", tie_embeddings=False, dtype="bfloat16",
            kv_cache_dtype="bfloat16")

TARGET = ModelConfig(
    name="kimi-k2-5l", d_model=7168, n_heads=64, n_kv_heads=64, d_ff=18432,
    n_experts=8, router_experts=384, expert_offset=0, top_k=8,
    expert_d_ff=2048, n_shared_experts=1, routed_scaling_factor=2.827,
    q_lora_rank=1536, norm_eps=1e-6,
    rope_scaling={"type": "yarn", "factor": 32,
                  "original_max_position_embeddings": 4096,
                  "beta_fast": 1, "beta_slow": 1, "mscale": 1,
                  "mscale_all_dim": 1},
    source="huggingface.co/moonshotai/Kimi-K2-Instruct; n_layers 61->5, "
           "8 of 384 experts held, vocab 163840->20480",
    **_MLA)

DRAFT = ModelConfig(
    name="moonlight-16b-a3b-5l", d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=11264, n_experts=64, router_experts=64, top_k=6, expert_d_ff=1408,
    n_shared_experts=2, routed_scaling_factor=2.446, q_lora_rank=0,
    norm_eps=1e-5,
    source="huggingface.co/moonshotai/Moonlight-16B-A3B; n_layers 27->5, "
           "vocab 163840->20480",
    **_MLA)
