"""deepseek-v2-lite-16b — DeepSeek-V2-Lite as published
(huggingface.co/deepseek-ai/DeepSeek-V2-Lite ``config.json``;
arXiv:2405.04434): 27 layers, hidden 2048, vocab 102400, 16 MLA heads
(``kv_lora_rank`` 512, ``qk_nope_head_dim`` 128, ``qk_rope_head_dim`` 64,
``v_head_dim`` 128, no q LoRA), layer 0 dense (``first_k_dense_replace``
1, ``intermediate_size`` 10944), then 26 MoE layers of 64 routed experts
of width 1408, 6 per token (softmax scores, greedy top-k,
``norm_topk_prob`` false, ``routed_scaling_factor`` 1: the gates are
the top-6 probabilities as they come) and 2 shared experts; YaRN rope
(factor 40 over 4096 original positions, ``beta_fast`` 32, ``beta_slow``
1, ``mscale`` = ``mscale_all_dim`` = 0.707), RMSNorm eps 1e-6.

Layout difference: the published code permutes each head's rope
dimensions from interleaved pairs to halves before rotating; this repo
rotates halves of the columns as they come, a fixed permutation of the
rope columns of ``wq`` and ``wkr`` (``attention.mla_rope``).

``SMOKE`` keeps the shape of the model at CPU size: a dense layer 0, two
MoE layers of 8 routed experts (3 per token) and 2 shared, YaRN, and one
chip's share of the experts (experts 2-5 held), so the CPU tests run
the served held-expert path."""

from repro.models.config import BlockSpec, ModelConfig, MoECfg, YarnCfg

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    first_dense=1,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    vocab=102400,
    period=(BlockSpec("attn", "moe"),),
    moe=MoECfg(num_experts=64, top_k=6, d_ff_expert=1408, num_shared=2,
               d_ff_shared=1408, router_norm_topk=False),
    attn_type="mla",
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    rope_theta=1e4,
    rope_scaling=YarnCfg(factor=40.0, original_max_position=4096,
                         beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                         mscale_all_dim=0.707),
    norm_eps=1e-6,
)

SMOKE = CONFIG.scaled(
    n_layers=3, d_model=128, n_heads=4, d_ff=256, vocab=512,
    moe=MoECfg(num_experts=8, top_k=3, d_ff_expert=64, num_shared=2,
               d_ff_shared=32, held_first=2, held=4),
    kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
    rope_scaling=YarnCfg(factor=40.0, original_max_position=64,
                         beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                         mscale_all_dim=0.707),
    dtype="float32")
