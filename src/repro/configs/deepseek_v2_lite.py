"""deepseek-v2-lite — MLA kv_lora=512 with YaRN, MoE 2 shared + 64 routed
top-6, no top-k renormalization [arXiv:2405.04434,
huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json].

Rope pairs its dims as halves (``common.apply_rope``); the published code
pairs them interleaved and permutes to halves before rotating, so a
published checkpoint maps onto this layout by permuting the 64 rotary
columns of ``w_q`` (per head) and of ``w_kv_down`` to
``[0, 2, ..., 62, 1, 3, ..., 63]``."""
from repro.configs.base import ModelConfig, MoEConfig, MLAConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    citation="arXiv:2405.04434",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,              # MLA: per-q-head keys decompressed from latent
    d_ff=10944,                   # dense FFN of layer 0 (first_k_dense_replace 1)
    vocab_size=102400,
    rope_theta=10000.0,
    rms_norm_eps=1e-6,
    max_seq_len=163840,
    moe=MoEConfig(num_experts=64, top_k=6, num_shared_experts=2,
                  expert_d_ff=1408, norm_topk=False),
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                  rope_factor=40.0, rope_original_max_positions=4096,
                  beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                  mscale_all_dim=0.707),
)
