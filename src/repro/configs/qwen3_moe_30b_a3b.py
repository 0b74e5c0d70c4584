"""Qwen3-30B-A3B: fine-grained MoE, 128 SwiGLU experts top-8 with
norm_topk_prob, QK-norm GQA attention (32 query, 4 KV heads of 128),
RMSNorm eps 1e-6, router aux loss 0.001 [hf:Qwen/Qwen3-30B-A3B]."""
from repro.configs.base import ModelConfig
from repro.core.moe import MoEConfig

CONFIG = ModelConfig(
    name="qwen3-moe-30b-a3b", arch_type="moe", n_layers=48, d_model=2048,
    n_heads=32, n_kv_heads=4, head_dim=128, d_ff=768, vocab_size=151936,
    rope_theta=1e6, norm_eps=1e-6, qk_norm=True,
    moe=MoEConfig(d_model=2048, d_ff=768, n_experts=128, top_k=8,
                  capacity_factor=1.25, normalize_topk=True,
                  aux_loss_weight=1e-3, z_loss_weight=0.0,
                  schedule="auto"),
    moe_period=1, source="hf:Qwen/Qwen3-30B-A3B")
