"""deepseek-v2-236b — MoE with Multi-head Latent Attention (arXiv:2405.04434; hf).

60L d_model=5120 128H d_ff(expert)=1536 vocab=102400, 160 routed experts top-6
+ 2 shared, MLA kv_lora=512.  First layer uses a dense FFN (12288), per the
HF reference config (first_k_dense_replace=1).  Routing is
group_limited_greedy (8 groups, top 3, gates x16, not renormalised); rope is
YaRN (factor 40 over 4096 positions, mscale 0.707 both) on interleaved pairs,
as huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json and its
modelling code state them.
"""
from repro.models.config import ModelConfig, YarnScaling

CONFIG = ModelConfig(
    name="deepseek-v2-236b",
    family="moe",
    num_layers=60,
    d_model=5120,
    num_heads=128,
    num_kv_heads=128,          # MLA: logical heads; the cache is the compressed latent
    head_dim=128,
    d_ff=12288,                # dense FFN width for the first_k_dense layers
    vocab_size=102400,
    attention_type="mla",
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=160,
    num_shared_experts=2,
    moe_top_k=6,
    moe_d_ff=1536,
    first_k_dense=1,
    n_group=8,
    topk_group=3,
    routed_scaling_factor=16.0,
    norm_topk_prob=False,
    rope_theta=10_000.0,
    rope_scaling=YarnScaling(factor=40.0, original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    rope_interleave=True,
    norm_eps=1e-6,
)


def smoke_config() -> ModelConfig:
    """Reduced same-family config for CPU smoke tests."""
    return CONFIG.replace(
        num_layers=3, d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
        d_ff=128, vocab_size=128, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        num_experts=8, num_shared_experts=2, moe_top_k=2, moe_d_ff=32,
        first_k_dense=1, n_group=4, topk_group=2, dtype="float32")
