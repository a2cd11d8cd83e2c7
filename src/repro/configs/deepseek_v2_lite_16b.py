"""deepseek-v2-lite-16b [moe] — MLA kv_lora=512, 2 shared + 64 routed top-6.

27L d_model=2048 16H d_ff(expert)=1408 vocab=102400, from the published
https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json:
first_k_dense_replace 1 (layer 0 dense, intermediate 10944), softmax
router with greedy top-6 and norm_topk_prob false, yarn rope scaling
(factor 40 over 4096 original positions, beta_fast 32, beta_slow 1,
mscale = mscale_all_dim = 0.707) and the per-sequence aux loss
(seq_aux true, aux_loss_alpha 0.001). [arXiv:2405.04434]
"""
from repro.configs.base import ModelConfig, register


@register("deepseek-v2-lite-16b")
def deepseek_v2_lite() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-16b",
        arch_type="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=10944,            # dense first layer
        vocab_size=102400,
        use_mla=True,
        kv_lora_rank=512,
        q_lora_rank=0,         # V2-Lite: no q compression
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        n_experts=64,
        n_shared_experts=2,
        top_k=6,
        moe_d_ff=1408,
        first_dense_layers=1,
        norm_topk=False,
        aux_loss="seq",
        aux_weight=0.001,
        rope_theta=10_000.0,
        yarn_factor=40.0,
        yarn_original_max_pos=4096,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707,
    )
