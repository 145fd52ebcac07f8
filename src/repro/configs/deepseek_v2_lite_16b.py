"""deepseek-v2-lite-16b [moe] — 27L d_model=2048 16H d_ff(expert)=1408
vocab=102400; MLA kv_lora=512 (q uncompressed), YaRN rotary scaling
(factor 40 over 4096 positions), 2 shared + 64 routed experts top-6 with
softmax gates left unnormalized, sequence-wise balance loss alpha 0.001,
first layer dense (d_ff 10944). [arXiv:2405.04434; the published
config.json]

Note (DESIGN.md §5): the pool line mentions "160 routed" which is full V2;
the lite config is 64 routed experts and that is what we implement.
"""
from repro.config import AttnConfig, MLAConfig, ModelConfig, MoEConfig, YarnConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-16b",
        family="moe",
        num_layers=27,
        d_model=2048,
        d_ff=10944,  # the leading dense layer's FFN
        vocab=102400,
        attn=AttnConfig(
            kind="mla", num_heads=16, num_kv_heads=16, head_dim=128,
            rope_theta=10000.0,
            mla=MLAConfig(kv_lora_rank=512, q_lora_rank=None,
                          qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
            rope_scaling=YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                                    beta_fast=32.0, beta_slow=1.0,
                                    mscale=0.707, mscale_all_dim=0.707),
        ),
        moe=MoEConfig(
            num_experts=64, top_k=6, num_shared=2, expert_ffn=1408,
            shared_ffn=2816, norm_topk_prob=False, routed_scale=1.0,
            first_dense_layers=1, aux_alpha=0.001,
        ),
        norm="rmsnorm",
        norm_eps=1e-6,
        tie_embeddings=False,
        remat="full",
        microbatch=1,
    )


def smoke_config() -> ModelConfig:
    """The same kinds of parts at a small size: a dense first layer, MLA with
    YaRN, routed experts top-3 with unnormalized gates, and a shared
    expert."""
    return ModelConfig(
        name="deepseek-v2-lite-smoke",
        family="moe",
        num_layers=3,
        d_model=64,
        d_ff=160,
        vocab=128,
        attn=AttnConfig(
            kind="mla", num_heads=4, num_kv_heads=4, head_dim=16,
            mla=MLAConfig(kv_lora_rank=32, q_lora_rank=None,
                          qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16),
            rope_scaling=YarnConfig(factor=4.0, original_max_position_embeddings=64,
                                    beta_fast=32.0, beta_slow=1.0,
                                    mscale=0.707, mscale_all_dim=0.707),
        ),
        moe=MoEConfig(num_experts=8, top_k=3, num_shared=1, expert_ffn=32,
                      shared_ffn=64, norm_topk_prob=False, first_dense_layers=1,
                      aux_alpha=0.001),
        norm="rmsnorm",
        norm_eps=1e-6,
        remat="none",
    )
