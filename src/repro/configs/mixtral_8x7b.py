"""mixtral-8x7b [moe] — 32L d_model=4096 32H (GQA kv=8) expert_ffn=14336
vocab=32000; 8 experts top-2 (gates renormalized over the selected: a
softmax over their logits), sliding-window
attention (4096) — which bounds the decode cache and makes long_500k
runnable. [arXiv:2401.04088]
"""
from repro.config import AttnConfig, ModelConfig, MoEConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="mixtral-8x7b",
        family="moe",
        num_layers=32,
        d_model=4096,
        d_ff=14336,
        vocab=32000,
        attn=AttnConfig(
            kind="gqa", num_heads=32, num_kv_heads=8, head_dim=128,
            rope_theta=1000000.0, qkv_bias=False, sliding_window=4096,
        ),
        moe=MoEConfig(
            num_experts=8, top_k=2, num_shared=0, expert_ffn=14336,
            norm_topk_prob=True,
        ),
        norm="rmsnorm",
        tie_embeddings=False,
        remat="full",
        microbatch=1,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="mixtral-smoke",
        family="moe",
        num_layers=2,
        d_model=64,
        d_ff=96,
        vocab=128,
        attn=AttnConfig(kind="gqa", num_heads=4, num_kv_heads=2, head_dim=16,
                        sliding_window=8),
        moe=MoEConfig(num_experts=4, top_k=2, expert_ffn=96, norm_topk_prob=True),
        norm="rmsnorm",
        remat="none",
    )
