"""Configuration dataclasses for models, shapes, meshes and training.

Every assigned architecture is described by a ``ModelConfig``; the registry
in ``repro.configs`` maps ``--arch <id>`` to one. Shapes (``--shape``) are
the assigned (seq_len, global_batch, step-kind) cells.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-style Multi-head Latent Attention compression."""
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None  # None => full-rank queries
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rotary scaling as DeepSeek-V2's ``rope_scaling`` gives it
    (arXiv:2309.00071): frequencies interpolated by ``factor`` outside the
    correction range that ``beta_fast``/``beta_slow`` set over
    ``original_max_position_embeddings``; cos/sin scaled by
    mscale(factor, mscale) / mscale(factor, mscale_all_dim) and the softmax
    scale by mscale(factor, mscale_all_dim)**2."""
    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclass(frozen=True)
class AttnConfig:
    kind: str = "gqa"  # gqa | mla | flare_stream | none
    num_heads: int = 8
    num_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 10000.0
    qkv_bias: bool = False
    sliding_window: Optional[int] = None  # tokens; None => full attention
    mrope_sections: Optional[Tuple[int, int, int]] = None  # qwen2-vl M-RoPE
    mla: Optional[MLAConfig] = None
    rope_scaling: Optional[YarnConfig] = None  # MLA's rotary dims only
    # flare_stream mixer options
    flare_latents: int = 0
    flare_chunk: int = 256

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    num_shared: int = 0
    expert_ffn: int = 1408          # per-expert hidden size
    shared_ffn: int = 0             # hidden size of the shared expert(s)
    norm_topk_prob: bool = True     # renormalize gates over the selected k
    routed_scale: float = 1.0       # deepseek routed_scaling_factor
    first_dense_layers: int = 0     # leading layers that use a dense FFN
    aux_alpha: float = 0.01         # weight of the sequence-wise balance loss
    # the routed experts this layer holds: [expert_offset, expert_offset +
    # experts_held) of num_experts (None: all) -- one rank's share under
    # expert parallelism; the router still scores all num_experts
    experts_held: Optional[int] = None
    expert_offset: int = 0

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None else self.experts_held


@dataclass(frozen=True)
class SSMConfig:
    kind: str = "mamba2"            # mamba2 | rwkv6
    state_dim: int = 64             # N (mamba2) / head_dim (rwkv6 keys)
    head_dim: int = 64
    num_heads: int = 0              # 0 => derived from d_inner / head_dim
    expand: int = 2                 # d_inner = expand * d_model
    conv_kernel: int = 4            # mamba2 depthwise conv width
    chunk: int = 64                 # chunked-scan block length
    dt_rank: int = 0                # unused by mamba2 (scalar dt per head)


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"           # dense | moe | ssm | hybrid | encdec | vlm | audio | pde
    num_layers: int = 4
    d_model: int = 256
    d_ff: int = 1024
    vocab: int = 32000
    attn: AttnConfig = field(default_factory=AttnConfig)
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    norm: str = "rmsnorm"           # rmsnorm | layernorm
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    mlp_bias: bool = False
    # enc-dec
    num_encoder_layers: int = 0
    encoder_mixer: str = "attn"     # attn | flare  (seamless FLARE-encoder variant)
    # hybrid (zamba2)
    shared_attn_every: int = 0      # apply shared attention block every k layers
    lora_rank: int = 0              # per-invocation LoRA rank on the shared block
    # vlm / audio frontends are stubs: inputs arrive as embeddings
    inputs_are_embeddings: bool = False
    # flare-LM / flare-PDE
    flare_latents: int = 0
    flare_heads: int = 0
    # numerics
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # remat / accumulation defaults (overridable per shape at launch)
    remat: str = "full"             # full | dots | none
    microbatch: int = 1             # per-device microbatch size for train

    @property
    def head_dim(self) -> int:
        return self.attn.head_dim


@dataclass(frozen=True)
class ShapeConfig:
    name: str = "train_4k"
    seq_len: int = 4096
    global_batch: int = 256
    step: str = "train"             # train | prefill | decode
    # decode shapes: KV cache of seq_len, one new token per sequence.


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
    # paper-native shapes (FLARE PDE surrogate; extra cells beyond the 40)
    "pde_40k": ShapeConfig("pde_40k", 40000, 8, "train"),
    "pde_1m": ShapeConfig("pde_1m", 1048576, 1, "train"),
}


@dataclass(frozen=True)
class MeshConfig:
    shape: Tuple[int, ...] = (16, 16)
    axes: Tuple[str, ...] = ("data", "model")

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


@dataclass(frozen=True)
class TrainConfig:
    steps: int = 100
    learning_rate: float = 1e-3
    warmup_frac: float = 0.1
    weight_decay: float = 1e-5
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    grad_clip: float = 1.0
    seed: int = 0
    checkpoint_every: int = 50
    checkpoint_dir: str = "/tmp/repro_ckpt"
    keep_checkpoints: int = 3
    grad_compression: bool = False  # int8 error-feedback DP all-reduce
    log_every: int = 10


def replace(cfg, **kw):
    return dataclasses.replace(cfg, **kw)
