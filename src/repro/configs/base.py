"""Config system: architecture + shape + run configs.

One ``configs/<arch>.py`` per assigned architecture exports ``CONFIG``
(exact published numbers) and ``reduced()`` (a tiny same-family variant for
CPU smoke tests). Shapes are the assigned input-shape set; each arch lists
which shapes apply (``long_500k`` only for sub-quadratic families,
per the assignment).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any

import jax.numpy as jnp


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int                 # query heads (0 => attention-free)
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 => d_model // num_heads
    # layer flavours
    hidden_act: str = "silu"       # silu | gelu | relu2
    mlp_gated: bool = True
    norm: str = "rmsnorm"          # rmsnorm | layernorm
    norm_offset: bool = False      # gemma-style (1 + w) RMSNorm scale
    rope_theta: float = 10_000.0
    pos_embedding: str = "rope"    # rope | learned | none
    logits_soft_cap: float = 0.0
    tie_embeddings: bool = False
    # MoE
    num_experts: int = 0
    experts_per_token: int = 0
    capacity_factor: float = 1.25
    router_aux_loss: float = 0.01
    # hybrid / recurrent
    block_pattern: tuple[str, ...] = ("attn",)   # cycled over layers
    local_window: int = 0          # sliding-window size for local_attn blocks
    lru_width: int = 0             # RG-LRU state width
    conv_width: int = 4
    # ssm (rwkv)
    rwkv_chunk: int = 16
    decay_lora: int = 64
    # enc-dec (whisper)
    encoder_layers: int = 0
    encoder_seq: int = 0           # precomputed frame-embedding length
    is_encoder_decoder: bool = False
    # vlm (llava)
    num_image_tokens: int = 0
    # numerics / execution
    dtype: str = "bfloat16"        # activation/compute dtype
    param_dtype: str = "bfloat16"
    remat: str = "none"            # none | full | dots
    scan_layers: bool = True
    attention_impl: str = "blocked"  # blocked | naive | pallas | triangular
    pad_attention_heads: bool = False  # pad H to the TP degree (see §Perf)
    attention_block_q: int = 512
    attention_block_kv: int = 1024
    # sharding rule overrides (logical -> mesh axes)
    sharding_overrides: dict = field(default_factory=dict, hash=False, compare=False)
    # max positions for learned embeddings
    max_position: int = 0

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    @property
    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # train | prefill | decode


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    zero1: bool = True             # shard optimizer state over 'data'(+pod)
    master_fp32: bool = True
    state_dtype: str = "float32"   # m/v moments dtype (bf16 for 1T configs)
    compression: str | None = None  # int8 gradient compression (DP path)


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: ShapeConfig
    optimizer: OptimizerConfig = OptimizerConfig()
    seed: int = 0
    checkpoint_dir: str | None = None
    checkpoint_every: int = 100
