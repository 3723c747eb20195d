"""Config system: model/architecture configs, input shapes, smoke reductions.

Every assigned architecture gets a ``configs/<id>.py`` exporting
``CONFIG`` (exact published hyper-parameters) and ``smoke_config()`` (a
reduced same-family config for CPU tests).  ``repro.configs.registry``
resolves ``--arch <id>`` strings.

Input-shape cells (LM family): ``train_4k``, ``prefill_32k``, ``decode_32k``,
``long_500k`` — see ``SHAPES``.  ``decode_*``/``long_*`` lower one
``serve_step`` (single new token against a KV/state cache of the given
length); ``long_500k`` only applies to sub-quadratic archs.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax.numpy as jnp

__all__ = [
    "AttentionConfig", "MoEConfig", "SSMConfig", "RGLRUConfig",
    "ModelConfig", "ShapeConfig", "SHAPES", "TrainConfig",
]


@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10_000.0
    window: Optional[int] = None          # sliding-window size (local attn)
    causal: bool = True
    attn_logit_softcap: Optional[float] = None
    # latent attention (DeepSeek-V2 MLA; kv_lora_rank 0 = none): keys and
    # values come from a cached (kv_lora_rank + qk_rope_head_dim) latent;
    # head_dim is then qk_nope_head_dim + qk_rope_head_dim
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rotary scaling (rope_factor 1 = none), with the paper's and
    # DeepSeek-V2's beta_fast 32 and beta_slow 1 (layers.yarn_inv_freq).
    # The scores take mscale_all_dim; the rotary's own mscale is taken
    # equal to it, so cos and sin are not rescaled (DeepSeek-V2's setting)
    rope_factor: float = 1.0
    rope_original_max_pos: int = 0
    rope_mscale_all_dim: float = 0.0

    @property
    def latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def group_size(self) -> int:
        assert self.num_heads % self.num_kv_heads == 0, \
            f"heads {self.num_heads} not a multiple of kv {self.num_kv_heads}"
        return self.num_heads // self.num_kv_heads


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int                      # per-expert hidden size
    d_ff_shared: int = 0                  # shared-expert hidden size (0 = none)
    capacity_factor: float = 1.25
    interleave_step: int = 1              # every n-th layer is MoE (1 = all)
    dispatch_group: int = 4096            # tokens per dispatch group (GShard G)
    # 1 -> all layers MoE; 2 -> layers 1,3,5,... MoE (llama4-style)
    first_dense_layers: int = 0           # leading dense layers (DeepSeek)
    # (first, count): the contiguous block of experts this chip holds.
    # Set, the layer routes over all num_experts and computes its own
    # share, dropping no token (models/moe.held_moe_block); None, the
    # GShard capacity path over all experts (models/moe.moe_block)
    held_experts: Optional[tuple[int, int]] = None


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk_size: int = 256

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def num_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class RGLRUConfig:
    d_rnn: Optional[int] = None           # None -> d_model
    d_conv: int = 4
    block_pattern: tuple[str, ...] = ("R", "R", "A")  # Griffin 2:1
    window: int = 2048                    # local-attention window


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                           # dense|moe|ssm|hybrid|vlm|audio
    num_layers: int
    d_model: int
    d_ff: int
    vocab_size: int
    attention: Optional[AttentionConfig] = None
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None
    activation: str = "silu"              # silu (SwiGLU) | gelu (plain MLP)
    norm: str = "rmsnorm"                 # rmsnorm | layernorm
    tie_embeddings: bool = False
    # enc-dec (audio) extras
    encoder_layers: int = 0
    encoder_seq: int = 0                  # stub frontend sequence length
    # vlm extras
    num_image_tokens: int = 0             # stub patch-embedding positions
    # numerics
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    kv_cache_dtype: str = ""              # "" = compute dtype; "int8" packs
    # the KV cache at 2x density (static scale; see models/transformer.py)
    remat_policy: str = "minimal"         # none|minimal|full

    @property
    def sub_quadratic(self) -> bool:
        """Can this arch decode at 500k context (O(1)-ish state)?"""
        return self.family in ("ssm", "hybrid")

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    def pdtype(self):
        return jnp.dtype(self.param_dtype)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                             # "train" | "prefill" | "decode"


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "adamw"              # adamw | adafactor
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1_000
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
