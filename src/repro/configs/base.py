"""Architecture + shape configuration schema.

One ``ArchConfig`` per assigned architecture (exact dims from the assignment
sheet) lives in ``repro.configs.<id>``. ``SHAPES`` defines the four assigned
input-shape cells; applicability per family follows the assignment rules
(long_500k only for sub-quadratic archs, decode only for archs with a
decoder).
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    arch_id: str
    family: str                    # dense | moe | hybrid | ssm | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None           # default d_model // n_heads
    qkv_bias: bool = False
    act: str = "swiglu"                       # swiglu | gelu
    norm: str = "rmsnorm"                     # rmsnorm | layernorm
    rope_theta: float = 10000.0
    tie_embeddings: bool = False

    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25             # 0: no capacity, drop no token
                                              # (sorted, grouped experts)
    norm_topk_prob: bool = True               # renormalise the top-k weights
    routed_scaling_factor: float = 1.0        # scales the routed experts' sum
    n_experts_held: int = 0                   # experts this chip holds (0:
    expert_offset: int = 0                    # all), from expert_offset on
    first_k_dense: int = 0                    # leading dense-FFN layers

    # MLA (deepseek)
    kv_lora_rank: int = 0
    qk_rope_dim: int = 64
    qk_nope_dim: int = 128
    v_head_dim: int = 128
    # YaRN RoPE scaling (deepseek ``rope_scaling``); factor 1 = plain RoPE
    rope_factor: float = 1.0
    rope_original_max_pos: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0

    # SSM / hybrid
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    attn_every_n: int = 0                     # zamba2: shared attn block cadence
    conv_width: int = 4

    # encoder-decoder (whisper)
    n_enc_layers: int = 0
    n_frames: int = 1500                      # stubbed conv-frontend output len

    # VLM
    n_patches: int = 256                      # stubbed ViT patch embeddings

    # numerics
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: str = "full"                       # none | full | dots
    optimizer: str = "adamw"                  # adamw | adafactor

    # per-arch logical-rule overrides (e.g. grok: 8 experts don't divide the
    # 16-way model axis -> keep experts replicated, TP inside each expert)
    rule_overrides: Optional[Dict[str, object]] = None

    # implementation switches (hillclimb knobs)
    attn_impl: str = "xla"                    # xla | ff
    decode_block_kv: Optional[int] = None     # pin the ff decode-attention
                                              # KV tile (None = heuristic);
                                              # serving pins it to the paged
                                              # graph's grid word of pages
                                              # so the contiguous path is
                                              # bitwise-equal to the paged
                                              # path
    layer_graph: bool = False                 # route dense-cache decode steps
                                              # through the whole-layer
                                              # decode_layer StreamGraph (one
                                              # planned multi-kernel program
                                              # per layer step)
    scan_impl: str = "xla"                    # xla | xla_tiled | ff
    scan_layers: bool = True                  # lax.scan over layer stack
    loss_chunk: int = 0                       # >1: chunked-vocab CE (no full
                                              # [B,S,V] f32 logits temp)
    scan_chunk: int = 64                      # GLA chunk length (hillclimb)
    moe_local_dispatch: bool = False          # per-data-shard MoE dispatch
                                              # (local scatter -> all-to-all)
    bf16_grads: bool = False                  # cast layer-boundary cotangents
                                              # to bf16 (halves bwd collective
                                              # and HBM bytes)
    unroll_layers: int = 0                    # >0: build only N unrolled layers
                                              # (cost-extraction variants)

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded to 128 so the table/logits shard on the model axis
        (standard padded-vocab practice; padded ids are never labels)."""
        return -(-self.vocab // 128) * 128

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def sub_quadratic(self) -> bool:
        return self.family in ("hybrid", "ssm")

    @property
    def has_decoder(self) -> bool:
        return True  # all assigned archs decode (whisper is enc-dec)

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                     # train | prefill | decode
    # rule overrides applied when this shape is lowered (e.g. batch=1 decode
    # cannot shard batch; shard the KV-cache sequence instead)
    rule_overrides: Optional[Dict[str, object]] = None


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    # prefill emits a cache: shard its seq ("kv") over model so no device
    # holds a replicated 32k cache
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill",
                               rule_overrides={"kv": "model"}),
    # decode: cache seq sharded over model (kv head counts rarely divide 16)
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode",
                              rule_overrides={"kv": "model", "seq": None,
                                              "kv_heads": None}),
    # batch=1: nothing to DP; shard the long cache seq over data instead
    "long_500k": ShapeConfig(
        "long_500k", 524288, 1, "decode",
        rule_overrides={"batch": None, "kv": "data", "seq": None,
                        "state": None}),
}

ARCH_IDS = (
    "zamba2_2p7b",
    "starcoder2_15b",
    "qwen2_72b",
    "llama3_2_1b",
    "qwen1_5_0p5b",
    "grok1_314b",
    "deepseek_v2_lite_16b",
    "whisper_tiny",
    "internvl2_1b",
    "rwkv6_7b",
)


def shape_applicable(cfg: ArchConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Assignment rules for which (arch x shape) cells run."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, "long_500k skipped: pure full-attention arch (see DESIGN.md)"
    return True, ""


def get_config(arch_id: str) -> ArchConfig:
    mod = importlib.import_module(f"repro.configs.{arch_id}")
    return mod.CONFIG


def smoke_config(arch_id: str) -> ArchConfig:
    mod = importlib.import_module(f"repro.configs.{arch_id}")
    return mod.SMOKE
