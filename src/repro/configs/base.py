"""Model configuration dataclasses.

Every assigned architecture is expressed as a ``ModelConfig``; the model zoo
(`repro.models`) consumes these to build parameter pytrees and forward fns.
Configs are plain frozen dataclasses so they hash/compare and can key jit
caches.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 0              # routed experts
    n_shared_experts: int = 0       # always-on experts (DeepSeek style)
    top_k: int = 1
    d_ff_expert: int = 0            # per-expert FFN hidden
    capacity_factor: float = 1.0    # GShard capacity factor
    router_dtype: str = "float32"
    # "einsum" = GShard one-hot dispatch (baseline, GSPMD-proven)
    # "sort"   = argsort/gather dropless dispatch (optimized path, §Perf)
    dispatch: str = "einsum"


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (DeepSeek-V2)."""
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    q_lora_rank: int = 0            # 0 = no query compression (V2-Lite)


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 SSD block configuration."""
    d_state: int = 128
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    conv_kernel: int = 4
    chunk: int = 256                # SSD chunk length


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0               # 0 -> d_model // n_heads
    act: str = "silu"               # silu (SwiGLU) | gelu (GeGLU)
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0         # chatglm3: 0.5 ("RoPE 2d")
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # --- MoE ---
    moe: Optional[MoEConfig] = None
    first_dense_layers: int = 0     # leading dense layers in an MoE stack
    first_dense_d_ff: int = 0       # their FFN width (dsv2-lite: 10944)
    # --- MLA ---
    mla: Optional[MLAConfig] = None
    # --- SSM / hybrid ---
    ssm: Optional[SSMConfig] = None
    attn_every: int = 0             # zamba2: shared attn block every N layers
    attn_window: int = 0            # sliding window for the shared attn (0=full)
    # --- encoder-decoder (whisper) ---
    is_encoder_decoder: bool = False
    n_enc_layers: int = 0
    enc_len: int = 1500             # stub frontend sequence length
    # --- VLM (llava) ---
    n_image_tokens: int = 0         # stub patch embeddings prepended to text
    # --- compute ---
    param_dtype: str = "float32"    # optimizer-held precision
    compute_dtype: str = "bfloat16"
    use_pallas: bool = False        # dense/training paths only (flash and
                                    # decode kernels); the paged engine
                                    # picks its kernel from its device's
                                    # platform, never from this flag
    remat: bool = True              # checkpoint each layer in train_step
    # --- performance knobs (§Perf hillclimb; defaults = paper-faithful) ---
    gqa_mode: str = "tiled"         # optimized default (§Perf A1c):
                                    # "tiled" KV -> GSPMD-shardable head dim;
                                    # "grouped" = the recorded baseline
                                    # (reports/dryrun_v3). Decode always
                                    # uses the grouped cache read.
    kv_cache_dtype: str = ""        # "" -> compute_dtype; "float8_e4m3fn"
                                    # halves decode HBM traffic
    remat_policy: str = "full"      # "full" | "dots" (save matmul outputs)
    attn_q_block: int = 1024        # XLA flash tile sizes; 256-512 keeps the
    attn_kv_block: int = 1024       # f32 score tile VMEM-resident
    attn_f32_inputs: bool = True    # False: keep bf16 operands and use
                                    # preferred_element_type=f32 (MXU-native;
                                    # avoids materialized f32 activation
                                    # copies — §Perf iteration B3/C3)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """True if sequence mixing is sub-quadratic (SSM / hybrid)."""
        return self.family in ("ssm", "hybrid")

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ShapeConfig:
    """One (input-shape) cell from the assignment."""
    name: str
    seq_len: int
    global_batch: int
    kind: str                       # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


SHAPES: Tuple[ShapeConfig, ...] = (
    ShapeConfig("train_4k", seq_len=4096, global_batch=256, kind="train"),
    ShapeConfig("prefill_32k", seq_len=32768, global_batch=32, kind="prefill"),
    ShapeConfig("decode_32k", seq_len=32768, global_batch=128, kind="decode"),
    ShapeConfig("long_500k", seq_len=524288, global_batch=1, kind="decode"),
)

SHAPES_BY_NAME = {s.name: s for s in SHAPES}


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether a (arch, shape) cell runs, per the assignment's skip rules."""
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return False, ("skip: full-attention arch, 524k decode requires "
                       "sub-quadratic mixing (DESIGN.md §4)")
    return True, ""
