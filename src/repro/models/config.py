"""Model + input-shape configuration schema.

Single source of truth for every selectable architecture (``--arch``) and
every assigned input shape. Configs are frozen dataclasses so they hash and
can key jit caches.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


#: mixer kinds that are attention (and so carry an MLP or MoE after them)
ATTENTION_KINDS = ("attn", "swa", "local", "mla")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // n_heads

    # --- attention options ---
    attn_bias: bool = False        # Qwen-style QKV bias
    window: int = 0                # 0 = full attention; >0 = sliding window
    causal: bool = True            # False for encoder-only (HuBERT)
    rope_theta: float = 10_000.0
    mrope: bool = False            # Qwen2-VL multimodal RoPE
    mrope_sections: Tuple[int, ...] = (16, 24, 24)

    # --- MoE options ---
    n_experts: int = 0             # routed experts the router scores
    topk: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    moe_d_ff: int = 0              # routed-expert width (0 -> d_ff)
    n_shared_experts: int = 0      # shared experts: one SwiGLU, n * moe_ff
    first_k_dense: int = 0         # leading layers with a dense MLP
    # expert parallelism: this chip holds experts [shard*held, (shard+1)*held)
    # and computes only their part, dropless (0 -> all, capacity dispatch)
    experts_held: int = 0
    expert_shard: int = 0
    norm_topk_prob: bool = True    # renormalize the top-k gate weights
    routed_scaling: float = 1.0

    # --- multi-head latent attention (kind "mla"; DeepSeek-V2) ---
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- YaRN rope scaling (0 = plain RoPE) ---
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # --- layer pattern ---
    # Unit of block kinds repeated down the stack; remainder handled
    # explicitly. Kinds: attn | swa | local | mla | mlstm | slstm | rglru
    pattern_unit: Tuple[str, ...] = ("attn",)

    # --- recurrent widths ---
    lru_width: int = 0             # RG-LRU width (0 -> d_model)
    conv_width: int = 4

    # --- MLP / norm ---
    mlp: str = "swiglu"            # swiglu | gelu | none
    norm: str = "rmsnorm"          # rmsnorm | layernorm
    norm_eps: float = 1e-6
    tie_embeddings: bool = False

    # --- modality frontend stub ---
    frontend: Optional[str] = None  # None | audio_frames | vision_patches
    d_frontend: int = 0

    # --- numerics ---
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"

    # --- runtime knobs (overridable by the MeshPlanner) ---
    remat: str = "full"            # none | dots | full
    scan_layers: bool = True
    use_pallas: bool = False       # TPU hot path; CPU CI uses the jnp path
    attn_q_chunk: int = 512
    attn_kv_chunk: int = 1024
    mlstm_chunk: int = 256

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def moe_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def n_experts_held(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def is_encoder_only(self) -> bool:
        return not self.causal

    @property
    def sub_quadratic(self) -> bool:
        """True if decode state is bounded (window, recurrence) -> long_500k ok."""
        kinds = set(self.pattern_unit)
        if kinds & {"mlstm", "slstm", "rglru"}:
            # fine unless some layer is *full* attention
            return "attn" not in kinds or self.window > 0
        return self.window > 0 or all(k in ("swa", "local") for k in kinds)

    @property
    def lru_d(self) -> int:
        return self.lru_width or self.d_model

    def pattern(self) -> Tuple[str, ...]:
        """Full per-layer kind list of length n_layers."""
        unit = self.pattern_unit
        reps = self.n_layers // len(unit)
        rem = self.n_layers % len(unit)
        return unit * reps + unit[:rem]

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def n_params(self) -> int:
        """Analytic parameter count (matches the schema; used for roofline)."""
        from repro.models.schema import count_params
        return count_params(self)

    def n_active_params(self) -> int:
        """Params touched per token (MoE: only topk routed experts active,
        of a held share ``topk * held / n_experts`` on average; the leading
        dense layers and the shared experts always are)."""
        from repro.models.schema import count_params
        total = count_params(self)
        if self.n_experts and self.topk:
            # routed-expert params per layer: 3*d*moe_ff each (fused
            # gate|up = 2, down = 1), of which n_experts_held are stored
            n_moe_layers = sum(1 for k in self.pattern()[self.first_k_dense:]
                               if k in ATTENTION_KINDS)
            held = self.n_experts_held
            idle = held - self.topk * held / self.n_experts
            return total - round(idle * 3 * self.d_model * self.moe_ff
                                 * n_moe_layers)
        return total


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # train | prefill | decode


SHAPES = {
    "train_4k":    ShapeSpec("train_4k",    4_096,   256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768,  32,  "prefill"),
    "decode_32k":  ShapeSpec("decode_32k",  32_768,  128, "decode"),
    "long_500k":   ShapeSpec("long_500k",   524_288, 1,   "decode"),
}


def cell_supported(cfg: ModelConfig, shape: ShapeSpec) -> Tuple[bool, str]:
    """Whether an (arch x shape) dry-run cell is runnable; reason if not."""
    if shape.kind == "decode" and cfg.is_encoder_only:
        return False, "encoder-only arch has no decode step"
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, "full-attention arch: 500k decode needs sub-quadratic attention"
    return True, ""
