"""Config system: model, shape, mesh and run configs.

Every assigned architecture is a `ModelConfig`; every assigned input shape a
`ShapeConfig`. Dataclasses are frozen (hashable) so they can be static
arguments to jit and keys into compile caches.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Routed experts (``n_experts`` router outputs, ``top_k`` a token),
    optional shared experts that every token passes through (acting as one
    SwiGLU of ``n_shared_experts * d_ff_expert``), and the share of the
    routed experts this device holds: experts ``held_start`` to
    ``held_start + n_held - 1`` (``held_count`` None: all of them). Routing
    is over all ``n_experts``; pairs routed to experts held elsewhere add
    nothing here (expert parallelism, one device's part)."""

    n_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    router_z_weight: float = 1e-3
    n_shared_experts: int = 0
    norm_topk_prob: bool = True       # renormalise the top-k gates to sum 1
    routed_scaling_factor: float = 1.0
    held_start: int = 0
    held_count: Optional[int] = None

    @property
    def n_held(self) -> int:
        return self.n_experts if self.held_count is None else self.held_count

    @property
    def holds_all(self) -> bool:
        return self.held_start == 0 and self.n_held == self.n_experts


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 / SSD hyperparameters."""

    d_state: int = 128
    expand: int = 2
    headdim: int = 64
    n_groups: int = 1
    conv_width: int = 4
    chunk: int = 256
    dt_min: float = 0.001
    dt_max: float = 0.1


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (DeepSeek-V2 / MiniCPM3).

    ``q_lora_rank`` None projects the query directly from the hidden state
    (DeepSeek-V2-Lite); ``rope_interleave`` takes the rope dims as
    interleaved pairs and permutes them to rotate-half order before
    rotating, as DeepSeek-V2's ``apply_rotary_pos_emb`` does."""

    q_lora_rank: Optional[int] = 768
    kv_lora_rank: int = 256
    qk_nope_dim: int = 64
    qk_rope_dim: int = 32
    v_head_dim: int = 64
    rope_interleave: bool = False


@dataclasses.dataclass(frozen=True)
class YaRNConfig:
    """YaRN rope scaling (Peng et al. 2023) as DeepSeek-V2 configures it:
    inverse frequencies blended between the interpolated (``/ factor``)
    and the original ones by a linear ramp over the dims whose rotations
    in ``original_max_position`` lie between ``beta_slow`` and
    ``beta_fast``; cos/sin scaled by mscale(mscale) / mscale(mscale_all_dim)
    and the softmax scale by mscale(mscale_all_dim) ** 2, where
    mscale(m) = 0.1 * m * ln(factor) + 1."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class RGLRUConfig:
    """Griffin / RecurrentGemma RG-LRU block."""

    lru_width: int = 0          # 0 -> d_model
    conv_width: int = 4
    c: float = 8.0              # Griffin's fixed decay sharpness


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab_size: int
    # Repeating layer pattern; cycled to n_layers (tail truncated).
    # kinds: "attn" (global self-attn + FFN), "local_attn" (windowed),
    #        "xattn" (cross-attn to frontend embeds + FFN),
    #        "ssm" (Mamba2 block, no FFN), "rec" (RG-LRU block + FFN)
    block_pattern: tuple[str, ...] = ("attn",)
    norm: str = "rmsnorm"          # rmsnorm | layernorm | layernorm_np
    ffn_kind: str = "swiglu"       # swiglu | gelu
    window: Optional[int] = None   # local_attn window size
    rope_theta: float = 10000.0
    rope_scaling: Optional[YaRNConfig] = None
    # the first ``first_k_dense`` layers are dense (SwiGLU of ``d_ff``)
    # blocks outside the stacked units; the rest use ``moe`` when set
    first_k_dense: int = 0
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    mla: Optional[MLAConfig] = None
    rglru: Optional[RGLRUConfig] = None
    n_img_tokens: int = 0          # vlm stub frontend tokens
    n_codebooks: int = 0           # audio codebook streams (musicgen)
    qkv_bias: bool = False
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    # --- framework knobs (not architecture) ---
    dtype: str = "bfloat16"        # params/activations dtype
    use_pallas: bool = False       # route hot-spots to Pallas kernels (TPU)
    mma_reductions: bool = True    # paper's technique on/off (off = baseline)
    remat: bool = True             # activation checkpointing per layer-unit
    logits_softcap: float = 0.0

    def __post_init__(self):
        # built from JSON too: nested configs as dicts, tuples as lists
        for name, cls in (("moe", MoEConfig), ("ssm", SSMConfig),
                          ("mla", MLAConfig), ("rglru", RGLRUConfig),
                          ("rope_scaling", YaRNConfig)):
            v = getattr(self, name)
            if isinstance(v, dict):
                object.__setattr__(self, name, cls(**v))
        object.__setattr__(self, "block_pattern", tuple(self.block_pattern))

    @property
    def pattern_layers(self) -> tuple[str, ...]:
        reps = -(-self.n_layers // len(self.block_pattern))
        return (self.block_pattern * reps)[: self.n_layers]

    @property
    def attention_free(self) -> bool:
        return all(k in ("ssm", "rec") for k in self.pattern_layers)

    @property
    def subquadratic(self) -> bool:
        """True if the arch can decode with O(1)-or-bounded state per token
        (SSM/recurrent state or bounded local-attention window)."""
        return all(
            k in ("ssm", "rec") or (k == "local_attn" and self.window)
            for k in self.pattern_layers
        )

    def param_count(self) -> int:
        """Analytic parameter count (embeddings + blocks + head)."""
        d = self.d_model
        total = self.vocab_size * d  # embed
        if self.n_codebooks:
            total += (self.n_codebooks - 1) * self.vocab_size * d
        if not self.tie_embeddings:
            total += self.vocab_size * d * max(1, self.n_codebooks or 1)
        for i, kind in enumerate(self.pattern_layers):
            if kind in ("attn", "local_attn"):
                if self.mla is not None:
                    m = self.mla
                    qk = self.n_heads * (m.qk_nope_dim + m.qk_rope_dim)
                    if m.q_lora_rank is None:
                        total += d * qk
                    else:
                        total += d * m.q_lora_rank + m.q_lora_rank * qk
                    total += d * (m.kv_lora_rank + m.qk_rope_dim)
                    total += m.kv_lora_rank * self.n_heads * (m.qk_nope_dim + m.v_head_dim)
                    total += self.n_heads * m.v_head_dim * d
                else:
                    total += d * self.n_heads * self.d_head
                    total += 2 * d * self.n_kv_heads * self.d_head
                    total += self.n_heads * self.d_head * d
                total += self._ffn_params(dense=i < self.first_k_dense)
            elif kind == "xattn":
                total += d * self.n_heads * self.d_head
                total += 2 * d * self.n_kv_heads * self.d_head
                total += self.n_heads * self.d_head * d
                total += self._ffn_params()
            elif kind == "ssm":
                s = self.ssm
                d_in = s.expand * d
                conv_dim = d_in + 2 * s.n_groups * s.d_state
                nh = d_in // s.headdim
                total += d * (2 * d_in + 2 * s.n_groups * s.d_state + nh)
                total += conv_dim * s.conv_width
                total += d_in * d
                total += d_in + 2 * nh  # gated-norm gamma + A, D, dt_bias approx
            elif kind == "rec":
                r = self.rglru or RGLRUConfig()
                w = r.lru_width or d
                total += 2 * d * w + w * d + r.conv_width * w + 3 * w
                total += self._ffn_params()
        return int(total)

    def _ffn_params(self, dense: bool = False) -> int:
        """One layer's feed-forward: a MoE layer holds its experts (those
        of this device's share), the shared experts and the router."""
        d = self.d_model
        mats = 3 if self.ffn_kind == "swiglu" else 2
        if self.moe is not None and not dense:
            e = self.moe
            per = mats * d * e.d_ff_expert
            return (e.n_held + e.n_shared_experts) * per + d * e.n_experts
        return mats * d * self.d_ff

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only top-k experts) -- the N in
        MODEL_FLOPS = 6*N_active*D."""
        if self.moe is None:
            return self.param_count()
        e = self.moe
        total = self.param_count()
        per = (3 if self.ffn_kind == "swiglu" else 2) * self.d_model * e.d_ff_expert
        n_moe_layers = sum(
            1 for i, k in enumerate(self.pattern_layers)
            if k in ("attn", "local_attn", "xattn") and i >= self.first_k_dense
        )
        total -= n_moe_layers * (e.n_held - e.top_k) * per
        return int(total)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    mode: str  # "train" | "prefill" | "decode"

    @property
    def tokens_per_step(self) -> int:
        if self.mode == "decode":
            return self.global_batch  # one new token per sequence
        return self.seq_len * self.global_batch


# The four assigned LM shape cells.
TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")
ALL_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)


def shape_applicable(model: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Spec rule: long_500k needs sub-quadratic attention; decoders run all
    decode shapes. Returns (runs, reason-if-skipped)."""
    if shape.name == "long_500k" and not model.subquadratic:
        return False, "full attention: 500k dense KV decode is the quadratic regime the spec excludes"
    return True, ""


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    grad_clip: float = 1.0
    microbatches: int = 1          # gradient-accumulation chunks per step
    grad_compression: bool = False  # int8 EF on cross-pod gradient hop
    # olmax-style scalar second-moment EMA per leaf, fed by the norm
    # launch's per-leaf sumsq slots: one HBM trip per grad leaf per step
    # (see optim.adamw); must match the init_state that built the opt state
    fused_second_moment: bool = False
    seed: int = 0
