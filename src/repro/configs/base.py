"""Configuration dataclasses for the repro framework.

Every assigned architecture is expressed as an :class:`ArchConfig`; every
assigned input shape as a :class:`ShapeConfig`.  Configs are plain frozen
dataclasses so they can be hashed, compared and embedded in jit static args.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Block kinds used to compose per-layer patterns.
# ---------------------------------------------------------------------------
ATTN_GLOBAL = "attn_global"      # full causal attention
ATTN_LOCAL = "attn_local"        # sliding-window causal attention
ATTN_CROSS = "attn_cross"        # encoder-decoder cross attention (whisper)
MLSTM = "mlstm"                  # xLSTM matrix-memory block (parallel form)
SLSTM = "slstm"                  # xLSTM scalar-memory block (recurrent scan)
RGLRU = "rglru"                  # RG-LRU recurrent block (Griffin/recurrentgemma)
ATTN_MLA = "attn_mla"            # multi-head latent attention (DeepSeek-V2)

FAMILIES = ("dense", "moe", "ssm", "hybrid", "vlm", "audio")


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts sub-config."""

    num_experts: int
    top_k: int
    # load-balance auxiliary loss weight (Switch-style)
    aux_loss_weight: float = 0.01
    # width of one routed expert's FFN; 0 means the config's ``d_ff``
    expert_ff: int = 0
    # shared experts, run as one MLP of ``num_shared_experts * expert width``
    # on every token and added to the routed experts' output (DeepSeek)
    num_shared_experts: int = 0
    # renormalise the chosen top-k gates to sum to 1 (Mixtral) or not (DeepSeek)
    norm_topk_prob: bool = True
    # factor on the routed experts' gates
    routed_scaling_factor: float = 1.0
    # route every token to its top-k experts with no capacity (grouped expert
    # products, no token dropped) on every pass, not only on decode
    dropless: bool = False

    def expert_width(self, d_ff: int) -> int:
        return self.expert_ff or d_ff


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2): keys and values come from a
    compressed latent ``c_kv`` of ``kv_lora_rank``; each head's query and key
    are a ``qk_nope_head_dim`` part without position and a
    ``qk_rope_head_dim`` part under RoPE (one rope key shared by all heads).
    Queries are not compressed (``q_lora_rank`` null)."""

    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN rope scaling (``rope_scaling`` of type ``yarn``)."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """Architecture description.

    ``pattern`` is the repeating unit of block kinds; the full model applies it
    cyclically over ``num_layers`` (e.g. gemma3's 5 local : 1 global uses a
    6-entry pattern).  ``d_ff == 0`` means the block family has no separate MLP
    (xLSTM blocks carry their own up-projection).
    """

    name: str
    family: str                      # one of FAMILIES
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None   # default d_model // num_heads
    pattern: Tuple[str, ...] = (ATTN_GLOBAL,)
    window: int = 0                  # sliding window for ATTN_LOCAL blocks
    moe: Optional[MoEConfig] = None
    qkv_bias: bool = False
    tie_embeddings: bool = False
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    act: str = "silu"                # silu | gelu | geglu (gated handled via gated_mlp)
    gated_mlp: bool = True           # llama-style SwiGLU MLP
    rope_theta: float = 10_000.0
    max_position: int = 131_072
    # encoder-decoder (whisper): number of encoder layers; frontend is stubbed.
    encoder_layers: int = 0
    encoder_frames: int = 1500       # whisper: 30 s audio -> 1500 frames
    # VLM: number of prepended image-patch embedding tokens (frontend stubbed).
    image_tokens: int = 0
    # citation of the source paper / model card for the exact geometry
    citation: str = ""
    # dtype of params/activations for the production dry-run
    dtype: str = "bfloat16"
    # dtype of the residual stream where it differs from ``dtype`` (weights
    # in ``dtype``, activations in this); "" means ``dtype``
    residual_dtype: str = ""
    # latent attention, for ATTN_MLA blocks
    mla: Optional[MLAConfig] = None
    rope_scaling: Optional[YarnScaling] = None
    # leading dense layers (DeepSeek's first_k_dense_replace): attention +
    # a dense MLP of width ``d_ff`` before the MoE layers
    first_dense_layers: int = 0

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.num_heads

    def block_kind(self, layer_idx: int) -> str:
        return self.pattern[layer_idx % len(self.pattern)]

    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(self.block_kind(i) for i in range(self.num_layers))

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + norms)."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        hd = self.resolved_head_dim
        h, kv = self.num_heads, self.num_kv_heads
        total = v * d                         # embedding
        if not self.tie_embeddings:
            total += v * d                    # unembedding
        for i, kind in enumerate(self.layer_kinds()):
            total += self._block_params(kind, d, f, h, kv, hd,
                                        dense=i < self.first_dense_layers)
        total += d                            # final norm
        if self.is_encdec:
            for _ in range(self.encoder_layers):
                total += self._block_params(ATTN_GLOBAL, d, f, h, h, hd)
            total += d
        return total

    def _mla_params(self, d: int, h: int) -> int:
        m = self.mla
        return (d * h * m.qk_head_dim + d * (m.kv_lora_rank + m.qk_rope_head_dim)
                + m.kv_lora_rank                                  # kv_a_layernorm
                + m.kv_lora_rank * h * (m.qk_nope_head_dim + m.v_head_dim)
                + h * m.v_head_dim * d)

    def _block_params(self, kind: str, d: int, f: int, h: int, kv: int, hd: int,
                      dense: bool = False) -> int:
        n = 2 * d  # two norms per block (pre-attn/pre-mlp or equivalents)
        if kind == ATTN_MLA:
            n += self._mla_params(d, h)
        elif kind in (ATTN_GLOBAL, ATTN_LOCAL, ATTN_CROSS):
            n += d * (h * hd) + 2 * d * (kv * hd) + (h * hd) * d
            if self.qkv_bias:
                n += h * hd + 2 * kv * hd
        elif kind == MLSTM:
            # q,k,v,o projections at 2x inner dim + gates
            inner = 2 * d
            n += 3 * d * inner + inner * d + 3 * d
        elif kind == SLSTM:
            inner = d
            n += 4 * d * inner + 4 * inner + inner * d
        elif kind == RGLRU:
            inner = 3 * d // 2  # griffin uses 1.5x expansion
            n += 2 * d * inner + inner * d + 2 * inner + 4 * inner
        if kind != MLSTM and kind != SLSTM and f > 0:
            mats = 3 if self.gated_mlp else 2
            if self.moe is not None and not dense:
                per_expert = mats * d * self.moe.expert_width(f)
                n += self.moe.num_experts * per_expert + d * self.moe.num_experts
                n += self.moe.num_shared_experts * per_expert
            else:
                n += mats * d * f
        return n

    @property
    def num_moe_layers(self) -> int:
        if self.moe is None:
            return 0
        return sum(1 for i, kind in enumerate(self.layer_kinds())
                   if i >= self.first_dense_layers and kind not in (MLSTM, SLSTM))

    def active_param_count(self) -> int:
        """Params touched per token (MoE counts only top-k experts)."""
        if self.moe is None:
            return self.param_count()
        d = self.d_model
        per_expert = (3 if self.gated_mlp else 2) * d * self.moe.expert_width(self.d_ff)
        inactive = (self.moe.num_experts - self.moe.top_k) * per_expert
        return self.param_count() - self.num_moe_layers * inactive


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """Assigned input shape."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


@dataclasses.dataclass(frozen=True)
class FLConfig:
    """Federated-learning hyper-parameters (paper §4.1 defaults)."""

    num_clients: int = 100           # M
    clients_per_round: int = 10      # P
    max_rounds: int = 100            # T
    local_epochs: int = 5
    batch_size: int = 128
    learning_rate: float = 0.1
    explore_decay: float = 0.98      # phi_t = explore_decay ** t
    es_threshold: float = 5.0        # psi (= P/2 recommended)
    dirichlet_alpha: float = 0.1
    seed: int = 0
