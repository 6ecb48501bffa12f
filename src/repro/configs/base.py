"""Model / run configuration schema.

One ``ModelConfig`` covers all 10 assigned architecture families; the
``family`` tag selects the model class in models/model_zoo.py.  Shapes for
the dry-run cells live in ``ShapeConfig`` (train/prefill/decode/long).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEParams:
    num_experts: int             # experts the router scores
    top_k: int
    d_ff: int                    # per-expert intermediate
    capacity_factor: float = 1.25
    aux_loss_coeff: float = 0.01
    num_shared_experts: int = 0  # one shared SwiGLU of width d_ff * this
    scoring: str = "softmax"     # softmax | sigmoid (DeepSeek-V3)
    routed_scale: float = 1.0    # gates times this (routed_scaling_factor)
    # experts this chip holds, [first_held, first_held + held_experts) of
    # num_experts: the one-chip dropless layer computes only their part of
    # the result.  None keeps every expert here (capacity dispatch / EP).
    held_experts: Optional[int] = None
    first_held: int = 0


@dataclasses.dataclass(frozen=True)
class MLAParams:
    """Multi-head latent attention (DeepSeek-V2/V3): keys and values come
    from a shared latent of ``kv_lora_rank`` plus one rotary key head."""
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int                     # the query projection is full rank


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | xlstm | hybrid | whisper | vlm
    num_layers: int
    d_model: int
    heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None          # default d_model // heads
    qk_norm: bool = False
    rope_theta: float = 10000.0
    tie_embeddings: bool = True
    # gemma3-style local:global attention
    sliding_window: Optional[int] = None    # window for local layers
    global_every: Optional[int] = None      # every Nth layer is global
    rms_norm_eps: float = 1e-6
    # MoE (``moe`` and ``mla`` also take plain dicts of their fields)
    moe: Optional[MoEParams] = None
    first_dense_layers: int = 0  # leading layers with a dense FFN of d_ff
    mla: Optional[MLAParams] = None
    moe_ep_axis: str = "data"    # mesh axis carrying EP all-to-all
    moe_tp: bool = True          # shard expert FFN intermediate over TP
    moe_token_scatter: bool = False  # shard expert queues over TP (M4)
    # qwen2-vl M-RoPE
    mrope_sections: Optional[Tuple[int, int, int]] = None
    # xLSTM
    xlstm_slstm_every: int = 4              # every Nth block is sLSTM
    # zamba2 hybrid
    ssm_state: int = 64
    shared_attn_every: int = 6
    mamba_head_dim: int = 64
    # whisper enc-dec
    enc_layers: int = 0                     # 0 = decoder-only
    max_positions: int = 1 << 20
    # numerics / execution
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    remat: bool = False
    attn_impl: str = "ref"                  # ref | flash

    def __post_init__(self):
        if isinstance(self.moe, dict):
            object.__setattr__(self, "moe", MoEParams(**self.moe))
        if isinstance(self.mla, dict):
            object.__setattr__(self, "mla", MLAParams(**self.mla))

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.heads)

    @property
    def attn_widths(self) -> Tuple[int, int]:
        """(query/key, value) widths of one attention head."""
        if self.mla is not None:
            a = self.mla
            return a.qk_nope_head_dim + a.qk_rope_head_dim, a.v_head_dim
        return self.resolved_head_dim, self.resolved_head_dim

    def _attn_params(self) -> int:
        D, Dh = self.d_model, self.resolved_head_dim
        if self.mla is not None:
            a, H = self.mla, self.heads
            return (D * H * (a.qk_nope_head_dim + a.qk_rope_head_dim)
                    + D * (a.kv_lora_rank + a.qk_rope_head_dim) + a.kv_lora_rank
                    + a.kv_lora_rank * H * (a.qk_nope_head_dim + a.v_head_dim)
                    + H * a.v_head_dim * D)
        return D * Dh * (self.heads * 2 + self.kv_heads * 2)

    def _moe_layer_ffn(self, experts: int) -> int:
        """FFN parameters of one MoE layer with ``experts`` routed experts
        counted: those, the shared expert and the router."""
        D, m = self.d_model, self.moe
        return 3 * D * m.d_ff * (experts + m.num_shared_experts) + D * m.num_experts

    def param_count(self) -> float:
        """Approximate parameter count (for 6ND model FLOPs); an MoE layer
        counts the experts held here."""
        D, L, V = self.d_model, self.num_layers, self.vocab
        attn = self._attn_params()
        if self.family == "xlstm":
            per_layer = 4 * D * D + 2 * D * self.heads
        elif self.family == "hybrid":
            d_inner = 2 * D
            per_layer = D * (2 * d_inner + 2 * self.ssm_state + d_inner // self.mamba_head_dim) + d_inner * D
        else:
            per_layer = attn
        total = L * per_layer + V * D * (1 if self.tie_embeddings else 2)
        if self.moe is not None:
            held = self.moe.held_experts or self.moe.num_experts
            n_dense = self.first_dense_layers
            total += (L - n_dense) * self._moe_layer_ffn(held) + n_dense * 3 * D * self.d_ff
        elif self.family not in ("xlstm",):
            total += L * 3 * D * self.d_ff
        if self.family == "whisper":
            enc = self.enc_layers * (attn + 2 * D * self.d_ff)
            dec_extra = L * attn  # cross attention
            total += enc + dec_extra
        return float(total)

    def active_param_count(self) -> float:
        """MoE: parameters touched per token (6*N_active*D FLOPs rule)."""
        if self.moe is None:
            return self.param_count()
        held = self.moe.held_experts or self.moe.num_experts
        moe_layers = self.num_layers - self.first_dense_layers
        return float(self.param_count() + moe_layers * (
            self._moe_layer_ffn(self.moe.top_k) - self._moe_layer_ffn(held)))


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str                    # train_4k | prefill_32k | decode_32k | long_500k
    kind: str                    # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Execution-level knobs consumed by launch/train/dry-run."""

    model: ModelConfig
    shape: ShapeConfig
    # parallelism mapping (logical axis sizes implied by the mesh)
    dp_schedule: str = "hierarchical"   # flat | hierarchical | ring2d | compressed
    microbatches: int = 1
    remat: bool = True
    fsdp: bool = True
