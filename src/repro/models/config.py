"""Model configuration covering every assigned architecture family.

One dataclass describes dense / MoE / SSM / hybrid / enc-dec / VLM backbones.
Family-specific fields default to "off" so each config file only sets what it
uses.  All configs are frozen + hashable so they can be closed over by jitted
functions safely.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN rope scaling as DeepSeek-V2 publishes it (``rope_scaling`` with
    ``type: yarn``): frequencies interpolated by ``factor`` past the
    correction range, extrapolated below it, and the softmax scale of the
    attention multiplied by ``mscale(factor, mscale_all_dim)**2``."""
    factor: float = 1.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """DeepSeek-V2's ``yarn_get_mscale``."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    # --- identity -----------------------------------------------------------
    name: str = "model"
    family: str = "dense"  # dense | moe | ssm | hybrid | vlm | audio

    # --- core transformer dims ----------------------------------------------
    num_layers: int = 2
    d_model: int = 128
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 0          # 0 -> d_model // num_heads
    d_ff: int = 512            # dense FFN width (for MoE archs: width of any dense layers)
    vocab_size: int = 1000
    norm_eps: float = 1e-6
    rope_theta: float = 10_000.0
    rope_scaling: Optional[YarnScaling] = None   # None: plain rope
    rope_interleave: bool = False      # rotate (x[2i], x[2i+1]) pairs (deepseek-v2)
    tie_embeddings: bool = False

    # --- attention variants ---------------------------------------------------
    attention_type: str = "gqa"        # gqa | mla | none
    qkv_bias: bool = False             # qwen2
    attn_logit_softcap: float = 0.0    # gemma2 (0 = off)
    final_logit_softcap: float = 0.0   # gemma2 (0 = off)
    sliding_window: int = 0            # window size for local layers (0 = off)
    local_global_period: int = 0       # gemma2: layer i is local iff i % period != period-1

    # --- MLA (deepseek-v2) ----------------------------------------------------
    q_lora_rank: int = 0               # 0 -> no q compression
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- MoE -------------------------------------------------------------------
    num_experts: int = 0               # routed experts this model holds (their weights)
    n_routed_experts: int = 0          # the router's width; 0 -> num_experts.  A
                                       # larger router holds a share: experts
                                       # 0..num_experts-1 (the rest are elsewhere)
    num_shared_experts: int = 0
    moe_top_k: int = 0
    n_group: int = 1                   # group-limited routing: experts in n_group
    topk_group: int = 1                # groups, top-k only inside the best topk_group
    routed_scaling_factor: float = 1.0  # gates x this (deepseek-v2: 16)
    norm_topk_prob: bool = True        # renormalise the top-k gates to sum 1
    moe_d_ff: int = 0                  # per-expert FFN width
    first_k_dense: int = 0             # leading layers that use a dense FFN instead
    moe_every: int = 1                 # layer i is MoE iff i >= first_k_dense and i % moe_every == 0
    capacity_factor: float = 1.25      # train-time dispatch capacity
    router_aux_coef: float = 0.01      # load-balance aux loss
    router_z_coef: float = 1e-3

    # --- SSM (mamba2 / SSD) -----------------------------------------------------
    ssm_state: int = 0                 # N (dstate); 0 = no ssm
    ssm_expand: int = 2
    ssm_head_dim: int = 64             # P
    ssm_conv: int = 4
    ssm_chunk: int = 256               # SSD chunk length

    # --- hybrid (zamba2) ---------------------------------------------------------
    shared_attn_every: int = 0         # apply the shared attention block every k ssm layers (0 = off)

    # --- encoder-decoder (whisper) -------------------------------------------------
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    encoder_len: int = 1500            # fixed encoder memory length for decode shapes

    # --- VLM (internvl) -------------------------------------------------------------
    vision_prefix_len: int = 0         # stub patch-embedding prefix length

    # --- numerics ----------------------------------------------------------------------
    dtype: str = "bfloat16"            # activations/weights dtype for lowering
    remat: bool = False                # activation checkpointing for train_step
    remat_policy: str = "none"         # none | dots | full (see training/train_step.py)

    # -----------------------------------------------------------------------------------
    def __post_init__(self):
        if self.head_dim == 0 and self.num_heads:
            object.__setattr__(self, "head_dim", self.d_model // max(self.num_heads, 1))

    # ---- derived helpers ---------------------------------------------------------------
    @property
    def adtype(self):
        return jnp.dtype(self.dtype)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_ssm(self) -> bool:
        return self.ssm_state > 0 and self.attention_type == "none"

    @property
    def is_hybrid(self) -> bool:
        return self.ssm_state > 0 and self.shared_attn_every > 0

    @property
    def router_width(self) -> int:
        """Routed experts the router scores (the published count)."""
        return self.n_routed_experts or self.num_experts

    @property
    def holds_share(self) -> bool:
        """Whether the model holds only a share of its routed experts."""
        return self.is_moe and self.router_width > self.num_experts

    @property
    def attn_softmax_scale(self) -> float:
        """The attention's softmax scale: q_head_dim**-0.5, times
        mscale(factor, mscale_all_dim)**2 under YaRN (deepseek-v2)."""
        scale = self.q_head_dim ** -0.5
        ys = self.rope_scaling
        if ys is not None and ys.mscale_all_dim:
            m = yarn_mscale(ys.factor, ys.mscale_all_dim)
            scale = scale * m * m
        return scale

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.ssm_d_inner // self.ssm_head_dim

    def layer_is_moe(self, i: int) -> bool:
        if not self.is_moe:
            return False
        return i >= self.first_k_dense and (i - self.first_k_dense) % self.moe_every == 0

    def num_moe_layers(self) -> int:
        """Scanned MoE layers — the leading dim of the placement stack."""
        return sum(self.layer_is_moe(i) for i in range(self.num_layers))

    def layer_is_local(self, i: int) -> bool:
        """gemma2-style alternation: with period p, layers i % p != p-1 are local."""
        if self.local_global_period <= 0 or self.sliding_window <= 0:
            return False
        return i % self.local_global_period != self.local_global_period - 1

    @property
    def q_head_dim(self) -> int:
        """Per-head query dim (MLA splits into nope+rope)."""
        if self.attention_type == "mla":
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_dim

    @property
    def o_head_dim(self) -> int:
        if self.attention_type == "mla":
            return self.v_head_dim
        return self.head_dim

    def kv_bytes_per_token(self) -> int:
        """Per-token KV-cache (or SSM-state-equivalent) bytes — the unified
        'KV usage' signal Gimbal's engine-level balancer consumes (Alg. 1)."""
        itemsize = jnp.dtype(self.dtype).itemsize
        n_attn = self.num_attention_layers()
        if self.attention_type == "mla":
            per_layer = self.kv_lora_rank + self.qk_rope_head_dim
        else:
            per_layer = 2 * self.num_kv_heads * self.head_dim
        return n_attn * per_layer * itemsize

    def num_attention_layers(self) -> int:
        if self.attention_type == "none":
            return 0
        if self.is_hybrid:
            return self.num_layers // max(self.shared_attn_every, 1)
        return self.num_layers

    def active_params(self) -> int:
        """Approximate activated parameter count (per token)."""
        return _param_count(self, active_only=True)

    def total_params(self) -> int:
        return _param_count(self, active_only=False)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


def _param_count(cfg: ModelConfig, active_only: bool) -> int:
    d = cfg.d_model
    emb = cfg.vocab_size * d * (1 if cfg.tie_embeddings else 2)
    per_layer = 0
    # attention
    if cfg.attention_type == "mla":
        q_in = cfg.q_lora_rank if cfg.q_lora_rank else d
        per_layer += (d * cfg.q_lora_rank if cfg.q_lora_rank else 0)
        per_layer += q_in * cfg.num_heads * cfg.q_head_dim
        per_layer += d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        per_layer += cfg.kv_lora_rank * cfg.num_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)
        per_layer += cfg.num_heads * cfg.v_head_dim * d
    elif cfg.attention_type == "gqa":
        per_layer += d * cfg.num_heads * cfg.head_dim          # Q
        per_layer += 2 * d * cfg.num_kv_heads * cfg.head_dim   # K,V
        per_layer += cfg.num_heads * cfg.head_dim * d          # O
    # ffn / experts
    ffn_dense = 3 * d * cfg.d_ff  # gated (swiglu)
    if cfg.is_moe:
        expert = 3 * d * cfg.moe_d_ff
        n_moe = sum(cfg.layer_is_moe(i) for i in range(cfg.num_layers))
        n_dense = cfg.num_layers - n_moe
        shared = cfg.num_shared_experts * expert
        if active_only:
            moe_part = n_moe * (cfg.moe_top_k * expert + shared)
        else:
            moe_part = n_moe * (cfg.num_experts * expert + shared)
        total_layers = moe_part + n_dense * ffn_dense + cfg.num_layers * per_layer
    elif cfg.is_ssm or cfg.is_hybrid:
        di, nh, ns = cfg.ssm_d_inner, cfg.ssm_heads, cfg.ssm_state
        ssm = d * (2 * di + 2 * ns + nh) + di * d + cfg.ssm_conv * (di + 2 * ns)
        total_layers = cfg.num_layers * ssm
        if cfg.is_hybrid:
            shared_blk = per_layer + ffn_dense
            total_layers += shared_blk  # weights shared across invocations
    else:
        total_layers = cfg.num_layers * (per_layer + ffn_dense)
    if cfg.is_encoder_decoder:
        # encoder self-attn + ffn, decoder cross-attn
        enc = cfg.num_encoder_layers * (per_layer + ffn_dense)
        cross = cfg.num_layers * per_layer
        total_layers += enc + cross
    return int(emb + total_layers)


# Input shape cells assigned to every architecture -------------------------------
@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPE_CELLS: Tuple[ShapeCell, ...] = (
    ShapeCell("train_4k", 4_096, 256, "train"),
    ShapeCell("prefill_32k", 32_768, 32, "prefill"),
    ShapeCell("decode_32k", 32_768, 128, "decode"),
    ShapeCell("long_500k", 524_288, 1, "decode"),
)

# Archs for which long_500k is runnable (sub-quadratic sequence handling).
LONG_CONTEXT_ARCHS = ("mamba2-370m", "zamba2-1.2b")


def cell_applicable(cfg: ModelConfig, cell: ShapeCell) -> Tuple[bool, str]:
    """Whether a shape cell applies to an arch, with the reason if not."""
    if cell.name == "long_500k" and cfg.name not in LONG_CONTEXT_ARCHS:
        return False, "full-attention KV at 524288 is quadratic-family; skipped per spec"
    return True, ""
