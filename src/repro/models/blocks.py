"""Per-layer decoder blocks for every family, shaped for scan-over-layers.

A "block" is (pre-norm -> mixer -> residual -> pre-norm -> FFN/MoE -> residual).
Mixer is GQA/MLA attention or Mamba2 depending on family.  All block params are
plain dicts so a stack of L layers is just the tree-stacked pytree (leading dim
L) consumed by jax.lax.scan in model.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.context import current_ctx
from repro.models import attention as attn
from repro.models import mamba2 as m2
from repro.models import moe as moe_lib
from repro.models.config import ModelConfig
from repro.models.layers import ffn_apply, init_ffn, init_rms_norm, rms_norm


def _moe_sharded(cfg: ModelConfig) -> bool:
    ctx = current_ctx()
    return ctx is not None and cfg.num_experts % ctx.tp == 0


def moe_reads_stacked(cfg: ModelConfig, dispatch_mode: str) -> bool:
    """Whether the MoE layers take the whole stack's expert weights and a
    layer index rather than one layer's slice: under the single-device
    "fused" dispatch, whose expert GEMMs are Pallas calls (moe_gemm)."""
    return cfg.is_moe and dispatch_mode == "fused" and not _moe_sharded(cfg)


def _moe(p, cfg, h, placement, dispatch_mode, stats, interpret):
    """Dispatch to the shard_map expert-parallel path when a shard context is
    active (distributed lowering), else the single-device reference path.
    ``interpret`` runs the "fused" dispatch's Pallas kernels in the
    interpreter (CPU) instead of compiling them."""
    ctx = current_ctx()
    if _moe_sharded(cfg):
        from repro.models.moe_sharded import moe_apply_sharded
        return moe_apply_sharded(p, cfg, h, placement, ctx, stats)
    return moe_lib.moe_apply(p, cfg, h, placement, dispatch_mode, stats,
                             interpret=interpret)


# --- init ---------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, is_moe_layer: bool, mixer: str = "attn") -> dict:
    """mixer: 'attn' | 'mamba'."""
    ks = jax.random.split(key, 4)
    p = {}
    if mixer == "attn":
        p["attn_norm"] = init_rms_norm(cfg.d_model, cfg.adtype)
        p["attn"] = attn.init_attention(ks[0], cfg)
    else:
        p["mamba_norm"] = init_rms_norm(cfg.d_model, cfg.adtype)
        p["mamba"] = m2.init_mamba2(ks[0], cfg)
        return p  # mamba2 blocks have no separate FFN
    p["ffn_norm"] = init_rms_norm(cfg.d_model, cfg.adtype)
    if is_moe_layer:
        p["moe"] = moe_lib.init_moe(ks[1], cfg)
    else:
        p["ffn"] = init_ffn(ks[1], cfg.d_model, cfg.d_ff, cfg.adtype)
    return p


def init_cross_block(key, cfg: ModelConfig) -> dict:
    """Whisper decoder block: self-attn + cross-attn + FFN."""
    ks = jax.random.split(key, 3)
    return {
        "attn_norm": init_rms_norm(cfg.d_model, cfg.adtype),
        "attn": attn.init_gqa(ks[0], cfg),
        "cross_norm": init_rms_norm(cfg.d_model, cfg.adtype),
        "cross": attn.init_gqa(ks[1], cfg),
        "ffn_norm": init_rms_norm(cfg.d_model, cfg.adtype),
        "ffn": init_ffn(ks[2], cfg.d_model, cfg.d_ff, cfg.adtype),
    }


# --- apply: attention-family block ------------------------------------------------

def attn_block_full(p: dict, cfg: ModelConfig, x, positions, is_local, cache,
                    is_moe_layer: bool, placement, dispatch_mode: str, stats: bool,
                    interpret: bool = False):
    h = rms_norm(x, p["attn_norm"]["scale"], cfg.norm_eps)
    if (cfg.sliding_window > 0 and cfg.local_global_period > 0
            and not isinstance(is_local, bool)):
        # gemma2 baseline: runtime-flagged local vs global under scan computes
        # BOTH and selects; the paired-scan path (model._scan_paired_local_
        # global) passes a STATIC bool instead and skips the double compute
        a_local, c_local = attn.attention_full(p["attn"], cfg, h, positions, True, cache)
        a_glob, c_glob = attn.attention_full(p["attn"], cfg, h, positions, False, cache)
        a = jnp.where(is_local, a_local, a_glob)
        new_cache = jax.tree.map(lambda l, g: jnp.where(is_local, l, g), c_local, c_glob) \
            if cache is not None else None
    else:
        local = is_local if isinstance(is_local, bool) else False
        a, new_cache = attn.attention_full(p["attn"], cfg, h, positions,
                                           local, cache)
    x = x + a

    h = rms_norm(x, p["ffn_norm"]["scale"], cfg.norm_eps)
    aux = {}
    if is_moe_layer:
        y, aux = _moe(p["moe"], cfg, h, placement, dispatch_mode, stats,
                      interpret)
    else:
        y = ffn_apply(p["ffn"], h)
    x = x + y
    return x, new_cache, aux


def attn_block_decode(p: dict, cfg: ModelConfig, x, cache, cache_pos, is_local,
                      is_moe_layer: bool, placement, dispatch_mode: str, stats: bool,
                      mla_absorb: bool = False, interpret: bool = False):
    h = rms_norm(x, p["attn_norm"]["scale"], cfg.norm_eps)
    if (cfg.sliding_window > 0 and cfg.local_global_period > 0
            and not isinstance(is_local, bool)):
        a_local, c_local = attn.attention_decode(p["attn"], cfg, h, cache, cache_pos, True)
        a_glob, c_glob = attn.attention_decode(p["attn"], cfg, h, cache, cache_pos, False)
        a = jnp.where(is_local, a_local, a_glob)
        new_cache = jax.tree.map(lambda l, g: jnp.where(is_local, l, g), c_local, c_glob)
    else:
        local = is_local if isinstance(is_local, bool) else False
        a, new_cache = attn.attention_decode(p["attn"], cfg, h, cache, cache_pos,
                                             local, mla_absorb=mla_absorb)
    x = x + a
    h = rms_norm(x, p["ffn_norm"]["scale"], cfg.norm_eps)
    aux = {}
    if is_moe_layer:
        y, aux = _moe(p["moe"], cfg, h, placement, dispatch_mode, stats,
                      interpret)
    else:
        y = ffn_apply(p["ffn"], h)
    x = x + y
    return x, new_cache, aux


def attn_block_decode_paged(p: dict, cfg: ModelConfig, x, cache, block_tables,
                            lengths, is_local, is_moe_layer: bool, placement,
                            dispatch_mode: str, stats: bool,
                            use_kernel: bool = False, interpret: bool = False):
    """attn_block_decode against one layer's paged KV pool (GQA only;
    PagedKVCache rejects other families up front)."""
    h = rms_norm(x, p["attn_norm"]["scale"], cfg.norm_eps)
    if (cfg.sliding_window > 0 and cfg.local_global_period > 0
            and not isinstance(is_local, bool)):
        a_local, c_local = attn.gqa_decode_paged(p["attn"], cfg, h, cache,
                                                 block_tables, lengths, True,
                                                 use_kernel, interpret)
        a_glob, c_glob = attn.gqa_decode_paged(p["attn"], cfg, h, cache,
                                               block_tables, lengths, False,
                                               use_kernel, interpret)
        a = jnp.where(is_local, a_local, a_glob)
        new_cache = jax.tree.map(lambda l, g: jnp.where(is_local, l, g),
                                 c_local, c_glob)
    else:
        local = is_local if isinstance(is_local, bool) else False
        a, new_cache = attn.gqa_decode_paged(p["attn"], cfg, h, cache,
                                             block_tables, lengths, local,
                                             use_kernel, interpret)
    x = x + a
    h = rms_norm(x, p["ffn_norm"]["scale"], cfg.norm_eps)
    aux = {}
    if is_moe_layer:
        y, aux = _moe(p["moe"], cfg, h, placement, dispatch_mode, stats,
                      interpret)
    else:
        y = ffn_apply(p["ffn"], h)
    x = x + y
    return x, new_cache, aux


# --- apply: mamba block --------------------------------------------------------------

def mamba_block_full(p: dict, cfg: ModelConfig, x, cache):
    h = rms_norm(x, p["mamba_norm"]["scale"], cfg.norm_eps)
    y, new_cache = m2.mamba2_full(p["mamba"], cfg, h, cache)
    return x + y, new_cache


def mamba_block_decode(p: dict, cfg: ModelConfig, x, cache):
    h = rms_norm(x, p["mamba_norm"]["scale"], cfg.norm_eps)
    y, new_cache = m2.mamba2_decode(p["mamba"], cfg, h, cache)
    return x + y, new_cache


# --- apply: whisper decoder block -----------------------------------------------------

def cross_block_full(p: dict, cfg: ModelConfig, x, positions, memory, cache):
    h = rms_norm(x, p["attn_norm"]["scale"], cfg.norm_eps)
    a, new_cache = attn.gqa_full(p["attn"], cfg, h, positions, False, cache)
    x = x + a
    h = rms_norm(x, p["cross_norm"]["scale"], cfg.norm_eps)
    x = x + attn.cross_attention(p["cross"], cfg, h, memory)
    h = rms_norm(x, p["ffn_norm"]["scale"], cfg.norm_eps)
    return x + ffn_apply(p["ffn"], h), new_cache


def cross_block_decode(p: dict, cfg: ModelConfig, x, cache, cache_pos, memory):
    h = rms_norm(x, p["attn_norm"]["scale"], cfg.norm_eps)
    a, new_cache = attn.gqa_decode(p["attn"], cfg, h, cache, cache_pos, False)
    x = x + a
    h = rms_norm(x, p["cross_norm"]["scale"], cfg.norm_eps)
    x = x + attn.cross_attention(p["cross"], cfg, h, memory)
    h = rms_norm(x, p["ffn_norm"]["scale"], cfg.norm_eps)
    return x + ffn_apply(p["ffn"], h), new_cache


# --- encoder block (whisper, non-causal) ------------------------------------------------

def encoder_block_full(p: dict, cfg: ModelConfig, x, positions):
    h = rms_norm(x, p["attn_norm"]["scale"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wv"])
    a = attn._sdpa_auto(cfg, q, k, v, 0, causal=False)
    x = x + jnp.einsum("bshk,hkd->bsd", a, p["attn"]["wo"])
    h = rms_norm(x, p["ffn_norm"]["scale"], cfg.norm_eps)
    return x + ffn_apply(p["ffn"], h)
