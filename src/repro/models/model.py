"""Unified language-model definition: init / train-forward / prefill / decode
for every assigned family (dense, MoE, SSM, hybrid, enc-dec, VLM).

All heavy stacks use jax.lax.scan over tree-stacked layer params so the HLO
stays one-layer-sized regardless of depth (MaxText-style), which keeps the
40-cell multi-pod dry-run compilable.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.context import current_ctx, divides
from repro.models import blocks as B
from repro.models.config import ModelConfig
from repro.models.layers import embed_apply, init_embed, init_rms_norm, rms_norm, unembed_apply
from repro.models.mamba2 import init_mamba2_cache
from repro.models.moe import ExpertPlacement


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


# =============================================================================
# init
# =============================================================================

def init_params(key: jax.Array, cfg: ModelConfig) -> Dict[str, Any]:
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": init_embed(keys[0], cfg.vocab_size, cfg.d_model, cfg.adtype, cfg.tie_embeddings),
        "final_norm": init_rms_norm(cfg.d_model, cfg.adtype),
    }
    lkeys = jax.random.split(keys[1], max(cfg.num_layers, 1))

    if cfg.is_encoder_decoder:
        ekeys = jax.random.split(keys[2], cfg.num_encoder_layers)
        params["enc_blocks"] = _stack([B.init_block(k, cfg, False, "attn") for k in ekeys])
        params["enc_final_norm"] = init_rms_norm(cfg.d_model, cfg.adtype)
        params["blocks"] = _stack([B.init_cross_block(k, cfg) for k in lkeys])
        return params

    if cfg.is_hybrid:
        k_in = cfg.shared_attn_every
        n_super = cfg.num_layers // k_in
        n_epi = cfg.num_layers % k_in
        params["shared_attn"] = B.init_block(keys[3], cfg, False, "attn")
        skeys = jax.random.split(keys[4], n_super)
        params["blocks"] = _stack([
            _stack([B.init_block(kk, cfg, False, "mamba")
                    for kk in jax.random.split(k, k_in)]) for k in skeys])
        if n_epi:
            params["epi_blocks"] = _stack([
                B.init_block(k, cfg, False, "mamba")
                for k in jax.random.split(keys[5], n_epi)])
        return params

    if cfg.is_ssm:
        params["blocks"] = _stack([B.init_block(k, cfg, False, "mamba") for k in lkeys])
        return params

    # attention families (dense / moe / vlm backbone)
    n_pro = cfg.first_k_dense if cfg.is_moe else 0
    if n_pro:
        params["prologue"] = [B.init_block(lkeys[i], cfg, False, "attn") for i in range(n_pro)]
    if cfg.is_moe and cfg.moe_every > 1:
        # interleaved MoE (llama4): scan over super-blocks of
        # [1 MoE layer + (moe_every-1) dense layers]
        me = cfg.moe_every
        n_super = (cfg.num_layers - n_pro) // me
        assert (cfg.num_layers - n_pro) % me == 0, "layers must group evenly"
        moe_b, dense_b = [], []
        for si in range(n_super):
            base = n_pro + si * me
            moe_b.append(B.init_block(lkeys[base], cfg, True, "attn"))
            dense_b.append(_stack([B.init_block(lkeys[base + j], cfg, False, "attn")
                                   for j in range(1, me)]))
        params["blocks"] = {"moe": _stack(moe_b), "dense": _stack(dense_b)}
        return params
    scanned = [B.init_block(lkeys[i], cfg, cfg.layer_is_moe(i), "attn")
               for i in range(n_pro, cfg.num_layers)]
    params["blocks"] = _stack(scanned)
    return params


def abstract_params(cfg: ModelConfig):
    """Parameter ShapeDtypeStructs without allocating anything (dry-run path)."""
    return jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))


def local_flags(cfg: ModelConfig) -> jax.Array:
    """(L_scan,) bool — gemma2 local/global alternation for the scanned stack."""
    n_pro = cfg.first_k_dense if cfg.is_moe else 0
    return jnp.asarray([cfg.layer_is_local(i) for i in range(n_pro, cfg.num_layers)], bool)


# =============================================================================
# caches
# =============================================================================

def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None) -> Dict[str, Any]:
    dt = dtype or cfg.adtype
    if cfg.is_encoder_decoder:
        kv = {
            "k": jnp.zeros((cfg.num_layers, batch, max_seq, cfg.num_kv_heads, cfg.head_dim), dt),
            "v": jnp.zeros((cfg.num_layers, batch, max_seq, cfg.num_kv_heads, cfg.head_dim), dt),
        }
        return {"layers": kv,
                "memory": jnp.zeros((batch, cfg.encoder_len, cfg.d_model), dt)}
    if cfg.is_hybrid:
        k_in = cfg.shared_attn_every
        n_super = cfg.num_layers // k_in
        n_epi = cfg.num_layers % k_in
        def mstack(n, inner=None):
            c = init_mamba2_cache(cfg, batch, dt)
            shape = (n,) if inner is None else (n, inner)
            return jax.tree.map(lambda x: jnp.zeros(shape + x.shape, x.dtype), c)
        cache = {
            "super_attn": {
                "k": jnp.zeros((n_super, batch, max_seq, cfg.num_kv_heads, cfg.head_dim), dt),
                "v": jnp.zeros((n_super, batch, max_seq, cfg.num_kv_heads, cfg.head_dim), dt),
            },
            "super_mamba": mstack(n_super, k_in),
        }
        if n_epi:
            cache["epi"] = mstack(n_epi)
        return cache
    if cfg.is_ssm:
        c = init_mamba2_cache(cfg, batch, dt)
        return {"layers": jax.tree.map(
            lambda x: jnp.zeros((cfg.num_layers,) + x.shape, x.dtype), c)}
    # attention families
    if cfg.attention_type == "mla":
        per = {"ckv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dt),
               "krope": jnp.zeros((batch, max_seq, cfg.qk_rope_head_dim), dt)}
    else:
        per = {"k": jnp.zeros((batch, max_seq, cfg.num_kv_heads, cfg.head_dim), dt),
               "v": jnp.zeros((batch, max_seq, cfg.num_kv_heads, cfg.head_dim), dt)}
    n_pro = cfg.first_k_dense if cfg.is_moe else 0
    n_scan = cfg.num_layers - n_pro
    if cfg.is_moe and cfg.moe_every > 1:
        me = cfg.moe_every
        n_super = n_scan // me
        layers = {
            "moe": jax.tree.map(lambda x: jnp.zeros((n_super,) + x.shape, x.dtype), per),
            "dense": jax.tree.map(lambda x: jnp.zeros((n_super, me - 1) + x.shape,
                                                      x.dtype), per),
        }
    else:
        layers = jax.tree.map(lambda x: jnp.zeros((n_scan,) + x.shape, x.dtype), per)
    cache: Dict[str, Any] = {"layers": layers}
    if n_pro:
        cache["prologue"] = [jax.tree.map(jnp.copy, per) for _ in range(n_pro)]
    return cache


# =============================================================================
# forward passes
# =============================================================================

def _placement_stack(cfg: ModelConfig, placements) -> Optional[jax.Array]:
    """placements: None | (L_scan, S) int32 slot-map array, S = E + R
    (slot -> logical expert; S == E is the unreplicated permutation case)."""
    if placements is None or not cfg.is_moe:
        return None
    return jnp.asarray(placements, jnp.int32)


def _hoist_expert_weights(cfg: ModelConfig, blocks, dispatch_mode: str):
    """Take the (L, E, D, F) expert weights out of a homogeneous scan's
    operands where Pallas calls consume them (blocks.moe_reads_stacked).
    XLA fuses a scan's dynamic slice into its own ops but copies one that
    feeds a custom call: 3 x E x D x F per layer and step.  Returns (the
    blocks left to scan, the stacked weights, the layer indices to scan),
    the last two None where nothing is hoisted; ``_with_layer`` puts them
    back inside the body."""
    if not B.moe_reads_stacked(cfg, dispatch_mode):
        return blocks, None, None
    moe = dict(blocks["moe"])
    stacked = {n: moe.pop(n) for n in ("w_gate", "w_up", "w_down")}
    layers = jnp.arange(stacked["w_gate"].shape[0], dtype=jnp.int32)
    return {**blocks, "moe": moe}, stacked, layers


def _with_layer(p, stacked, layer):
    """One scan step's block params with the hoisted expert weights (closed
    over, loop-invariant) and the index of the layer moe_gemm reads."""
    if stacked is None:
        return p
    return {**p, "moe": {**p["moe"], **stacked, "layer": layer}}


def _unroll() -> int:
    ctx = current_ctx()
    return max(int(ctx.unroll), 1) if ctx is not None else 1


def _seq_constraint(x: jax.Array) -> jax.Array:
    """Sequence-parallel residual stream (Megatron SP, GSPMD-derived): between
    blocks the (B, S, d) activations are sharded over the model axis on S, so
    per-layer saved residuals shrink by the TP degree.  GSPMD inserts the
    all-gather (into attention/FFN) / reduce-scatter (out) pairs."""
    ctx = current_ctx()
    if ctx is None or not ctx.seq_parallel or x.ndim != 3 or x.shape[1] == 1:
        return x
    if not divides(x.shape[1], ctx.tp):
        return x
    bdim = 1
    for a in ctx.batch_axes:
        bdim *= int(ctx.mesh.shape[a])
    b_ax = ctx.batch_axes if divides(x.shape[0], bdim) else None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, P(b_ax, ctx.model_axis, None)))


def _scan_attn_stack(params, cfg: ModelConfig, x, positions, cache, cache_pos,
                     placements, dispatch_mode, stats, decode: bool,
                     mla_absorb: bool = False, interpret: bool = False):
    """Scan over the attention-family stack (homogeneous, or interleaved-MoE
    super-blocks for moe_every > 1)."""
    if cfg.is_moe and cfg.moe_every > 1:
        return _scan_interleaved(params, cfg, x, positions, cache, cache_pos,
                                 placements, dispatch_mode, stats, decode,
                                 mla_absorb, interpret)
    ctx = current_ctx()
    if (ctx is not None and ctx.paired_lg and cfg.local_global_period == 2
            and cfg.sliding_window > 0 and not cfg.is_moe
            and cfg.num_layers % 2 == 0):
        return _scan_paired_local_global(params, cfg, x, positions, cache,
                                         cache_pos, decode)
    flags = local_flags(cfg)
    is_moe = cfg.is_moe  # scanned stack is homogeneous (prologue handled outside)
    pstack = _placement_stack(cfg, placements)
    blocks, stacked, layers = _hoist_expert_weights(cfg, params["blocks"],
                                                    dispatch_mode)

    def body(x, xs):
        p, c, flag, inv, layer = xs
        p = _with_layer(p, stacked, layer)
        plc = (ExpertPlacement.from_slot_map(inv, cfg.num_experts)
               if inv is not None else None)
        if decode:
            x, newc, aux = B.attn_block_decode(p, cfg, x, c, cache_pos, flag, is_moe,
                                               plc, dispatch_mode, stats, mla_absorb,
                                               interpret)
        else:
            x, newc, aux = B.attn_block_full(p, cfg, x, positions, flag, c, is_moe,
                                             plc, dispatch_mode, stats, interpret)
        return _seq_constraint(x), (newc, aux)

    if cfg.remat:
        body = jax.checkpoint(body, policy=_remat_policy(cfg))
    x, (new_cache, auxs) = jax.lax.scan(body, x, (blocks, cache, flags, pstack, layers),
                                        unroll=_unroll())
    return x, new_cache, auxs


def _scan_paired_local_global(params, cfg: ModelConfig, x, positions, cache,
                              cache_pos, decode: bool):
    """gemma2 SSPerf optimization: the baseline scans single layers with a
    runtime local/global flag, which computes BOTH attention variants and
    selects (2x attention compute + bytes).  Period-2 alternation lets us scan
    (local, global) PAIRS with STATIC flags — each attention computed once.
    Numerics identical (tests/test_perf_opts.py)."""
    pair = lambda t: jax.tree.map(
        lambda a: a.reshape((a.shape[0] // 2, 2) + a.shape[1:]), t)
    blocks2 = pair(params["blocks"])
    cache2 = pair(cache) if cache is not None else None

    def one(p, x, c, local_flag):
        if decode:
            return B.attn_block_decode(p, cfg, x, c, cache_pos, local_flag,
                                       False, None, "dense", False)
        return B.attn_block_full(p, cfg, x, positions, local_flag, c,
                                 False, None, "dense", False)

    def body(x, xs):
        p2, c2 = xs
        sub = lambda t, i: jax.tree.map(lambda a: a[i], t)
        x, c_l, _ = one(sub(p2, 0), x, sub(c2, 0) if c2 is not None else None, True)
        x = _seq_constraint(x)
        x, c_g, _ = one(sub(p2, 1), x, sub(c2, 1) if c2 is not None else None, False)
        newc = jax.tree.map(lambda a, b2: jnp.stack([a, b2]), c_l, c_g) \
            if c2 is not None else None
        return _seq_constraint(x), (newc, {})

    x, (new_cache2, _) = jax.lax.scan(body, x, (blocks2, cache2),
                                      unroll=_unroll())
    new_cache = None
    if cache is not None:
        unpair = lambda t: jax.tree.map(
            lambda a: a.reshape((a.shape[0] * 2,) + a.shape[2:]), t)
        new_cache = unpair(new_cache2)
    return x, new_cache, {}


def _scan_interleaved(params, cfg: ModelConfig, x, positions, cache, cache_pos,
                      placements, dispatch_mode, stats, decode: bool,
                      mla_absorb: bool = False, interpret: bool = False):
    """llama4-style interleaved MoE: scan over super-blocks of
    [1 MoE layer + (moe_every-1) dense layers]."""
    pstack = _placement_stack(cfg, placements)   # (n_super, S) or None

    def apply_block(p, x, c, is_moe_layer):
        if decode:
            return B.attn_block_decode(p, cfg, x, c, cache_pos, False,
                                       is_moe_layer, apply_block.plc,
                                       dispatch_mode, stats and is_moe_layer,
                                       mla_absorb, interpret)
        return B.attn_block_full(p, cfg, x, positions, False, c, is_moe_layer,
                                 apply_block.plc, dispatch_mode,
                                 stats and is_moe_layer, interpret)

    def super_body(x, xs):
        pm, pd, cm, cd, inv = xs
        apply_block.plc = (ExpertPlacement.from_slot_map(inv, cfg.num_experts)
                           if inv is not None else None)
        x, new_cm, aux = apply_block(pm, x, cm, True)
        x = _seq_constraint(x)

        def inner(x, ys):
            p, c = ys
            x, newc, _ = apply_block(p, x, c, False)
            return _seq_constraint(x), newc

        x, new_cd = jax.lax.scan(inner, x, (pd, cd), unroll=_unroll())
        return x, ((new_cm, new_cd), aux)

    if cfg.remat:
        super_body = jax.checkpoint(super_body, policy=_remat_policy(cfg))
    cm = cache["moe"] if cache is not None else None
    cd = cache["dense"] if cache is not None else None
    x, (new_caches, auxs) = jax.lax.scan(
        super_body, x, (params["blocks"]["moe"], params["blocks"]["dense"],
                        cm, cd, pstack), unroll=_unroll())
    new_cache = None
    if cache is not None:
        new_cache = {"moe": new_caches[0], "dense": new_caches[1]}
    return x, new_cache, auxs


def _remat_policy(cfg: ModelConfig):
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    if cfg.remat_policy == "none":
        return jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint_policies.everything_saveable


def _agg_aux(auxs: dict) -> dict:
    out = {}
    for k, v in (auxs or {}).items():
        if k in ("load_balance_loss", "router_z_loss"):
            out[k] = jnp.sum(v)
        else:
            out[k] = v  # stacked per-layer stats (L, ...)
    return out


def forward(params, cfg: ModelConfig, tokens: Optional[jax.Array] = None, *,
            cache=None, cache_pos=None, decode: bool = False,
            vision_embeds=None, frames=None,
            placements=None, dispatch_mode: str = "dense", stats: bool = False,
            mla_absorb: bool = False, interpret: bool = False):
    """One entry point for train-forward (cache=None), prefill (cache given,
    full seq) and decode (decode=True, one token).  ``interpret`` runs the
    Pallas kernels of dispatch_mode="fused" in the interpreter (CPU).

    Returns (logits, new_cache, aux).  logits: (B, S, V) fp32.
    """
    # ---- input embedding -----------------------------------------------------
    if cfg.is_encoder_decoder:
        return _forward_encdec(params, cfg, tokens, frames, cache, cache_pos, decode)

    x = embed_apply(params["embed"], tokens)
    if cfg.family == "vlm" and vision_embeds is not None and not decode:
        x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    if decode:
        positions = cache_pos[:, None]
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    aux: dict = {}
    # ---- mixer stacks ----------------------------------------------------------
    if cfg.is_hybrid:
        x, new_cache = _hybrid_stack(params, cfg, x, positions, cache, cache_pos, decode)
    elif cfg.is_ssm:
        def body(x, xs):
            p, c = xs
            if decode:
                x, newc = B.mamba_block_decode(p, cfg, x, c)
            else:
                x, newc = B.mamba_block_full(p, cfg, x, c)
            return _seq_constraint(x), newc
        if cfg.remat:
            body = jax.checkpoint(body, policy=_remat_policy(cfg))
        x, new_layer_cache = jax.lax.scan(
            body, x, (params["blocks"], cache["layers"] if cache else None),
            unroll=_unroll())
        new_cache = {"layers": new_layer_cache} if cache is not None else None
    else:
        # attention families: optional dense prologue then the scanned stack
        pro_caches = []
        n_pro = cfg.first_k_dense if cfg.is_moe else 0
        for i in range(n_pro):
            c = cache["prologue"][i] if cache is not None else None
            if decode:
                x, newc, _ = B.attn_block_decode(params["prologue"][i], cfg, x, c,
                                                 cache_pos, False, False, None,
                                                 dispatch_mode, False, mla_absorb,
                                                 interpret)
            else:
                x, newc, _ = B.attn_block_full(params["prologue"][i], cfg, x, positions,
                                               False, c, False, None, dispatch_mode, False,
                                               interpret)
            pro_caches.append(newc)
        layer_cache = cache["layers"] if cache is not None else None
        x, new_layer_cache, auxs = _scan_attn_stack(
            params, cfg, x, positions, layer_cache, cache_pos,
            placements, dispatch_mode, stats, decode, mla_absorb, interpret)
        aux = _agg_aux(auxs)
        new_cache = None
        if cache is not None:
            new_cache = {"layers": new_layer_cache}
            if n_pro:
                new_cache["prologue"] = pro_caches

    # ---- head ---------------------------------------------------------------------
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    unemb = params["embed"] if cfg.tie_embeddings else params["embed"]
    w = unemb["embedding"] if cfg.tie_embeddings else unemb["unembedding"]
    logits = unembed_apply({"unembedding": w}, x, cfg.final_logit_softcap)
    return logits, new_cache, aux


def _hybrid_stack(params, cfg: ModelConfig, x, positions, cache, cache_pos, decode):
    """zamba2: super-blocks of [shared-attn + k mamba layers], plus epilogue."""
    shared_p = params["shared_attn"]

    def super_body(x, xs):
        sp, attn_c, mamba_c = xs
        # shared attention block (weights closed over -> identical every call)
        if decode:
            x, new_attn_c, _ = B.attn_block_decode(shared_p, cfg, x, attn_c, cache_pos,
                                                   False, False, None, "dense", False)
        else:
            x, new_attn_c, _ = B.attn_block_full(shared_p, cfg, x, positions, False,
                                                 attn_c, False, None, "dense", False)

        def inner(x, ys):
            p, c = ys
            if decode:
                x, newc = B.mamba_block_decode(p, cfg, x, c)
            else:
                x, newc = B.mamba_block_full(p, cfg, x, c)
            return x, newc
        x, new_mamba_c = jax.lax.scan(inner, x, (sp, mamba_c), unroll=_unroll())
        return _seq_constraint(x), (new_attn_c, new_mamba_c)

    sup_attn_c = cache["super_attn"] if cache is not None else None
    sup_mamba_c = cache["super_mamba"] if cache is not None else None
    x, (new_attn_c, new_mamba_c) = jax.lax.scan(
        super_body, x, (params["blocks"], sup_attn_c, sup_mamba_c),
        unroll=_unroll())

    new_cache = None
    new_epi = None
    if "epi_blocks" in params:
        def epi(x, ys):
            p, c = ys
            if decode:
                x, newc = B.mamba_block_decode(p, cfg, x, c)
            else:
                x, newc = B.mamba_block_full(p, cfg, x, c)
            return x, newc
        x, new_epi = jax.lax.scan(epi, x, (params["epi_blocks"],
                                           cache["epi"] if cache is not None else None),
                                  unroll=_unroll())
    if cache is not None:
        new_cache = {"super_attn": new_attn_c, "super_mamba": new_mamba_c}
        if new_epi is not None:
            new_cache["epi"] = new_epi
    return x, new_cache


def _forward_encdec(params, cfg: ModelConfig, tokens, frames, cache, cache_pos, decode):
    """whisper: encoder over stub frame embeddings, decoder with cross-attn."""
    if decode:
        memory = cache["memory"]
    else:
        # encode
        def ebody(x, p):
            return _seq_constraint(B.encoder_block_full(p, cfg, x, None)), None
        enc_x, _ = jax.lax.scan(ebody, frames.astype(cfg.adtype), params["enc_blocks"],
                                unroll=_unroll())
        memory = rms_norm(enc_x, params["enc_final_norm"]["scale"], cfg.norm_eps)

    x = embed_apply(params["embed"], tokens)
    b, s, _ = x.shape
    positions = cache_pos[:, None] if decode else jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    def dbody(x, xs):
        p, c = xs
        if decode:
            x, newc = B.cross_block_decode(p, cfg, x, c, cache_pos, memory)
        else:
            x, newc = B.cross_block_full(p, cfg, x, positions, memory, c)
        return _seq_constraint(x), newc
    layer_cache = cache["layers"] if cache is not None else None
    x, new_layer_cache = jax.lax.scan(dbody, x, (params["blocks"], layer_cache),
                                      unroll=_unroll())

    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    w = params["embed"]["embedding"] if cfg.tie_embeddings else params["embed"]["unembedding"]
    logits = unembed_apply({"unembedding": w}, x, cfg.final_logit_softcap)
    new_cache = None
    if cache is not None:
        new_cache = {"layers": new_layer_cache, "memory": memory}
    return logits, new_cache, {}


# =============================================================================
# public convenience wrappers
# =============================================================================

def forward_train(params, cfg: ModelConfig, tokens, **kw):
    logits, _, aux = forward(params, cfg, tokens, cache=None, decode=False, **kw)
    return logits, aux


def prefill(params, cfg: ModelConfig, tokens, cache, **kw):
    logits, new_cache, aux = forward(params, cfg, tokens, cache=cache, decode=False, **kw)
    return logits, new_cache, aux


def decode_step(params, cfg: ModelConfig, token, cache, cache_pos, **kw):
    """token: (B, 1) int32; cache_pos: (B,) next write position per row."""
    logits, new_cache, aux = forward(params, cfg, token, cache=cache,
                                     cache_pos=cache_pos, decode=True, **kw)
    return logits[:, -1], new_cache, aux


def decode_step_paged(params, cfg: ModelConfig, token, pages, block_tables,
                      lengths, *, placements=None, dispatch_mode: str = "dense",
                      stats: bool = False, use_kernel: bool = False,
                      interpret: bool = False):
    """One decode step against a paged KV pool (serving/kvcache.PagedKVCache).

    token: (B, 1) int32; pages: per-layer page pytree with leading L
    ({"k": (L,P,Hkv,BS,D), "v": ..., optional "k_scale"/"v_scale": (L,P)});
    block_tables: (B, NB) int32; lengths: (B,) tokens resident per row.
    Homogeneous GQA stacks only (no prologue / hybrid / MLA — PagedKVCache
    enforces this at construction).  ``use_kernel`` takes attention through
    flash_decode_paged; ``interpret`` runs every Pallas kernel of the step in
    the interpreter (CPU) instead of compiling it.  Returns (logits (B,V),
    new_pages, aux)."""
    x = embed_apply(params["embed"], token)
    flags = local_flags(cfg)
    is_moe = cfg.is_moe
    pstack = _placement_stack(cfg, placements)
    blocks, stacked, layers = _hoist_expert_weights(cfg, params["blocks"],
                                                    dispatch_mode)

    def body(x, xs):
        p, c, flag, inv, layer = xs
        p = _with_layer(p, stacked, layer)
        plc = (ExpertPlacement.from_slot_map(inv, cfg.num_experts)
               if inv is not None else None)
        x, newc, aux = B.attn_block_decode_paged(
            p, cfg, x, c, block_tables, lengths, flag, is_moe, plc,
            dispatch_mode, stats, use_kernel, interpret)
        return _seq_constraint(x), (newc, aux)

    x, (new_pages, auxs) = jax.lax.scan(
        body, x, (blocks, pages, flags, pstack, layers), unroll=_unroll())
    aux = _agg_aux(auxs)

    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    unemb = params["embed"] if cfg.tie_embeddings else params["embed"]
    w = unemb["embedding"] if cfg.tie_embeddings else unemb["unembedding"]
    logits = unembed_apply({"unembedding": w}, x, cfg.final_logit_softcap)
    return logits[:, -1], new_pages, aux
