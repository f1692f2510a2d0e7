"""Attention variants: GQA/MQA (qwen2/granite/gemma2/llama4/internvl/whisper),
MLA (deepseek-v2), sliding-window + logit-softcap (gemma2).

Conventions
-----------
* Full-sequence call (train / prefill): q over the whole sequence, causal mask.
* Decode call: one new token per sequence against a static-shape KV cache with
  per-row write positions (`cache_pos`, shape (B,)).
* GQA KV caches: {"k": (B, S, Hkv, D), "v": (B, S, Hkv, D)}.
* MLA KV caches are COMPRESSED: {"ckv": (B, S, R), "krope": (B, S, Dr)} — this
  is the whole point of MLA for serving (tiny cache) and the layout we shard.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.context import current_ctx, divides
from repro.models.config import ModelConfig
from repro.models.layers import apply_rope, rms_norm, softcap


def _head_constraint(x: jax.Array, allow_seq: bool = False) -> jax.Array:
    """Pin (B, S, H, D) activations to batch x head-TP sharding.  Without this
    GSPMD lets the sequence-parallel residual sharding leak into the attention
    einsums and picks pathological score partitions (heads replicated).

    When the head count doesn't divide the TP degree (gemma2 8H, llama4 40H on
    a 16-way model axis) and allow_seq is set, shard the QUERY SEQ dim instead
    (context-parallel attention): scores stay 16-way sharded on Sq rather than
    replicated — §Perf iteration C2."""
    ctx = current_ctx()
    if ctx is None or x.ndim != 4:
        return x
    bdim = 1
    for a in ctx.batch_axes:
        bdim *= int(ctx.mesh.shape[a])
    b_ax = ctx.batch_axes if divides(x.shape[0], bdim) else None
    if divides(x.shape[2], ctx.tp):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(ctx.mesh, P(b_ax, None, ctx.model_axis, None)))
    if allow_seq and x.shape[1] > 1 and divides(x.shape[1], ctx.tp):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(ctx.mesh, P(b_ax, ctx.model_axis, None, None)))
    return x

NEG_INF = -2.0 ** 30  # large-but-finite: keeps masked softmax NaN-free in bf16

# materialize full (Sq, Skv) score tensors only below this element count;
# larger sequences take the chunked-query path (bounded VMEM/HBM footprint)
CHUNK_THRESHOLD = 1 << 22
Q_CHUNK = 512


# =============================================================================
# GQA / MQA
# =============================================================================

def init_gqa(key, cfg: ModelConfig) -> dict:
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (d, hq, hd)) * s).astype(cfg.adtype),
        "wk": (jax.random.normal(ks[1], (d, hkv, hd)) * s).astype(cfg.adtype),
        "wv": (jax.random.normal(ks[2], (d, hkv, hd)) * s).astype(cfg.adtype),
        "wo": (jax.random.normal(ks[3], (hq, hd, d)) * (hq * hd) ** -0.5).astype(cfg.adtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq, hd), cfg.adtype)
        p["bk"] = jnp.zeros((hkv, hd), cfg.adtype)
        p["bv"] = jnp.zeros((hkv, hd), cfg.adtype)
    return p


def _qkv(params: dict, cfg: ModelConfig, x: jax.Array):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    return q, k, v


def _expand_kv(k: jax.Array, hq: int) -> jax.Array:
    """(B,S,Hkv,D) -> (B,S,Hq,D) by repeating each KV head over its Q group.
    Keeps every attention einsum sharded on the (divisible) Q-head dim — the
    Megatron recipe for TP degree > kv_heads (kv replicated per group) — at
    the cost of a broadcasted KV activation, instead of forcing GSPMD to
    replicate the (much larger) score tensors."""
    hkv = k.shape[2]
    if hkv != hq:
        k = jnp.repeat(k, hq // hkv, axis=2)
    return _head_constraint(k)


def _sdpa(cfg: ModelConfig, q, k, v, mask, scale=None) -> jax.Array:
    """q: (B,Sq,Hq,D)  k,v: (B,Skv,Hkv,D)  mask: broadcastable to (B,Sq,Skv).
    ``scale``: the softmax scale (default D**-0.5)."""
    b, sq, hq, d = q.shape
    k = _expand_kv(k, hq)
    v = _expand_kv(v, hq)
    # scores leave the MXU in f32 (no bf16 rounding before the softmax), as
    # kernels/flash_decode.py computes them
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * (
                            d ** -0.5 if scale is None else scale)
    if cfg.attn_logit_softcap > 0:
        scores = softcap(scores, cfg.attn_logit_softcap)
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v)
    return out


def _causal_mask(sq: int, skv: int, window: int) -> jax.Array:
    i = jnp.arange(sq)[:, None] + (skv - sq)  # absolute query positions
    j = jnp.arange(skv)[None, :]
    m = j <= i
    if window > 0:
        m &= j > (i - window)
    return m[None]  # (1, Sq, Skv)


def _sdpa_chunked(cfg: ModelConfig, q, k, v, window: int, causal: bool = True,
                  q_chunk: int = Q_CHUNK, scale=None) -> jax.Array:
    """Memory-bounded full-sequence attention: scan over query chunks so only
    a (q_chunk, Skv) score block is live at a time (flash-attention-lite in
    pure XLA; kernels/flash_decode.py shows the full-Pallas treatment).
    q: (B,Sq,Hq,D), k/v: (B,Skv,Hkv,D)."""
    b, sq, hq, dh = q.shape
    skv = k.shape[1]
    qc = min(q_chunk, sq)
    if sq % qc != 0:
        qc = sq  # ragged: fall back to one chunk
    n_chunks = sq // qc
    k = _expand_kv(k, hq)
    v = _expand_kv(v, hq)
    scale = dh ** -0.5 if scale is None else scale
    j = jnp.arange(skv)[None, :]

    def one(ci):
        qb = jax.lax.dynamic_slice_in_dim(q, ci * qc, qc, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k,
                            preferred_element_type=jnp.float32) * scale
        if cfg.attn_logit_softcap > 0:
            scores = softcap(scores, cfg.attn_logit_softcap)
        i = (ci * qc + jnp.arange(qc))[:, None] + (skv - sq)
        m = (j <= i) if causal else jnp.ones((qc, skv), bool)
        if window > 0:
            m &= j > (i - window)
        # additive mask: one (qc, skv) f32 bias broadcast into the add instead
        # of a score-shaped pred broadcast + select pair (SSPerf iteration D1)
        scores = scores + jnp.where(m, 0.0, NEG_INF)[None, None]
        w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        ob = jnp.einsum("bhqk,bkhd->bqhd", w, v)
        return ob

    ctx = current_ctx()
    unroll = max(int(ctx.unroll), 1) if ctx is not None else 1
    _, out = jax.lax.scan(lambda c, ci: (c, one(ci)), None,
                          jnp.arange(n_chunks), unroll=unroll)  # (n, B, qc, Hq, D)
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, hq, v.shape[-1])


def _sdpa_auto(cfg: ModelConfig, q, k, v, window: int, causal: bool = True,
               scale=None, q_chunk: int = Q_CHUNK):
    """Pick chunked vs. materialized scores by footprint.

    When the head count doesn't divide the TP degree (gemma2 8H / llama4 40H
    at TP=16) the scores can't shard on heads; chunking doesn't help either —
    its dynamic q-slice on a seq-sharded operand makes GSPMD all-gather q
    (SSPerf iteration C5).  Context-parallel full-score attention (q seq-
    sharded via _head_constraint's seq fallback, scores sharded on the q-seq
    dim end to end) bounds per-device score memory by 1/TP instead."""
    ctx = current_ctx()
    if (ctx is not None and q.shape[1] > 1
            and not divides(q.shape[2], ctx.tp)
            and divides(q.shape[1], ctx.tp)):
        mask = _causal_mask(q.shape[1], k.shape[1], window) if causal else \
            jnp.ones((1, q.shape[1], k.shape[1]), bool)
        return _sdpa(cfg, q, k, v, mask, scale)
    if q.shape[1] * k.shape[1] > CHUNK_THRESHOLD and q.shape[1] > 1:
        return _sdpa_chunked(cfg, q, k, v, window, causal, q_chunk, scale)
    mask = _causal_mask(q.shape[1], k.shape[1], window) if causal else \
        jnp.ones((1, q.shape[1], k.shape[1]), bool)
    return _sdpa(cfg, q, k, v, mask, scale)


def gqa_full(params: dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
             local: bool, cache: Optional[dict] = None):
    """Train / prefill attention.  Returns (out, new_cache_or_None)."""
    q, k, v = _qkv(params, cfg, x)
    # constrain BEFORE rope: rope splits the head_dim in half, and when hd is
    # the TP-sharded dim (H < tp archs) that split makes GSPMD replicate the
    # full f32 q tensor (SSPerf iteration C3) — seq/head sharding first keeps
    # the split local
    q = apply_rope(_head_constraint(q, allow_seq=True), positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    window = cfg.sliding_window if local else 0
    new_cache = None
    if cache is not None:
        new_cache = {
            "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
        }
    out = _sdpa_auto(cfg, q, k, v, window, causal=True)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return out, new_cache


def gqa_decode(params: dict, cfg: ModelConfig, x: jax.Array, cache: dict,
               cache_pos: jax.Array, local: bool):
    """One-token decode.  x: (B,1,d); cache_pos: (B,) int32 write positions.
    Returns (out, updated_cache)."""
    q, k_new, v_new = _qkv(params, cfg, x)
    q = apply_rope(q, cache_pos[:, None], cfg.rope_theta)
    k_new = apply_rope(k_new, cache_pos[:, None], cfg.rope_theta)

    ctx = current_ctx()
    if ctx is not None and divides(cache["k"].shape[1], ctx.tp):
        out = _gqa_decode_seqsharded(cfg, q, k_new, v_new, cache, cache_pos,
                                     local, ctx)
        out, k, v = out
        out = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
        return out, {"k": k, "v": v}

    def write(c, new, p):
        return jax.lax.dynamic_update_slice(c, new.astype(c.dtype), (p, 0, 0))

    k = jax.vmap(write)(cache["k"], k_new, cache_pos)
    v = jax.vmap(write)(cache["v"], v_new, cache_pos)

    s_max = k.shape[1]
    j = jnp.arange(s_max)[None, :]
    mask = j <= cache_pos[:, None]
    if local and cfg.sliding_window > 0:
        mask &= j > (cache_pos[:, None] - cfg.sliding_window)
    out = _sdpa(cfg, q, k.astype(q.dtype), v.astype(q.dtype), mask[:, None, :])
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return out, {"k": k, "v": v}


def _paged_append_int8(pages, scales, phys, off, new):
    """Append one token per row into int8 pages with per-page scales.
    pages: (P, Hkv, BS, D) int8; scales: (P,) f32; phys/off: (B,) page id /
    in-page offset; new: (B, Hkv, D) f32.  The scale update is MONOTONE
    (never shrinks), so when the new token fits the old scale the requantize
    round-trips existing entries exactly (round(q*s/s) == q)."""
    blk = pages[phys].astype(jnp.float32) * scales[phys][:, None, None, None]
    blk = jax.vmap(
        lambda c, t, o: jax.lax.dynamic_update_slice(c, t[:, None], (0, o, 0))
    )(blk, new.astype(jnp.float32), off)
    amax = jnp.max(jnp.abs(blk), axis=(1, 2, 3))
    new_scale = jnp.maximum(scales[phys], jnp.maximum(amax, 1e-12) / 127.0)
    q = jnp.clip(jnp.round(blk / new_scale[:, None, None, None]),
                 -127, 127).astype(jnp.int8)
    return pages.at[phys].set(q), scales.at[phys].set(new_scale)


def gqa_decode_paged(params: dict, cfg: ModelConfig, x: jax.Array, cache: dict,
                     block_tables: jax.Array, lengths: jax.Array, local: bool,
                     use_kernel: bool = False, interpret: bool = False):
    """One-token decode against a paged KV pool (one layer's pages).

    x: (B,1,d); cache: {"k": (P,Hkv,BS,D), "v": ..., optional "k_scale"/
    "v_scale": (P,) f32 for int8 pages}; block_tables: (B,NB) physical page per
    logical block (page 0 = reserved garbage page — free rows write there);
    lengths: (B,) tokens resident = write position.  Returns (out, new_cache).

    The host guarantees (PagedKVCache.prepare_append) that active rows' tail
    pages are private (copy-on-write) and allocated; inactive rows carry
    lengths=0 and all-zero table rows, so their scatter lands in the garbage
    page and their (discarded) output attends only to it.

    ``use_kernel`` runs the attention through the Pallas flash_decode_paged
    kernel, compiled unless ``interpret`` asks for the interpreter (CPU)."""
    q, k_new, v_new = _qkv(params, cfg, x)
    q = apply_rope(q, lengths[:, None], cfg.rope_theta)
    k_new = apply_rope(k_new, lengths[:, None], cfg.rope_theta)

    b = x.shape[0]
    bs_blk = cache["k"].shape[2]
    nb = block_tables.shape[1]
    bidx = lengths // bs_blk
    off = lengths % bs_blk
    phys = block_tables[jnp.arange(b), bidx]                  # (B,)
    quantized = "k_scale" in cache

    new_cache = dict(cache)
    if quantized:
        new_cache["k"], new_cache["k_scale"] = _paged_append_int8(
            cache["k"], cache["k_scale"], phys, off, k_new[:, 0])
        new_cache["v"], new_cache["v_scale"] = _paged_append_int8(
            cache["v"], cache["v_scale"], phys, off, v_new[:, 0])
    else:
        new_cache["k"] = cache["k"].at[phys, :, off].set(
            k_new[:, 0].astype(cache["k"].dtype))
        new_cache["v"] = cache["v"].at[phys, :, off].set(
            v_new[:, 0].astype(cache["v"].dtype))

    windowed = local and cfg.sliding_window > 0
    if use_kernel and not windowed:
        from repro.kernels.flash_decode import flash_decode_paged
        o = flash_decode_paged(
            q[:, 0], new_cache["k"], new_cache["v"], block_tables, lengths + 1,
            k_scale=new_cache.get("k_scale"), v_scale=new_cache.get("v_scale"),
            softcap=float(cfg.attn_logit_softcap), interpret=interpret)
        out = o[:, None].astype(x.dtype)
    else:
        kb = new_cache["k"][block_tables]                     # (B,NB,Hkv,BS,D)
        vb = new_cache["v"][block_tables]
        if quantized:
            kb = kb.astype(jnp.float32) \
                * new_cache["k_scale"][block_tables][..., None, None, None]
            vb = vb.astype(jnp.float32) \
                * new_cache["v_scale"][block_tables][..., None, None, None]
        hkv, dh = cache["k"].shape[1], cache["k"].shape[3]
        kb = kb.transpose(0, 1, 3, 2, 4).reshape(b, nb * bs_blk, hkv, dh)
        vb = vb.transpose(0, 1, 3, 2, 4).reshape(b, nb * bs_blk, hkv, dh)
        j = jnp.arange(nb * bs_blk)[None, :]
        mask = j <= lengths[:, None]
        if windowed:
            mask &= j > (lengths[:, None] - cfg.sliding_window)
        out = _sdpa(cfg, q, kb.astype(q.dtype), vb.astype(q.dtype),
                    mask[:, None, :])
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return out, new_cache


def _gqa_decode_seqsharded(cfg: ModelConfig, q, k_new, v_new, cache, cache_pos,
                           local: bool, ctx):
    """Flash-decode with the KV cache sharded over the model axis on the SEQ
    dim (DESIGN.md §5): each rank attends over its local KV chunk and partial
    softmax statistics are combined with pmax/psum — the collective-derived
    equivalent of flash attention's online softmax.

    q: (B,1,Hq,D) k_new/v_new: (B,1,Hkv,D) cache k/v: (B,S,Hkv,D).
    Returns (out (B,1,Hq,D), k, v)."""
    b = q.shape[0]
    bdim = 1
    for a in ctx.batch_axes:
        bdim *= int(ctx.mesh.shape[a])
    b_ax = ctx.batch_axes if divides(b, bdim) else None
    window = cfg.sliding_window if local else 0

    def body(qb, kn, vn, kc, vc, pos):
        r = jax.lax.axis_index(ctx.model_axis)
        s_loc = kc.shape[1]
        start = r * s_loc
        lp = pos - start
        in_range = (lp >= 0) & (lp < s_loc)
        lp_safe = jnp.clip(lp, 0, s_loc - 1)

        def write(c, new, p, ok):
            # conditional write WITHOUT a full-cache select: out-of-range ranks
            # re-write the existing row (reads 1 row, writes 1 row — the
            # jnp.where(sel, updated, cache) formulation copies the whole
            # cache per layer, §Perf iteration B2)
            cur = jax.lax.dynamic_slice(c, (p, 0, 0), new.shape)
            val = jnp.where(ok, new.astype(c.dtype), cur)
            return jax.lax.dynamic_update_slice(c, val, (p, 0, 0))

        kc = jax.vmap(write)(kc, kn, lp_safe, in_range)
        vc = jax.vmap(write)(vc, vn, lp_safe, in_range)

        hq, dh = qb.shape[2], qb.shape[3]
        hkv = kc.shape[2]
        g = hq // hkv
        qg = qb.reshape(b if b_ax is None else qb.shape[0], 1, hkv, g, dh)
        kcq = kc.astype(qb.dtype)
        vcq = vc.astype(qb.dtype)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kcq).astype(jnp.float32) * (dh ** -0.5)
        if cfg.attn_logit_softcap > 0:
            scores = softcap(scores, cfg.attn_logit_softcap)
        jg = start + jnp.arange(s_loc)
        mask = jg[None, :] <= pos[:, None]
        if window > 0:
            mask &= jg[None, :] > (pos[:, None] - window)
        scores = jnp.where(mask[:, None, None, None, :], scores, NEG_INF)

        m_loc = scores.max(-1, keepdims=True)
        m = jax.lax.pmax(m_loc, ctx.model_axis)
        p = jnp.exp(scores - m)
        l = jax.lax.psum(p.sum(-1, keepdims=True), ctx.model_axis)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(qb.dtype), vcq)
        o = jax.lax.psum(o, ctx.model_axis)
        out = (o / jnp.maximum(l, 1e-20).astype(o.dtype).transpose(0, 3, 1, 2, 4)
               ).reshape(qb.shape[0], 1, hq, vcq.shape[-1])
        return out, kc, vc

    rep4 = P(b_ax, None, None, None)
    shard4 = P(b_ax, ctx.model_axis, None, None)
    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(rep4, rep4, rep4, shard4, shard4, P(b_ax)),
        out_specs=(rep4, shard4, shard4),
        check_vma=False,
    )(q, k_new, v_new, cache["k"], cache["v"], cache_pos)


# =============================================================================
# Cross attention (whisper decoder)
# =============================================================================

def cross_attention(params: dict, cfg: ModelConfig, x: jax.Array, memory: jax.Array):
    """x: (B,Sq,d) queries; memory: (B,Skv,d) encoder output.  No mask, no rope."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", memory, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", memory, params["wv"])
    mask = jnp.ones((1, q.shape[1], k.shape[1]), bool)
    out = _sdpa(cfg, q, k, v, mask)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


# =============================================================================
# MLA (deepseek-v2)
# =============================================================================

def init_mla(key, cfg: ModelConfig) -> dict:
    d, h = cfg.d_model, cfg.num_heads
    r_q, r_kv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    s = d ** -0.5
    p = {
        "wkv_a": (jax.random.normal(ks[0], (d, r_kv + dr)) * s).astype(cfg.adtype),
        "kv_norm": jnp.zeros((r_kv,), cfg.adtype),
        "wkv_b": (jax.random.normal(ks[1], (r_kv, h, dn + dv)) * r_kv ** -0.5).astype(cfg.adtype),
        "wo": (jax.random.normal(ks[2], (h, dv, d)) * (h * dv) ** -0.5).astype(cfg.adtype),
    }
    if r_q > 0:
        p["wq_a"] = (jax.random.normal(ks[3], (d, r_q)) * s).astype(cfg.adtype)
        p["q_norm"] = jnp.zeros((r_q,), cfg.adtype)
        p["wq_b"] = (jax.random.normal(ks[4], (r_q, h, dn + dr)) * r_q ** -0.5).astype(cfg.adtype)
    else:
        p["wq"] = (jax.random.normal(ks[5], (d, h, dn + dr)) * s).astype(cfg.adtype)
    return p


def _mla_q(params: dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank > 0:
        cq = rms_norm(jnp.einsum("bsd,dr->bsr", x, params["wq_a"]), params["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, params["wq_b"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling,
                        cfg.rope_interleave)
    return q_nope, q_rope


def _mla_ckv(params: dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    r_kv, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    kv = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"])
    ckv = rms_norm(kv[..., :r_kv], params["kv_norm"], cfg.norm_eps)
    krope = apply_rope(kv[..., None, r_kv:], positions, cfg.rope_theta,
                       cfg.rope_scaling, cfg.rope_interleave)[..., 0, :]
    return ckv, krope


def _score_chunk(heads: int, skv: int, budget: int = 1 << 26) -> int:
    """Query rows per chunk of the chunked prefill attention: Q_CHUNK, halved
    until one chunk's (heads, rows, skv) f32 scores hold at most ``budget``
    elements (256 MB): deepseek-v2's 128 heads over an 8192-token prompt
    take 64 rows, where 512 would make 2 GB of scores."""
    qc = Q_CHUNK
    while qc > 8 and heads * qc * skv > budget:
        qc //= 2
    return qc


def mla_full(params: dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
             cache: Optional[dict] = None):
    """Naive (paper-faithful) MLA for train/prefill: decompress then SDPA."""
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(params, cfg, x, positions)
    ckv, krope = _mla_ckv(params, cfg, x, positions)
    new_cache = None
    if cache is not None:
        new_cache = {
            "ckv": jax.lax.dynamic_update_slice(cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, 0, 0)),
            "krope": jax.lax.dynamic_update_slice(cache["krope"], krope.astype(cache["krope"].dtype), (0, 0, 0)),
        }
    kv = jnp.einsum("bsr,rhk->bshk", ckv, params["wkv_b"])
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(krope[:, :, None, :],
                                                  (*krope.shape[:2], cfg.num_heads, krope.shape[-1]))], axis=-1)
    out = _sdpa_auto(cfg, q, k, v, 0, causal=True,
                     scale=cfg.attn_softmax_scale,
                     q_chunk=_score_chunk(cfg.num_heads, k.shape[1]))
    out = jnp.einsum("bshk,hkd->bsd", out[..., :dv], params["wo"])
    return out, new_cache


def mla_decode(params: dict, cfg: ModelConfig, x: jax.Array, cache: dict,
               cache_pos: jax.Array, absorb: bool = False):
    """One-token MLA decode against the COMPRESSED cache.

    absorb=False: paper-faithful — decompress every cached step then SDPA.
    absorb=True : weight-absorbed decode (beyond-paper §Perf optimization) —
      scores in latent space; never materializes per-head K/V for the cache.
    """
    dn, dv, r_kv = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    q_nope, q_rope = _mla_q(params, cfg, x, cache_pos[:, None])
    ckv_new, krope_new = _mla_ckv(params, cfg, x, cache_pos[:, None])

    ctx = current_ctx()
    if ctx is not None and divides(cache["ckv"].shape[1], ctx.tp):
        out, ckv, krope = _mla_decode_seqsharded(
            cfg, params, q_nope, q_rope, ckv_new, krope_new, cache, cache_pos,
            ctx, absorb)
        out = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
        return out, {"ckv": ckv, "krope": krope}

    def write(c, new, p):
        return jax.lax.dynamic_update_slice(c, new.astype(c.dtype), (p, 0))

    ckv = jax.vmap(write)(cache["ckv"], ckv_new, cache_pos)
    krope = jax.vmap(write)(cache["krope"], krope_new, cache_pos)
    new_cache = {"ckv": ckv, "krope": krope}

    s_max = ckv.shape[1]
    mask = jnp.arange(s_max)[None, :] <= cache_pos[:, None]      # (B, Skv)
    scale = cfg.attn_softmax_scale
    ckv_c = ckv.astype(x.dtype)
    krope_c = krope.astype(x.dtype)

    if absorb:
        wkb_k = params["wkv_b"][..., :dn]  # (r, h, dn)
        wkb_v = params["wkv_b"][..., dn:]  # (r, h, dv)
        # q_nope (b,1,h,dn) -> latent space (b,1,h,r)
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, wkb_k)
        scores = (jnp.einsum("bshr,btr->bhst", q_lat, ckv_c)
                  + jnp.einsum("bshk,btk->bhst", q_rope, krope_c)).astype(jnp.float32) * scale
        scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        o_lat = jnp.einsum("bhst,btr->bshr", w, ckv_c)           # (b,1,h,r)
        out = jnp.einsum("bshr,rhk->bshk", o_lat, wkb_v)          # (b,1,h,dv)
    else:
        kv = jnp.einsum("btr,rhk->bthk", ckv_c, params["wkv_b"])  # decompress ALL steps
        k_nope, v = kv[..., :dn], kv[..., dn:]
        scores = (jnp.einsum("bshk,bthk->bhst", q_nope, k_nope)
                  + jnp.einsum("bshk,btk->bhst", q_rope, krope_c)).astype(jnp.float32) * scale
        scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhst,bthk->bshk", w, v)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return out, new_cache


def mla_decode_paged(params: dict, cfg: ModelConfig, x: jax.Array, pages: dict,
                     layer: jax.Array, block_tables: jax.Array,
                     lengths: jax.Array, use_kernel: bool = False,
                     interpret: bool = False):
    """One-token MLA decode against the paged latent pool of EVERY layer.

    x: (B,1,d); pages: {"ckv": (L,P,BS,R), "krope": (L,P,BS,Dr)}, the whole
    stack's pages (serving/kvcache.PagedKVCache), written and read in place
    at ``layer`` (an int32 scalar); block_tables (B,NB) and lengths (B,) as
    gqa_decode_paged.  Returns (out (B,1,d), new pages).

    Weight-absorbed: the queries move into latent space (q_nope W_UK) before
    the attention, so every head attends the one shared latent, an MQA of
    R + Dr for scores and R for values; o_lat W_UV and wo follow it.
    ``use_kernel`` runs that attention through the Pallas
    flash_decode_latent kernel (interpreted under ``interpret``), else
    through a gather of the row's pages in XLA."""
    dn = cfg.qk_nope_head_dim
    q_nope, q_rope = _mla_q(params, cfg, x, lengths[:, None])
    ckv_new, krope_new = _mla_ckv(params, cfg, x, lengths[:, None])
    b = x.shape[0]
    bs = pages["ckv"].shape[2]
    phys = block_tables[jnp.arange(b), lengths // bs]          # (B,)
    off = lengths % bs
    new_pages = {
        "ckv": pages["ckv"].at[layer, phys, off].set(
            ckv_new[:, 0].astype(pages["ckv"].dtype)),
        "krope": pages["krope"].at[layer, phys, off].set(
            krope_new[:, 0].astype(pages["krope"].dtype)),
    }
    wkb_k = params["wkv_b"][..., :dn]                          # (R, H, dn)
    wkb_v = params["wkv_b"][..., dn:]                          # (R, H, dv)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, wkb_k)[:, 0]  # (B, H, R)
    q_pe = q_rope[:, 0]                                        # (B, H, Dr)
    scale = cfg.attn_softmax_scale
    if use_kernel:
        from repro.kernels.flash_decode import flash_decode_latent
        o_lat = flash_decode_latent(
            q_lat, q_pe, new_pages["ckv"], new_pages["krope"], layer,
            block_tables, lengths + 1, scale=scale, interpret=interpret)
    else:
        nb = block_tables.shape[1]
        c = new_pages["ckv"][layer][block_tables].reshape(b, nb * bs, -1)
        kr = new_pages["krope"][layer][block_tables].reshape(b, nb * bs, -1)
        c, kr = c.astype(x.dtype), kr.astype(x.dtype)
        scores = (jnp.einsum("bhr,btr->bht", q_lat, c,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bhk,btk->bht", q_pe, kr,
                               preferred_element_type=jnp.float32)) * scale
        valid = jnp.arange(nb * bs)[None, :] <= lengths[:, None]
        scores = jnp.where(valid[:, None, :], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        o_lat = jnp.einsum("bht,btr->bhr", w, c)
    out = jnp.einsum("bhr,rhk->bhk", o_lat.astype(x.dtype), wkb_v)
    out = jnp.einsum("bhk,hkd->bd", out, params["wo"])[:, None]
    return out, new_pages


def _mla_decode_seqsharded(cfg: ModelConfig, params, q_nope, q_rope, ckv_new,
                           krope_new, cache, cache_pos, ctx, absorb: bool):
    """Seq-sharded MLA decode against the compressed cache (flash-decode
    combine over the model axis).  absorb=True scores in latent space and
    never materializes per-position K/V (§Perf optimization); absorb=False is
    the paper-faithful decompress-then-attend baseline, decompressing only the
    local chunk per rank."""
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    b = q_nope.shape[0]
    bdim = 1
    for a in ctx.batch_axes:
        bdim *= int(ctx.mesh.shape[a])
    b_ax = ctx.batch_axes if divides(b, bdim) else None
    scale = cfg.attn_softmax_scale
    wkb = params["wkv_b"]                       # (r, H, dn+dv) replicated inside

    if absorb:
        wkb_k = wkb[..., :dn]
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, wkb_k)   # latent queries
    else:
        q_lat = q_nope                                         # placeholder (unused)

    def body(qn, qr, ql, cn, kn, ckv, krope, pos, wkb_b):
        r_idx = jax.lax.axis_index(ctx.model_axis)
        s_loc = ckv.shape[1]
        start = r_idx * s_loc
        lp = pos - start
        in_range = (lp >= 0) & (lp < s_loc)
        lp_safe = jnp.clip(lp, 0, s_loc - 1)

        def write(c, new, p, ok):
            # row-conditional write (no full-cache select; see GQA analogue)
            cur = jax.lax.dynamic_slice(c, (p, 0), new.shape)
            val = jnp.where(ok, new.astype(c.dtype), cur)
            return jax.lax.dynamic_update_slice(c, val, (p, 0))

        ckv = jax.vmap(write)(ckv, cn, lp_safe, in_range)
        krope = jax.vmap(write)(krope, kn, lp_safe, in_range)

        ckv_c = ckv.astype(qn.dtype)
        krope_c = krope.astype(qn.dtype)
        jg = start + jnp.arange(s_loc)
        mask = jg[None, :] <= pos[:, None]

        if absorb:
            scores = (jnp.einsum("bshr,btr->bhst", ql, ckv_c)
                      + jnp.einsum("bshk,btk->bhst", qr, krope_c)
                      ).astype(jnp.float32) * scale
        else:
            kv = jnp.einsum("btr,rhk->bthk", ckv_c, wkb_b)     # local decompress
            k_nope = kv[..., :dn]
            scores = (jnp.einsum("bshk,bthk->bhst", qn, k_nope)
                      + jnp.einsum("bshk,btk->bhst", qr, krope_c)
                      ).astype(jnp.float32) * scale
        scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
        m = jax.lax.pmax(scores.max(-1, keepdims=True), ctx.model_axis)
        p = jnp.exp(scores - m)
        l = jax.lax.psum(p.sum(-1, keepdims=True), ctx.model_axis)
        w = p.astype(qn.dtype)
        if absorb:
            o_lat = jax.lax.psum(jnp.einsum("bhst,btr->bshr", w, ckv_c),
                                 ctx.model_axis)
            out = jnp.einsum("bshr,rhk->bshk", o_lat / jnp.maximum(l, 1e-20)
                             .astype(o_lat.dtype).transpose(0, 2, 1, 3),
                             wkb_b[..., dn:])
        else:
            v = kv[..., dn:]
            o = jax.lax.psum(jnp.einsum("bhst,bthk->bshk", w, v), ctx.model_axis)
            out = o / jnp.maximum(l, 1e-20).astype(o.dtype).transpose(0, 2, 1, 3)
        return out, ckv, krope

    rep3 = P(b_ax, None, None)
    rep4 = P(b_ax, None, None, None)
    shard3 = P(b_ax, ctx.model_axis, None)
    return jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(rep4, rep4, rep4, rep3, rep3, shard3, shard3, P(b_ax),
                  P(None, None, None)),
        out_specs=(rep4, shard3, shard3),
        check_vma=False,
    )(q_nope, q_rope, q_lat, ckv_new, krope_new, cache["ckv"], cache["krope"],
      cache_pos, wkb)


# =============================================================================
# Unified entry points used by blocks.py
# =============================================================================

def init_attention(key, cfg: ModelConfig) -> dict:
    if cfg.attention_type == "mla":
        return init_mla(key, cfg)
    return init_gqa(key, cfg)


def attention_full(params, cfg: ModelConfig, x, positions, layer_idx_local: bool, cache=None):
    if cfg.attention_type == "mla":
        return mla_full(params, cfg, x, positions, cache)
    return gqa_full(params, cfg, x, positions, layer_idx_local, cache)


def attention_decode(params, cfg: ModelConfig, x, cache, cache_pos, layer_idx_local: bool,
                     mla_absorb: bool = False):
    if cfg.attention_type == "mla":
        return mla_decode(params, cfg, x, cache, cache_pos, absorb=mla_absorb)
    return gqa_decode(params, cfg, x, cache, cache_pos, layer_idx_local)
