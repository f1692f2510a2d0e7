"""Pure-jnp oracles for every Pallas kernel (the numerics ground truth).

Each ref_* mirrors its kernel's contract exactly; tests sweep shapes/dtypes
and assert_allclose kernel-vs-ref with interpret=True on CPU.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def ref_moe_gemm(xe: jax.Array, w: jax.Array) -> jax.Array:
    """Grouped expert GEMM.  xe: (E, C, D), w: (E, D, F) -> (E, C, F) in fp32
    accumulation, cast back to xe.dtype."""
    out = jnp.einsum("ecd,edf->ecf", xe.astype(jnp.float32),
                     w.astype(jnp.float32))
    return out.astype(xe.dtype)


def ref_flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, softcap: float = 0.0) -> jax.Array:
    """Single-token GQA decode attention.
    q: (B, Hq, D); k, v: (B, S, Hkv, D); lengths: (B,) valid KV length per row.
    Returns (B, Hq, D)."""
    b, hq, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)
    scores = jnp.einsum("bhgd,bshd->bhgs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * (d ** -0.5)
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    mask = jnp.arange(s)[None, :] < lengths[:, None]          # (B, S)
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    wts = jax.nn.softmax(scores, axis=-1)
    # length-0 rows have an all -inf score row (softmax -> NaN); the kernel
    # contract is zeros there (its accumulator never fires), so match it.
    wts = jnp.where(lengths[:, None, None, None] > 0, wts, 0.0)
    out = jnp.einsum("bhgs,bshd->bhgd", wts, v.astype(jnp.float32))
    return out.reshape(b, hq, d).astype(q.dtype)


def ref_flash_decode_paged(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                           block_tables: jax.Array, lengths: jax.Array,
                           softcap: float = 0.0,
                           k_scale: jax.Array = None,
                           v_scale: jax.Array = None) -> jax.Array:
    """Paged single-token GQA decode attention (block-table indexed).
    q: (B, Hq, D); k_pages, v_pages: (P, Hkv, BS, D) head-major global page
    pool; block_tables: (B, NB) int32 physical page per logical block (page 0
    is the reserved garbage page); lengths: (B,) valid KV length.  Optional
    per-page int8 scales k_scale/v_scale: (P,) f32.  Returns (B, Hq, D)."""
    b = q.shape[0]
    p_, hkv, bs, d = k_pages.shape
    nb = block_tables.shape[1]
    k = k_pages[block_tables].astype(jnp.float32)     # (B, NB, Hkv, BS, D)
    v = v_pages[block_tables].astype(jnp.float32)
    if k_scale is not None:
        k = k * k_scale[block_tables][:, :, None, None, None]
    if v_scale is not None:
        v = v * v_scale[block_tables][:, :, None, None, None]
    k = k.transpose(0, 1, 3, 2, 4).reshape(b, nb * bs, hkv, d)
    v = v.transpose(0, 1, 3, 2, 4).reshape(b, nb * bs, hkv, d)
    return ref_flash_decode(q, k, v, lengths, softcap)


def ref_topk_router_replicated(logits: jax.Array, k: int,
                               replica_slots: jax.Array,
                               replica_count: jax.Array, num_slots: int
                               ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                          jax.Array]:
    """Replica-aware fused router: logical ids map to physical slots
    round-robin on the global selection index ((t*k + j) mod n_replicas,
    ExpertPlacement.dispatch_slots' rule); capacity positions count per SLOT.
    Returns (gates (T,k), ids (T,k) logical, slots (T,k) physical,
    pos (T,k))."""
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, ids = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    ids = ids.astype(jnp.int32)
    sel = (jnp.arange(t, dtype=jnp.int32)[:, None] * k
           + jnp.arange(k, dtype=jnp.int32)[None, :])
    ridx = sel % jnp.maximum(replica_count[ids], 1)
    slots = replica_slots[ids, ridx]
    onehot = jax.nn.one_hot(slots.reshape(-1), num_slots, dtype=jnp.int32)
    pos_flat = (jnp.cumsum(onehot, axis=0) - 1) * onehot
    pos = pos_flat.sum(-1).reshape(t, k).astype(jnp.int32)
    return gates, ids, slots.astype(jnp.int32), pos


def ref_topk_router(logits: jax.Array, k: int
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused router: softmax -> top-k (renormalized gates) -> capacity
    positions (GShard order: token-major, then selection index).
    logits: (T, E) fp32.  Returns (gates (T,k) f32, ids (T,k) i32,
    pos (T,k) i32 position-within-expert)."""
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, ids = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(ids.reshape(-1), e, dtype=jnp.int32)  # (T*k, E)
    pos_flat = (jnp.cumsum(onehot, axis=0) - 1) * onehot
    pos = pos_flat.sum(-1).reshape(t, k).astype(jnp.int32)
    return gates, ids.astype(jnp.int32), pos
