"""Shared primitive layers: RMSNorm, RoPE, gated FFN, embedding.

Pure-functional: every layer is (params, inputs) -> outputs with params as
plain dicts of jnp arrays, so pjit/shard_map see a transparent pytree.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(dt)


def init_rms_norm(d: int, dtype) -> dict:
    return {"scale": jnp.zeros((d,), dtype)}


# --- RoPE -------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float, scaling=None) -> jax.Array:
    """Rotary frequencies (head_dim // 2,); with ``scaling`` (a
    config.YarnScaling) those of DeepSeek-V2's YaRN rope."""
    if scaling is not None:
        return jnp.asarray(yarn_freqs(head_dim, theta, scaling))
    exponent = np.arange(0, head_dim, 2, dtype=np.float32) / head_dim
    return jnp.asarray(1.0 / (theta ** exponent))  # (head_dim // 2,)


def yarn_correction_range(dim: int, theta: float, scaling) -> tuple:
    """DeepSeek-V2's ``yarn_find_correction_range``: the rotary dims between
    which the frequencies ramp from extrapolated to interpolated."""
    def corr(rot):
        return (dim * math.log(scaling.original_max_position_embeddings
                               / (rot * 2 * math.pi))) / (2 * math.log(theta))
    low = math.floor(corr(scaling.beta_fast))
    high = math.ceil(corr(scaling.beta_slow))
    return max(low, 0), min(high, dim - 1)


def yarn_freqs(dim: int, theta: float, scaling) -> np.ndarray:
    """``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies: extrapolated
    (theta^(-2i/dim)) below the correction range, interpolated (divided by
    the factor) above it, a linear ramp between.  Its cos/sin factor,
    mscale(f, mscale) / mscale(f, mscale_all_dim), is 1 for DeepSeek-V2
    (both 0.707) and is not applied."""
    from repro.models.config import yarn_mscale
    assert yarn_mscale(scaling.factor, scaling.mscale) == yarn_mscale(
        scaling.factor, scaling.mscale_all_dim), "cos/sin mscale != 1"
    exponent = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = 1.0 / (theta ** exponent)
    inter = 1.0 / (scaling.factor * theta ** exponent)
    low, high = yarn_correction_range(dim, theta, scaling)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp                     # 1: extrapolated, 0: interpolated
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling=None, interleave: bool = False) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq).

    Rotates the half-split pairs (x[i], x[i + hd/2]).  ``interleave``
    first gathers the even then the odd dims, as DeepSeek-V2's
    ``apply_rotary_pos_emb`` does, so the pairs rotated are (x[2i],
    x[2i+1]) and the output is in that gathered order.  ``scaling``: YaRN
    frequencies (``rope_freqs``)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta, scaling)                 # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    xf = x.astype(jnp.float32)
    if interleave:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
    else:
        x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --- gated FFN (SwiGLU) -------------------------------------------------------

def ffn_apply(params: dict, x: jax.Array) -> jax.Array:
    gate = jnp.einsum("...d,df->...f", x, params["w_gate"])
    up = jnp.einsum("...d,df->...f", x, params["w_up"])
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    return jnp.einsum("...f,fd->...d", act, params["w_down"])


def init_ffn(key, d: int, f: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in, s_out = d ** -0.5, f ** -0.5
    return {
        "w_gate": (jax.random.normal(k1, (d, f)) * s_in).astype(dtype),
        "w_up": (jax.random.normal(k2, (d, f)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(k3, (f, d)) * s_out).astype(dtype),
    }


# --- embeddings ----------------------------------------------------------------

def embed_apply(params: dict, tokens: jax.Array) -> jax.Array:
    return params["embedding"][tokens]


def unembed_apply(params: dict, x: jax.Array, softcap: float = 0.0) -> jax.Array:
    logits = jnp.einsum("...d,vd->...v", x, params["unembedding"]).astype(jnp.float32)
    if softcap > 0:
        logits = jnp.tanh(logits / softcap) * softcap
    return logits


def init_embed(key, vocab: int, d: int, dtype, tie: bool) -> dict:
    k1, k2 = jax.random.split(key)
    emb = (jax.random.normal(k1, (vocab, d)) * (d ** -0.5)).astype(dtype)
    if tie:
        return {"embedding": emb}
    return {
        "embedding": emb,
        "unembedding": (jax.random.normal(k2, (vocab, d)) * (d ** -0.5)).astype(dtype),
    }


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return x
    return jnp.tanh(x / cap) * cap
