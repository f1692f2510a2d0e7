"""Seeded random weights in the served program's parameter layout.

One jitted call builds the whole tree on the device, in the type the
configuration serves (bf16; the router in f32, as the program keeps it),
drawing the stacked leaves one layer (one expert) at a time so that no
leaf-sized f32 temporary exists.  The benchmark makes these weights for the program, and makes them
again, from the same seed, for the reference: the reference takes nothing
the program made.

Norm scales are drawn too (the program's RMSNorm multiplies by
``1 + scale``), so a norm applied wrongly cannot hide behind a unit scale.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import spec

NORM_STD = 0.1


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, 64 bits of it."""
    seed %= 1 << 64
    k = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(seed >> 32))


def _stacked(key, n: int, shape, scale: float, dtype):
    """(n, *shape) normal * scale, one layer at a time."""
    return jax.lax.map(
        lambda k: (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype),
        jax.random.split(key, n))


def _tree(key, config: dict) -> Dict[str, Any]:
    z = spec.dims(config)
    L, d, hq, hkv, hd, V = z["L"], z["d"], z["hq"], z["hkv"], z["hd"], z["V"]
    dt = jnp.dtype(config["torch_dtype"])
    ks = iter(jax.random.split(key, 16))
    s_d = d ** -0.5
    normal = lambda k, shape, scale: (
        jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)
    embed = {"embedding": normal(next(ks), (V, d), s_d)}
    unembed_key = next(ks)
    if not config.get("tie_word_embeddings", False):
        embed["unembedding"] = normal(unembed_key, (V, d), s_d)
    norm = lambda k, n: _stacked(k, n, (d,), NORM_STD, dt)
    blocks: Dict[str, Any] = {
        "attn_norm": {"scale": norm(next(ks), L)},
        "attn": {"wq": _stacked(next(ks), L, (d, hq, hd), s_d, dt),
                 "wk": _stacked(next(ks), L, (d, hkv, hd), s_d, dt),
                 "wv": _stacked(next(ks), L, (d, hkv, hd), s_d, dt),
                 "wo": _stacked(next(ks), L, (hq, hd, d), (hq * hd) ** -0.5, dt)},
        "ffn_norm": {"scale": norm(next(ks), L)},
    }
    if z["E"]:
        E, f = z["E"], z["f"]
        blocks["moe"] = {
            "w_router": _stacked(next(ks), L, (d, E), s_d, jnp.float32),
            "w_gate": _stacked(next(ks), L * E, (d, f), s_d, dt).reshape(L, E, d, f),
            "w_up": _stacked(next(ks), L * E, (d, f), s_d, dt).reshape(L, E, d, f),
            "w_down": _stacked(next(ks), L * E, (f, d), f ** -0.5, dt).reshape(L, E, f, d),
        }
    else:
        F = z["F"]
        blocks["ffn"] = {"w_gate": _stacked(next(ks), L, (d, F), s_d, dt),
                         "w_up": _stacked(next(ks), L, (d, F), s_d, dt),
                         "w_down": _stacked(next(ks), L, (F, d), F ** -0.5, dt)}
    return {"embed": embed,
            "final_norm": {"scale": _stacked(next(ks), 1, (d,), NORM_STD, dt)[0]},
            "blocks": blocks}


def make(config: dict, seed: int, device=None) -> Dict[str, Any]:
    """The weights of ``config`` for ``seed``, built by one compiled program
    straight onto ``device`` (default: the first device)."""
    device = device if device is not None else jax.devices()[0]
    fn = jax.jit(lambda k: _tree(k, config),
                 out_shardings=jax.sharding.SingleDeviceSharding(device))
    return fn(seed_key(seed))


def shapes(config: dict):
    """The tree's ShapeDtypeStructs, allocating nothing."""
    return jax.eval_shape(lambda k: _tree(k, config), seed_key(0))
