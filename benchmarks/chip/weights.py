"""Seeded random weights in the served program's parameter layout.

The configuration's arch module draws the tree (``tree(key, config)``);
one jitted call here builds it on the device, in the type the
configuration serves (bf16; the router in f32, as the program keeps it).
The arch draws stacked leaves with ``stacked``, one layer (one expert) at a
time, so that no leaf-sized f32 temporary exists.  The benchmark makes
these weights for the program, and makes them again, from the same seed,
for the reference: the reference takes nothing the program made.

Norm scales are drawn too, with spread ``NORM_STD`` (the program's RMSNorm
multiplies by ``1 + scale``), so a norm applied wrongly cannot hide behind
a unit scale.
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


def stacked(key, n: int, shape, scale: float, dtype):
    """(n, *shape) normal * scale, one layer at a time."""
    return jax.lax.map(
        lambda k: (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype),
        jax.random.split(key, n))


def make(config: dict, seed: int, device=None) -> Dict[str, Any]:
    """The weights of ``config`` for ``seed``, built by one compiled program
    straight onto ``device`` (default: the first device)."""
    device = device if device is not None else jax.devices()[0]
    tree = spec.arch_module(config).tree
    fn = jax.jit(lambda k: tree(k, config),
                 out_shardings=jax.sharding.SingleDeviceSharding(device))
    return fn(seed_key(seed))


def shapes(config: dict):
    """The tree's ShapeDtypeStructs, allocating nothing."""
    tree = spec.arch_module(config).tree
    return jax.eval_shape(lambda k: tree(k, config), seed_key(0))
