"""Grouped expert GEMM — the MoE hot loop, tiled for the MXU.

TPU re-think of pplx-style grouped GEMM (DESIGN.md §6): instead of per-SM
dynamic work-stealing, a static (expert, token-block, f-block) grid whose
BlockSpec index maps keep one expert's weight tile resident in VMEM while the
MXU streams token blocks through it.  Ragged group edges are handled by the
caller zero-padding dropped rows (capacity dispatch), so every tile is dense.

Tiling: x (1, BC, D) + w (1, D, BF) + out (1, BC, BF) live in VMEM;
BC = BF = 128 matches the 128x128 MXU; D is streamed whole per tile
(d_model <= 8192 -> <= 4 MB bf16, within the ~16 MB VMEM budget together
with the weight tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(x_ref, w_ref, o_ref):
    x = x_ref[0]                                   # (BC, D)
    w = w_ref[0]                                   # (D, BF)
    acc = jnp.dot(x, w, preferred_element_type=jnp.float32)
    o_ref[0] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "interpret"))
def moe_gemm(xe: jax.Array, w: jax.Array, *, block_c: int = 128,
             block_f: int = 128, interpret: bool = False) -> jax.Array:
    """xe: (E, C, D), w: (E, D, F) -> (E, C, F)."""
    e, c, d = xe.shape
    _, _, f = w.shape
    bc = min(block_c, c)
    bf = min(block_f, f)
    # pad C/F up to tile multiples (masked rows are zeros -> harmless)
    cp = -(-c // bc) * bc
    fp = -(-f // bf) * bf
    if cp != c:
        xe = jnp.pad(xe, ((0, 0), (0, cp - c), (0, 0)))
    if fp != f:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, fp - f)))

    out = pl.pallas_call(
        _kernel,
        grid=(e, cp // bc, fp // bf),
        in_specs=[
            pl.BlockSpec((1, bc, d), lambda ei, ci, fi: (ei, ci, 0)),
            pl.BlockSpec((1, d, bf), lambda ei, ci, fi: (ei, 0, fi)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda ei, ci, fi: (ei, ci, fi)),
        out_shape=jax.ShapeDtypeStruct((e, cp, fp), xe.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(xe, w)
    return out[:, :c, :f]
