"""Grouped expert GEMM — the MoE hot loop, tiled for the MXU.

TPU re-think of pplx-style grouped GEMM (DESIGN.md §6): instead of per-SM
dynamic work-stealing, a static (expert, token-block, f-block) grid whose
BlockSpec index maps keep one expert's weight tile resident in VMEM while the
MXU streams token blocks through it.  Ragged group edges are handled by the
caller zero-padding dropped rows (capacity dispatch), so every tile is dense.

Tiling: x (1, BC, D) + w (1, 1, D, BF) + out (1, BC, BF) live in VMEM;
BC = BF = 128 matches the 128x128 MXU; D is streamed whole per tile
(d_model <= 8192 -> <= 4 MB bf16, within the ~16 MB VMEM budget together
with the weight tile).

The weights may be a whole layer stack's (L, E, D, F) with the index of the
layer to read: the index rides in as a scalar-prefetch operand (SMEM) and
the weight index map selects the layer, so the DMA reads the tiles straight
from the stacked buffer.  A layer scan hands the kernel the stack this way:
XLA fuses a dynamic slice into its own ops but copies one that feeds a
custom call, which for qwen3-30b-a3b is 1.2 GB per layer and step.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(layer_ref, x_ref, w_ref, o_ref):
    x = x_ref[0]                                   # (BC, D)
    w = w_ref[0, 0]                                # (D, BF)
    acc = jnp.dot(x, w, preferred_element_type=jnp.float32)
    o_ref[0] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "interpret"))
def moe_gemm(xe: jax.Array, w: jax.Array, layer: Optional[jax.Array] = None, *,
             block_c: int = 128, block_f: int = 128,
             interpret: bool = False) -> jax.Array:
    """xe: (E, C, D), w: (E, D, F) -> (E, C, F).

    Or w: (L, E, D, F), a layer stack's weights, with ``layer`` the int32
    index of the layer to multiply by (a scalar, not checked against L).
    The stack is read in place and never padded, so its F must be a multiple
    of the f-block."""
    e, c, d = xe.shape
    f = w.shape[-1]
    bc = min(block_c, c)
    bf = min(block_f, f)
    # pad C/F up to tile multiples (masked rows are zeros -> harmless)
    cp = -(-c // bc) * bc
    fp = -(-f // bf) * bf
    if cp != c:
        xe = jnp.pad(xe, ((0, 0), (0, cp - c), (0, 0)))
    if w.ndim == 3:
        if fp != f:
            w = jnp.pad(w, ((0, 0), (0, 0), (0, fp - f)))
        w, layer = w[None], jnp.zeros((), jnp.int32)
    elif layer is None:
        raise ValueError("stacked (L, E, D, F) weights need the layer index")
    elif fp != f:
        raise ValueError(f"stacked weights are read in place, so F={f} must be "
                         f"a multiple of block_f={bf}; padding would copy "
                         "every layer")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(e, cp // bc, fp // bf),
        in_specs=[
            pl.BlockSpec((1, bc, d), lambda ei, ci, fi, l: (ei, ci, 0)),
            pl.BlockSpec((1, 1, d, bf), lambda ei, ci, fi, l: (l[0], ei, 0, fi)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda ei, ci, fi, l: (ei, ci, fi)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, cp, fp), xe.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), xe, w)
    return out[:, :c, :f]
