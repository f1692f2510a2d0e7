"""Flash-decode: single-token GQA attention with an online softmax over KV
pages streamed HBM -> VMEM (DESIGN.md §6).

Page layout is head-major, (P, Hkv, BS, D): a page holds every KV head's
(BS, D) tile, contiguous, so one DMA moves a whole page.

Grid (B,): one step per batch row.  The page pools stay in HBM; the step
walks only the row's resident pages, ceil(length / (ppb * BS)) compute
blocks in a ``fori_loop``, each block ``ppb`` pages copied into a
double-buffered VMEM scratch (the next block's copies start before this
block's compute).  ``ppb`` is derived from the page size (about
BLOCK_TOKENS tokens a block, within KV_VMEM_BYTES), not a knob.  The
(m, l, acc) running statistics of the f32 online softmax are carried across
blocks in VMEM scratch — the kernel analogue of the shard_map flash-decode
combine in models/attention.py (which splits the same recurrence across
chips).

GQA-aware: every KV head's G = Hq/Hkv query heads attend the block at once
(a dot_general batched over Hkv), so each K/V tile is read exactly once
(decode attention is KV-bandwidth-bound).

``flash_decode`` (a contiguous (B, S, Hkv, D) cache) is the same kernel over
that cache cut into private pages with an identity block table.

``flash_decode_latent`` is the MLA twin over latent pages (L, P, BS, R) and
(L, P, BS, Dr) of every layer: with the queries absorbed into latent space
the attention is an MQA, so grid (B, NB) reads one page per step for all
heads, with the same scalar-prefetched tables, masked-page skip and f32
online softmax.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -2.0 ** 30
BLOCK_TOKENS = 128        # tokens a compute block of flash_decode_paged aims at
KV_VMEM_BYTES = 4 << 20   # cap on its K and V buffers, two slots each


def pages_per_block(block_s: int, page_bytes: int, nb: int) -> int:
    """Pages one compute block of ``flash_decode_paged`` copies: about
    BLOCK_TOKENS tokens, no more than a row's NB pages, and K + V x 2 slots of
    ``page_bytes`` pages (all KV heads of one page of K) within
    KV_VMEM_BYTES."""
    return max(1, min(BLOCK_TOKENS // block_s,
                      KV_VMEM_BYTES // (4 * page_bytes), nb))


def _kernel(bt_ref, len_ref, ks_ref, vs_ref, q_ref, k_hbm, v_hbm,
            o_ref, k_buf, v_buf, sems, m_ref, l_ref, acc_ref,
            *, ppb: int, softcap: float, quantized: bool):
    bi = pl.program_id(0)
    _, hkv, _, bs, d = k_buf.shape
    nb = bt_ref.shape[1]
    tb = ppb * bs                                   # tokens a block
    length = len_ref[bi]
    n_pages = jnp.minimum((length + bs - 1) // bs, nb)
    n_blocks = (n_pages + ppb - 1) // ppb

    def copies(blk, slot):
        """The DMAs of block ``blk`` into buffer ``slot``, one per resident
        page of K and of V: pages past the row's last are not copied."""
        out = []
        for p in range(ppb):
            page = blk * ppb + p
            phys = bt_ref[bi, jnp.minimum(page, nb - 1)]
            out.append((page < n_pages, [
                pltpu.make_async_copy(src.at[phys], buf.at[slot, :, p],
                                      sems.at[i, slot])
                for i, (src, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf)))]))
        return out

    def start(blk, slot):
        for resident, cps in copies(blk, slot):
            @pl.when(resident)
            def _():
                for c in cps:
                    c.start()

    def wait(blk, slot):
        for resident, cps in copies(blk, slot):
            @pl.when(resident)
            def _():
                for c in cps:
                    c.wait()

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                             # (Hkv, G, D)

    @pl.when(n_blocks > 0)
    def _first():
        start(0, 0)

    def body(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _next():
            start(blk + 1, 1 - slot)

        wait(blk, slot)
        k = k_buf[slot].astype(jnp.float32).reshape(hkv, tb, d)  # (Hkv, T, D)
        v = v_buf[slot].astype(jnp.float32).reshape(hkv, tb, d)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        s = s * (d ** -0.5)                                      # (Hkv, G, T)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, tb), 2)
        valid = blk * tb + col < length
        if quantized:
            # per-page scales act on the scores and the probabilities: the
            # same products as dequantizing K and V, on (Hkv, G, T) only
            def per_col(sc_ref):
                row = jnp.zeros((1, 1, tb), jnp.float32)
                for p in range(ppb):
                    phys = bt_ref[bi, jnp.minimum(blk * ppb + p, nb - 1)]
                    row = jnp.where(col // bs == p, sc_ref[phys], row)
                return row
            s = s * per_col(ks_ref)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]                                      # (Hkv, G, 1)
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        # rows past the length hold stale or unwritten VMEM and a page past
        # it may have any scale: 0 * NaN is NaN, so they are zeroed, not
        # only given zero weight
        if quantized:
            p = jnp.where(valid, p * per_col(vs_ref), 0.0)
        row = jax.lax.broadcasted_iota(jnp.int32, (1, tb, 1), 1)
        v = jnp.where(blk * tb + row < length, v, 0.0)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_blocks, body, 0)
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
                ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def flash_decode_paged(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       block_tables: jax.Array, lengths: jax.Array, *,
                       k_scale: jax.Array = None, v_scale: jax.Array = None,
                       softcap: float = 0.0,
                       interpret: bool = False) -> jax.Array:
    """Block-table-indexed flash decode over a paged KV pool.

    q: (B, Hq, D); k_pages, v_pages: (P, Hkv, BS, D) global page pool;
    block_tables: (B, NB) int32 physical page per logical block; lengths: (B,)
    valid tokens per row.  Optional per-page int8 scales (P,) f32 dequantize
    pages in-kernel.  Returns (B, Hq, D).

    Grid (B,): one step per row.  The pools stay in HBM (``pl.ANY``); the
    step walks ceil(length / (ppb * BS)) compute blocks in a ``fori_loop``,
    each ``ppb`` (``pages_per_block``) whole (Hkv, BS, D) pages of K and of V
    copied by DMA into a double-buffered VMEM scratch, the next block's
    copies started before this block's compute.  The block table, lengths
    and scales ride in as scalar-prefetch operands (SMEM), so the DMAs chase
    the physical page ids.  Every KV head's G query heads attend the block
    at once (a dot_general batched over Hkv), with an f32 online softmax
    across blocks; a length-0 row runs no block and its output is exactly
    zero.  ``interpret`` runs the kernel in the Pallas interpreter (CPU); it
    has no default other than compiled."""
    b, hq, d = q.shape
    _, hkv, bs, _ = k_pages.shape
    nb = block_tables.shape[1]
    g = hq // hkv
    ppb = pages_per_block(bs, hkv * bs * d * k_pages.dtype.itemsize, nb)
    qg = q.reshape(b, hkv, g, d)
    quantized = k_scale is not None
    ks = k_scale if quantized else jnp.zeros((1,), jnp.float32)
    vs = v_scale if quantized else jnp.zeros((1,), jnp.float32)

    def row_map(bi, bt, ln, ks_, vs_):
        return (bi, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hkv, g, d), row_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, d), row_map),
        scratch_shapes=[
            pltpu.VMEM((2, hkv, ppb, bs, d), k_pages.dtype),   # K blocks
            pltpu.VMEM((2, hkv, ppb, bs, d), v_pages.dtype),   # V blocks
            pltpu.SemaphoreType.DMA((2, 2)),                   # (K|V, slot)
            pltpu.VMEM((hkv, g, 1), jnp.float32),              # m
            pltpu.VMEM((hkv, g, 1), jnp.float32),              # l
            pltpu.VMEM((hkv, g, d), jnp.float32),              # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, ppb=ppb, softcap=softcap,
                          quantized=quantized),
        grid_spec=grid_spec,
        name="flash_decode_paged",
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), ks, vs,
      qg, k_pages, v_pages)
    return out.reshape(b, hq, d)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "softcap", "interpret"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, lengths: jax.Array,
                 *, block_s: int = 256, softcap: float = 0.0,
                 interpret: bool = False) -> jax.Array:
    """q: (B, Hq, D); k, v: (B, S, Hkv, D); lengths: (B,).  -> (B, Hq, D).

    Each row's S axis is cut into NB = ceil(S / BS) private pages (BS =
    min(block_s, S)) and handed to ``flash_decode_paged`` with the identity
    block table."""
    b, s, hkv, d = k.shape
    bs = min(block_s, s)
    nb = -(-s // bs)

    def pages(x):
        x = jnp.pad(x, ((0, 0), (0, nb * bs - s), (0, 0), (0, 0)))
        return x.reshape(b, nb, bs, hkv, d).transpose(0, 1, 3, 2, 4
                                                      ).reshape(b * nb, hkv, bs, d)

    tables = jnp.arange(b * nb, dtype=jnp.int32).reshape(b, nb)
    return flash_decode_paged(q, pages(k), pages(v), tables, lengths,
                              softcap=softcap, interpret=interpret)


# =============================================================================
# Latent pages (MLA, weight-absorbed)
# =============================================================================

def _latent_kernel(layer_ref, bt_ref, len_ref, ql_ref, qr_ref, c_ref, r_ref,
                   o_ref, m_ref, l_ref, acc_ref, *, block_s: int,
                   scale: float):
    bi = pl.program_id(0)
    si = pl.program_id(1)
    n_s = pl.num_programs(1)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[bi]

    @pl.when(si * block_s < length)                  # masked pages: skipped
    def _update():
        ql = ql_ref[0].astype(jnp.float32)           # (H, R)
        qr = qr_ref[0].astype(jnp.float32)           # (H, Dr)
        c = c_ref[0, 0].astype(jnp.float32)          # (BS, R)
        kr = r_ref[0, 0].astype(jnp.float32)         # (BS, Dr)
        dims = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(ql, c, dims, preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr, kr, dims,
                                   preferred_element_type=jnp.float32))
        s = s * scale                                # (H, BS)
        jpos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(jpos < length, s, NEG_INF)

        m_prev = m_ref[...]                          # (H, 1)
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, c, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(si == n_s - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def flash_decode_latent(q_lat: jax.Array, q_rope: jax.Array,
                        ckv_pages: jax.Array, krope_pages: jax.Array,
                        layer: jax.Array, block_tables: jax.Array,
                        lengths: jax.Array, *, scale: float,
                        interpret: bool = False) -> jax.Array:
    """Flash decode over paged MLA latents (weight-absorbed MQA).

    q_lat: (B, H, R) queries already in latent space (q_nope W_UK);
    q_rope: (B, H, Dr); ckv_pages: (L, P, BS, R) and krope_pages: (L, P,
    BS, Dr), the latent page pools of every layer, read in place at
    ``layer`` (int32 scalar); block_tables: (B, NB); lengths: (B,) valid
    tokens per row; ``scale``: the softmax scale.  Returns o_lat (B, H, R),
    softmax(q_lat ckv^T + q_rope krope^T) ckv.

    Grid (B, NB): each step reads one latent page (BS, R) + (BS, Dr) and
    every head attends it, with the (m, l, acc) online-softmax state of
    flash_decode_paged carried across pages in VMEM.  The layer index, the
    block table and the lengths ride in as scalar prefetch; a masked page
    maps to the row's last valid page, so the pipeline fetches nothing new
    for it, and its compute is skipped."""
    b, h, r = q_lat.shape
    dr = q_rope.shape[-1]
    _, _, bs, _ = ckv_pages.shape
    nb = block_tables.shape[1]

    def q_map(bi, si, ly, bt, ln):
        return (bi, 0, 0)

    def page_map(bi, si, ly, bt, ln):
        last = jnp.maximum((ln[bi] + bs - 1) // bs - 1, 0)
        return (ly[0], bt[bi, jnp.minimum(si, last)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, h, r), q_map),
            pl.BlockSpec((1, h, dr), q_map),
            pl.BlockSpec((1, 1, bs, r), page_map),
            pl.BlockSpec((1, 1, bs, dr), page_map),
        ],
        out_specs=pl.BlockSpec((1, h, r), q_map),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),         # m
            pltpu.VMEM((h, 1), jnp.float32),         # l
            pltpu.VMEM((h, r), jnp.float32),         # acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_kernel, block_s=bs, scale=scale),
        grid_spec=grid_spec,
        name="flash_decode_latent",
        out_shape=jax.ShapeDtypeStruct((b, h, r), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q_lat, q_rope, ckv_pages, krope_pages)
