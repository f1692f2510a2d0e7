"""Fused MoE router: softmax + top-k + capacity positions in one pass.

The per-token scheduling primitive the Gimbal expert level feeds on: gates and
expert ids drive dispatch; the position-in-expert counter implements the
GShard capacity rule.  Cross-token positions need a running per-slot counter
-> the token-block grid axis is sequential ("arbitrary") and the counter lives
in VMEM scratch, carried across blocks (same pattern as flash_decode's online
softmax state).

Top-k is computed by iterative max (k <= 8 for every assigned arch): the
selected index is the lowest lane holding the row max, which is lax.top_k's
tie order.  Everything stays 2-D (tokens on sublanes, experts on lanes) and
every per-selection column is written with a lane select, so the kernel needs
no reshape, stack or cumsum — none of which Mosaic lowers for these shapes.

Capacity positions: the k selections of one token hit k distinct slots (each
slot holds exactly one logical expert), so the position of selection (t, j)
in slot s is the number of EARLIER tokens of the stream that selected s.
Within a block that is a strictly-lower-triangular (BT, BT) matmul against
the (BT, S) 0/1 slot-occupancy matrix; exact in f32 accumulation.  VMEM use
is O(BT·S + BT²) whatever the token count.

Replicated placements (hot-expert redundancy, core/placement.py) are handled
in-kernel by ``topk_router_replicated``: logical expert ids are mapped to one
of the expert's physical slots round-robin on the global selection index
((t*k + j) mod n_replicas — the same rule as ExpertPlacement.dispatch_slots),
and the capacity counter runs over the S = E + R slots, so replicas split a
hot expert's token stream without a second pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -2.0 ** 30


def _group_limit(probs, lane_e, n_group: int, topk_group: int):
    """DeepSeek-V2's group_limited_greedy mask: the experts split into
    ``n_group`` equal lane groups, each scored by its best prob; the probs
    outside the ``topk_group`` best groups become 0 (lowest group first
    among ties)."""
    bt, e = probs.shape
    gs = e // n_group
    lane_g = jax.lax.broadcasted_iota(jnp.int32, (bt, n_group), 1
                                      ).astype(jnp.float32)
    in_g = [(lane_e >= g * gs) & (lane_e < (g + 1) * gs)
            for g in range(n_group)]
    gscore = jnp.zeros((bt, n_group), jnp.float32)
    for g in range(n_group):
        best = jnp.max(jnp.where(in_g[g], probs, NEG_INF), -1, keepdims=True)
        gscore = jnp.where(lane_g == g, best, gscore)
    chosen = jnp.zeros((bt, n_group), jnp.float32)
    for _ in range(topk_group):
        val = gscore.max(-1, keepdims=True)
        gi = jnp.min(jnp.where(gscore == val, lane_g, float(n_group)), -1,
                     keepdims=True)
        hit = lane_g == gi
        chosen = jnp.where(hit, 1.0, chosen)
        gscore = jnp.where(hit, NEG_INF, gscore)
    keep = jnp.zeros_like(probs)
    for g in range(n_group):
        cg = jnp.sum(jnp.where(lane_g == g, chosen, 0.0), -1, keepdims=True)
        keep = jnp.where(in_g[g], cg, keep)
    return jnp.where(keep > 0, probs, 0.0)


def _kernel(x_ref, rs_ref, rc_ref, gates_ref, ids_ref, slots_ref, pos_ref,
            count_ref, *, k: int, num_slots: int, replicated: bool,
            n_group: int = 1, topk_group: int = 1, scale: float = 1.0,
            renorm: bool = True):
    ti = pl.program_id(0)

    @pl.when(ti == 0)
    def _init():
        count_ref[...] = jnp.zeros_like(count_ref)

    logits = x_ref[...].astype(jnp.float32)          # (BT, E)
    bt, e = logits.shape
    m = logits.max(-1, keepdims=True)
    p = jnp.exp(logits - m)
    probs = p / p.sum(-1, keepdims=True)

    iota = jax.lax.broadcasted_iota
    lane_e = iota(jnp.int32, (bt, e), 1).astype(jnp.float32)
    lane_k = iota(jnp.int32, (bt, k), 1)
    lane_s = iota(jnp.int32, (bt, num_slots), 1)

    work = probs
    if n_group > 1:
        work = _group_limit(probs, lane_e, n_group, topk_group)
    gates = jnp.zeros((bt, k), jnp.float32)
    ids = jnp.zeros((bt, k), jnp.int32)
    slots = jnp.zeros((bt, k), jnp.int32)
    occ = jnp.zeros((bt, num_slots), jnp.float32)    # token -> slot, 0/1
    sel_slots = []
    for j in range(k):                               # iterative-max top-k
        val = work.max(-1, keepdims=True)            # (BT, 1)
        idx_f = jnp.min(jnp.where(work == val, lane_e, float(e)), -1,
                        keepdims=True)               # lowest lane at the max
        hit = lane_e == idx_f                        # (BT, E) one-hot
        work = jnp.where(hit, NEG_INF, work)
        idx = idx_f.astype(jnp.int32)
        if replicated:
            # slot = replica_slots[e, (t*k + j) % replica_count[e]] via
            # one-hot lane selects over the tiny (max_rep, E) table
            cnt = jnp.sum(jnp.where(hit, rc_ref[...], 0.0), -1,
                          keepdims=True).astype(jnp.int32)
            row = iota(jnp.int32, (bt, 1), 0)
            sel = (ti * bt + row) * k + j            # global selection index
            r = jax.lax.rem(sel, jnp.maximum(cnt, 1))
            slot = idx
            for c in range(rs_ref.shape[0]):
                cand = jnp.sum(jnp.where(hit, rs_ref[c:c + 1, :], 0.0), -1,
                               keepdims=True).astype(jnp.int32)
                slot = jnp.where(r == c, cand, slot)
        else:
            slot = idx
        gates = jnp.where(lane_k == j, val, gates)
        ids = jnp.where(lane_k == j, idx, ids)
        slots = jnp.where(lane_k == j, slot, slots)
        occ = occ + (lane_s == slot).astype(jnp.float32)
        sel_slots.append(slot)
    if renorm:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    if scale != 1.0:
        gates = gates * scale

    # capacity positions: token-major then selection order (GShard rule),
    # counted per PHYSICAL slot and carried across blocks in count_ref
    earlier = (iota(jnp.int32, (bt, bt), 1)
               < iota(jnp.int32, (bt, bt), 0)).astype(jnp.float32)
    base = count_ref[...]                            # (1, S) carried counter
    run = jnp.dot(earlier, occ, preferred_element_type=jnp.float32) + base
    pos = jnp.zeros((bt, k), jnp.int32)
    for j, slot in enumerate(sel_slots):
        pj = jnp.sum(jnp.where(lane_s == slot, run, 0.0), -1, keepdims=True)
        pos = jnp.where(lane_k == j, pj.astype(jnp.int32), pos)
    count_ref[...] = base + occ.sum(0, keepdims=True)

    gates_ref[...] = gates
    ids_ref[...] = ids
    slots_ref[...] = slots
    pos_ref[...] = pos


def _call(logits: jax.Array, k: int, replica_slots, replica_count,
          num_slots: int, block_t: int, interpret: bool, n_group: int = 1,
          topk_group: int = 1, scale: float = 1.0, renorm: bool = True):
    t, e = logits.shape
    bt = min(block_t, t)
    tp = -(-t // bt) * bt
    if tp != t:
        # pad rows come after every real token (so they never shift a real
        # capacity position) and are sliced off below
        logits = jnp.pad(logits, ((0, tp - t), (0, 0)),
                         constant_values=NEG_INF / 2)
    replicated = replica_slots is not None
    if not replicated:                 # identity tables keep the arity static
        replica_slots = jnp.arange(e, dtype=jnp.int32)[:, None]
        replica_count = jnp.ones((e,), jnp.int32)
    max_rep = replica_slots.shape[1]
    # slot ids and counts are small ints, exact in f32
    rs_t = jnp.asarray(replica_slots, jnp.float32).T            # (max_rep, E)
    rc = jnp.asarray(replica_count, jnp.float32).reshape(1, e)
    gates, ids, slots, pos = pl.pallas_call(
        functools.partial(_kernel, k=k, num_slots=num_slots,
                          replicated=replicated, n_group=n_group,
                          topk_group=topk_group, scale=scale, renorm=renorm),
        grid=(tp // bt,),
        in_specs=[
            pl.BlockSpec((bt, e), lambda ti: (ti, 0)),
            pl.BlockSpec((max_rep, e), lambda ti: (0, 0)),
            pl.BlockSpec((1, e), lambda ti: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bt, k), lambda ti: (ti, 0)),
            pl.BlockSpec((bt, k), lambda ti: (ti, 0)),
            pl.BlockSpec((bt, k), lambda ti: (ti, 0)),
            pl.BlockSpec((bt, k), lambda ti: (ti, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp, k), jnp.float32),
            jax.ShapeDtypeStruct((tp, k), jnp.int32),
            jax.ShapeDtypeStruct((tp, k), jnp.int32),
            jax.ShapeDtypeStruct((tp, k), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((1, num_slots), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(logits.astype(jnp.float32), rs_t, rc)
    return gates[:t], ids[:t], slots[:t], pos[:t]


@functools.partial(jax.jit, static_argnames=("k", "block_t", "interpret"))
def topk_router(logits: jax.Array, k: int, *, block_t: int = 256,
                interpret: bool = False):
    """logits: (T, E).  Returns (gates (T,k) f32, ids (T,k) i32, pos (T,k) i32)."""
    t, e = logits.shape
    gates, ids, _, pos = _call(logits, k, None, None, e, block_t, interpret)
    return gates, ids, pos


@functools.partial(jax.jit,
                   static_argnames=("k", "num_slots", "block_t", "interpret",
                                    "n_group", "topk_group", "scale",
                                    "renorm"))
def topk_router_replicated(logits: jax.Array, k: int,
                           replica_slots: jax.Array, replica_count: jax.Array,
                           num_slots: int, *, block_t: int = 256,
                           interpret: bool = False, n_group: int = 1,
                           topk_group: int = 1, scale: float = 1.0,
                           renorm: bool = True):
    """Replica-aware router.  replica_slots: (E, max_rep) physical slots per
    logical expert (padded with the primary); replica_count: (E,);
    num_slots: S = E + R.  Returns (gates (T,k) f32, ids (T,k) i32 logical,
    slots (T,k) i32 physical, pos (T,k) i32 position-within-slot).

    A slot of ``num_slots`` in the table marks an expert held elsewhere: its
    selections are returned with that slot and take no capacity position.
    ``n_group`` > 1 limits the top-k to the ``topk_group`` best expert
    groups (group_limited_greedy); the gates are renormalised to sum 1 when
    ``renorm``, then multiplied by ``scale``."""
    return _call(logits, k, replica_slots, replica_count, num_slots,
                 block_t, interpret, n_group, topk_group, scale, renorm)
