"""Expert-parallel MoE under shard_map (the production distributed path).

Layout (DESIGN.md §5): experts sharded over the "model" mesh axis (EP), expert
FFN hidden dim additionally FSDP-sharded over "data"; activations sharded over
the batch ("pod","data") axes and replicated over "model" on entry.

Dispatch ("gather" mode, TPU-native re-think of pplx all-to-all): because
activations are replicated across the model axis, every EP rank already holds
all tokens of its data shard — dispatch is a LOCAL gather of the tokens routed
to the rank's experts (no send), and combine is a single psum over "model".
Communication per MoE layer = one all-reduce of (T_local, d), the same volume
as a Megatron TP FFN, with zero routing-dependent traffic.

"a2a" mode (beyond-paper §Perf alternative): tokens are additionally split
over the model axis (seq-parallel residual), ranks exchange routed tokens with
jax.lax.all_to_all, compute, and exchange back — traffic scales with top_k/EP
instead of the full token set; better when top_k << EP degree.

The placement (Gimbal Alg. 3, optionally with hot-expert replication) maps
S = E + R physical slots -> logical experts; slot s lives on EP rank
s // (S / tp), and a token stream is split round-robin over an expert's
replicas (ExpertPlacement.dispatch_slots).  Relocating or replicating an
expert only rewrites the slot map + gathers the stacked weights; numerics are
invariant.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.context import ShardCtx, divides
from repro.models.config import ModelConfig
from repro.models.layers import ffn_apply
from repro.models.moe import (ExpertPlacement, _capacity, _dispatch_tables,
                              router_probs, top_k_gating)


def _fsdp_gather(w: jax.Array, axis: int, sharded: bool) -> jax.Array:
    if not sharded:
        return w
    return jax.lax.all_gather(w, "data", axis=axis, tiled=True)


def _use_token_gather(cfg: ModelConfig, ctx: ShardCtx, t_loc: int,
                      f_sharded: bool) -> bool:
    """Pick the cheaper EP communication pattern per layer:

    * weight-gather ("gather"): all-gather the FSDP-sharded expert FFN weights
      over "data" (3*E_loc*d*f bytes) — right for train/prefill where the
      token set is huge.
    * token-gather ("tokengather"): weights stay f-sharded; the (tiny) token
      set is all-gathered over "data" and the down-projection partial-summed —
      ~3 orders of magnitude less wire traffic at decode (T_all*d ~ MB vs
      weight tiles ~ GB).  Beyond-paper SSPerf optimization.
    """
    if ctx.ep_mode == "tokengather":
        return True
    if ctx.ep_mode != "auto" or not f_sharded:
        return False
    dp = int(ctx.mesh.shape["data"])
    e_loc = cfg.num_experts // ctx.tp
    weight_bytes = 3 * e_loc * cfg.d_model * cfg.moe_d_ff * 2
    token_bytes = 2 * (t_loc * dp) * cfg.d_model * 2     # gather + psum
    return token_bytes < weight_bytes


def moe_apply_sharded(params: dict, cfg: ModelConfig, x: jax.Array,
                      placement: Optional[ExpertPlacement], ctx: ShardCtx,
                      return_stats: bool = False):
    """x: (B, S, d) sharded over batch axes.  Returns (y, aux) like moe_apply."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.moe_top_k
    tp = ctx.tp
    if placement is None:
        placement = ExpertPlacement.identity(e)
    ns = placement.num_slots                  # S = E + R physical expert slots
    assert divides(ns, tp), f"model axis {tp} must divide expert slots {ns}"
    e_loc = ns // tp                          # slots owned per EP rank

    bdim = 1
    for a in ctx.batch_axes:
        bdim *= int(ctx.mesh.shape[a])
    b_ax = ctx.batch_axes if divides(b, bdim) else None
    t_loc = (b // bdim if b_ax else b) * s
    f_sharded = divides(cfg.moe_d_ff, int(ctx.mesh.shape["data"]))
    token_gather = b_ax is not None and _use_token_gather(cfg, ctx, t_loc, f_sharded)
    t_disp = t_loc * (bdim if token_gather else 1)   # tokens seen by dispatch
    cap = _capacity(cfg, t_disp)

    # --- router in logical-expert space (replicated over model) -----------------
    xf = x.reshape(b * s, d)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), params["w_router"])
    probs = router_probs(logits)
    gates, expert_ids = top_k_gating(probs, k)
    slot_idx = placement.dispatch_slots(expert_ids)           # replica-split slots
    gates = gates.astype(x.dtype)

    wg_spec = P("model", None, "data" if f_sharded else None)
    wd_spec = P("model", "data" if f_sharded else None, None)

    def body_a2a(xb, slots, gt, wg, wu, wd):
        """pplx-style expert parallelism (paper §V-A.1 testbed analogue):
        tokens are additionally split over the model axis, routed to their
        expert owners with jax.lax.all_to_all, computed, and exchanged back.
        Traffic scales with top_k/TP of the token set instead of a full
        all-reduce — the right trade when top_k << TP degree."""
        r = jax.lax.axis_index("model")
        tl = xb.shape[0] * xb.shape[1]
        assert tl % tp == 0, "token count must divide the model axis for a2a"
        tc = tl // tp
        # this rank keeps its token chunk (router ran replicated over model)
        xr = jax.lax.dynamic_slice_in_dim(xb.reshape(tl, d), r * tc, tc, 0)
        sr = jax.lax.dynamic_slice_in_dim(slots.reshape(tl, k), r * tc, tc, 0)
        gr = jax.lax.dynamic_slice_in_dim(gt.reshape(tl, k), r * tc, tc, 0)
        wg_ = _fsdp_gather(wg, 2, f_sharded)
        wu_ = _fsdp_gather(wu, 2, f_sharded)
        wd_ = _fsdp_gather(wd, 1, f_sharded)

        cap_c = _capacity(cfg, tc)                       # per-chunk capacity
        pos, keep = _dispatch_tables(sr, gr, ns, cap_c)
        tok_ids = jnp.broadcast_to(jnp.arange(tc, dtype=jnp.int32)[:, None],
                                   (tc, k)).reshape(-1)
        slot_flat = jnp.where(keep, sr, ns).reshape(-1)
        pos_flat = jnp.where(keep, pos, 0).reshape(-1)
        table = jnp.full((ns + 1, cap_c), tc, dtype=jnp.int32)
        table = table.at[slot_flat, pos_flat].set(tok_ids, mode="drop")[:ns]
        gate_tbl = jnp.zeros((ns + 1, cap_c), x.dtype).at[slot_flat, pos_flat].set(
            (gr * keep).reshape(-1), mode="drop")[:ns]
        valid = table < tc
        safe = jnp.minimum(table, tc - 1)
        xe_send = jnp.where(valid[..., None], jnp.take(xr, safe, axis=0), 0)
        # (E, C, d) -> (tp, e_loc, C, d): destination-major, exchange tokens
        xe_send = xe_send.reshape(tp, e_loc, cap_c, d)
        xe_recv = jax.lax.all_to_all(xe_send, "model", 0, 0)   # src-major now

        # received layout (src, e_loc, C, d): group by MY experts
        xe = xe_recv.transpose(1, 0, 2, 3).reshape(e_loc, tp * cap_c, d)
        gate_h = jnp.einsum("ecd,edf->ecf", xe, wg_)
        up_h = jnp.einsum("ecd,edf->ecf", xe, wu_)
        act = jax.nn.silu(gate_h.astype(jnp.float32)).astype(xe.dtype) * up_h
        ye = jnp.einsum("ecf,efd->ecd", act, wd_)
        ye = ye.reshape(e_loc, tp, cap_c, d).transpose(1, 0, 2, 3)
        ye_back = jax.lax.all_to_all(ye, "model", 0, 0)  # back to sources
        ye_back = ye_back.reshape(ns, cap_c, d)          # my tokens' outputs

        yr = jnp.zeros((tc, d), x.dtype).at[safe.reshape(-1)].add(
            (ye_back * gate_tbl[..., None]).reshape(ns * cap_c, d)
            * valid.reshape(-1, 1).astype(x.dtype), mode="drop")
        # restore model-replication of the residual stream
        y = jax.lax.all_gather(yr, "model", axis=0, tiled=True)
        return y.reshape(xb.shape)

    def body(xb, slots, gt, wg, wu, wd):
        # xb: (B_loc, S, d) replicated over model; slots/gt: (B_loc, S, k)
        r = jax.lax.axis_index("model")
        tl = xb.shape[0] * xb.shape[1]
        xfl = xb.reshape(tl, d)
        slots = slots.reshape(tl, k)
        gt = gt.reshape(tl, k)
        if token_gather:
            # weights stationary (f stays sharded over "data"); replicate the
            # small token set instead and partial-sum the down-projection
            xfl = jax.lax.all_gather(xfl, ctx.batch_axes, axis=0, tiled=True)
            slots = jax.lax.all_gather(slots, ctx.batch_axes, axis=0, tiled=True)
            gt = jax.lax.all_gather(gt, ctx.batch_axes, axis=0, tiled=True)
            tl = xfl.shape[0]
        else:
            wg = _fsdp_gather(wg, 2, f_sharded)
            wu = _fsdp_gather(wu, 2, f_sharded)
            wd = _fsdp_gather(wd, 1, f_sharded)

        pos, keep = _dispatch_tables(slots, gt, ns, cap)
        # token-index table over ALL slots, then slice this rank's slots
        tok_ids = jnp.broadcast_to(jnp.arange(tl, dtype=jnp.int32)[:, None],
                                   (tl, k)).reshape(-1)
        slot_flat = jnp.where(keep, slots, ns).reshape(-1)
        pos_flat = jnp.where(keep, pos, 0).reshape(-1)
        table = jnp.full((ns + 1, cap), tl, dtype=jnp.int32)
        table = table.at[slot_flat, pos_flat].set(tok_ids, mode="drop")
        gate_tbl = jnp.zeros((ns + 1, cap), x.dtype).at[slot_flat, pos_flat].set(
            (gt * keep).reshape(-1), mode="drop")
        table = jax.lax.dynamic_slice_in_dim(table[:ns], r * e_loc, e_loc, 0)
        gate_tbl = jax.lax.dynamic_slice_in_dim(gate_tbl[:ns], r * e_loc, e_loc, 0)

        valid = table < tl
        safe = jnp.minimum(table, tl - 1)
        xe = jnp.where(valid[..., None], jnp.take(xfl, safe, axis=0), 0)

        gate_h = jnp.einsum("ecd,edf->ecf", xe, wg)
        up_h = jnp.einsum("ecd,edf->ecf", xe, wu)
        act = jax.nn.silu(gate_h.astype(jnp.float32)).astype(xe.dtype) * up_h
        ye = jnp.einsum("ecf,efd->ecd", act, wd)

        y = jnp.zeros((tl, d), x.dtype).at[safe.reshape(-1)].add(
            (ye * gate_tbl[..., None]).reshape(e_loc * cap, d)
            * valid.reshape(-1, 1).astype(x.dtype), mode="drop")
        if token_gather:
            # combine over experts (model) AND partial-f products (data),
            # then keep this data-rank's token slice
            y = jax.lax.psum(y, ("model",) + tuple(ctx.batch_axes))
            my = jax.lax.axis_index(ctx.batch_axes[0])
            if len(ctx.batch_axes) == 2:
                my = my * ctx.mesh.shape[ctx.batch_axes[1]] \
                    + jax.lax.axis_index(ctx.batch_axes[1])
            t_own = xb.shape[0] * xb.shape[1]
            y = jax.lax.dynamic_slice_in_dim(y, my * t_own, t_own, 0)
        else:
            y = jax.lax.psum(y, "model")
        return y.reshape(xb.shape)

    t_shard = (b // bdim if b_ax else b) * s
    fn = body_a2a if (ctx.ep_mode == "a2a" and not token_gather
                      and divides(t_shard, tp)) else body
    y = jax.shard_map(
        fn, mesh=ctx.mesh,
        in_specs=(P(b_ax, None, None), P(b_ax, None, None), P(b_ax, None, None),
                  wg_spec, wg_spec, wd_spec),
        out_specs=P(b_ax, None, None),
        check_vma=False,
    )(x, slot_idx.reshape(b, s, k), gates.reshape(b, s, k),
      params["w_gate"], params["w_up"], params["w_down"])

    y = y.reshape(b * s, d)
    if cfg.num_shared_experts > 0:
        y = y + ffn_apply(params["shared"], xf)

    me = probs.mean(0)
    ce = jnp.zeros((e,), jnp.float32).at[expert_ids.reshape(-1)].add(1.0) / (b * s * k)
    aux = {
        "load_balance_loss": e * jnp.sum(me * ce),
        "router_z_loss": jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
    }
    if return_stats:
        aux["expert_counts"] = jnp.zeros((e,), jnp.int32).at[expert_ids.reshape(-1)].add(1)
        aux["expert_ids"] = expert_ids.reshape(b, s, k)
        aux["dropped_frac"] = jnp.float32(0.0)  # keep computed in-body if needed
    return y.reshape(b, s, d), aux
