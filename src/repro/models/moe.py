"""Mixture-of-Experts layer with placement-aware, replica-splitting dispatch.

The Gimbal expert level (core/placement.py) produces a *placement*: a slot map
over S = E + R physical slots (R >= 0 redundant replicas of hot experts).
Expert weights are stored in SLOT order and sharded over the ``model`` mesh
axis (slot s lives on chip s // (S / |model|)), so relocating or replicating
an expert == gathering the stacked weight arrays + updating the placement.
The router works in logical-expert space and maps selected ids to slots via
``ExpertPlacement.dispatch_slots`` (round-robin over an expert's replicas)
before dispatch.  Placement never changes numerics as long as no token is
capacity-dropped (property-tested in tests/test_placement.py and
tests/test_models.py); under overflow, each replica slot carries its own
capacity budget, so replicating a hot expert can only RESCUE tokens the
unreplicated placement would have dropped — fewer drops, never different
routing for surviving tokens.

Three dispatch strategies (same numerics; §Perf compares them):
  * "dense"  — GShard/Switch-style one-hot einsum dispatch (classic TPU MoE,
               our paper-faithful baseline).
  * "gather" — sort-free gather/scatter dispatch: build an (E, C) token-index
               table with the same capacity rule, gather tokens, grouped GEMM,
               scatter-add back.  Avoids the O(T·E·C·d) dispatch matmuls.
  * "fused"  — "gather" with the Pallas kernels: the replica-aware top-k
               router (kernels/topk_router.py) and the grouped expert GEMM
               (kernels/moe_gemm.py), compiled unless ``interpret`` is set.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import init_ffn


class ExpertPlacement(NamedTuple):
    """Replicated expert placement over S = E + R physical slots.

    ``inv[s]`` = logical expert in slot s (every expert holds >= 1 slot; the
    R redundant slots hold replicas of hot experts).  ``perm[e]`` = primary
    (lowest) slot of expert e.  ``replica_slots[e, r]`` enumerates e's slots,
    padded by repeating the primary so shapes stay static; ``replica_count[e]``
    is the true copy count.  Dispatch splits a token stream round-robin over
    an expert's replicas (see moe_apply); every replica holds identical
    weights, so surviving tokens compute identically — replication can only
    reduce capacity drops (each copy has its own capacity budget).  R=0
    reduces to the old pure permutation."""
    perm: jax.Array            # (E,) int32 primary slot per logical expert
    inv: jax.Array             # (S,) int32 logical expert per slot
    replica_slots: jax.Array   # (E, max_rep) int32, padded with the primary
    replica_count: jax.Array   # (E,) int32

    @property
    def num_slots(self) -> int:
        return self.inv.shape[0]

    @property
    def num_experts(self) -> int:
        return self.perm.shape[0]

    @staticmethod
    def identity(num_experts: int) -> "ExpertPlacement":
        eye = jnp.arange(num_experts, dtype=jnp.int32)
        return ExpertPlacement(perm=eye, inv=eye,
                               replica_slots=eye[:, None],
                               replica_count=jnp.ones_like(eye))

    @staticmethod
    def from_perm(perm) -> "ExpertPlacement":
        perm = jnp.asarray(perm, jnp.int32)
        inv = jnp.zeros_like(perm).at[perm].set(jnp.arange(perm.shape[0], dtype=jnp.int32))
        return ExpertPlacement(perm=perm, inv=inv,
                               replica_slots=perm[:, None],
                               replica_count=jnp.ones_like(perm))

    @staticmethod
    def from_slot_map(inv, num_experts: int) -> "ExpertPlacement":
        """Build from a slot map (core/placement.py ``*_rep`` solvers).  All
        shapes are static in (S, E), so this is jit/scan-safe."""
        inv = jnp.asarray(inv, jnp.int32)
        s, e = inv.shape[0], num_experts
        max_rep = s - e + 1                      # static copy-count bound
        onehot = inv[None, :] == jnp.arange(e, dtype=jnp.int32)[:, None]  # (E,S)
        count = onehot.sum(1).astype(jnp.int32)
        rank = jnp.cumsum(onehot, axis=1) * onehot           # 1-based per slot
        slots_row = jnp.arange(s, dtype=jnp.int32)[None, :]
        cols = [jnp.where(rank == r + 1, slots_row, s).min(1)
                for r in range(max_rep)]                     # s == "absent"
        tbl = jnp.stack(cols, axis=1)
        primary = tbl[:, 0]
        tbl = jnp.where(tbl == s, primary[:, None], tbl)
        return ExpertPlacement(perm=primary.astype(jnp.int32), inv=inv,
                               replica_slots=tbl.astype(jnp.int32),
                               replica_count=count)

    def dispatch_slots(self, expert_ids: jax.Array) -> jax.Array:
        """Physical slot per selection with round-robin load splitting:
        selection (t, j) of a replicated expert goes to replica
        (t*k + j) mod n_replicas.  expert_ids: (T, k) logical -> (T, k)
        slots.  The divisor is clamped to 1 (same guard as the Pallas
        kernel) so a malformed slot map missing an expert cannot
        mod-by-zero."""
        t, k = expert_ids.shape
        sel = (jnp.arange(t, dtype=jnp.int32)[:, None] * k
               + jnp.arange(k, dtype=jnp.int32)[None, :])
        ridx = sel % jnp.maximum(self.replica_count[expert_ids], 1)
        return self.replica_slots[expert_ids, ridx]


def init_moe(key, cfg: ModelConfig) -> dict:
    e, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    s_in, s_out = d ** -0.5, f ** -0.5
    p = {
        "w_router": (jax.random.normal(ks[0], (d, e)) * s_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, f)) * s_in).astype(cfg.adtype),
        "w_up": (jax.random.normal(ks[2], (e, d, f)) * s_in).astype(cfg.adtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d)) * s_out).astype(cfg.adtype),
    }
    if cfg.num_shared_experts > 0:
        p["shared"] = init_ffn(ks[4], d, cfg.moe_d_ff * cfg.num_shared_experts, cfg.adtype)
    return p


def router_probs(logits: jax.Array) -> jax.Array:
    return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)


def top_k_gating(probs: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Returns (gates (T,k) renormalized, expert ids (T,k))."""
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx


def _capacity(cfg: ModelConfig, num_tokens: int) -> int:
    c = int(cfg.capacity_factor * cfg.moe_top_k * num_tokens / cfg.num_experts) + 1
    # MXU-friendly: round capacity up to a multiple of 8 (sublane dim)
    return max(8, -(-c // 8) * 8)


def _expert_ffn(params: dict, xe: jax.Array) -> jax.Array:
    """xe: (E, C, d) -> (E, C, d) gated FFN per expert (grouped GEMM)."""
    gate = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])
    up = jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(xe.dtype) * up
    return jnp.einsum("ecf,efd->ecd", act, params["w_down"])


def _expert_ffn_kernel(params: dict, xe: jax.Array, interpret: bool) -> jax.Array:
    """_expert_ffn through three moe_gemm Pallas calls.  Inside a layer scan
    ``params`` may hold the whole stack's (L, E, ...) expert weights and the
    scan's ``"layer"`` index (model.py), which moe_gemm reads in place."""
    from repro.kernels.moe_gemm import moe_gemm
    layer = params.get("layer")
    gate = moe_gemm(xe, params["w_gate"], layer, interpret=interpret)
    up = moe_gemm(xe, params["w_up"], layer, interpret=interpret)
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(xe.dtype) * up
    return moe_gemm(act, params["w_down"], layer, interpret=interpret)


def _dispatch_tables(slot_idx: jax.Array, gates: jax.Array, num_slots: int, capacity: int):
    """Capacity assignment shared by both dispatch modes.

    slot_idx: (T, k) physical slot per selection; gates: (T, k).
    Returns (pos (T,k) position-in-slot or >=capacity if dropped,
             keep (T,k) bool).
    Priority: earlier tokens first, then lower k — the GShard rule.
    """
    t, k = slot_idx.shape
    flat = slot_idx.reshape(-1)                                   # (T*k,) token-major
    onehot = jax.nn.one_hot(flat, num_slots, dtype=jnp.int32)     # (T*k, E)
    pos_flat = (jnp.cumsum(onehot, axis=0) - 1) * onehot          # (T*k, E)
    pos = (pos_flat.sum(-1)).reshape(t, k)                        # position within its slot
    keep = pos < capacity
    return pos, keep


def moe_apply(params: dict, cfg: ModelConfig, x: jax.Array,
              placement: Optional[ExpertPlacement] = None,
              dispatch_mode: str = "dense",
              return_stats: bool = False, interpret: bool = False):
    """x: (B, S, d).  Returns (y, aux) where aux carries router losses and,
    when return_stats, per-expert activation counts + per-token expert ids
    (the signals Gimbal's affinity/EPLB collectors consume).  ``interpret``
    runs the "fused" mode's kernels in the Pallas interpreter (CPU).  Under
    "fused", ``params`` may carry stacked expert weights and a ``"layer"``
    index (see _expert_ffn_kernel); the einsum modes take one layer's."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.num_experts, cfg.moe_top_k
    xf = x.reshape(t, d)
    if placement is None:
        placement = ExpertPlacement.identity(e)

    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), params["w_router"])
    probs = router_probs(logits)                                   # logical space
    ns = placement.num_slots                                       # S = E + R
    cap = _capacity(cfg, t)
    if dispatch_mode == "fused":
        # Fused router -> dispatch: the Pallas kernel produces gates, logical
        # ids, physical slots AND per-slot capacity positions in one pass
        # (VMEM count scratch carried across token blocks) — same contract as
        # top_k_gating + dispatch_slots + _dispatch_tables.
        from repro.kernels.topk_router import topk_router_replicated
        gates, expert_ids, slot_idx, pos = topk_router_replicated(
            logits, k, placement.replica_slots, placement.replica_count, ns,
            interpret=interpret)
        keep = pos < cap
    else:
        gates, expert_ids = top_k_gating(probs, k)                 # (T,k) logical
        slot_idx = placement.dispatch_slots(expert_ids)            # physical slots
        pos, keep = _dispatch_tables(slot_idx, gates, ns, cap)
    gates = gates.astype(x.dtype)

    if dispatch_mode == "dense":
        # (T,k,S) x (T,k,C) -> dispatch (T,S,C)
        oh_e = jax.nn.one_hot(slot_idx, ns, dtype=x.dtype) * keep[..., None]
        oh_c = jax.nn.one_hot(pos, cap, dtype=x.dtype)
        dispatch = jnp.einsum("tke,tkc->tec", oh_e, oh_c)
        combine = jnp.einsum("tke,tkc,tk->tec", oh_e, oh_c, gates)
        xe = jnp.einsum("tec,td->ecd", dispatch, xf)
        ye = _expert_ffn(params, xe)
        y = jnp.einsum("tec,ecd->td", combine, ye)
    elif dispatch_mode in ("gather", "fused"):
        # token-index table (S, C): which token sits in slot (s, c)
        tok_ids = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[:, None], (t, k)).reshape(-1)
        slot_flat = jnp.where(keep, slot_idx, ns).reshape(-1)      # dropped -> slot S (overflow row)
        pos_flat = jnp.where(keep, pos, 0).reshape(-1)
        table = jnp.full((ns + 1, cap), t, dtype=jnp.int32)        # t == "no token"
        table = table.at[slot_flat, pos_flat].set(tok_ids, mode="drop")
        table = table[:ns]                                         # (S, C)
        valid = table < t
        xe = jnp.where(valid[..., None],
                       jnp.take(xf, jnp.minimum(table, t - 1), axis=0), 0).astype(x.dtype)
        if dispatch_mode == "fused":
            ye = _expert_ffn_kernel(params, xe, interpret)         # 3x moe_gemm
        else:
            ye = _expert_ffn(params, xe)
        # combine: scatter-add expert outputs back, weighted by gate.  The k
        # weighted outputs of a token are summed in f32 and rounded once, as
        # the dense einsum combine does: k bf16 roundings drift from it.
        gate_tbl = jnp.zeros((ns + 1, cap), x.dtype).at[slot_flat, pos_flat].set(
            (gates * keep).reshape(-1), mode="drop")[:ns]
        y = jnp.zeros((t, d), jnp.float32).at[jnp.minimum(table, t - 1).reshape(-1)].add(
            (ye.astype(jnp.float32) * gate_tbl[..., None]).reshape(ns * cap, d) *
            valid.reshape(-1, 1), mode="drop").astype(x.dtype)
    else:
        raise ValueError(f"unknown dispatch_mode {dispatch_mode!r}")

    if cfg.num_shared_experts > 0:
        from repro.models.layers import ffn_apply
        y = y + ffn_apply(params["shared"], xf)

    # ---- router aux (always fp32) -------------------------------------------
    me = probs.mean(0)                                             # (E,) mean prob, logical
    # fraction of tokens routed to each LOGICAL expert (pre-placement)
    ce = jnp.zeros((e,), jnp.float32).at[expert_ids.reshape(-1)].add(1.0) / (t * k)
    aux = {
        "load_balance_loss": e * jnp.sum(me * ce),
        "router_z_loss": jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
    }
    if return_stats:
        aux["expert_counts"] = jnp.zeros((e,), jnp.int32).at[expert_ids.reshape(-1)].add(1)
        aux["expert_ids"] = expert_ids.reshape(b, s, k)            # logical ids per token
        aux["dropped_frac"] = 1.0 - keep.mean()
    return y.reshape(b, s, d), aux


def permute_expert_weights(params: dict, old: ExpertPlacement, new: ExpertPlacement) -> dict:
    """Physically relocate stacked expert weights from placement `old` to `new`.
    Works across slot counts: each new slot gathers its expert's weights from
    that expert's primary slot under `old`, so growing E -> E+R slots
    materializes the replica copies."""
    gather_idx = old.perm[new.inv]    # for each new slot, an old slot holding that expert
    out = dict(params)
    for name in ("w_gate", "w_up", "w_down"):
        out[name] = params[name][gather_idx]
    return out
