"""JaxBackend: the real-compute execution substrate behind SchedulerCore.

Owns everything physical about serving — the jitted prefill/decode functions,
the fixed-slot device KV cache (JetStream-style static shapes for XLA), the
per-slot last-token state, and expert-weight relocation when the expert level
fires.  Every scheduling *decision* (admission, preemption, completion) is
made by core/scheduler.py; this module only executes them.

Timing is logical: ``step_time`` returns the caller-supplied ``now`` (the
cluster/simulator owns the clock), so behaviour tests are deterministic.
"""
from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from typing import Any, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trace
from repro.core.eplb import ExpertRebalancer
from repro.core.types import Request
from repro.models import config as mcfg
from repro.models import model as M
from repro.serving.kvcache import PagedKVCache, SlotKVCache, write_slot


def _bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


class JaxBackend:
    """Backend protocol implementation over the real JAX model (runs the
    actual compute on ``device``: its parameters, KV pool and every step's
    inputs are placed there, so several backends in one process can each
    serve from their own chip).

    ``kernel_mode`` is decided once, from the platform of ``device``:
    "compiled" on a TPU (Mosaic kernels), "interpret" elsewhere (the Pallas
    interpreter — how the CPU tests run the kernel paths).  It only matters
    when the step runs a kernel (``dispatch_mode="fused"`` or
    ``use_kernels``).

    ``charge_prefix_hits`` is False: the live engine recomputes the full
    prefill (its prefix cache is a routing/affinity signal, not block reuse),
    so admission must charge the full prompt length against the budget.
    """

    charge_prefix_hits = False

    def __init__(self, model_cfg: mcfg.ModelConfig, params: Any, *,
                 max_slots: int = 4, max_seq: int = 256,
                 eos_id: Optional[int] = None, dispatch_mode: str = "dense",
                 rebalancer: Optional[ExpertRebalancer] = None,
                 kv_layout: str = "slot", kv_block_size: int = 16,
                 kv_quant: Optional[str] = None, use_kernels: bool = False,
                 device: Optional[jax.Device] = None):
        assert kv_layout in ("slot", "paged")
        assert kv_quant in (None, "int8")
        self.cfg = model_cfg
        self.device = device if device is not None else jax.devices()[0]
        self.kernel_mode = ("compiled" if self.device.platform == "tpu"
                            else "interpret")
        self.params = jax.device_put(params, self.device)
        self.rebalancer = rebalancer
        self.kv_layout = kv_layout
        self.use_kernels = use_kernels
        if kv_layout == "paged":
            self.kv = PagedKVCache(model_cfg, max_slots, max_seq,
                                   block_size=kv_block_size,
                                   quantize=(kv_quant == "int8"),
                                   device=self.device)
            # block-granular accounting: SchedulerCore rounds every per-request
            # charge up to whole blocks and gates admission on distinct blocks
            self.kv_block_size = kv_block_size
            kv_capacity = self.kv.capacity_tokens
        else:
            self.kv = SlotKVCache(model_cfg, max_slots, max_seq,
                                  device=self.device)
            self.kv_block_size = 1
            kv_capacity = max_slots * max_seq
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.dispatch_mode = dispatch_mode
        self.max_concurrency = max_slots
        self.kv_capacity = kv_capacity
        # prompts are physically truncated to the slot length (see start()),
        # so a request can never hold more than one slot's worth of KV — the
        # core's pool accounting must match or over-long prompts starve
        self.max_ctx_tokens: Optional[int] = max_seq
        # layered-prefill micro-step count (SchedulerCore reads it; the sim
        # twin derives the same number from the same ModelConfig)
        self.n_layers = model_cfg.num_layers
        # optional offline-profiled CostModel powering est_iter_time (the
        # SLO-aware shedding estimate); None = shedding never fires here
        self.cost_hint = None
        self.slot_req: List[Optional[Request]] = [None] * max_slots
        self.slot_last_token = np.zeros(max_slots, np.int32)
        self.relocations = 0
        self._n_scan = model_cfg.num_moe_layers()
        self._applied_map: Optional[np.ndarray] = None   # slot -> logical
        self._jit_decode = jax.jit(self._decode_fn)
        self._jit_decode_paged = jax.jit(self._decode_paged_fn)
        # One compiled prefill per BUCKETED length: prompts are padded to the
        # next power-of-two bucket and the jit cache is keyed on that bucket,
        # so repeated prefills of previously-unseen lengths inside a bucket
        # reuse the compiled fn instead of re-tracing.
        self._prefill_for_bucket = functools.lru_cache(maxsize=None)(
            self._make_prefill)

    # ------------------------------------------------------------------ jit fns
    def _put(self, x) -> jax.Array:
        """Host array -> this backend's device."""
        return jax.device_put(np.asarray(x), self.device)

    def _placements(self):
        if self.rebalancer is None:
            return None
        return self._put(self.rebalancer.placement_stack(self._n_scan))

    def _sync_placement(self) -> None:
        """Catch up with the (possibly cluster-shared) expert level: when
        ANOTHER engine's core tick fired the rebalance, this backend sees the
        new slot map here, before its next forward pass — weights and
        placement always move together."""
        rb = self.rebalancer
        if rb is None or getattr(rb, "slot_map", None) is None:
            return
        tgt = np.asarray(rb.slot_map)
        cur = self._applied_map
        if cur is None:
            cur = np.arange(self.cfg.num_experts)   # initial identity layout
        if not np.array_equal(cur, tgt):
            self.apply_placement(tgt)

    def _decode_fn(self, params, tokens, cache, cache_pos, placements):
        stats = self.cfg.is_moe and self.rebalancer is not None
        return M.decode_step(params, self.cfg, tokens, cache, cache_pos,
                             placements=placements, stats=stats,
                             dispatch_mode=self.dispatch_mode,
                             interpret=self.kernel_mode == "interpret")

    def _decode_paged_fn(self, params, tokens, pages, block_tables, lengths,
                         placements):
        stats = self.cfg.is_moe and self.rebalancer is not None
        return M.decode_step_paged(params, self.cfg, tokens, pages,
                                   block_tables, lengths,
                                   placements=placements, stats=stats,
                                   dispatch_mode=self.dispatch_mode,
                                   use_kernel=self.use_kernels,
                                   interpret=self.kernel_mode == "interpret")

    def _make_prefill(self, plen: int):
        @jax.jit
        def fn(params, tokens, placements):
            # the batch=1 cache is born inside the program, on the device
            slot_cache = M.init_cache(self.cfg, 1, self.max_seq)
            return M.prefill(params, self.cfg, tokens, slot_cache,
                             placements=placements,
                             dispatch_mode=self.dispatch_mode,
                             interpret=self.kernel_mode == "interpret")
        return fn

    def prefill_cache_info(self):
        """(hits, misses, ...) of the bucketed prefill jit cache."""
        return self._prefill_for_bucket.cache_info()

    def warmup(self, prompt_lens: Sequence[int]) -> None:
        """Compile (or load from the compile cache) the prefill program of
        each of these prompt lengths' buckets and the decode step, side by
        side in threads, by running each once on dummy inputs.  Nothing is
        written: the KV pool and the slots stay as they were."""
        placements = self._placements()
        buckets = sorted({_bucket(min(n, self.max_seq - 1))
                          for n in prompt_lens})
        jobs = [functools.partial(self._prefill_for_bucket(bl), self.params,
                                  self._put(np.zeros((1, bl), np.int32)),
                                  placements) for bl in buckets]
        tokens = self._put(self.slot_last_token[:, None])
        pos = self._put(self.kv.positions())
        if self.kv_layout == "paged":
            jobs.append(functools.partial(
                self._jit_decode_paged, self.params, tokens, self.kv.pages,
                self._put(self.kv.block_tables), pos, placements))
        else:
            jobs.append(functools.partial(
                self._jit_decode, self.params, tokens, self.kv.cache, pos,
                placements))
        with ThreadPoolExecutor(len(jobs)) as pool:
            outs = list(pool.map(lambda job: job(), jobs))
        jax.block_until_ready(outs)

    # ------------------------------------------------------------------ Backend protocol
    def start(self, r: Request, now: float
              ) -> Tuple[int, Optional[np.ndarray]]:
        with trace.span("backend.prefill", r.req_id) as sp:
            self._sync_placement()
            plen = min(r.prompt_len, self.max_seq - 1)
            if r.prompt_tokens is not None:
                toks = np.asarray(r.prompt_tokens, np.int32).reshape(-1)[:plen]
            else:
                rng = np.random.default_rng(r.req_id)
                toks = rng.integers(0, self.cfg.vocab_size, plen).astype(np.int32)
            if self.kv_layout == "paged":
                # share only when the core's block accounting also shared: real
                # tokens, not a migrated sequence (its KV travelled, all private)
                share = (r.prompt_tokens is not None
                         and not getattr(r, "kv_migrated", False))
                slot = self.kv.alloc(plen, toks.tolist() if share else None)
            else:
                slot = self.kv.alloc()
            assert slot is not None, "SchedulerCore admitted past slot capacity"
            bl = _bucket(plen)
            sp.note("plen", plen)
            sp.note("bucket", bl)
            padded = np.zeros((1, bl), np.int32)
            padded[0, :plen] = toks
            fn = self._prefill_for_bucket(bl)
            logits, slot_cache, aux = fn(self.params, self._put(padded),
                                         self._placements())
            if self.kv_layout == "paged":
                self.kv.write_prefill(slot, slot_cache)
            else:
                self.kv.cache = write_slot(self.kv.cache, slot_cache, slot,
                                           self.kv.write_axes)
            self.slot_req[slot] = r
            self.kv.slot_len[slot] = plen
            first = int(jnp.argmax(logits[0, plen - 1]))
            self.slot_last_token[slot] = first
            if not (r.kv_migrated and r.first_token_time is not None):
                r.output_tokens = [first]   # a resumed request keeps its stream
            stats = None
            if "expert_ids" in aux:
                stats = np.asarray(aux["expert_ids"])[:, :, :plen]
            return slot, stats

    def decode(self, active: Sequence[Tuple[int, Request]], now: float
               ) -> Tuple[Set[int], Optional[np.ndarray]]:
        with trace.span("backend.decode") as sp:
            sp.note("rows", len(active))
            sp.note("max_slots", self.max_slots)
            self._sync_placement()
            tokens = self._put(self.slot_last_token[:, None])
            pos = self._put(self.kv.positions())
            if self.kv_layout == "paged":
                for slot, _r in active:
                    self.kv.prepare_append(slot)     # alloc/CoW tail pages
                logits, new_pages, aux = self._jit_decode_paged(
                    self.params, tokens, self.kv.pages,
                    self._put(self.kv.block_tables), pos, self._placements())
                self.kv.pages = new_pages
            else:
                logits, new_cache, aux = self._jit_decode(
                    self.params, tokens, self.kv.cache, pos, self._placements())
                self.kv.cache = new_cache
            with trace.span("backend.decode.wait"):
                nxt = np.asarray(jnp.argmax(logits, -1), np.int32)
            eos: Set[int] = set()
            rows = []
            for slot, r in active:
                rows.append(slot)
                self.slot_last_token[slot] = nxt[slot]
                if r.output_tokens is not None:
                    r.output_tokens.append(int(nxt[slot]))
                self.kv.slot_len[slot] = min(self.kv.slot_len[slot] + 1,
                                             self.max_seq - 1)
                if self.eos_id is not None and nxt[slot] == self.eos_id:
                    eos.add(r.req_id)
            stats = None
            if "expert_ids" in aux and rows:
                stats = np.asarray(aux["expert_ids"])[:, rows]   # (L, B, 1, K)
            return eos, stats

    def release(self, handle: int, r: Request) -> None:
        self.slot_req[handle] = None
        self.kv.free(handle)

    def step_time(self, now: float, prefill_tokens: int, decode_batch: int,
                  avg_ctx: float, queue_len: int,
                  layer_jobs: Optional[Sequence[int]] = None) -> float:
        return now      # logical clock: the caller owns time

    def transfer_time(self, kv_tokens: int) -> float:
        """Disaggregated hand-off cost.  The live engine runs on a logical
        clock (see step_time), so KV transfers are free here; the sim twin
        prices them through CostModel.migration_time."""
        return 0.0

    def est_iter_time(self, prefill_tokens: int, decode_batch: int,
                      avg_ctx: float, queue_len: int) -> float:
        """Admission-control hint: estimated wall seconds for one iteration.
        The live engine runs on a logical clock, so the estimate comes from
        an offline-profiled cost model (``cost_hint``, a sim.costmodel
        CostModel) the way production admission controllers use calibrated
        service rates; with no hint the estimate is 0.0 and SLO-aware
        shedding never fires."""
        if self.cost_hint is None:
            return 0.0
        return self.cost_hint.iteration_time(prefill_tokens, decode_batch,
                                             avg_ctx, queue_len=queue_len)

    def kv_usage(self, kv_tokens: int) -> float:
        if self.kv_layout == "paged":
            # identical formula to CostModelBackend so ScoredRouter's w_kv term
            # is plane-invariant AND reads true block occupancy (the core
            # passes blocks_used * block_size as kv_tokens in block mode)
            return min(kv_tokens / max(self.kv_capacity, 1), 1.0)
        return self.kv.usage()

    def apply_placement(self, new_map: np.ndarray) -> None:
        """EDR fired: physically gather the stacked expert weights into the
        new slot layout (``new_map``: S = E + R slots -> logical expert; a
        replicated expert's weights are copied into each of its slots).
        Numerics are invariant (tests/test_placement.py, test_engine.py).
        Param trees without a stacked 'moe' block (non-MoE or interleaved
        layouts this backend doesn't relocate) are left untouched and do NOT
        count as a relocation."""
        with trace.span("backend.relocate"):
            blocks = self.params["blocks"]
            if "moe" not in blocks:
                return
            new_map = np.asarray(new_map)
            # weights are currently laid out for the PREVIOUS slot map (initial
            # layout == identity: slot s holds logical expert s)
            old_map = self._applied_map
            if old_map is None:
                old_map = np.arange(self.cfg.num_experts)
            if np.array_equal(old_map, new_map):
                return                  # already laid out — not a relocation
            self.relocations += 1
            # each new slot gathers from ONE old slot holding its expert (the
            # expert's first old slot — every expert has >= 1)
            old_primary = np.full(self.cfg.num_experts, -1, np.int64)
            for s in range(len(old_map) - 1, -1, -1):
                old_primary[int(old_map[s])] = s
            gather_idx = old_primary[new_map]
            assert (gather_idx >= 0).all(), "new placement names an unknown expert"
            moe = dict(blocks["moe"])
            for name in ("w_gate", "w_up", "w_down"):
                moe[name] = blocks["moe"][name][:, gather_idx]
            blocks = dict(blocks)
            blocks["moe"] = moe
            self.params = dict(self.params)
            self.params["blocks"] = blocks
            self._applied_map = new_map.copy()
