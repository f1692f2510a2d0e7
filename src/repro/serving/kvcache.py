"""KV-cache management for the serving engine.

Three layers:
  * SlotKVCache — the legacy device cache: fixed decode slots (JetStream-style
    TPU serving layout; static shapes for XLA).  Wraps models.init_cache and
    tracks per-slot occupancy.  `usage()` is the KV-usage signal Alg. 1 reads;
    for SSM/hybrid archs it generalizes to state-slot occupancy (DESIGN.md §4).
  * PagedKVCache — vLLM-style paged device cache: a global pool of
    `block_size`-token pages, per-slot block tables, refcounted copy-on-write
    prefix sharing keyed by core/prefix_cache.block_hashes, and optional int8
    page storage with per-(layer, page) scales (docs/kernels.md).
  * BlockLedger — block accounting (host-side bookkeeping) used for the
    prefix cache and the simulator's KV-pressure model.
"""
from __future__ import annotations

import heapq
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.prefix_cache import block_hashes
from repro.models import config as mcfg
from repro.models import model as M
from repro.training.compression import quantize_int8

_SKIP = -1  # write_slot axis sentinel: leaf has no batch axis, leave untouched


def batch_axes(model_cfg: mcfg.ModelConfig, max_slots: int, max_seq: int,
               dtype=None) -> Any:
    """Per-leaf batch-axis tree for a batched model cache, found structurally:
    the unique axis whose size differs between a batch=`max_slots` and a
    batch=1 cache (abstract eval only — nothing is allocated).  Leaves whose
    shape does not depend on batch get the sentinel ``-1`` (skipped by
    ``write_slot``); genuinely ambiguous leaves raise instead of silently
    guessing axis 0."""
    assert max_slots > 1, "batch-axis discovery requires max_slots > 1"
    big = jax.eval_shape(lambda: M.init_cache(model_cfg, max_slots, max_seq, dtype))
    one = jax.eval_shape(lambda: M.init_cache(model_cfg, 1, max_seq, dtype))

    def find(b, s):
        diff = [i for i, (x, y) in enumerate(zip(b.shape, s.shape)) if x != y]
        if not diff:
            return _SKIP
        if len(diff) > 1:
            raise ValueError(
                f"ambiguous batch axis for cache leaf {b.shape} vs {s.shape}")
        return diff[0]

    return jax.tree.map(find, big, one)


def write_slot(cache, slot_cache, slot, axes) -> Any:
    """Insert a batch=1 sub-cache into batch slot `slot` of the batched cache.

    `axes` names the batch axis explicitly: either a single int applied to
    every leaf, or a pytree of ints matching `cache` (as produced by
    ``batch_axes``; ``-1`` skips a leaf).  Shape-diff inference was removed —
    it silently picked axis 0 whenever shapes coincided."""
    if isinstance(axes, int):
        ax_tree = jax.tree.map(lambda _: axes, cache)
    else:
        ax_tree = axes

    def upd(c, s, ax):
        if ax == _SKIP:
            return c
        idx = [0] * c.ndim
        idx[ax] = slot
        return jax.lax.dynamic_update_slice(c, s.astype(c.dtype), tuple(idx))

    return jax.tree.map(upd, cache, slot_cache, ax_tree)


class SlotKVCache:
    def __init__(self, model_cfg: mcfg.ModelConfig, max_slots: int, max_seq: int,
                 dtype=None, device=None):
        assert max_slots > 1, "slot cache requires max_slots > 1"
        self.model_cfg = model_cfg
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.cache = M.init_cache(model_cfg, max_slots, max_seq, dtype)
        if device is not None:
            self.cache = jax.device_put(self.cache, device)
        self.write_axes = batch_axes(model_cfg, max_slots, max_seq, dtype)
        self.slot_len = np.zeros(max_slots, np.int64)     # tokens resident per slot
        self._free_heap: List[int] = list(range(max_slots))  # sorted => valid heap
        self._is_free = [True] * max_slots

    # --- allocation -------------------------------------------------------------
    def alloc(self) -> Optional[int]:
        """Lowest free slot index, via an explicit min-heap free-list (O(log n)
        instead of the old O(max_slots) scan; same lowest-first order)."""
        if not self._free_heap:
            return None
        i = heapq.heappop(self._free_heap)
        self._is_free[i] = False
        self.slot_len[i] = 0
        return i

    def free(self, slot: int) -> None:
        if not self._is_free[slot]:
            self._is_free[slot] = True
            heapq.heappush(self._free_heap, slot)
        self.slot_len[slot] = 0

    @property
    def num_free(self) -> int:
        return len(self._free_heap)

    # --- metrics (Alg. 1 signal) --------------------------------------------------
    def usage(self) -> float:
        """Fraction of KV capacity in use.  Attention archs: resident tokens /
        total token capacity.  Pure-SSM archs: occupied slots / slots (state is
        constant-size per sequence)."""
        if self.model_cfg.num_attention_layers() == 0:
            return 1.0 - self.num_free / self.max_slots
        return float(self.slot_len.sum()) / (self.max_slots * self.max_seq)

    def kv_bytes_used(self) -> int:
        return int(self.slot_len.sum()) * self.model_cfg.kv_bytes_per_token()

    def positions(self) -> np.ndarray:
        """(max_slots,) int32 write position per slot (host array)."""
        return np.minimum(self.slot_len, self.max_seq - 1).astype(np.int32)


class PagedKVCache:
    """Paged device KV cache for homogeneous GQA attention stacks, and for
    MLA stacks with a dense prologue (deepseek-v2).

    GQA layout: per-layer head-major K/V pages of shape (L, P, Hkv, BS, D) where
    P is the global pool size and BS the block size (head-major so the
    flash-decode kernel's (BS, D) page tile is a legal TPU block; see
    kernels/flash_decode.py).  Physical page 0 is a reserved
    garbage page: free/inactive slots' block-table rows point at it, so the
    full-batch decode scatter lands harmlessly there.  Full prompt blocks are
    refcounted and shared across slots keyed by the same chained block hashes
    the prefix cache uses (causal attention => identical prefixes produce
    identical K/V pages); a prefix hit pins the resident pages instead of
    re-writing them.  Optional int8 storage keeps a per-(layer, page) scale,
    quantized with training/compression.py::quantize_int8.  The pool lives
    on ``device`` (default: JAX's default device).

    MLA layout: the compressed latents, {"ckv": (L, P, BS, kv_lora_rank),
    "krope": (L, P, BS, qk_rope_head_dim)}, over all L layers (the dense
    prologue's included): (R + Dr) x itemsize bytes per token and layer, as
    ``ModelConfig.kv_bytes_per_token`` counts.  No int8 storage.
    """

    def __init__(self, model_cfg: mcfg.ModelConfig, max_slots: int, max_seq: int,
                 *, block_size: int = 16, total_blocks: Optional[int] = None,
                 dtype=None, quantize: bool = False, device=None):
        cfg = model_cfg
        if (cfg.attention_type not in ("gqa", "mla") or cfg.is_ssm
                or cfg.is_hybrid or cfg.is_encoder_decoder):
            raise ValueError("PagedKVCache supports GQA and MLA stacks only")
        self.latent = cfg.attention_type == "mla"
        if cfg.is_moe and (cfg.moe_every != 1 or (
                cfg.first_k_dense != 0 and not self.latent)):
            raise ValueError("PagedKVCache requires a homogeneous layer stack "
                             "(moe_every == 1; a dense prologue for MLA only)")
        if self.latent and quantize:
            raise ValueError("PagedKVCache has no int8 latent pages")
        assert max_slots > 1 and block_size > 0
        self.model_cfg = cfg
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.block_size = block_size
        self.quantized = quantize
        self.max_blocks = -(-max_seq // block_size)
        self.usable_blocks = total_blocks or max_slots * self.max_blocks
        assert self.usable_blocks >= max_slots * self.max_blocks, \
            "pool must cover max_slots full-length sequences (admission is " \
            "gated upstream by SchedulerCore block accounting)"
        n_pages = self.usable_blocks + 1                      # + garbage page 0
        L = cfg.num_layers
        hkv, d = cfg.num_kv_heads, cfg.head_dim
        store = jnp.int8 if quantize else (dtype or cfg.adtype)
        if self.latent:
            widths = {"ckv": cfg.kv_lora_rank, "krope": cfg.qk_rope_head_dim}
            self.pages: Dict[str, jnp.ndarray] = {
                n: jnp.zeros((L, n_pages, block_size, w), store, device=device)
                for n, w in widths.items()}
        else:
            self.pages = {
                "k": jnp.zeros((L, n_pages, hkv, block_size, d), store,
                               device=device),
                "v": jnp.zeros((L, n_pages, hkv, block_size, d), store,
                               device=device),
            }
        if quantize:
            for name in ("k_scale", "v_scale"):
                self.pages[name] = jnp.zeros((L, n_pages), jnp.float32,
                                             device=device)

        self.block_tables = np.zeros((max_slots, self.max_blocks), np.int32)
        self.slot_len = np.zeros(max_slots, np.int64)
        self._free_slots: List[int] = list(range(max_slots))
        self._is_free = [True] * max_slots
        self._free_blocks: List[int] = list(range(1, n_pages))
        self._ref = np.zeros(n_pages, np.int32)
        self._block_hash: Dict[int, int] = {}   # page -> chained block hash
        self._hash_block: Dict[int, int] = {}   # chained block hash -> page
        self._slot_nblocks = np.zeros(max_slots, np.int32)
        self._slot_shared = np.zeros(max_slots, np.int32)
        # counters for tests / metrics
        self.shared_hits = 0

    # --- pool geometry ----------------------------------------------------------
    @property
    def capacity_tokens(self) -> int:
        return self.usable_blocks * self.block_size

    @property
    def blocks_used(self) -> int:
        return self.usable_blocks - len(self._free_blocks)

    @property
    def num_free(self) -> int:
        return len(self._free_slots)

    # --- allocation -------------------------------------------------------------
    def alloc(self, plen: int,
              tokens: Optional[Sequence[int]] = None) -> Optional[int]:
        """Allocate a slot plus pages for a `plen`-token prompt.  When `tokens`
        is given, leading full blocks already resident (same chained hashes)
        are pinned (refcount++) instead of allocated; the caller then skips
        re-writing them (`write_prefill` does this automatically)."""
        if not self._free_slots:
            return None
        n_total = -(-plen // self.block_size)
        hashes = block_hashes(tokens[:plen], self.block_size) \
            if tokens is not None else []
        n_shared = 0
        for h in hashes:
            if h in self._hash_block:
                n_shared += 1
            else:
                break
        if n_total - n_shared > len(self._free_blocks):
            return None
        slot = heapq.heappop(self._free_slots)
        self._is_free[slot] = False
        self.block_tables[slot, :] = 0
        for i in range(n_total):
            if i < n_shared:
                blk = self._hash_block[hashes[i]]
                self._ref[blk] += 1
                self.shared_hits += 1
            else:
                blk = heapq.heappop(self._free_blocks)
                self._ref[blk] = 1
                if i < len(hashes) and hashes[i] not in self._hash_block:
                    self._hash_block[hashes[i]] = blk
                    self._block_hash[blk] = hashes[i]
            self.block_tables[slot, i] = blk
        self._slot_nblocks[slot] = n_total
        self._slot_shared[slot] = n_shared
        self.slot_len[slot] = 0
        return slot

    def _deref(self, blk: int) -> None:
        self._ref[blk] -= 1
        if self._ref[blk] == 0:
            h = self._block_hash.pop(blk, None)
            if h is not None and self._hash_block.get(h) == blk:
                del self._hash_block[h]
            heapq.heappush(self._free_blocks, blk)

    def free(self, slot: int) -> None:
        if self._is_free[slot]:
            return
        for i in range(int(self._slot_nblocks[slot])):
            self._deref(int(self.block_tables[slot, i]))
        self.block_tables[slot, :] = 0
        self._slot_nblocks[slot] = 0
        self._slot_shared[slot] = 0
        self.slot_len[slot] = 0
        self._is_free[slot] = True
        heapq.heappush(self._free_slots, slot)

    # --- device writes ----------------------------------------------------------
    def _quant(self, blocks: jnp.ndarray):
        """Per-(layer, page) int8 quantization via vmapped quantize_int8."""
        L, m = blocks.shape[:2]
        flat = blocks.reshape(L * m, -1)
        q, scale = jax.vmap(quantize_int8)(flat)
        return q.reshape(blocks.shape), scale.reshape(L, m)

    def write_prefill(self, slot: int, slot_cache) -> None:
        """Scatter a batch=1 prefill cache ({"layers": {"k": (L,1,S,Hkv,D)}},
        or for MLA {"layers": {"ckv": (L',1,S,R), ...}, "prologue": [{"ckv":
        (1,S,R), ...}]}) into this slot's non-shared pages.  Shared
        (prefix-hit) pages were pinned by `alloc` and are NOT re-written —
        that is the point."""
        bs = self.block_size
        start = int(self._slot_shared[slot])
        n = int(self._slot_nblocks[slot])
        if n == start:
            return
        phys = self.block_tables[slot, start:n].copy()
        if self.latent:
            self._write_latent(slot_cache, phys, start, n)
            return
        for name in ("k", "v"):
            src = slot_cache["layers"][name]                 # (L, 1, S, Hkv, D)
            need = n * bs
            if src.shape[2] < need:
                pad = [(0, 0)] * src.ndim
                pad[2] = (0, need - src.shape[2])
                src = jnp.pad(src, pad)
            L = src.shape[0]
            blocks = src[:, 0, start * bs:n * bs].reshape(
                L, n - start, bs, src.shape[3], src.shape[4]
            ).transpose(0, 1, 3, 2, 4)                       # head-major pages
            if self.quantized:
                q, scale = self._quant(blocks)
                self.pages[name] = self.pages[name].at[:, phys].set(q)
                self.pages[name + "_scale"] = \
                    self.pages[name + "_scale"].at[:, phys].set(scale)
            else:
                self.pages[name] = self.pages[name].at[:, phys].set(
                    blocks.astype(self.pages[name].dtype))

    def _write_latent(self, slot_cache, phys, start: int, n: int) -> None:
        """write_prefill's MLA part: the prologue's latents and the scanned
        stack's, in layer order, into token-major pages (P, BS, width)."""
        bs = self.block_size
        pro = slot_cache.get("prologue", [])
        for name in ("ckv", "krope"):
            src = jnp.concatenate([c[name][None] for c in pro]
                                  + [slot_cache["layers"][name]], axis=0)
            need = n * bs                                    # (L, 1, S, W)
            if src.shape[2] < need:
                src = jnp.pad(src, ((0, 0), (0, 0), (0, need - src.shape[2]),
                                    (0, 0)))
            blocks = src[:, 0, start * bs:need].reshape(
                src.shape[0], n - start, bs, src.shape[3])
            self.pages[name] = self.pages[name].at[:, phys].set(
                blocks.astype(self.pages[name].dtype))

    def prepare_append(self, slot: int) -> None:
        """Make the page holding position `slot_len` writable before a decode
        step: allocate a fresh private page at a block boundary, and
        copy-on-write if the target page is shared (refcount > 1)."""
        pos = min(int(self.slot_len[slot]), self.max_seq - 1)
        bidx = pos // self.block_size
        n = int(self._slot_nblocks[slot])
        if bidx >= n:
            assert bidx == n, "append skipped a block"
            assert self._free_blocks, "paged pool exhausted (admission bug)"
            blk = heapq.heappop(self._free_blocks)
            self._ref[blk] = 1
            self.block_tables[slot, bidx] = blk
            self._slot_nblocks[slot] = n + 1
            return
        blk = int(self.block_tables[slot, bidx])
        if self._ref[blk] > 1:                               # copy-on-write
            assert self._free_blocks, "paged pool exhausted (admission bug)"
            nb = heapq.heappop(self._free_blocks)
            self._ref[nb] = 1
            for name in self.pages:
                self.pages[name] = self.pages[name].at[:, nb].set(
                    self.pages[name][:, blk])
            self._deref(blk)
            self.block_tables[slot, bidx] = nb
            if bidx < self._slot_shared[slot]:
                self._slot_shared[slot] = bidx

    # --- step inputs ------------------------------------------------------------
    def positions(self) -> np.ndarray:
        """(max_slots,) int32 write position per slot (host array)."""
        return np.minimum(self.slot_len, self.max_seq - 1).astype(np.int32)

    # --- metrics (Alg. 1 signal) --------------------------------------------------
    def usage(self) -> float:
        """True block occupancy: distinct pages held / pool size.  Shared
        pages count once — this is what `ScoredRouter.w_kv` should read."""
        return self.blocks_used / max(self.usable_blocks, 1)

    def kv_bytes_used(self) -> int:
        per_block = sum(int(np.prod(p.shape[2:])) * p.dtype.itemsize * p.shape[0]
                        for n, p in self.pages.items() if not n.endswith("_scale"))
        scale_b = sum(4 * p.shape[0] for n, p in self.pages.items()
                      if n.endswith("_scale"))
        return self.blocks_used * (per_block + scale_b)


class BlockLedger:
    """vLLM-style block accounting: seq -> blocks of `block_size` tokens.
    Used for simulator KV pressure + prefix-cache hit bookkeeping."""

    def __init__(self, total_blocks: int, block_size: int = 16):
        self.total_blocks = total_blocks
        self.block_size = block_size
        self.used_blocks = 0
        self.seq_blocks: Dict[int, int] = {}

    def blocks_for(self, tokens: int) -> int:
        return -(-tokens // self.block_size)

    def can_alloc(self, tokens: int) -> bool:
        return self.used_blocks + self.blocks_for(tokens) <= self.total_blocks

    def alloc(self, seq_id: int, tokens: int) -> bool:
        need = self.blocks_for(tokens)
        if self.used_blocks + need > self.total_blocks:
            return False
        self.seq_blocks[seq_id] = need
        self.used_blocks += need
        return True

    def extend(self, seq_id: int, new_total_tokens: int) -> bool:
        """Grow a sequence to `new_total_tokens`; returns False on OOM."""
        have = self.seq_blocks.get(seq_id, 0)
        need = self.blocks_for(new_total_tokens)
        if need <= have:
            return True
        if self.used_blocks + (need - have) > self.total_blocks:
            return False
        self.used_blocks += need - have
        self.seq_blocks[seq_id] = need
        return True

    def release(self, seq_id: int) -> None:
        self.used_blocks -= self.seq_blocks.pop(seq_id, 0)

    @property
    def usage(self) -> float:
        return self.used_blocks / max(self.total_blocks, 1)
