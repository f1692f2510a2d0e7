"""Host-side pool mechanics of the paged KV cache (fast lane: no model
forwards, no Pallas) — free-list order, refcounted prefix sharing,
copy-on-write, int8 page storage, and the core's distinct-block accounting
driven through a cost-model SimEngine."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.prefix_cache import block_hashes
from repro.models.config import ModelConfig
from repro.serving.kvcache import (PagedKVCache, SlotKVCache, batch_axes,
                                   write_slot)
from repro.training.compression import dequantize_int8


def tiny():
    return ModelConfig(name="t", family="moe", num_layers=2, d_model=32,
                       num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                       vocab_size=64, num_experts=4, moe_top_k=2, moe_d_ff=32,
                       capacity_factor=8.0, dtype="float32")


# --- SlotKVCache free-list ----------------------------------------------------

def test_slot_alloc_lowest_first_and_free_order():
    kv = SlotKVCache(tiny(), max_slots=4, max_seq=32)
    assert [kv.alloc() for _ in range(4)] == [0, 1, 2, 3]
    assert kv.alloc() is None and kv.num_free == 0
    kv.free(2)
    kv.free(0)
    assert kv.num_free == 2
    assert kv.alloc() == 0          # lowest free wins, not LIFO
    assert kv.alloc() == 2


def test_slot_free_is_idempotent():
    kv = SlotKVCache(tiny(), max_slots=3, max_seq=32)
    s = kv.alloc()
    kv.free(s)
    kv.free(s)                      # double-free must not duplicate the slot
    assert kv.num_free == 3
    assert sorted(kv.alloc() for _ in range(3)) == [0, 1, 2]
    assert kv.alloc() is None


def test_write_slot_explicit_axes():
    """write_slot takes the batch axis explicitly (int or per-leaf tree) and
    honours the skip sentinel for batch-independent leaves."""
    cache = {"a": jnp.zeros((4, 8)), "b": jnp.ones((3,))}
    sub = {"a": jnp.full((1, 8), 7.0), "b": jnp.zeros((3,))}
    out = write_slot(cache, sub, 2, {"a": 0, "b": -1})
    a = np.asarray(out["a"])
    assert (a[2] == 7.0).all()
    assert (a[[0, 1, 3]] == 0.0).all()
    np.testing.assert_array_equal(np.asarray(out["b"]), 1.0)   # skipped


def test_batch_axes_structural_discovery():
    import jax
    axes = batch_axes(tiny(), max_slots=4, max_seq=32)
    leaves = set(jax.tree_util.tree_leaves(axes))
    assert leaves <= {0, 1, -1} and any(a >= 0 for a in leaves)


# --- PagedKVCache pool --------------------------------------------------------

def make_paged(**kw):
    return PagedKVCache(tiny(), max_slots=4, max_seq=64, block_size=16, **kw)


def test_paged_rejects_heterogeneous_stacks():
    with pytest.raises(ValueError):
        PagedKVCache(dataclasses.replace(tiny(), first_k_dense=1),
                     max_slots=4, max_seq=64)


def test_paged_geometry_and_private_alloc():
    kv = make_paged()
    assert kv.capacity_tokens == 4 * 4 * 16 and kv.blocks_used == 0
    s = kv.alloc(40)                          # 3 blocks, no token sharing
    assert s == 0 and kv.blocks_used == 3
    # page 0 is the reserved garbage page: never handed out
    assert (kv.block_tables[s, :3] > 0).all()
    kv.free(s)
    assert kv.blocks_used == 0 and kv.num_free == 4


def test_paged_prefix_sharing_pins_not_copies():
    kv = make_paged()
    toks = list(np.random.default_rng(0).integers(0, 64, 40))
    s0 = kv.alloc(40, toks)                   # 2 full blocks + 1 partial
    assert kv.blocks_used == 3 and kv.shared_hits == 0
    s1 = kv.alloc(40, toks)
    # the two full prompt blocks are pinned, only the partial is private
    assert kv.shared_hits == 2
    assert kv.blocks_used == 4                # NOT 6: shared counted once
    np.testing.assert_array_equal(kv.block_tables[s0, :2],
                                  kv.block_tables[s1, :2])
    assert kv.block_tables[s0, 2] != kv.block_tables[s1, 2]
    # releases are refcounted: shared pages survive the first free
    kv.free(s0)
    assert kv.blocks_used == 3
    kv.free(s1)
    assert kv.blocks_used == 0
    # hashes deregistered at ref 0: a fresh alloc shares nothing
    kv.alloc(40, toks)
    assert kv.shared_hits == 2 and kv.blocks_used == 3


def test_paged_divergent_suffix_shares_leading_run_only():
    kv = make_paged()
    toks = list(np.random.default_rng(1).integers(0, 64, 48))
    other = list(toks[:16]) + list((np.asarray(toks[16:]) + 1) % 64)
    kv.alloc(48, toks)
    s1 = kv.alloc(48, other)
    assert kv._slot_shared[s1] == 1           # chained hashes stop at block 1
    assert kv.blocks_used == 5                # 3 + 2 private


def test_paged_append_allocates_and_cows():
    kv = make_paged()
    toks = list(np.random.default_rng(2).integers(0, 64, 32))
    s0 = kv.alloc(32, toks)
    s1 = kv.alloc(32, toks)                   # both blocks shared, ref 2
    assert kv.blocks_used == 2
    # append at a block boundary: fresh private page
    kv.slot_len[s0] = 32
    kv.prepare_append(s0)
    assert kv.blocks_used == 3 and kv._slot_nblocks[s0] == 3
    # append INTO a shared page: copy-on-write, the peer keeps the original
    old = int(kv.block_tables[s1, 1])
    kv.slot_len[s1] = 20
    kv.prepare_append(s1)
    new = int(kv.block_tables[s1, 1])
    assert new != old and kv._ref[old] == 1 and kv._ref[new] == 1
    assert int(kv.block_tables[s0, 1]) == old
    assert kv.blocks_used == 4


def test_paged_int8_prefill_roundtrip():
    kv = make_paged(quantize=True)
    assert kv.pages["k"].dtype == jnp.int8
    rng = np.random.default_rng(3)
    L, S, H, D = 2, 32, 2, 16
    cache = {"layers": {n: jnp.asarray(rng.normal(size=(L, 1, S, H, D)),
                                       jnp.float32) for n in ("k", "v")}}
    s = kv.alloc(32)
    kv.write_prefill(s, cache)
    for n in ("k", "v"):
        phys = kv.block_tables[s, :2]
        got = dequantize_int8(kv.pages[n][:, phys],
                              kv.pages[n + "_scale"][:, phys, None, None, None])
        want = np.asarray(cache["layers"][n][:, 0]).reshape(
            L, 2, 16, H, D).transpose(0, 1, 3, 2, 4)     # head-major pages
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-2)
    # scale bookkeeping doubles the byte accounting honestly
    assert kv.kv_bytes_used() > 0


def test_paged_capacity_check_blocks_unshared_overflow():
    kv = make_paged()
    for _ in range(4):
        assert kv.alloc(64) is not None       # fills all 16 blocks
    assert kv.alloc(16) is None               # no slot AND no blocks
    assert kv.blocks_used == kv.usable_blocks
    assert kv.usage() == 1.0


# --- SchedulerCore distinct-block accounting (cost-model plane) ---------------

def _sim(kv_pool_tokens, bs=16):
    from repro.core.gimbal import make_sim_expert_level
    from repro.core.types import GimbalConfig
    from repro.sim.costmodel import CostModel, PROFILES
    from repro.sim.simulator import SimEngine
    gcfg = GimbalConfig(tau=10_000, theta_age=1.0)
    cfg = tiny()
    eng = SimEngine(0, CostModel(cfg, PROFILES["a100"], 2, block_size=bs),
                    gcfg, sjf=True,
                    expert_level=make_sim_expert_level("gimbal", cfg, 2, gcfg),
                    prefill_budget=256, max_running=8,
                    kv_pool_tokens=kv_pool_tokens, kv_block_size=bs,
                    max_ctx_tokens=64)
    eng.core.backend.charge_prefix_hits = False
    return eng


def _req(rid, toks, max_new=4):
    from repro.core.types import Request
    return Request(req_id=rid, arrival_time=0.0, prompt_len=len(toks),
                   max_new_tokens=max_new,
                   prompt_tokens=np.asarray(toks, np.int64))


def test_core_blocks_round_up_and_gate_admission():
    eng = _sim(kv_pool_tokens=3 * 16)         # 3-block pool
    rng = np.random.default_rng(5)
    # two 17-token prompts: 34 tokens would FIT a token gate, but each costs
    # ceil(18/16) = 2 distinct blocks -> only one is admissible
    for i in range(2):
        eng.submit(_req(i, rng.integers(0, 64, 17)), 0.0)
    eng.step(0.0)
    assert eng.core.num_running() == 1
    assert eng.core.kv_blocks == 2
    kinds = [k for k, _, _ in eng.core.event_log()]
    assert kinds.count("admit") == 1


def test_core_shared_prefix_blocks_not_double_counted():
    eng = _sim(kv_pool_tokens=3 * 16)         # 3-block pool again
    toks = list(np.random.default_rng(6).integers(0, 64, 17))
    # same 17-token prompt: block 0 is pinned, each costs 1 private block ->
    # BOTH fit in 3 blocks (1 shared + 2 private) where unshared ones did not
    for i in range(2):
        eng.submit(_req(i, toks), 0.0)
    eng.step(0.0)
    assert eng.core.num_running() == 2
    assert eng.core.kv_blocks == 3
    assert eng.core._shared_refs == {block_hashes(toks, 16)[0]: 2}
    # finishing returns every block, shared ones on the LAST unpin
    for t in range(1, 8):
        eng.step(float(t))
    assert eng.core.num_running() == 0
    assert eng.core.kv_blocks == 0 and not eng.core._shared_refs


def test_core_block_mode_metrics_read_block_occupancy():
    eng = _sim(kv_pool_tokens=8 * 16)
    eng.submit(_req(0, list(np.random.default_rng(7).integers(0, 64, 17))), 0.0)
    eng.step(0.0)
    m = eng.metrics(0.0)
    # 2 blocks of 8 = 32/128 tokens -- NOT the 18-token sum
    assert m.kv_usage == pytest.approx(eng.core.kv_blocks * 16 / (8 * 16))
