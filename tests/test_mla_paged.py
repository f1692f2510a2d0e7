"""DeepSeek-V2 on the paged path, at a tiny size on the CPU: YaRN rope on
interleaved pairs, group-limited routing over a held share of the routed
experts, a dense prologue, shared experts, latent pages and the latent
decode kernel (Pallas, interpreted), checked against the benchmark's plain
reference (benchmarks/chip/references/mla_moe.py) and by hand."""
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import spec, weights
from repro.configs import get_config
from repro.core.eplb import ExpertRebalancer
from repro.core.types import Request
from repro.kernels.flash_decode import flash_decode_latent
from repro.kernels.topk_router import topk_router_replicated
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models.config import ModelConfig, YarnScaling
from repro.models.layers import ffn_apply, rope_freqs, yarn_correction_range
from repro.serving.backend import JaxBackend
from repro.serving.kvcache import PagedKVCache

ROOT = Path(__file__).resolve().parents[1]
BENCH_CONFIG = ROOT / "benchmarks" / "chip" / "configs" / "deepseek-v2-d5-ep8.json"

# The benchmark configuration's keys at a tiny size: 32 routed experts in 8
# groups of 4, the chip holding group 0 (experts 0-3), top-3 from the best
# 3 groups, gates x16, YaRN, one dense layer then two MoE layers.  float32,
# so that program and reference differ only by the order of summation.
TINY = dict(json.loads(BENCH_CONFIG.read_text()), name="tiny-dsv2",
            hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
            q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
            moe_intermediate_size=24, router_experts=32, n_routed_experts=4,
            num_experts_per_tok=3, num_hidden_layers=3, vocab_size=128,
            torch_dtype="float32",
            serving={"max_slots": 3, "max_seq": 64, "page_size": 16,
                     "prefill_budget": 64, "capacity_factor": 32 / 3})


def _tiny_cfg(**kw) -> ModelConfig:
    return ModelConfig(**dict(spec.arch_module(TINY).model_fields(TINY), **kw))


# ------------------------------------------------------------- constants
def test_yarn_constants_by_hand():
    """DeepSeek-V2's published YaRN: correction dims 10 and 23 of the 64
    rope dims; softmax scale 192**-0.5 * (0.1 * 0.707 * ln 40 + 1)**2."""
    cfg = get_config("deepseek-v2-236b")
    ys = cfg.rope_scaling
    assert yarn_correction_range(64, 10000.0, ys) == (10, 23)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale == pytest.approx(1.26080, abs=1e-5)
    assert cfg.attn_softmax_scale == pytest.approx(0.114721, abs=1e-6)
    f = np.asarray(rope_freqs(64, 10000.0, ys))
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    # dims below 10 extrapolate (plain), above 23 interpolate (plain / 40)
    np.testing.assert_allclose(f[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-6)
    ramp = (np.arange(10, 23) - 10) / 13
    np.testing.assert_allclose(
        f[10:23], plain[10:23] / 40 * ramp + plain[10:23] * (1 - ramp),
        rtol=1e-6)
    ref = spec.reference_module(TINY)
    np.testing.assert_allclose(f, ref.yarn_inv_freq(
        64, 10000.0, json.loads(BENCH_CONFIG.read_text())["rope_scaling"]),
        rtol=1e-6)     # f32 against the reference's f64
    assert ref.softmax_scale(json.loads(BENCH_CONFIG.read_text())) == \
        pytest.approx(0.114721, abs=1e-6)


def test_published_config_fields():
    cfg = get_config("deepseek-v2-236b")
    assert (cfg.n_group, cfg.topk_group, cfg.routed_scaling_factor,
            cfg.norm_topk_prob, cfg.rope_interleave) == (8, 3, 16.0, False, True)
    assert cfg.rope_scaling == YarnScaling(40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    # a plain config keeps the defaults: one group, no scaling, renormalise
    plain = ModelConfig()
    assert (plain.n_group, plain.routed_scaling_factor, plain.norm_topk_prob,
            plain.rope_scaling, plain.holds_share) == (1, 1.0, True, None, False)


# --------------------------------------------------------------- routing
def test_group_limited_routing_by_hand():
    """8 experts in 4 groups of 2, top-2 from the best 2 groups, x16."""
    cfg = ModelConfig(family="moe", num_experts=8, moe_top_k=2, n_group=4,
                      topk_group=2, routed_scaling_factor=16.0,
                      norm_topk_prob=False)
    p = jnp.asarray([[0.05, 0.30, 0.20, 0.01, 0.12, 0.11, 0.15, 0.06]])
    # group bests 0.30, 0.20, 0.12, 0.15 -> groups 0 and 1; inside them the
    # top two are experts 1 (0.30) and 2 (0.20); expert 6 (0.15, the third
    # largest overall) is in group 3, which is not chosen
    gates, ids = moe_lib.route(p, cfg)
    assert ids.tolist() == [[1, 2]]
    np.testing.assert_allclose(gates, [[16 * 0.30, 16 * 0.20]], rtol=1e-6)
    plain = cfg.replace(n_group=1, routed_scaling_factor=1.0,
                        norm_topk_prob=True)
    gates, ids = moe_lib.route(p, plain)
    assert ids.tolist() == [[1, 2]]
    np.testing.assert_allclose(gates, [[0.6, 0.4]], rtol=1e-6)


@pytest.mark.parametrize("t", [16, 300])                  # decode, prefill
def test_grouped_router_kernel_matches_route_over_a_held_share(t):
    """The Pallas router (interpreted): group-limited top-k of 32 experts in
    8 groups, x16 without renormalising; experts 4.. held elsewhere take the
    sentinel slot and no capacity position."""
    cfg = _tiny_cfg()
    logits = jax.random.normal(jax.random.key(3), (t, 32), jnp.float32)
    gates, ids = moe_lib.route(jax.nn.softmax(logits, -1), cfg)
    plc = moe_lib.ExpertPlacement.identity(4)
    rs = jnp.concatenate([plc.replica_slots, jnp.full((28, 1), 4, jnp.int32)])
    rc = jnp.concatenate([plc.replica_count, jnp.ones((28,), jnp.int32)])
    kg, kid, kslot, kpos = topk_router_replicated(
        logits, 3, rs, rc, 4, interpret=True, n_group=8, topk_group=3,
        scale=16.0, renorm=False)
    np.testing.assert_array_equal(kid, ids)
    np.testing.assert_allclose(kg, gates, rtol=1e-6)     # same f32 softmax
    held = np.asarray(ids) < 4
    np.testing.assert_array_equal(np.asarray(kslot)[~held], 4)
    np.testing.assert_array_equal(np.asarray(kslot)[held], np.asarray(ids)[held])
    # positions count the earlier held selections of the same expert only
    flat, pos = np.asarray(ids).reshape(-1), np.asarray(kpos).reshape(-1)
    for e in range(4):
        np.testing.assert_array_equal(pos[flat == e], np.arange((flat == e).sum()))


@pytest.mark.parametrize("mode", ["gather", "fused"])
def test_the_shares_add_up_to_the_whole_layer(mode):
    """All 8 shares of a group-limited MoE layer (share s holds group s,
    rolled to experts 0-3 with the router's columns), their shared experts
    counted once, give the uncut layer."""
    full = _tiny_cfg(num_experts=32, n_routed_experts=0)
    part = _tiny_cfg()
    p = moe_lib.init_moe(jax.random.key(1), full)
    x = jax.random.normal(jax.random.key(2), (2, 5, 64), jnp.float32)
    whole, _ = moe_lib.moe_apply(p, full, x, dispatch_mode=mode,
                                 interpret=True)
    shared = ffn_apply(p["shared"], x)
    total = shared
    for s in range(8):
        ps = {"w_router": jnp.roll(p["w_router"], -4 * s, axis=1),
              "shared": p["shared"],
              **{n: p[n][4 * s:4 * s + 4] for n in ("w_gate", "w_up", "w_down")}}
        y, aux = moe_lib.moe_apply(ps, part, x, dispatch_mode=mode,
                                   interpret=True, return_stats=True)
        total = total + (y - shared)
        assert float(aux["dropped_frac"]) == 0.0      # dropless for held rows
    # float32 sums in another order: 1e-5 of outputs of size ~1
    np.testing.assert_allclose(total, whole, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------- kernel
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_latent_kernel_matches_absorbed_xla_decode(dtype):
    """mla_decode_paged through flash_decode_latent (interpreted) against
    mla_decode(absorb=True) over a contiguous cache: ragged lengths (one
    row at 0), shuffled page tables, a layer read in place from a stack."""
    cfg = _tiny_cfg(dtype="float32" if dtype == jnp.float32 else "bfloat16")
    p = attn.init_mla(jax.random.key(4), cfg)
    b, bs, nb, s_max, L = 4, 16, 4, 64, 3
    lengths = np.array([37, 0, 63, 16], np.int32)
    ks = jax.random.split(jax.random.key(5), 3)
    ckv = jax.random.normal(ks[0], (b, s_max, 16)).astype(dtype)
    krope = jax.random.normal(ks[1], (b, s_max, 8)).astype(dtype)
    x = jax.random.normal(ks[2], (b, 1, 64)).astype(dtype)
    perm = np.random.default_rng(0).permutation(b * nb) + 1   # page 0: garbage
    tables = perm.reshape(b, nb).astype(np.int32)
    pages = {n: jnp.zeros((L, b * nb + 1, bs, w), dtype)
             for n, w in (("ckv", 16), ("krope", 8))}
    for n, src in (("ckv", ckv), ("krope", krope)):
        blocks = src.reshape(b * nb, bs, -1)
        pages[n] = pages[n].at[1, tables.reshape(-1)].set(blocks)
    want, cache = attn.mla_decode(p, cfg, x, {"ckv": ckv, "krope": krope},
                                  jnp.asarray(lengths), absorb=True)
    for kernel in (True, False):
        got, new = attn.mla_decode_paged(p, cfg, x, pages, jnp.int32(1),
                                         jnp.asarray(tables),
                                         jnp.asarray(lengths), kernel, True)
        # float32: summation order only; bfloat16: the reference rounds its
        # softmax weights to bf16 before the value sum, the kernel keeps f32
        tol = 1e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=tol, rtol=tol)
        # the new token's latents went to its page, in layer 1 only
        row, pos = 2, int(lengths[2])
        page = tables[row, pos // bs]
        np.testing.assert_array_equal(new["ckv"][1, page, pos % bs],
                                      cache["ckv"][row, pos])
        np.testing.assert_array_equal(new["ckv"][0], pages["ckv"][0])


def test_latent_kernel_zero_length_row_is_zero():
    b, h, r, dr, bs, nb = 2, 4, 16, 8, 16, 2
    ks = jax.random.split(jax.random.key(6), 4)
    pages_c = jax.random.normal(ks[0], (1, 5, bs, r))
    pages_r = jax.random.normal(ks[1], (1, 5, bs, dr))
    out = flash_decode_latent(
        jax.random.normal(ks[2], (b, h, r)), jax.random.normal(ks[3], (b, h, dr)),
        pages_c, pages_r, jnp.int32(0), jnp.asarray([[1, 2], [3, 4]], jnp.int32),
        jnp.asarray([5, 0], jnp.int32), scale=0.1, interpret=True)
    assert np.all(np.asarray(out[1]) == 0.0)
    assert np.all(np.isfinite(np.asarray(out[0])))


# ------------------------------------------------------------ pool, level
def test_latent_pool_layout_and_accounting():
    """Latent pages of every layer (the dense one too), counted as
    ModelConfig.kv_bytes_per_token counts them; int8 latents refused."""
    cfg = _tiny_cfg()
    kv = PagedKVCache(cfg, 3, 64, block_size=16)
    assert kv.pages["ckv"].shape == (3, 3 * 4 + 1, 16, 16)
    assert kv.pages["krope"].shape == (3, 3 * 4 + 1, 16, 8)
    kv.alloc(40)
    assert kv.blocks_used == 3
    assert kv.kv_bytes_used() == kv.blocks_used * 16 * cfg.kv_bytes_per_token()
    assert cfg.kv_bytes_per_token() == 3 * (16 + 8) * 4
    with pytest.raises(ValueError):
        PagedKVCache(cfg, 3, 64, quantize=True)
    with pytest.raises(ValueError):        # a GQA prologue has no paged path
        PagedKVCache(cfg.replace(attention_type="gqa"), 3, 64)


def test_expert_level_counts_and_places_the_held_experts_only():
    cfg = _tiny_cfg()
    rb = ExpertRebalancer(cfg, 2)
    assert rb.num_slots == 4
    ids = np.array([[[[0, 5, 31]], [[3, 3, 9]]],
                    [[[1, 2, 30]], [[0, 7, 8]]]])       # (L=2, B=2, 1, k=3)
    rb.observe(ids)
    np.testing.assert_array_equal(rb.tracker.A, [[1, 0, 0, 2], [1, 1, 1, 0]])
    # inter-layer pairs among held experts only: 0->1, 0->2, 3->0, 3->0
    assert rb.tracker.W.sum() == 4 and rb.tracker.W[3, 0] == 2


# ------------------------------------------------------ through the backend
def _served(seed: int, prompt_len: int, steps: int):
    """Serve one prompt and ``steps`` decode steps through JaxBackend (paged
    latents, fused router -> moe_gemm, latent kernel; kernels interpreted)
    beside a second, shorter request; the logits of every served token."""
    cfg = _tiny_cfg()
    params = weights.make(TINY, seed)
    rb = ExpertRebalancer(cfg, 2)
    be = JaxBackend(cfg, params, max_slots=3, max_seq=64, dispatch_mode="fused",
                    rebalancer=rb, kv_layout="paged", kv_block_size=16,
                    use_kernels=True)
    got = {}
    prefill, decode = be._prefill_for_bucket, be._jit_decode_paged

    def on_prefill(bl):
        fn = prefill(bl)
        return lambda *a: got.setdefault("rows", []).append(fn(*a)) or got["rows"][-1]

    def on_decode(*a):
        out = decode(*a)
        got.setdefault("steps", []).append(np.asarray(out[0]))
        return out
    be._prefill_for_bucket, be._jit_decode_paged = on_prefill, on_decode
    rng = np.random.default_rng(seed)
    reqs = [Request(req_id=i, prompt_len=n, max_new_tokens=steps + 1,
                    arrival_time=0.0,
                    prompt_tokens=rng.integers(0, 128, n).astype(np.int32))
            for i, n in enumerate((prompt_len, 9))]
    slots = [be.start(r, 0.0)[0] for r in reqs]
    for _ in range(steps):
        be.decode(list(zip(slots, reqs)), 0.0)
    return params, reqs, slots, got, be


def test_backend_serves_deepseek_v2_as_the_reference_computes_it():
    """The prefill's logits and then several paged decode steps' match the
    reference's full forward at every served position, for two rows of
    different lengths (a page boundary crossed on the way)."""
    ref = spec.reference_module(TINY)
    params, reqs, slots, got, be = _served(seed=11, prompt_len=14, steps=5)
    assert be.kv.blocks_used == 2 + 1
    for i, (r, slot) in enumerate(zip(reqs, slots)):
        prog = np.stack([np.asarray(got["rows"][i][0])]
                        + [s[slot] for s in got["steps"]])
        seq = np.concatenate([r.prompt_tokens, np.asarray(r.output_tokens[:-1],
                                                          np.int32)])
        pos = np.arange(r.prompt_len - 1, r.prompt_len - 1 + len(r.output_tokens))
        want, _ = ref.logits_at(params, TINY, seq, pos)
        want = np.asarray(want)[:len(pos)]
        # float32 on both sides at "highest": 1e-4 of logits of size ~3 is
        # summation order; the reference's int8 control differs by ~0.1
        np.testing.assert_allclose(prog, want, atol=1e-4, rtol=1e-4)
        assert r.output_tokens == want.argmax(-1).tolist()
        low, _ = ref.logits_at(params, TINY, seq, pos, "int8")
        assert np.abs(np.asarray(low)[:len(pos)] - want).max() > 1e-2


def test_backend_records_held_rows_and_latent_tokens():
    from repro.core import trace
    trace.enable()
    try:
        _, reqs, slots, got, be = _served(seed=12, prompt_len=20, steps=2)
    finally:
        trace.disable()
    recs = trace.drain()
    pre = [r for r in recs if r.name == "repro:backend.prefill"]
    dec = [r for r in recs if r.name == "repro:backend.decode"]
    # every prompt token routes 3 selections per MoE layer (2 layers); the
    # held ones are a share of them
    assert all(0 <= r.attrs["held_rows"] <= 3 * 2 * r.attrs["plen"] for r in pre)
    assert sum(r.attrs["held_rows"] for r in pre) > 0
    # decode: the two rows' resident tokens plus their new ones
    assert [r.attrs["latent_tokens"] for r in dec] == [20 + 9 + 2, 20 + 9 + 4]
    assert all(0 <= r.attrs["held_rows"] <= 3 * 2 * 2 for r in dec)


# ------------------------------------------------ the benchmark's config
def test_benchmark_config_is_the_programs_layout_at_published_widths():
    """deepseek-v2-d5-ep8: the arch's fields and seeded tree take the
    program's layout (abstract: nothing allocated); 8.13 GB of weights."""
    from repro.models import model as M
    config = json.loads(BENCH_CONFIG.read_text())
    cfg = ModelConfig(**spec.arch_module(config).model_fields(config))
    assert (cfg.num_layers, cfg.first_k_dense, cfg.num_experts,
            cfg.router_width, cfg.moe_top_k, cfg.n_group, cfg.topk_group,
            cfg.kv_bytes_per_token()) == (5, 1, 20, 160, 6, 8, 3, 5 * 1152)
    assert cfg.attn_softmax_scale == pytest.approx(0.114721, abs=1e-6)
    assert cfg.capacity_factor * cfg.moe_top_k >= cfg.router_width  # dropless
    ours = weights.shapes(config)
    theirs = M.abstract_params(cfg)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(ours))
    assert nbytes / 1e9 == pytest.approx(8.13, abs=0.01)


def test_benchmark_work_counts_by_hand():
    import types
    config = json.loads(BENCH_CONFIG.read_text())
    arch = spec.arch_module(config)
    dec = types.SimpleNamespace(rows=2, lengths=np.array([9, 19]),
                                experts=np.array([[[[0, 21, 19]], [[40, 3, 3]]]] * 4))
    # 30 tokens attended in each of 5 layers: 2*128*(576+512) FLOPs and
    # (512+64)*2 bytes each
    assert arch.kernel_work(config, "flash_decode", dec) == (
        5 * 30 * 2 * 128 * 1088, 5 * 30 * 1152)
    # 4 held rows per layer (0, 19; 3, 3) over 4 layers, 3 distinct experts
    flops, nbytes = arch.kernel_work(config, "moe_gemm", dec)
    assert flops == 16 * 6 * 5120 * 1536
    assert nbytes == 12 * 3 * 5120 * 1536 * 2 + 16 * 3 * (5120 + 1536) * 2
    # the program's counter wins over the ids
    dec.held_rows = 10
    assert arch.kernel_work(config, "moe_gemm", dec)[0] == 10 * 6 * 5120 * 1536
