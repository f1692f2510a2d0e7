"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_decode import flash_decode
from repro.kernels.moe_gemm import moe_gemm
from repro.kernels.topk_router import topk_router, topk_router_replicated

# compile-heavy (jits real JAX models / Pallas kernels on CPU): runs in
# the full CI job; the PR lane runs `-m 'not slow'` (see README)
pytestmark = pytest.mark.slow

TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
       jnp.bfloat16: dict(rtol=5e-2, atol=5e-2)}


@pytest.mark.parametrize("e,c,d,f", [(2, 8, 16, 32), (4, 96, 64, 160),
                                     (1, 200, 128, 96), (8, 128, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gemm_matches_ref(e, c, d, f, dtype):
    k1, k2 = jax.random.split(jax.random.key(e * 1000 + c))
    xe = jax.random.normal(k1, (e, c, d), dtype)
    w = jax.random.normal(k2, (e, d, f), dtype)
    out = moe_gemm(xe, w, interpret=True)
    want = ref.ref_moe_gemm(xe, w)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("block", [32, 128])
def test_moe_gemm_block_shapes(block):
    xe = jax.random.normal(jax.random.key(0), (3, 70, 48), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (3, 48, 90), jnp.float32)
    out = moe_gemm(xe, w, block_c=block, block_f=block, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.ref_moe_gemm(xe, w)),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("c", [8, 200])                 # decode, multi-block prefill
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gemm_stacked_reads_layer_in_place(c, dtype):
    """The stacked form (w: (L, E, D, F) + layer index) is the per-layer
    form on ``w[l]``, bit for bit, for every layer of the stack."""
    k1, k2 = jax.random.split(jax.random.key(c))
    xe = jax.random.normal(k1, (4, c, 64), dtype)
    w = jax.random.normal(k2, (3, 4, 64, 256), dtype)
    for l in range(w.shape[0]):
        out = moe_gemm(xe, w, jnp.int32(l), interpret=True)
        want = moe_gemm(xe, w[l], interpret=True)
        assert out.shape == want.shape and out.dtype == dtype
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(want, np.float32))


def test_moe_gemm_stacked_refuses_to_pad():
    """A stack whose F is not a multiple of the f-block is refused (padding
    it would copy every layer), as is a stack without its layer index."""
    xe = jnp.ones((2, 8, 32), jnp.float32)
    with pytest.raises(ValueError, match="multiple of block_f"):
        moe_gemm(xe, jnp.ones((3, 2, 32, 200), jnp.float32), jnp.int32(0),
                 interpret=True)
    with pytest.raises(ValueError, match="layer index"):
        moe_gemm(xe, jnp.ones((3, 2, 32, 128), jnp.float32), interpret=True)


@pytest.mark.parametrize("b,hq,hkv,s,d", [(2, 4, 4, 64, 16), (3, 8, 2, 300, 32),
                                          (1, 16, 1, 1024, 64), (4, 8, 8, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_matches_ref(b, hq, hkv, s, d, dtype):
    ks = jax.random.split(jax.random.key(b * 7 + s), 4)
    q = jax.random.normal(ks[0], (b, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    lengths = jax.random.randint(ks[3], (b,), 1, s + 1)
    out = flash_decode(q, k, v, lengths, block_s=64, interpret=True)
    want = ref.ref_flash_decode(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_flash_decode_softcap():
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (2, 4, 32), jnp.float32) * 10
    k = jax.random.normal(ks[1], (2, 100, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 100, 2, 32), jnp.float32)
    lengths = jnp.asarray([50, 100], jnp.int32)
    out = flash_decode(q, k, v, lengths, softcap=30.0, interpret=True)
    want = ref.ref_flash_decode(q, k, v, lengths, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_decode_length_one_attends_first_token_only():
    """With length=1 the output must equal v[:, 0] per head group."""
    b, hq, hkv, s, d = 1, 4, 2, 64, 16
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
    out = flash_decode(q, k, v, jnp.asarray([1]), interpret=True)
    want = jnp.repeat(v[:, 0], hq // hkv, axis=1).reshape(b, hq, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5)


@pytest.mark.parametrize("t,e,k", [(64, 8, 1), (500, 16, 2), (1000, 64, 8),
                                   (128, 128, 6)])
def test_topk_router_matches_ref(t, e, k):
    logits = jax.random.normal(jax.random.key(t + e), (t, e), jnp.float32) * 2
    g, i, p = topk_router(logits, k, block_t=128, interpret=True)
    gr, ir, pr = ref.ref_topk_router(logits, k)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
    np.testing.assert_array_equal(np.asarray(p), np.asarray(pr))


def test_topk_router_positions_cross_block_carry():
    """Positions keep counting across token blocks (running counter)."""
    t, e = 256, 4
    logits = jnp.zeros((t, e)).at[:, 0].set(10.0)   # everyone picks expert 0
    _, ids, pos = topk_router(logits, 1, block_t=64, interpret=True)
    assert (np.asarray(ids) == 0).all()
    np.testing.assert_array_equal(np.asarray(pos).reshape(-1), np.arange(t))


def test_topk_router_gates_normalized():
    logits = jax.random.normal(jax.random.key(9), (200, 32))
    g, _, _ = topk_router(logits, 4, interpret=True)
    np.testing.assert_allclose(np.asarray(g).sum(-1), 1.0, rtol=1e-5)


@pytest.mark.parametrize("t,e,k,r", [(64, 8, 2, 2), (300, 16, 4, 8)])
def test_topk_router_replicated_matches_ref(t, e, k, r):
    """Replica-aware routing: slots follow ExpertPlacement.dispatch_slots'
    round-robin rule and capacity positions count per physical slot, carried
    across token blocks."""
    from repro.core.placement import gimbal_placement_rep
    from repro.models.moe import ExpertPlacement
    rng = np.random.default_rng(t + e)
    logits = jnp.asarray(rng.normal(size=(t, e)) * 2, jnp.float32)
    A = rng.random((2, e)) + 0.1
    W = rng.random((e, e))
    np.fill_diagonal(W, 0.0)
    inv = gimbal_placement_rep(A, W, g=2, redundancy=r, top_e=4)
    plc = ExpertPlacement.from_slot_map(inv, e)
    got = topk_router_replicated(logits, k, plc.replica_slots,
                                 plc.replica_count, e + r, block_t=64,
                                 interpret=True)
    want = ref.ref_topk_router_replicated(logits, k, plc.replica_slots,
                                          plc.replica_count, e + r)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-6)
    for g_, w_ in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(g_), np.asarray(w_))
    # the kernel's slot choice IS the model's dispatch rule
    np.testing.assert_array_equal(np.asarray(got[2]),
                                  np.asarray(plc.dispatch_slots(got[1])))


def test_topk_router_replicated_splits_hot_expert():
    """All tokens picking one replicated expert spread evenly over its
    copies, halving the per-slot capacity pressure."""
    from repro.models.moe import ExpertPlacement
    t, e = 128, 4
    logits = jnp.zeros((t, e)).at[:, 1].set(10.0)     # everyone -> expert 1
    inv = np.array([0, 1, 2, 1, 3, 2], np.int32)      # expert 1 in slots 1+3
    plc = ExpertPlacement.from_slot_map(inv, e)
    _, ids, slots, pos = topk_router_replicated(
        logits, 1, plc.replica_slots, plc.replica_count, 6, block_t=32,
        interpret=True)
    assert (np.asarray(ids) == 1).all()
    s = np.asarray(slots).reshape(-1)
    assert set(s) == {1, 3} and (s == 1).sum() == (s == 3).sum() == t // 2
    assert np.asarray(pos).max() == t // 2 - 1        # per-slot counters


# --- paged flash-decode (ISSUE 8) ---------------------------------------------

def _paged_case(seed, b, hq, hkv, d, bs, nb, dtype=jnp.float32):
    """Random head-major page pool + non-aliasing random block tables (page
    0 reserved as the garbage page, like PagedKVCache)."""
    pool = b * nb + 1
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, hq, d), dtype)
    k_pages = jax.random.normal(ks[1], (pool, hkv, bs, d), dtype)
    v_pages = jax.random.normal(ks[2], (pool, hkv, bs, d), dtype)
    perm = np.random.default_rng(seed).permutation(pool - 1)[:b * nb] + 1
    tables = jnp.asarray(perm.reshape(b, nb), jnp.int32)
    return q, k_pages, v_pages, tables


@pytest.mark.parametrize("b,hq,hkv,d,bs,nb", [(4, 4, 2, 16, 16, 4),
                                              (2, 8, 8, 32, 32, 3),
                                              (3, 4, 1, 64, 16, 2),
                                              # granite's and qwen3's heads
                                              # (Hkv 8 / G 4, Hkv 4 / G 8);
                                              # NB 11 is not a multiple of
                                              # the 8 pages a block
                                              (6, 32, 8, 16, 16, 11),
                                              (6, 32, 4, 16, 16, 11),
                                              (5, 32, 4, 32, 16, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_paged_matches_ref(b, hq, hkv, d, bs, nb, dtype):
    """Ragged lengths, zero-length rows and the exactly-full case in one
    sweep: lengths cover {0, mid-page, page boundary, compute-block
    boundary (ppb * bs, where b > 4), nb*bs}."""
    from repro.kernels.flash_decode import flash_decode_paged, pages_per_block
    q, kp, vp, bt = _paged_case(b * 31 + nb, b, hq, hkv, d, bs, nb, dtype)
    ppb = pages_per_block(bs, hkv * bs * d * jnp.dtype(dtype).itemsize, nb)
    lens = np.linspace(0, nb * bs, b).astype(np.int32)
    lens[b // 2] = bs                                     # a page boundary
    if b > 4:
        lens[1] = ppb * bs                                # a block boundary
    lengths = jnp.asarray(lens)
    out = flash_decode_paged(q, kp, vp, bt, lengths, interpret=True)
    want = ref.ref_flash_decode_paged(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])
    # a zero-length row attends to nothing and must be exactly zero
    assert (np.asarray(out, np.float32)[np.asarray(lengths) == 0] == 0).all()


@pytest.mark.parametrize("store", ["float32", "int8"])
def test_flash_decode_paged_ignores_pages_past_length(store):
    """Every page past each row's length, the tail of each row's last page
    and the reserved page 0 hold NaN (int8: NaN scales), and one row's
    table points its tail at page 0: the output is finite and equals the
    reference on the same pool without the NaN, so no masked page or row
    reaches the result."""
    from repro.kernels.flash_decode import flash_decode_paged
    b, hq, hkv, d, bs, nb = 4, 32, 8, 16, 16, 11
    q, kp, vp, bt = _paged_case(23, b, hq, hkv, d, bs, nb)
    lens = np.asarray([37, 0, 128, 150], np.int32)
    tables = np.array(bt)
    tables[0, 3:] = 0                    # a free tail, as PagedKVCache keeps
    bt = jnp.asarray(tables)
    kw, kw_clean = {}, {}
    if store == "int8":
        kp, ksc = _int8_pages(kp)
        vp, vsc = _int8_pages(vp)
        kw_clean = dict(k_scale=ksc, v_scale=vsc)
    dead = np.zeros(kp.shape[:1] + (bs,), bool)      # (P, BS) unread rows
    dead[0] = True
    for r in range(b):
        used = -(-lens[r] // bs)
        dead[tables[r, used:]] = True
        if lens[r] % bs:
            dead[tables[r, used - 1], lens[r] % bs:] = True
    if store == "int8":
        nan_pages = dead.all(-1)
        kw = dict(k_scale=jnp.where(nan_pages, jnp.nan, ksc),
                  v_scale=jnp.where(nan_pages, jnp.nan, vsc))
        kn, vn = kp, vp
    else:
        mask = jnp.asarray(dead)[:, None, :, None]
        kn, vn = jnp.where(mask, jnp.nan, kp), jnp.where(mask, jnp.nan, vp)
    lengths = jnp.asarray(lens)
    out = np.asarray(flash_decode_paged(q, kn, vn, bt, lengths, **kw,
                                        interpret=True))
    assert np.isfinite(out).all()
    want = ref.ref_flash_decode_paged(q, kp, vp, bt, lengths, **kw_clean)
    np.testing.assert_allclose(out, np.asarray(want), rtol=2e-4, atol=2e-4)
    assert (out[1] == 0).all()


def test_flash_decode_paged_single_block_pages():
    from repro.kernels.flash_decode import flash_decode_paged
    q, kp, vp, bt = _paged_case(7, 3, 4, 2, 16, 16, 1)
    lengths = jnp.asarray([16, 1, 9], jnp.int32)
    out = flash_decode_paged(q, kp, vp, bt, lengths, interpret=True)
    want = ref.ref_flash_decode_paged(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_decode_paged_softcap():
    from repro.kernels.flash_decode import flash_decode_paged
    q, kp, vp, bt = _paged_case(11, 2, 4, 2, 16, 16, 4)
    lengths = jnp.asarray([40, 64], jnp.int32)
    out = flash_decode_paged(q * 10, kp, vp, bt, lengths, softcap=30.0,
                             interpret=True)
    want = ref.ref_flash_decode_paged(q * 10, kp, vp, bt, lengths, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_decode_paged_matches_contiguous_slot_kernel():
    """The paged kernel over a shuffled pool == the slot kernel over the
    gathered contiguous cache (same math, different layout)."""
    from repro.kernels.flash_decode import flash_decode_paged
    b, hq, hkv, d, bs, nb = 3, 4, 2, 32, 16, 4
    q, kp, vp, bt = _paged_case(13, b, hq, hkv, d, bs, nb)
    lengths = jnp.asarray([0, 17, 64], jnp.int32)
    paged = flash_decode_paged(q, kp, vp, bt, lengths, interpret=True)
    k = kp[bt].transpose(0, 1, 3, 2, 4).reshape(b, nb * bs, hkv, d)
    v = vp[bt].transpose(0, 1, 3, 2, 4).reshape(b, nb * bs, hkv, d)
    slot = flash_decode(q, k, v, lengths, block_s=16, interpret=True)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(slot),
                               rtol=2e-4, atol=2e-4)


def test_flash_decode_zero_length_rows_are_zero():
    """The slot kernel's length-0 contract (an inactive decode slot): output
    exactly zero, not softmax(-inf) garbage or mean(v)."""
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (4, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (4, 64, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (4, 64, 2, 16), jnp.float32)
    lengths = jnp.asarray([0, 5, 0, 64], jnp.int32)
    out = np.asarray(flash_decode(q, k, v, lengths, block_s=16, interpret=True))
    assert (out[[0, 2]] == 0).all()
    want = np.asarray(ref.ref_flash_decode(q, k, v, lengths))
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-4)


def _int8_pages(pages):
    from repro.training.compression import quantize_int8
    P = pages.shape[0]
    q, scale = jax.vmap(quantize_int8)(pages.reshape(P, -1))
    return q.reshape(pages.shape), scale.reshape(P)


def test_flash_decode_paged_int8_matches_ref_and_bounds_drift():
    """int8 KV: the kernel's in-flight dequant matches the reference on the
    same quantized pages (tight), and the quantization itself stays within
    the documented drift bound of full-precision attention (loose)."""
    from repro.kernels.flash_decode import flash_decode_paged
    q, kp, vp, bt = _paged_case(17, 4, 8, 2, 32, 16, 4)
    lengths = jnp.asarray([0, 16, 33, 64], jnp.int32)
    kq, ksc = _int8_pages(kp)
    vq, vsc = _int8_pages(vp)
    out = flash_decode_paged(q, kq, vq, bt, lengths, k_scale=ksc, v_scale=vsc,
                             interpret=True)
    want = ref.ref_flash_decode_paged(q, kq, vq, bt, lengths,
                                      k_scale=ksc, v_scale=vsc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    fp = ref.ref_flash_decode_paged(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(fp),
                               rtol=5e-2, atol=5e-2)


# --- fused router -> dispatch -> expert-FFN decode step -----------------------

def test_moe_apply_fused_matches_dense():
    """dispatch_mode='fused' (Pallas replica-aware router + gather dispatch +
    grouped-GEMM expert FFN) is numerically the dense one-hot einsum path,
    with identical expert choices — under a replicated placement."""
    from repro.models.config import ModelConfig
    from repro.models.moe import ExpertPlacement, moe_apply
    cfg = ModelConfig(name="t", family="moe", num_layers=2, d_model=32,
                      num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                      vocab_size=64, num_experts=4, moe_top_k=2, moe_d_ff=32,
                      capacity_factor=8.0, dtype="float32")
    rng = np.random.default_rng(19)
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    params = {
        "w_router": jnp.asarray(rng.normal(size=(d, e)), jnp.float32),
        "w_gate": jnp.asarray(rng.normal(size=(e, d, f)) * 0.1, jnp.float32),
        "w_up": jnp.asarray(rng.normal(size=(e, d, f)) * 0.1, jnp.float32),
        "w_down": jnp.asarray(rng.normal(size=(e, f, d)) * 0.1, jnp.float32),
    }
    x = jnp.asarray(rng.normal(size=(2, 8, d)), jnp.float32)
    # identity placement: fused vs the dense one-hot einsum
    y_d, aux_d = moe_apply(params, cfg, x, None, "dense", return_stats=True)
    y_f, aux_f = moe_apply(params, cfg, x, None, "fused", return_stats=True,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_d),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(aux_f["expert_ids"]),
                                  np.asarray(aux_d["expert_ids"]))
    # replicated placement (expert 1 in two slots): fused vs gather over the
    # slot-gathered weights, the layout apply_placement produces
    inv = np.array([0, 1, 2, 3, 1], np.int32)
    plc = ExpertPlacement.from_slot_map(inv, e)
    slot_params = dict(params)
    for n in ("w_gate", "w_up", "w_down"):
        slot_params[n] = params[n][inv]
    y_g, aux_g = moe_apply(slot_params, cfg, x, plc, "gather",
                           return_stats=True)
    y_f2, aux_f2 = moe_apply(slot_params, cfg, x, plc, "fused",
                             return_stats=True, interpret=True)
    np.testing.assert_allclose(np.asarray(y_f2), np.asarray(y_g),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(aux_f2["expert_ids"]),
                                  np.asarray(aux_g["expert_ids"]))
    # replication is numerics-invariant too
    np.testing.assert_allclose(np.asarray(y_f2), np.asarray(y_d),
                               rtol=1e-5, atol=1e-5)
