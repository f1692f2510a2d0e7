"""The fused MoE path reads the stacked expert weights in place.

Under dispatch_mode="fused" the layer scans of the prefill and of the paged
decode step take the (L, E, D, F) expert weights out of their scanned
operands and scan the layer index instead, so no per-layer slice of the
weights feeds moe_gemm (XLA copies such a slice before a custom call).  The
einsum dispatch modes and dense stacks scan exactly what they did before.
Numerically the fused path stays the gather path, also after the served
backend relocates experts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import model as M
from repro.serving.backend import JaxBackend

# compile-heavy (jits real JAX models / Pallas kernels on CPU): runs in
# the full CI job; the PR lane runs `-m 'not slow'` (see README)
pytestmark = pytest.mark.slow

EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")
SLOTS, PAGE, NB = 2, 4, 4
MOE = get_smoke_config("qwen3-30b-a3b")
DENSE = MOE.replace(name="d", family="dense", num_experts=0, moe_top_k=0)


def _pages(cfg, seed=0):
    shape = (cfg.num_layers, SLOTS * NB + 1, cfg.num_kv_heads, PAGE, cfg.head_dim)
    k1, k2 = jax.random.split(jax.random.key(seed))
    return {"k": jax.random.normal(k1, shape, jnp.float32),
            "v": jax.random.normal(k2, shape, jnp.float32)}


def _decode_inputs(cfg):
    tokens = jnp.asarray([[3], [17]], jnp.int32)
    tables = jnp.arange(1, SLOTS * NB + 1, dtype=jnp.int32).reshape(SLOTS, NB)
    lengths = jnp.asarray([5, 11], jnp.int32)
    return tokens, _pages(cfg), tables, lengths


def _layer_scan(fn, *args):
    """(xs avals, const avals) of the one top-level scan in fn's jaxpr."""
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    eqn = scans[0]
    n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
    avals = [(tuple(v.aval.shape), v.aval.dtype) for v in eqn.invars]
    return avals[n_consts + n_carry:], avals[:n_consts]


def _avals(tree):
    return [(tuple(a.shape), a.dtype) for a in jax.tree.leaves(tree)]


def _traced(cfg, mode, step):
    """params, the layer scan's (xs avals, const avals) and the cache it
    scans, for the paged decode step or the prefill."""
    params = M.init_params(jax.random.key(0), cfg)
    if step == "decode":
        tokens, pages, tables, lengths = _decode_inputs(cfg)
        scan = _layer_scan(
            lambda p, t, pg, bt, ln: M.decode_step_paged(
                p, cfg, t, pg, bt, ln, dispatch_mode=mode, interpret=True),
            params, tokens, pages, tables, lengths)
        return params, scan, pages
    cache = M.init_cache(cfg, 1, 16)
    scan = _layer_scan(
        lambda p, t, c: M.prefill(p, cfg, t, c, dispatch_mode=mode,
                                  interpret=True),
        params, jnp.ones((1, 8), jnp.int32), cache)
    return params, scan, cache["layers"]


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_fused_scan_hoists_expert_weights(step):
    """fused: the scan's scanned operands are the blocks less the expert
    weights, the caches, the flags and the layer indices; the stacked expert
    weights ride in as loop invariants."""
    params, (xs, consts), cache = _traced(MOE, "fused", step)
    blocks = jax.tree.map(lambda a: a, params["blocks"])
    stacked = {n: blocks["moe"].pop(n) for n in EXPERT_WEIGHTS}
    L = MOE.num_layers
    assert xs == (_avals(blocks) + _avals(cache) + _avals(M.local_flags(MOE))
                  + [((L,), jnp.dtype(jnp.int32))])
    expert = _avals(stacked)
    assert not [a for a in xs if a in expert]
    for a in expert:
        assert a in consts


@pytest.mark.parametrize("step", ["decode", "prefill"])
@pytest.mark.parametrize("cfg,mode", [(MOE, "gather"), (MOE, "dense"),
                                      (DENSE, "fused"), (DENSE, "gather")],
                         ids=["moe-gather", "moe-dense", "dense-fused",
                              "dense-gather"])
def test_other_scans_slice_per_layer(step, cfg, mode):
    """The einsum modes and dense stacks scan what they always did: every
    block parameter (expert weights included), the caches and the flags."""
    params, (xs, _), cache = _traced(cfg, mode, step)
    assert xs == (_avals(params["blocks"]) + _avals(cache)
                  + _avals(M.local_flags(cfg)))


def _relocated(cfg, params, inv):
    """The served backend's relocation of ``params`` to slot map ``inv``,
    with the (L, S) placement stack that goes with it."""
    be = JaxBackend(cfg, params, max_slots=SLOTS, max_seq=16,
                    dispatch_mode="fused", kv_layout="paged",
                    kv_block_size=PAGE)
    be.apply_placement(inv)
    assert be.relocations == 1
    return be.params, jnp.asarray(np.tile(inv, (cfg.num_layers, 1)), jnp.int32)


def test_fused_matches_gather_before_and_after_relocation():
    """Prefill and paged-decode logits of the fused path equal the gather
    path's within test_moe_apply_fused_matches_dense's tolerance, at the
    identity layout and after apply_placement replicates experts; relocated,
    the fused step reads the new stacked arrays (placement invariance)."""
    cfg = MOE.replace(capacity_factor=8.0)        # dropless, as served
    params = M.init_params(jax.random.key(1), cfg)
    tokens, pages, tables, lengths = _decode_inputs(cfg)
    prompt = jax.random.randint(jax.random.key(2), (1, 8), 0, cfg.vocab_size)

    def step(mode):
        def fn(params, placements):
            dec, new_pages, _ = M.decode_step_paged(
                params, cfg, tokens, pages, tables, lengths,
                placements=placements, dispatch_mode=mode, interpret=True)
            pre, _, _ = M.prefill(params, cfg, prompt, M.init_cache(cfg, 1, 16),
                                  placements=placements, dispatch_mode=mode,
                                  interpret=True)
            return dec, new_pages, pre
        return jax.jit(fn)

    fused, gather = step("fused"), step("gather")
    ident = jnp.asarray(np.tile(np.arange(cfg.num_experts), (cfg.num_layers, 1)),
                        jnp.int32)
    base = fused(params, ident)
    inv = np.concatenate([np.arange(cfg.num_experts)[::-1], [1, 5]]).astype(np.int32)
    moved, placements = _relocated(cfg, params, inv)
    assert moved["blocks"]["moe"]["w_up"].shape[1] == len(inv)
    for p, plc in ((params, ident), (moved, placements)):
        got, want = fused(p, plc), gather(p, plc)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-5)
        for g, b in zip(jax.tree.leaves(got), jax.tree.leaves(base)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)
