"""Distribution-layer tests: PartitionSpec validity for every arch (abstract
mesh, no devices needed) + affinity/statistics plumbing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import list_archs, get_config
from repro.distributed.context import ShardCtx
from repro.distributed.sharding import cache_specs, param_specs
from repro.launch.steps import placements_input
from repro.models import model as M
from repro.models.config import SHAPE_CELLS

# compile-heavy (jits real JAX models / Pallas kernels on CPU): runs in
# the full CI job; the PR lane runs `-m 'not slow'` (see README)
pytestmark = pytest.mark.slow


def abstract_ctx(multi_pod=False):
    if multi_pod:
        mesh = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
        return ShardCtx(mesh=mesh, batch_axes=("pod", "data"))
    mesh = AbstractMesh((16, 16), ("data", "model"))
    return ShardCtx(mesh=mesh, batch_axes=("data",))


def _check_spec_tree(abstract, specs, mesh):
    flat_a, _ = jax.tree_util.tree_flatten(abstract)
    flat_s = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_a) == len(flat_s)
    sizes = dict(mesh.shape)
    for leaf, spec in zip(flat_a, flat_s):
        assert len(spec) <= leaf.ndim, (leaf.shape, spec)
        used = []
        for i, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            factor = 1
            for a in axes:
                assert a in sizes, f"unknown axis {a}"
                assert a not in used, f"axis {a} reused in {spec}"
                used.append(a)
                factor *= sizes[a]
            assert leaf.shape[i] % factor == 0, \
                f"dim {leaf.shape[i]} not divisible by {factor} in {spec} {leaf.shape}"


@pytest.mark.parametrize("arch", list_archs())
@pytest.mark.parametrize("multi_pod", [False, True])
def test_param_specs_valid(arch, multi_pod):
    cfg = get_config(arch)
    ctx = abstract_ctx(multi_pod)
    specs = param_specs(cfg, ctx)
    _check_spec_tree(M.abstract_params(cfg), specs, ctx.mesh)


@pytest.mark.parametrize("arch", list_archs())
def test_cache_specs_valid(arch):
    cfg = get_config(arch)
    ctx = abstract_ctx()
    for cell in SHAPE_CELLS:
        if cell.kind != "decode":
            continue
        total = cell.seq_len + (cfg.vision_prefix_len if cfg.family == "vlm" else 0)
        abstract = jax.eval_shape(lambda: M.init_cache(cfg, cell.global_batch, total))
        specs = cache_specs(cfg, ctx, cell.global_batch, total)
        _check_spec_tree(abstract, specs, ctx.mesh)


def test_big_params_are_sharded_not_replicated():
    """Every parameter above 64 MB (bf16) must be sharded on at least one
    axis — replicating large tensors would blow the 16 GB/chip budget."""
    for arch in ("deepseek-v2-236b", "qwen2-72b", "llama4-maverick-400b-a17b"):
        cfg = get_config(arch)
        ctx = abstract_ctx()
        specs = param_specs(cfg, ctx)
        flat_a = jax.tree_util.tree_leaves(M.abstract_params(cfg))
        flat_s = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
        for leaf, spec in zip(flat_a, flat_s):
            nbytes = int(np.prod(leaf.shape)) * 2
            if nbytes > 64 * 2 ** 20:
                assert any(ax is not None for ax in spec), \
                    f"{arch}: {leaf.shape} ({nbytes/2**20:.0f} MB) replicated"


def test_expert_weights_ep_sharded():
    cfg = get_config("deepseek-v2-236b")
    specs = param_specs(cfg, abstract_ctx())
    moe = specs["blocks"]["moe"]
    assert moe["w_gate"][1] == "model"     # (L, E, d, f): E on model axis
    assert moe["w_down"][1] == "model"


def test_decode_cache_seq_sharded_over_model():
    cfg = get_config("qwen2-72b")
    ctx = abstract_ctx()
    specs = cache_specs(cfg, ctx, batch=128, max_seq=32768)
    assert specs["layers"]["k"][2] == "model"   # (L, B, S, H, D): S on model


def test_placements_input_shape():
    assert placements_input(get_config("granite-3-8b")) is None
    pl = placements_input(get_config("deepseek-v2-236b"))
    assert pl.shape == (59, 160)
    pl4 = placements_input(get_config("llama4-maverick-400b-a17b"))
    assert pl4.shape == (24, 128)


# --- affinity statistics plumbing -----------------------------------------------

def test_accumulate_stats_counts():
    from repro.core.affinity import accumulate_stats
    ids = jnp.asarray([[[[0, 1]], [[2, 3]]],        # layer 0: tokens pick 0,1 / 2,3
                       [[[1, 1]], [[0, 2]]]])       # layer 1
    # shape (L=2, B=2, S=1, K=2)
    A, W = accumulate_stats(ids, num_experts=4)
    np.testing.assert_array_equal(np.asarray(A),
                                  [[1, 1, 1, 1], [1, 2, 1, 0]])
    # token (b=0): layer0 {0,1} -> layer1 {1,1}: pairs (0,1)x2, (1,1)x2
    assert int(W[0, 1]) == 2 and int(W[1, 1]) == 2
    # token (b=1): {2,3} -> {0,2}: (2,0),(2,2),(3,0),(3,2)
    assert int(W[2, 0]) == 1 and int(W[3, 2]) == 1


def test_affinity_tracker_pairs_and_decay():
    from repro.core.affinity import AffinityTracker
    tr = AffinityTracker(num_layers=2, num_experts=4, decay=0.5)
    ids = np.zeros((2, 1, 4, 2), np.int32)
    ids[1, :, :, :] = 1                  # layer0 expert0 -> layer1 expert1
    tr.update(ids)
    w1 = tr.W[0, 1]
    tr.update(np.zeros((2, 1, 4, 2), np.int32))   # now 0 -> 0
    assert tr.W[0, 1] == pytest.approx(w1 * 0.5)
    pairs = tr.affinity_pairs(top_e=2)
    assert pairs[0][:2] == (0, 1)


def test_synthetic_stats_shapes_and_skew():
    from repro.core.affinity import synthetic_stats
    A, W, pairs = synthetic_stats(jax.random.key(0), 4, 32, tokens=10_000)
    assert A.shape == (4, 32) and W.shape == (32, 32)
    assert (A.max(1) / A.mean(1)).mean() > 2.0     # hot experts exist (Fig. 3)
    assert len(pairs) > 0
