"""Numerics tests for the SSPerf optimizations: every beyond-paper speedup
must be bit-compatible (up to fp tolerance) with the paper-faithful baseline.

Multi-device cases run in a subprocess with XLA_FLAGS-forced host devices
(jax locks the device count at first init, so the main pytest process stays
single-device)."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# compile-heavy (jits real JAX models / Pallas kernels on CPU): runs in
# the full CI job; the PR lane runs `-m 'not slow'` (see README)
pytestmark = pytest.mark.slow

from repro.distributed.context import ShardCtx, shard_ctx
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.models.config import ModelConfig


def gemma_cfg():
    return ModelConfig(name="g", family="dense", num_layers=4, d_model=64,
                       num_heads=4, num_kv_heads=2, head_dim=16, d_ff=96,
                       vocab_size=128, sliding_window=8, local_global_period=2,
                       attn_logit_softcap=50.0, dtype="float32")


def test_paired_local_global_matches_baseline():
    """Paired (local, global) scan == runtime-flag scan, forward + decode."""
    cfg = gemma_cfg()
    params = M.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)

    base, _ = M.forward_train(params, cfg, toks)
    mesh = make_mesh((1, 1), ("data", "model"))
    ctx = ShardCtx(mesh=mesh, batch_axes=("data",), paired_lg=True,
                   seq_parallel=False)
    with shard_ctx(ctx):
        paired, _ = M.forward_train(params, cfg, toks)
    np.testing.assert_allclose(np.asarray(base), np.asarray(paired),
                               rtol=2e-5, atol=2e-5)

    cache_b = M.init_cache(cfg, 2, 24)
    _, cache_b, _ = M.prefill(params, cfg, toks, cache_b)
    pos = jnp.full((2,), 16, jnp.int32)
    nxt = toks[:, :1]
    l_base, _, _ = M.decode_step(params, cfg, nxt, cache_b, pos)
    cache_p = M.init_cache(cfg, 2, 24)
    with shard_ctx(ctx):
        _, cache_p, _ = M.prefill(params, cfg, toks, cache_p)
        l_pair, _, _ = M.decode_step(params, cfg, nxt, cache_p, pos)
    np.testing.assert_allclose(np.asarray(l_base), np.asarray(l_pair),
                               rtol=2e-5, atol=2e-5)


_MULTIDEV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.context import ShardCtx, shard_ctx
    from repro.launch.mesh import make_mesh
    from repro.models import model as M, moe_sharded
    from repro.models.moe import init_moe, moe_apply, ExpertPlacement
    from repro.models.config import ModelConfig

    cfg = ModelConfig(name="m", family="moe", num_layers=2, d_model=32,
                      num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64,
                      vocab_size=64, num_experts=8, moe_top_k=2, moe_d_ff=16,
                      capacity_factor=8.0, dtype="float32")
    params = init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (8, 4, cfg.d_model), jnp.float32)
    ref, _ = moe_apply(params, cfg, x, dispatch_mode="gather")

    mesh = make_mesh((2, 4), ("data", "model"))
    outs = {}
    for mode in ("gather", "tokengather", "a2a"):
        ctx = ShardCtx(mesh=mesh, batch_axes=("data",), ep_mode=mode,
                       seq_parallel=False)
        with mesh, shard_ctx(ctx):
            y, _ = jax.jit(lambda p, xx: moe_sharded.moe_apply_sharded(
                p, cfg, xx, None, ctx))(params, x)
        outs[mode] = np.asarray(y)
        np.testing.assert_allclose(outs[mode], np.asarray(ref),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"mode={mode} vs single-device ref")
    np.testing.assert_allclose(outs["gather"], outs["tokengather"],
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(outs["gather"], outs["a2a"],
                               rtol=2e-4, atol=2e-4)
    print("MULTIDEV_OK")
""")


def test_moe_ep_modes_match_reference_multidevice():
    """shard_map EP in all three comm modes == single-device MoE, on an 8-device
    (2 data x 4 model) mesh (capacity set dropless so dispatch is identical)."""
    r = subprocess.run([sys.executable, "-c", _MULTIDEV], capture_output=True,
                       text=True, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                                       "HOME": "/root"}, cwd="/root/repo",
                       timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "MULTIDEV_OK" in r.stdout


def test_mla_absorb_flag_reachable_via_ctx():
    """ShardCtx.mla_absorb drives decode_step through the absorbed path."""
    cfg = ModelConfig(name="d", family="moe", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=4, head_dim=16, d_ff=96,
                      vocab_size=128, attention_type="mla", q_lora_rank=32,
                      kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
                      v_head_dim=16, num_experts=8, moe_top_k=2, moe_d_ff=32,
                      capacity_factor=8.0, dtype="float32")
    params = M.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab_size)
    cache = M.init_cache(cfg, 2, 12)
    _, cache, _ = M.prefill(params, cfg, toks, cache)
    pos = jnp.full((2,), 8, jnp.int32)
    l0, _, _ = M.decode_step(params, cfg, toks[:, :1], cache, pos,
                             mla_absorb=False)
    l1, _, _ = M.decode_step(params, cfg, toks[:, :1], cache, pos,
                             mla_absorb=True)
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                               rtol=2e-4, atol=2e-4)
