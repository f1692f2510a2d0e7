"""The serving entry point (repro.launch.serve) and the example cluster:
replicas share one set of weights, each on its device; unfinished requests
fail the run; the compile cache lands where it is told to."""
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core.types import GimbalConfig
from repro.launch import serve


def _same_weights(engines):
    ref = jax.tree.leaves(engines[0].params)
    for e in engines[1:]:
        for a, b in zip(ref, jax.tree.leaves(e.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_build_cluster_replicas_share_one_weight_key():
    cfg = get_smoke_config("qwen3-30b-a3b")
    c = serve.build_cluster(cfg, "gimbal", 3, GimbalConfig())
    engines = list(c.engines.values())
    _same_weights(engines)
    for e in engines:
        b = e.backend
        assert b.device == jax.devices()[0]      # one CPU device: shared
        assert b.kernel_mode == "interpret"
        assert (b.kv_layout, b.kv_block_size, b.dispatch_mode,
                b.use_kernels) == ("paged", 16, "fused", True)


def test_backend_warmup_compiles_each_bucket_and_writes_nothing():
    cfg = get_smoke_config("qwen3-30b-a3b")
    b = serve.build_cluster(cfg, "gimbal", 1, GimbalConfig()).engines[0].backend
    pages = jax.tree.map(np.asarray, b.kv.pages)
    b.warmup([10, 20, 30, 500])        # buckets 16, 32, 32 and max_seq's 128
    info = b.prefill_cache_info()
    assert (info.misses, info.currsize) == (3, 3)
    jax.tree.map(np.testing.assert_array_equal, pages,
                 jax.tree.map(np.asarray, b.kv.pages))
    assert not b.kv.slot_len.any() and not b.kv.block_tables.any()
    assert b.slot_req == [None] * b.max_slots


def test_example_cluster_replicas_share_one_weight_key():
    from examples.serve_burstgpt import build_cluster
    _same_weights(list(build_cluster("vllm", 2).engines.values()))


def test_serve_full_size_needs_a_depth(capsys):
    with pytest.raises(SystemExit) as e:
        serve.main(["--size", "full"])
    assert e.value.code == 2 and "--depth" in capsys.readouterr().err


def test_model_config_full_widths_at_depth():
    cfg = serve.model_config("qwen3-30b-a3b", "full", 4)
    full = get_config("qwen3-30b-a3b")
    assert cfg.num_layers == 4 and cfg.replace(num_layers=48) == full
    assert serve.model_config("qwen3-30b-a3b") == \
        get_smoke_config("qwen3-30b-a3b")


def test_compile_cache_location(monkeypatch, tmp_path):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert serve.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was   # left to JAX
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        d = serve.use_compile_cache()
        assert d == str(serve.REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == d
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


_COMPILE_ONE = ("from repro.launch.serve import use_compile_cache\n"
                "import jax, jax.numpy as jnp\n"
                "print(use_compile_cache())\n"
                "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()\n")


@pytest.mark.parametrize("where", ["env", "checkout"])
def test_compile_cache_files_land_there(tmp_path, where):
    """A fresh process (JAX reads the variable when it starts) writes its
    compiled programs to $JAX_COMPILATION_CACHE_DIR, else to .jax_cache/ of
    the checkout it runs from (a copy of src/, so the repo stays clean)."""
    shutil.copytree(serve.REPO_ROOT / "src", tmp_path / "src",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(tmp_path / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    want = tmp_path / ("env_cache" if where == "env" else ".jax_cache")
    if where == "env":
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    r = subprocess.run([sys.executable, "-c", _COMPILE_ONE], env=env,
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(want)]
    assert any(want.iterdir())


@pytest.mark.parametrize("horizon,rc", [(0.1, 1), (120.0, 0)])
def test_serve_exit_code_counts_unfinished_requests(monkeypatch, tmp_path,
                                                    capsys, horizon, rc):
    # the cache env var keeps the run from turning on a cache in the repo
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(serve, "HORIZON", horizon)
    assert serve.main(["--n", "4", "--engines", "2"]) == rc
    out = capsys.readouterr()
    assert ("unfinished" in out.err) == (rc == 1)
    assert ("4/4 done" in out.out) == (rc == 0)
