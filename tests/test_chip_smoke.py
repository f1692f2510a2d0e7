"""chip_smoke.py's phases on the CPU at the smoke config, kernels interpreted.

The chip run drives the same functions at qwen3-30b-a3b's full widths with
compiled kernels; here they run small, so a wrong path, argument or check
fails without a chip.  The four-replica phase runs in a fresh interpreter
whose JAX sees four forced host devices (the device count is fixed when JAX
starts, so it cannot be changed in this process).
"""
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.configs import at_depth, get_smoke_config

ROOT = Path(__file__).resolve().parent.parent
SMALL = dict(prompt_range=(8, 100), new_range=(4, 8))


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, str(ROOT))
    import chip_smoke
    return chip_smoke


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env.update(extra)
    return env


def test_exits_nonzero_without_a_tpu(tmp_path):
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=_cpu_env(),
                       cwd=tmp_path, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout and "no TPU found" in r.stderr
    # alone in a directory (none of the repo beside it) it fails as well
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], capture_output=True,
                       text=True, env=_cpu_env(PYTHONPATH=""), cwd=tmp_path,
                       timeout=300)
    assert r.returncode != 0 and '"ok"' not in r.stdout


def test_kernels_phase_matches_xla(smoke):
    out = smoke.phase_kernels(get_smoke_config("qwen3-30b-a3b"),
                              prompt_lens=(20, 30, 40), decode_steps=3,
                              max_seq=128, interpret=True)
    assert out["ok"] and out["max_err"] < 1e-3 and out["tol_used"] <= 1.0
    assert out["rows"] == 20 + 30 + 40 + 3 * 3      # prefill + decode rows
    assert out["routed_alike"] == 1.0
    assert out["max_err"] == max(out["max_err_prefill"], out["max_err_decode"])
    assert not out["custom_call"]          # interpreted: no Mosaic call


@pytest.mark.parametrize("depth,dtype", [(1, "bfloat16"), (2, "float32")])
def test_kernels_phase_checks_at_smoke_width(smoke, depth, dtype):
    """The (layers, dtype) pairs the chip run compares, at smoke widths."""
    assert (depth, dtype) in smoke.KERNEL_CHECKS
    cfg = at_depth(get_smoke_config("qwen3-30b-a3b"), depth).replace(dtype=dtype)
    out = smoke.phase_kernels(cfg, prompt_lens=(20, 30, 40), decode_steps=3,
                              max_seq=128, interpret=True)
    assert out["ok"] and out["routed_alike"] == 1.0


def test_serve_phase_finishes_relocates_and_shares(smoke):
    cfg = get_smoke_config("qwen3-30b-a3b")
    trace = smoke.serve_trace(cfg, **SMALL)
    assert trace[1].prompt_len == trace[0].prompt_len
    out = smoke.phase_serve(cfg, trace, max_seq=128, tau=2)
    assert out["finished"] == out["sent"] == 8
    assert out["streams_whole"] and out["on_own_device"]
    assert out["tokens"] == sum(len(s) for s in out["streams"].values())
    assert out["relocations"] >= 1 and out["shared_hits"] >= 1
    assert out["kernel_modes"] == ["interpret"]
    assert out["served_per_engine"] == [8]
    assert smoke.peak_bytes(jax.devices()[0]) is None or \
        smoke.peak_bytes(jax.devices()[0]) > 0


_REPLICAS = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {root!r})
    import jax
    import chip_smoke as cs
    from repro.configs import at_depth, get_smoke_config
    cfg = get_smoke_config("qwen3-30b-a3b")
    trace = cs.serve_trace(cfg, prompt_range=(8, 100), new_range=(4, 8))
    out = cs.phase_replicas(cfg, trace, jax.devices()[:4], max_seq=128)
    out.pop("streams")
    print(json.dumps(out))
""")


def test_replica_phase_on_four_host_devices():
    r = subprocess.run(
        [sys.executable, "-c", _REPLICAS.format(root=str(ROOT))],
        capture_output=True, text=True, cwd=ROOT, timeout=900,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["on_own_device"] and out["served_per_engine"] == [2, 2, 2, 2]
    assert out["finished"] == out["reference_finished"] == out["sent"]
    assert out["streams_equal"] and out["relocations"] == 0
