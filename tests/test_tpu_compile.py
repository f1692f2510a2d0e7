"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e at
qwen3-30b-a3b widths (d_model 2048, 32 q / 4 kv heads x 128, 128 experts
top-8, expert d_ff 768, 16-token KV pages).

Nothing runs: each kernel is lowered and compiled by the TPU compiler for a
described (not attached) v5e chip, so a block that breaks the tiling rules,
a primitive Mosaic cannot lower, or a VMEM overrun fails here instead of on
the chip.  Interpret mode (tests/test_kernels.py) can show none of these.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and the test workers
must all collect the same tests.  The persistent compilation cache is off
around the compiles (an entry written for a described chip cannot be read
back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.moe_gemm import moe_gemm
from repro.kernels.topk_router import topk_router_replicated
from repro.models.moe import ExpertPlacement

D_MODEL, HQ, HKV, HEAD, E, TOPK, D_FF = 2048, 32, 4, 128, 128, 8, 768
PAGE, MAX_SEQ, SLOTS = 16, 2048, 8
NB = MAX_SEQ // PAGE
POOL = SLOTS * NB + 1                          # + the reserved garbage page


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled HLO"


@pytest.mark.parametrize("store", ["bfloat16", "int8"])
def test_flash_decode_paged_compiles(one_chip, store):
    pages = ((POOL, HKV, PAGE, HEAD), jnp.dtype(store))
    shapes = [((SLOTS, HQ, HEAD), jnp.bfloat16), pages, pages,
              ((SLOTS, NB), jnp.int32), ((SLOTS,), jnp.int32)]
    if store == "int8":
        shapes += [((POOL,), jnp.float32)] * 2
        _compile(lambda q, k, v, bt, ln, ks, vs: flash_decode_paged(
            q, k, v, bt, ln, k_scale=ks, v_scale=vs), one_chip, *shapes)
    else:
        _compile(flash_decode_paged, one_chip, *shapes)


def test_flash_decode_contiguous_compiles(one_chip):
    kv = ((SLOTS, MAX_SEQ, HKV, HEAD), jnp.bfloat16)
    _compile(flash_decode, one_chip, ((SLOTS, HQ, HEAD), jnp.bfloat16), kv, kv,
             ((SLOTS,), jnp.int32))


@pytest.mark.parametrize("tokens", [SLOTS, MAX_SEQ])     # decode, prefill
@pytest.mark.parametrize("redundancy", [0, 8])
def test_topk_router_replicated_compiles(one_chip, tokens, redundancy):
    slots = E + redundancy
    max_rep = ExpertPlacement.identity(E).replica_slots.shape[1] + redundancy

    def route(logits, rs, rc):
        return topk_router_replicated(logits, TOPK, rs, rc, slots)

    _compile(route, one_chip, ((tokens, E), jnp.float32),
             ((E, max_rep), jnp.int32), ((E,), jnp.int32))


@pytest.mark.parametrize("capacity", [8, 168])           # decode, prefill
@pytest.mark.parametrize("proj", ["up", "down"])
def test_moe_gemm_compiles(one_chip, capacity, proj):
    d_in, d_out = (D_MODEL, D_FF) if proj == "up" else (D_FF, D_MODEL)
    _compile(moe_gemm, one_chip, ((E, capacity, d_in), jnp.bfloat16),
             ((E, d_in, d_out), jnp.bfloat16))
