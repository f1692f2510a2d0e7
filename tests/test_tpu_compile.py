"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e at
qwen3-30b-a3b widths (d_model 2048, 32 q / 4 kv heads x 128, 128 experts
top-8, expert d_ff 768, 16-token KV pages, 4 layers), and of DeepSeek-V2's
served prefill and paged decode programs at published widths (5 layers, 20
of 160 routed experts held, 32 rows of 8192-token latent pages).

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

from repro.configs import get_config
from repro.kernels.flash_decode import (flash_decode, flash_decode_latent,
                                        flash_decode_paged)
from repro.models import model as M
from repro.kernels.moe_gemm import moe_gemm
from repro.kernels.topk_router import topk_router_replicated
from repro.models.moe import ExpertPlacement

D_MODEL, HQ, HKV, HEAD, E, TOPK, D_FF = 2048, 32, 4, 128, 128, 8, 768
PAGE, MAX_SEQ, SLOTS = 16, 2048, 8
LAYERS = 4
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
    """At both GQA cells' serving shapes: 32 slots of 2048 tokens in
    16-token pages, 32 query heads over 4 (qwen3) or 8 (granite) KV heads."""
    slots = 32
    pool = slots * NB + 1
    for hkv in (4, 8):
        pages = ((pool, hkv, PAGE, HEAD), jnp.dtype(store))
        shapes = [((slots, HQ, HEAD), jnp.bfloat16), pages, pages,
                  ((slots, NB), jnp.int32), ((slots,), jnp.int32)]
        if store == "int8":
            shapes += [((pool,), jnp.float32)] * 2
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


@pytest.mark.parametrize("capacity", [40, 1032])         # decode, prefill
@pytest.mark.parametrize("proj", ["up", "down"])
def test_moe_gemm_stacked_compiles(one_chip, capacity, proj):
    """The stacked form a 4-layer scan hands the kernel: the weights of
    every layer and the scan's layer index, read in place."""
    d_in, d_out = (D_MODEL, D_FF) if proj == "up" else (D_FF, D_MODEL)
    _compile(moe_gemm, one_chip, ((E, capacity, d_in), jnp.bfloat16),
             ((LAYERS, E, d_in, d_out), jnp.bfloat16), ((), jnp.int32))



# DeepSeek-V2 as the benchmark's deepseek-v2-d5-ep8 serves it
DSV2 = get_config("deepseek-v2-236b").replace(
    num_layers=5, num_experts=20, n_routed_experts=160,
    capacity_factor=160 / 6)
DSV2_SEQ, DSV2_SLOTS = 8192, 32
DSV2_POOL = DSV2_SLOTS * DSV2_SEQ // PAGE + 1


def _dsv2_args(sharding):
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        M.abstract_params(DSV2))
    placements = jax.ShapeDtypeStruct((4, 20), jnp.int32, sharding=sharding)
    return params, placements


def test_latent_decode_kernel_compiles(one_chip):
    nb = DSV2_SEQ // PAGE
    _compile(lambda ql, qr, c, r, ly, bt, ln: flash_decode_latent(
        ql, qr, c, r, ly, bt, ln, scale=DSV2.attn_softmax_scale), one_chip,
        ((DSV2_SLOTS, 128, 512), jnp.bfloat16), ((DSV2_SLOTS, 128, 64), jnp.bfloat16),
        ((5, DSV2_POOL, PAGE, 512), jnp.bfloat16),
        ((5, DSV2_POOL, PAGE, 64), jnp.bfloat16), ((), jnp.int32),
        ((DSV2_SLOTS, nb), jnp.int32), ((DSV2_SLOTS,), jnp.int32))


def test_deepseek_v2_prefill_compiles(one_chip):
    """The served prefill (JaxBackend's latent prefill: the logits of the
    last prompt position, the batch=1 latent cache, held rows) of a
    4096-token bucket: grouped router and moe_gemm over the held stack."""
    params, placements = _dsv2_args(one_chip)

    def prefill(params, tokens, placements, last):
        cache = M.init_cache(DSV2, 1, DSV2_SEQ)
        logits, cache, aux = M.prefill(params, DSV2, tokens, cache,
                                       placements=placements,
                                       dispatch_mode="fused", head_at=last)
        return logits[0, 0], cache, aux["held_rows"].sum()

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = jax.jit(prefill).lower(params, sds((1, 4096), jnp.int32), placements,
                                  sds((), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text


def test_deepseek_v2_paged_decode_compiles(one_chip):
    """The served paged decode step: dense prologue, then the MoE scan, the
    latent pool of every layer carried and read in place by the kernel."""
    params, placements = _dsv2_args(one_chip)
    nb = DSV2_SEQ // PAGE
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pages = {"ckv": sds((5, DSV2_POOL, PAGE, 512), jnp.bfloat16),
             "krope": sds((5, DSV2_POOL, PAGE, 64), jnp.bfloat16)}

    def decode(params, tokens, pages, tables, lengths, placements):
        return M.decode_step_paged(params, DSV2, tokens, pages, tables, lengths,
                                   placements=placements, stats=True,
                                   dispatch_mode="fused", use_kernel=True)

    text = jax.jit(decode).lower(
        params, sds((DSV2_SLOTS, 1), jnp.int32), pages,
        sds((DSV2_SLOTS, nb), jnp.int32), sds((DSV2_SLOTS,), jnp.int32),
        placements).compile().as_text()
    assert "flash_decode_latent" in text and "moe_gemm" in text
