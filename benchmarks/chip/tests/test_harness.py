"""The harness's own arithmetic and plumbing, on the CPU, in seconds.

    PYTHONPATH=src python3 -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import json
import math
import shutil
import types
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import devtrace, spec, stats, traffic, work
from benchmarks.chip.serve import Span, Track
from benchmarks.chip.traffic import Arrival

HERE = Path(__file__).resolve().parents[1]


# ------------------------------------------------------------ metric arithmetic
def _track(due, deliveries, submitted=None):
    a = Arrival(0, 0.0, np.zeros(4, np.int32), 8)
    t = Track(a, due, due if submitted is None else submitted, None)
    t.deliveries = list(deliveries)
    return t


@pytest.mark.parametrize("q", [0, 5, 50, 95, 100])
def test_percentile_matches_numpy(q):
    xs = list(np.random.default_rng(0).exponential(1.0, 37))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_is_nan():
    assert math.isnan(stats.percentile([], 50))


def test_ttft_from_due_and_censored_at_close():
    tracks = [_track(1.0, [(1.5, 1)], submitted=1.2),   # 0.5 from due
              _track(2.0, []),                          # no token: 3.0 - 2.0
              _track(2.5, [(3.5, 1)]),                  # token after close
              _track(0.5, [(1.1, 1)]),                  # due before open
              _track(3.0, [(3.1, 1)])]                  # due at close: out
    assert stats.ttfts(tracks, 1.0, 3.0) == pytest.approx([0.5, 1.0, 0.5])


def test_tpot_is_each_requests_mean_gap_in_window():
    tracks = [_track(0.0, [(1.0, 1), (1.2, 1), (1.6, 1)]),      # 0.3
              _track(0.0, [(0.5, 1), (1.5, 1), (2.5, 2)]),      # 1.0 / 2
              _track(0.0, [(1.4, 1)]),                          # one token
              _track(0.0, [(1.0, 1), (3.5, 1)])]                # one in window
    assert stats.tpots(tracks, 1.0, 3.0) == pytest.approx([0.3, 0.5])


def test_rate_counts_every_token_delivered_in_window():
    tracks = [_track(0.0, [(0.9, 5), (1.0, 2), (2.0, 3)]),
              _track(5.0, [(2.5, 1), (3.0, 4)])]
    assert stats.tokens(tracks, 1.0, 3.0) == 6


def test_lateness_is_submit_minus_due():
    assert stats.lateness([_track(1.0, [], submitted=1.25)]) == [0.25]


# ---------------------------------------------------------------- trace reduction
def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=float(start),
                                 duration_ns=float(dur), stats=[])


def _plane(name, lines):
    return types.SimpleNamespace(
        name=name, lines=[types.SimpleNamespace(name=k, events=v)
                          for k, v in lines.items()])


def _synthetic():
    host = _plane("/host:CPU", {"python3": [
        _ev("bench:step", 100, 400), _ev("bench:decode", 150, 300),
        _ev("bench:idle", 520, 60), _ev("bench:step", 600, 300),
        _ev("other", 0, 1000)]})
    dev = _plane("/device:TPU:0", {
        "XLA Ops": [
            _ev("%fusion.1 = f32[8] fusion(f32[8] %p)", 50, 100),  # from 100
            _ev("%while.3 = (s32[]) while(%t), body=%b", 200, 160),
            _ev("%moe_gemm.7 = bf16[8,128] custom-call(%a, %b)", 200, 100),
            _ev("%moe_gemm.9 = bf16[8,128] custom-call(%c, %d)", 300, 50),
            _ev("%fusion.2 = f32[8] fusion(%moe_gemm.9, %x)", 650, 50),
            _ev("%flash_decode_paged.4 = bf16[2] custom-call(%q)", 700, 25),
            _ev("%fusion.3 = f32[8] fusion(%y)", 950, 10)],        # after
        "XLA Modules": [_ev("jit_step", 100, 800)]})
    return devtrace.reduce([host, dev])


def test_trace_window_spans_the_step_annotations():
    t = _synthetic()
    assert t.window == (100.0, 900.0)
    assert t.window_s == pytest.approx(800e-9)


def test_trace_busy_is_the_union_of_op_intervals():
    # [100,150] + [200,360] + [650,725] = 50 + 160 + 75
    assert _synthetic().busy_s() == pytest.approx(285e-9)


def test_trace_kernel_time_by_instruction_name_not_operands():
    t = _synthetic()
    assert t.kernel_s("moe_gemm") == pytest.approx(150e-9)   # not fusion.2
    assert t.kernel_s("flash_decode") == pytest.approx(25e-9)
    assert t.kernel_s("absent") == 0.0


def test_op_label_is_the_instruction_name():
    assert devtrace.label("%moe_gemm.23 = bf16[1] custom-call(%a.1)") == "moe_gemm"
    assert devtrace.label("%copy_bitcast_fusion.20 = bf16[1] fusion()") == \
        "copy_bitcast_fusion"
    assert devtrace.label("convolution") == "convolution"


def test_trace_idle_gaps_labelled_by_innermost_host_span():
    gaps = _synthetic().idle_gaps(3)
    # 360..650 (middle 505, between two steps), 725..900, 150..200
    assert gaps[0] == ["host:other", pytest.approx(290e-9)]
    assert gaps[1] == ["bench:step", pytest.approx(175e-9)]
    assert gaps[2] == ["bench:decode", pytest.approx(50e-9)]


def test_trace_without_steps_or_device_reduces_to_none():
    assert devtrace.reduce([_plane("/host:CPU", {"x": [_ev("bench:idle", 0, 5)]})]) is None


def test_trace_top_ops_rank_by_self_time():
    top = dict((k, v) for k, v in _synthetic().top_ops())
    assert top["moe_gemm"] == pytest.approx(150e-9)
    assert top["while"] == pytest.approx(10e-9)       # 160 less its body
    assert top["fusion"] == pytest.approx(100e-9)


# ------------------------------------------------------------------ work counts
QWEN = spec.load_json(HERE / "configs" / "qwen3-30b-a3b-d4.json")
GRANITE = spec.load_json(HERE / "configs" / "granite-3-8b-d10.json")


def _decode(lengths, experts=None):
    return Span(0.0, 1.0, len(lengths), lengths=np.asarray(lengths, np.int64),
                experts=experts)


def test_flash_decode_work_counts_valid_kv_only():
    # qwen3-d4: 4 layers, 32 q heads, 4 kv heads, head_dim 128, bf16.
    # rows with 9 and 99 resident tokens attend 10 + 100 = 110 tokens.
    flops, nbytes = work.kernel_work(QWEN, "flash_decode", _decode([9, 99]))
    assert flops == 4 * (4 * 32 * 128 * 110)
    assert nbytes == 4 * (2 * 4 * 128 * 2 * 110 + 2 * 2 * 32 * 128 * 2)
    assert work.kernel_work(QWEN, "flash_decode", Span(0.0, 1.0, 7)) is None


def test_moe_gemm_work_counts_routed_rows_and_hit_experts():
    # 10 tokens, top-8: 80 rows per layer; d 2048, f 768; 300 experts hit,
    # 75 in each of the 4 layers (80 routed ids, 5 of them repeats)
    ids = np.stack([np.concatenate([np.arange(75), np.arange(5)]) + l
                    for l in range(4)]).reshape(4, 10, 8)
    flops, nbytes = work.kernel_work(QWEN, "moe_gemm",
                                     Span(0.0, 1.0, 10, experts=ids))
    assert flops == 4 * 3 * 2 * 80 * 2048 * 768
    assert nbytes == 300 * 3 * 2048 * 768 * 2 + 4 * 80 * 3 * (2048 + 768) * 2
    assert work.kernel_work(GRANITE, "moe_gemm", Span(0.0, 1.0, 10)) is None
    assert work.kernel_work(QWEN, "absent", Span(0.0, 1.0, 10)) is None


def test_distinct_and_expected_experts():
    ids = np.array([[[1, 2], [2, 3]], [[5, 5], [5, 6]]])     # (L=2, T=2, k=2)
    assert work.distinct_experts(ids) == 3 + 2
    arch = spec.arch_module(QWEN)
    assert arch.expected_experts(QWEN, 1) == pytest.approx(4 * 8)
    assert arch.expected_experts(QWEN, 10_000) == pytest.approx(4 * 128)
    # a prefill hands back no ids: its count of hit experts is expected
    assert work.kernel_work(QWEN, "moe_gemm", Span(0.0, 1.0, 1)) == \
        arch.moe_gemm(QWEN, 1, arch.expected_experts(QWEN, 1))
    assert (work.held_experts(QWEN), work.held_experts(GRANITE)) == (128, 0)


def test_model_flops_by_hand():
    # granite-d10 per token per layer: attention projections
    # 2*4096*(2*32 + 2*8)*128 and a 3-matrix SwiGLU 2*3*4096*12800
    per = 2 * 4096 * 80 * 128 + 2 * 3 * 4096 * 12800
    head = 2 * 4096 * 49155
    assert work.model_flops(GRANITE, Span(0.0, 1.0, 3)) == \
        10 * (3 * per + 4 * 32 * 128 * 6) + head
    assert work.model_flops(GRANITE, _decode([4, 0])) == \
        10 * (2 * per + 4 * 32 * 128 * 6) + 2 * head
    # qwen3-d4: router 2*2048*128 and 8 experts 2*3*2048*768 per token
    per_q = 2 * 2048 * 72 * 128 + 2 * 2048 * 128 + 8 * 2 * 3 * 2048 * 768
    assert work.model_flops(QWEN, _decode([0])) == 4 * (per_q + 4 * 32 * 128) \
        + 2 * 2048 * 151936


def test_peaks_table_refuses_an_unknown_device():
    assert work.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_least_seconds_takes_the_binding_bound():
    pk = {"bf16_flops": 100.0, "hbm_bytes_s": 10.0}
    assert work.least_seconds(1000, 5, pk) == 10.0
    assert work.least_seconds(10, 50, pk) == 5.0


# ----------------------------------------------------------------------- traffic
CHAT = spec.load_json(HERE / "traffic" / "burstgpt-descending.json")


def _sig(reqs):
    return [(round(a.due, 9), a.max_new, a.prompt.tobytes()) for a in reqs]


def test_traffic_same_seed_same_requests():
    a = traffic.generate(CHAT, 5.0, 20.0, 2**31 + 77, 151936)
    b = traffic.generate(CHAT, 5.0, 20.0, 2**31 + 77, 151936)
    assert _sig(a) == _sig(b)


def test_traffic_other_seed_other_tokens_same_work():
    a = traffic.generate(CHAT, 5.0, 20.0, 1, 151936)
    b = traffic.generate(CHAT, 5.0, 20.0, 2, 151936)
    assert _sig(a) != _sig(b)
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in b]
    assert [(x.due, x.max_new) for x in a] == [(x.due, x.max_new) for x in b]
    assert sorted(len(x.prompt) for x in a) == \
        traffic.prompt_lengths(CHAT, 5.0, 20.0)


def test_traffic_holds_the_stated_shape():
    reqs = traffic.generate(CHAT, 6.4, 50.0, 3, 1000)
    plens = np.array([len(r.prompt) for r in reqs])
    outs = np.array([r.max_new for r in reqs])
    assert len(reqs) == 320                    # five rounds of 64 levels
    assert plens.min() >= 16 and plens.max() <= 1536
    assert outs.min() >= 8 and outs.max() <= 512
    # 16 + exponential(746) truncated at 1536: median 16 + 746 ln(2 / (1 + e^(-1520/746)))
    trunc = math.exp(-1520 / 746)
    assert np.median(plens) == pytest.approx(
        16 + 746 * math.log(2 / (1 + trunc)), rel=0.03)
    assert np.median(outs) == pytest.approx(99.5, rel=0.03)
    gaps = np.diff([r.due for r in reqs])
    assert gaps.mean() == pytest.approx(1 / 6.4, rel=0.1)
    assert all(r.prompt.max() < 1000 for r in reqs)


def test_traffic_lengths_take_the_same_levels_at_every_rate():
    """The program compiles per prompt length: a sweep's rates and a cell's
    window draw from one set of 64 lengths, and every round deals each
    level once."""
    levels = set(traffic.prompt_lengths(CHAT, 6.4, 10.0))
    assert len(levels) == 64
    for rate, dur in ((1.2, 40.0), (2.9, 61.0), (9.0, 30.0)):
        assert set(traffic.prompt_lengths(CHAT, rate, dur)) <= levels
    ten = traffic.prompt_lengths(CHAT, 12.8, 10.0)
    assert all(ten.count(x) == 2 for x in levels)


def test_traffic_truncation_keeps_quantiles_inside_the_range():
    lv = traffic._levels({"dist": "lognormal", "median": 100, "sigma": 2.0,
                          "min": 50, "max": 60}, 8)
    assert lv.min() >= 50 and lv.max() <= 60 and len(set(lv)) > 4
    with pytest.raises(ValueError):
        traffic._levels({"dist": "pareto", "min": 1, "max": 2}, 4)


def test_stalls_split_a_long_step_by_what_ran_inside_it():
    from benchmarks.chip import serve
    rec = serve.Recorder()
    rec.steps = [(10.0, 10.1), (11.0, 12.5), (20.0, 21.0), (13.0, 13.2)]
    rec.prefills = [serve.Span(11.0, 11.4, 300)]
    rec.decodes = [serve.Span(11.4, 12.3, 8), serve.Span(20.0, 21.0, 8)]
    rec.relocs = [serve.Span(11.4, 11.45, 0)]
    rec.gcs = [(12.3, 12.5)]
    rec.stall_memory = {11.0: {"bytes_in_use": 7, "largest_free_block_bytes": 3}}
    out = rec.stalls(10.5, 15.0)               # the step at 20 s is after close
    assert len(out) == 1
    st = out[0]
    assert st["at_s"] == pytest.approx(0.5) and st["step_s"] == pytest.approx(1.5)
    assert (st["prefill_s"], st["decode_s"], st["relocation_s"], st["gc_s"]) == \
        pytest.approx((0.4, 0.9, 0.05, 0.2))
    assert (st["bytes_in_use"], st["largest_free_block_bytes"]) == (7, 3)


# ------------------------------------------------------------ the comparison
def test_summary_of_gaps_by_hand():
    from benchmarks.chip import check
    gaps = np.array([0.0, 0.3, 0.05, 0.0, 0.2])
    margins = np.array([0.5, 0.001, 0.03, 0.02, 0.019])
    out = check.summary(gaps, margins, 0.02)
    assert out == {"widest_gap": 0.3, "share_gap_over_0.1": 0.4,
                   "decisive_tokens": 3.0, "widest_gap_decisive": 0.05}
    assert "decisive_tokens" not in check.summary(gaps, margins)
    held = check.compare(out, {"widest_gap_decisive": {"max": 0.04},
                               "decisive_tokens": {"min": 3}})
    assert not held["widest_gap_decisive"]["ok"] and held["decisive_tokens"]["ok"]


# ------------------------------------------------------------- found by name
def test_new_cell_config_traffic_and_metric_are_found_by_name(tmp_path):
    here = tmp_path / "bench"
    for sub in ("configs", "traffic", "cells", "metrics"):
        (here / sub).mkdir(parents=True)
    (here / "configs" / "m-new.json").write_text(json.dumps(dict(QWEN, name="m-new")))
    (here / "traffic" / "t-new.json").write_text(json.dumps(CHAT))
    (here / "cells" / "c-new.json").write_text(json.dumps({"rate_rps": 1.0}))
    (here / "metrics" / "new.metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench = {"workloads": [{"name": "c-new", "config": "m-new", "traffic": "t-new",
                            "chips": 1, "why": "x"}],
             "end_to_end": [{"name": "setup_s"},
                            {"name": "only_other", "workloads": ["c-other"]}],
             "per_layer": [{"name": "new.metric", "workloads": ["c-new"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("c-new", root=tmp_path, here=here)
    assert cell.config["name"] == "m-new" and cell.params == {"rate_rps": 1.0}
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["new.metric"]
    assert spec.metric_reader("new.metric", here=here).read(None) == 42.0
    with pytest.raises(KeyError):
        spec.load_cell("absent", root=tmp_path, here=here)


def test_every_metric_of_the_benchmark_has_a_reader():
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]).read)
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert spec.reference_module(cell.config).logits_at


def test_config_maps_to_the_program_config():
    from repro.models.config import ModelConfig
    cfg = ModelConfig(**spec.arch_module(QWEN).model_fields(QWEN))
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.num_experts, cfg.moe_top_k, cfg.moe_d_ff,
            cfg.vocab_size, cfg.dtype) == (4, 2048, 32, 4, 128, 128, 8, 768,
                                           151936, "bfloat16")
    assert cfg.capacity_factor * cfg.moe_top_k >= cfg.num_experts  # dropless
    g = ModelConfig(**spec.arch_module(GRANITE).model_fields(GRANITE))
    assert (g.num_layers, g.d_model, g.head_dim, g.d_ff, g.tie_embeddings,
            g.norm_eps, g.is_moe) == (10, 4096, 128, 12800, True, 1e-5, False)


def test_weights_take_the_programs_layout():
    import jax
    from repro.models import model as M
    from repro.models.config import ModelConfig
    from benchmarks.chip import weights
    for config in (QWEN, GRANITE):
        ours = weights.shapes(config)
        theirs = M.abstract_params(
            ModelConfig(**spec.arch_module(config).model_fields(config)))
        assert jax.tree.structure(ours) == jax.tree.structure(theirs)
        for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
            assert (a.shape, a.dtype) == (b.shape, b.dtype)


# Digests of the weights the harness drew for these tiny configurations
# before their architecture moved into archs/gqa_stack.py: the same seed
# must keep giving the same leaves.
WEIGHT_DIGESTS = {
    ("moe", 0): "f1e21af55121e62db9c078954ef915af7f882bf0abd3dbbac6dbbc87d2fc261e",
    ("moe", 2**40 + 7): "196febaa7ed94e368d96dd6ce6554914929c419d26321d12835d33b08d8a6de1",
    ("dense", 0): "d8eefbd4d5fbf827e4efd0e086ca290051b9ec31d0cd3fb5478ede6bf782b168",
    ("dense", 2**40 + 7): "b19694d9515c82b0eb3dce1ec462f101a814a79f097af8c13da881935ac67760",
}


@pytest.mark.parametrize("kind,seed", sorted(WEIGHT_DIGESTS))
def test_same_seed_same_weights(kind, seed):
    import hashlib

    import jax
    from benchmarks.chip import weights
    from benchmarks.chip.tests.test_correctness import TINY, TINY_DENSE
    h = hashlib.sha256()
    tree = weights.make(TINY if kind == "moe" else TINY_DENSE, seed)
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == WEIGHT_DIGESTS[kind, seed]


# ------------------------------------------------ an architecture as new files
# A latent-attention mixture of experts in DeepSeek-V2's keys, as a later
# configuration would bring it: its own key names, one dense leading layer,
# shared experts, and a chip that holds a share of the routed experts.
TOY_ARCH = r"""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import weights

B = 2


def _z(c):
    return (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"])


def model_fields(c):
    return {"name": c["name"], "family": "moe", "attention_type": "mla",
            "num_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_attention_heads"],
            "q_lora_rank": c["q_lora_rank"], "kv_lora_rank": c["kv_lora_rank"],
            "qk_nope_head_dim": c["qk_nope_head_dim"],
            "qk_rope_head_dim": c["qk_rope_head_dim"],
            "v_head_dim": c["v_head_dim"], "d_ff": c["intermediate_size"],
            "vocab_size": c["vocab_size"], "num_experts": c["n_routed_experts"],
            "num_shared_experts": c["n_shared_experts"],
            "moe_top_k": c["num_experts_per_tok"],
            "moe_d_ff": c["moe_intermediate_size"],
            "first_k_dense": c["first_k_dense_replace"],
            "dtype": c["torch_dtype"]}


def held_experts(c):
    return c["serving"]["held_experts"]


def tree(key, c):
    d, h, rq, rkv, dn, dr, dv = _z(c)
    n_pro = c["first_k_dense_replace"]
    L, E = c["num_hidden_layers"] - n_pro, c["n_routed_experts"]
    f, F = c["moe_intermediate_size"], c["intermediate_size"]
    dt = jnp.dtype(c["torch_dtype"])
    ks = iter(jax.random.split(key, 64))
    st = lambda n, shape: weights.stacked(next(ks), n, shape, 0.02, dt)
    norm = lambda n, w: weights.stacked(next(ks), n, (w,), weights.NORM_STD, dt)

    def block(n, ffn):
        return {"attn_norm": {"scale": norm(n, d)},
                "attn": {"wkv_a": st(n, (d, rkv + dr)), "kv_norm": norm(n, rkv),
                         "wkv_b": st(n, (rkv, h, dn + dv)),
                         "wo": st(n, (h, dv, d)), "wq_a": st(n, (d, rq)),
                         "q_norm": norm(n, rq), "wq_b": st(n, (rq, h, dn + dr))},
                "ffn_norm": {"scale": norm(n, d)}, **ffn}

    pro = block(n_pro, {"ffn": {"w_gate": st(n_pro, (d, F)),
                                "w_up": st(n_pro, (d, F)),
                                "w_down": st(n_pro, (F, d))}})
    g = f * c["n_shared_experts"]
    moe = {"w_router": weights.stacked(next(ks), L, (d, E), 0.02, jnp.float32),
           "w_gate": st(L, (E, d, f)), "w_up": st(L, (E, d, f)),
           "w_down": st(L, (E, f, d)),
           "shared": {"w_gate": st(L, (d, g)), "w_up": st(L, (d, g)),
                      "w_down": st(L, (g, d))}}
    return {"embed": {"embedding": st(1, (c["vocab_size"], d))[0],
                      "unembedding": st(1, (c["vocab_size"], d))[0]},
            "final_norm": {"scale": norm(1, d)[0]},
            "prologue": [jax.tree.map(lambda x: x[i], pro) for i in range(n_pro)],
            "blocks": block(L, {"moe": moe})}


def model_flops(c, call):
    return 1000 * (call.rows if call.lengths is None else len(call.lengths))


def kernel_work(c, kernel, call):
    d, h, rq, rkv, dn, dr, dv = _z(c)
    if kernel == "flash_decode" and call.lengths is not None:
        toks = int((np.asarray(call.lengths) + 1).sum())
        return 0, toks * (rkv + dr) * B         # latents, not K/V
    if kernel == "moe_gemm" and call.experts is not None:
        rows = int((np.asarray(call.experts) < held_experts(c)).sum())
        return rows * 6 * d * c["moe_intermediate_size"], 0
    return None
"""

TOY_CONFIG = {
    "name": "toy-latent", "arch": "latent_moe", "reference": "latent_moe",
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 24,
    "n_routed_experts": 8, "n_shared_experts": 2, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "num_hidden_layers": 3, "vocab_size": 128,
    "torch_dtype": "bfloat16",
    "serving": {"max_slots": 4, "max_seq": 64, "held_experts": 4}}

TOY_METRIC = '''"""Prompt tokens the window's prefills took, from the program's records."""


def read(run):
    n = sum(r.attrs["plen"] for r in run.spans
            if r.name == "repro:backend.prefill"
            and run.open * 1e9 <= r.start < run.close * 1e9)
    return float(n) if n else None
'''

# Runs in the copy: what a cell of the toy architecture asks of the harness.
TOY_RUN = r"""
import json, types
import jax
import numpy as np
from repro.core.trace import Record
from repro.models import model as M
from repro.models.config import ModelConfig
from benchmarks.chip import run, spec, weights, work
from benchmarks.chip.serve import Span

cell = spec.load_cell("toy-chat")
config = cell.config
cfg = ModelConfig(**spec.arch_module(config).model_fields(config))
ours, theirs = weights.shapes(config), M.abstract_params(cfg)
same = (jax.tree.structure(ours) == jax.tree.structure(theirs) and all(
    (a.shape, a.dtype) == (b.shape, b.dtype)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs))))
ids = np.array([[[0, 5], [3, 7]], [[4, 1], [2, 6]]])    # (L=2, T=2, k=2)
pre = Span(100.5, 100.6, 12, experts=ids)
dec = Span(101.0, 101.1, 2, lengths=np.array([9, 19]), experts=ids)
s = 1_000_000_000
recs = [Record("repro:backend.prefill", 100 * s + 1, 100 * s + 9,
               attrs={"plen": 12, "bucket": 16}),
        Record("repro:backend.prefill", 103 * s, 103 * s + 5,
               attrs={"plen": 20, "bucket": 32}),
        Record("repro:backend.prefill", 111 * s, 111 * s + 5,
               attrs={"plen": 7, "bucket": 16})]
trace = types.SimpleNamespace(window_s=1.0, kernel_s=lambda k: 1.0)
ran = run.Run(cell, config, 10.0, 100.0, 110.0, 1.0, [],
              types.SimpleNamespace(prefills=[pre], decodes=[dec]),
              trace=trace, trace_host=(100.0, 102.0),
              peaks={"bf16_flops": 1e6, "hbm_bytes_s": 1e4}, spans=recs)
read = {k: spec.metric_reader(k).read(ran) for k in (
    "prompt_tokens", "prefill_pad_share", "expert_host_ms", "model_mfu",
    "moe_gemm_roofline", "flash_decode_roofline")}
print(json.dumps({
    "fields": [cfg.attention_type, cfg.first_k_dense, cfg.num_shared_experts,
               cfg.num_experts, cfg.moe_top_k],
    "same_layout": same, "held": work.held_experts(config),
    "moe_gemm": work.kernel_work(config, "moe_gemm", dec),
    "flash_decode": work.kernel_work(config, "flash_decode", dec),
    "reference": callable(spec.reference_module(config).logits_at),
    "per_layer": [m["name"] for m in cell.per_layer], "read": read}))
"""

CORE = ("spec.py", "weights.py", "work.py", "run.py")


def test_an_architecture_and_its_metrics_are_new_files_only(tmp_path):
    """A toy architecture, its configuration, reference, cell and a metric
    on the program's spans, written into a copy of the harness beside its
    checkout's files, reach the program's layout, the work counts and the
    metric readers with the core files unedited."""
    import filecmp
    import subprocess
    import sys
    here = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns("__pycache__"))
    (here / "archs" / "latent_moe.py").write_text(TOY_ARCH)
    (here / "configs" / "toy-latent.json").write_text(json.dumps(TOY_CONFIG))
    (here / "references" / "latent_moe.py").write_text(
        '"""Stands for the plain reference of the toy."""\n\n\n'
        "def logits_at(*args, **kw):\n    raise NotImplementedError\n")
    (here / "cells" / "toy-chat.json").write_text(json.dumps(
        {"rate_rps": 1.0, "lead_in_s": 1.0, "sample_requests": 2, "limits": {}}))
    (here / "metrics" / "prompt_tokens.py").write_text(TOY_METRIC)
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    bench["configs"].append({"name": "toy-latent", "file": "x", "source": "x",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "toy-chat", "config": "toy-latent",
                               "traffic": "burstgpt-descending", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "prompt_tokens", "unit": "tokens",
                               "better": "higher", "source": "program_counter",
                               "layer": "x", "moves": "ttft_p50_ms",
                               "workloads": ["toy-chat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    p = subprocess.run(
        [sys.executable, "-c", TOY_RUN], cwd=tmp_path, capture_output=True,
        text=True, timeout=300,
        env={"PATH": "/usr/bin:/bin", "HOME": str(tmp_path),
             "PYTHONPATH": str(spec.ROOT / "src"), "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["fields"] == ["mla", 1, 2, 8, 2]
    assert out["same_layout"] and out["held"] == 4 and out["reference"]
    # 4 of the 8 routed ids land on the 4 held experts: 4 rows of 6*64*24
    assert out["moe_gemm"] == [4 * 6 * 64 * 24, 0]
    assert out["flash_decode"] == [0, (10 + 20) * (16 + 8) * 2]
    assert "prompt_tokens" in out["per_layer"]
    assert "expert_host_ms" not in out["per_layer"]    # qwen3-chat's alone
    read = out["read"]
    assert read["prompt_tokens"] == 12 + 20                  # 111 s: after
    assert read["prefill_pad_share"] == pytest.approx(100 * (4 + 12) / 48)
    assert read["expert_host_ms"] is None               # no expert records
    assert read["model_mfu"] == pytest.approx(100 * (1000 * 12 + 1000 * 2) / 1e6)
    # the prefill and the decode step each route 4 rows to held experts
    assert read["moe_gemm_roofline"] == pytest.approx(
        100 * 2 * (4 * 6 * 64 * 24 / 1e6))
    assert read["flash_decode_roofline"] == pytest.approx(
        100 * (30 * 24 * 2 / 1e4))
    assert all(filecmp.cmp(here / f, HERE / f, shallow=False) for f in CORE)


# ------------------------------------------------------------- chip required
def test_measurement_refuses_a_non_tpu_platform(capsys):
    import jax
    from benchmarks.chip import run
    assert jax.devices()[0].platform != "tpu"
    rc = run.main(["--workload", "qwen3-chat", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "TPU" in out.err


def test_a_checkout_without_the_program_prints_no_result(tmp_path):
    import subprocess
    import sys
    (tmp_path / "benchmarks").mkdir()
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run([sys.executable, "-m", "benchmarks.chip", "--workload",
                        "qwen3-chat", "--seed", "3", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
