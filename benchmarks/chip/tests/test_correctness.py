"""``correct`` has to come out false when the timed path is broken, and for
a lower-precision control; true for a sound run.  Whole runs (set-up,
traffic, window, reference) at a size the CPU holds, with the harness's look
for a chip skipped and the Pallas kernels interpreted: a few minutes.

    PYTHONPATH=src python3 -m pytest -q benchmarks/chip/tests/test_correctness.py

The limits here are this size's own, set like the cells' (PERF.md) from
CPU readings of five seeds: a sound MoE run's widest gap reads 0.02-1.93
and its share of tokens off by more than 0.1 reads 0-0.025, the fp8
control's 0.108-0.136, a planted fault's 0.38-0.85 (widest 3.9-5.1); a
sound dense run's widest gap reads 0-0.025, the fp8 control's 0.113-0.297.
At this width int8 weights move the logits too little to separate
(0.010-0.111 dense), so the control here is fp8 for both.
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

from benchmarks.chip import check, faults, run, spec

TINY = {"name": "tiny-moe", "arch": "gqa_stack", "reference": "gqa_stack",
        "hidden_size": 128,
        "intermediate_size": 256, "head_dim": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
        "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 64,
        "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
        "tie_word_embeddings": False, "torch_dtype": "bfloat16",
        "serving": {"max_slots": 4, "max_seq": 128, "prefill_budget": 128,
                    "capacity_factor": 4.0}}
TRAFFIC = {"arrivals": {"process": "poisson"},
           "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                      "min": 8, "max": 60},
           "output": {"dist": "lognormal", "median": 28, "sigma": 0.4, "min": 16, "max": 40},
           "levels": 16, "master_seed": 0}
TINY_DENSE = dict({k: v for k, v in TINY.items() if "expert" not in k},
                  name="tiny-dense", tie_word_embeddings=True,
                  serving={"max_slots": 4, "max_seq": 128, "prefill_budget": 128})
LIMITS = {
    "moe": {"widest_gap": {"max": 2.5}, "share_gap_over_0.1": {"max": 0.08},
            "compared_tokens": {"min": 100}, "after_relocation": {"min": 1}},
    "dense": {"widest_gap": {"max": 0.08}, "compared_tokens": {"min": 100}},
}


def _cell(kind: str = "moe"):
    params = {"rate_rps": 6.0, "lead_in_s": 1.0, "sample_requests": 6,
              "limits": LIMITS[kind]}
    return spec.Cell(f"tiny-{kind}", TINY if kind == "moe" else TINY_DENSE,
                     TRAFFIC, params, 1, [{"name": "setup_s", "unit": "s"}], [])


def _measure(kind="moe", tamper=None, controls=None, keep_gaps=False):
    return run.measure(_cell(kind), 5, 3.0, False, jax.devices(),
                       tamper=tamper, controls=controls, keep_gaps=keep_gaps)


@pytest.fixture(scope="module", params=["moe", "dense"])
def sound(request):
    return request.param, _measure(request.param, controls=("fp8",),
                                   keep_gaps=True)


def test_a_sound_run_is_correct(sound):
    _kind, line = sound
    assert line["correct"], line["checks"]


def test_the_kept_gaps_are_the_compared_tokens(sound, tmp_path):
    _kind, line = sound
    n = line["checks"]["compared_tokens"]["value"]
    path = tmp_path / "gaps.npz"
    run.save_gaps(path, line["gap_arrays"])
    kept = np.load(path)
    assert sorted(kept.files) == ["fp8_gaps", "fp8_margins",
                                  "program_gaps", "program_margins"]
    assert len(kept["program_gaps"]) == len(kept["fp8_margins"]) == n
    assert kept["program_gaps"].max() == line["checks"]["widest_gap"]["value"]


def test_the_lower_precision_control_is_not_correct(sound):
    kind, line = sound
    held = check.compare(line["gap_stats"]["fp8"], LIMITS[kind])
    gap_limits = [k for k in held if k in line["gap_stats"]["fp8"]]
    assert not all(held[k]["ok"] for k in gap_limits), held


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_broken_timed_path_is_not_correct(fault):
    line = _measure(tamper=faults.plant(fault))
    assert not line["correct"], line["checks"]
