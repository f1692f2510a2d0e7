"""The chip's peaks, and the work an algorithm needs, counted from shapes.

The counts belong to the configuration's arch module (spec.py), which this
module asks.  They are of the work the computation needs, whatever
implements it: the K/V of valid tokens for attention, the routed rows and
the weights of the experts that received a token for the MoE GEMMs, the
prompt and generated tokens at their true lengths for the model.  Never
bucket padding, capacity padding or masked pages: a kernel that stops doing
padded work then reads higher, not stale.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from benchmarks.chip import spec

# Published peaks of one chip, by jax's device_kind.  Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def least_seconds(flops: float, nbytes: float, pk: Dict[str, float]) -> float:
    """The roofline's least time: the larger of the two bounds."""
    return max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_s"])


def distinct_experts(expert_ids) -> int:
    """Experts that received a token, summed over layers.  ``expert_ids``:
    (L, ...) routed ids of the tokens of one call."""
    ids = np.asarray(expert_ids)
    return int(sum(len(np.unique(ids[l])) for l in range(ids.shape[0])))


def held_experts(config: dict) -> int:
    """Routed experts of a layer that the chip holds (0: dense)."""
    return spec.arch_module(config).held_experts(config)


def model_flops(config: dict, call) -> int:
    """Model FLOPs of one recorded prefill or decode step (serve.Span)."""
    return spec.arch_module(config).model_flops(config, call)


def kernel_work(config: dict, kernel: str, call) -> Optional[tuple]:
    """(flops, bytes) of ``kernel`` in one recorded call, or None where the
    call runs no such kernel."""
    return spec.arch_module(config).kernel_work(config, kernel, call)
