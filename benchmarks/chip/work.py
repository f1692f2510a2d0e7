"""The chip's peaks, and the work an algorithm needs, counted from shapes.

Counts are of the work the computation needs, whatever implements it: the
K/V of valid tokens for attention, T*k routed rows and the weights of the
experts that received a token for the MoE GEMMs, the prompt and generated
tokens at their true lengths for the model.  Never bucket padding, capacity
padding or masked pages: a kernel that stops doing padded work then reads
higher, not stale.
"""
from __future__ import annotations

from typing import Dict, Iterable

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


def flash_decode(config: dict, lengths: Iterable[int]) -> tuple:
    """(flops, bytes) of one paged decode-attention call over every layer:
    each row with ``n`` resident tokens attends n + 1 (its new token too).
    Bytes: the valid K/V, the queries in and the outputs out."""
    z = spec.dims(config)
    lengths = np.asarray(list(lengths), np.int64)
    toks = int((lengths + 1).sum())
    flops = 4 * z["hq"] * z["hd"] * toks
    nbytes = 2 * z["hkv"] * z["hd"] * z["b"] * toks \
        + 2 * len(lengths) * z["hq"] * z["hd"] * z["b"]
    return z["L"] * flops, z["L"] * nbytes


def distinct_experts(expert_ids) -> int:
    """Experts that received a token, summed over layers.  ``expert_ids``:
    (L, ...) routed ids of the tokens of one call."""
    ids = np.asarray(expert_ids)
    return int(sum(len(np.unique(ids[l])) for l in range(ids.shape[0])))


def expected_experts(config: dict, tokens: int) -> float:
    """Experts hit, summed over layers, by ``tokens`` tokens under uniform
    top-k routing: E * (1 - (1 - k/E)^T) per layer (used where the program
    hands back no routed ids)."""
    z = spec.dims(config)
    return z["L"] * z["E"] * (1.0 - (1.0 - z["k"] / z["E"]) ** tokens)


def moe_gemm(config: dict, tokens: int, hit: int) -> tuple:
    """(flops, bytes) of the three expert GEMMs (gate, up, down) of one call
    over every layer, for ``tokens`` real tokens whose routing hit ``hit``
    experts summed over layers.  Bytes: the hit experts' weights once, and
    each routed row's input and output of each GEMM."""
    z = spec.dims(config)
    rows = tokens * z["k"]
    flops = z["L"] * 3 * 2 * rows * z["d"] * z["f"]
    nbytes = hit * 3 * z["d"] * z["f"] * z["b"] \
        + z["L"] * rows * 3 * (z["d"] + z["f"]) * z["b"]
    return flops, nbytes


def _per_token(z: Dict[str, int]) -> int:
    """Matmul FLOPs of one token through one layer, attention scores apart."""
    attn = 2 * z["d"] * (2 * z["hq"] + 2 * z["hkv"]) * z["hd"]
    if z["E"]:
        ffn = 2 * z["d"] * z["E"] + 2 * 3 * z["k"] * z["d"] * z["f"]
    else:
        ffn = 2 * 3 * z["d"] * z["F"]
    return attn + ffn


def model_prefill(config: dict, plen: int) -> int:
    """Model FLOPs of a ``plen``-token prefill: every layer for every token,
    causal attention over the true length, and the output head for the one
    position whose logits are used."""
    z = spec.dims(config)
    scores = 4 * z["hq"] * z["hd"] * plen * (plen + 1) // 2
    return z["L"] * (plen * _per_token(z) + scores) + 2 * z["d"] * z["V"]


def model_decode(config: dict, lengths: Iterable[int]) -> int:
    """Model FLOPs of one decode step over rows with these resident lengths."""
    z = spec.dims(config)
    lengths = np.asarray(list(lengths), np.int64)
    rows = len(lengths)
    scores = 4 * z["hq"] * z["hd"] * int((lengths + 1).sum())
    return z["L"] * (rows * _per_token(z) + scores) + rows * 2 * z["d"] * z["V"]
