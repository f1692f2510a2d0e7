"""A decoder stack of GQA attention over L uniform layers, each with a SwiGLU
feed-forward (dense) or a top-k mixture of SwiGLU experts, every expert on
the chip: Qwen3-MoE and Granite-3 as the served program states them.

What the harness knows of this architecture (the contract of an arch
module, spec.py): the program's ``ModelConfig`` fields, the seeded weight
tree in the program's layout, the routed experts the chip holds, and the
work of the model and of each kernel it runs, counted from shapes and from
what the harness's ``Recorder`` keeps of a call (serve.Span: prompt length,
resident lengths, routed expert ids).
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import weights, work

# Published config.json keys -> the program's ModelConfig fields.  Keys the
# program has no field for (Granite's multipliers, Qwen3's q/k norm) are
# listed as departures in the configuration file itself.
_FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "num_experts": "num_experts",
    "num_experts_per_tok": "moe_top_k",
    "moe_intermediate_size": "moe_d_ff",
    "torch_dtype": "dtype",
}


_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(config: dict) -> Dict[str, int]:
    """The sizes the harness computes with, from a configuration file:
    layers L, width d, heads hq / hkv of hd, vocab V, dense width F,
    experts E (0: dense) with top-k k and width f, bytes b per element."""
    d, hq = config["hidden_size"], config["num_attention_heads"]
    return {"L": config["num_hidden_layers"], "d": d, "hq": hq,
            "hkv": config["num_key_value_heads"],
            "hd": config.get("head_dim", d // hq), "V": config["vocab_size"],
            "F": config["intermediate_size"],
            "E": config.get("num_experts", 0),
            "k": config.get("num_experts_per_tok", 0),
            "f": config.get("moe_intermediate_size", 0),
            "b": _ITEMSIZE[config["torch_dtype"]]}


def model_fields(config: dict) -> Dict[str, object]:
    """ModelConfig keyword arguments for a configuration file: the published
    keys it holds, mapped by ``_FIELDS``, plus ``capacity_factor`` from the
    serving group.  ``head_dim`` defaults to hidden_size / heads as in the
    published models that omit it."""
    kw: Dict[str, object] = {"name": config["name"],
                             "family": "moe" if config.get("num_experts") else "dense",
                             "attention_type": "gqa"}
    for key, field in _FIELDS.items():
        if key in config:
            kw[field] = config[key]
    if "head_dim" not in kw:
        kw["head_dim"] = config["hidden_size"] // config["num_attention_heads"]
    cf: Optional[float] = config["serving"].get("capacity_factor")
    if cf is not None:
        kw["capacity_factor"] = float(cf)
    return kw


def held_experts(config: dict) -> int:
    """Routed experts of each layer on the chip: all of them (0: dense)."""
    return config.get("num_experts", 0)


# ----------------------------------------------------------------- weights
def tree(key, config: dict) -> Dict[str, Any]:
    """The weights in the program's layout (``models.model.init_params``):
    leaves stacked over the layers, experts stacked within a layer."""
    z = dims(config)
    L, d, hq, hkv, hd, V = z["L"], z["d"], z["hq"], z["hkv"], z["hd"], z["V"]
    dt = jnp.dtype(config["torch_dtype"])
    stacked = weights.stacked
    ks = iter(jax.random.split(key, 16))
    s_d = d ** -0.5
    normal = lambda k, shape, scale: (
        jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)
    embed = {"embedding": normal(next(ks), (V, d), s_d)}
    unembed_key = next(ks)
    if not config.get("tie_word_embeddings", False):
        embed["unembedding"] = normal(unembed_key, (V, d), s_d)
    norm = lambda k, n: stacked(k, n, (d,), weights.NORM_STD, dt)
    blocks: Dict[str, Any] = {
        "attn_norm": {"scale": norm(next(ks), L)},
        "attn": {"wq": stacked(next(ks), L, (d, hq, hd), s_d, dt),
                 "wk": stacked(next(ks), L, (d, hkv, hd), s_d, dt),
                 "wv": stacked(next(ks), L, (d, hkv, hd), s_d, dt),
                 "wo": stacked(next(ks), L, (hq, hd, d), (hq * hd) ** -0.5, dt)},
        "ffn_norm": {"scale": norm(next(ks), L)},
    }
    if z["E"]:
        E, f = z["E"], z["f"]
        blocks["moe"] = {
            "w_router": stacked(next(ks), L, (d, E), s_d, jnp.float32),
            "w_gate": stacked(next(ks), L * E, (d, f), s_d, dt).reshape(L, E, d, f),
            "w_up": stacked(next(ks), L * E, (d, f), s_d, dt).reshape(L, E, d, f),
            "w_down": stacked(next(ks), L * E, (f, d), f ** -0.5, dt).reshape(L, E, f, d),
        }
    else:
        F = z["F"]
        blocks["ffn"] = {"w_gate": stacked(next(ks), L, (d, F), s_d, dt),
                         "w_up": stacked(next(ks), L, (d, F), s_d, dt),
                         "w_down": stacked(next(ks), L, (F, d), F ** -0.5, dt)}
    return {"embed": embed,
            "final_norm": {"scale": stacked(next(ks), 1, (d,), weights.NORM_STD, dt)[0]},
            "blocks": blocks}


# -------------------------------------------------------------------- work
def flash_decode(config: dict, lengths: Iterable[int]) -> tuple:
    """(flops, bytes) of one paged decode-attention call over every layer:
    each row with ``n`` resident tokens attends n + 1 (its new token too).
    Bytes: the valid K/V, the queries in and the outputs out."""
    z = dims(config)
    lengths = np.asarray(list(lengths), np.int64)
    toks = int((lengths + 1).sum())
    flops = 4 * z["hq"] * z["hd"] * toks
    nbytes = 2 * z["hkv"] * z["hd"] * z["b"] * toks \
        + 2 * len(lengths) * z["hq"] * z["hd"] * z["b"]
    return z["L"] * flops, z["L"] * nbytes


def expected_experts(config: dict, tokens: int) -> float:
    """Experts hit, summed over layers, by ``tokens`` tokens under uniform
    top-k routing: E * (1 - (1 - k/E)^T) per layer (used where the program
    hands back no routed ids)."""
    z = dims(config)
    return z["L"] * z["E"] * (1.0 - (1.0 - z["k"] / z["E"]) ** tokens)


def moe_gemm(config: dict, tokens: int, hit: int) -> tuple:
    """(flops, bytes) of the three expert GEMMs (gate, up, down) of one call
    over every layer, for ``tokens`` real tokens whose routing hit ``hit``
    experts summed over layers.  Bytes: the hit experts' weights once, and
    each routed row's input and output of each GEMM."""
    z = dims(config)
    rows = tokens * z["k"]
    flops = z["L"] * 3 * 2 * rows * z["d"] * z["f"]
    nbytes = hit * 3 * z["d"] * z["f"] * z["b"] \
        + z["L"] * rows * 3 * (z["d"] + z["f"]) * z["b"]
    return flops, nbytes


def kernel_work(config: dict, kernel: str, call) -> Optional[tuple]:
    """(flops, bytes) of ``kernel`` in one recorded call: ``flash_decode`` in
    a decode step (resident lengths), ``moe_gemm`` in a prefill or a decode
    step of a mixture of experts (routed ids where the call handed them
    back, else the uniform router's expectation); None for a kernel the
    call does not run."""
    if kernel == "flash_decode" and call.lengths is not None:
        return flash_decode(config, call.lengths)
    if kernel == "moe_gemm" and held_experts(config):
        hit = (work.distinct_experts(call.experts) if call.experts is not None
               else expected_experts(config, call.rows))
        return moe_gemm(config, call.rows, hit)
    return None


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
    z = dims(config)
    scores = 4 * z["hq"] * z["hd"] * plen * (plen + 1) // 2
    return z["L"] * (plen * _per_token(z) + scores) + 2 * z["d"] * z["V"]


def model_decode(config: dict, lengths: Iterable[int]) -> int:
    """Model FLOPs of one decode step over rows with these resident lengths."""
    z = dims(config)
    lengths = np.asarray(list(lengths), np.int64)
    rows = len(lengths)
    scores = 4 * z["hq"] * z["hd"] * int((lengths + 1).sum())
    return z["L"] * (rows * _per_token(z) + scores) + rows * 2 * z["d"] * z["V"]


def model_flops(config: dict, call) -> int:
    """Model FLOPs of one recorded call: a prefill of ``call.rows`` tokens
    (no resident lengths) or a decode step over ``call.lengths``."""
    if call.lengths is None:
        return model_prefill(config, call.rows)
    return model_decode(config, call.lengths)
