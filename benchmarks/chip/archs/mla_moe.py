"""DeepSeek-V2 as the served program states it: multi-head latent attention
(MLA) with YaRN rope, a dense leading layer, then MoE layers of shared
experts and group-limited routed experts, of which the chip holds a share
(``n_routed_experts``, listed in ``reduced``: routed experts 0..held-1 of
each layer, one routing group of an expert-parallel deployment; the
router keeps its published width, ``router_experts``).

What the harness knows of this architecture (the contract of an arch
module, spec.py): the program's ``ModelConfig`` fields, the seeded weight
tree in the program's layout, the routed experts the chip holds, and the
work of the model and of each kernel it runs, at true lengths.

Rows routed to held experts: where a call carries ``held_rows`` (the
program's ``moe.held_rows`` counter, attached by the metric that reads the
program's records) that count; else the ids below ``held`` in the call's
routed ids (a decode step); else the expectation of k * held / n_routed
per token (a prefill, which hands back no ids).
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import weights, work

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(config: dict) -> Dict[str, int]:
    """The sizes the harness computes with: layers L (P of them dense,
    the prologue), width d, heads h, q / kv latent ranks rq / r, nope / rope
    / v head dims dn / dr / dv, dense width F, routed experts E (n of them
    held) of width f, top-k k, shared experts sh, vocab V, bytes b."""
    c = config
    return {"L": c["num_hidden_layers"], "P": c["first_k_dense_replace"],
            "d": c["hidden_size"], "h": c["num_attention_heads"],
            "rq": c["q_lora_rank"], "r": c["kv_lora_rank"],
            "dn": c["qk_nope_head_dim"], "dr": c["qk_rope_head_dim"],
            "dv": c["v_head_dim"], "F": c["intermediate_size"],
            "E": c["router_experts"], "n": held_experts(c),
            "f": c["moe_intermediate_size"], "k": c["num_experts_per_tok"],
            "sh": c["n_shared_experts"], "V": c["vocab_size"],
            "b": _ITEMSIZE[c["torch_dtype"]]}


def model_fields(config: dict) -> Dict[str, object]:
    """ModelConfig keyword arguments: the published keys, the held share
    (``num_experts`` = held, ``n_routed_experts`` = the router's width),
    YaRN from ``rope_scaling``, interleaved rope pairs (the published
    modelling code's ``apply_rotary_pos_emb``), and ``capacity_factor``
    from the serving group."""
    from repro.models.config import YarnScaling
    c = config
    ys = c["rope_scaling"]
    return {
        "name": c["name"], "family": "moe", "attention_type": "mla",
        "num_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
        "num_heads": c["num_attention_heads"],
        "num_kv_heads": c["num_key_value_heads"],
        "head_dim": c["qk_nope_head_dim"], "d_ff": c["intermediate_size"],
        "vocab_size": c["vocab_size"], "norm_eps": c["rms_norm_eps"],
        "rope_theta": c["rope_theta"],
        "tie_embeddings": c["tie_word_embeddings"],
        "q_lora_rank": c["q_lora_rank"], "kv_lora_rank": c["kv_lora_rank"],
        "qk_nope_head_dim": c["qk_nope_head_dim"],
        "qk_rope_head_dim": c["qk_rope_head_dim"],
        "v_head_dim": c["v_head_dim"],
        "num_experts": held_experts(c), "n_routed_experts": c["router_experts"],
        "num_shared_experts": c["n_shared_experts"],
        "moe_top_k": c["num_experts_per_tok"],
        "moe_d_ff": c["moe_intermediate_size"],
        "first_k_dense": c["first_k_dense_replace"],
        "moe_every": c["moe_layer_freq"],
        "n_group": c["n_group"], "topk_group": c["topk_group"],
        "routed_scaling_factor": float(c["routed_scaling_factor"]),
        "norm_topk_prob": c["norm_topk_prob"],
        "rope_scaling": YarnScaling(
            factor=float(ys["factor"]),
            original_max_position_embeddings=ys["original_max_position_embeddings"],
            beta_fast=float(ys["beta_fast"]), beta_slow=float(ys["beta_slow"]),
            mscale=float(ys["mscale"]), mscale_all_dim=float(ys["mscale_all_dim"])),
        "rope_interleave": True,
        "capacity_factor": float(c["serving"]["capacity_factor"]),
        "dtype": c["torch_dtype"],
    }


def held_experts(config: dict) -> int:
    """Routed experts of each MoE layer on the chip: experts 0..held-1."""
    return config["n_routed_experts"]


# ----------------------------------------------------------------- weights
def tree(key, config: dict) -> Dict[str, Any]:
    """The weights in the program's layout (``models.model.init_params``):
    the dense prologue as a list of layers, the MoE layers stacked, the
    held experts stacked within a layer.  Normal, scaled by fan-in; norm
    scales drawn around 0 (the program's RMSNorm multiplies by 1 + scale)."""
    z = dims(config)
    d, h, rq, r, dn, dr, dv = (z[x] for x in ("d", "h", "rq", "r", "dn", "dr", "dv"))
    n_pro, n_moe = z["P"], z["L"] - z["P"]
    dt = jnp.dtype(config["torch_dtype"])
    ks = iter(jax.random.split(key, 32))
    st = lambda n, shape, fan_in: weights.stacked(next(ks), n, shape,
                                                  fan_in ** -0.5, dt)
    norm = lambda n, w: weights.stacked(next(ks), n, (w,), weights.NORM_STD, dt)

    def block(n, ffn):
        return {"attn_norm": {"scale": norm(n, d)},
                "attn": {"wq_a": st(n, (d, rq), d), "q_norm": norm(n, rq),
                         "wq_b": st(n, (rq, h, dn + dr), rq),
                         "wkv_a": st(n, (d, r + dr), d), "kv_norm": norm(n, r),
                         "wkv_b": st(n, (r, h, dn + dv), r),
                         "wo": st(n, (h, dv, d), h * dv)},
                "ffn_norm": {"scale": norm(n, d)}, **ffn}

    F = z["F"]
    pro = block(n_pro, {"ffn": {"w_gate": st(n_pro, (d, F), d),
                                "w_up": st(n_pro, (d, F), d),
                                "w_down": st(n_pro, (F, d), F)}})
    E, f, g = z["n"], z["f"], z["f"] * z["sh"]
    moe = {"w_router": weights.stacked(next(ks), n_moe, (d, z["E"]), d ** -0.5,
                                       jnp.float32),
           "w_gate": st(n_moe * E, (d, f), d).reshape(n_moe, E, d, f),
           "w_up": st(n_moe * E, (d, f), d).reshape(n_moe, E, d, f),
           "w_down": st(n_moe * E, (f, d), f).reshape(n_moe, E, f, d),
           "shared": {"w_gate": st(n_moe, (d, g), d), "w_up": st(n_moe, (d, g), d),
                      "w_down": st(n_moe, (g, d), g)}}
    return {"embed": {"embedding": st(1, (z["V"], d), d)[0],
                      "unembedding": st(1, (z["V"], d), d)[0]},
            "final_norm": {"scale": norm(1, d)[0]},
            "prologue": [jax.tree.map(lambda x, i=i: x[i], pro)
                         for i in range(n_pro)],
            "blocks": block(n_moe, {"moe": moe})}


# -------------------------------------------------------------------- work
def held_rows(config: dict, call) -> float:
    """Routed (token, expert) pairs of one call on held experts, over every
    MoE layer (the module docstring says where the count comes from)."""
    z = dims(config)
    rows = getattr(call, "held_rows", None)
    if rows is not None:
        return float(rows)
    if call.experts is not None:
        return float((np.asarray(call.experts) < z["n"]).sum())
    return (z["L"] - z["P"]) * call.rows * z["k"] * z["n"] / z["E"]


def held_hit(config: dict, call) -> float:
    """Held experts that received a token, summed over the MoE layers: from
    the routed ids where the call has them, else the expectation n * (1 -
    (1 - k/E)^T) per layer."""
    z = dims(config)
    if call.experts is not None:
        ids = np.asarray(call.experts)
        return float(sum(len(np.unique(ids[l][ids[l] < z["n"]]))
                         for l in range(ids.shape[0])))
    return (z["L"] - z["P"]) * z["n"] * (1.0 - (1.0 - z["k"] / z["E"]) ** call.rows)


def flash_decode(config: dict, lengths: Iterable[int]) -> tuple:
    """(flops, bytes) of the latent decode attention over every layer: each
    row with ``n`` resident tokens attends n + 1, every head scoring the
    latent and rope key (R + Dr) and summing the latent (R), reading each
    token's (R + Dr) latents once."""
    z = dims(config)
    toks = int((np.asarray(list(lengths), np.int64) + 1).sum())
    flops = 2 * z["h"] * (z["r"] + z["dr"] + z["r"]) * toks
    nbytes = (z["r"] + z["dr"]) * z["b"] * toks
    return z["L"] * flops, z["L"] * nbytes


def moe_gemm(config: dict, rows: float, hit: float) -> tuple:
    """(flops, bytes) of the three expert GEMMs over ``rows`` held (token,
    expert) pairs of every layer: 6 d f FLOPs a row; the weights of the
    ``hit`` held experts once, and each row's input and output of each."""
    z = dims(config)
    flops = rows * 6 * z["d"] * z["f"]
    nbytes = hit * 3 * z["d"] * z["f"] * z["b"] \
        + rows * 3 * (z["d"] + z["f"]) * z["b"]
    return flops, nbytes


def kernel_work(config: dict, kernel: str, call) -> Optional[tuple]:
    """(flops, bytes) of ``kernel`` in one recorded call: ``flash_decode``
    in a decode step, ``moe_gemm`` over the rows routed to held experts;
    None for a kernel the call does not run."""
    if kernel == "flash_decode" and call.lengths is not None:
        return flash_decode(config, call.lengths)
    if kernel == "moe_gemm":
        return moe_gemm(config, held_rows(config, call), held_hit(config, call))
    return None


def _projections(z: Dict[str, int]) -> int:
    """Matmul FLOPs of one token's attention projections in one layer: the
    q latent and its heads, the kv latent and rope key, and the output."""
    h = z["h"]
    return 2 * (z["d"] * z["rq"] + z["rq"] * h * (z["dn"] + z["dr"])
                + z["d"] * (z["r"] + z["dr"]) + h * z["dv"] * z["d"])


def _ffn(z: Dict[str, int], tokens: int, rows: float) -> float:
    """FFN FLOPs of ``tokens`` tokens over every layer: the dense prologue's,
    and in each MoE layer the router, the shared experts, and the ``rows``
    held rows (summed over the MoE layers)."""
    dense = z["P"] * tokens * 6 * z["d"] * z["F"]
    moe = (z["L"] - z["P"]) * tokens * (2 * z["d"] * z["E"]
                                        + 6 * z["d"] * z["f"] * z["sh"])
    return dense + moe + rows * 6 * z["d"] * z["f"]


def model_prefill(config: dict, call) -> float:
    """Model FLOPs of a prefill of ``call.rows`` tokens: the latents
    decompressed to per-head keys and values, causal attention over the
    true length, the FFNs, and the head for the one position used."""
    z = dims(config)
    t = call.rows
    kv_b = 2 * z["r"] * z["h"] * (z["dn"] + z["dv"])
    scores = 2 * z["h"] * (z["dn"] + z["dr"] + z["dv"]) * t * (t + 1) // 2
    attn = z["L"] * (t * (_projections(z) + kv_b) + scores)
    return attn + _ffn(z, t, held_rows(config, call)) + 2 * z["d"] * z["V"]


def model_decode(config: dict, call) -> float:
    """Model FLOPs of one decode step (weight-absorbed attention over the
    resident latents) over rows with these resident lengths."""
    z = dims(config)
    rows = len(call.lengths)
    absorb = 2 * z["h"] * z["r"] * (z["dn"] + z["dv"])  # q W_UK, o_lat W_UV
    att, _ = flash_decode(config, call.lengths)
    attn = z["L"] * rows * (_projections(z) + absorb) + att
    return attn + _ffn(z, rows, held_rows(config, call)) \
        + rows * 2 * z["d"] * z["V"]


def model_flops(config: dict, call) -> float:
    """Model FLOPs of one recorded call: a prefill (no resident lengths) or
    a decode step."""
    if call.lengths is None:
        return model_prefill(config, call)
    return model_decode(config, call)
