"""Plain reference of DeepSeek-V2 (multi-head latent attention with YaRN
rope, a dense leading layer, shared experts and group-limited routed
experts) over the share of routed experts the chip holds, in
straightforward jax.numpy, following the published modelling code
(huggingface.co/deepseek-ai/DeepSeek-V2, modeling_deepseek.py).

One sequence at a time, the whole sequence at once (no cache, no paging, no
batching, no kernels, no weight absorption), layer by layer, the attention
computed in blocks of query rows so that 128 heads over thousands of tokens
fit.  ``mode="f32"`` is the reference: every weight and activation in
float32 at "highest" matmul precision.  The lower modes are the controls
that ``correct`` must refuse: every weight rounded to int8 (``"int8"``) or
float8 e4m3 (``"fp8"``) with a symmetric scale per output channel, and
activations in bfloat16.

Equations (per layer, x the residual stream; norms scale by 1 + w):
    h = rmsnorm(x)
    q = rmsnorm(h Wq_a) Wq_b = [q_nope | q_pe]           (128 + 64 per head)
    [c | k_pe] = h Wkv_a;  c = rmsnorm(c)                 (512 + 64)
    q_pe, k_pe = rope(q_pe), rope(k_pe)    YaRN frequencies, the pairs
                                           (x[2i], x[2i+1]) (the published
                                           code de-interleaves, then
                                           rotates halves)
    [k_nope | v] = c Wkv_b                                (128 + 128 per head)
    x = x + softmax(causal([q_nope|q_pe] [k_nope|k_pe]^T * s)) v Wo
        s = 192**-0.5 * mscale(40, 0.707)**2
    h = rmsnorm(x)
    dense:   x = x + (silu(h Wg) * (h Wu)) Wd
    experts: p = softmax(h Wr) over all 160 routed experts; each group of
             20 scored by its best p; p outside the 3 best groups set to 0;
             the top 6 of p, gates = 16 * p (not renormalised);
             x = x + shared(h) + sum over the chosen experts e held here
                 (e < held) of gate_e * swiglu_e(h)
    logits = rmsnorm(x) Wunemb^T

Beside the logits it reports, per position, how decisive the routing was:
the smallest over the layers of the 6th largest masked score minus the 7th
and of the 3rd best group score minus the 4th.  Where that margin is within
rounding, any precision may choose either expert or group (check.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256                # query rows of one attention block


def _wq(w, mode: str, contract: tuple):
    """A weight as ``mode`` computes with it; ``contract`` are its input axes
    (the scale is per output channel)."""
    if mode == "f32":
        return w.astype(jnp.float32)
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=contract, keepdims=True)
    if mode == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        q = jnp.clip(jnp.round(w / s), -127, 127) * s
    elif mode == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        q = (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    else:
        raise ValueError(f"unknown mode {mode!r}")
    return q.astype(jnp.bfloat16)


def _act(mode: str):
    return jnp.float32 if mode == "f32" else jnp.bfloat16


def _mm(eq, a, b, mode):
    return jnp.einsum(eq, a.astype(_act(mode)), b,
                      preferred_element_type=jnp.float32)


def _rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * (1.0 + scale.astype(jnp.float32))


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, ys: dict) -> np.ndarray:
    """DeepseekV2YarnRotaryEmbedding's inverse frequencies, in float64."""
    def corr(rot):
        return (dim * math.log(ys["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(theta))
    low = max(math.floor(corr(ys["beta_fast"])), 0)
    high = min(math.ceil(corr(ys["beta_slow"])), dim - 1)
    high = high + 0.001 if low == high else high
    pos = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra, inter = 1.0 / theta ** pos, 1.0 / (ys["factor"] * theta ** pos)
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return inter * (1 - mask) + extra * mask


def softmax_scale(config: dict) -> float:
    ys = config["rope_scaling"]
    m = _mscale(ys["factor"], ys["mscale_all_dim"])
    return (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, inv_freq, cos_sin_scale):
    """x: (T, H, D) f32.  The published apply_rotary_pos_emb: de-interleave
    (even dims, then odd), then rotate halves by position."""
    t, _, dh = x.shape
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    emb = jnp.concatenate([ang, ang], -1)[:, None, :]
    cos, sin = jnp.cos(emb) * cos_sin_scale, jnp.sin(emb) * cos_sin_scale
    rot = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], -1)
    return x * cos + rot * sin


def _attention(q, k, v, scale, mode):
    """Causal attention in blocks of Q_BLOCK query rows.  q, k: (T, H, Dq);
    v: (T, H, Dv); T a multiple of Q_BLOCK."""
    t = q.shape[0]
    dt = _act(mode)
    k, v = k.astype(dt), v.astype(dt)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = _mm("qhd,khd->hqk", qb, k, mode) * scale
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(rows[None, :, None] >= jnp.arange(t)[None, None, :],
                      s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return _mm("hqk,khd->qhd", w, v, mode)

    out = jax.lax.map(block, jnp.arange(t // Q_BLOCK))
    return out.reshape(t, *out.shape[2:])


def _route(h, w_router, n_group, topk_group, k, scale, held, mode):
    """(combine (T, held) gate of each held expert, margin (T,)): the
    group_limited_greedy choice over all routed experts, its gates, and how
    far the 6th masked score and the 3rd group score clear the next."""
    logits = _mm("td,de->te", h, _wq(w_router, mode, (0,)), mode)
    scores = jax.nn.softmax(logits, -1)                        # (T, E)
    t, e = scores.shape
    group = scores.reshape(t, n_group, e // n_group).max(-1)   # (T, G)
    gtop, gidx = jax.lax.top_k(group, topk_group + 1)
    chosen = jnp.zeros((t, n_group), bool).at[
        jnp.arange(t)[:, None], gidx[:, :topk_group]].set(True)
    masked = jnp.where(jnp.repeat(chosen, e // n_group, 1), scores, 0.0)
    top, idx = jax.lax.top_k(masked, k + 1)
    gates = top[:, :k] * scale
    combine = jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], idx[:, :k]].set(gates)[:, :held]
    margin = jnp.minimum(top[:, k - 1] - top[:, k],
                         gtop[:, topk_group - 1] - gtop[:, topk_group])
    return combine, margin


def _swiglu(h, wg, wu, wd, mode, contract):
    a = jax.nn.silu(_mm("td,df->tf", h, _wq(wg, mode, contract), mode)) \
        * _mm("td,df->tf", h, _wq(wu, mode, contract), mode)
    return _mm("tf,fd->td", a, _wq(wd, mode, contract), mode)


def _layer(p, x, *, mode: str, geo: tuple):
    (h_n, dn, dr, eps, theta, ys, scale, n_group, topk_group, k, rscale,
     held) = geo
    ys = dict(ys)
    t = x.shape[0]
    a = p["attn"]
    h = _rmsnorm(x, p["attn_norm"]["scale"], eps)
    cq = _rmsnorm(_mm("td,dr->tr", h, _wq(a["wq_a"], mode, (0,)), mode),
                  a["q_norm"], eps)
    q = _mm("tr,rhk->thk", cq, _wq(a["wq_b"], mode, (0,)), mode)
    kv_a = _mm("td,dr->tr", h, _wq(a["wkv_a"], mode, (0,)), mode)
    r = kv_a.shape[-1] - dr
    c = _rmsnorm(kv_a[:, :r], a["kv_norm"], eps)
    inv = yarn_inv_freq(dr, theta, ys)
    cs = _mscale(ys["factor"], ys["mscale"]) / _mscale(ys["factor"], ys["mscale_all_dim"])
    q_pe = _rope(q[..., dn:], inv, cs)
    k_pe = _rope(kv_a[:, None, r:], inv, cs)
    kv = _mm("tr,rhk->thk", c, _wq(a["wkv_b"], mode, (0,)), mode)
    k_full = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_pe, (t, h_n, dr))], -1)
    q_full = jnp.concatenate([q[..., :dn], q_pe], -1)
    o = _attention(q_full, k_full, kv[..., dn:], scale, mode)
    x = x + _mm("thk,hkd->td", o, _wq(a["wo"], mode, (0, 1)), mode)
    h = _rmsnorm(x, p["ffn_norm"]["scale"], eps)
    if "moe" in p:
        m = p["moe"]
        combine, margin = _route(h, m["w_router"], n_group, topk_group, k,
                                 rscale, held, mode)
        sh = m["shared"]
        y = _swiglu(h, sh["w_gate"], sh["w_up"], sh["w_down"], mode, (0,))

        def expert(acc, e):
            ye = _swiglu(h, m["w_gate"][e], m["w_up"][e], m["w_down"][e],
                         mode, (0,))
            return acc + ye * combine[:, e][:, None], None

        y, _ = jax.lax.scan(expert, y, jnp.arange(held))
    else:
        f = p["ffn"]
        y = _swiglu(h, f["w_gate"], f["w_up"], f["w_down"], mode, (0,))
        margin = jnp.full((t,), jnp.inf, jnp.float32)
    return (x + y).astype(jnp.float32 if mode == "f32" else jnp.bfloat16), margin


@functools.partial(jax.jit, static_argnames=("mode", "geo"))
def _dense_layer(p, x, *, mode: str, geo: tuple):
    return _layer(p, x, mode=mode, geo=geo)


@functools.partial(jax.jit, static_argnames=("mode", "geo"))
def _stack_layer(blocks, l, x, *, mode: str, geo: tuple):
    """Layer ``l`` of the stacked (MoE) layers, sliced inside the program."""
    return _layer(jax.tree.map(lambda a: a[l], blocks), x, mode=mode, geo=geo)


@functools.partial(jax.jit, static_argnames=("mode", "eps"))
def _head(params, x, rows, *, mode: str, eps: float):
    emb = params["embed"].get("unembedding", params["embed"]["embedding"])
    h = _rmsnorm(x[rows], params["final_norm"]["scale"], eps)
    return _mm("nd,vd->nv", h, _wq(emb, mode, (1,)), mode)


def _geo(config: dict) -> tuple:
    ys = tuple(sorted((k, v) for k, v in config["rope_scaling"].items()
                      if k != "type"))
    return (config["num_attention_heads"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], float(config["rms_norm_eps"]),
            float(config["rope_theta"]), ys, softmax_scale(config),
            config["n_group"], config["topk_group"],
            config["num_experts_per_tok"],
            float(config["routed_scaling_factor"]), config["n_routed_experts"])


def logits_at(params, config: dict, tokens: np.ndarray, rows: np.ndarray,
              mode: str = "f32", pad_to: int = 1024):
    """(logits, margin) at positions ``rows`` of the sequence ``tokens``:
    logits (R, V) f32, and for each row the smallest over the MoE layers of
    its routing margin (module docstring).  Computed over the whole
    sequence padded with zeros to a multiple of ``pad_to`` (causal: the
    padding cannot reach a row).  ``rows`` is padded to R, a multiple of
    ``pad_to``, by repeating its last row: the first len(rows) rows are the
    ones asked for.  (1024: prompts of up to 4096 tokens and answers of up
    to 1024 compile at most five shapes.)"""
    t, n = len(tokens), len(rows)
    tp = -(-t // pad_to) * pad_to
    ids = np.zeros(tp, np.int32)
    ids[:t] = tokens
    sel = np.full(-(-n // pad_to) * pad_to, rows[-1], np.int32)
    sel[:n] = rows
    geo = _geo(config)
    with jax.default_matmul_precision("highest" if mode == "f32" else "default"):
        x = params["embed"]["embedding"][jnp.asarray(ids)].astype(_act(mode))
        if mode == "f32":
            x = x.astype(jnp.float32)
        margin = jnp.full((tp,), jnp.inf, jnp.float32)
        for p in params["prologue"]:
            x, m = _dense_layer(p, x, mode=mode, geo=geo)
            margin = jnp.minimum(margin, m)
        blocks = params["blocks"]
        for l in range(jax.tree.leaves(blocks)[0].shape[0]):
            x, m = _stack_layer(blocks, l, x, mode=mode, geo=geo)
            margin = jnp.minimum(margin, m)
        sel = jnp.asarray(sel)
        return _head(params, x, sel, mode=mode, eps=geo[3]), margin[sel]
