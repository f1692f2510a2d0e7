"""Plain reference of a decoder stack of GQA attention and a SwiGLU feed-forward
(dense) or a top-k mixture of SwiGLU experts: the equations of Qwen3-MoE and
Granite-3 as the served program states them, in straightforward jax.numpy.

One sequence at a time, the whole sequence at once (no cache, no paging, no
batching, no kernels), layer by layer.  ``mode="f32"`` is the reference:
every weight and activation in float32 at "highest" matmul precision.  The
lower modes are the controls that ``correct`` must refuse: every weight
rounded to int8 (``"int8"``) or float8 e4m3 (``"fp8"``) with a symmetric
scale per output channel, and activations in bfloat16.

Equations (per layer, x the residual stream):
    h = rmsnorm(x) * (1 + attn_norm)
    q, k, v = h Wq, h Wk, h Wv;  q, k = rope(q), rope(k)   (half-split pairs)
    x = x + softmax(causal(q k^T / sqrt(head_dim))) v Wo   (kv heads shared
                                                            by groups of q heads)
    h = rmsnorm(x) * (1 + ffn_norm)
    dense:  x = x + (silu(h Wg) * (h Wu)) Wd
    experts: p = softmax(h Wr);  top-k of p, renormalised to sum 1 (gates);
            x = x + sum over the k chosen experts e of gate_e * swiglu_e(h)
    logits = (rmsnorm(x) * (1 + final_norm)) Wemb^T     (Wemb tied or not)

Beside the logits it reports, per position, how decisive the routing was:
the smallest over the layers of the k-th largest router logit minus the
(k+1)-th.  Where that margin is within rounding, any precision may choose
either expert (check.py).

Departures from the published models are the program's and are listed in
each configuration file: Qwen3's q/k RMSNorm and Granite's four scalar
multipliers are not part of the served equations, so not of these.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EXPERT_CHUNK = 8             # experts computed together (bounds temporaries)


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


def _rope(x, theta: float):
    """x: (T, H, D) f32; rotates (x[:D/2], x[D/2:]) pairs by position."""
    t, _, dh = x.shape
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@functools.partial(jax.jit, static_argnames=("mode", "geo"))
def _layer(blocks, l, x, *, mode: str, geo: tuple):
    hq, hkv, hd, topk, eps, theta = geo
    p = jax.tree.map(lambda a: a[l], blocks)
    t = x.shape[0]
    dt = _act(mode)
    h = _rmsnorm(x, p["attn_norm"]["scale"], eps)
    q = _mm("td,dhk->thk", h, _wq(p["attn"]["wq"], mode, (0,)), mode)
    k = _mm("td,dhk->thk", h, _wq(p["attn"]["wk"], mode, (0,)), mode)
    v = _mm("td,dhk->thk", h, _wq(p["attn"]["wv"], mode, (0,)), mode)
    q, k = _rope(q, theta), _rope(k, theta)
    g = hq // hkv
    qg = q.reshape(t, hkv, g, hd)
    s = _mm("tkgd,skd->kgts", qg, k.astype(dt), mode) * hd ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = _mm("kgts,skd->tkgd", w, v.astype(dt), mode).reshape(t, hq, hd)
    x = x + _mm("thk,hkd->td", o, _wq(p["attn"]["wo"], mode, (0, 1)), mode)
    h = _rmsnorm(x, p["ffn_norm"]["scale"], eps)
    if "moe" in p:
        m = p["moe"]
        logits = _mm("td,de->te", h, _wq(m["w_router"], mode, (0,)), mode)
        probs = jax.nn.softmax(logits, -1)
        top, idx = jax.lax.top_k(probs, topk)
        gates = top / top.sum(-1, keepdims=True)
        edge = jax.lax.top_k(logits, topk + 1)[0]
        margin = edge[:, topk - 1] - edge[:, topk]
        n_e = probs.shape[-1]
        combine = jnp.zeros((t, n_e), jnp.float32).at[
            jnp.arange(t)[:, None], idx].set(gates)
        c = EXPERT_CHUNK

        def chunk(acc, i):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * c, c, 0)
            wg = _wq(sl(m["w_gate"]), mode, (1,))
            wu = _wq(sl(m["w_up"]), mode, (1,))
            wd = _wq(sl(m["w_down"]), mode, (1,))
            a = jax.nn.silu(_mm("td,edf->etf", h, wg, mode)) \
                * _mm("td,edf->etf", h, wu, mode)
            y = _mm("etf,efd->etd", a, wd, mode)
            wt = jax.lax.dynamic_slice_in_dim(combine, i * c, c, 1)   # (T, c)
            return acc + jnp.einsum("etd,te->td", y, wt,
                                    precision="highest"), None

        y, _ = jax.lax.scan(chunk, jnp.zeros_like(x, jnp.float32),
                            jnp.arange(n_e // c))
    else:
        f = p["ffn"]
        a = jax.nn.silu(_mm("td,df->tf", h, _wq(f["w_gate"], mode, (0,)), mode)) \
            * _mm("td,df->tf", h, _wq(f["w_up"], mode, (0,)), mode)
        y = _mm("tf,fd->td", a, _wq(f["w_down"], mode, (0,)), mode)
        margin = jnp.full((t,), jnp.inf, jnp.float32)
    return (x + y).astype(dt if mode != "f32" else jnp.float32), margin


@functools.partial(jax.jit, static_argnames=("mode", "eps"))
def _head(params, x, rows, *, mode: str, eps: float):
    emb = params["embed"].get("unembedding", params["embed"]["embedding"])
    h = _rmsnorm(x[rows], params["final_norm"]["scale"], eps)
    return _mm("nd,vd->nv", h, _wq(emb, mode, (1,)), mode)


def _geo(config: dict) -> tuple:
    d, hq = config["hidden_size"], config["num_attention_heads"]
    return (hq, config["num_key_value_heads"], config.get("head_dim", d // hq),
            config.get("num_experts_per_tok", 0), float(config["rms_norm_eps"]),
            float(config["rope_theta"]))


def logits_at(params, config: dict, tokens: np.ndarray, rows: np.ndarray,
              mode: str = "f32", pad_to: int = 256):
    """(logits, margin) at positions ``rows`` of the sequence ``tokens``:
    logits (R, V) f32, and for each row the smallest over the layers of its
    router's margin, the k-th largest router logit minus the (k+1)-th (inf
    for a dense stack).  Computed over the whole sequence padded with zeros
    to a multiple of ``pad_to`` (causal: the padding cannot reach a row).
    ``rows`` is padded to R, a multiple of ``pad_to``, by repeating its last
    row, so that few shapes compile: the first len(rows) rows are the ones
    asked for."""
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
        for l in range(config["num_hidden_layers"]):
            x, m = _layer(params["blocks"], l, x, mode=mode, geo=geo)
            margin = jnp.minimum(margin, m)
        sel = jnp.asarray(sel)
        return _head(params, x, sel, mode=mode, eps=geo[4]), margin[sel]
