"""Expert activation + inter-layer affinity statistics (paper §III-D, Figs. 3-4).

Consumes the per-layer expert ids that moe_apply(return_stats=True) emits
((L, B, S, K) logical ids per scanned layer) and accumulates:

  * A  (n_layers, E)  — activation counts per expert per layer (Eq. 1)
  * W  (E, E)         — aggregated inter-layer traffic W[j,k] = sum_i E_{i,j,k}
                        (Eq. 2): expert j selected at layer i and expert k at
                        layer i+1 by the same token.

The accumulation kernel is jit-compiled; the tracker object is host-side state
(the paper collects these offline with vLLM's random benchmark, §V-A.6).
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("num_experts", "held_only"))
def accumulate_stats(expert_ids: jax.Array, num_experts: int,
                     held_only: bool = False) -> Tuple[jax.Array, jax.Array]:
    """expert_ids: (L, B, S, K) int32 logical expert ids.
    Returns (A (L, E) int32 counts, W (E, E) int32 inter-layer pair counts).
    ``held_only``: ids >= E (experts held elsewhere) count nowhere."""
    if held_only:
        ids = jnp.minimum(expert_ids, num_experts)     # E: the one "elsewhere"
        a, w = accumulate_stats(ids, num_experts + 1)
        return a[:, :num_experts], w[:num_experts, :num_experts]
    l, b, s, k = expert_ids.shape
    flat = expert_ids.reshape(l, b * s, k)
    a = jax.vmap(lambda ids: jnp.zeros((num_experts,), jnp.int32)
                 .at[ids.reshape(-1)].add(1))(flat)                       # (L, E)
    # inter-layer pairs: token t selects ids[i, t, :] then ids[i+1, t, :]
    up, dn = flat[:-1], flat[1:]                                          # (L-1, T, K)
    pair_idx = (up[..., :, None] * num_experts + dn[..., None, :])        # (L-1,T,K,K)
    w = jnp.zeros((num_experts * num_experts,), jnp.int32).at[
        pair_idx.reshape(-1)].add(1).reshape(num_experts, num_experts)
    return a, w


class AffinityTracker:
    """Host-side accumulator with exponential decay (recent traffic dominates,
    matching the paper's 'recent activation statistics' in Alg. 3)."""

    def __init__(self, num_layers: int, num_experts: int, decay: float = 1.0,
                 held_only: bool = False):
        """``held_only``: the ids fed may name experts >= num_experts, held
        elsewhere, which are not counted."""
        self.num_layers = num_layers
        self.num_experts = num_experts
        self.held_only = held_only
        self.decay = decay
        self.A = np.zeros((num_layers, num_experts), np.float64)
        self.W = np.zeros((num_experts, num_experts), np.float64)
        self.tokens_seen = 0

    def update(self, expert_ids) -> None:
        ids = jnp.asarray(expert_ids)
        a, w = accumulate_stats(ids, self.num_experts, self.held_only)
        if self.decay < 1.0:
            self.A *= self.decay
            self.W *= self.decay
        self.A += np.asarray(a, np.float64)
        self.W += np.asarray(w, np.float64)
        self.tokens_seen += int(np.prod(ids.shape[1:3]))

    # --- paper Fig. 4: retain only the strongest dependencies ---------------------
    def affinity_pairs(self, top_e: int = 16, min_count: float = 0.0
                       ) -> List[Tuple[int, int, float]]:
        """Top-E strongest (j, k, weight) inter-layer expert pairs, j != k."""
        w = self.W.copy()
        np.fill_diagonal(w, 0.0)
        flat = w.reshape(-1)
        order = np.argsort(flat)[::-1]
        out = []
        for idx in order[: top_e * 4]:
            val = flat[idx]
            if val <= min_count or len(out) >= top_e:
                break
            j, k = divmod(int(idx), self.num_experts)
            out.append((j, k, float(val)))
        return out

    def hot_experts(self, quantile: float = 0.9) -> np.ndarray:
        """Experts whose total activation exceeds the given quantile (Fig. 3)."""
        tot = self.A.sum(0)
        thr = np.quantile(tot, quantile)
        return np.where(tot >= thr)[0]

    def imbalance(self) -> float:
        """Mean over layers of (max expert load / mean expert load) — the
        hotspot severity signal motivating EDR."""
        a = self.A + 1e-9
        return float(np.mean(a.max(1) / a.mean(1)))


def synthetic_stats(key, num_layers: int, num_experts: int, tokens: int = 100_000,
                    hot_frac: float = 0.1, hot_boost: float = 8.0,
                    n_affine_pairs: int = 12, affine_strength: float = 6.0,
                    top_k: int = 2):
    """Generate Fig.3/Fig.4-shaped statistics without model weights: a few hot
    experts per layer and sparse strong inter-layer pairs (paper §III-D notes
    strong dependencies are 'sparse and localized').

    Used by the simulator and benchmarks when real routed traffic is not being
    replayed.  Returns (A (L,E) float, W (E,E) float, pairs list)."""
    rng = np.random.default_rng(np.asarray(jax.random.key_data(key)).sum() % (2**31))
    n_hot = max(1, int(num_experts * hot_frac))
    A = np.zeros((num_layers, num_experts))
    base = rng.dirichlet(np.ones(num_experts) * 4.0, size=num_layers)
    for i in range(num_layers):
        hot = rng.choice(num_experts, n_hot, replace=False)
        base[i, hot] *= hot_boost
        base[i] /= base[i].sum()
        A[i] = base[i] * tokens * top_k
    W = np.outer(A.mean(0), A.mean(0)) / (tokens * top_k)  # weak background coupling
    pairs = []
    for _ in range(n_affine_pairs):
        j, k = rng.choice(num_experts, 2, replace=False)
        W[j, k] += affine_strength * W.mean() * num_experts
        pairs.append((int(j), int(k)))
    return A, W, pairs
