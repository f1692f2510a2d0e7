"""How ``correct`` is decided for a served model.

Once the window has closed and the program's state is freed, a sample drawn
from the seed of the requests the run finished (always with the longest of
them, and for a mixture of experts with at least one prefilled after an
expert relocation) is replayed through the plain reference: each prompt
followed by its served tokens, teacher-forced, in one pass.  At every
position whose logits produced a served token the reference's best logit
minus its logit of the served token is that token's gap: 0 where the
program chose what the reference would, and the margin by which the
reference prefers another token where rounding made the program choose
otherwise.  Numbers of the gaps over the sample (``summary``) are compared
with the cell's limits, set from chip runs (PERF.md).  The served tokens are
greedy, and came from the prefill's logits (the first) and from the paged
decode steps (the rest), so the comparison covers the router, engine,
scheduler, backend, model and kernels of the timed path at the timed sizes.

In a mixture of experts a token whose k-th and (k+1)-th router logits
nearly tie in some layer takes either expert under any rounding, and the
swap moves its logits by an expert's share: such gaps say little about
precision.  So beside the widest gap over every token, ``summary`` gives
the widest over the decisive tokens, those whose smallest router margin
(the reference's, over the layers) is at least the cell's
``decisive_margin``.

The control is the reference itself at a lower precision (int8 or fp8
weights, bf16 activations): at each position of the same sequences, the gap
of the token the control ranks first.  It has to fail one of the limits.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Served:
    req_id: int
    prompt: np.ndarray
    tokens: List[int]
    after_relocation: bool


def sample(served: List[Served], seed: int, n: int,
           want_relocation: bool) -> List[Served]:
    """``n`` requests: the one with the most served tokens, and others drawn
    from the seed; for ``want_relocation`` one of the others is swapped for
    a request prefilled after a relocation when the draw holds none."""
    if not served:
        return []
    order = sorted(served, key=lambda s: (-len(s.tokens), s.req_id))
    longest, rest = order[0], sorted(order[1:], key=lambda s: s.req_id)
    rng = np.random.default_rng([seed % (1 << 64), 2])
    picks = [rest[i] for i in rng.permutation(len(rest))[:max(n - 1, 0)]]
    chosen = [longest] + picks
    if want_relocation and not any(s.after_relocation for s in chosen):
        late = [s for s in rest if s.after_relocation and s not in picks]
        if late:
            swap = late[int(rng.integers(len(late)))]
            chosen = chosen[:-1] + [swap] if picks else chosen + [swap]
    return chosen


@jax.jit
def _gap(logits, chosen):
    best = jnp.max(logits, -1)
    return best - jnp.take_along_axis(logits, chosen[:, None], -1)[:, 0]


def _rows(s: Served):
    seq = np.concatenate([s.prompt, np.asarray(s.tokens[:-1], np.int32)])
    p = len(s.prompt)
    return seq, np.arange(p - 1, p - 1 + len(s.tokens), dtype=np.int32)


def _pad(x: np.ndarray, n: int) -> np.ndarray:
    out = np.full(n, x[-1], x.dtype)
    out[:len(x)] = x
    return out


def served_gaps(ref, params, config: dict, s: Served):
    """(gaps, margins): the reference's gap of each served token of ``s``,
    and the reference's router margin at its position."""
    seq, rows = _rows(s)
    logits, margin = ref.logits_at(params, config, seq, rows, "f32")
    chosen = jnp.asarray(_pad(np.asarray(s.tokens, np.int32), logits.shape[0]))
    n = len(rows)
    return np.asarray(_gap(logits, chosen))[:n], np.asarray(margin)[:n]


def control_gaps(ref, params, config: dict, s: Served, mode: str):
    """(gaps, margins): the reference's gap of the token the ``mode`` control
    ranks first at each position where ``s`` was served a token, and the
    reference's router margin there."""
    seq, rows = _rows(s)
    low, _ = ref.logits_at(params, config, seq, rows, mode)
    chosen = jnp.argmax(low, -1).astype(jnp.int32)
    del low
    logits, margin = ref.logits_at(params, config, seq, rows, "f32")
    n = len(rows)
    return np.asarray(_gap(logits, chosen))[:n], np.asarray(margin)[:n]


def summary(gaps: np.ndarray, margins: np.ndarray,
            decisive_margin: Optional[float] = None) -> Dict[str, float]:
    """The numbers a limit is set on, from the gaps of every compared token:
    the widest and the share whose gap passes 0.1; with
    ``decisive_margin``, also the count of decisive tokens and the widest
    gap among them (0 where there are none: the count's own limit catches
    that)."""
    g = np.asarray(gaps, np.float64)
    out = {"widest_gap": float(g.max()),
           "share_gap_over_0.1": float((g > 0.1).mean())}
    if decisive_margin is not None:
        keep = np.asarray(margins) >= decisive_margin
        out["decisive_tokens"] = float(keep.sum())
        out["widest_gap_decisive"] = float(g[keep].max()) if keep.any() else 0.0
    return out


def compare(readings: Dict[str, float], limits: Dict[str, dict]) -> Dict[str, dict]:
    """Each number with its limit and whether it holds.  A limit is
    ``{"max": x}`` (the number may not exceed x) or ``{"min": x}``."""
    out = {}
    for name, lim in limits.items():
        v = readings.get(name)
        if "max" in lim:
            ok = v is not None and v <= lim["max"]
            bound = lim["max"]
        else:
            ok = v is not None and v >= lim["min"]
            bound = lim["min"]
        out[name] = {"value": v, "limit": bound,
                     "kind": "max" if "max" in lim else "min", "ok": bool(ok)}
    return out
