"""The one traffic generator: a traffic file's parameters, a cell's rate and a
seed in; a list of timed requests out.

Every seed gets the same schedule.  Prompt lengths, output lengths and
inter-arrival gaps are quantiles of the stated distributions, put in order
by the file's own ``master_seed``: the traffic file, the rate and the
duration fix when each request is due and how long it is.  The run's seed
draws the prompt tokens (and, in the harness, the weights).  So runs on
different seeds do the same amount of work in the same arrangement, a tail
is not the luck of one seed's bursts, and every seed compiles the same
shapes.

Traffic file keys:

    arrivals   {"process": "poisson"}                 exponential gaps
    prompt     a length distribution (below)
    output     a length distribution
    levels     lengths take this many quantile levels of their distribution
               (the midpoints of equal slices of probability), dealt to the
               requests in turn, so every rate and window draws its lengths
               from the same set (the program compiles per prompt length)
    master_seed  seed of the orderings that fix the schedule

A length distribution is truncated to [min, max] (its quantiles are those
of the distribution conditioned on that range):

    {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
    {"dist": "exponential", "mean_above_min": m, "min": a, "max": b}
                                       a + an exponential of mean m

The files name the public source of their parameters.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List

import numpy as np


@dataclasses.dataclass
class Arrival:
    idx: int
    due: float               # seconds after the traffic starts
    prompt: np.ndarray       # int32 token ids
    max_new: int


def _quantile_points(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _levels(dist: dict, n: int) -> np.ndarray:
    """The n quantile levels of ``dist`` truncated to [min, max]."""
    lo, hi = float(dist["min"]), float(dist["max"])
    kind = dist["dist"]
    if kind == "lognormal":
        nd = statistics.NormalDist(math.log(dist["median"]), dist["sigma"])
        cdf, inv = (lambda x: nd.cdf(math.log(x))), (lambda p: math.exp(nd.inv_cdf(p)))
    elif kind == "exponential":
        m = float(dist["mean_above_min"])
        cdf = lambda x: 1.0 - math.exp(-(x - lo) / m)
        inv = lambda p: lo - m * math.log1p(-p)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    a, b = cdf(lo), cdf(hi)
    x = np.array([inv(a + float(u) * (b - a)) for u in _quantile_points(n)])
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def _lengths(dist: dict, n: int, levels: int,
             master: np.random.Generator) -> np.ndarray:
    """n lengths: the levels, each dealt once per round in an order drawn
    from ``master`` (the last round short)."""
    lv = _levels(dist, levels)
    rounds = -(-n // levels)
    return np.concatenate([master.permutation(lv) for _ in range(rounds)])[:n]


def _gaps(arr: dict, n: int, rate: float) -> np.ndarray:
    kind = arr["process"]
    if kind == "poisson":
        return -np.log1p(-_quantile_points(n)) / rate
    raise ValueError(f"unknown arrival process {kind!r}")


def count(rate: float, duration: float) -> int:
    return max(1, int(round(rate * duration)))


def prompt_lengths(spec: dict, rate: float, duration: float) -> List[int]:
    """The multiset of prompt lengths every seed gets (sorted)."""
    return sorted(len(a.prompt) for a in generate(spec, rate, duration, 0, 2))


def generate(spec: dict, rate: float, duration: float, seed: int,
             vocab: int) -> List[Arrival]:
    """Requests due over ``duration`` seconds at a mean ``rate`` per second,
    sorted by due time."""
    n = count(rate, duration)
    master = np.random.default_rng(spec.get("master_seed", 0))
    levels = int(spec["levels"])
    plens = _lengths(spec["prompt"], n, levels, master)
    outs = _lengths(spec["output"], n, levels, master)
    gaps = master.permutation(_gaps(spec["arrivals"], n, rate))
    due = np.cumsum(gaps) - gaps[0]          # the first request is due at 0
    rng = np.random.default_rng(seed % (1 << 64))
    return [Arrival(i, float(due[i]),
                    rng.integers(0, vocab, int(plens[i])).astype(np.int32),
                    int(outs[i]))
            for i in range(n)]
