"""Client-side arithmetic over the requests of one window.

A request counts toward the latency metrics when it was due inside the
window [open, close).  Its TTFT runs from when it was due (not from when the
generator got round to submitting it) to when its first token reached the
host; a request with no token by the close counts at its wait so far.  Its
TPOT is the mean gap between its output tokens delivered in the window,
after the first of them, for requests with at least two tokens there.  The
output rate counts every token delivered in the window, whichever request
it belongs to, over the window's length.
"""
from __future__ import annotations

import math
from typing import List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile by linear interpolation between closest ranks
    (numpy's default).  NaN for an empty sample."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def in_window(tracks, open_: float, close: float) -> list:
    return [t for t in tracks if open_ <= t.due < close]


def ttfts(tracks, open_: float, close: float) -> List[float]:
    """Seconds from due to first token, censored at ``close``."""
    out = []
    for t in in_window(tracks, open_, close):
        first = t.first
        out.append((first if first is not None and first < close else close)
                   - t.due)
    return out


def _times(track, open_: float, close: float) -> List[float]:
    """One stamp per token delivered in [open_, close)."""
    out = []
    for t, n in track.deliveries:
        if open_ <= t < close:
            out.extend([t] * n)
    return out


def tpots(tracks, open_: float, close: float) -> List[float]:
    """Each request's mean gap between tokens delivered in the window."""
    out = []
    for t in tracks:
        ts = _times(t, open_, close)
        if len(ts) >= 2:
            out.append((ts[-1] - ts[0]) / (len(ts) - 1))
    return out


def tokens(tracks, open_: float, close: float) -> int:
    return sum(len(_times(t, open_, close)) for t in tracks)


def lateness(tracks) -> List[float]:
    """Seconds by which the generator submitted each request after it was due."""
    return [t.submitted - t.due for t in tracks]
