"""Median time to first token, ms, over the requests due in the window
(host clock; a request with no token by the close counts at its wait)."""
from benchmarks.chip import stats


def read(run):
    v = stats.ttfts(run.tracks, run.open, run.close)
    return 1e3 * stats.percentile(v, 50) if v else None
