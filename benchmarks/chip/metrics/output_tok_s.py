"""Output tokens delivered to the host in the window, over its length."""
from benchmarks.chip import stats


def read(run):
    n = stats.tokens(run.tracks, run.open, run.close)
    return n / run.seconds if n else None
