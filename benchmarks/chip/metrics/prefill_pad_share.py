"""Prompt padding of the window's prefills, %: sum(bucket - plen) /
sum(bucket), from the counters of the program's ``backend.prefill``
records (spans.prefill_pad_share)."""
from benchmarks.chip import spans


def read(run):
    return spans.prefill_pad_share(run.spans, run.open * 1e9, run.close * 1e9)
