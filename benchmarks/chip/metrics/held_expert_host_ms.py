"""Host time of the expert level per Cluster.step in the window, ms, where
the chip holds a share of each layer's routed experts (observing, placing
and relocating only those): the program's ``expert.observe``,
``expert.tick`` and ``backend.relocate`` records, as ``expert_host_ms``
reads them (spans.expert_host_ms)."""
from benchmarks.chip import spans


def read(run):
    return spans.expert_host_ms(run.spans, run.open * 1e9, run.close * 1e9)
