"""Host time of the expert level per Cluster.step in the window, ms: the
program's ``expert.observe``, ``expert.tick`` and ``backend.relocate``
records (spans.expert_host_ms); none without an expert level."""
from benchmarks.chip import spans


def read(run):
    return spans.expert_host_ms(run.spans, run.open * 1e9, run.close * 1e9)
