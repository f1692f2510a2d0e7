"""moe_gemm's share of its roofline, %: the least time the chip needs for
the expert GEMMs of the traced prefills and decode steps over the kernel's
device time in the trace.  The arch module counts each call's work: for
every expert on the chip (archs/gqa_stack.py), T*k routed rows and the
weights of the experts that received a token, no capacity padding.  Decode
steps hand back their routed expert ids, so their count of experts hit is
exact; the program's prefill returns none, so a prefill counts the experts
a uniform router would hit on average, E * (1 - (1 - k/E)^T) per layer."""
from benchmarks.chip import work

KERNEL = "moe_gemm"


def read(run):
    t = run.trace
    if t is None or not work.held_experts(run.config):
        return None
    spent = t.kernel_s(KERNEL)
    a, b = run.trace_host
    least = 0.0
    for s in run.rec.prefills + run.rec.decodes:
        if not (a <= s.t0 and s.t1 <= b):
            continue
        c = work.kernel_work(run.config, KERNEL, s)
        if c is not None:
            least += work.least_seconds(*c, run.peaks)
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
