"""moe_gemm's share of its roofline, %: the least time the chip needs for
the expert GEMMs of the traced prefills and decode steps (T*k routed rows,
the weights of the experts that received a token, no capacity padding) over
the kernel's device time in the trace.  Decode steps hand back their routed
expert ids, so their count of experts hit is exact; the program's prefill
returns none, so a prefill counts the experts a uniform router would hit on
average, E * (1 - (1 - k/E)^T) per layer."""
from benchmarks.chip import work

KERNEL = "moe_gemm"


def read(run):
    t = run.trace
    if t is None or not run.config.get("num_experts"):
        return None
    spent = t.kernel_s(KERNEL)
    a, b = run.trace_host
    least = 0.0
    for s in run.rec.prefills + run.rec.decodes:
        if not (a <= s.t0 and s.t1 <= b):
            continue
        hit = (work.distinct_experts(s.experts) if s.experts is not None
               else work.expected_experts(run.config, s.rows))
        least += work.least_seconds(*work.moe_gemm(run.config, s.rows, hit),
                                    run.peaks)
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
