"""flash_decode_paged's share of its roofline, %: the least time the chip
needs for the attention of the traced decode steps (valid K/V only, at the
peak FLOP/s or HBM bytes/s, whichever bounds) over the kernel's device time
in the trace."""
from benchmarks.chip import work

KERNEL = "flash_decode"


def read(run):
    t = run.trace
    if t is None:
        return None
    spent = t.kernel_s(KERNEL)
    a, b = run.trace_host
    calls = [s for s in run.rec.decodes if a <= s.t0 and s.t1 <= b]
    if spent <= 0 or not calls:
        return None
    least = sum(work.least_seconds(*work.flash_decode(run.config, s.lengths),
                                   run.peaks) for s in calls)
    return 100.0 * least / spent
