"""flash_decode_paged's share of its roofline, %: the least time the chip
needs for the attention of the traced decode steps (valid K/V only, at the
peak FLOP/s or HBM bytes/s, whichever bounds; the arch module counts them)
over the kernel's device time in the trace."""
from benchmarks.chip import work

KERNEL = "flash_decode"


def read(run):
    t = run.trace
    if t is None:
        return None
    spent = t.kernel_s(KERNEL)
    a, b = run.trace_host
    counts = [work.kernel_work(run.config, KERNEL, s) for s in run.rec.decodes
              if a <= s.t0 and s.t1 <= b]
    counts = [c for c in counts if c is not None]
    if spent <= 0 or not counts:
        return None
    least = sum(work.least_seconds(*c, run.peaks) for c in counts)
    return 100.0 * least / spent
