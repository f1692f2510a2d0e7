"""moe_gemm's share of its roofline where the chip holds a share of each
layer's routed experts, %: the least time the chip needs for the expert
GEMMs of the traced prefills and decode steps over the kernel's device time
in the trace.  The rows are the (token, expert) pairs routed to held
experts, from the program's ``moe.held_rows`` counter (the ``held_rows``
attr of the ``backend.prefill`` and ``backend.decode`` record inside each
call), handed to the arch module's count (archs/mla_moe.py) with the call;
a call without one counts its routed ids, or the expectation."""
import dataclasses

from benchmarks.chip import spans, work

KERNEL = "moe_gemm"


@dataclasses.dataclass
class _Call:
    rows: int
    lengths: object
    experts: object
    held_rows: object


def _held_rows(records, t0: float, t1: float, name: str):
    """The ``held_rows`` of the program's ``name`` record inside the call
    that ran from t0 to t1 (seconds), or None."""
    for r in records:
        if r.name == name and t0 * 1e9 <= r.start and r.end <= t1 * 1e9:
            return r.attrs.get("held_rows")
    return None


def read(run):
    t = run.trace
    if t is None or not work.held_experts(run.config):
        return None
    spent = t.kernel_s(KERNEL)
    a, b = run.trace_host
    least = 0.0
    for calls, name in ((run.rec.prefills, spans.PREFILL),
                        (run.rec.decodes, spans.DECODE)):
        for s in calls:
            if not (a <= s.t0 and s.t1 <= b):
                continue
            call = _Call(s.rows, s.lengths, s.experts,
                         _held_rows(run.spans, s.t0, s.t1, name))
            c = work.kernel_work(run.config, KERNEL, call)
            if c is not None:
                least += work.least_seconds(*c, run.peaks)
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
