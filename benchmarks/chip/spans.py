"""The program's own spans and counters (``repro.core.trace``), reduced to
per-layer numbers over a window of the host clock.

A record has ``name`` (``repro:<span>``), ``start`` and ``end`` in
``perf_counter_ns``, ``parent`` (index of the enclosing record in the same
drained list, -1 for none), ``req`` and integer ``attrs``.  A record belongs
to the window ``[a, b)`` (nanoseconds) when it starts there; a queue wait
when it ends there.  Each reader returns None when the window holds nothing
for it, as in a program without the recorder.

    queue_wait_ms      mean ``request.queued`` (SchedulerCore's queue)
    sched_host_ms      mean time per ``cluster.step`` outside every
                       ``backend.*`` and ``expert.*`` record: Cluster and
                       SchedulerCore host code
    expert_host_ms     mean time per ``cluster.step`` in ``expert.observe``,
                       ``expert.tick`` and ``backend.relocate``
    decode_host_ms     mean ``backend.decode`` less its ``backend.decode.wait``
                       (and any relocation inside it): host preparation,
                       launch and commit of a decode step
    prefill_pad_share  sum(bucket - plen) / sum(bucket) over the prefills, %
    batch_occupancy    mean ``rows`` / ``max_slots`` of ``backend.decode``, %:
                       the harness's metric of that name, from the program's
                       counters
    queue_len          mean ``queue`` of ``sched.schedule``: requests waiting
                       when SchedulerCore scans its queue
    admit_share        sum(``admitted``) / sum(``queue``) over the scans, %:
                       below 100 the prefill budget or the slots held
                       waiting requests back

``reduce`` is ``devtrace.reduce`` with the program's annotations added to
the host spans, so an idle gap is labelled by the innermost span of either
kind; the window, busy time, kernel time and top operations are the same.
``stall_spans`` names the program spans that took a long step's time,
``loop_gaps`` the loop's longest pauses between steps, and ``ttft_parts``
splits each request's time to first token at the records' boundaries.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.chip import devtrace

PREFIX = "repro:"                     # repro.core.trace.PREFIX
STEP = PREFIX + "cluster.step"
QUEUED = PREFIX + "request.queued"
PREFILL = PREFIX + "backend.prefill"
DECODE = PREFIX + "backend.decode"
WAIT = PREFIX + "backend.decode.wait"
SCHEDULE = PREFIX + "sched.schedule"
RELOCATE = PREFIX + "backend.relocate"
EXPERT = (PREFIX + "expert.observe", PREFIX + "expert.tick", RELOCATE)


def _starting(records, name: str, a: float, b: float) -> list:
    return [r for r in records if r.name == name and a <= r.start < b]


def _mean_ms(values_ns: Sequence[float]) -> Optional[float]:
    return 1e-6 * sum(values_ns) / len(values_ns) if values_ns else None


def _per_step(records, a: float, b: float, keep) -> Optional[float]:
    """Mean over the window's ``cluster.step`` records of the time inside
    each covered by the records ``keep`` selects, ms."""
    steps = _starting(records, STEP, a, b)
    if not steps:
        return None
    inner = devtrace._union([r for r in records if keep(r.name)])
    ends = [e for _, e in inner]
    covered = []
    for st in steps:
        tot = 0
        for s, e in inner[bisect.bisect_right(ends, st.start):]:
            if s >= st.end:
                break
            tot += min(e, st.end) - max(s, st.start)
        covered.append(tot)
    return _mean_ms(covered)


def queue_wait_ms(records, a: float, b: float) -> Optional[float]:
    return _mean_ms([r.end - r.start for r in records
                     if r.name == QUEUED and a <= r.end < b])


def sched_host_ms(records, a: float, b: float) -> Optional[float]:
    busy = _per_step(records, a, b, lambda n: n.startswith(
        (PREFIX + "backend.", PREFIX + "expert.")))
    if busy is None:
        return None
    steps = _starting(records, STEP, a, b)
    return _mean_ms([s.end - s.start for s in steps]) - busy


def expert_host_ms(records, a: float, b: float) -> Optional[float]:
    if not any(r.name in EXPERT and a <= r.start < b for r in records):
        return None
    return _per_step(records, a, b, lambda n: n in EXPERT)


def decode_host_ms(records, a: float, b: float) -> Optional[float]:
    inner: Dict[int, int] = {}
    for c in records:
        if c.name in (WAIT, RELOCATE):
            inner[c.parent] = inner.get(c.parent, 0) + (c.end - c.start)
    return _mean_ms([r.end - r.start - inner.get(i, 0)
                     for i, r in enumerate(records)
                     if r.name == DECODE and a <= r.start < b])


def prefill_pad_share(records, a: float, b: float) -> Optional[float]:
    pre = _starting(records, PREFILL, a, b)
    total = sum(r.attrs["bucket"] for r in pre)
    if not total:
        return None
    return 100.0 * sum(r.attrs["bucket"] - r.attrs["plen"] for r in pre) / total


def batch_occupancy(records, a: float, b: float) -> Optional[float]:
    dec = _starting(records, DECODE, a, b)
    if not dec:
        return None
    return 100.0 * sum(r.attrs["rows"] / r.attrs["max_slots"]
                       for r in dec) / len(dec)


def queue_len(records, a: float, b: float) -> Optional[float]:
    scans = _starting(records, SCHEDULE, a, b)
    return sum(r.attrs["queue"] for r in scans) / len(scans) if scans else None


def admit_share(records, a: float, b: float) -> Optional[float]:
    scans = _starting(records, SCHEDULE, a, b)
    waiting = sum(r.attrs["queue"] for r in scans)
    if not waiting:
        return None
    return 100.0 * sum(r.attrs["admitted"] for r in scans) / waiting


READERS = {"queue_wait_ms": queue_wait_ms, "sched_host_ms": sched_host_ms,
           "expert_host_ms": expert_host_ms, "decode_host_ms": decode_host_ms,
           "prefill_pad_share": prefill_pad_share,
           "batch_occupancy": batch_occupancy, "queue_len": queue_len,
           "admit_share": admit_share}


def stall_spans(records, a: float, b: float, n: int = 3) -> List[list]:
    """The ``n`` span names with the most self time (a record's time less
    its children's) in [a, b), seconds; queue waits left out."""
    child: Dict[int, int] = {}
    for r in records:
        if r.parent >= 0:
            child[r.parent] = child.get(r.parent, 0) + (r.end - r.start)
    tot: Dict[str, float] = {}
    for i, r in enumerate(records):
        if r.name != QUEUED and r.start < b and r.end > a:
            self_ns = min(r.end, b) - max(r.start, a) - child.get(i, 0)
            tot[r.name] = tot.get(r.name, 0.0) + max(self_ns, 0) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def loop_gaps(records, a: float, b: float, at: float, n: int = 3
              ) -> List[list]:
    """The ``n`` longest pauses of the serving loop in [a, b): from one
    ``cluster.step`` record's end to the next one's start, as ``[start,
    length]`` in seconds, ``start`` counted from ``at`` (perf_counter s).
    The loop submits, stamps tokens and runs the harness's hooks there."""
    steps = sorted((r.start, r.end) for r in records
                   if r.name == STEP and r.end > a and r.start < b)
    gaps = [(e0, s1 - e0) for (_, e0), (s1, _) in zip(steps, steps[1:])]
    gaps.sort(key=lambda g: -g[1])
    return [[g0 * 1e-9 - at, d * 1e-9] for g0, d in gaps[:n]]


def ttft_parts(records, firsts: Dict[int, Tuple[float, float]]
               ) -> Dict[str, float]:
    """Mean split, ms, of the time to first token of the requests in
    ``firsts`` (req_id -> (due, first token stamped), perf_counter seconds)
    at the records' boundaries: due to queue entry (the step in flight when
    it fell due, since requests are submitted between steps, and the
    router), the queue wait, admission to prefill start (the scheduler and
    the prefills before it in the step), the prefill, the decode steps after
    it in the same ``cluster.step``, the rest of that step (the prefills
    after it, the expert level, relocations) and step end to the stamp.  The
    parts add up to the mean time to first token.  Requests with a record
    missing are left out; ``n`` counts the rest."""
    ns = 1e9
    wait = {}
    prefill = {}
    for r in records:
        if r.name == QUEUED:
            wait.setdefault(r.req, r)
        elif r.name == PREFILL:
            prefill.setdefault(r.req, r)
    steps = sorted((r.start, r.end) for r in records if r.name == STEP)
    decodes = [r for r in records if r.name == DECODE]
    keys = ("to_queue", "queued", "to_prefill", "prefill", "decode_after",
            "rest_of_step", "to_stamp")
    rows = []
    for req, (due, first) in firsts.items():
        w, p = wait.get(req), prefill.get(req)
        step = next((s for s in steps if s[0] <= p.start and p.end <= s[1]),
                    None) if p is not None else None
        if w is None or step is None:
            continue
        dec = sum(d.end - d.start for d in decodes
                  if p.end <= d.start and d.end <= step[1])
        rows.append((w.start - due * ns, w.end - w.start, p.start - w.end,
                     p.end - p.start, dec, step[1] - p.end - dec,
                     first * ns - step[1]))
    out = {k: 1e-6 * sum(x[i] for x in rows) / len(rows)
           for i, k in enumerate(keys)} if rows else {}
    out["n"] = len(rows)
    return out


def reduce(planes) -> Optional[devtrace.Trace]:
    """``devtrace.reduce(planes)`` with the program's host annotations
    (``repro:`` events) that overlap its window added to its spans."""
    planes = list(planes)
    t = devtrace.reduce(planes)
    if t is None:
        return None
    lo, hi = t.window
    prog = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for pl in planes if pl.name.startswith("/host")
            for ln in pl.lines for ev in ln.events
            if ev.name.startswith(PREFIX)
            and ev.start_ns + ev.duration_ns > lo and ev.start_ns < hi]
    return devtrace.Trace(t.window, t.ops, t.spans + prog)


def load(trace_dir) -> Optional[devtrace.Trace]:
    """``devtrace.load`` through ``reduce``."""
    path = devtrace.find(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(str(path)).planes)
