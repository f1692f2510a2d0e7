"""The system under test, driven at the wall clock.

``build`` makes the cluster through ``repro.launch.serve.build_cluster`` (the
deployment entry point) with the benchmark's own seeded weights, ``warm``
compiles what this cell's traffic will run, and ``drive`` offers the traffic
open loop: each request is submitted when it is due by ``time.perf_counter``,
``Cluster.step`` runs whenever there is work, and every token is stamped when
the step that made it returns to the host.  The program's own timestamps
(a logical clock) are not read.

``Recorder`` wraps each engine's ``JaxBackend.start`` (one prefill, ending in
a host read of the first token), ``JaxBackend.decode`` (one decode step) and
``JaxBackend.apply_placement`` (an expert relocation) with host-clock spans,
and keeps the counters they expose: prompt length, the active rows and their
resident lengths, and the routed expert ids.  It also times every
``Cluster.step`` and the collector's passes, and reads the device's memory
after a step that took longer than ``STALL_S`` (``stalls``).  With
``annotate`` the spans also go into the profiler's trace, where the trace
reduction uses them to label the device's idle gaps.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks.chip.traffic import Arrival

GIMBAL = {"tau": 25, "theta_load": 64}
STALL_S = 0.5                 # a Cluster.step this long is looked into


def build(cfg, params, serving: dict, device):
    """One engine on ``device`` behind the gimbal router, built by
    ``build_cluster`` with ``params`` as its weights.  ``build_cluster``
    draws weights of its own from a fixed seed; the benchmark's seeded tree
    is handed to it in place of that draw."""
    from repro.core.types import GimbalConfig
    from repro.launch import serve as launch

    def given(c, seed=0, device=None):
        assert c == cfg, "build_cluster asked for another model's weights"
        return params

    own = launch.init_params
    launch.init_params = given
    try:
        return launch.build_cluster(
            cfg, "gimbal", 1, GimbalConfig(**GIMBAL),
            max_slots=serving["max_slots"], max_seq=serving["max_seq"],
            prefill_budget=serving["prefill_budget"], devices=[device])
    finally:
        launch.init_params = own


@dataclasses.dataclass
class Span:
    t0: float
    t1: float
    rows: int                 # prompt length (prefill) or active rows (decode)
    lengths: Optional[np.ndarray] = None   # decode: resident tokens per row
    experts: Optional[np.ndarray] = None   # routed expert ids (L, ..., k)
    relocations: int = 0      # engine relocations since attach(), before it


class Recorder:
    def __init__(self, annotate: bool = False, device=None):
        self.annotate = annotate
        self.device = device
        self.prefills: List[Span] = []
        self.decodes: List[Span] = []
        self.relocs: List[Span] = []
        self.steps: List[tuple] = []      # (t0, t1) of each Cluster.step
        self.gcs: List[tuple] = []        # (t0, t1) of each collector pass
        self.stall_memory: Dict[float, dict] = {}   # step t0 -> memory_stats
        # req_id -> relocations since attach() when its prefill ran
        self.started_after_reloc: Dict[int, int] = {}
        self.max_slots = 0
        self._gc_t0 = 0.0

    def _on_gc(self, phase, _info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.gcs.append((self._gc_t0, time.perf_counter()))

    def step_done(self, t0: float, t1: float) -> None:
        self.steps.append((t0, t1))
        if t1 - t0 > STALL_S and self.device is not None:
            self.stall_memory[t0] = dict(self.device.memory_stats() or {})

    def detach(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def stalls(self, open_: float, close: float) -> List[dict]:
        """Each step of the window longer than ``STALL_S``: its length and
        the time inside it spent in prefills, decode steps, relocations and
        collector passes, with the device memory read after it."""
        def inside(spans, a, b):
            return sum(min(e, b) - max(s, a) for s, e in spans if s < b and e > a)
        out = []
        for a, b in self.steps:
            if b - a <= STALL_S or not open_ <= a < close:
                continue
            mem = self.stall_memory.get(a, {})
            out.append({
                "at_s": a - open_, "step_s": b - a,
                "prefill_s": inside([(p.t0, p.t1) for p in self.prefills], a, b),
                "decode_s": inside([(d.t0, d.t1) for d in self.decodes], a, b),
                "relocation_s": inside([(r.t0, r.t1) for r in self.relocs], a, b),
                "gc_s": inside(self.gcs, a, b),
                "bytes_in_use": mem.get("bytes_in_use"),
                "largest_free_block_bytes": mem.get("largest_free_block_bytes")})
        return out

    def span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def attach(self, cluster) -> None:
        for eng in cluster.engines.values():
            self._wrap(eng.backend)
        gc.callbacks.append(self._on_gc)

    def _wrap(self, be) -> None:
        start, decode, place = be.start, be.decode, be.apply_placement
        self.max_slots = max(self.max_slots, be.max_slots)
        clock = time.perf_counter
        base = be.relocations

        def timed_start(r, now):
            reloc = be.relocations - base
            t0 = clock()
            with self.span("bench:prefill"):
                slot, stats = start(r, now)
            t1 = clock()
            self.prefills.append(Span(t0, t1, min(r.prompt_len, be.max_seq - 1),
                                      experts=stats, relocations=reloc))
            self.started_after_reloc[r.req_id] = reloc
            return slot, stats

        def timed_decode(active, now):
            lengths = np.array([be.kv.slot_len[s] for s, _ in active], np.int64)
            reloc = be.relocations - base
            t0 = clock()
            with self.span("bench:decode"):
                eos, stats = decode(active, now)
            t1 = clock()
            self.decodes.append(Span(t0, t1, len(active), lengths=lengths,
                                     experts=stats, relocations=reloc))
            return eos, stats

        def timed_placement(new_map):
            t0 = clock()
            with self.span("bench:relocate"):
                place(new_map)
            self.relocs.append(Span(t0, clock(), 0))

        be.start, be.decode = timed_start, timed_decode
        be.apply_placement = timed_placement


@dataclasses.dataclass
class Track:
    """One request as the client sees it."""
    arrival: Arrival
    due: float                # absolute perf_counter time it was due
    submitted: float
    req: object               # the program's Request
    deliveries: List[tuple] = dataclasses.field(default_factory=list)  # (t, n)
    seen: int = 0             # tokens stamped so far
    finished: Optional[float] = None
    failed: bool = False

    @property
    def first(self) -> Optional[float]:
        return self.deliveries[0][0] if self.deliveries else None


def _request(a: Arrival, base: float = 0.0):
    from repro.core.types import Request
    return Request(req_id=a.idx, prompt_len=len(a.prompt),
                   max_new_tokens=a.max_new, arrival_time=base + a.due,
                   prompt_tokens=a.prompt)


def warm(cluster, arrivals: List[Arrival], vocab: int, seed: int) -> float:
    """Compile what the traffic will run, before the clock starts: every
    prefill bucket and the decode step (``JaxBackend.warmup``), then one
    two-token request per distinct prompt length through the cluster, which
    runs whatever the program compiles per length (its host-side slicing of
    logits and KV pages), then ``max_slots`` requests that decode in
    batches of every size (the expert level's statistics compile per
    batch size); for a mixture of experts, one relocation of the expert
    weights and its undoing.  The warm-up prompts are
    drawn apart from the traffic's, so they share no prefix with it.
    Returns the program clock's time after the warm-up (``drive`` goes on
    from there, so the program never sees time run backwards)."""
    lens = sorted({len(a.prompt) for a in arrivals})
    for eng in cluster.engines.values():
        be = eng.backend
        be.warmup(lens)
        if be.cfg.is_moe:
            # an expert relocation there and back (the weights end as built)
            ident = np.arange(be.cfg.num_experts)
            be.apply_placement(np.roll(ident, 1))
            be.apply_placement(ident)
    rng = np.random.default_rng([seed % (1 << 64), 1])
    base = 1 << 40                       # ids clear of the traffic's
    for i, n in enumerate(lens):
        toks = rng.integers(0, vocab, n).astype(np.int32)
        cluster.submit(_request(Arrival(base + i, 0.0, toks, 2)), 0.0)
    dt = 0.01
    cluster.run_until_drained(t0=0.0, dt=dt, max_steps=100_000)
    # a decode batch of every size: max_slots requests admitted together,
    # finishing one per step (the expert level compiles per batch size)
    slots = max(e.max_slots for e in cluster.engines.values())
    for i in range(slots):
        toks = rng.integers(0, vocab, lens[0]).astype(np.int32)
        cluster.submit(_request(Arrival(base + len(lens) + i, 0.0, toks,
                                        i + 2)), 0.0)
    cluster.run_until_drained(t0=0.0, dt=dt, max_steps=100_000)
    steps = max(e.steps for e in cluster.engines.values())
    cluster.finished.clear()
    return steps * dt + 1.0


def drive(cluster, arrivals: List[Arrival], t0: float, stop: float,
          rec: Recorder, base: float = 0.0, on_step=None) -> List[Track]:
    """Offer ``arrivals`` (due ``t0 + a.due``) until ``stop``.  Returns a
    Track for every request submitted.  The program's clock reads
    ``base + (perf_counter() - t0)``.  ``on_step(now)`` runs after every
    loop turn (the profiler's start and stop hang off it)."""
    clock = time.perf_counter
    pending = collections.deque(arrivals)
    tracks: List[Track] = []
    live: List[Track] = []
    while True:
        now = clock()
        if now >= stop:
            break
        with rec.span("bench:submit"):
            while pending and t0 + pending[0].due <= now:
                a = pending.popleft()
                r = _request(a, base)
                tr = Track(a, t0 + a.due, clock(), r)
                cluster.submit(r, base + clock() - t0)
                tracks.append(tr)
                if getattr(r, "shed_time", None) is not None:
                    tr.failed = True
                else:
                    live.append(tr)
        if cluster.pending() == 0:
            nxt = t0 + pending[0].due if pending else stop
            with rec.span("bench:idle"):
                time.sleep(max(0.0, min(nxt, stop) - clock()))
        else:
            t_step = clock()
            with rec.span("bench:step"):
                cluster.step(base + t_step - t0)
            t = clock()
            rec.step_done(t_step, t)
            with rec.span("bench:stamp"):
                still = []
                for tr in live:
                    out = tr.req.output_tokens
                    n = len(out) if out is not None else 0
                    if n > tr.seen:
                        tr.deliveries.append((t, n - tr.seen))
                        tr.seen = n
                    if tr.req.finish_time is not None:
                        tr.finished = t
                    else:
                        still.append(tr)
                live = still
        if on_step is not None:
            on_step(clock())
    return tracks
