"""In-program spans and counters of the served path, on the host's clock.

One module-level recorder, off by default.  ``enable()`` turns it on, ``disable()`` off, and ``drain()`` hands back what it recorded and
forgets it.  While it is off a span site costs one flag check and gets the
shared ``NULL`` context: no record, no clock read, no JAX call.

A ``Record`` is one span: its name (every name starts with ``PREFIX``),
start and end in ``time.perf_counter_ns``, the index of the record it
nests in (-1 for none), the request it belongs to (-1 for none) and a few
integer attributes (``attrs``).  A span's record is appended when it opens,
so a parent always precedes its children.

While the recorder is on, every span also opens a
``jax.profiler.TraceAnnotation`` of the same name: a profiler trace then
shows it on its host plane, on the device trace's clock (with no profiler
running the annotation records nothing).  While the recorder is on, each XLA backend compile
(``jax.monitoring``'s ``/jax/core/compile/backend_compile_duration``)
becomes a ``compile`` record under the span open when it ended.

The spans and where they open:

  ==========================  ==========================================
  ``cluster.step``            ``Cluster.step``
  ``sched.step``              ``SchedulerCore.step``
  ``sched.schedule``          ``SchedulerCore.schedule``; ``queue``, the
                              queue's length, and ``admitted``
  ``request.queued``          a request's wait in an engine's queue:
                              ``SchedulerCore.submit`` or a victim's
                              re-queue to its admission (``wait_open`` /
                              ``wait_close``); not annotated
  ``backend.prefill``         ``JaxBackend.start``; ``plen``, ``bucket``;
                              ``held_rows`` (the ``moe.held_rows``
                              counter) where the model holds a share of
                              its routed experts: the prompt's routed
                              (token, expert) pairs on held experts
  ``backend.decode``          ``JaxBackend.decode``; ``rows``,
                              ``max_slots``; ``held_rows`` as above (of
                              the active rows); ``latent_tokens`` on
                              paged MLA latents: the resident tokens the
                              step's attention read, new ones included;
                              ``kv_blocks`` on paged GQA pages through
                              the kernel: the compute blocks of one
                              layer's ``flash_decode_paged`` call, summed
                              over the active rows
  ``backend.decode.wait``     the host's read of the decoded tokens
  ``backend.relocate``        ``JaxBackend.apply_placement``
  ``expert.observe``          ``ExpertRebalancer.observe``
  ``expert.tick``             ``ExpertRebalancer.tick`` (with rebalance)
  ==========================  ==========================================

``repro.launch.serve`` prints the mean of some of them; the benchmark's
readers (``benchmarks/chip/spans.py``) reduce the records of a time window
to per-layer metrics.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

PREFIX = "repro:"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Record:
    """One span or queue wait, timed in ``time.perf_counter_ns``."""
    name: str
    start: int                    # perf_counter_ns
    end: int
    parent: int = -1              # index of the enclosing record
    req: int = -1
    attrs: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def ms(self) -> float:
        return (self.end - self.start) * 1e-6


class _Null:
    """The context every span site gets while the recorder is off."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, key: str, value: int) -> None:
        pass


NULL = _Null()

on = False
_annotation = None                # jax.profiler.TraceAnnotation while on
_records: List[Record] = []
_stack: List[int] = []            # indices of the open spans, innermost last
_waits: Dict[int, int] = {}       # req_id -> start of its open queue wait


class _Span:
    __slots__ = ("rec", "index", "ann")

    def __init__(self, name: str, req: int):
        self.index = len(_records)
        self.rec = Record(PREFIX + name, time.perf_counter_ns(), 0,
                          _stack[-1] if _stack else -1, req)
        self.ann = _annotation(self.rec.name)

    def __enter__(self):
        _records.append(self.rec)
        _stack.append(self.index)
        self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        self.ann.__exit__(*exc)
        self.rec.end = time.perf_counter_ns()
        if _stack:
            _stack.pop()
        return False

    def note(self, key: str, value: int) -> None:
        self.rec.attrs[key] = int(value)


def span(name: str, req: int = -1):
    """A context manager timing ``name`` (``PREFIX`` is added); ``note``
    sets an integer attribute on it."""
    if not on:
        return NULL
    return _Span(name, req)


def wait_open(req_id: int) -> None:
    """A request entered an engine's queue.  A wait already open (a hedged
    request moving between queues) goes on."""
    if on and req_id not in _waits:
        _waits[req_id] = time.perf_counter_ns()


def wait_close(req_id: int) -> None:
    """A request left the queue for admission: its wait becomes a record."""
    if on:
        t0 = _waits.pop(req_id, None)
        if t0 is not None:
            _records.append(Record(PREFIX + "request.queued", t0,
                                   time.perf_counter_ns(), -1, req_id))


def wait_drop(req_id: int) -> None:
    """A request left the queue without admission (an engine drained)."""
    if on:
        _waits.pop(req_id, None)


def _on_compile(event: str, duration: float, **_) -> None:
    if on and event == COMPILE_EVENT:
        end = time.perf_counter_ns()
        _records.append(Record(PREFIX + "compile", end - int(duration * 1e9),
                               end, _stack[-1] if _stack else -1))


def enable() -> None:
    """Start recording, spans annotated to the profiler."""
    global on, _annotation
    import jax
    if not on:
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
    on = True
    _annotation = jax.profiler.TraceAnnotation


def disable() -> None:
    """Stop recording; what was recorded stays until ``drain``."""
    global on, _annotation
    if on:
        import jax
        jax.monitoring.unregister_event_duration_listener(_on_compile)
    on, _annotation = False, None
    _stack.clear()
    _waits.clear()


def drain() -> List[Record]:
    """The records so far, in the order they opened, which the recorder
    then forgets.  Call it between spans: parent indices count from the
    last drain."""
    out = list(_records)
    _records.clear()
    return out

