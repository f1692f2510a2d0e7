"""The in-program recorder (repro.core.trace) on a tiny CPU cluster of the
served path (paged KV, MoE, fused dispatch, Pallas kernels interpreted):
the span tree nests, queue waits equal admission minus submission, the
prefill pad counter is the bucket's padding, and with the recorder off
nothing is recorded and the served tokens are the same."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import trace
from repro.core.types import GimbalConfig, Request
from repro.launch import serve
from repro.serving.backend import _bucket

P = trace.PREFIX
VOCAB = 64


@pytest.fixture(autouse=True)
def recorder_off():
    trace.disable()
    trace.drain()
    yield
    trace.disable()
    trace.drain()


@pytest.fixture(scope="module")
def cluster():
    """Two engines sharing one expert level that rebalances every 3 steps,
    so relocations happen inside the traced steps."""
    cfg = get_smoke_config("qwen3-30b-a3b")
    return serve.build_cluster(cfg, "gimbal", 2,
                               GimbalConfig(tau=3, enable_preemption=True),
                               max_slots=2, max_seq=64, prefill_budget=64)


def _requests(lens, out=4, base=0, cls="batch", seed=0):
    rng = np.random.default_rng(seed)
    return [Request(req_id=base + i, prompt_len=n, max_new_tokens=out,
                    arrival_time=0.0, priority_class=cls,
                    prompt_tokens=rng.integers(0, VOCAB, n).astype(np.int32))
            for i, n in enumerate(lens)]


def _serve(cluster, reqs):
    for r in reqs:
        cluster.submit(r, 0.0)
    cluster.run_until_drained(t0=0.0, dt=0.01)
    cluster.finished.clear()
    return {r.req_id: list(r.output_tokens) for r in reqs}


def _named(records, name):
    return [r for r in records if r.name == P + name]


def test_span_is_the_shared_null_while_off():
    assert not trace.on
    assert trace.span("cluster.step") is trace.NULL
    with trace.span("cluster.step") as sp:
        sp.note("rows", 3)
    trace.wait_open(1)
    trace.wait_close(1)
    assert trace.drain() == []


@pytest.mark.slow
def test_span_tree_nests_with_one_sched_step_per_engine(cluster):
    trace.enable()
    _serve(cluster, _requests([5, 20, 33, 9, 17], out=6))
    trace.disable()
    recs = trace.drain()
    at = {id(r): i for i, r in enumerate(recs)}
    for r in recs:
        assert r.name.startswith(P) and r.start <= r.end
        if r.parent >= 0:
            assert r.parent < at[id(r)]
            up = recs[r.parent]
            assert up.start <= r.start and r.end <= up.end, (up, r)
    steps = _named(recs, "cluster.step")
    assert steps and all(s.parent == -1 for s in steps)
    for s in steps:
        kids = [r for r in recs if r.parent == at[id(s)]]
        assert [k.name for k in kids].count(P + "sched.step") == 2
    parent_of = {name: {recs[r.parent].name for r in _named(recs, name)}
                 for name in ("sched.schedule", "backend.prefill",
                              "backend.decode", "backend.decode.wait",
                              "expert.tick")}
    assert parent_of == {"sched.schedule": {P + "sched.step"},
                         "backend.prefill": {P + "sched.step"},
                         "backend.decode": {P + "sched.step"},
                         "backend.decode.wait": {P + "backend.decode"},
                         "expert.tick": {P + "sched.step"}}
    assert {recs[r.parent].name for r in _named(recs, "expert.observe")} \
        == {P + "sched.step"}
    # the tick's relocation, or the other engine catching up before its
    # next forward pass
    assert {recs[r.parent].name for r in _named(recs, "backend.relocate")} \
        <= {P + "sched.step", P + "backend.prefill", P + "backend.decode"}
    assert _named(recs, "backend.relocate")
    for d in _named(recs, "backend.decode"):
        assert 1 <= d.attrs["rows"] <= d.attrs["max_slots"] == 2
    for s in _named(recs, "sched.schedule"):
        assert 0 <= s.attrs["admitted"] <= s.attrs["queue"]


class _Clock:
    """A stand-in for the recorder's clock that moves only when told."""

    def __init__(self):
        self.t = 0

    def perf_counter_ns(self):
        return self.t


@pytest.mark.slow
def test_queue_wait_is_admission_minus_submission(cluster, monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(trace, "time", clock)
    eng = cluster.engines[0]
    trace.enable()
    # two long batch requests take both slots ...
    batch = _requests([12, 14], out=12, base=100)
    clock.t = 1_000
    for r in batch:
        eng.submit(r, 0.0)
    clock.t = 5_000
    eng.step(0.0)                           # both admitted here
    # ... then an interactive arrival evicts one of them
    inter = _requests([8], out=3, base=200, cls="interactive", seed=1)[0]
    clock.t = 7_000
    eng.submit(inter, 0.01)
    clock.t = 11_000
    eng.step(0.01)                          # admits it, re-queues a victim
    victim = next(r for r in batch if r.preempted)
    t, now = 20_000, 0.02
    while not eng.core.idle:
        clock.t = t
        eng.step(now)
        t, now = t + 10_000, now + 0.01
    trace.disable()
    waits = {}
    for r in _named(trace.drain(), "request.queued"):
        waits.setdefault(r.req, []).append((r.start, r.end))
    assert waits[inter.req_id] == [(7_000, 11_000)]
    other = next(r for r in batch if r is not victim)
    assert waits[other.req_id] == [(1_000, 5_000)]
    # the victim waited twice: from submission, then from its eviction to
    # its re-admission, which came in one of the later steps
    first, second = waits[victim.req_id]
    assert first == (1_000, 5_000)
    assert second[0] == 11_000 and second[1] in range(20_000, t, 10_000)


@pytest.mark.slow
def test_pad_counter_is_bucket_minus_prompt_length(cluster):
    be = cluster.engines[0].backend
    asked = []
    own = be._prefill_for_bucket

    def spy(bl):
        asked.append(bl)
        return own(bl)

    be._prefill_for_bucket = spy
    try:
        trace.enable()
        reqs = _requests([5, 16, 17, 40, 100], out=2, base=300)
        for r in reqs:
            cluster.engines[0].submit(r, 0.0)
        cluster.run_until_drained(t0=0.0, dt=0.01)
        cluster.finished.clear()
        trace.disable()
    finally:
        be._prefill_for_bucket = own
    pre = {r.req: r.attrs for r in _named(trace.drain(), "backend.prefill")}
    for r in reqs:
        plen = min(r.prompt_len, be.max_seq - 1)
        assert pre[r.req_id]["plen"] == plen
        assert pre[r.req_id]["bucket"] - plen == _bucket(plen) - plen
    assert sorted(asked) == sorted(a["bucket"] for a in pre.values())
    assert [pre[r.req_id]["bucket"] - pre[r.req_id]["plen"]
            for r in reqs] == [11, 0, 15, 24, 1]


@pytest.mark.slow
def test_decode_counts_the_paged_kernels_compute_blocks():
    """``kv_blocks`` on each ``backend.decode`` record: the compute blocks
    of one layer's flash_decode_paged call, ceil((resident + 1) / (ppb *
    BS)) summed over the active rows; here 8 pages of 16 a block over a
    16-page table, and one row crosses a block boundary between steps."""
    from repro.serving.backend import JaxBackend
    cfg = get_smoke_config("granite-3-8b")
    be = JaxBackend(cfg, serve.init_params(cfg), max_slots=3, max_seq=256,
                    kv_layout="paged", kv_block_size=16, use_kernels=True)
    reqs = _requests([127, 9], out=3)
    trace.enable()
    active = [(be.start(r, 0.0)[0], r) for r in reqs]
    for _ in range(2):
        be.decode(active, 0.0)
    dec = _named(trace.drain(), "backend.decode")
    assert [r.attrs["kv_blocks"] for r in dec] == [1 + 1, 2 + 1]


def test_recorder_off_records_nothing_and_serves_the_same_tokens():
    cfg = get_smoke_config("qwen3-30b-a3b")
    outs = []
    for on in (False, True):
        # a fresh cluster each time: the expert level's state decides
        # relocations, and both runs start from the same one
        c = serve.build_cluster(cfg, "gimbal", 1, GimbalConfig(tau=3),
                                max_slots=2, max_seq=64, prefill_budget=64)
        if on:
            trace.enable()
        outs.append(_serve(c, _requests([7, 30, 12, 21], out=5, base=400)))
        trace.disable()
        recs = trace.drain()
        assert bool(recs) == on
        assert bool(_named(recs, "backend.relocate")) == on
    assert outs[0] == outs[1]


def test_compile_in_an_enabled_span_is_its_child():
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        trace.enable()
        with trace.span("cluster.step"):
            jax.jit(lambda x: jnp.tanh(x) * 3.25 + 0.5)(np.arange(11.0))
        jax.jit(lambda x: x - 7.5)(np.arange(5.0))       # outside any span
        trace.disable()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    recs = trace.drain()
    step = next(i for i, r in enumerate(recs) if r.name == P + "cluster.step")
    compiles = _named(recs, "compile")
    assert compiles and compiles[0].parent == step
    assert recs[step].start <= compiles[0].start <= compiles[0].end \
        <= recs[step].end
    assert compiles[-1].parent == -1


def test_waits_follow_hedges_and_drop_with_a_drain():
    trace.enable()
    trace.wait_open(1)
    trace.wait_open(1)              # moved to another queue: still one wait
    trace.wait_open(2)
    trace.wait_drop(2)              # its engine drained: no record
    trace.wait_close(1)
    trace.wait_close(2)
    trace.disable()
    assert [(r.name, r.req) for r in trace.drain()] == [(P + "request.queued", 1)]


def test_summary_means_by_name():
    recs = [trace.Record(P + "backend.decode", 0, 2_000_000),
            trace.Record(P + "backend.decode", 0, 4_000_000),
            trace.Record(P + "compile", 0, 1_000_000),
            trace.Record(P + "expert.tick", 0, 9_000_000)]
    assert serve.measured_means(recs) == {
        "backend.decode": (2, 3.0), "compile": (1, 1.0),
        "request.queued": (0, 0.0), "backend.prefill": (0, 0.0)}


def test_a_span_opens_a_profiler_annotation_of_its_name(monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("in", self.name))

        def __exit__(self, *exc):
            seen.append(("out", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    trace.enable()
    with trace.span("cluster.step"):
        with trace.span("sched.step"):
            pass
    trace.disable()
    assert seen == [("in", P + "cluster.step"), ("in", P + "sched.step"),
                    ("out", P + "sched.step"), ("out", P + "cluster.step")]
    assert [r.name for r in trace.drain()] == [P + "cluster.step",
                                               P + "sched.step"]


def test_serve_turns_the_recorder_off_when_the_loop_raises(monkeypatch):
    class Broken:
        engines = {}

        def submit(self, *args):
            raise RuntimeError("serving failed")

        step = submit

    monkeypatch.setattr(serve, "use_compile_cache", lambda: "")
    monkeypatch.setattr(serve, "build_cluster", lambda *a, **k: Broken())
    with pytest.raises(RuntimeError, match="serving failed"):
        serve.main(["--n", "2", "--engines", "1"])
    assert not trace.on


@pytest.mark.slow
def test_serve_prints_logical_and_measured_numbers(monkeypatch, tmp_path,
                                                   capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert serve.main(["--n", "4", "--engines", "1"]) == 0
    out = capsys.readouterr().out
    assert "logical clock" in out
    line = next(ln for ln in out.splitlines() if "measured (host clock)" in ln)
    for label in serve.MEASURED:
        assert f"{label} mean " in line
    assert "prefill mean 0.000ms" not in line and "n=4" in line
    assert not trace.on
