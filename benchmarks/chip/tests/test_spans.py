"""The readers of the program's own records (spans.py), by hand on made-up
records and a synthetic trace, and the tool that runs a cell with the
recorder on (tools/spans.py), whole, at a size the CPU holds.

    PYTHONPATH=src python3 -m pytest -q benchmarks/chip/tests/test_spans.py
"""
from __future__ import annotations

import dataclasses
import types

import jax
import pytest

from benchmarks.chip import devtrace, spans
from benchmarks.chip.tests.test_correctness import _cell
from benchmarks.chip.tools import spans as tool

MS = 1_000_000          # ns


def _rec(name, s, e, parent=-1, req=-1, **attrs):
    return types.SimpleNamespace(name=spans.PREFIX + name, start=s * MS,
                                 end=e * MS, parent=parent, req=req,
                                 attrs=attrs)


def _records(expert=True):
    """Two Cluster.steps of one engine, times in ms.  The second step's
    decode holds a relocation (an engine catching up with a placement)."""
    recs = [
        _rec("cluster.step", 0, 100),                                 # 0
        _rec("sched.step", 5, 95, 0),                                 # 1
        _rec("sched.schedule", 6, 10, 1, queue=2, admitted=1),        # 2
        _rec("request.queued", -20, 8, req=7),                        # 3
        _rec("backend.prefill", 10, 40, 1, 7, plen=20, bucket=32),    # 4
        _rec("expert.observe", 40, 45, 1),                            # 5
        _rec("backend.decode", 45, 80, 1, rows=2, max_slots=4),       # 6
        _rec("backend.decode.wait", 60, 75, 6),                       # 7
        _rec("expert.observe", 80, 83, 1),                            # 8
        _rec("expert.tick", 83, 90, 1),                               # 9
        _rec("backend.relocate", 90, 94, 1),                          # 10
        _rec("cluster.step", 200, 260),                               # 11
        _rec("sched.step", 201, 259, 11),                             # 12
        _rec("request.queued", 150, 201.5, req=8),                    # 13
        _rec("backend.prefill", 202, 204, 12, 8, plen=5, bucket=16),  # 14
        _rec("backend.decode", 205, 255, 12, rows=3, max_slots=4),    # 15
        _rec("backend.relocate", 206, 216, 15),                       # 16
        _rec("backend.decode.wait", 220, 250, 15),                    # 17
        _rec("sched.schedule", 201, 202, 12, queue=1, admitted=1),    # 18
    ]
    if not expert:
        for r in recs:
            if r.name in spans.EXPERT:
                r.name = spans.PREFIX + "other"
    return recs


def _read(name, a, b, expert=True):
    return spans.READERS[name](_records(expert), a * MS, b * MS)


def test_queue_wait_is_the_mean_of_waits_closed_in_the_window():
    assert _read("queue_wait_ms", 0, 300) == pytest.approx((28 + 51.5) / 2)
    assert _read("queue_wait_ms", 100, 300) == pytest.approx(51.5)


def test_sched_host_is_step_time_outside_backend_and_expert_records():
    # step 1: 100 - union(10..94); step 2: 60 - (2 + 50)
    assert _read("sched_host_ms", 0, 300) == pytest.approx((16 + 8) / 2)
    assert _read("sched_host_ms", 100, 300) == pytest.approx(8)


def test_expert_host_counts_observe_tick_and_relocations_per_step():
    assert _read("expert_host_ms", 0, 300) == pytest.approx(
        ((5 + 3 + 7 + 4) + 10) / 2)


def test_expert_host_is_none_without_expert_records():
    assert _read("expert_host_ms", 0, 300, expert=False) is None


def test_decode_host_is_decode_less_its_wait_and_relocation():
    assert _read("decode_host_ms", 0, 300) == pytest.approx(
        ((35 - 15) + (50 - 30 - 10)) / 2)


def test_pad_share_is_padding_over_bucket_tokens():
    assert _read("prefill_pad_share", 0, 300) == pytest.approx(
        100 * (12 + 11) / (32 + 16))
    assert _read("prefill_pad_share", 100, 300) == pytest.approx(100 * 11 / 16)


def test_batch_occupancy_is_decode_rows_over_slots():
    assert _read("batch_occupancy", 0, 300) == pytest.approx(
        100 * (2 / 4 + 3 / 4) / 2)
    assert _read("batch_occupancy", 100, 300) == pytest.approx(75)


def test_queue_len_and_admit_share_read_the_scans():
    assert _read("queue_len", 0, 300) == pytest.approx((2 + 1) / 2)
    assert _read("admit_share", 0, 300) == pytest.approx(100 * 2 / 3)
    assert _read("admit_share", 0, 100) == pytest.approx(50)


@pytest.mark.parametrize("name", sorted(spans.READERS))
def test_an_empty_window_reads_none(name):
    assert _read(name, 1000, 2000) is None
    assert spans.READERS[name]([], 0, 1e12) is None


def test_stall_spans_rank_program_spans_by_self_time():
    top = spans.stall_spans(_records(), 0, 100 * MS)
    assert [k for k, _ in top] == [spans.PREFIX + n for n in
                                   ("backend.prefill", "backend.decode",
                                    "backend.decode.wait")]
    assert [v for _, v in top] == pytest.approx([0.030, 0.020, 0.015])


def test_ttft_parts_split_due_to_stamp_at_the_records():
    parts = spans.ttft_parts(_records(), {7: (-0.025, 0.101), 99: (0.0, 1.0)})
    assert parts == pytest.approx({
        "to_queue": 5, "queued": 28, "to_prefill": 2, "prefill": 30,
        "decode_after": 35, "rest_of_step": 25, "to_stamp": 1, "n": 1})
    assert sum(v for k, v in parts.items() if k != "n") == pytest.approx(126)


def test_loop_gaps_are_the_pauses_between_steps():
    assert spans.loop_gaps(_records(), 0, 300 * MS, 0.05) == [
        [pytest.approx(0.05), pytest.approx(0.1)]]
    assert spans.loop_gaps(_records(), 0, 150 * MS, 0.0) == []


# ----------------------------------------------------------- the trace's gaps
def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=float(start),
                                 duration_ns=float(dur))


def _plane(name, lines):
    return types.SimpleNamespace(
        name=name, lines=[types.SimpleNamespace(name=k, events=v)
                          for k, v in lines.items()])


def _planes(program=True):
    host = [_ev("bench:step", 100, 400), _ev("bench:decode", 150, 300),
            _ev("bench:idle", 520, 60), _ev("bench:step", 600, 300)]
    if program:
        host += [_ev("repro:cluster.step", 105, 390),
                 _ev("repro:backend.decode", 155, 290),
                 _ev("repro:backend.decode.wait", 380, 60),
                 _ev("repro:cluster.step", 605, 290),
                 _ev("repro:expert.tick", 780, 60),
                 _ev("repro:cluster.step", 950, 40)]     # after the window
    dev = {"XLA Ops": [
        _ev("%fusion.1 = f32[8] fusion(f32[8] %p)", 50, 100),
        _ev("%while.3 = (s32[]) while(%t), body=%b", 200, 160),
        _ev("%moe_gemm.7 = bf16[8,128] custom-call(%a, %b)", 200, 100),
        _ev("%fusion.2 = f32[8] fusion(%moe_gemm.9, %x)", 650, 50),
        _ev("%flash_decode_paged.4 = bf16[2] custom-call(%q)", 700, 25)]}
    return [_plane("/host:CPU", {"python3": host}),
            _plane("/device:TPU:0", dev)]


def test_program_spans_label_the_gaps_inside_cluster_step():
    t = spans.reduce(_planes())
    # 360..650 lies between the program's steps; 725..900 holds the tick
    # at its middle; 150..200 lies inside the decode
    assert t.idle_gaps(3) == [
        ["host:other", pytest.approx(290e-9)],
        ["repro:expert.tick", pytest.approx(175e-9)],
        ["repro:backend.decode", pytest.approx(50e-9)]]
    assert all(s[0] != "repro:cluster.step" or s[1] < 900 for s in t.spans)


def test_program_spans_leave_window_busy_and_kernels_unchanged():
    bare, both = devtrace.reduce(_planes(False)), spans.reduce(_planes())
    assert both.window == bare.window == devtrace.reduce(_planes()).window
    assert both.busy_s() == bare.busy_s()
    for k in ("moe_gemm", "flash_decode", "fusion"):
        assert both.kernel_s(k) == bare.kernel_s(k)
    assert both.top_ops() == bare.top_ops()
    assert spans.reduce([_plane("/host:CPU", {"x": []})]) is None


# ------------------------------------------------------------------ whole runs
@pytest.fixture(scope="module")
def moe_run():
    return tool.measure(_cell("moe"), 5, 3.0, False, jax.devices())


def test_a_run_with_the_recorder_reads_every_number(moe_run):
    assert moe_run["correct"]
    prog = moe_run["program"]
    assert set(prog) == set(spans.READERS)
    assert all(v is not None for v in prog.values()), prog
    assert 0 <= prog["prefill_pad_share"] < 100
    assert 0 < prog["batch_occupancy"] <= 100
    assert 0 < prog["admit_share"] <= 100
    assert moe_run["ttft_parts_ms"]["n"] > 0
    assert moe_run["loop_gaps"] and moe_run["profiler"] == []
    assert all(v is not None for v in moe_run["harness"].values())


def test_a_dense_run_has_no_expert_host_time():
    out = tool.measure(_cell("dense"), 5, 3.0, False, jax.devices())
    assert out["program"]["expert_host_ms"] is None
    assert out["program"]["decode_host_ms"] is not None


def test_with_the_recorder_off_the_program_reads_nothing():
    out = tool.measure(_cell("moe"), 5, 3.0, False, jax.devices(),
                       recorder=False)
    assert all(v is None for v in out["program"].values())
    assert out["ttft_parts_ms"] == {"n": 0}
    assert out["harness"]["decode_step_ms"] is not None


def test_a_traced_run_records_the_program_for_its_metrics(monkeypatch):
    """``--trace 1``: run.measure turns the recorder on through the drive
    (no stand-in) and the metrics that read ``Run.spans`` are reported.
    The CPU gets a row of peaks, since a traced run asks for them (its
    trace holds no TPU, so no device metric reads them)."""
    from benchmarks.chip import work
    monkeypatch.setitem(work.PEAKS, jax.devices()[0].device_kind,
                        work.PEAKS["TPU v5 lite"])
    cell = dataclasses.replace(_cell("moe"), per_layer=[
        {"name": "expert_host_ms", "unit": "ms"},
        {"name": "prefill_pad_share", "unit": "%"}])
    out = tool.measure(cell, 5, 3.0, True, jax.devices())
    assert out["correct"]
    assert out["metrics"] == {k: out["program"][k] for k in
                              ("expert_host_ms", "prefill_pad_share")}
    assert all(v is not None for v in out["metrics"].values())
    assert [op for op, _, _ in out["profiler"]] == ["start", "stop"]
