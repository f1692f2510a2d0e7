"""Runs of one cell with the program's own recorder (``repro.core.trace``)
on through the window, reduced by ``spans.py``: the five per-layer numbers
it reads, each long step's three largest program spans, the split of the
time to first token, and (``--trace 1``) the idle gaps labelled by the
program's spans.  It also gives the loop's longest pauses between two
``cluster.step`` records and when the profiler started and stopped, which
tell a stall of the loop from a stall of a step.  With ``--recorder 0`` the
run leaves the recorder off;
``--recorder 0 1`` runs each seed both ways in turn, which gives the
recorder's cost on ``prefill_ms`` and ``decode_step_ms``.

    python3 -m benchmarks.chip.tools.spans --workload qwen3-chat \\
        --seeds 11 12 13 --seconds 51 --trace 1 --out chiprun_out/spans.jsonl

Each run is ``run.measure`` with three stand-ins, in one process so the
compiles are paid once: ``serve.drive`` turns the recorder on as it starts,
which is where set-up ends, and drains it when it returns;
``devtrace.load`` reduces the trace through ``spans.reduce``; the
profiler's start and stop are timed.  Prints one
JSON line per run (and appends it to ``--out``).  Not part of a benchmark
run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types
from pathlib import Path

from benchmarks.chip import devtrace, run, serve, spans, spec, stats


def measure(cell: spec.Cell, seed: int, seconds: float, trace: bool,
            devices, recorder: bool = True) -> dict:
    """``run.measure`` with the recorder on (or, ``recorder=False``, off)
    through the traffic; returns its result line's numbers with the
    recorder's beside them."""
    import jax
    from repro.core import trace as tracer
    got = {}
    own_drive, own_load = serve.drive, devtrace.load
    own_prof = jax.profiler.start_trace, jax.profiler.stop_trace
    prof = []

    def timed(op, fn):
        def call(*a, **k):
            t = time.perf_counter()
            try:
                return fn(*a, **k)
            finally:
                prof.append((op, t, time.perf_counter() - t))
        return call

    def drive(cluster, arrivals, t0, stop, rec, base=0.0, on_step=None):
        if recorder:
            tracer.enable()
        try:
            tracks = own_drive(cluster, arrivals, t0, stop, rec, base, on_step)
        finally:
            tracer.disable()
        got.update(tracks=tracks, rec=rec, close=stop, records=tracer.drain())
        return tracks

    serve.drive, devtrace.load = drive, spans.load
    jax.profiler.start_trace = timed("start", own_prof[0])
    jax.profiler.stop_trace = timed("stop", own_prof[1])
    try:
        line = run.measure(cell, seed, seconds, trace, devices)
    finally:
        serve.drive, devtrace.load = own_drive, own_load
        jax.profiler.start_trace, jax.profiler.stop_trace = own_prof
    close = got["close"]
    open_ = close - seconds
    recs, rec = got["records"], got["rec"]
    a, b = open_ * 1e9, close * 1e9
    view = types.SimpleNamespace(rec=rec, open=open_, close=close)
    firsts = {tr.arrival.idx: (tr.due, tr.first)
              for tr in stats.in_window(got["tracks"], open_, close)
              if tr.first is not None and tr.first < close}
    out = {"workload": cell.name, "seed": seed, "trace": int(trace),
           "recorder": int(recorder), "correct": line["correct"],
           "metrics": {k: m["value"] for k, m in line["metrics"].items()},
           "harness": {k: spec.metric_reader(k).read(view)
                       for k in ("prefill_ms", "decode_step_ms")},
           "program": {k: f(recs, a, b) for k, f in spans.READERS.items()},
           "ttft_parts_ms": spans.ttft_parts(recs, firsts),
           "loop_gaps": spans.loop_gaps(recs, a, b, open_),
           "profiler": [[op, t - open_, d] for op, t, d in prof],
           "compiles_in_window": sum(
               r.name == spans.PREFIX + "compile" and a <= r.start < b
               for r in recs),
           "stalls": [dict(st, spans=spans.stall_spans(
               recs, (open_ + st["at_s"]) * 1e9,
               (open_ + st["at_s"] + st["step_s"]) * 1e9))
               for st in line["diag"]["stalls"]],
           "device": line["device"]}
    if "breakdown" in line:
        out["breakdown"] = line["breakdown"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=1)
    ap.add_argument("--recorder", type=int, choices=[0, 1], nargs="+",
                    default=[1])
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    sys.path.insert(0, str(spec.ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("spans: needs a TPU", file=sys.stderr)
        return 2
    run.use_compile_cache(spec.ROOT)
    for seed in args.seeds:
        for on in args.recorder:
            out = measure(cell, seed, args.seconds, bool(args.trace),
                          devices[:cell.chips], recorder=bool(on))
            text = json.dumps(out)
            print(text, flush=True)
            if args.out is not None:
                args.out.parent.mkdir(parents=True, exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
