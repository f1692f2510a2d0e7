"""One run of one cell of the on-chip benchmark.

    python3 -m benchmarks.chip --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run needs a TPU and the chips the cell names: with neither it exits 2
and prints no result.  It keeps JAX's compile cache where
``$JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/`` of the checkout,
and the profiler's trace (``--trace 1``) in ``.chipbench/`` of the checkout,
deleted once read.  With ``--trace 1`` the program's own recorder
(``repro.core.trace``) is on from the lead-in to the close, for the
metrics that read its records; ``--trace 0`` leaves it off.

Set-up (``setup_s``, from process start to the window's opening): weights
drawn from the seed on the device, the cluster built, every shape of this
cell's traffic compiled and run once, then the traffic itself for the cell's
lead-in, so the window opens on a running system.  The window lasts
``--seconds``; only requests due inside it count toward its latencies.  Once
it closes the program's state is freed and the reference checks a sample of
what was served (check.py).

Standard output's last line is one JSON object: ``correct``, ``attempted``
(requests due in the window), ``failed`` (of those, shed or refused),
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` a ``breakdown``, ``diag``
(what the tools read: backlog, decode batch, tails and the window's stalls,
each a step longer than half a second with what took its time), and last
``checks``: each compared number with its limit.  The same numbers close
standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, List, Optional  # noqa: E402

from benchmarks.chip import check, spec, stats, traffic  # noqa: E402

OUT_DIR = ".chipbench"          # run-time files, inside the checkout
TRACE_AFTER_S, TRACE_FOR_S = 2.0, 3.0    # the traced part of the window


class CompileClock:
    """Times (perf_counter) and durations of XLA backend compiles, and the
    count of persistent-cache hits, from jax.monitoring (copied from
    chip_smoke.py; a persistent-cache load counts as its retrieval)."""

    def __init__(self):
        import jax
        self.events: List[tuple] = []
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), duration))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def between(self, a: float, b: float) -> List[float]:
        return [d for t, d in self.events if a <= t < b]


@dataclasses.dataclass
class Run:
    """What a metric reader sees.  ``spans``: the program's own records
    (``repro.core.trace``) of the whole drive in a traced run, empty in an
    untraced one; spans.py reduces them."""
    cell: spec.Cell
    config: dict
    seconds: float
    open: float
    close: float
    setup_s: float
    tracks: list
    rec: object
    trace: object = None          # devtrace.Trace of the traced part
    trace_host: tuple = ()        # (start, stop) of the trace, host clock
    peaks: Optional[dict] = None
    spans: list = dataclasses.field(default_factory=list)


def use_compile_cache(root: Path) -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` where set (JAX reads it itself), else
    the fixed ``.jax_cache/`` of the checkout.  Every program is kept,
    however quick its compile, so a second run compiles nothing."""
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def save_gaps(path: Path, arrays: dict) -> None:
    """``measure``'s gap arrays as one .npz: ``<mode>_gaps``, ``<mode>_margins``."""
    import numpy as np
    path.parent.mkdir(parents=True, exist_ok=True)
    np.savez(path, **{f"{mode}_{part}": a for mode, pair in arrays.items()
                      for part, a in zip(("gaps", "margins"), pair)})


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def measure(cell: spec.Cell, seed: int, seconds: float, trace: bool,
            devices, *, tamper: Optional[Callable] = None,
            controls: Optional[tuple] = None, rate: Optional[float] = None,
            keep_gaps: bool = False,
            on_run: Optional[Callable[[Run], None]] = None) -> dict:
    """One run on ``devices`` (platform unchecked: ``main`` checks it).
    ``tamper(cluster)``, for the tests, may break the timed path after
    warm-up.  The tools may offer another ``rate`` than the cell's
    (tools/sweep.py), and name lower-precision references to read on the
    same sample (``controls``, tools/calibrate.py): the gap statistics of the
    program's tokens and of each control's come back under ``"gap_stats"``,
    and with ``keep_gaps`` the gaps and margins themselves under
    ``"gap_arrays"``.  ``on_run`` is handed the ``Run`` before its metrics
    are read (tools/spans.py).  With ``trace`` the program's own recorder
    (``repro.core.trace``) is on through ``serve.drive`` and its records
    go to ``Run.spans``; without, it stays off, so no end-to-end metric is
    taken with it on.  Returns the result line as a dict, with ``diag``
    (rate, backlog at the close, mean decode batch, tails, the window's
    stalls) beside the metrics."""
    import jax
    import numpy as np
    from repro.core import trace as tracer
    from repro.models.config import ModelConfig

    from benchmarks.chip import devtrace, serve, weights, work

    clock = CompileClock()
    device = devices[0]
    config, cp = cell.config, cell.params
    cfg = ModelConfig(**spec.arch_module(config).model_fields(config))
    t = time.perf_counter()
    params = weights.make(config, seed, device)
    jax.block_until_ready(params)
    t_weights = time.perf_counter() - t
    cluster = serve.build(cfg, params, config["serving"], device)
    del params
    lead = float(cp["lead_in_s"])
    rate = float(cp["rate_rps"]) if rate is None else float(rate)
    arrivals = traffic.generate(cell.traffic, rate, lead + seconds, seed,
                                config["vocab_size"])
    t = time.perf_counter()
    base = serve.warm(cluster, arrivals, config["vocab_size"], seed)
    t_warm = time.perf_counter() - t
    rec = serve.Recorder(annotate=trace, device=device)
    rec.attach(cluster)
    if tamper is not None:
        tamper(cluster)

    gc.collect()
    gc.freeze()          # set-up's objects: no collector pass walks them again
    t0 = time.perf_counter()
    open_, close = t0 + lead, t0 + lead + seconds
    setup_s = open_ - T_START
    trace_dir = spec.ROOT / OUT_DIR / f"trace-{cell.name}"
    prof = {"at": open_ + TRACE_AFTER_S, "on": None, "off": None}

    def on_step(now):
        if not trace or prof["off"] is not None:
            return
        if prof["on"] is None and now >= prof["at"]:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
            prof["on"] = time.perf_counter()
        elif prof["on"] is not None and now >= prof["on"] + TRACE_FOR_S:
            prof["off"] = time.perf_counter()
            jax.profiler.stop_trace()

    if trace:
        tracer.enable()
    try:
        tracks = serve.drive(cluster, arrivals, t0, close, rec, base, on_step)
    finally:
        tracer.disable()
    records = tracer.drain()
    if prof["on"] is not None and prof["off"] is None:
        prof["off"] = time.perf_counter()
        jax.profiler.stop_trace()
    rec.detach()
    mem = (device.memory_stats() or {}).get("peak_bytes_in_use")
    backlog = cluster.pending()
    engines = list(cluster.engines.values())
    relocations = sum(e.relocations for e in engines)
    kernel_modes = sorted({e.backend.kernel_mode for e in engines})
    served = [check.Served(tr.arrival.idx, tr.arrival.prompt,
                           list(tr.req.output_tokens),
                           rec.started_after_reloc.get(tr.arrival.idx, 0) >= 1)
              for tr in tracks if tr.finished is not None and not tr.failed]
    del cluster, engines
    gc.unfreeze()
    gc.collect()

    in_win = stats.in_window(tracks, open_, close)
    late = stats.lateness(tracks)
    stalls = rec.stalls(open_, close)
    batches = [d.rows for d in rec.decodes if open_ <= d.t0 < close]
    diag = {"rate_rps": rate, "backlog_at_close": backlog,
            "mean_batch": sum(batches) / max(len(batches), 1),
            "ttft_p95_ms": 1e3 * stats.percentile(
                stats.ttfts(tracks, open_, close), 95),
            "tpot_p95_ms": 1e3 * stats.percentile(
                stats.tpots(tracks, open_, close), 95),
            "stalls": stalls[:10]}
    in_compiles = clock.between(open_, close)
    set_compiles = clock.between(0.0, open_)
    _log(f"[run] {cell.name} seed={seed} device={device.device_kind} "
         f"kernels={','.join(kernel_modes)} weights_s={t_weights:.3f} "
         f"warm_s={t_warm:.3f} lead_in_s={lead} setup_s={setup_s:.3f} "
         f"setup_compiles={len(set_compiles)} ({sum(set_compiles):.3f}s) "
         f"cache_hits={clock.hits} memory_peak_bytes={mem}")
    _log(f"[run] window_s={seconds} due={len(in_win)} "
         f"submitted={len(tracks)} finished={len(served)} "
         f"tokens={stats.tokens(tracks, open_, close)} "
         f"ttft_samples={len(stats.ttfts(tracks, open_, close))} "
         f"tpot_samples={len(stats.tpots(tracks, open_, close))} "
         f"relocations={relocations} compiles_in_window={len(in_compiles)} "
         f"({sum(in_compiles):.3f}s) generator_late_p50_ms="
         f"{1e3 * stats.percentile(late, 50):.3f} "
         f"generator_late_max_ms={1e3 * max(late, default=0.0):.3f}")
    for st in stalls:
        _log("[stall] " + " ".join(f"{k}={v}" for k, v in st.items()))

    run = Run(cell, config, seconds, open_, close, setup_s, tracks, rec,
              spans=records)
    device_info = {"platform": device.platform, "kind": device.device_kind,
                   "count": len(devices), "memory_peak_bytes": mem}
    breakdown = None
    if trace:
        run.trace_host = (prof["on"], prof["off"])
        run.trace = devtrace.load(trace_dir)
        run.peaks = work.peaks(device.device_kind)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if run.trace is not None:
            device_info["busy_s"] = run.trace.busy_s()
            device_info["window_s"] = run.trace.window_s
            breakdown = {"device_ops": run.trace.top_ops(10),
                         "idle_gaps": run.trace.idle_gaps(10)}

    if on_run is not None:
        on_run(run)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = spec.metric_reader(m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # ------------------------------------------------------------ correctness
    t = time.perf_counter()
    ref = spec.reference_module(config)
    moe = work.held_experts(config) > 0
    picks = check.sample(served, seed, int(cp["sample_requests"]), moe)
    readings = {"compared_tokens": float(sum(len(s.tokens) for s in picks))}
    if moe:
        readings["after_relocation"] = float(sum(s.after_relocation
                                                 for s in picks))
    gaps = {}
    decisive = cp.get("decisive_margin")

    def joined(parts):
        return tuple(np.concatenate(x) for x in zip(*parts))
    if picks:
        params = weights.make(config, seed, device)
        gaps["program"] = joined(
            [check.served_gaps(ref, params, config, s) for s in picks])
        for mode in controls or ():
            gaps[mode] = joined(
                [check.control_gaps(ref, params, config, s, mode) for s in picks])
        del params
        readings.update(check.summary(*gaps["program"], decisive))
    checks = check.compare(readings, cp["limits"])
    _log(f"[check] sample={[s.req_id for s in picks]} "
         f"check_s={time.perf_counter() - t:.3f}")
    correct = bool(checks) and all(c["ok"] for c in checks.values())
    line = {"correct": correct, "attempted": len(in_win),
            "failed": sum(tr.failed for tr in in_win),
            "metrics": metrics, "device": device_info}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["diag"] = diag
    if controls is not None:
        line["gap_stats"] = {k: check.summary(*v, decisive)
                             for k, v in gaps.items()}
    if keep_gaps:
        line["gap_arrays"] = gaps
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"],
                          "kind": c["kind"]} for k, c in checks.items()}
    for k, c in checks.items():
        _log(f"check {k} {c['value']!r} {c['kind']} {c['limit']!r} "
             f"{'ok' if c['ok'] else 'FAILED'}")
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--keep-gaps", type=Path, default=None,
                    help="also save the compared tokens' gaps and router "
                         "margins to <dir>/<workload>-<seed>.npz")
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        _log(f"chipbench: {e}")
        return 2
    src = spec.ROOT / "src"
    if not (src / "repro").is_dir():
        _log(f"chipbench: the program is missing ({src / 'repro'})")
        return 2
    sys.path.insert(0, str(src))
    # libtpu would log to a fixed /tmp directory: keep its logs off
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        _log(f"chipbench: needs {cell.chips} TPU chip(s); JAX found "
             f"{len(devices)} {devices[0].platform!r} device(s)")
        return 2
    _log(f"[run] compile_cache={use_compile_cache(spec.ROOT)}")
    line = measure(cell, args.seed, args.seconds, bool(args.trace),
                   devices[:cell.chips], keep_gaps=args.keep_gaps is not None)
    arrays = line.pop("gap_arrays", None)
    if arrays:
        save_gaps(args.keep_gaps / f"{cell.name}-{args.seed}.npz", arrays)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
