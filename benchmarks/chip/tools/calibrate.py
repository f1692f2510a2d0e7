"""Readings that set a cell's correctness limits, on many seeds in one
process (the compiles are paid once): the gap statistics of the program's
served tokens; of each lower-precision control's, on the first
``--control-seeds`` seeds; and of a run with a fault planted
(``faults.py``) on each of ``--fault-seeds``.

    python3 -m benchmarks.chip.tools.calibrate --workload qwen3-chat \\
        --seeds 11 12 13 --seconds 30 --controls int8 fp8 --control-seeds 2 \\
        --fault decoded_token_altered --fault-seeds 21 22 --out gaps/

Prints one JSON line per run, with each control's numbers held against the
cell's limits (``control_checks``: a control has to fail one).  With
``--out`` it also keeps every compared token's gap and router margin, one
``<workload>-<seed>[-<fault>].npz`` per run, to set limits from.  Not part
of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

from benchmarks.chip import run, spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", nargs="*", default=["int8", "fp8"])
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault", default="decoded_token_altered")
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    sys.path.insert(0, str(spec.ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    from benchmarks.chip import check, faults
    run.use_compile_cache(spec.ROOT)
    jobs = [(seed, None, tuple(args.controls) if i < args.control_seeds else ())
            for i, seed in enumerate(args.seeds)]
    jobs += [(seed, args.fault, ()) for seed in args.fault_seeds]
    for seed, fault, controls in jobs:
        line = run.measure(cell, seed, args.seconds, False, devices[:1],
                           controls=controls, keep_gaps=args.out is not None,
                           tamper=faults.plant(fault) if fault else None)
        arrays = line.pop("gap_arrays", {})
        if args.out is not None and arrays:
            name = f"{cell.name}-{seed}" + (f"-{fault}" if fault else "")
            run.save_gaps(args.out / f"{name}.npz", arrays)
        held = {mode: {k: c["ok"] for k, c in check.compare(
                    dict(line["gap_stats"][mode],
                         compared_tokens=line["checks"]["compared_tokens"]["value"]),
                    {k: v for k, v in cell.params["limits"].items()
                     if k != "after_relocation"}).items()}
                for mode in controls}
        print(json.dumps({"seed": seed, "fault": fault,
                          "correct": line["correct"], "checks": line["checks"],
                          "gap_stats": line["gap_stats"],
                          "control_checks": held,
                          "metrics": line["metrics"], "diag": line["diag"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
