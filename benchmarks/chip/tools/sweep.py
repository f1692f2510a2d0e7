"""Find a cell's knee: its traffic offered at several fixed rates, one run
each (``run.measure`` with the rate overridden), in one process so the
compiles are paid once.

    python3 -m benchmarks.chip.tools.sweep --workload qwen3-chat \\
        --rates 1.2 1.6 2.0 2.4 --seconds 30 --seed 5

Prints one JSON line per rate: the end-to-end numbers and ``diag`` (the
requests still queued or running at the close, which grow with the window
above the rate the system sustains, the mean decode batch and the tails).
Not part of a benchmark run; the cell's rate is written into its file.
"""
from __future__ import annotations

import argparse
import json
import sys

from benchmarks.chip import run, spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    sys.path.insert(0, str(spec.ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    run.use_compile_cache(spec.ROOT)
    for rate in args.rates:
        line = run.measure(cell, args.seed, args.seconds, False, devices[:1],
                           rate=rate)
        print(json.dumps({"rate_rps": rate, "correct": line["correct"],
                          "due": line["attempted"],
                          "metrics": {k: m["value"]
                                      for k, m in line["metrics"].items()},
                          "diag": line["diag"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
