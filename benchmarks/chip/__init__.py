"""The on-chip benchmark: see run.py for one run, BENCHMARK.json at the
checkout root for the cells, and PERF.md for what each number means."""
