"""Everything the harness reads by name: ``BENCHMARK.json`` at the checkout
root, and the files of one cell under this directory.

    configs/<config>.json    the model as it is run (published keys, cuts,
                             serving geometry, the names of its arch and
                             reference modules)
    traffic/<traffic>.json   parameters of the general generator (traffic.py)
    cells/<workload>.json    what belongs to one cell: its offered rate, the
                             lead-in before the window, the correctness sample
                             and each compared number's limit
    metrics/<metric>.py      one reader per metric: ``read(run) -> float|None``
                             (run.Run: the window, the harness's Recorder, the
                             device trace, and in traced runs the program's
                             own span records, ``run.spans``)
    references/<name>.py     a plain reference: ``logits_at(...)`` (check.py)
    archs/<name>.py          what the harness knows of one architecture,
                             named by the configuration's ``"arch"``

An arch module provides, each taking the configuration dict:

    model_fields(config)        the program's ModelConfig keyword arguments
    tree(key, config)           the seeded weights in the program's layout
                                (weights.py compiles it into one program)
    held_experts(config)        routed experts of a layer on the chip (0: dense)
    model_flops(config, call)   model FLOPs of one recorded prefill or decode
                                step (model_mfu)
    kernel_work(config, kernel, call)
                                (flops, bytes) of the named kernel in that
                                call, or None where the call runs no such
                                kernel (the kernels' rooflines)

A ``call`` is what serve.Recorder keeps of one backend call (serve.Span):
``rows`` (a prefill's prompt length, a decode step's active rows),
``lengths`` (a decode step's resident tokens per row, None for a prefill)
and ``experts`` (routed expert ids, where the program hands them back).

A new cell, configuration, architecture, traffic mix or metric is a new
file plus its entry in ``BENCHMARK.json``; nothing here, in weights.py,
work.py or run.py changes.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]            # the checkout root (holds BENCHMARK.json)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict                  # configs/<config>.json
    traffic: dict                 # traffic/<traffic>.json
    params: dict                  # cells/<workload>.json
    chips: int
    end_to_end: List[dict]        # BENCHMARK.json metric entries of this cell
    per_layer: List[dict]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT, here: Path = HERE) -> Cell:
    """The cell named ``workload`` in ``root/BENCHMARK.json``, with its files
    found under ``here``.  Raises KeyError for an unknown workload and
    FileNotFoundError for a missing file."""
    bench = load_json(root / "BENCHMARK.json")
    w = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if w is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    return Cell(
        name=workload,
        config=load_json(here / "configs" / f"{w['config']}.json"),
        traffic=load_json(here / "traffic" / f"{w['traffic']}.json"),
        params=load_json(here / "cells" / f"{workload}.json"),
        chips=int(w["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
    )


def load_module(path: Path, prefix: str) -> ModuleType:
    """Import one file by path (metric names may hold '.' and '-')."""
    name = prefix + "".join(c if c.isalnum() else "_" for c in path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, here: Path = HERE) -> ModuleType:
    return load_module(here / "metrics" / f"{name}.py", "chipbench_metric_")


def reference_module(config: dict, here: Path = HERE) -> ModuleType:
    return load_module(here / "references" / f"{config['reference']}.py",
                       "chipbench_reference_")


@functools.lru_cache(maxsize=None)
def _arch(path: Path) -> ModuleType:
    return load_module(path, "chipbench_arch_")


def arch_module(config: dict, here: Path = HERE) -> ModuleType:
    """The arch module the configuration names, loaded once per path (the
    work counts ask it for every recorded call)."""
    return _arch(here / "archs" / f"{config['arch']}.py")
