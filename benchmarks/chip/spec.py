"""Everything the harness reads by name: ``BENCHMARK.json`` at the checkout
root, and the files of one cell under this directory.

    configs/<config>.json    the model as it is run (published keys, cuts,
                             serving geometry, the reference module's name)
    traffic/<traffic>.json   parameters of the general generator (traffic.py)
    cells/<workload>.json    what belongs to one cell: its offered rate, the
                             lead-in before the window, the correctness sample
                             and each compared number's limit
    metrics/<metric>.py      one reader per metric: ``read(run) -> float|None``
    references/<name>.py     a plain reference: ``logits_at(...)`` (check.py)

A new cell, configuration, traffic mix or metric is a new file plus its
entry in ``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

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


# --------------------------------------------------------------- the model
# Published config.json keys -> the program's ModelConfig fields.  Keys the
# program has no field for (Granite's multipliers, Qwen3's q/k norm) are
# listed as departures in the configuration file itself.
_FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "num_experts": "num_experts",
    "num_experts_per_tok": "moe_top_k",
    "moe_intermediate_size": "moe_d_ff",
    "torch_dtype": "dtype",
}


_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(config: dict) -> Dict[str, int]:
    """The sizes the harness computes with, from a configuration file:
    layers L, width d, heads hq / hkv of hd, vocab V, dense width F,
    experts E (0: dense) with top-k k and width f, bytes b per element."""
    d, hq = config["hidden_size"], config["num_attention_heads"]
    return {"L": config["num_hidden_layers"], "d": d, "hq": hq,
            "hkv": config["num_key_value_heads"],
            "hd": config.get("head_dim", d // hq), "V": config["vocab_size"],
            "F": config["intermediate_size"],
            "E": config.get("num_experts", 0),
            "k": config.get("num_experts_per_tok", 0),
            "f": config.get("moe_intermediate_size", 0),
            "b": _ITEMSIZE[config["torch_dtype"]]}


def model_fields(config: dict) -> Dict[str, object]:
    """ModelConfig keyword arguments for a configuration file: the published
    keys it holds, mapped by ``_FIELDS``, plus ``capacity_factor`` from the
    serving group.  ``head_dim`` defaults to hidden_size / heads as in the
    published models that omit it."""
    kw: Dict[str, object] = {"name": config["name"],
                             "family": "moe" if config.get("num_experts") else "dense",
                             "attention_type": "gqa"}
    for key, field in _FIELDS.items():
        if key in config:
            kw[field] = config[key]
    if "head_dim" not in kw:
        kw["head_dim"] = config["hidden_size"] // config["num_attention_heads"]
    cf: Optional[float] = config["serving"].get("capacity_factor")
    if cf is not None:
        kw["capacity_factor"] = float(cf)
    return kw
