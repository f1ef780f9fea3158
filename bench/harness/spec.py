"""Cells, configurations, model modules, traffic mixes and metric readers,
found by name.

``BENCHMARK.json`` at the checkout's root names every cell. A cell's
configuration is ``bench/configs/<config>.json`` (through the ``file`` key); the
configuration's ``"model"`` key names its model module
``bench/models/<model>.py``, which gives the weight layout, the plain
reference, the operations per token and the paged attention's shape (see
``bench/models/decoder.py``). The cell's traffic mix is
``bench/traffic/<traffic>.json``, its limits ``bench/limits/<cell>.json``, and
each metric it reports a reader ``bench/metrics/<metric>.py`` with a function
``read(run)``.

Adding a cell, a configuration, a mix or a metric therefore means adding files
and entries; no code here changes. A new architecture is a new configuration
file whose ``"model"`` names a new ``bench/models/<name>.py``, beside a mix and
limits for its cell: no existing file of the benchmark is edited.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import types
from typing import Callable, Dict, List

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file's contents
    model: types.ModuleType  # the configuration's model module
    traffic_name: str
    traffic: dict         # the traffic file's contents
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics; raises
    ``KeyError`` for a name ``BENCHMARK.json`` does not hold."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, model=model_module(config["model"], root),
                traffic_name=w["traffic"], traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def _load(path: pathlib.Path, name: str) -> types.ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no module {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_module(name: str, root: pathlib.Path = ROOT) -> types.ModuleType:
    """``bench/models/<name>.py``: ``layout``, ``calibrate``, ``logits``,
    ``work`` and ``attention`` of one architecture. Each call executes the
    file anew; a cell keeps the module it was loaded with, and with it the
    reference's compiled programs."""
    return _load(root / "bench" / "models" / f"{name}.py", f"bench_model_{name}")


def reader(metric: str, root: pathlib.Path = ROOT) -> Callable:
    """``read(run)`` of ``bench/metrics/<metric>.py``."""
    return _load(root / "bench" / "metrics" / f"{metric}.py", f"bench_metric_{metric}").read


def read_metrics(metrics: List[dict], run, root: pathlib.Path = ROOT) -> Dict[str, dict]:
    """Each metric's value as its reader gives it; a reader that returns None
    found nothing to read and its metric is left out of the line."""
    out = {}
    for m in metrics:
        v = reader(m["name"], root)(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
