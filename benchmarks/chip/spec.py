"""Find a cell's parts by name.

``BENCHMARK.json`` names each cell's configuration, traffic mix and
per-layer metrics; each lives in a file of its own under this directory:

* ``configs/<config>.json``    — the deployment (engine and service
                                  settings, source, cuts);
* ``traffic/<mix>.json``       — the mix's parameters, read by the
                                  module its ``kind`` names
                                  (``kinds/<kind>.py``);
* ``metrics/<metric>.py``      — a reader with ``read(ctx)`` returning a
                                  number, or None where it finds nothing.

A later cell, mix or metric is added as files and entries only.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from typing import Callable, Dict, List

HERE = pathlib.Path(__file__).resolve().parent
# where the harness sits in a checkout (BENCHMARK.json's ``paths``)
HARNESS = pathlib.PurePosixPath("benchmarks/chip")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict                 # the configuration's file
    traffic: dict                # the mix's file
    end_to_end: List[dict]       # BENCHMARK.json entries this cell reports
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / HARNESS / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, base: pathlib.Path = HERE) -> Callable:
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    path = base / "metrics" / f"{metric}.py"
    return load_module(path, f"chip_metric_{metric}").read


def kind_module(kind: str, base: pathlib.Path = HERE):
    """The module ``kinds/<kind>.py`` that a mix's ``kind`` names."""
    return load_module(base / "kinds" / f"{kind}.py", f"chip_kind_{kind}")


def read_metrics(entries: List[dict], ctx: Dict,
                 base: pathlib.Path = HERE) -> Dict[str, dict]:
    """Every per-layer metric whose reader finds something to read."""
    out = {}
    for m in entries:
        value = reader(m["name"], base)(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
