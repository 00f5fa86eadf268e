"""Find a cell and everything that belongs to it, by name.

``BENCHMARK.json`` at the checkout's root names each cell's configuration
and traffic mix; their parameters live in ``bench/configs/<config>.json``
and ``bench/traffic/<traffic>.json``.  A traffic file names its loop, the
code that turns its parameters into load: ``bench/loops/<loop>.py``.  Each
per-layer metric's reader is ``bench/metrics/<name>.py``.  Adding a cell
adds files and entries; no code here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
BENCH_DIR = os.path.basename(BENCH)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    root: str = ROOT


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(root, BENCH_DIR, "traffic",
                                     w["traffic"] + ".json"))
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"], config=config,
        traffic_name=w["traffic"], traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        root=root,
    )


def _module(kind: str, name: str, root: str):
    """The module ``bench/<kind>/<name>.py`` of the checkout at ``root``."""
    path = os.path.join(root, BENCH_DIR, kind, name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def metric_reader(name: str, root: str = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    return _module("metrics", name, root).read


def loop_module(name: str, root: str = ROOT):
    """``bench/loops/<name>.py``: its ``setup(ctx)`` and ``window(ctx,
    state)`` (see ``harness``)."""
    return _module("loops", name, root)
