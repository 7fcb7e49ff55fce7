"""Finds a cell's parts by name: ``BENCHMARK.json`` at the root of the
checkout, ``configs/<config>.json``, ``traffic/<traffic>.json``,
``limits/<cell>.json``, ``metrics/<metric>.py`` and the device peaks in
``peaks.json``.  Adding a configuration, a traffic mix, a cell or a
per-layer metric adds files; nothing here changes."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple     # metric entries of BENCHMARK.json; a per-layer
    per_layer: tuple      # reader that finds nothing in a cell returns None


def _read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_cell(name: str, root: str = ROOT, here: str = HERE) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files.
    Raises ``LookupError`` naming what is missing."""
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise LookupError(f"no BENCHMARK.json at {root}")
    bench = _read_json(path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise LookupError(f"no workload {name!r} in BENCHMARK.json; "
                          f"cells: {sorted(cells)}")
    w = cells[name]
    files = {"config": os.path.join(here, "configs", w["config"] + ".json"),
             "traffic": os.path.join(here, "traffic", w["traffic"] + ".json"),
             "limits": os.path.join(here, "limits", name + ".json")}
    for what, f in files.items():
        if not os.path.exists(f):
            raise LookupError(f"cell {name!r}: no {what} file {f}")
    return Cell(name=name, chips=int(w["chips"]),
                config=_read_json(files["config"]),
                traffic=_read_json(files["traffic"]),
                limits=_read_json(files["limits"]),
                end_to_end=tuple(bench["end_to_end"]),
                per_layer=tuple(bench["per_layer"]))


def load_module(path: str, name: str):
    """Import the Python file ``path`` as module ``name``."""
    if not os.path.exists(path):
        raise LookupError(f"no file {path}")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def metric_reader(name: str, here: str = HERE):
    """The reader module of a per-layer metric: ``metrics/<name>.py``,
    whose ``read(ctx)`` returns the value or None."""
    return load_module(os.path.join(here, "metrics", name + ".py"),
                       f"bench.metrics.{name}")


def peaks(device_kind: str) -> dict:
    """Published peaks of a device kind; a kind not in the table is an
    error."""
    table = _read_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise LookupError(f"no peaks for device kind {device_kind!r} in "
                          f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
