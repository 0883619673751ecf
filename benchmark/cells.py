"""Finds everything a cell is made of, by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix. The configuration's
file (``configs[].file``) names its entry-point adapter
(``benchmark/entries/<entry>.py``) and its plain reference; the traffic
mix is ``benchmark/traffic/<traffic>.json``; each metric has a reader
of its own, ``benchmark/end_to_end/<metric>.py`` or
``benchmark/layer_metrics/<metric>.py``. Adding a cell, a
configuration, a traffic mix or a per-layer metric therefore adds files
and entries and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_module(path: str):
    """Import a file by path (names with ``-`` and ``.`` included)."""
    if not os.path.isabs(path):
        path = os.path.join(ROOT, path)
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT)
    )
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _read_json(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict  # the configuration's file
    traffic: dict  # the traffic mix's file
    end_to_end: tuple[dict, ...]  # BENCHMARK.json entries this cell reports
    per_layer: tuple[dict, ...]

    def entry(self):
        return load_module(os.path.join(HERE, "entries", self.config["entry"] + ".py"))

    def reference(self):
        return load_module(self.config["reference"])


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str) -> Cell:
    bench = _read_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json (has {sorted(cells)})")
    cell = cells[name]
    (config_entry,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    return Cell(
        name=name,
        chips=cell["chips"],
        config=_read_json(config_entry["file"]),
        traffic=_read_json(os.path.join("benchmark", "traffic", cell["traffic"] + ".json")),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
    )


def read_metrics(metrics, directory: str, record: dict) -> dict:
    """Each metric's own reader over the run's record. A reader that
    finds nothing to read returns ``None`` and the metric is left out
    of the line."""
    out = {}
    for metric in metrics:
        reader = load_module(os.path.join(HERE, directory, metric["name"] + ".py"))
        for const, key in (("UNIT", "unit"), ("LAYER", "layer"), ("MOVES", "moves")):
            if key in metric and getattr(reader, const) != metric[key]:
                raise ValueError(
                    f"{metric['name']}: its reader says {const} = "
                    f"{getattr(reader, const)!r}, BENCHMARK.json says {metric[key]!r}"
                )
        value = reader.read(record)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out
