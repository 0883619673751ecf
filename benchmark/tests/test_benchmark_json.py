"""BENCHMARK.json against the files it names and the contract's limits
that can be checked without a run."""

import json
import os
import re

import pytest

from benchmark import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_names(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert 1 <= bench["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200 and NAME.match(w["traffic"])
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


def test_every_cell_finds_its_files_and_readers(bench):
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"])
        assert hasattr(cell.entry(), "run")
        assert os.path.exists(os.path.join(cells.ROOT, cell.config["reference"]))
        assert len(cell.traffic["learning_rates"]) == cell.chips
        assert {"setup_s"} < {m["name"] for m in cell.end_to_end}
        assert cell.per_layer
        for directory, metrics in (("end_to_end", cell.end_to_end),
                                   ("layer_metrics", cell.per_layer)):
            for m in metrics:
                reader = cells.load_module(
                    os.path.join(cells.HERE, directory, m["name"] + ".py"))
                assert reader.UNIT == m["unit"] and callable(reader.read)
                if directory == "layer_metrics":
                    assert (reader.LAYER, reader.MOVES) == (m["layer"], m["moves"])


def test_config_file_holds_what_is_run(bench):
    for c in bench["configs"]:
        with open(os.path.join(cells.ROOT, c["file"])) as f:
            held = json.load(f)
        assert held["source"] == c["source"]
        assert sorted(held["reduced"]) == sorted(c["reduced"])
        assert all(k in held for k in c["reduced"])
