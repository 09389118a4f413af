"""BENCHMARK.json and the files it names: every configuration, traffic
mix, entry, reference and metric is found by its name."""

from __future__ import annotations

import os
import re

import pytest

from perfbench import harness

BENCH = harness.load_bench()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_name_is_well_formed_and_unique():
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in BENCH[key]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_finds_its_files(workload):
    cell = harness.load_cell(BENCH, workload)
    assert hasattr(harness.load_module("entries", cell.config["entry"]),
                   "open")
    ref = harness.load_module("references", cell.config["reference"])
    assert callable(ref.gap) and callable(ref.control)
    for m in cell.end_to_end + cell.per_layer:
        assert callable(harness.load_module("metrics", m["name"]).read)
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    assert all(m["moves"] in reported for m in cell.per_layer)
    for g in cell.traffic["groups"]:
        assert g["order"] in ("interleave", "slices")


def test_configurations_and_paths():
    root = harness.ROOT
    for c in BENCH["configs"]:
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert os.path.isfile(os.path.join(root, c["file"]))
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(root, p))
    assert BENCH["command"] == ["python3", "perfbench/run.py"]
