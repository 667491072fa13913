"""BENCHMARK.json keeps to the contract's letter, and every name in it
resolves to a file."""
import importlib
import json
import os
import re

import pytest

from benchmark import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def m():
    return manifest.manifest()


def test_keys_and_sizes(m):
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) \
        < 64 * 1024
    assert m["paths"] == ["benchmark"]
    assert not any(w.startswith("/") or ".." in w for w in m["command"])


def test_names_units_and_lines(m):
    metrics = m["end_to_end"] + m["per_layer"]
    names = [x["name"] for x in metrics]
    assert len(set(names)) == len(names)
    for x in metrics:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"]), x
        assert x["better"] in ("lower", "higher") and x["source"] in SOURCES
    for x in m["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert x["source"] in ("host_clock", "device_trace")
        assert 0.01 <= x["bound"] <= 0.1
    assert "setup_s" in names
    for x in m["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert "\n" not in x["layer"] and len(x["layer"]) <= 200
        if x["name"].endswith("_roofline"):
            assert x["unit"] == "%"
    for entry in m["configs"] + m["workloads"]:
        assert NAME.match(entry["name"])
        assert 1 <= len(entry["why"]) <= 200 and "\t" not in entry["why"]
    for c in m["configs"]:
        assert 1 <= len(c["source"]) <= 200 and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(len(m["workloads"]) // 4, 1)


def test_every_cell_resolves_and_reports_what_its_metrics_move(m):
    e2e = {x["name"]: x for x in m["end_to_end"]}
    for w in m["workloads"]:
        cell = manifest.cell(w["name"])
        reported = {x["name"] for x in cell["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell["per_layer"], w["name"]
        for x in cell["per_layer"]:
            assert x["moves"] in reported, (w["name"], x["name"])
            importlib.import_module("benchmark.readers." + x["reader"])
        cfg = cell["config"]
        importlib.import_module("benchmark.datagen." + cfg["datagen"])
        importlib.import_module("benchmark.drivers."
                                + cell["traffic"]["driver"])
        entry = next(c for c in m["configs"] if c["name"] == w["config"])
        assert set(entry["reduced"]) == set(cfg["reduced"])
        assert set(cell["limits"]["limits"]) >= {"leaf_rows", "leaf_value_median"}
    for x in m["per_layer"]:
        assert x["moves"] in e2e
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}
    files = [c["file"] for c in m["configs"]]
    assert len(set(files)) == len(files)
    for f in files:
        assert f.startswith("benchmark/") and os.path.exists(
            os.path.join(manifest.ROOT, f))


def test_peaks_name_their_source():
    peaks = manifest.load_json("peaks.json")
    assert "Google Cloud" in peaks["_source"]
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
