"""BENCHMARK.json and the files its names resolve to. Nothing here names a
cell: a later PR adds ``configs/<name>.json``, ``traffic/<name>.json``,
``layer_metrics/<name>.json`` ... and an entry in BENCHMARK.json."""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str) -> dict:
    """One entry of ``workloads`` with its configuration, traffic mix,
    limits and metric entries resolved by name."""
    m = manifest()
    found = [w for w in m["workloads"] if w["name"] == name]
    if not found:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json "
                         f"(has {[w['name'] for w in m['workloads']]})")
    w = found[0]
    entry = next(c for c in m["configs"] if c["name"] == w["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)

    def reported(metric):
        return name in metric.get("workloads", [name])
    return {
        "name": name, "chips": int(w["chips"]), "config": config,
        "traffic": load_json("traffic", w["traffic"] + ".json"),
        "limits": load_json("limits", name + ".json"),
        "end_to_end": [x for x in m["end_to_end"] if reported(x)],
        "per_layer": [dict(x, **load_json("layer_metrics",
                                          x["name"] + ".json"))
                      for x in m["per_layer"] if reported(x)],
    }
