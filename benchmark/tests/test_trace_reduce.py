"""The trace reduction on a small trace recorded on a TPU v5e (one boosting
iteration, 2^20 x 28, 31 leaves; my chip run, PR 25) and on hand-made
events."""
import gzip
import os
import re
import shutil

import pytest

from benchmark import manifest, trace_reduce, xplane

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    with gzip.open(os.path.join(HERE, "data", "small_v5e.xplane.pb.gz")) as f, \
            open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    return trace_reduce.reduce(xplane.read(str(path)))


def test_recorded_trace_busy_time_is_the_sum_of_self_times(reduced):
    assert reduced["chips"] == 1 and len(reduced["ops"]) == 13240
    assert 0.59 < reduced["busy_s"] < 0.60 < reduced["window_s"] < 0.61
    total = sum(op["self_s"] for op in reduced["ops"])
    assert total == pytest.approx(reduced["busy_s"], rel=1e-3)
    assert all(op["self_s"] >= -1e-9 for op in reduced["ops"])


def test_recorded_trace_layer_metrics(reduced):
    got = {m["name"]: trace_reduce.selected_seconds(reduced, m["select"])
           for m in manifest.cell("higgs-train")["per_layer"]
           if m.get("reduction") == "self_ms_per_iteration"}
    # the partition's scatter dominated that iteration; the kernel was 5 %
    assert got["tree_device_ms"] == pytest.approx(0.5953, abs=1e-3)
    assert got["partition_device_ms"] == pytest.approx(0.5578, abs=1e-3)
    assert got["hist_device_ms"] == pytest.approx(0.0284, abs=1e-3)
    assert 0 < got["grad_device_ms"] < 1e-3
    assert got["partition_device_ms"] + got["hist_device_ms"] \
        < got["tree_device_ms"] < reduced["busy_s"]
    bd = trace_reduce.breakdown(reduced, manifest.cell(
        "higgs-train")["per_layer"])
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][1][0] == "tree_device_ms"


def test_the_gradient_selector_finds_only_the_objectives_programs(reduced):
    select = manifest.load_json("layer_metrics",
                                "grad_device_ms.json")["select"]
    found = {re.sub(r"\(\d+\)$", "", op["module"]) for op in reduced["ops"]
             if trace_reduce._matches(op, select)}
    assert found == {"jit_fn"}
    # ``fn`` and ``loop`` are generic names: the selector stands only while
    # the program defines no other function of either name that it could
    # jit. One that does has to give grad_device_ms a tighter selector.
    named = set()
    root = os.path.join(manifest.ROOT, "lambdagap_tpu")
    for folder, _, files in os.walk(root):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    if re.search(r"^\s*def (fn|loop)\(", f.read(), re.M):
                        named.add(os.path.relpath(
                            os.path.join(folder, name), root))
    assert named == {"objectives/base.py", "objectives/rank.py"}


def ev(plane, line, name, start, dur, **stats):
    return {"plane": plane, "line": line, "name": name, "display": name,
            "start_ns": float(start), "dur_ns": float(dur), "stats": stats}


def test_hand_made_events_nesting_gaps_and_phases():
    D, H = "/device:TPU:0", "/host:CPU"
    events = [
        ev(H, "python", "lg_iteration", 0, 1000),
        ev(H, "python", "lg_phase:gradients", 0, 100),
        ev(H, "python", "lg_phase:tree", 100, 300),
        ev(D, "XLA Modules", "jit_prog(7)", 100, 700),
        # a while of 600 ns with two children of 200 ns: self 200
        ev(D, "XLA Ops", "%while", 100, 600, program_id=7, tf_op="jit(p)/while"),
        ev(D, "XLA Ops", "%a", 150, 200, program_id=7,
           tf_op="jit(p)/while/body/histogram/dot"),
        ev(D, "XLA Ops", "%b", 400, 200, program_id=7,
           tf_op="jit(p)/while/body/partition/scatter"),
    ]
    red = trace_reduce.reduce(events)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(600e-9)
    sel = trace_reduce.selected_seconds
    assert sel(red, {"scope": "(^|/)histogram(/|$)"}) == pytest.approx(200e-9)
    assert sel(red, {"module": r"^jit_prog\("}) == pytest.approx(600e-9)
    assert sel(red, {"any": [{"scope": "/partition/"},
                             {"scope": "/histogram/"}]}) == pytest.approx(400e-9)
    assert sel(red, {"module": "^jit_other"}) == 0.0
    gaps = {(g["phase"], round(g["seconds"] * 1e9)) for g in red["gaps"]}
    # idle before the program (host computing gradients) and after it
    assert gaps == {("gradients", 100), ("iteration_other", 300)}


def test_no_device_plane_reads_nothing():
    red = trace_reduce.reduce([ev("/host:CPU", "python", "lg_iteration", 0, 10)])
    assert red["ops"] == [] and red["busy_s"] == 0.0
    from benchmark.readers import device_trace
    assert device_trace.read({"reduction": "idle_pct"}, {"reduced": red}) is None
