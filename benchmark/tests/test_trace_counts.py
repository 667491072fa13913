"""The reader that sets device time against the program's work counts
(``readers/trace_counts.py``), on hand-made events and on a small trace
recorded on a TPU v5e with the program's scopes of PR 26 (one boosting
iteration, 2^20 x 28, 31 leaves; ``record_small_trace.py``; my chip run,
PR 26), and the selectors of the metrics that came with it."""
import gzip
import json
import os
import shutil

import pytest

from benchmark import manifest, trace_reduce, xplane
from benchmark.readers import trace_counts
from benchmark.tests.test_trace_reduce import ev

HERE = os.path.dirname(os.path.abspath(__file__))
D, H = "/device:TPU:0", "/host:CPU"
TREE = "jit__train_tree_impl(7)"
NEW_TRACE_METRICS = (
    "partition_pass_device_ms", "partition_scatter_device_ms",
    "partition_copyback_device_ms", "layout_device_ms",
    "split_scan_device_ms", "split_state_device_ms", "tree_fixed_device_ms",
    "hist_kernel_device_ms", "score_update_device_ms")


def metric(name):
    return manifest.load_json("layer_metrics", name + ".json")


def reduced(ops):
    """``ops``: (scope path under the tree program, ns) pairs, one after
    the other on one chip."""
    events = [ev(H, "python", "lg_iteration", 0, 10000),
              ev(D, "XLA Modules", TREE, 0, 10000)]
    t = 0
    for i, (scope, dur) in enumerate(ops):
        events.append(ev(D, "XLA Ops", f"%op.{i}", t, dur, program_id=7,
                         tf_op=scope))
        t += dur
    return trace_reduce.reduce(events)


def view(ops, records, traced=1):
    return {"reduced": reduced(ops), "records": records,
            "traced_iterations": traced}


P = "jit(_train_tree_impl)/while/body/closed_call/"
COUNTS = {"splits": 4, "partition_rows": 1000, "partition_trips": 10,
          "hist_rows": 500, "hist_trips": 5}


def test_time_per_count_on_hand_made_events():
    v = view([(P + "partition/while/body/partition_decide/cumsum:", 300),
              (P + "partition/while/body/partition_scatter/scatter:", 600),
              (P + "partition_copyback/while/body/select_n:", 100),
              (P + "histogram/while/body/jit(hist_pallas)/lg_hist/"
                   "pallas_call:", 250),
              (P + "split_scan/vmap()/reduce:", 40),
              (P + "split_state/scatter:", 30),
              (P + "leaf_select/argmax:", 20),
              (P + "hist_subtract/sub:", 10)],
             [{"counts": COUNTS}, {"counts": {"partition_rows": 10 ** 9}}])
    # (300 + 600 + 100) ns over 1000 rows, over 10 trips; the second
    # record is outside the traced iterations
    assert trace_counts.read(metric("partition_ns_per_row"), v) \
        == pytest.approx(1.0)
    assert trace_counts.read(metric("partition_us_per_trip"), v) \
        == pytest.approx(0.1)
    assert trace_counts.read(metric("hist_ns_per_row"), v) \
        == pytest.approx(0.5)
    assert trace_counts.read(metric("split_fixed_us"), v) \
        == pytest.approx(0.025)
    assert trace_counts.read(metric("tree_unscoped_pct"), v) == 0.0


@pytest.mark.parametrize("records", [
    [], [{}], [{"counts": {}}], [{"counts": dict(COUNTS, hist_rows=0)}],
    [{"counts": COUNTS}, {"phases": {}}]],
    ids=["no_record", "no_counts", "no_key", "zero", "second_traced_lacks"])
def test_a_missing_count_reads_nothing(records):
    v = view([(P + "histogram/while/body/dot:", 100)], records,
             traced=max(len(records), 1))
    assert trace_counts.read(metric("hist_ns_per_row"), v) is None


def test_a_missing_scope_reads_nothing():
    v = view([(P + "histogram/while/body/dot:", 100)], [{"counts": COUNTS}])
    assert trace_counts.read(metric("hist_ns_per_row"), v) \
        == pytest.approx(0.2)
    for name in ("partition_ns_per_row", "partition_us_per_trip",
                 "split_fixed_us"):
        assert trace_counts.read(metric(name), v) is None
    none = {"reduced": trace_reduce.reduce(
        [ev(H, "python", "lg_iteration", 0, 10)]), "records": [],
        "traced_iterations": 1}
    for name in ("tree_unscoped_pct", "hist_ns_per_row"):
        assert trace_counts.read(metric(name), none) is None
        assert trace_counts.read(metric(name), {"reduced": None}) is None


def test_unscoped_share():
    m = metric("tree_unscoped_pct")
    half = view([(P + "histogram/while/body/dot:", 500),
                 ("jit(_train_tree_impl)/while:", 300),
                 ("", 150), ("reduce_window_sum:", 50)], [])
    assert trace_counts.read(m, half) == pytest.approx(50.0)
    # a name that only starts like a scope is no scope; another program's
    # ops are not this metric's
    red = reduced([(P + "histogram_extra/dot:", 100),
                   (P + "jit(partition)/sort:", 100),
                   (P + "tree_init/iota:", 200)])
    red["ops"].append({"name": "%g", "module": "jit_gather(9)", "scope": "",
                       "self_s": 1.0})
    assert trace_counts.read(m, {"reduced": red}) == pytest.approx(50.0)
    assert set(m["scopes"]) >= {"histogram", "partition", "split_scan",
                                "partition_copyback", "tree_init"}


def test_the_vocabulary_is_the_programs():
    telemetry = pytest.importorskip("lambdagap_tpu.obs.telemetry")
    assert tuple(metric("tree_unscoped_pct")["scopes"]) \
        == telemetry.DEVICE_SCOPES


def test_nested_partition_scopes_and_the_old_selector():
    sel = {name: metric(name)["select"] for name in (
        "partition_device_ms", "partition_pass_device_ms",
        "partition_scatter_device_ms", "partition_copyback_device_ms")}
    red = reduced([(P + "partition/while/body/partition_scatter/scatter:",
                    1)])
    op, = red["ops"]
    assert trace_reduce._matches(op, sel["partition_device_ms"])
    assert trace_reduce._matches(op, sel["partition_pass_device_ms"])
    assert trace_reduce._matches(op, sel["partition_scatter_device_ms"])
    assert not trace_reduce._matches(op, sel["partition_copyback_device_ms"])
    op, = reduced([(P + "partition_copyback/while/body/select_n:", 1)])["ops"]
    assert trace_reduce._matches(op, sel["partition_device_ms"])
    assert trace_reduce._matches(op, sel["partition_copyback_device_ms"])
    assert not trace_reduce._matches(op, sel["partition_pass_device_ms"])
    assert not trace_reduce._matches(op, sel["partition_scatter_device_ms"])


def test_the_kernel_is_selected_by_name_or_by_its_scope():
    sel = metric("hist_kernel_device_ms")["select"]
    scope = P + "histogram/while/body/jit(hist_pallas)/"
    by_name = {"name": "%lg_hist.3 = f32[8,7168] custom-call(...)",
               "module": TREE, "scope": scope + "pallas_call:"}
    by_scope = {"name": "%custom-call.9", "module": TREE,
                "scope": scope + "lg_hist/pallas_call:"}
    feeding = {"name": "%fusion.1", "module": TREE,
               "scope": scope + "slice:"}
    assert trace_reduce._matches(by_name, sel)
    assert trace_reduce._matches(by_scope, sel)
    assert not trace_reduce._matches(feeding, sel)
    assert trace_reduce._matches(feeding, metric("hist_device_ms")["select"])


def test_new_metrics_keep_the_breakdowns_first_rows():
    """Ten rows: the four trace metrics the benchmark had, then the first
    six of PR 26 in BENCHMARK.json's order."""
    cell = manifest.cell("higgs-train")
    names = [m["name"] for m in cell["per_layer"]
             if m.get("reduction") == "self_ms_per_iteration"]
    assert names[:4] == ["grad_device_ms", "tree_device_ms",
                         "partition_device_ms", "hist_device_ms"]
    assert tuple(names[4:]) == NEW_TRACE_METRICS
    assert all(m["reader"] in ("device_trace", "trace_counts")
               for m in cell["per_layer"] if m["source"] == "device_trace")


# -- the recorded trace --------------------------------------------------
@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "scopes.xplane.pb"
    with gzip.open(os.path.join(HERE, "data",
                                "small_v5e_scopes.xplane.pb.gz")) as f, \
            open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    with open(os.path.join(HERE, "data", "small_v5e_scopes.json")) as f:
        meta = json.load(f)
    return {"reduced": trace_reduce.reduce(xplane.read(str(path))),
            "records": meta["records"], "traced_iterations": 1}, meta


def test_recorded_trace_new_trace_metrics_read_and_tile(recorded):
    view, meta = recorded
    assert meta["device"] == "TPU v5 lite" and meta["window"] == 32768
    from benchmark.readers import device_trace
    cell = manifest.cell("higgs-train")
    got = {m["name"]: device_trace.read(m, view) for m in cell["per_layer"]
           if m.get("reduction") == "self_ms_per_iteration"}
    for name in NEW_TRACE_METRICS:
        assert got[name] is not None and got[name] > 0, name
    # three selectors over the ops of one: the accepted metric's
    assert got["partition_pass_device_ms"] \
        + got["partition_copyback_device_ms"] + got["layout_device_ms"] \
        == pytest.approx(got["partition_device_ms"], rel=1e-6)
    assert got["partition_scatter_device_ms"] \
        < got["partition_pass_device_ms"]
    assert got["partition_scatter_device_ms"] \
        > 0.98 * got["partition_device_ms"]      # the scatter is the pass
    assert got["hist_kernel_device_ms"] == pytest.approx(21.23, abs=0.05)
    assert got["hist_kernel_device_ms"] < got["hist_device_ms"]
    # what is left of the tree program has names now, to 0.6 %
    named = sum(got[n] for n in (
        "partition_device_ms", "hist_device_ms", "split_scan_device_ms",
        "split_state_device_ms", "tree_fixed_device_ms"))
    assert 0.993 * got["tree_device_ms"] < named < got["tree_device_ms"]


def test_recorded_trace_ratios(recorded):
    view, meta = recorded
    counts = meta["records"][0]["counts"]
    assert counts == {"splits": 30, "partition_rows": 5432085,
                      "partition_trips": 182, "hist_rows": 2702587,
                      "hist_trips": 101}
    got = {m["name"]: trace_counts.read(m, view)
           for m in manifest.cell("higgs-train")["per_layer"]
           if m["reader"] == "trace_counts"}
    assert got["partition_ns_per_row"] == pytest.approx(118.67, abs=0.05)
    assert got["partition_us_per_trip"] == pytest.approx(3542.0, abs=1)
    assert got["hist_ns_per_row"] == pytest.approx(10.05, abs=0.02)
    assert got["split_fixed_us"] == pytest.approx(33.6, abs=0.1)
    assert 0 < got["tree_unscoped_pct"] < 1
    # without the records the trace alone gives no ratio
    bare = dict(view, records=[])
    assert trace_counts.read(metric("partition_ns_per_row"), bare) is None
    assert trace_counts.read(metric("tree_unscoped_pct"), bare) \
        == got["tree_unscoped_pct"]


def test_the_old_fixture_reads_no_new_scope():
    """PR 25's trace was recorded before the program had the new scopes:
    every metric that selects one reads nothing there, never 0; the ones
    that select what it had still read."""
    path = os.path.join(HERE, "data", "small_v5e.xplane.pb.gz")
    tmp = path[:-3] + ".tmp"
    try:
        with gzip.open(path) as f, open(tmp, "wb") as g:
            shutil.copyfileobj(f, g)
        view = {"reduced": trace_reduce.reduce(xplane.read(tmp)),
                "records": [], "traced_iterations": 1}
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    from benchmark.readers import device_trace
    got = {m["name"]: (device_trace if m["reader"] == "device_trace"
                       else trace_counts).read(m, view)
           for m in manifest.cell("higgs-train")["per_layer"]
           if m.get("reduction") in ("self_ms_per_iteration",
                                     "time_per_count", "unscoped_pct")}
    for name in ("partition_scatter_device_ms", "split_state_device_ms",
                 "tree_fixed_device_ms", "hist_kernel_device_ms",
                 "score_update_device_ms", "partition_ns_per_row",
                 "hist_ns_per_row", "split_fixed_us"):
        assert got[name] is None, name
    for name in ("partition_pass_device_ms", "partition_copyback_device_ms",
                 "layout_device_ms", "split_scan_device_ms"):
        assert got[name] > 0, name
    # its remainder had no name: 1.4 % of the tree and layout programs
    assert 1.0 < got["tree_unscoped_pct"] < 2.0
