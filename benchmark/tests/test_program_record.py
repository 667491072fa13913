"""The reader of the program's own iteration records
(``readers/program_record.py``) and the metrics that read it: set-up's
fresh compiles and cache loads, as the window's first record carries them,
and nothing where a record does not."""
import pytest

from benchmark import manifest
from benchmark.readers import program_record

NAMES = ("compile_fresh_s", "compile_load_s")


def metric(name):
    return manifest.load_json("layer_metrics", name + ".json")


def record(fresh_secs, load_secs, run_fresh, run_load, **extra):
    compiles = {"total": 1, "steady": 0, "secs": fresh_secs + load_secs,
                "fresh": 1, "fresh_secs": fresh_secs, "loaded": 0,
                "load_secs": load_secs,
                "run": {"total": 40, "secs": run_fresh + run_load,
                        "fresh": 3, "fresh_secs": run_fresh, "loaded": 37,
                        "load_secs": run_load, "fresh_by_program": {},
                        "loaded_by_program": {}}}
    compiles.update(extra)
    return {"type": "iteration", "iter": 2, "wall_s": 1.0, "phases": {},
            "compiles": compiles, "transfers": {"total": 0}}


def test_before_window_is_the_first_records_run_less_its_own():
    view = {"records": [record(0.5, 0.25, 30.5, 12.25),
                        record(0.0, 0.0, 99.0, 99.0)]}
    assert program_record.read(metric("compile_fresh_s"), view) \
        == pytest.approx(30.0)
    assert program_record.read(metric("compile_load_s"), view) \
        == pytest.approx(12.0)


def test_a_window_that_compiled_nothing_reads_the_runs_totals():
    view = {"records": [record(0.0, 0.0, 21.5, 0.0)]}
    assert program_record.read(metric("compile_fresh_s"), view) == 21.5
    assert program_record.read(metric("compile_load_s"), view) == 0.0


@pytest.mark.parametrize("records", [
    [], None,
    [{"type": "iteration", "iter": 2}],
    [{"type": "iteration", "compiles": {"total": 1, "steady": 0,
                                        "secs": 0.1}}],
    [record(0.5, 0.25, 30.5, 12.25, run={"total": 40, "secs": 1.0})],
], ids=["no_records", "none", "no_compiles", "a_parents_record",
        "a_run_without_the_field"])
@pytest.mark.parametrize("name", NAMES)
def test_a_record_without_the_keys_reads_nothing(records, name):
    got = program_record.read(metric(name), {"records": records})
    assert got is None


def test_an_unknown_reduction_is_refused():
    with pytest.raises(ValueError):
        program_record.read({"reduction": "per_iteration",
                             "field": "fresh_secs"}, {"records": []})


def test_every_cell_reports_both_and_the_ranking_cells_the_sort():
    for w in manifest.manifest()["workloads"]:
        names = {m["name"] for m in manifest.cell(w["name"])["per_layer"]}
        assert set(NAMES) <= names, w["name"]
        rank = {"rank_sort_device_ms", "rank_sort_ns_per_pad_doc"}
        assert (rank <= names) == w["config"].startswith("istella-s"), \
            w["name"]


def test_the_sorts_metrics_select_what_the_lattices_select_but_the_scope():
    for name, like in (("rank_sort_device_ms", "rank_lattice_device_ms"),
                       ("rank_sort_ns_per_pad_doc", "rank_ns_per_pair_cell")):
        got, ref = metric(name), metric(like)
        assert got["select"]["module"] == ref["select"]["module"]
        assert got["select"]["scope"] == \
            ref["select"]["scope"].replace("rank_lattice", "rank_sort")
        assert {k: v for k, v in got.items() if k not in ("select", "count")} \
            == {k: v for k, v in ref.items() if k not in ("select", "count")}
    assert metric("rank_sort_ns_per_pad_doc")["count"] == "rank_pad_docs"


def test_the_sort_tiles_the_gradient_program_with_its_three_neighbours():
    """Hand-made ops of the ranking gradient program, one traced
    iteration: ``rank_sort_device_ms`` is ``grad_device_ms`` less the
    lattice and ``rank_scatter_device_ms`` (which reads ``rank_gather``
    too), and ``rank_sort_ns_per_pad_doc`` its time over the record's
    padded documents."""
    from benchmark import trace_reduce
    from benchmark.readers import device_trace, trace_counts
    from benchmark.tests.test_trace_reduce import ev
    D, H = "/device:TPU:0", "/host:CPU"
    path = "jit(loop)/gradients/"
    ops = [("rank_gather/gather", 120), ("rank_sort/sort", 300),
           ("jit(_lambdarank_bucket)/rank_sort/gather", 200),
           ("jit(_lambdarank_bucket)/rank_lattice/fusion", 250),
           ("jit(_lambdarank_bucket)/rank_sort/sort", 100),
           ("rank_scatter/scatter-add", 30)]
    events = [ev(H, "python", "lg_iteration", 0, 2000),
              ev(D, "XLA Modules", "jit_loop(3)", 0, 2000)]
    t = 0
    for i, (scope, dur) in enumerate(ops):
        events.append(ev(D, "XLA Ops", f"%op.{i}", t, dur, program_id=3,
                         tf_op=path + scope))
        t += dur
    view = {"reduced": trace_reduce.reduce(events), "traced_iterations": 1,
            "records": [{"counts": {"rank_pad_docs": 50}}]}
    got = {name: device_trace.read(metric(name), view) for name in (
        "grad_device_ms", "rank_lattice_device_ms", "rank_scatter_device_ms",
        "rank_sort_device_ms")}
    assert got["rank_sort_device_ms"] == pytest.approx(600e-6)
    assert got["rank_sort_device_ms"] == pytest.approx(
        got["grad_device_ms"] - got["rank_lattice_device_ms"]
        - got["rank_scatter_device_ms"])
    assert trace_counts.read(metric("rank_sort_ns_per_pad_doc"), view) \
        == pytest.approx(600 / 50)
    # a record without the count reads nothing
    assert trace_counts.read(metric("rank_sort_ns_per_pad_doc"),
                             dict(view, records=[{"counts": {}}])) is None
