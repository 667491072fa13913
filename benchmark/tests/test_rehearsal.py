"""Each cell end to end at its rehearsal size on the CPU (Pallas
interpreted), and ``correct`` seen to come out false: under the
lower-precision control, and with the timed path broken underneath -- a
step that leaves its state unchanged (in the middle of the window, and the
window's last), half of the batch left out, a split scan that misses the
best split, a score and a leaf altered where they are produced. These drive
``run.run_cell``: everything of a run but the look for a chip. A CPU run
says nothing about speed."""
import argparse

import pytest

from benchmark import manifest, run

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
# BENCHMARK.json's cells, and the shelved ones: proven on the chip, then
# taken out of BENCHMARK.json (too small for a cell), their files kept for
# the next cell of their kind -- rehearsed here so that those stay true
BENCHMARK = manifest.manifest()
SHELVED = manifest.load_json("tests", "data", "shelved_cells.json")
WITH_SHELVED = dict(
    BENCHMARK, configs=BENCHMARK["configs"] + SHELVED["configs"],
    workloads=BENCHMARK["workloads"] + SHELVED["workloads"])
CELLS = [w["name"] for w in WITH_SHELVED["workloads"]]


@pytest.fixture(autouse=True, scope="module")
def shelved_cells_resolve():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(manifest, "manifest", lambda: WITH_SHELVED)
        yield


WARMUP = manifest.load_json("traffic", "train_window.json")[
    "warmup_iterations"]


def drive(cell, seed=11, trace=0, control="", hooks=None, seconds=0.5):
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=trace, rehearse_cpu=True,
                              control=control)
    return run.run_cell(args, DEVICE, hooks)


def reading(result, name):
    return result["compared"][name][0]


@pytest.fixture(scope="module")
def sound():
    return {cell: drive(cell) for cell in CELLS}


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct_and_stamped(cell, sound):
    res = sound[cell]
    assert res["correct"] is True and res["rehearsal"] is True
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(res)[-1] == "compared"
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_iter_s", "setup_s"}
    assert reading(res, "leaf_rows") == 0
    for name, (value, limit) in res["compared"].items():
        assert limit is None or value <= limit, (name, value, limit)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_no_device_metric(cell):
    res = drive(cell, seed=12, trace=1)
    assert res["correct"] is True
    names = set(res["metrics"])
    assert {"construct_s", "compile_s", "window_compiles", "loop_host_ms",
            "iter_max_s"} <= names
    # no device plane on the CPU: the device-trace readers read nothing
    assert not names & {"hist_device_ms", "tree_device_ms", "hist_roofline",
                        "device_idle_pct", "grad_device_ms"}
    assert res["metrics"]["window_compiles"]["value"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_lower_precision_control_is_not_correct(cell, sound):
    res = drive(cell, control="bf16_hist")
    assert res["control"] == "bf16_hist" and res["correct"] is False
    for name in ("leaf_value_median", "leaf_hess_median"):
        assert reading(res, name) > res["compared"][name][1] \
            > 3 * reading(sound[cell], name)


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_batch_left_out_is_not_correct(cell):
    res = drive(cell, control="half_batch")
    assert res["correct"] is False
    assert reading(res, "leaf_rows") > 0


@pytest.mark.parametrize("cell", CELLS)
def test_a_split_scan_that_misses_the_best_split_is_not_correct(cell, sound):
    res = drive(cell, control="random_split")
    assert res["correct"] is False
    assert reading(res, "split_gap") > res["compared"]["split_gap"][1]
    # self-consistent otherwise: no other number sees it
    assert reading(res, "leaf_rows") == 0
    assert reading(res, "leaf_value") <= res["compared"]["leaf_value"][1]


LONG = 3


def long_drive(cell, **kw):
    """The usual window with LONG boosting iterations to a step: LONG times
    the usual run's trees, whatever the host's pace."""
    def update(bst):
        for _ in range(LONG):
            bst.update()
    res = drive(cell, hooks={"update": update}, **kw)
    assert reading(res, "trees_followed") \
        == LONG * (WARMUP + res["attempted"])
    return res


@pytest.mark.parametrize("cell", CELLS)
def test_a_long_rehearsal_is_correct(cell):
    # split_gap does not grow with the trees a window holds
    res = long_drive(cell)
    assert res["correct"] is True
    assert reading(res, "split_gap") <= 0.5 * res["compared"]["split_gap"][1]
    assert 0 <= reading(res, "split_gap_tree") < reading(res, "trees_followed")
    assert reading(res, "split_gap_node") in (0, 1, 2)


@pytest.mark.parametrize("cell", CELLS)
def test_a_long_window_does_not_hide_a_poor_split_scan(cell):
    res = long_drive(cell, control="random_split")
    assert res["correct"] is False and passes_all_but(res, "split_gap")
    assert reading(res, "split_gap") > 1.5 * res["compared"]["split_gap"][1]


def passes_all_but(res, name):
    return all(limit is None or value <= limit
               for other, (value, limit) in res["compared"].items()
               if other != name)


@pytest.mark.parametrize("cell", CELLS)
def test_a_window_step_that_leaves_its_state_unchanged_is_not_correct(cell):
    calls = {"n": 0}

    def update(bst):
        gb = bst._booster
        before = gb.scores
        bst.update()
        calls["n"] += 1
        if calls["n"] == WARMUP + 1:     # the window's first step is lost
            gb.scores = before
    # long enough for a second window step: its tree shows the stale scores
    res = drive(cell, hooks={"update": update}, seconds=8.0)
    assert res["attempted"] >= 2 and res["correct"] is False
    assert reading(res, "leaf_value_median") \
        > res["compared"]["leaf_value_median"][1]


@pytest.mark.parametrize("cell", CELLS)
def test_a_last_step_that_leaves_its_state_unchanged_is_not_correct(cell):
    kept = {}

    def update(bst):
        # every step's state is lost until the next step puts it back, so
        # at the end of the run only the LAST step's is: no later tree
        # shows it, the training scores do
        gb = bst._booster
        if kept:
            gb.scores = kept["true"]
        before = gb.scores
        bst.update()
        kept["true"] = gb.scores
        gb.scores = before
    res = drive(cell, hooks={"update": update})
    assert res["correct"] is False and passes_all_but(res, "train_score")
    assert reading(res, "train_score") > res["compared"]["train_score"][1]
    assert reading(res, "train_score") == pytest.approx(
        reading(res, "train_score_last_step"), rel=1e-3)


@pytest.mark.parametrize("cell", CELLS)
def test_a_score_altered_where_it_is_produced_is_not_correct(cell):
    def update(bst):
        bst.update()
        gb = bst._booster
        gb.scores = gb.scores.at[0, gb.scores.shape[1] // 2].add(0.01)
    res = drive(cell, hooks={"update": update})
    assert res["correct"] is False
    assert reading(res, "train_score") > res["compared"]["train_score"][1]


@pytest.mark.parametrize("cell", CELLS)
def test_a_leaf_altered_after_training_is_not_correct(cell):
    state = {"n": 0}

    def update(bst):
        bst.update()
        state["n"] += 1
        if state["n"] == 2:
            bst.set_leaf_output(1, 3, bst.get_leaf_output(1, 3) * 1.05)
    res = drive(cell, hooks={"update": update})
    assert res["correct"] is False
    assert reading(res, "leaf_value") > res["compared"]["leaf_value"][1]
