"""The device side of the tracing: the closed vocabulary of named scopes
tiles the hot programs (obs.telemetry.DEVICE_SCOPES), the histogram kernel
carries its name, and the iteration record's work counts are exact.
Counts and names only: nothing here reads a time."""
import collections
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lambdagap_tpu as lgb
from lambdagap_tpu.obs.telemetry import (DEVICE_SCOPES, EVAL_SCOPES,
                                         GRADIENT_SCOPES, device_scope)
from lambdagap_tpu.ops.hist_pallas import KERNEL_NAME

# ops that move values around and compute nothing: they may sit outside
# every scope (a constant carries the location of whatever first used it)
PLUMBING = {"func.func", "func.return", "func.call", "stablehlo.return",
            "stablehlo.constant", "stablehlo.tuple",
            "stablehlo.get_tuple_element", "stablehlo.while",
            "stablehlo.case", "stablehlo.if"}
# the split loop's own trip counter (``fori_loop`` is opened under no scope:
# a scope around it would become a path component of every op inside)
LOOP_COUNTER = re.compile(r"^jit\(\w+\)/while/(cond/lt|body/add)$")


def _loc_name(op) -> str:
    m = re.match(r'loc\("([^"]*)"', str(op.location))
    return m.group(1) if m else ""


def _leaf_scope(name: str, vocabulary=DEVICE_SCOPES):
    found = [c for c in name.split("/") if c in vocabulary]
    return found[-1] if found else None


def _ops_and_calls(lowered):
    """([(function, op kind, location name, op)] of every op, {callee:
    [(calling function, location name of the call)]}) of a lowered
    program."""
    ops, calls = [], collections.defaultdict(list)

    def walk(op, fn):
        for region in op.regions:
            for block in region.blocks:
                for child in block.operations:
                    kind, name = child.operation.name, _loc_name(child)
                    if kind == "func.func":
                        walk(child,
                             str(child.attributes["sym_name"]).strip('"'))
                        continue
                    if kind == "func.call":
                        callee = str(child.attributes["callee"]).lstrip("@")
                        calls[callee].append((fn, name))
                    ops.append((fn, kind, name, child))
                    walk(child, fn)

    walk(lowered.compiler_ir(dialect="stablehlo").operation, None)
    return ops, calls


def scope_census(lowered, vocabulary=DEVICE_SCOPES):
    """(ops per innermost scope of ``vocabulary``, [(function, op, location
    name)] of the ops under none) of a lowered program. An op of a private
    function (one ``cumsum`` or ``where`` body shared by its callers) counts
    as scoped when every call of that function is."""
    ops, calls = _ops_and_calls(lowered)
    memo = {}

    def by_call_site(fn) -> bool:
        if fn not in calls:
            return False
        if fn not in memo:
            memo[fn] = False
            memo[fn] = all(_leaf_scope(name, vocabulary) is not None
                           or by_call_site(caller)
                           for caller, name in calls[fn])
        return memo[fn]

    scoped, outside = collections.Counter(), []
    for fn, kind, name, _ in ops:
        scope = _leaf_scope(name, vocabulary)
        if scope is not None:
            scoped[scope] += 1
        elif kind not in PLUMBING and not by_call_site(fn) \
                and not LOOP_COUNTER.match(name):
            outside.append((fn, kind, name))
    return scoped, outside


def ops_under(lowered, scope: str):
    """[(op kind, element counts of its results)] of the ops under ``scope``
    as a path component, those of the private functions called from under
    it included."""
    ops, calls = _ops_and_calls(lowered)
    inside = set()
    grew = True
    while grew:
        grew = False
        for callee, sites in calls.items():
            if callee not in inside and any(
                    scope in name.split("/") or caller in inside
                    for caller, name in sites):
                inside.add(callee)
                grew = True
    return [(kind, [math.prod(r.type.shape) for r in op.results
                    if hasattr(r.type, "shape")])
            for fn, kind, name, op in ops
            if scope in name.split("/") or fn in inside]


def op_kinds_under(lowered, scope: str) -> collections.Counter:
    """Kinds of the ops ``ops_under`` finds."""
    return collections.Counter(kind for kind, _ in ops_under(lowered, scope))


def _learner(layout: str, hist: str = "onehot", rows: int = 600,
             leaves: int = 8):
    rng = np.random.default_rng(0)
    X = rng.normal(size=(rows, 5)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] ** 2 > 0.5).astype(np.float32)
    params = {"objective": "binary", "num_leaves": leaves, "max_bin": 15,
              "tree_layout": layout, "tpu_fused_learner": 1,
              "tpu_hist_impl": hist, "min_data_in_leaf": 1, "verbose": -1}
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    learner = bst._booster.learner
    assert (type(learner).__name__, learner.layout, learner.hist_impl) \
        == ("FusedTreeLearner", layout, hist)
    return bst, learner


def _lower_tree(learner, has_mask: bool):
    """The layout program (sorted only) and the tree program, lowered at the
    learner's own shapes."""
    n = learner.num_data
    grad, hess = jnp.zeros(n), jnp.ones(n)
    mask = jnp.ones(n if has_mask else 1, bool)
    q = jnp.zeros(1, jnp.int8)
    out = {}
    srows = learner._srows_dummy
    if learner.layout == "sorted":
        args = (grad, hess, mask, learner.hx_rows, q, q)
        out["layout"] = learner._layout_jit.lower(*args, has_mask=has_mask)
        srows = jax.eval_shape(
            lambda *a: learner._build_sorted_impl(*a, has_mask=has_mask),
            *args)
    out["tree"] = learner._train_jit.lower(
        grad, hess, mask, learner._feature_mask(), learner.hx_rows,
        learner.x_cols, srows, q, q, jnp.float32(1), jnp.float32(1),
        jnp.zeros((2, 2), jnp.uint32), has_mask=has_mask)
    return out


@pytest.mark.parametrize("has_mask", [False, True], ids=["all_rows", "mask"])
@pytest.mark.parametrize("layout", ["gather", "sorted"])
def test_every_op_of_the_tree_programs_is_under_one_scope(layout, has_mask):
    _, learner = _learner(layout)
    lowered = _lower_tree(learner, has_mask)
    scoped, outside = scope_census(lowered["tree"])
    assert outside == []
    # the four names the accepted metrics select, and this PR's
    assert set(scoped) >= {
        "histogram", "partition", "partition_copyback", "split_scan",
        "tree_init", "leaf_select", "partition_decide", "partition_scatter",
        "split_state", "hist_subtract", "row_leaf"}
    assert set(scoped) <= set(DEVICE_SCOPES)
    # the pass moves a window's rows by compaction and contiguous window
    # writes (PR 27): a scatter under ``partition`` is the regression
    moved = op_kinds_under(lowered["tree"], "partition")
    assert moved["stablehlo.dynamic_update_slice"] >= 2
    assert moved["stablehlo.scatter"] == 0, moved
    assert op_kinds_under(lowered["tree"], "row_leaf")["stablehlo.scatter"]
    if layout == "sorted":
        scoped, outside = scope_census(lowered["layout"])
        assert outside == [] and set(scoped) == {"layout_apply"}


@pytest.mark.parametrize("layout", ["gather", "sorted"])
def test_the_epilogue_reaches_no_row_by_a_gather(layout):
    """The epilogue finds each position's leaf by streaming
    (``ops.partition.position_leaf``, PR 32): the one N-row random access
    left under ``row_leaf`` is the scatter that carries positions to rows.
    An N-row gather (a round of ``searchsorted`` in the sorted leaf begins,
    a lookup of the leaf id in a table) is the regression."""
    _, learner = _learner(layout)
    n = learner.num_data
    rows = collections.Counter(
        kind for kind, sizes in ops_under(_lower_tree(learner, False)["tree"],
                                          "row_leaf")
        if n in sizes)
    assert rows["stablehlo.scatter"] >= 1, rows
    assert rows["stablehlo.gather"] == 0, rows


def test_scope_nesting_is_only_where_the_vocabulary_says():
    """``partition`` holds ``partition_decide`` and ``partition_scatter``;
    every other pair of scopes is disjoint, so selectors on a path
    component tile the program."""
    _, learner = _learner("sorted")
    module = _lower_tree(learner, False)["tree"].as_text(debug_info=True)
    pairs = set()
    for name in set(re.findall(r'loc\("([^"]*)"', module)):
        found = [c for c in name.split("/") if c in DEVICE_SCOPES]
        pairs |= {(a, b) for i, a in enumerate(found) for b in found[i + 1:]
                  if a != b}
    assert pairs == {("partition", "partition_decide"),
                     ("partition", "partition_scatter")}


def _lambdarank_loop(sizes, target="ndcg"):
    rng = np.random.default_rng(2)
    n = int(np.sum(sizes))
    X = rng.normal(size=(n, 3)).astype(np.float32)
    ds = lgb.Dataset(X, label=rng.integers(0, 4, n).astype(np.float32),
                     group=sizes)
    gb = lgb.Booster({"objective": "lambdarank", "verbose": -1,
                      "lambdarank_target": target}, ds)._booster
    gb.boosting()                           # builds the jitted program
    obj = gb.objective
    return obj._loop_jit.lower(
        gb.scores[0], obj.label, jnp.zeros(1, jnp.int32),
        jnp.zeros(1, jnp.float32), obj._next_key(), obj._loop_idxs,
        obj._loop_auxs)


@pytest.mark.parametrize("program", ["fn", "loop", "score_update"])
def test_gradient_and_score_programs_are_scoped(program):
    if program == "score_update":
        from lambdagap_tpu.models.gbdt import _score_update
        lowered = _score_update.lower(
            jnp.zeros((1, 64)), jnp.zeros(8), jnp.zeros(64, jnp.int32), k=0)
        want = "score_update"
    else:
        if program == "fn":
            rng = np.random.default_rng(1)
            X = rng.normal(size=(240, 4)).astype(np.float32)
            ds = lgb.Dataset(X, label=(X[:, 0] > 0).astype(np.float32))
            gb = lgb.Booster({"objective": "binary", "verbose": -1},
                             ds)._booster
            gb.boosting()                   # builds the jitted program
            obj = gb.objective
            lowered = obj._grad_jit.lower(gb.scores, *[
                getattr(obj, f) for f in obj._GRAD_ARRAY_FIELDS
                if getattr(obj, f, None) is not None])
        else:
            lowered = _lambdarank_loop([24] * 10)
        assert f"jit_{program}" in lowered.as_text()[:200]
        want = "gradients"
    scoped, outside = scope_census(lowered)
    assert outside == [] and set(scoped) == {want}


def _lower_eval_programs(categorical: bool):
    """``_valid_tree_score`` and ``_ndcg_at`` lowered at a watched fold's
    shapes: a ranking booster with one validation set, one iteration in."""
    from lambdagap_tpu.metrics.rank import _ndcg_at
    from lambdagap_tpu.models.gbdt import _valid_tree_score
    from lambdagap_tpu.ops.predict import RoutingTree
    rng = np.random.default_rng(3)

    def fold(sizes):
        n = int(np.sum(sizes))
        X = rng.normal(size=(n, 4)).astype(np.float32)
        X[:, 2] = rng.integers(0, 5, n)
        return X, rng.integers(0, 4, n).astype(np.float32), sizes
    params = {"objective": "lambdarank", "verbose": -1, "num_leaves": 8,
              "min_data_in_leaf": 2, "tpu_fused_learner": 1,
              "eval_at": [1, 10],
              "categorical_feature": [2] if categorical else []}
    X, y, g = fold([5, 12, 24, 40, 100] * 3)
    ds = lgb.Dataset(X, label=y, group=g, params=params)
    bst = lgb.Booster(params, ds)
    Xv, yv, gv = fold([3, 9, 30, 70])
    bst.add_valid(lgb.Dataset(Xv, label=yv, group=gv, reference=ds), "v")
    bst.update()
    gb = bst._booster
    rec = gb.models[-1].rec
    meta = [jnp.asarray(gb._meta[k]) for k in
            ("default_bins", "missing_types", "num_bins")]
    score = _valid_tree_score.lower(
        gb.valid_scores[0], gb.valid_binned[0],
        RoutingTree(*(getattr(rec, f) for f in RoutingTree._fields)),
        rec.leaf_value, *meta, k=0, has_categorical=categorical)
    ks, buckets, disc, ones, inv = gb.valid_metrics[0][0]._dev
    metric = _ndcg_at.lower(gb.valid_scores[0][0], buckets, disc, ones, inv,
                            ks=ks)
    return {"valid_score": score, "valid_metric": metric}


@pytest.mark.parametrize("categorical", [False, True],
                         ids=["numerical", "categorical"])
def test_every_op_of_the_evaluation_programs_is_under_one_eval_scope(
        categorical):
    """``EVAL_SCOPES`` tiles the two programs that run only with a watched
    set attached, one name a program, under programs of their own names
    (``grad_device_ms`` and ``tree_device_ms`` select theirs by name); the
    routing reaches no row by a gather, the metric sorts once a bucket."""
    lowered = _lower_eval_programs(categorical)
    for want, program in lowered.items():
        scoped, outside = scope_census(program, EVAL_SCOPES)
        assert outside == [] and set(scoped) == {want}
        assert not set(scope_census(program, DEVICE_SCOPES
                                    + GRADIENT_SCOPES)[0])
    assert "jit__valid_tree_score" in lowered["valid_score"].as_text()[:200]
    assert "jit__ndcg_at" in lowered["valid_metric"].as_text()[:200]
    kinds = op_kinds_under(lowered["valid_score"], "valid_score")
    assert kinds["stablehlo.dot_general"] >= 2
    assert kinds["stablehlo.sort"] == 0 and kinds["stablehlo.scatter"] <= 1
    n = 3 + 9 + 30 + 70
    rows = [k for k, sizes in ops_under(lowered["valid_score"],
                                        "valid_score")
            if k == "stablehlo.gather" and any(s >= n for s in sizes)]
    assert rows == []
    kinds = op_kinds_under(lowered["valid_metric"], "valid_metric")
    assert kinds["stablehlo.sort"] == 4 == kinds["stablehlo.gather"]


def test_the_evaluation_vocabulary_is_closed_and_apart():
    assert EVAL_SCOPES == ("valid_score", "valid_metric")
    assert not set(EVAL_SCOPES) & set(DEVICE_SCOPES + GRADIENT_SCOPES)
    with device_scope("valid_score"), device_scope("valid_metric"):
        pass
    with pytest.raises(ValueError):
        device_scope("valid_scores")


def test_the_ranking_gradient_program_is_tiled_by_its_inner_scopes():
    """Every op of the lambdarank gradient program carries ``gradients`` and
    at most one of GRADIENT_SCOPES, as a path component of its own name or of
    the calls that reach its function (a scope opened INSIDE a vmapped
    function would read ``vmap(<scope>)`` and match no selector)."""
    ops, calls = _ops_and_calls(_lambdarank_loop([5, 12, 24, 40, 100]))
    memo = {}

    def reached_under(fn):
        """Scope names on some call path from the program's entry to fn."""
        if fn not in memo:
            memo[fn] = set()
            for caller, name in calls.get(fn, []):
                memo[fn] |= set(name.split("/")) | reached_under(caller)
        return memo[fn]

    census = collections.Counter()
    for fn, kind, name, _ in ops:
        if kind in PLUMBING:
            continue
        comps = set(name.split("/")) | reached_under(fn)
        assert "gradients" in comps, (fn, kind, name)
        inner = comps & set(GRADIENT_SCOPES)
        assert len(inner) <= 1, (fn, kind, name)
        assert not any(c.startswith("vmap(rank_") for c in comps), name
        census[next(iter(inner), None)] += 1
    assert set(census) >= set(GRADIENT_SCOPES)
    assert census["rank_lattice"] > census["rank_sort"] > 0
    # the loop's own zero of the pair-rate sum is all that may sit outside
    assert census[None] <= 2, census
    kinds = {s: collections.Counter(
        kind for fn, kind, name, _ in ops
        if s in set(name.split("/")) | reached_under(fn))
        for s in GRADIENT_SCOPES}
    assert kinds["rank_scatter"]["stablehlo.scatter"] == 2 * 5   # 5 buckets
    assert kinds["rank_gather"]["stablehlo.gather"] == 2 * 5
    assert kinds["rank_sort"]["stablehlo.sort"] == 2 * 5
    # each sort carries its data with the key: nothing is gathered after
    assert kinds["rank_sort"]["stablehlo.gather"] == 0
    assert not kinds["rank_lattice"]["stablehlo.sort"] \
        and not kinds["rank_lattice"]["stablehlo.scatter"]


# the three targets whose pair weight reads the labels' gains
GAIN_TARGETS = ["ndcg", "lambdaloss-ndcg", "lambdaloss-ndcg-plus-plus"]


def _lower_bucket(target, nq, L, tile):
    """One bucket kernel alone, lowered at ``[nq, L]``."""
    from lambdagap_tpu.objectives.rank import _lambdarank_bucket
    f = jnp.zeros((nq, L), jnp.float32)
    return _lambdarank_bucket.lower(
        f, f, jnp.ones((nq, L), bool), jnp.ones(nq), jnp.ones(nq),
        jnp.arange(4, dtype=jnp.float32), target=target, sigmoid=1.0,
        norm=True, truncation_level=20, lambdagap_weight=0.5, tile=tile)


@pytest.mark.parametrize("form", ["dense", "tiled"])
@pytest.mark.parametrize("target", GAIN_TARGETS)
def test_the_lattice_looks_a_gain_up_once_a_document(target, form):
    """A label's gain is a property of a DOCUMENT: under ``rank_lattice``
    the one gather of a bucket is ``label_gain[ls]`` over its ``[nq, L]``
    sorted documents (PR 34). A lookup per pair cell (``[nq, L, L]``, two a
    bucket: the orientation done on the labels, not on the gains) is the
    regression: on the chip it compiled to stand-alone gathers that took
    19 % of ``istella-s-train``'s iteration."""
    if form == "dense":
        # three queries a bucket: nq x L (24 ... 384) is no bucket's L x L
        sizes = [5] * 3 + [12] * 3 + [24] * 3 + [40] * 3 + [100] * 3
        lowered = _lambdarank_loop(sizes, target)
        docs, slices = [3 * L for L in (8, 16, 32, 64, 128)], []
    else:
        # the row-block sweep, as test_rank's tiled-against-dense test
        # reaches it; under ``vmap`` each ``dynamic_slice`` of a row block
        # (scores, labels, gains, validity, the two row sums) is a gather of
        # nq x T elements
        nq, L, T = 3, 256, 64
        lowered = _lower_bucket(target, nq, L, T)
        docs, slices = [nq * L], [nq * T] * 6
    gathers = sorted(max(sizes) for kind, sizes
                     in ops_under(lowered, "rank_lattice")
                     if kind == "stablehlo.gather")
    # one lookup a bucket, of its nq x L documents: nothing of a pair
    # block's size (L x L dense, T x L tiled; 64 cells the least here)
    assert gathers == sorted(docs + slices), gathers


@pytest.mark.parametrize("target", sorted(
    set(lgb.config.LAMBDARANK_TARGETS) - set(GAIN_TARGETS)))
def test_a_target_that_reads_no_gain_carries_no_lookup(target):
    """The other fifteen targets never read ``gain_gap``: the per-document
    vector is dead code there and is dropped when the program is lowered,
    so their lattices hold no gather at all, as before PR 34."""
    lowered = _lower_bucket(target, 3, 64, None)
    assert not op_kinds_under(lowered, "rank_lattice")["stablehlo.gather"]


def test_the_vocabulary_is_closed():
    with pytest.raises(ValueError):
        device_scope("histogramm")
    with pytest.raises(ValueError):
        device_scope("rank_latice")
    assert len(set(DEVICE_SCOPES)) == len(DEVICE_SCOPES) == 15
    # the gradient program's inner scopes: a tuple of their own, accepted
    assert GRADIENT_SCOPES == ("rank_gather", "rank_sort", "rank_lattice",
                               "rank_scatter")
    assert not set(GRADIENT_SCOPES) & set(DEVICE_SCOPES)
    for name in GRADIENT_SCOPES:
        with device_scope(name):
            pass


def test_the_histogram_kernel_call_carries_its_name():
    """``name=`` on the one ``pallas_call``: the kernel is an op of its own
    name inside the ``histogram`` scope (``%lg_hist`` on the chip)."""
    assert KERNEL_NAME == "lg_hist"
    _, learner = _learner("sorted", hist="pallas", rows=256, leaves=4)
    module = _lower_tree(learner, False)["tree"].as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', module))
    # ``hist_pallas`` is a function of its own in the module: its ops are
    # named from ``lg_hist`` down, its one call sits under ``histogram``
    assert any(re.search(r"(^|/)lg_hist/pallas_call$", n) for n in names)
    calls = {n for n in names if n.endswith("/jit(hist_pallas)")}
    assert calls and all("histogram/while/body/" in n for n in calls)


# -- work counts --------------------------------------------------------
def _brute_force_counts(tree, binned, window: int) -> dict:
    """Rows and window trips of one tree's partition and histogram passes,
    counted by sending every training row down the host tree."""
    n = binned.shape[0]
    node_rows = {0: np.arange(n)}
    counts = collections.Counter(splits=tree.num_leaves - 1, hist_rows=n,
                                 hist_trips=-(-n // window))
    for k in range(tree.num_leaves - 1):      # nodes in order of creation
        rows = node_rows[k]
        left = binned[rows, tree.split_feature_inner[k]] \
            <= tree.threshold_bin[k]
        for child, side in ((tree.left_child[k], left),
                            (tree.right_child[k], ~left)):
            if child >= 0:
                node_rows[child] = rows[side]
        small = min(int(left.sum()), int((~left).sum()))
        counts["partition_rows"] += len(rows)
        counts["partition_trips"] += -(-len(rows) // window)
        counts["hist_rows"] += small
        counts["hist_trips"] += -(-small // window)
    return dict(counts)


@pytest.mark.parametrize("bagging", [False, True], ids=["all_rows", "bagged"])
def test_work_counts_equal_a_brute_force_count(bagging, monkeypatch):
    from lambdagap_tpu.models.fused_learner import FusedTreeLearner
    monkeypatch.setattr(FusedTreeLearner, "_pick_chunk",
                        lambda self: 1024)          # several trips a pass
    rng = np.random.default_rng(3)
    X = rng.normal(size=(5000, 6)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] + X[:, 2] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 20, "max_bin": 31,
              "tpu_fused_learner": 1, "tree_layout": "sorted",
              "enable_bundle": False, "min_data_in_leaf": 5,
              "telemetry": True, "verbose": -1}
    if bagging:
        params.update(bagging_fraction=0.5, bagging_freq=1)
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=3)
    gb = bst._booster
    window = gb.learner._window(5000)
    assert window == 1024
    records = list(gb.telemetry.records)
    assert [r["iter"] for r in records] == [0, 1, 2]
    binned = np.asarray(gb.train_set.binned)
    total = collections.Counter()
    for i, rec in enumerate(records):
        tree = gb._tree(i)
        assert tree.num_leaves == 20
        want = _brute_force_counts(tree, binned, window)
        assert rec["counts"] == want, i
        assert rec["counts"]["partition_trips"] > rec["counts"]["splits"]
        total.update(want)
    assert gb.telemetry.summary()["counts_total"] == dict(total)


def test_no_work_counts_and_no_read_with_telemetry_off(monkeypatch):
    from lambdagap_tpu.models.fused_learner import FusedTreeLearner
    reads = []
    monkeypatch.setattr(FusedTreeLearner, "work_counts",
                        lambda self, host: reads.append(host) or {})
    X = np.random.default_rng(4).normal(size=(400, 4)).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 6, "verbose": -1,
              "tpu_fused_learner": 1}
    bst = lgb.train(params, lgb.Dataset(X, label=(X[:, 0] > 0).astype(
        np.float32)), num_boost_round=2)
    tel = bst._booster.telemetry
    assert not tel.enabled and reads == [] and len(tel.records) == 0
    assert "counts_total" not in tel.summary()
    # the kept records hold neither the row map nor the work counts
    assert all(m.rec.work is None and m.rec.row_leaf is None
               for m in bst._booster.models if hasattr(m, "rec"))
