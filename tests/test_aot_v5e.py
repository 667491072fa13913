"""Compiles for a TPU v5e that is described, not attached (libtpu's own
compiler, no chip): what the chip's compiler does with a program at the
benchmark's real size, which no CPU run shows. Nothing runs, so nothing
here is a time or a value. One file, and the topology only inside a
fixture: one process may load libtpu, and only a test of this file does."""
import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from lambdagap_tpu.objectives.rank import _lambdarank_bucket
from lambdagap_tpu.ops.partition import position_leaf

# instructions that move no data and so take no device time
FREE = re.compile(r"= \S+ (bitcast|parameter|constant|get-tuple-element|"
                  r"tuple)\(")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("n", [10_500_000, 3_408_630],
                         ids=["higgs", "istella-s"])
def test_position_leaf_keeps_its_scope_through_the_tpu_compiler(one_chip, n):
    """Every instruction of the compiled step that touches N positions
    carries the scope it was traced under: the device metrics select by
    that name, and XLA's own split of a long 1-D cumulative sum drops it."""
    leaves = jax.ShapeDtypeStruct((255,), jnp.int32, sharding=one_chip)

    def scoped(begin, count):
        with jax.named_scope("row_leaf"):
            return position_leaf(begin, count, n)

    compiled = jax.jit(scoped).lower(leaves, leaves).compile()
    text = compiled.as_text()
    nameless = [
        line.strip()[:120] for line in text[text.index("ENTRY"):].splitlines()
        if re.search(r"= \(?s32\[\d{5,}", line) and not FREE.search(line)
        and "/row_leaf/" not in line]
    assert nameless == []
    # and it streams: no temporary beyond a few N-vectors
    assert compiled.memory_analysis().temp_size_in_bytes <= 4 * 4 * n


@functools.lru_cache(maxsize=None)
def _istella_bucket(one_chip, L):
    """``istella-s``'s bucket of padded length ``L`` (the configuration's
    fixed multiset of query lengths), target ``ndcg``, truncation 30,
    sigmoid 1, norm: its query count and the bucket program compiled for a
    v5e, as text."""
    from benchmark import manifest
    from benchmark.datagen.mslr_like import query_lengths
    cfg = manifest.load_json("configs", "istella-s.json")
    sizes = query_lengths(cfg["num_queries"], cfg["num_docs"],
                          cfg["assumed"]["query_length_median"],
                          cfg["assumed"]["query_length_longest"])
    nq = int(np.sum((sizes > L // 2) & (sizes <= L)))
    obj = cfg["objective_params"]

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _lambdarank_bucket.lower(
        arg((nq, L)), arg((nq, L)), arg((nq, L), jnp.bool_), arg((nq,)),
        arg((nq,)), arg((32,)), target=obj["target"],
        sigmoid=obj["sigmoid"], norm=obj["norm"],
        truncation_level=obj["truncation_level"], lambdagap_weight=1.0,
        tile=None).compile().as_text()
    return nq, text


@pytest.mark.parametrize("L", [1024, 2048])
def test_no_gain_lookup_per_pair_cell_survives_the_tpu_compiler(one_chip, L):
    """``istella-s``'s two longest buckets (``[51, 1024]`` and ``[1, 2048]``):
    the compiled bucket kernel holds no gather of a pair block's size. A
    lookup of ``label_gain`` per pair cell compiled to two stand-alone
    ``f32[53477376]`` gathers in the 1,024-bucket (19 % of the cell's
    iteration); per document it is one of ``nq x L``."""
    nq, text = _istella_bucket(one_chip, L)
    assert nq == {1024: 51, 2048: 1}[L]
    gathers = [math.prod(int(d) for d in m.group(1).split(",") if d)
               for m in re.finditer(r"= \w+\[([\d,]*)\]\S* gather\(", text)]
    # what is left: the per-document lookup, nq x L (the one-query bucket's
    # XLA turns into selects by itself); the sorts gather nothing
    assert len(gathers) == {1024: 1, 2048: 0}[L], gathers
    assert max(gathers, default=0) <= nq * L < L * L


@pytest.mark.parametrize("L", [128, 2048])
def test_the_bucket_programs_document_arrays_keep_their_rank_scopes(
        one_chip, L):
    """Every instruction of the compiled bucket program that moves ``[nq,
    L]`` elements or more carries one of the ``rank_*`` scopes it was
    traced under: ``rank_sort_device_ms`` reads the sort into score order
    and the one back by the name ``rank_sort``, and an instruction the
    compiler made without a name would fall to no metric. 128 is the
    bucket with the most documents (many short queries), 2,048 the one
    with a single query."""
    nq, text = _istella_bucket(one_chip, L)
    assert nq > {128: 5000, 2048: 0}[L]
    assert re.search(r"sort\(.*/rank_sort/", text)
    assert _unnamed(text, "rank_", nq * L) == []


@pytest.mark.parametrize("L", [128, 2048])
def test_the_bucket_goes_into_score_order_and_back_without_a_gather(
        one_chip, L):
    """``istella-s``'s 128- and 2,048-buckets: under ``rank_sort`` the
    compiled bucket program holds two sorts, each carrying its data with
    the key, and no gather of ``nq x L`` elements or more. An argsort a
    query with three gathers into score order and two back compiled to five
    ``[nq, L]`` gathers of one index a row: ~12.6 ns an element on a v5e,
    307.9 of ``istella-s-train``'s ~1,740 device ms an iteration, against
    ~0.4 ns for the sorts that made the indices."""
    nq, text = _istella_bucket(one_chip, L)
    sorted_gathers = [
        line.strip()[:140] for line in text.splitlines()
        if "/rank_sort/" in line and (m := re.search(
            r"= \(?\w+\[([\d,]*)\]\S* gather\(", line))
        and _elements(m.group(1) or "1") >= nq * L]
    assert sorted_gathers == []
    assert len(re.findall(r" sort\(.*/rank_sort/", text)) == 2


def _instructions(text):
    """(is ENTRY, line) of every instruction of the compiled program's own
    computations (not of a fusion's body: a trace shows the fusion)."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    comp = entry = None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%([\w.\-]+) \(", line)
        if head:
            entry, comp = bool(head.group(1)), head.group(2)
        elif comp not in fused:
            yield entry, line


def _elements(dims: str) -> int:
    return math.prod(int(d) for d in dims.split(","))


def _unnamed(text, scope, least):
    """Instructions that produce ``least`` elements or more and do not
    carry ``scope`` in their name."""
    bad = []
    for _, line in _instructions(text):
        m = re.search(r"= \(?\w+\[([\d,]+)\]", line)
        if not m or FREE.search(line) \
                or re.search(r" (copy-start|copy-done|slice-start|"
                             r"slice-done|while|iota|tuple)\(|ConcatBitcast",
                             line):
            # (the compiler's own prefetches between memory spaces, the
            # loop itself, a tuple of results: nothing a selector would
            # attribute)
            continue
        if _elements(m.group(1)) >= least and f"/{scope}" not in line:
            bad.append(line.strip()[:140])
    return bad


def test_the_evaluation_programs_compile_at_the_watched_folds_shapes(one_chip):
    """``istella-s-valid``'s validation fold (7,211 queries of the
    configuration's fixed multiset of lengths, ~744k documents x 220 uint8
    bins, 255 leaves): the routing program and the NDCG@10 program compile
    for a v5e, every instruction of either that touches a fold-sized array
    carries the scope its metric selects by, the routing holds no gather
    or sort and streams (temporaries of a few row blocks), the metric
    sorts once a bucket."""
    from benchmark import manifest
    from benchmark.datagen.mslr_like import query_lengths
    from lambdagap_tpu.metrics.rank import _ndcg_at
    from lambdagap_tpu.models.gbdt import _valid_tree_score
    from lambdagap_tpu.ops.predict import ROUTE_BLOCK, RoutingTree
    cfg = manifest.load_json("configs", "istella-s-valid.json")
    sizes = query_lengths(cfg["num_queries"], cfg["num_docs"],
                          cfg["assumed"]["query_length_median"],
                          cfg["assumed"]["query_length_longest"])
    fold = sizes[np.random.default_rng(0).permutation(len(sizes))[
        :cfg["folds"]["valid"]]]
    n, f = int(fold.sum()), int(cfg["num_features"])
    leaves = int(cfg["params"]["num_leaves"])

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    m = leaves - 1
    tree = RoutingTree(
        arg((m,), jnp.int32), arg((m,), jnp.int32), arg((m,), jnp.bool_),
        arg((m,), jnp.bool_), arg((m, 8), jnp.uint32), arg((m,), jnp.int32),
        arg((m,), jnp.int32), arg((), jnp.int32))
    routed = _valid_tree_score.lower(
        arg((1, n)), arg((n, f), jnp.uint8), tree, arg((leaves,)),
        arg((f,), jnp.int32), arg((f,), jnp.int32), arg((f,), jnp.int32),
        k=0, has_categorical=False).compile()
    text = routed.as_text()
    assert "jit__valid_tree_score" in text[:200]
    assert _unnamed(text, "valid_score", ROUTE_BLOCK) == []
    assert not re.search(r" (gather|sort|scatter)\(.*\[\d{5,}", text)
    assert routed.memory_analysis().temp_size_in_bytes \
        <= 8 * 4 * ROUTE_BLOCK * leaves

    padded = np.maximum(8, 2 ** np.ceil(np.log2(fold)).astype(np.int64))
    buckets = tuple(
        (arg((nq, L), jnp.int32), arg((nq, L)), arg((nq, 1)))
        for L, nq in zip(*np.unique(padded, return_counts=True)))
    metric = _ndcg_at.lower(arg((n,)), buckets, arg((10,)), arg((1,)),
                            arg(()), ks=(10,)).compile()
    text = metric.as_text()
    assert "jit__ndcg_at" in text[:200]
    assert _unnamed(text, "valid_metric", 1 << 17) == []
    assert len(re.findall(r" sort\(", text)) == len(buckets)


# instructions that name a buffer and move none of it, or a window of it
WINDOWED = {"dynamic-update-slice", "while", "parameter",
            "get-tuple-element", "tuple", "bitcast"}


def _loop_results(text: str):
    """(opcode, element count of each array of the result, line) of every
    instruction of the compiled program's loops (every computation but
    ``ENTRY``); a tuple result counts each of its arrays."""
    for entry, line in _instructions(text):
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(",
                     line)
        if not entry and m:
            yield (m.group(2), [_elements(dims) for dims in
                                re.findall(r"\w+\[([\d,]+)\]", m.group(1))],
                   line.strip()[:160])


def _payload_producers(text: str, elements: int):
    """Instructions of the compiled program's loops whose result, or any
    element of a tuple result, is an array of ``elements`` elements or
    more, whatever the instruction: a copy, a transpose, a fusion of one
    output or several, an asynchronous copy's tuple. An in-place
    ``dynamic-update-slice`` names the whole buffer too and writes a window
    of it, and a loop, its parameter and the tuples around it carry it:
    ``WINDOWED`` are the only names let through."""
    return [line for op, sizes, line in _loop_results(text)
            if op not in WINDOWED and max(sizes, default=0) >= elements]


@pytest.fixture(scope="module", params=[
    (220, 3_408_630, 8_192), (28, 10_500_000, 32_768)],
    ids=["istella-s-57-words", "higgs-9-words"])
def tree_program(request, one_chip):
    """The tree program compiled for a v5e at one of two cells' shapes (255
    leaves, 255 bins, the Pallas kernel, ``tree_layout=sorted``; a learner
    built on 4,096 rows and lowered at the cell's N and W), once for every
    test of this file that reads it: a compile takes ~20 s at 28 features
    and ~40 s at 220."""
    import lambdagap_tpu as lgb
    from lambdagap_tpu.ops import hist_pallas
    features, n, window = request.param
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        mp.setattr(hist_pallas, "_interpret", lambda: False)
        rng = np.random.default_rng(0)
        X = rng.normal(size=(4096, features)).astype(np.float32)
        params = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
                  "tree_layout": "sorted", "tpu_fused_learner": 1,
                  "tpu_hist_impl": "pallas", "enable_bundle": False,
                  "verbose": -1}
        learner = lgb.Booster(params, lgb.Dataset(
            X, label=(X[:, 0] > 0).astype(np.float32),
            params=params))._booster.learner
        assert (type(learner).__name__, learner.layout, learner.hist_impl) \
            == ("FusedTreeLearner", "sorted", "pallas")
        learner.chunk = window      # the cell's own W (_pick_chunk at its N)

        def arg(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        x_rows, q = arg((n, features), jnp.uint8), arg((1,), jnp.int8)
        srows = jax.eval_shape(
            lambda *a: learner._build_sorted_impl(*a, has_mask=False),
            arg((n,)), arg((n,)), arg((1,), jnp.bool_), x_rows, q, q)
        text = learner._train_jit.lower(
            arg((n,)), arg((n,)), arg((1,), jnp.bool_),
            arg((features,), jnp.bool_), x_rows, arg((1, 1), jnp.uint8),
            arg(srows.shape, srows.dtype), q, q, arg(()), arg(()),
            arg((2, 2), jnp.uint32), has_mask=False).compile().as_text()
    assert "jit__train_tree_impl" in text[:200] and "lg_hist" in text
    return {"features": features, "n": n, "window": window,
            "srows": srows.shape, "text": text,
            # the carried histogram state, [L + 1, C, Bb, 3]
            "hist": (255 + 1) * features * learner.Bb * 3}


def test_the_split_loop_moves_the_sorted_payload_by_windows_alone(
        tree_program):
    """The tree program at the two cells' shapes: outside ``ENTRY`` no
    instruction produces an array as large as the sorted payload. Held as
    ``[N + W, 57]`` the split loop's carry and the copy-back loop were
    row-major, the partition and histogram loops N-minor, and the compiler
    converted all 779 MB between them twice a split (``%copy.135``,
    ``%copy.136``: 1,940 of ``istella-s-train``'s 3,990 device ms on a v5e);
    at 9 words it never did. The payload is word-major since
    (``_build_sorted_impl``)."""
    features, n, window = (tree_program[k] for k in ("features", "n",
                                                     "window"))
    words = -(-(features + 8) // 4)
    assert sorted(tree_program["srows"]) == [words, n + window]
    assert _payload_producers(tree_program["text"],
                              words * (n + window)) == []


def _whole_copies(text: str, elements: int):
    """``copy``, ``copy-start`` and ``transpose`` instructions of the
    compiled program's loops whose result, or an element of a tuple
    result, is an array of exactly ``elements`` elements."""
    return [line for op, sizes, line in _loop_results(text)
            if op in ("copy", "copy-start", "transpose") and elements in sizes]


def test_the_split_loop_writes_the_childrens_histograms_in_place(
        tree_program):
    """The tree program at the two cells' shapes: outside ``ENTRY`` no copy
    or transpose produces an array of the carried histogram state's size,
    ``[L + 1, C, Bb, 3]``. Written as two ``.at[].set`` of children that
    each read the parent's slice, the second write read the loop's
    incoming carry after the first had changed it, and the compiler copied
    the whole state twice a split to keep that carry intact (``%copy.161``
    and ``%copy.213``, 173 MB each at 220 features: 273.5 of
    ``istella-s-train``'s 2,041 device ms on a v5e; ``%copy.255`` and
    ``%copy.307`` at 28). ``ops.histogram.write_children`` reads the parent
    once, before either write."""
    assert _whole_copies(tree_program["text"], tree_program["hist"]) == []


def test_the_gather_tree_program_at_2000_features_writes_its_state_in_place(
        one_chip):
    """``epsilon-train``'s tree program (400,000 x 2,000 u8 rows, W 4,096,
    255 leaves, the Pallas kernel, ``tree_layout=gather``; a learner built on
    4,096 rows and lowered at the cell's N): the kernel runs 16 feature
    tiles a window, and outside ``ENTRY`` no copy or transpose produces the
    1.57 GB histogram state ``[256, 2000, 256, 3]`` or the packed rows
    ``[400000, 502]``: the split loop writes both children in place at this
    size too, and gathers a window's rows without a copy of the matrix."""
    import lambdagap_tpu as lgb
    from lambdagap_tpu.ops import hist_pallas
    features, n = 2000, 400_000
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        mp.setattr(hist_pallas, "_interpret", lambda: False)
        X = np.random.default_rng(0).normal(size=(4096, features)).astype(
            np.float32)
        params = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
                  "min_data_in_leaf": 1, "tpu_fused_learner": 1,
                  "tpu_hist_impl": "pallas", "verbose": -1}
        learner = lgb.Booster(params, lgb.Dataset(
            X, label=(X[:, 0] > 0).astype(np.float32),
            params=params))._booster.learner
        assert (learner.layout, learner.hist_impl, learner.Bb) \
            == ("gather", "pallas", 256)
        learner.num_data = n
        learner.chunk = learner._pick_chunk()
        assert learner.chunk == 4096

        def arg(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        q = arg((1,), jnp.int8)
        compiled = learner._train_jit.lower(
            arg((n,)), arg((n,)), arg((1,), jnp.bool_),
            arg((features,), jnp.bool_), arg((n, features), jnp.uint8),
            arg((features, n), jnp.uint8), arg((1, 1), jnp.uint32), q, q,
            arg(()), arg(()), arg((2, 2), jnp.uint32),
            has_mask=False).compile()
    text = compiled.as_text()
    assert hist_pallas.row_tiles(4096, features, 256) == 4096 * 16
    assert "lg_hist" in text
    hist = 256 * features * 256 * 3
    assert _whole_copies(text, hist) == []
    assert _whole_copies(text, n * (features + 8) // 4) == []
    # live: the two binned matrices; temporaries: the state, the packed
    # rows and the repack's widening (PERF.md section 7, 25 iii)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 1.7e9
    assert mem.temp_size_in_bytes < 7e9
