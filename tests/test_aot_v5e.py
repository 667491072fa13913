"""Compiles for a TPU v5e that is described, not attached (libtpu's own
compiler, no chip): what the chip's compiler does with a program at the
benchmark's real size, which no CPU run shows. Nothing runs, so nothing
here is a time or a value. One file, and the topology only inside a
fixture: one process may load libtpu, and only a test of this file does."""
import re

import math

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


@pytest.mark.parametrize("L", [1024, 2048])
def test_no_gain_lookup_per_pair_cell_survives_the_tpu_compiler(one_chip, L):
    """``istella-s``'s two longest buckets (``[51, 1024]`` and ``[1, 2048]``:
    the configuration's fixed multiset of query lengths), target ``ndcg``,
    truncation 30, sigmoid 1, norm: the compiled bucket kernel holds no
    gather of a pair block's size. A lookup of ``label_gain`` per pair cell
    compiled to two stand-alone ``f32[53477376]`` gathers in the
    1,024-bucket (19 % of the cell's iteration; PR 34); per document it is
    one of ``nq x L``."""
    from benchmark import manifest
    from benchmark.datagen.mslr_like import query_lengths
    cfg = manifest.load_json("configs", "istella-s.json")
    sizes = query_lengths(cfg["num_queries"], cfg["num_docs"],
                          cfg["assumed"]["query_length_median"],
                          cfg["assumed"]["query_length_longest"])
    nq = int(np.sum((sizes > L // 2) & (sizes <= L)))
    assert nq == {1024: 51, 2048: 1}[L]
    obj = cfg["objective_params"]

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _lambdarank_bucket.lower(
        arg((nq, L)), arg((nq, L)), arg((nq, L), jnp.bool_), arg((nq,)),
        arg((nq,)), arg((32,)), target=obj["target"],
        sigmoid=obj["sigmoid"], norm=obj["norm"],
        truncation_level=obj["truncation_level"], lambdagap_weight=1.0,
        tile=None).compile().as_text()
    gathers = [math.prod(int(d) for d in m.group(1).split(",") if d)
               for m in re.finditer(r"= \w+\[([\d,]*)\]\S* gather\(", text)]
    # what is left: the sort's own gathers and the per-document lookup (the
    # one-query bucket's XLA turns into selects by itself), nq x L each
    assert gathers and max(gathers) <= nq * L < L * L, gathers


def _unnamed(text, scope, least):
    """Instructions of the compiled program's own computations (not of a
    fusion's body: a trace shows the fusion) that produce ``least`` elements
    or more and do not carry ``scope`` in their name."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    bad, comp = [], None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            comp = head.group(1)
            continue
        m = re.search(r"= \(?\w+\[([\d,]+)\]", line)
        if comp in fused or not m or FREE.search(line) \
                or re.search(r" (copy-start|copy-done|slice-start|"
                             r"slice-done|while|iota)\(|ConcatBitcast",
                             line):
            # (the compiler's own prefetches between memory spaces, the
            # loop itself: nothing a selector would attribute)
            continue
        if math.prod(int(d) for d in m.group(1).split(",")) >= least \
                and f"/{scope}" not in line:
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
