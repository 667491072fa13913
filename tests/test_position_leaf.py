"""``ops.partition.position_leaf``: the leaf of every position of a tree's
final partition, against a plain numpy statement of the chain it replaced
(``searchsorted`` of the positions in the sorted leaf begins). Integers, so
every element is held equal."""
import jax
import numpy as np
import pytest

from lambdagap_tpu.ops.partition import position_leaf

KINDS = ["random", "some_empty", "short_tree", "one_leaf", "leaf0_first"]


def searchsorted_chain(begin, count, n):
    """What the fused epilogue computed before: leaves with no rows are
    pushed past the end, the rest found by their begins."""
    leaves = len(begin)
    lb = np.where(count > 0, begin, n + np.arange(leaves))
    order = np.argsort(lb, kind="stable")
    which = np.searchsorted(lb[order], np.arange(n), side="right") - 1
    return order[which].astype(np.int32)


def partition(rng, n, leaves, kind):
    """(begin, count) of ``leaves`` leaf slots over ``n`` positions, as the
    tree program leaves them: real leaves tile ``[0, n)`` in some order of
    ids, a leaf that lost all its rows keeps a begin inside the range, a
    slot the tree never reached has begin ``n + id`` and count 0."""
    real = {"short_tree": max(1, leaves // 3), "one_leaf": 1}.get(kind,
                                                                  leaves)
    cuts = rng.integers(0, n + 1, real - 1)
    if kind == "some_empty" and real > 2:   # repeated cuts: leaves of 0 rows
        cuts = rng.choice(cuts[:real // 2], real - 1)
    cuts = np.sort(cuts)
    edges = np.concatenate([[0], cuts, [n]]).astype(np.int64)
    ids = rng.permutation(leaves)[:real]
    if kind == "leaf0_first":
        ids = np.concatenate([[0], ids[ids != 0]])[:real]
    elif kind == "one_leaf":
        ids = np.array([leaves - 1])
    begin = (n + np.arange(leaves)).astype(np.int32)
    count = np.zeros(leaves, np.int32)
    begin[ids] = edges[:-1]
    count[ids] = np.diff(edges)
    assert count.sum() == n
    return begin, count


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("leaves", [1, 2, 31, 255])
@pytest.mark.parametrize("n", [1, 7, 1000, 2 ** 16 + 3])
def test_position_leaf_equals_the_searchsorted_chain(n, leaves, kind):
    rng = np.random.default_rng([n, leaves, KINDS.index(kind)])
    fn = jax.jit(position_leaf, static_argnums=2)
    for _ in range(3):
        begin, count = partition(rng, n, leaves, kind)
        got = np.asarray(fn(begin, count, n))
        assert got.dtype == np.int32 and got.shape == (n,)
        np.testing.assert_array_equal(got,
                                      searchsorted_chain(begin, count, n))
