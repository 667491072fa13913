"""Batched tree traversal on device.

TPU analog of the reference's prediction paths: per-row inline traversal
(reference: include/LightGBM/tree.h:130-141 Predict/NumericalDecision) and the
binned-data traversal used for validation-score updates
(reference: tree.h AddPredictionToScore over the train/valid Dataset).

Trees are stacked into padded arrays and traversed with a bounded
``fori_loop`` (leaf-wise trees record their true max depth at build time);
rows are vectorized with ``vmap`` so the whole batch advances one level per
iteration — the same shape as the CUDA tree-predict kernel
(reference: src/io/cuda/cuda_tree.cu).

Whole forests are traversed in ONE jitted dispatch: trees are stacked along a
leading ``T`` axis and a ``lax.scan`` accumulates per-class scores without
materializing the ``[T, N]`` intermediate (the analog of ``GBDT::Predict``
iterating inlined trees, reference: src/boosting/gbdt_prediction.cpp).
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

K_ZERO_THRESHOLD = 1e-35
#: trees per dispatch of the blocked forest traversals (and of serve's
#: compiled forest): no single kernel grows with the forest
TREE_BLOCK = 64
MT_NONE, MT_ZERO, MT_NAN = 0, 1, 2


class TreeArrays(NamedTuple):
    """One tree in device-friendly form. M = padded internal-node count.
    When stacked into a forest, every field gains a leading T axis."""
    split_feature: jax.Array   # i32 [M] — feature index (original or inner)
    threshold: jax.Array       # f32 [M] raw threshold (numerical)
    threshold_bin: jax.Array   # i32 [M] bin threshold (numerical, binned data)
    default_left: jax.Array    # bool [M]
    missing_type: jax.Array    # i32 [M]
    default_bin: jax.Array     # i32 [M] (binned decisions, Zero-missing)
    num_bin: jax.Array         # i32 [M] (binned decisions, NaN-missing)
    left_child: jax.Array      # i32 [M]
    right_child: jax.Array     # i32 [M]
    is_categorical: jax.Array  # bool [M]
    cat_bitset: jax.Array      # u32 [M, 8] bin-space bitset
    cat_bitset_real: jax.Array  # u32 [M, W] raw-category bitset (W >= 8,
    #                              sized to the largest category; reference
    #                              sizes these dynamically via
    #                              Common::ConstructBitset, src/io/tree.cpp)
    leaf_value: jax.Array      # f32 [L]
    # piece-wise linear leaf payload (docs/linear-trees.md): constant term,
    # padded per-leaf feature ids (-1 = empty slot) and coefficients. For
    # constant trees leaf_const == leaf_value and every slot is empty, so
    # the linear traversal carry degenerates to the constant gather —
    # engines only read these under has_linear=True (raw rows only).
    leaf_const: jax.Array      # f32 [L]
    leaf_feat: jax.Array       # i32 [L, FL]
    leaf_coeff: jax.Array      # f32 [L, FL]


def tree_to_arrays(tree, feature_meta=None, use_inner_feature: bool = False,
                   pad_nodes: int = 0, pad_leaves: int = 0,
                   pad_cat_words: int = 0, pad_leaf_feats: int = 0) -> TreeArrays:
    """Stack a host Tree into TreeArrays.

    feature_meta: dict from BinnedDataset.feature_arrays() — required for
    binned traversal (default_bin / num_bin per node's feature).
    pad_nodes / pad_leaves / pad_cat_words / pad_leaf_feats: minimum padded
    sizes, used to align trees before stacking them into a forest.
    """
    n = max(tree.num_internal, 1)
    M = max(n, pad_nodes)

    def pad_i(vals, fill=0, dtype=np.int32):
        a = np.full(M, fill, dtype=dtype)
        a[:len(vals)] = vals
        return jnp.asarray(a)

    def pad_f(vals, fill=0.0):
        a = np.full(M, fill, dtype=np.float32)
        a[:len(vals)] = vals
        return jnp.asarray(a)

    feats = tree.split_feature_inner if use_inner_feature else tree.split_feature
    if tree.num_internal == 0:
        # degenerate single-leaf tree: both children point at leaf 0
        left = [~0]
        right = [~0]
        feats = [0]
    else:
        left = tree.left_child
        right = tree.right_child

    default_bin = np.zeros(M, dtype=np.int32)
    num_bin = np.zeros(M, dtype=np.int32)
    if feature_meta is not None:
        fi = np.asarray(tree.split_feature_inner[:tree.num_internal], dtype=np.int64)
        if len(fi):
            default_bin[:len(fi)] = feature_meta["default_bins"][fi]
            num_bin[:len(fi)] = feature_meta["num_bins"][fi]

    W = max(8, pad_cat_words,
            max((len(tree.cat_bitset_real[i]) for i in range(tree.num_internal)),
                default=0))
    bits = np.zeros((M, 8), dtype=np.uint32)
    bits_real = np.zeros((M, W), dtype=np.uint32)
    for i in range(tree.num_internal):
        bb = np.asarray(tree.cat_bitset[i], dtype=np.uint32)[:8]
        bits[i, :len(bb)] = bb
        br = np.asarray(tree.cat_bitset_real[i], dtype=np.uint32)
        bits_real[i, :len(br)] = br

    L = max(tree.num_leaves, 1, pad_leaves)
    leaf_value = np.zeros(L, dtype=np.float32)
    leaf_value[:max(tree.num_leaves, 1)] = \
        tree.leaf_value[:max(tree.num_leaves, 1)]
    # linear payload: constant trees carry leaf_const == leaf_value with
    # every slot empty, so a mixed (linear + constant) forest evaluates
    # uniformly under has_linear=True
    FL = max(1, pad_leaf_feats,
             max((len(tree.leaf_features[i]) for i in range(tree.num_leaves)),
                 default=0) if getattr(tree, "is_linear", False) else 0)
    leaf_const = leaf_value.copy()
    leaf_feat = np.full((L, FL), -1, dtype=np.int32)
    leaf_coeff = np.zeros((L, FL), dtype=np.float32)
    if getattr(tree, "is_linear", False):
        nl = tree.num_leaves
        leaf_const[:nl] = np.asarray(tree.leaf_const[:nl], np.float32)
        for i in range(nl):
            lfeats = tree.leaf_features[i]
            if lfeats:
                leaf_feat[i, :len(lfeats)] = lfeats
                leaf_coeff[i, :len(lfeats)] = np.asarray(tree.leaf_coeff[i],
                                                         np.float32)
    return TreeArrays(
        split_feature=pad_i(feats[:max(tree.num_internal, 1)]),
        threshold=pad_f(tree.threshold_real),
        threshold_bin=pad_i(tree.threshold_bin),
        default_left=pad_i(tree.default_left, dtype=bool),
        missing_type=pad_i(tree.missing_type),
        default_bin=jnp.asarray(default_bin),
        num_bin=jnp.asarray(num_bin),
        left_child=pad_i(left, fill=~0),
        right_child=pad_i(right, fill=~0),
        is_categorical=pad_i(tree.is_categorical, dtype=bool),
        cat_bitset=jnp.asarray(bits),
        cat_bitset_real=jnp.asarray(bits_real),
        leaf_value=jnp.asarray(leaf_value),
        leaf_const=jnp.asarray(leaf_const),
        leaf_feat=jnp.asarray(leaf_feat),
        leaf_coeff=jnp.asarray(leaf_coeff),
    )


def forest_to_arrays(trees, feature_meta=None,
                     use_inner_feature: bool = False
                     ) -> Tuple[TreeArrays, int]:
    """Stack host Trees into one TreeArrays with a leading T axis, padded to
    common node/leaf/bitset-width sizes (rounded up to bound jit retraces).
    Returns (stacked arrays, padded max_depth)."""
    assert trees, "forest_to_arrays needs at least one tree"

    def _round32(v: int) -> int:
        return max(32, ((v + 31) // 32) * 32)

    M = _round32(max(max(t.num_internal, 1) for t in trees))
    L = _round32(max(max(t.num_leaves, 1) for t in trees))
    W = max([8] + [len(t.cat_bitset_real[i]) for t in trees
                   for i in range(t.num_internal)])
    # linear leaf slots, rounded up so appended trees rarely change FL
    # (a new width re-stacks the forest, it never recompiles silently)
    FLr = max([0] + [len(t.leaf_features[i]) for t in trees
                     if getattr(t, "is_linear", False)
                     for i in range(t.num_leaves)])
    FL = max(1, ((FLr + 3) // 4) * 4) if FLr else 1
    depth = _round_depth(max(t.max_depth for t in trees) + 1)
    per_tree = [tree_to_arrays(t, feature_meta, use_inner_feature,
                               pad_nodes=M, pad_leaves=L, pad_cat_words=W,
                               pad_leaf_feats=FL)
                for t in trees]
    stacked = TreeArrays(*(jnp.stack(cols) for cols in zip(*per_tree)))
    return stacked, depth


def _round_depth(d: int) -> int:
    """Pad traversal depth to a multiple of 8 to bound jit specializations."""
    return max(8, ((d + 7) // 8) * 8)


def _cat_go_left(cat: jax.Array, bitset_row: jax.Array) -> jax.Array:
    inb = (cat >= 0) & (cat < bitset_row.shape[-1] * 32)
    safe = jnp.clip(cat, 0, bitset_row.shape[-1] * 32 - 1)
    word = safe // 32
    bit = (bitset_row[word] >> (safe % 32).astype(jnp.uint32)) & jnp.uint32(1)
    return inb & (bit == jnp.uint32(1))


def _traverse_leaf_id(x: jax.Array, t: TreeArrays, max_depth: int,
                      binned: bool) -> jax.Array:
    """Vectorized traversal of one tree over all rows -> leaf index [N].

    binned=True routes exactly like train-time partitioning
    (ops.partition.decision_go_left); binned=False uses raw thresholds with
    the reference's NaN/zero missing semantics (tree.h NumericalDecision).
    """

    def traverse(row):
        def body(_, node):
            def step(n):
                f = t.split_feature[n]
                if binned:
                    b = row[f].astype(jnp.int32)
                    mt = t.missing_type[n]
                    missing = ((mt == MT_ZERO) & (b == t.default_bin[n])) | \
                              ((mt == MT_NAN) & (b == t.num_bin[n] - 1))
                    go_num = jnp.where(missing, t.default_left[n],
                                       b <= t.threshold_bin[n])
                    go_cat = _cat_go_left(b, t.cat_bitset[n])
                else:
                    v = row[f]
                    nan = jnp.isnan(v)
                    mt = t.missing_type[n]
                    # NaN converted to 0 unless NaN-missing
                    # (reference: tree.h NumericalDecision)
                    v0 = jnp.where(nan & (mt != MT_NAN), 0.0, v)
                    missing = ((mt == MT_NAN) & nan) | \
                              ((mt == MT_ZERO) & (jnp.abs(v0) <= K_ZERO_THRESHOLD))
                    go_num = jnp.where(missing, t.default_left[n],
                                       v0 <= t.threshold[n])
                    cat = jnp.where(nan, -1, v).astype(jnp.int32)
                    go_cat = _cat_go_left(cat, t.cat_bitset_real[n])
                go = jnp.where(t.is_categorical[n], go_cat, go_num)
                return jnp.where(go, t.left_child[n], t.right_child[n])
            return jnp.where(node < 0, node, step(jnp.maximum(node, 0)))

        return ~lax.fori_loop(0, max_depth, body, jnp.int32(0))

    return jax.vmap(traverse)(x)


class RoutingTree(NamedTuple):
    """What routes a binned row through ONE tree, under the names and in the
    shapes the fused learner's ``DeviceTree`` keeps them (M = num_leaves - 1
    node slots of the configuration, of which the first ``num_leaves - 1``
    are live): nothing here depends on the tree that was grown."""
    node_feature: jax.Array       # i32 [M] inner feature index
    node_threshold: jax.Array     # i32 [M] bin threshold
    node_default_left: jax.Array  # bool [M]
    node_is_cat: jax.Array        # bool [M]
    node_cat_bits: jax.Array      # u32 [M, 8] bin-space bitset
    node_left: jax.Array          # i32 [M] (>=0 node, <0 ~leaf)
    node_right: jax.Array         # i32 [M]
    num_leaves: jax.Array         # i32 scalar


def routing_tree_from_host(tree, num_leaves: int) -> RoutingTree:
    """A host Tree as a :class:`RoutingTree` padded to ``num_leaves`` (the
    configuration's, or the tree's own when it is larger)."""
    M = max(max(num_leaves, tree.num_leaves) - 1, 1)
    n = tree.num_internal

    def col(vals, dtype):
        a = np.zeros(M, dtype)
        a[:n] = np.asarray(vals[:n], dtype)
        return a
    bits = np.zeros((M, 8), np.uint32)
    for i in range(n):
        bb = np.asarray(tree.cat_bitset[i], np.uint32)[:8]
        bits[i, :len(bb)] = bb
    return RoutingTree(
        node_feature=col(tree.split_feature_inner, np.int32),
        node_threshold=col(tree.threshold_bin, np.int32),
        node_default_left=col(tree.default_left, bool),
        node_is_cat=col(tree.is_categorical, bool),
        node_cat_bits=bits,
        node_left=col(tree.left_child, np.int32),
        node_right=col(tree.right_child, np.int32),
        num_leaves=np.int32(max(tree.num_leaves, 1)))


#: rows routed at a time by :func:`route_tree_binned`: bounds its
#: ``[rows, nodes]`` temporaries whatever the set's size
ROUTE_BLOCK = 1 << 15


def _leaf_paths(t: RoutingTree, L: int) -> Tuple[jax.Array, jax.Array]:
    """The tree's paths as a matrix: ``turn[l, m]`` is +1 where the way to
    leaf ``l`` goes LEFT at node ``m``, -1 where it goes right, 0 where it
    does not pass ``m``; ``lefts[l]`` counts the +1s. A row sits in leaf
    ``l`` iff its go-left decisions ``d[m]`` in {0, 1} give
    ``sum_m d[m] * turn[l, m] == lefts[l]`` (every left turn taken, no right
    turn refused). Built from the child pointers alone by ~log2(M) products
    of M x M matrices of 0 and 1: a fixed shape for any tree, any depth."""
    M = t.node_left.shape[0]
    f32 = jnp.float32
    live = jnp.arange(M, dtype=jnp.int32) < t.num_leaves - 1
    node = jnp.arange(M, dtype=jnp.int32)[:, None]
    leaf = ~jnp.arange(L, dtype=jnp.int32)[:, None]
    # [child, parent]: the child is the parent's left (right) node / leaf
    nl = ((t.node_left[None, :] == node) & live[None, :]).astype(f32)
    nr = ((t.node_right[None, :] == node) & live[None, :]).astype(f32)
    ll = ((t.node_left[None, :] == leaf) & live[None, :]).astype(f32)
    lr = ((t.node_right[None, :] == leaf) & live[None, :]).astype(f32)
    # anc[m, a]: a is m or above it -- the closure of "parent of", doubled
    anc = jnp.minimum(jnp.eye(M, dtype=f32) + nl + nr, 1.0)
    for _ in range(max(M - 1, 1).bit_length()):     # 2^k >= the longest path
        anc = jnp.minimum(anc @ anc, 1.0)
    par = ll + lr                                  # [L, M]: the leaf's parent
    left = ll + par @ (anc @ nl)
    right = lr + par @ (anc @ nr)
    return left - right, jnp.sum(left, axis=1)


def route_tree_binned(x_binned: jax.Array, t: RoutingTree,
                      leaf_value: jax.Array, default_bins: jax.Array,
                      missing_types: jax.Array, num_bins: jax.Array,
                      has_categorical: bool) -> jax.Array:
    """One tree's value of every binned row ``[N, F] -> [N]``, with no
    traversal: every row's decision at EVERY node (the statement training
    rows are routed by, ``ops.partition``), then the leaf whose path the
    decisions satisfy (:func:`_leaf_paths`). Two matrix products a block of
    rows -- the nodes' feature columns picked out of the row, the decisions
    against the paths -- and no gather by row; the shape holds no property
    of the tree, so one compile serves every tree of a run. Bit-equal to
    :func:`predict_tree_binned` on the materialised tree: the sums are of
    small whole numbers, the value is selected, not multiplied."""
    from .partition import decisions_by_node
    N, F = x_binned.shape
    L = leaf_value.shape[0]
    turn, lefts = _leaf_paths(t, L)
    # bins up to 256 are whole numbers a bfloat16 holds; wider bins take
    # the float32 product at full precision
    narrow = x_binned.dtype.itemsize == 1
    sel_t = jnp.bfloat16 if narrow else jnp.float32
    prec = None if narrow else lax.Precision.HIGHEST
    feat = jnp.clip(t.node_feature, 0, F - 1)
    pick = (feat[None, :] == jnp.arange(F, dtype=jnp.int32)[:, None]
            ).astype(sel_t)                                       # [F, M]
    args = (t.node_threshold, t.node_default_left, default_bins[feat],
            missing_types[feat], num_bins[feat], t.node_is_cat,
            t.node_cat_bits)
    live_leaf = jnp.arange(L, dtype=jnp.int32) < jnp.maximum(t.num_leaves, 1)
    turn_t = turn.T.astype(jnp.bfloat16)                          # [M, L]
    blk = min(ROUTE_BLOCK, N)
    assert 0 < blk <= N

    def block(xb):
        bins = jnp.dot(xb.astype(sel_t), pick, precision=prec,
                       preferred_element_type=jnp.float32)
        go = decisions_by_node(bins, *args, has_categorical)
        met = jnp.dot(go.astype(jnp.bfloat16), turn_t,
                      preferred_element_type=jnp.float32)         # [blk, L]
        here = (met == lefts[None, :]) & live_leaf[None, :]
        return jnp.sum(jnp.where(here, leaf_value[None, :], 0.0), axis=1)

    if blk == N:
        return block(x_binned)

    def body(b, out):
        # the last block is moved back to end at N: its overlap with the
        # block before is computed twice and written twice, alike
        lo = jnp.minimum(b * blk, N - blk)
        xb = lax.dynamic_slice(x_binned, (lo, 0), (blk, F))
        return lax.dynamic_update_slice(out, block(xb), (lo,))
    return lax.fori_loop(0, -(-N // blk), body,
                         jnp.zeros(N, jnp.float32))


@functools.partial(jax.jit, static_argnames=("max_depth",))
def predict_tree_raw(x: jax.Array, t: TreeArrays, max_depth: int) -> jax.Array:
    """Predict one tree on raw float features [N, D] -> [N] leaf values."""
    return t.leaf_value[_traverse_leaf_id(x, t, max_depth, binned=False)]


@functools.partial(jax.jit, static_argnames=("max_depth",))
def predict_tree_binned(x_binned: jax.Array, t: TreeArrays,
                        max_depth: int) -> jax.Array:
    """Predict one tree on the binned matrix [N, F] (train/valid data)."""
    return t.leaf_value[_traverse_leaf_id(x_binned, t, max_depth, binned=True)]


@functools.partial(jax.jit, static_argnames=("max_depth", "output_leaf"))
def predict_leaf_index_binned(x_binned: jax.Array, t: TreeArrays,
                              max_depth: int, output_leaf: bool = True) -> jax.Array:
    """Leaf index per row (for refit / predict_leaf_index)."""
    del output_leaf
    return _traverse_leaf_id(x_binned, t, max_depth, binned=True)


def _tree_leaf_vals(x: jax.Array, t: TreeArrays, max_depth: int,
                    binned: bool, has_linear: bool) -> jax.Array:
    """One tree's per-row output [N]: the constant leaf gather, or — for
    linear forests on raw rows — the shared per-leaf dot-product
    evaluation (ops/linear.py), identical op-for-op to the tensor
    engine's so both engines stay ``array_equal``."""
    leaf = _traverse_leaf_id(x, t, max_depth, binned)
    if not has_linear:
        return t.leaf_value[leaf]
    from .linear import linear_leaf_values
    return linear_leaf_values(x, leaf[:, None], t.leaf_value, t.leaf_const,
                              t.leaf_feat, t.leaf_coeff)[:, 0]


@functools.partial(jax.jit,
                   static_argnames=("num_class", "max_depth", "binned",
                                    "early_stop_freq", "has_linear"))
def _predict_forest_block(x: jax.Array, forest: TreeArrays,
                          tree_class: jax.Array, carry,
                          num_class: int, max_depth: int, binned: bool,
                          early_stop_freq: int = 0,
                          early_stop_margin: float = 0.0,
                          has_linear: bool = False):
    """One bounded block of trees, threading the (out, stopped, i) carry."""
    if early_stop_freq <= 0:
        out, stopped, i = carry

        def step(o, tk):
            t, k = tk
            vals = _tree_leaf_vals(x, t, max_depth, binned, has_linear)
            return o.at[k].add(vals), None

        out, _ = lax.scan(step, out, (forest, tree_class))
        return out, stopped, i

    def margin_of(out):
        if num_class == 1:
            # reference binary margin is 2*|raw score|
            # (src/boosting/prediction_early_stop.cpp)
            return 2.0 * jnp.abs(out[0])
        top2 = lax.top_k(out.T, 2)[0]          # [N, 2]
        return top2[:, 0] - top2[:, 1]

    def step(c, tk):
        out, stopped, i = c
        t, k = tk
        vals = _tree_leaf_vals(x, t, max_depth, binned, has_linear)
        out = out.at[k].add(jnp.where(stopped, 0.0, vals))
        i = i + 1
        check = (i % early_stop_freq) == 0
        stopped = jnp.where(check, stopped | (margin_of(out)
                                              > early_stop_margin), stopped)
        return (out, stopped, i), None

    (out, stopped, i), _ = lax.scan(step, carry, (forest, tree_class))
    return out, stopped, i


def build_forest_blocks(forest: TreeArrays, tree_class: jax.Array,
                        tree_block: int = TREE_BLOCK):
    """Pre-slice a stacked forest into bounded, padded tree blocks ONCE.

    The blocked predict paths used to re-slice and zero-pad-concatenate the
    stacked forest per block on EVERY call, adding device copies of the
    whole forest each invocation (ADVICE round 5, predict.py:313). The
    forest is immutable between calls, so callers (the booster's predict
    cache, serve's CompiledForestCache) build the blocks once and pass them
    to :func:`predict_forest` / :func:`predict_forest_leaf`.

    Returns a tuple of ``(block TreeArrays, block tree_class, n_real)``
    entries, or ``None`` when the forest fits a single dispatch (callers
    pass the unsliced forest through unchanged in that case)."""
    T = int(tree_class.shape[0])
    if tree_block <= 0 or T <= tree_block:
        return None
    out = []
    for b in range(0, T, tree_block):
        blk, tc = _forest_block(forest, tree_class, b, tree_block, T)
        out.append((blk, tc, min(b + tree_block, T) - b))
    return tuple(out)


def predict_forest(x: jax.Array, forest: TreeArrays, tree_class: jax.Array,
                   num_class: int, max_depth: int, binned: bool,
                   early_stop_freq: int = 0,
                   early_stop_margin: float = 0.0,
                   tree_block: int = TREE_BLOCK,
                   blocks=None, has_linear: bool = False) -> jax.Array:
    """Sum a whole forest's leaf values into per-class scores.

    x: [N, D] raw floats (binned=False) or [N, F] binned (binned=True).
    forest: TreeArrays stacked along a leading T axis (forest_to_arrays).
    tree_class: i32 [T] — class index of each tree (iter-major, class-minor).
    early_stop_freq/margin: margin-based prediction early stopping — every
    ``freq`` trees, rows whose decision margin exceeds ``margin`` stop
    accumulating further trees (reference:
    src/boosting/prediction_early_stop.cpp; binary margin = |score|,
    multiclass = top1 - top2).
    Returns [num_class, N] float32.

    A ``lax.scan`` over trees keeps peak memory at O(N) instead of the
    O(T·N) a tree-vmapped traversal would materialize — the device analog
    of GBDT::Predict accumulating over inlined trees
    (reference: src/boosting/gbdt_prediction.cpp, cuda_tree.cu:459).

    The scan is dispatched in bounded blocks of ``tree_block`` trees
    (default :data:`TREE_BLOCK`) with the accumulator
    carried between dispatches: no single kernel grows with the forest,
    at the cost of T/block dispatches. Forests at most one block long
    compile to the identical single kernel as before.

    ``blocks``: pre-sliced device blocks from :func:`build_forest_blocks`;
    passing them skips the per-call forest re-slice entirely.

    ``has_linear``: evaluate the per-leaf linear payload (raw rows only —
    linear leaves read raw feature values, which binned matrices no longer
    carry; callers replay binned linear forests host-side)."""
    assert not (binned and has_linear), \
        "linear forests traverse raw rows; binned linear replay is host-side"
    N = x.shape[0]
    T = tree_class.shape[0]
    init = (jnp.zeros((num_class, N), jnp.float32),
            jnp.zeros(N, dtype=bool), jnp.int32(0))
    if blocks is None:
        if tree_block <= 0 or T <= tree_block:
            out, _, _ = _predict_forest_block(
                x, forest, tree_class, init, num_class, max_depth,
                binned, early_stop_freq, early_stop_margin, has_linear)
            return out
        blocks = build_forest_blocks(forest, tree_class, tree_block)
    carry = init
    for blk, tc, _ in blocks:
        carry = _predict_forest_block(
            x, blk, tc, carry, num_class, max_depth, binned,
            early_stop_freq, early_stop_margin, has_linear)
    return carry[0]


def _forest_block(forest: TreeArrays, tree_class: jax.Array, b: int,
                  tree_block: int, T: int):
    """Trees [b, b+tree_block) of the stacked forest; only the TAIL block
    pads, with no-op trees (all-zero arrays: the bounded traversal lands on
    ``leaf_value[-1] == 0``, adding nothing — and pads sit strictly after
    every real tree, so early-stop margins are unaffected)."""
    hi = min(b + tree_block, T)
    pad = tree_block - (hi - b)

    def cut(a):
        blk = lax.slice_in_dim(a, b, hi)
        if pad:
            blk = jnp.concatenate(
                [blk, jnp.zeros((pad,) + a.shape[1:], a.dtype)])
        return blk

    return (jax.tree_util.tree_map(cut, forest),
            cut(tree_class))


@functools.partial(jax.jit, static_argnames=("max_depth", "binned"))
def _predict_forest_leaf_block(x: jax.Array, forest: TreeArrays,
                               max_depth: int, binned: bool) -> jax.Array:
    def step(_, t):
        return None, _traverse_leaf_id(x, t, max_depth, binned)

    _, ys = lax.scan(step, None, forest)
    return ys


def predict_forest_leaf(x: jax.Array, forest: TreeArrays,
                        max_depth: int, binned: bool,
                        tree_block: int = TREE_BLOCK,
                        blocks=None) -> jax.Array:
    """Leaf index per (tree, row) for a whole forest: [T, N] int32.

    Dispatched in the same bounded tree blocks as :func:`predict_forest`
    (refit / linear-tree replay / pred_leaf hit this path with full-size
    forests). ``blocks`` from
    :func:`build_forest_blocks` skips the per-call forest re-slice."""
    T = forest.leaf_value.shape[0]
    if blocks is None:
        if tree_block <= 0 or T <= tree_block:
            return _predict_forest_leaf_block(x, forest, max_depth, binned)
        blocks = build_forest_blocks(
            forest, jnp.zeros(T, jnp.int32), tree_block)
    outs = []
    for blk, _, n_real in blocks:
        ys = _predict_forest_leaf_block(x, blk, max_depth, binned)
        outs.append(ys[:n_real])
    return jnp.concatenate(outs, axis=0)


# graftir IR contract
from ..analysis.ir.contracts import register_program

register_program(
    "predict._predict_forest_block", collective_free=True,
    notes="scan-engine block kernel; steady-state predict replays the "
          "one trace")
