"""Tensorized forest traversal: all rows x all trees per depth step.

The sequential engine (:mod:`lambdagap_tpu.ops.predict`) scans trees one at
a time, each tree a per-row ``fori_loop`` of scalar node gathers — the
500-tree dimension is serialized instead of exploited as data parallelism,
which is exactly the anti-pattern the GPU GBDT literature fixes with
batched node-table traversal (GPU-acceleration for Large-scale Tree
Boosting, arXiv:1706.08359; XGBoost: Scalable GPU Accelerated Learning,
arXiv:1806.11248).

This engine traverses a ``[R, Tt]`` node-index carry — R rows x a tile of
Tt trees — with ONE depth-major ``fori_loop`` whose body does batched 2-D
gathers on the stacked SoA node tables (``TreeArrays`` with the leading T
axis flattened to ``T*M``), plus one ``take_along_axis`` per level for the
feature values. Tiles are bounded by the ``predict_tree_tile`` knob so the
working set never grows with the forest; the accumulator carries across
tiles exactly like the sequential engine's tree blocks.

Bit-exactness contract: after the (parallel) traversal computes every
tree's leaf value, the per-class accumulation runs as a ``lax.scan`` over
trees IN FOREST ORDER — the identical f32 addition order as the sequential
engine — so both engines return bit-identical scores (the parity suite in
``tests/test_predict_tensor.py`` asserts equality, not closeness). The
early-stop margin check replays the sequential semantics tree by tree on
the accumulation scan; the traversal itself still computes stopped rows
(a latency trade the parallel engine accepts for exactness).

Semantics (NaN/default-left routing, categorical bitsets, binned bin
compares, zero-missing) replicate ``ops.predict._traverse_leaf_id``
decision for decision; that per-tree path stays behind
``predict_engine=scan`` as the reference oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .predict import (K_ZERO_THRESHOLD, MT_NAN, MT_ZERO, TreeArrays,
                      build_forest_blocks)


#: trees per tensorized tile dispatch when the caller passes no
#: ``predict_tree_tile``
TREE_TILE = 64


def _traverse_tile(x: jax.Array, t: TreeArrays, max_depth: int,
                   binned: bool) -> jax.Array:
    """All rows through all trees of one tile -> final node carry [R, Tt]
    (negative entries are ``~leaf``; non-negative means the tree never
    reached a leaf — only the zero-padded no-op trees do that)."""
    R = x.shape[0]
    Tt, M = t.split_feature.shape
    W = t.cat_bitset_real.shape[-1]
    # flatten the stacked node tables once; every per-level gather is then
    # one flat [R*Tt] gather at index tree*M + node
    feat = t.split_feature.reshape(-1)
    left = t.left_child.reshape(-1)
    right = t.right_child.reshape(-1)
    missing_type = t.missing_type.reshape(-1)
    default_left = t.default_left.reshape(-1)
    is_cat = t.is_categorical.reshape(-1)
    if binned:
        thr_bin = t.threshold_bin.reshape(-1)
        default_bin = t.default_bin.reshape(-1)
        num_bin = t.num_bin.reshape(-1)
        cat_bits = t.cat_bitset.reshape(-1)
        cat_words = t.cat_bitset.shape[-1]
    else:
        thr = t.threshold.reshape(-1)
        cat_bits = t.cat_bitset_real.reshape(-1)
        cat_words = W
    base = (jnp.arange(Tt, dtype=jnp.int32) * M)[None, :]     # [1, Tt]

    def cat_go_left(cat, idx):
        """_cat_go_left over the [R, Tt] lattice (same clipping/bit math)."""
        nbits = cat_words * 32
        inb = (cat >= 0) & (cat < nbits)
        safe = jnp.clip(cat, 0, nbits - 1)
        word = idx * cat_words + safe // 32
        bit = (cat_bits[word] >> (safe % 32).astype(jnp.uint32)) \
            & jnp.uint32(1)
        return inb & (bit == jnp.uint32(1))

    def body(_, node):
        idx = base + jnp.maximum(node, 0)                     # [R, Tt]
        f = feat[idx]
        mt = missing_type[idx]
        if binned:
            b = jnp.take_along_axis(x, f, axis=1).astype(jnp.int32)
            missing = ((mt == MT_ZERO) & (b == default_bin[idx])) | \
                      ((mt == MT_NAN) & (b == num_bin[idx] - 1))
            go_num = jnp.where(missing, default_left[idx],
                               b <= thr_bin[idx])
            go_cat = cat_go_left(b, idx)
        else:
            v = jnp.take_along_axis(x, f, axis=1)
            nan = jnp.isnan(v)
            # NaN converted to 0 unless NaN-missing
            # (reference: tree.h NumericalDecision)
            v0 = jnp.where(nan & (mt != MT_NAN), 0.0, v)
            missing = ((mt == MT_NAN) & nan) | \
                      ((mt == MT_ZERO) & (jnp.abs(v0) <= K_ZERO_THRESHOLD))
            go_num = jnp.where(missing, default_left[idx], v0 <= thr[idx])
            cat = jnp.where(nan, -1, v).astype(jnp.int32)
            go_cat = cat_go_left(cat, idx)
        go = jnp.where(is_cat[idx], go_cat, go_num)
        nxt = jnp.where(go, left[idx], right[idx])
        return jnp.where(node < 0, node, nxt)

    return lax.fori_loop(0, max_depth, body,
                         jnp.zeros((R, Tt), jnp.int32))


def _tile_leaf_values(node: jax.Array, t: TreeArrays, x: jax.Array,
                      has_linear: bool) -> jax.Array:
    """Leaf-value gather for a traversed tile: [R, Tt] f32. No-op pad trees
    (node >= 0) contribute exactly 0.0, like the sequential engine's padded
    tail blocks. Under ``has_linear`` the gather becomes the shared
    per-leaf dot-product evaluation (ops/linear.py) over the flattened
    leaf tables — the same elementwise op sequence the scan engine runs,
    so the engines stay ``array_equal`` on linear forests."""
    Tt = t.split_feature.shape[0]
    L = t.leaf_value.shape[-1]
    done = node < 0
    leaf = jnp.where(done, ~node, 0)
    idx = (jnp.arange(Tt, dtype=jnp.int32) * L)[None, :] + leaf   # [R, Tt]
    if has_linear:
        from .linear import linear_leaf_values
        FL = t.leaf_feat.shape[-1]
        vals = linear_leaf_values(
            x, idx, t.leaf_value.reshape(-1), t.leaf_const.reshape(-1),
            t.leaf_feat.reshape(-1, FL), t.leaf_coeff.reshape(-1, FL))
    else:
        vals = t.leaf_value.reshape(-1)[idx]
    return jnp.where(done, vals, jnp.float32(0.0))


@functools.partial(jax.jit,
                   static_argnames=("num_class", "max_depth", "binned",
                                    "early_stop_freq", "has_linear"))
def _predict_tensor_tile(x: jax.Array, t: TreeArrays, tree_class: jax.Array,
                         carry, num_class: int, max_depth: int, binned: bool,
                         early_stop_freq: int = 0,
                         early_stop_margin: float = 0.0,
                         has_linear: bool = False):
    """One tile: parallel [R, Tt] traversal, then an in-forest-order
    accumulation scan threading the sequential engine's (out, stopped, i)
    carry — identical f32 addition order, identical early-stop points."""
    node = _traverse_tile(x, t, max_depth, binned)
    vals = _tile_leaf_values(node, t, x, has_linear)          # [R, Tt]
    if early_stop_freq <= 0:
        out, stopped, i = carry

        def step(o, vk):
            v, k = vk
            return o.at[k].add(v), None

        out, _ = lax.scan(step, out, (vals.T, tree_class))
        return out, stopped, i

    def margin_of(out):
        if num_class == 1:
            # reference binary margin is 2*|raw score|
            # (src/boosting/prediction_early_stop.cpp)
            return 2.0 * jnp.abs(out[0])
        top2 = lax.top_k(out.T, 2)[0]          # [N, 2]
        return top2[:, 0] - top2[:, 1]

    def step(c, vk):
        out, stopped, i = c
        v, k = vk
        out = out.at[k].add(jnp.where(stopped, 0.0, v))
        i = i + 1
        check = (i % early_stop_freq) == 0
        stopped = jnp.where(check, stopped | (margin_of(out)
                                              > early_stop_margin), stopped)
        return (out, stopped, i), None

    carry, _ = lax.scan(step, carry, (vals.T, tree_class))
    return carry


@functools.partial(jax.jit, static_argnames=("max_depth", "binned"))
def _leaf_tensor_tile(x: jax.Array, t: TreeArrays, max_depth: int,
                      binned: bool) -> jax.Array:
    """Leaf index per (tree, row) for one tile: [Tt, R] int32."""
    return (~_traverse_tile(x, t, max_depth, binned)).T


def build_tree_tiles(forest: TreeArrays, tree_class: jax.Array,
                     tree_tile: int = TREE_TILE):
    """Pre-slice a stacked forest into ``predict_tree_tile``-sized tiles
    ONCE (same padded-tail layout as :func:`predict.build_forest_blocks`,
    so either engine can consume the result). Returns None when the forest
    fits one tile."""
    return build_forest_blocks(forest, tree_class, tree_tile)


def predict_forest_tensor(x: jax.Array, forest: TreeArrays,
                          tree_class: jax.Array, num_class: int,
                          max_depth: int, binned: bool,
                          early_stop_freq: int = 0,
                          early_stop_margin: float = 0.0,
                          tree_tile: int = TREE_TILE,
                          tiles=None, has_linear: bool = False) -> jax.Array:
    """Tensorized drop-in for :func:`ops.predict.predict_forest`.

    Same signature semantics: x is [N, D] raw floats (binned=False) or
    [N, F] binned; returns [num_class, N] float32, bit-identical to the
    sequential engine. ``tiles`` (from :func:`build_tree_tiles`) skips the
    per-call forest re-slice; ``tree_tile`` bounds the [R, Tt] working set
    per dispatch (default ``predict_tree_tile``). ``has_linear`` switches
    the leaf gather to the per-leaf dot-product payload (raw rows only)."""
    assert not (binned and has_linear), \
        "linear forests traverse raw rows; binned linear replay is host-side"
    N = x.shape[0]
    T = tree_class.shape[0]
    init = (jnp.zeros((num_class, N), jnp.float32),
            jnp.zeros(N, dtype=bool), jnp.int32(0))
    if tiles is None:
        if tree_tile <= 0 or T <= tree_tile:
            out, _, _ = _predict_tensor_tile(
                x, forest, tree_class, init, num_class, max_depth,
                binned, early_stop_freq, early_stop_margin, has_linear)
            return out
        tiles = build_tree_tiles(forest, tree_class, tree_tile)
    carry = init
    for blk, tc, _ in tiles:
        carry = _predict_tensor_tile(
            x, blk, tc, carry, num_class, max_depth, binned,
            early_stop_freq, early_stop_margin, has_linear)
    return carry[0]


def predict_forest_leaf_tensor(x: jax.Array, forest: TreeArrays,
                               max_depth: int, binned: bool,
                               tree_tile: int = TREE_TILE,
                               tiles=None) -> jax.Array:
    """Tensorized drop-in for :func:`ops.predict.predict_forest_leaf`:
    leaf index per (tree, row), [T, N] int32."""
    T = forest.leaf_value.shape[0]
    if tiles is None:
        if tree_tile <= 0 or T <= tree_tile:
            return _leaf_tensor_tile(x, forest, max_depth, binned)
        tiles = build_tree_tiles(forest, jnp.zeros(T, jnp.int32), tree_tile)
    outs = []
    for blk, _, n_real in tiles:
        ys = _leaf_tensor_tile(x, blk, max_depth, binned)
        outs.append(ys[:n_real])
    return jnp.concatenate(outs, axis=0)


# graftir IR contract
from ..analysis.ir.contracts import register_program

register_program(
    "predict_tensor._predict_tensor_tile", collective_free=True,
    notes="tensorized predict tile; steady-state predict replays the "
          "one trace")
