"""Compiled-forest traversal engine (``predict_engine=compiled``).

Runs the serving-shaped artifact :mod:`lambdagap_tpu.infer.compile` emits.
Where the tensor engine (ops/predict_tensor.py) gathers over the stacked
TRAINING-shaped node tables — 4-byte thresholds, split-order nodes, one
flat gather lattice per depth step — this engine walks the compiled form:
per node block, a ``[rows, groups]`` node lattice is carried through the
block's breadth-first level slabs, decoding u8/u16 palette codes back to
the exact f32 thresholds on the way. Merged trees are
traversed ONCE per structure group; the per-tree leaf payloads are gathered
afterwards through the compile-time ``group_of_tree`` map.

Bit-exactness contract (the same one predict_tensor.py honors): traversal
only computes leaf INDICES — any correct traversal yields the same ones —
and the per-class score accumulation then runs as a ``lax.scan`` over trees
in forest order with the identical f32 addition order (and the identical
early-stop replay) as the scan oracle, with the leaf gather going through
the very same tables and ops (``ops/linear.linear_leaf_values`` included)
``forest_to_arrays`` feeds the other engines. ``tests/test_infer.py``
asserts ``array_equal``, not closeness, across the whole parity matrix.

:class:`PackedForests` extends the bucket idea ACROSS models: many small
per-tenant forests concatenated into ONE executable whose single dispatch
traverses every model's blocks and masks each row's accumulation to its own
model's trees — a mixed FairQueue batch costs one dispatch instead of one
per tenant. Masked trees contribute an exact ``+0.0``, so each row's scores
stay value-identical to its model served alone.

The table walk is plain XLA under the caller's jit on every backend. It
was first written as a Pallas kernel; Mosaic refuses its data-dependent
table gathers (``feat[idx]`` with a ``[rows, groups]`` index lattice over
a 1-D node table: "NotImplementedError: Only 2D gather is supported",
jax 0.9.0 — Mosaic gathers only ``take_along_axis`` between equal shapes),
and interpret mode lowered to these same XLA ops anyway.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.linear import linear_leaf_values
from ..ops.predict import K_ZERO_THRESHOLD, MT_NAN, MT_ZERO
from .compile import (FLAG_CATEGORICAL, FLAG_DEFAULT_LEFT, FLAG_MT_SHIFT,
                      ForestArtifact)

# ---------------------------------------------------------------------------
# traversal: one node block, [rows, groups] lattice
# ---------------------------------------------------------------------------
def _traverse_block(x: jax.Array, tables, depth: int) -> jax.Array:
    """Carry every row through every structure group of ONE node block ->
    node carry [R, Gb] (every live entry is ``~leaf``; a non-negative
    survivor means the block's recorded depth was wrong — compile-time
    invariant, not a runtime case).

    Node tables arrive level-major (compile-time BFS packing), so the whole
    lattice's step-d gathers land in the block's depth-d slab — the "one
    depth step = one contiguous fetch" layout the compiler exists to
    produce. Decision math mirrors predict_tensor._traverse_tile decision
    for decision (NaN->0 conversion, missing routing, categorical bitset
    word math); only the node id space differs (block-local breadth-first
    ids, palette-coded thresholds decoded through ``thr_tab``)."""
    (feat, thr_code, flags, catc, left, right, thr_tab, cat_tab,
     root) = tables                                    # root: [Gb] i32
    feat = feat.astype(jnp.int32)
    thr_code = thr_code.astype(jnp.int32)
    flags = flags.astype(jnp.int32)
    catc = catc.astype(jnp.int32)
    cat_words = cat_tab.shape[1]
    cat_bits = cat_tab.reshape(-1)                     # [C * W] u32
    node0 = jnp.broadcast_to(root[None, :], (x.shape[0], root.shape[0]))

    def body(_, node):
        idx = jnp.maximum(node, 0)                     # [R, Gb]
        f = feat[idx]
        fl = flags[idx]
        dl = (fl & FLAG_DEFAULT_LEFT) != 0
        mt = (fl >> FLAG_MT_SHIFT) & 3
        is_cat = (fl & FLAG_CATEGORICAL) != 0
        v = jnp.take_along_axis(x, f, axis=1)
        nan = jnp.isnan(v)
        # NaN converted to 0 unless NaN-missing
        # (reference: tree.h NumericalDecision)
        v0 = jnp.where(nan & (mt != MT_NAN), 0.0, v)
        missing = ((mt == MT_NAN) & nan) | \
                  ((mt == MT_ZERO) & (jnp.abs(v0) <= K_ZERO_THRESHOLD))
        go_num = jnp.where(missing, dl, v0 <= thr_tab[thr_code[idx]])
        cat = jnp.where(nan, -1, v).astype(jnp.int32)
        nbits = cat_words * 32
        inb = (cat >= 0) & (cat < nbits)
        safe = jnp.clip(cat, 0, nbits - 1)
        word = catc[idx] * cat_words + safe // 32
        bit = (cat_bits[word] >> (safe % 32).astype(jnp.uint32)) \
            & jnp.uint32(1)
        go = jnp.where(is_cat, inb & (bit == jnp.uint32(1)), go_num)
        nxt = jnp.where(go, left[idx], right[idx])
        return jnp.where(node < 0, node, nxt)

    return lax.fori_loop(0, depth, body, node0.astype(jnp.int32))


def _traverse_all(x: jax.Array, blocks,
                  depths: Tuple[int, ...]) -> jax.Array:
    """Every node block over every row -> [R, G] node carry (blocks hold
    contiguous group ranges, so concatenation restores group order). All B
    block walks live inside the caller's jit: one executable, one
    dispatch."""
    outs = [_traverse_block(x, tb, depths[i])
            for i, tb in enumerate(blocks)]
    return jnp.concatenate(outs, axis=1)


def _leaf_values(x: jax.Array, node: jax.Array, group_of_tree: jax.Array,
                 leaf, has_linear: bool) -> jax.Array:
    """[R, G] group node carry -> [R, T] per-tree leaf values, through the
    same flattened-leaf-table gather (and linear payload op) as
    predict_tensor._tile_leaf_values — the tables ARE forest_to_arrays',
    copied into the artifact unchanged."""
    nodeT = jnp.take(node, group_of_tree, axis=1)      # [R, T]
    done = nodeT < 0
    leaf_idx = jnp.where(done, ~nodeT, 0)
    T = group_of_tree.shape[0]
    L = leaf[0].shape[-1]
    idx = (jnp.arange(T, dtype=jnp.int32) * L)[None, :] + leaf_idx
    if has_linear:
        lv, lc, lf, lcf = leaf
        FL = lf.shape[-1]
        vals = linear_leaf_values(x, idx, lv.reshape(-1), lc.reshape(-1),
                                  lf.reshape(-1, FL), lcf.reshape(-1, FL))
    else:
        vals = leaf[0].reshape(-1)[idx]
    return jnp.where(done, vals, jnp.float32(0.0))


def _accumulate(vals: jax.Array, tree_class: jax.Array, carry,
                num_class: int, early_stop_freq: int, early_stop_margin):
    """Forest-order accumulation scan — a verbatim mirror of
    predict_tensor._predict_tensor_tile's (out, stopped, i) carry, early
    stop replay included, so the f32 addition order (and therefore the
    bits) matches the scan oracle."""
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
        top2 = lax.top_k(out.T, 2)[0]                  # [N, 2]
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


@functools.partial(jax.jit,
                   static_argnames=("depths", "num_class", "early_stop_freq",
                                    "has_linear"))
def _predict_compiled(x, blocks, group_of_tree, tree_class, leaf,
                      early_stop_margin, *, depths, num_class,
                      early_stop_freq, has_linear):
    """One compiled forest over one row batch -> [num_class, R] raw f32.
    Every artifact buffer arrives as an ARGUMENT (never closed over), so
    the executable is shared across forests of the same shape instead of
    baking each forest's tables in as constants."""
    R = x.shape[0]
    node = _traverse_all(x, blocks, depths)
    vals = _leaf_values(x, node, group_of_tree, leaf, has_linear)
    carry = (jnp.zeros((num_class, R), jnp.float32),
             jnp.zeros(R, dtype=bool), jnp.int32(0))
    return _accumulate(vals, tree_class, carry, num_class,
                       early_stop_freq, early_stop_margin)


@functools.partial(jax.jit,
                   static_argnames=("depths", "num_class", "has_linear"))
def _predict_packed(x, row_model, blocks, group_of_tree, tree_class,
                    tree_model, leaf, *, depths, num_class, has_linear):
    """Many packed forests, one mixed row batch, ONE dispatch.

    Every row traverses every model's blocks; the mask then zeroes the
    trees that are not the row's model before the single forest-order
    accumulation scan. A masked tree adds an exact ``+0.0`` — each row's
    scores are value-identical to its model predicted alone (early stop is
    excluded from packs; its tree-count replay is per-model by nature)."""
    R = x.shape[0]
    node = _traverse_all(x, blocks, depths)
    vals = _leaf_values(x, node, group_of_tree, leaf, has_linear)
    vals = jnp.where(tree_model[None, :] == row_model[:, None], vals,
                     jnp.float32(0.0))
    out = jnp.zeros((num_class, R), jnp.float32)

    def step(o, vk):
        v, k = vk
        return o.at[k].add(v), None

    out, _ = lax.scan(step, out, (vals.T, tree_class))
    return out


# ---------------------------------------------------------------------------
# device-resident forms
# ---------------------------------------------------------------------------
def _device_blocks(buffers) -> Tuple[tuple, Tuple[int, ...]]:
    """Slice an artifact's block-major node tables into per-block device
    tuples (palette dtypes kept narrow — decode happens in the walk). A
    node-less block (all member groups are stumps) gets one dead
    placeholder node: its depth is 0, so the walk never gathers it."""
    b = buffers
    lo = np.asarray(b["block_node_lo"])
    glo = np.asarray(b["block_group_lo"])
    depths = tuple(int(d) for d in np.asarray(b["block_depth"]))
    thr_tab = jnp.asarray(b["thr_table"])
    cat_tab = jnp.asarray(b["cat_table"])
    blocks = []
    for i in range(len(depths)):
        s = slice(int(lo[i]), int(lo[i + 1]))
        if s.stop == s.start:
            feat = jnp.zeros(1, b["node_feat"].dtype)
            thr = jnp.zeros(1, b["node_thr"].dtype)
            flags = jnp.zeros(1, np.uint8)
            catc = jnp.zeros(1, b["node_cat"].dtype)
            left = jnp.full(1, -1, jnp.int32)
            right = jnp.full(1, -1, jnp.int32)
        else:
            feat = jnp.asarray(b["node_feat"][s])
            thr = jnp.asarray(b["node_thr"][s])
            flags = jnp.asarray(b["node_flags"][s])
            catc = jnp.asarray(b["node_cat"][s])
            left = jnp.asarray(b["node_left"][s])
            right = jnp.asarray(b["node_right"][s])
        root = jnp.asarray(b["root"][int(glo[i]):int(glo[i + 1])])
        blocks.append((feat, thr, flags, catc, left, right,
                       thr_tab, cat_tab, root))
    return tuple(blocks), depths


class CompiledForest:
    """A device-resident compiled forest: the artifact's packed buffers
    uploaded once, predicted through :func:`_predict_compiled`.

    ``predict`` returns RAW per-class scores ``[num_class, N]`` f32 — the
    same contract as ``predict_forest_tensor`` before averaging/objective
    conversion, which stays with the caller (models/gbdt.py or the serve
    cache), exactly where the other engines leave it."""

    def __init__(self, artifact: ForestArtifact, *,
                 early_stop_freq: int = 0,
                 early_stop_margin: float = 0.0) -> None:
        self.artifact = artifact
        m = artifact.meta
        self.num_class = int(m["num_class"])
        self.num_trees = int(m["num_trees"])
        self.width = int(m["width"])
        self.has_linear = bool(m["has_linear"])
        self.early_stop_freq = int(early_stop_freq)
        self._es_margin = float(early_stop_margin)
        b = artifact.buffers
        self._blocks, self._depths = _device_blocks(b)
        self._group_of_tree = jnp.asarray(b["group_of_tree"])
        self._tree_class = jnp.asarray(b["tree_class"])
        if self.has_linear:
            self._leaf = (jnp.asarray(b["leaf_value"]),
                          jnp.asarray(b["leaf_const"]),
                          jnp.asarray(b["leaf_feat"]),
                          jnp.asarray(b["leaf_coeff"]))
        else:
            self._leaf = (jnp.asarray(b["leaf_value"]),)

    def predict(self, x: jax.Array) -> jax.Array:
        x = jnp.asarray(x, jnp.float32)
        out, _, _ = _predict_compiled(
            x, self._blocks, self._group_of_tree, self._tree_class,
            self._leaf, jnp.float32(self._es_margin),
            depths=self._depths, num_class=self.num_class,
            early_stop_freq=self.early_stop_freq,
            has_linear=self.has_linear)
        return out

    @property
    def nbytes(self) -> int:
        n = sum(int(t.nbytes) for blk in self._blocks for t in blk)
        n += int(self._group_of_tree.nbytes) + int(self._tree_class.nbytes)
        n += sum(int(a.nbytes) for a in self._leaf)
        return n


class PackedForests:
    """Many small compiled forests padded into ONE executable.

    The cross-model extension of serve/cache.py's padding buckets: members'
    node blocks concatenate (each block is self-contained — block-local
    child ids, its own palette tables), leaf tables pad to the widest
    member and stack along the tree axis, and ``tree_model`` records each
    tree's owner. ``predict(x, row_model)`` then serves a MIXED per-tenant
    batch in one dispatch; each row's accumulation is masked to its own
    model's trees, so scores are value-identical to the member served
    alone. Averaging and objective conversion stay per-model with the
    caller (serve/cache.ModelPack), AFTER the one packed dispatch — the
    O(trees) work is what dispatches once.

    Members must not use prediction early stop (its tree-count replay is
    inherently per-model); mixed num_class is fine — rows of a narrower
    model leave the extra class rows at zero.
    """

    def __init__(self, members: Dict[str, CompiledForest]) -> None:
        if not members:
            raise ValueError("PackedForests needs at least one member")
        for name, cf in members.items():
            if cf.early_stop_freq > 0:
                raise ValueError(
                    f"model {name!r} uses prediction early stop; packs "
                    "dispatch many models at once and cannot replay a "
                    "per-model tree-count stop")
        self.names = list(members)
        self.model_index = {n: i for i, n in enumerate(self.names)}
        cfs = list(members.values())
        self.num_class = max(cf.num_class for cf in cfs)
        self.width = max(cf.width for cf in cfs)
        self.has_linear = any(cf.has_linear for cf in cfs)
        self._blocks = tuple(blk for cf in cfs for blk in cf._blocks)
        self._depths = tuple(d for cf in cfs for d in cf._depths)
        goff = 0
        gofs, tcs, tms = [], [], []
        for mi, cf in enumerate(cfs):
            g = np.asarray(cf.artifact.buffers["group_of_tree"])
            gofs.append(g + goff)
            goff += int(np.asarray(cf.artifact.buffers["root"]).shape[0])
            tcs.append(np.asarray(cf.artifact.buffers["tree_class"]))
            tms.append(np.full(g.shape[0], mi, np.int32))
        self._group_of_tree = jnp.asarray(np.concatenate(gofs))
        self._tree_class = jnp.asarray(np.concatenate(tcs))
        self._tree_model = jnp.asarray(np.concatenate(tms))
        self._leaf = tuple(jnp.asarray(t)
                           for t in _pack_leaf_tables(cfs, self.has_linear))
        self.num_trees = int(self._tree_model.shape[0])

    def predict(self, x: jax.Array, row_model: jax.Array) -> jax.Array:
        """x: [N, pack width] raw rows; row_model: [N] member index per
        row (see ``model_index``). Returns raw [num_class, N] f32."""
        x = jnp.asarray(x, jnp.float32)
        return _predict_packed(
            x, jnp.asarray(row_model, jnp.int32), self._blocks,
            self._group_of_tree, self._tree_class, self._tree_model,
            self._leaf, depths=self._depths, num_class=self.num_class,
            has_linear=self.has_linear)

    @property
    def nbytes(self) -> int:
        n = sum(int(t.nbytes) for blk in self._blocks for t in blk)
        n += sum(int(a.nbytes) for a in
                 (self._group_of_tree, self._tree_class, self._tree_model))
        n += sum(int(a.nbytes) for a in self._leaf)
        return n


def _pack_leaf_tables(cfs, has_linear: bool):
    """Member leaf tables padded to the pack's (L, FL) and stacked along
    the tree axis. Padding preserves member bits: extra leaf rows are
    never selected by the member's trees, constant members in a linear
    pack carry ``leaf_const == leaf_value`` with all slots ``-1`` (the
    exact encoding tree_to_arrays gives constant trees), and extra ``-1``
    slots add an exact ``+0.0`` in the fixed-order linear evaluation."""
    L = max(np.asarray(cf.artifact.buffers["leaf_value"]).shape[-1]
            for cf in cfs)
    lv_all, lc_all, lf_all, lcf_all = [], [], [], []
    FL = 1
    if has_linear:
        FL = max(np.asarray(cf.artifact.buffers["leaf_feat"]).shape[-1]
                 for cf in cfs if cf.has_linear)
    for cf in cfs:
        b = cf.artifact.buffers
        lv = np.asarray(b["leaf_value"], np.float32)
        T, Li = lv.shape
        lv_all.append(np.pad(lv, ((0, 0), (0, L - Li))))
        if not has_linear:
            continue
        if cf.has_linear:
            lc = np.asarray(b["leaf_const"], np.float32)
            lf = np.asarray(b["leaf_feat"], np.int32)
            lcf = np.asarray(b["leaf_coeff"], np.float32)
            FLi = lf.shape[-1]
        else:
            lc = lv.copy()
            lf = np.full((T, Li, 1), -1, np.int32)
            lcf = np.zeros((T, Li, 1), np.float32)
            FLi = 1
        lc_all.append(np.pad(lc, ((0, 0), (0, L - Li))))
        lf_all.append(np.pad(lf, ((0, 0), (0, L - Li), (0, FL - FLi)),
                             constant_values=-1))
        lcf_all.append(np.pad(lcf, ((0, 0), (0, L - Li), (0, FL - FLi))))
    if has_linear:
        return (np.concatenate(lv_all), np.concatenate(lc_all),
                np.concatenate(lf_all), np.concatenate(lcf_all))
    return (np.concatenate(lv_all),)


# graftir IR contract
from ..analysis.ir.contracts import register_program

register_program(
    "engine._predict_compiled", collective_free=True,
    notes="compiled-forest palette walk; steady-state predict replays "
          "the one trace")
