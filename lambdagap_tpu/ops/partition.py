"""Leaf data partition.

TPU analog of ``DataPartition`` (reference:
src/treelearner/data_partition.hpp:21-123): a permutation array of row indices
grouped by leaf plus per-leaf (begin, count). Splitting a leaf stably
partitions its index slice. The reference CPU uses a parallel two-way stable
partition; the CUDA learner uses bit-vector + prefix sums
(reference: src/treelearner/cuda/cuda_data_partition.hpp:106-139). Here the
stable partition is a key sort over the padded slice (O(P log P) but fully
vectorized on the VPU), followed by an in-range scatter back into the
permutation array. The fused tree program partitions a leaf window by window
instead and moves each window's rows with neither: ``route_window``
(``compact`` + ``write_front``) below.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .split import MT_NAN, MT_ZERO


def numerical_go_left(b: jax.Array, threshold: jax.Array,
                      default_left: jax.Array, default_bin: jax.Array,
                      missing_type: jax.Array, num_bin: jax.Array
                      ) -> jax.Array:
    """The numerical half of :func:`decision_go_left` on int32 bins: left
    iff ``bin <= threshold``, rows in the missing bin follow
    ``default_left``. Every argument broadcasts."""
    is_missing = jnp.where(
        missing_type == MT_ZERO, b == default_bin,
        jnp.where(missing_type == MT_NAN, b == num_bin - 1, False))
    return jnp.where(is_missing, default_left, b <= threshold)


def decision_go_left(bin_vals: jax.Array, threshold: jax.Array,
                     default_left: jax.Array, default_bin: jax.Array,
                     missing_type: jax.Array, num_bin: jax.Array,
                     is_categorical: jax.Array, cat_bitset: jax.Array) -> jax.Array:
    """Routing decision for a batch of bin values of one feature.

    Mirrors the train-time split semantics of the reference's Bin::Split
    (reference: src/io/dense_bin.hpp Split / tree.h Decision): numerical goes
    left iff ``bin <= threshold``; rows in the missing bin follow
    ``default_left``; categorical goes left iff its bin is in the bitset.
    """
    b = bin_vals.astype(jnp.int32)
    num_left = numerical_go_left(b, threshold, default_left, default_bin,
                                 missing_type, num_bin)
    word = jnp.clip(b // 32, 0, cat_bitset.shape[0] - 1)
    bit = jnp.right_shift(cat_bitset[word], (b % 32).astype(jnp.uint32)) & 1
    cat_left = bit == 1
    return jnp.where(is_categorical, cat_left, num_left)


def decisions_by_node(bin_vals: jax.Array, threshold: jax.Array,
                      default_left: jax.Array, default_bin: jax.Array,
                      missing_type: jax.Array, num_bin: jax.Array,
                      is_categorical: jax.Array, cat_bits: jax.Array,
                      has_categorical: bool) -> jax.Array:
    """:func:`decision_go_left` for every (row, node) pair at once:
    ``bin_vals`` is ``[R, M]`` (row r's bin of node m's feature), the other
    arguments are per node (``[M]``; ``cat_bits`` ``[M, 8]``). The bitset
    word is picked by eight selects, not by a gather: a gather by a
    ``[R, M]`` index is the one thing here a TPU is slow at."""
    b = bin_vals.astype(jnp.int32)
    num_left = numerical_go_left(b, threshold, default_left, default_bin,
                                 missing_type, num_bin)
    if not has_categorical:
        return num_left
    words = cat_bits.shape[1]
    word = jnp.clip(b // 32, 0, words - 1)
    picked = jnp.broadcast_to(cat_bits[:, 0], b.shape)
    for j in range(1, words):
        picked = jnp.where(word == j, cat_bits[:, j], picked)
    bit = jnp.right_shift(picked, (b % 32).astype(jnp.uint32)) & 1
    return jnp.where(is_categorical, bit == 1, num_left)


def compact(keep: jax.Array, *cols: jax.Array, back: bool = False):
    """Stable compaction of one window: the lanes where ``keep`` holds move
    to the front (``back``: to the back) in lane order, in every array of
    ``cols`` alike. The lanes are an array's LAST axis (W, a power of two):
    a window of row ids is ``[W]``, a window of the sorted payload
    ``[SW, W]``, word-major like the payload itself. What the other lanes
    hold afterwards is unspecified.

    The compress network (Hacker's Delight 7-4): a kept lane has to move
    down by the number of dropped lanes before it; stage k moves every
    element whose displacement has bit k set by the static 2^k. The
    displacement grows with the lane, so moves in the order 1, 2, 4, ...
    never land on a live element. log2(W) stages of slice, pad and select:
    no scatter, no gather, no sort, and exact for any dtype. ``back`` is the
    mirror image: up by the number of dropped lanes behind.
    """
    W = keep.shape[0]
    assert W & (W - 1) == 0, W
    lane = jnp.arange(W, dtype=jnp.int32)
    kept = jnp.cumsum(keep.astype(jnp.int32))        # up to and with a lane
    # how far a kept lane moves; 0 marks a lane nobody waits for: a dropped
    # one, or one already left
    goal = W - 1 - (kept[W - 1] - kept) if back else kept - 1
    disp = jnp.where(keep, jnp.abs(goal - lane), 0)

    def shifted(x, s):       # front: lane i + s comes to i; back: lane i - s
        pad = (s, 0, 0) if back else (0, s, 0)
        return lax.pad(x[..., :W - s] if back else x[..., s:],
                       jnp.zeros((), x.dtype),
                       [(0, 0, 0)] * (x.ndim - 1) + [pad])

    s = 1
    while s < W:
        coming = shifted(disp, s)
        take = (coming & s) != 0
        cols = tuple(jnp.where(take, shifted(c, s), c) for c in cols)
        disp = jnp.where(take, coming, jnp.where((disp & s) != 0, 0, disp))
        s *= 2
    return cols


def write_front(buf: jax.Array, vals: jax.Array, start: jax.Array,
                n: jax.Array) -> jax.Array:
    """``buf[..., start : start + n] = vals[..., :n]`` as one contiguous
    window along the last axis (positions: the permutation is ``[N + W]``,
    the sorted payload ``[SW, N + W]``): read W positions at ``start``, keep
    what lies behind ``n``, write W positions back. Nothing but that window
    of ``buf`` is read or written: the payload keeps the one layout it has
    through the tree program (``tests/test_aot_v5e.py`` compiles it for a
    v5e and fails on a whole-payload copy in the split loop). The caller
    keeps ``start + W`` inside ``buf`` (the training buffers carry a W-wide
    tail pad and no start passes their N-th position), so neither slice
    clamps."""
    W = vals.shape[-1]
    assert buf.shape[-1] >= W and buf.shape[:-1] == vals.shape[:-1]
    idx = (0,) * (buf.ndim - 1) + (start,)
    old = lax.dynamic_slice(buf, idx, vals.shape)
    mask = jnp.arange(W, dtype=jnp.int32) < n
    return lax.dynamic_update_slice(buf, jnp.where(mask, vals, old), idx)


def route_window(bufs, win, go_left: jax.Array, go_right: jax.Array,
                 lcur: jax.Array, rcur: jax.Array):
    """One trip of the chunked stable partition, for every array of the
    window ``win`` into its buffer of ``bufs`` (lanes and positions on the
    last axis of both: ``[W]`` row ids into ``[N + W]``, a ``[SW, W]``
    payload window into the ``[SW, N + W]`` payload): the lefts land at
    ``[lcur, lcur + nl)`` in lane order, the rights at ``[rcur - nr, rcur)``
    in REVERSED lane order (the rights of a leaf fill backward from its
    end). Each side is one contiguous run, so it is compacted in the window
    (the rights to the back, then flipped: flipping the compacted window
    costs one reverse where flipping its inputs cost XLA two) and written
    as one masked window — where a scatter by position cost ~30x more on a
    TPU v5e (PERF.md section 6, PR 27). Dead lanes are in neither mask.
    Returns ``(bufs, nl, nr)``: the next trip starts at ``lcur + nl`` and
    ``rcur - nr``."""
    nl = jnp.sum(go_left, dtype=jnp.int32)
    nr = jnp.sum(go_right, dtype=jnp.int32)
    lefts = compact(go_left, *win)
    rights = compact(go_right, *win, back=True)
    bufs = tuple(
        write_front(write_front(buf, lw, lcur, nl), rw[..., ::-1],
                    rcur - nr, nr)
        for buf, lw, rw in zip(bufs, lefts, rights))
    return bufs, nl, nr


_LANES = 128


def _cumsum_by_lanes(x: jax.Array) -> jax.Array:
    """``jnp.cumsum`` of a long 1-D array as sums inside 128-wide rows plus
    the rows' carries, level by level. XLA splits a long cumulative sum
    the same way on a TPU but gives the pieces no op name, so a device
    trace files them under no scope; written out, each piece keeps its
    caller's (``tests/test_aot_v5e.py``: the split happens after lowering,
    where ``tests/test_scopes.py`` cannot see it)."""
    n = x.shape[0]
    if n <= _LANES:
        return jnp.cumsum(x, dtype=x.dtype)
    rows = -(-n // _LANES)
    inner = jnp.cumsum(
        jnp.pad(x, (0, rows * _LANES - n)).reshape(rows, _LANES), axis=1,
        dtype=x.dtype)
    last = inner[:, -1]
    carry = _cumsum_by_lanes(last) - last
    return (inner + carry[:, None]).reshape(-1)[:n]


def position_leaf(leaf_begin: jax.Array, leaf_count: jax.Array,
                  n: int) -> jax.Array:
    """Leaf id of every position of ``[0, n)`` after a tree's last split
    (``[n]`` int32): leaf ``l`` holds the positions ``[leaf_begin[l],
    leaf_begin[l] + leaf_count[l])`` and the leaves with rows tile the
    range. A leaf with no rows (a slot the tree never reached, or a leaf
    that is empty on this shard and so shares another's begin) holds no
    position. The answer is a step function of at most ``L`` steps, so it
    is streamed: the change of leaf id at each step is written at the
    step's position, and one cumulative sum over the positions recovers
    the ids. ``searchsorted`` of the positions in the sorted begins cost
    eight dependent N-row gathers and a ninth to look the id up: 925 ms at
    10.5M rows x 255 leaves on a TPU v5e, where this takes 3.4 (PERF.md
    section 6, PR 32)."""
    ids = jnp.arange(leaf_begin.shape[0], dtype=jnp.int32)
    # a leaf with no rows steps past the end, where nothing is written
    begin = jnp.where(leaf_count > 0, leaf_begin.astype(jnp.int32), n + ids)
    order = jnp.argsort(begin).astype(jnp.int32)
    step = order - jnp.concatenate([jnp.zeros(1, jnp.int32), order[:-1]])
    marks = jnp.zeros(n, jnp.int32).at[begin[order]].add(step, mode="drop")
    return _cumsum_by_lanes(marks)


@functools.partial(jax.jit, static_argnames=("padded_size",))
def split_partition(x_binned: jax.Array, perm: jax.Array,
                    begin: jax.Array, count: jax.Array,
                    feature: jax.Array, threshold: jax.Array,
                    default_left: jax.Array, default_bin: jax.Array,
                    missing_type: jax.Array, num_bin: jax.Array,
                    is_categorical: jax.Array, cat_bitset: jax.Array,
                    padded_size: int):
    """Stably partition one leaf's slice of the permutation array.

    Returns ``(new_perm, left_count)``. Rows with ``go_left`` keep their
    relative order at the front of the slice, the rest follow — matching the
    reference's stable two-way partition (data_partition.hpp:100-123) so that
    ordered-gradient gathers stay deterministic.
    """
    N = perm.shape[0]
    lane = jnp.arange(padded_size, dtype=jnp.int32)
    idx = begin + lane
    safe_idx = jnp.clip(idx, 0, N - 1)
    rows = perm[safe_idx]
    valid = lane < count

    bin_vals = x_binned[rows, feature]
    go_left = decision_go_left(bin_vals, threshold, default_left, default_bin,
                               missing_type, num_bin, is_categorical, cat_bitset)
    go_left = go_left & valid

    # stable 3-way key: valid&left -> 0, valid&right -> 1, padding -> 2;
    # combined with the lane index so one int32 sort is stable
    key = jnp.where(go_left, 0, jnp.where(valid, 1, 2)).astype(jnp.int32)
    order = jnp.argsort(key * padded_size + lane)
    new_slice = rows[order]

    left_count = jnp.sum(go_left, dtype=jnp.int32)
    # scatter back; out-of-range lanes dropped, padding lanes rewrite their
    # original values (they sort after all valid lanes, preserving order)
    new_perm = perm.at[idx].set(new_slice, mode="drop")
    return new_perm, left_count


@functools.partial(jax.jit, static_argnames=("padded_size",))
def split_partition_sorted(x_sorted: jax.Array, gh_sorted: jax.Array,
                           perm: jax.Array, begin: jax.Array,
                           count: jax.Array, feature: jax.Array,
                           threshold: jax.Array, default_left: jax.Array,
                           default_bin: jax.Array, missing_type: jax.Array,
                           num_bin: jax.Array, is_categorical: jax.Array,
                           cat_bitset: jax.Array, padded_size: int):
    """:func:`split_partition` under ``tree_layout=sorted``: the stable
    partition of one leaf's slice is applied PHYSICALLY — the binned row
    payload (``x_sorted``, position-ordered [N, F]) and the gradient
    channels (``gh_sorted``, [N, 2 or 3] f32 grad/hess[/in-bag]) are
    permuted alongside the permutation array, so the next histogram pass
    reads the leaf as a contiguous stream (docs/performance.md).

    The split feature's bin values come straight out of the sorted window
    (a consecutive-index read) instead of a row gather through ``perm``.
    Functional updates (no donation): this is the host-orchestrated oracle
    path; the zero-copy production variant lives inside the fused program.

    Returns ``(new_perm, new_x_sorted, new_gh_sorted, left_count)``.
    """
    N = perm.shape[0]
    lane = jnp.arange(padded_size, dtype=jnp.int32)
    idx = begin + lane
    safe_idx = jnp.clip(idx, 0, N - 1)
    rows = perm[safe_idx]
    valid = lane < count

    bin_vals = x_sorted[safe_idx, feature]
    go_left = decision_go_left(bin_vals, threshold, default_left, default_bin,
                               missing_type, num_bin, is_categorical,
                               cat_bitset)
    go_left = go_left & valid

    key = jnp.where(go_left, 0, jnp.where(valid, 1, 2)).astype(jnp.int32)
    order = jnp.argsort(key * padded_size + lane)
    left_count = jnp.sum(go_left, dtype=jnp.int32)

    # the same scatter-back contract as split_partition: padding lanes sort
    # after all valid lanes in their original order, so they rewrite their
    # own values; out-of-range lanes drop
    new_perm = perm.at[idx].set(rows[order], mode="drop")
    new_x = x_sorted.at[idx].set(x_sorted[safe_idx][order], mode="drop")
    new_gh = gh_sorted.at[idx].set(gh_sorted[safe_idx][order], mode="drop")
    return new_perm, new_x, new_gh, left_count


# ---------------------------------------------------------------------------
# data_residency=stream variants (docs/performance.md "Out-of-core"):
# the split feature's bin values arrive as an UPLOADED buffer (the host
# gathered them from its shards — 1-2 bytes per row over the link instead
# of holding the whole matrix in HBM). Decision + permutation math is
# bit-identical to the resident kernels above; the host mirrors the
# resulting order from the returned go_left flags (stable: lefts then
# rights, each in slice order).
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("padded_size",))
def split_partition_vals(bin_vals: jax.Array, perm: jax.Array,
                         begin: jax.Array, count: jax.Array,
                         threshold: jax.Array, default_left: jax.Array,
                         default_bin: jax.Array, missing_type: jax.Array,
                         num_bin: jax.Array, is_categorical: jax.Array,
                         cat_bitset: jax.Array, padded_size: int):
    """:func:`split_partition` with host-supplied bin values.

    ``bin_vals[i]`` is the split feature's bin for the row at slice lane
    ``i`` (padding lanes arbitrary — they sort last and never count).
    Returns ``(new_perm, left_count, go_left)``; ``go_left`` lets the host
    update its permutation mirror without a second transfer of the slice.
    """
    N = perm.shape[0]
    lane = jnp.arange(padded_size, dtype=jnp.int32)
    idx = begin + lane
    safe_idx = jnp.clip(idx, 0, N - 1)
    rows = perm[safe_idx]
    valid = lane < count

    go_left = decision_go_left(bin_vals.astype(jnp.int32), threshold,
                               default_left, default_bin, missing_type,
                               num_bin, is_categorical, cat_bitset)
    go_left = go_left & valid

    key = jnp.where(go_left, 0, jnp.where(valid, 1, 2)).astype(jnp.int32)
    order = jnp.argsort(key * padded_size + lane)
    left_count = jnp.sum(go_left, dtype=jnp.int32)
    new_perm = perm.at[idx].set(rows[order], mode="drop")
    return new_perm, left_count, go_left


@functools.partial(jax.jit, static_argnames=("padded_size",))
def split_partition_sorted_vals(bin_vals: jax.Array, gh_sorted: jax.Array,
                                perm: jax.Array, begin: jax.Array,
                                count: jax.Array, threshold: jax.Array,
                                default_left: jax.Array,
                                default_bin: jax.Array,
                                missing_type: jax.Array, num_bin: jax.Array,
                                is_categorical: jax.Array,
                                cat_bitset: jax.Array, padded_size: int):
    """:func:`split_partition_sorted` with host-supplied bin values: the
    binned payload lives in HOST shards under stream residency, so only
    ``perm`` and the device-resident gradient channels are permuted here;
    the host applies the same stable order to its payload slice from the
    returned ``go_left`` flags. Returns
    ``(new_perm, new_gh_sorted, left_count, go_left)``."""
    N = perm.shape[0]
    lane = jnp.arange(padded_size, dtype=jnp.int32)
    idx = begin + lane
    safe_idx = jnp.clip(idx, 0, N - 1)
    rows = perm[safe_idx]
    valid = lane < count

    go_left = decision_go_left(bin_vals.astype(jnp.int32), threshold,
                               default_left, default_bin, missing_type,
                               num_bin, is_categorical, cat_bitset)
    go_left = go_left & valid

    key = jnp.where(go_left, 0, jnp.where(valid, 1, 2)).astype(jnp.int32)
    order = jnp.argsort(key * padded_size + lane)
    left_count = jnp.sum(go_left, dtype=jnp.int32)
    new_perm = perm.at[idx].set(rows[order], mode="drop")
    new_gh = gh_sorted.at[idx].set(gh_sorted[safe_idx][order], mode="drop")
    return new_perm, new_gh, left_count, go_left


# graftir IR contract
from ..analysis.ir.contracts import register_program

register_program(
    "partition.split_partition", collective_free=True, max_traces=6,
    notes="host-serial permutation update retraces per pow2 leaf bucket "
          "by design; the 1603-row scenario exercises 4 buckets")
