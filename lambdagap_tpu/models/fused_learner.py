"""Fused whole-tree-on-device leaf-wise learner.

The TPU production path: the entire leaf-wise tree build — histogram
construction, best-split scans, the argmax over leaves, and the data
partition — runs as ONE jitted program per tree, with zero host round-trips.
This is the TPU answer to the reference's CUDA learner
(reference: src/treelearner/cuda/cuda_single_gpu_tree_learner.cpp:158-260),
which keeps all state device-resident but still drives each split from the
host: here even the per-split control flow (which leaf to split next) stays
on device, because a D2H sync per split (254 a tree) would serialize the
device behind the host link's latency.

Structure: ``fori_loop`` over the ``num_leaves-1`` splits. Row-sized work
(gathering a leaf's rows for histograms; partitioning the chosen leaf) runs
in inner ``while_loop``s over fixed-width chunks — static shapes, dynamic
trip counts — so device time is proportional to actual rows touched, keeping
the histogram-subtraction trick's O(min(|L|,|R|)) economics
(reference: serial_tree_learner.cpp:408-476) inside a fully-compiled program.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..config import Config
from ..data.dataset import BinnedDataset
from ..obs.telemetry import device_scope as _scope
from ..ops.histogram import gh_contract, write_children
from ..ops.partition import decision_go_left, position_leaf, route_window
from ..ops.split import (K_MIN_SCORE, SplitParams, calculate_leaf_output,
                         gather_threshold_split, leaf_gain, per_feature_best)
from .learner import SerialTreeLearner, _next_pow2
from .tree import Tree

HIST_C = 3


class DeviceTree(NamedTuple):
    """One trained tree, resident on device."""
    node_feature: jax.Array      # i32 [NODES] (inner feature index)
    node_threshold: jax.Array    # i32 [NODES]
    node_default_left: jax.Array  # bool [NODES]
    node_is_cat: jax.Array       # bool [NODES]
    node_cat_bits: jax.Array     # u32 [NODES, 8]
    node_left: jax.Array         # i32 [NODES] (>=0 node, <0 ~leaf)
    node_right: jax.Array        # i32 [NODES]
    node_gain: jax.Array         # f32 [NODES]
    node_value: jax.Array        # f32 [NODES] parent output
    node_weight: jax.Array       # f32 [NODES] parent hess sum
    node_count: jax.Array        # f32 [NODES]
    leaf_value: jax.Array        # f32 [L]
    leaf_weight: jax.Array       # f32 [L]
    leaf_count: jax.Array        # f32 [L]
    leaf_depth: jax.Array        # i32 [L]
    leaf_parent_node: jax.Array  # i32 [L]
    num_leaves: jax.Array        # i32 scalar
    row_leaf: jax.Array          # i32 [N] leaf id per training row
    # i32 [NODES, 2] per realised split: local rows the partition pass
    # visited, local rows of the child whose histogram was built. Read by
    # the telemetry's work counts alone (FusedTreeLearner.work_counts) and
    # dropped with row_leaf; the host-streamed builds leave it out
    work: Optional[jax.Array] = None


# what materialization fetches: everything that describes the tree
_HOST_FIELDS = tuple(f for f in DeviceTree._fields
                     if f not in ("row_leaf", "work"))


class FusedTreeLearner(SerialTreeLearner):
    """Whole-tree-per-dispatch learner. Reuses SerialTreeLearner's dataset
    plumbing (bin meta, split params, feature sampling)."""

    #: smallest row window _pick_chunk resolves
    min_chunk = 1 << 12

    def __init__(self, dataset: BinnedDataset, config: Config) -> None:
        super().__init__(dataset, config)
        if self.residency == "stream":
            # out-of-core mode (docs/performance.md): the binned matrix
            # stays in host shards; _train_tree_stream drives per-tree
            # multi-dispatch builds whose kernels replicate the fused
            # program's math window-for-window. EFB bundling is skipped
            # (its construction needs the full resident matrix) and the
            # options _stream_blockers lists fell back to hbm upstream.
            self.bundled = False
            self.Bb = self.B
            self.chunk = self._pick_chunk()
            self.quant = False
            self.quant_exact = False
            self.forced_seq = None
            self._need_step_keys = False
            self.axis: Optional[str] = None
            self.voting = False
            self.pack32 = False
            self._srows_dummy = jnp.zeros((1, 1), jnp.uint32)
            self.last_row_leaf: Optional[jax.Array] = None
            self._init_stream_jits()
            return
        # EFB: histograms and partitions run over the bundled matrix when
        # the dataset built one; histograms are un-bundled back to feature
        # space before every split scan, and partition decisions decode the
        # chosen feature's bin from its bundle column
        bun = dataset.ensure_bundle(config)
        self.bundled = bun is not None
        if self.bundled:
            hx = bun.cols
            self.Bb = _next_pow2(max(bun.num_bins))
            self.bcol = jnp.asarray(bun.col_of)
            self.boff = jnp.asarray(bun.off_of)
            self.bsingle = jnp.asarray(bun.single)
            from ..data.bundling import unbundle_map
            src, kind = unbundle_map(
                bun, np.asarray(dataset.feature_num_bins, np.int32),
                np.asarray([dataset.mappers[j].default_bin
                            for j in dataset.used_features], np.int32),
                self.B, self.Bb)
            self.ub_src = jnp.asarray(src)
            self.ub_kind = jnp.asarray(kind)
        else:
            hx = dataset.binned
            self.Bb = self.B
        self._place_binned(np.asarray(hx))
        self.chunk = self._pick_chunk()
        # quantized-gradient training (reference: GradientDiscretizer,
        # src/treelearner/gradient_discretizer.hpp): int8 grad/hess levels
        # with stochastic rounding; on TPU the histogram contraction runs
        # as an int8 MXU matmul with exact int32 accumulation
        from ..ops.hist_pallas import MAX_QUANT_BINS, exact_accum_limit
        self.quant = bool(config.use_quantized_grad)
        # int8-level histograms accumulate into int32 only WITHIN one
        # W-row chunk (cross-chunk accumulation is float32, chunk_hist), so
        # the worst in-chunk sum is chunk*MAX_QUANT_BINS — overflow would
        # need a chunk of ~16.9M rows; guard the configurable chunk width,
        # not num_data
        if self.chunk * MAX_QUANT_BINS >= 2**31 - 1:
            from ..utils import log
            log.fatal("tpu_rows_per_block=%d makes the histogram chunk too "
                      "large for int32 accumulation", config.tpu_rows_per_block)
        # exact integer histogram reduction (reference: the 16/32-bit integer
        # reduce paths, src/treelearner/data_parallel_tree_learner.cpp:283-298):
        # accumulate RAW int levels across chunks (int32 under Pallas,
        # integer-valued f32 under the one-hot path) and apply the gradient
        # scales only after the cross-shard psum. Integer sums are
        # order-independent, so the distributed reduction is deterministic
        # for any shard count. Falls back to per-chunk scaled f32 when the
        # worst-case level sum could overflow the accumulator
        # (exact_accum_limit — the same helper config validation queries
        # for the num_grad_quant_bins bound).
        if self.quant:
            qb = config.num_grad_quant_bins   # config-validated int in
            # [2, MAX_QUANT_BINS]; the old silent min(.., 127) cap is gone
            limit = exact_accum_limit(self.hist_impl)
            self.quant_exact = dataset.num_data * qb < limit
            if not self.quant_exact:
                from ..utils import log
                log.warning("quantized histogram level sums may exceed the "
                            "exact accumulator range (%d rows x %d levels); "
                            "using per-chunk scaled float32 accumulation",
                            dataset.num_data, qb)
        else:
            self.quant_exact = False
        if self.quant:
            self._qkey = jax.random.PRNGKey(config.data_random_seed + 7919)
        # forced splits (reference: serial_tree_learner.cpp:624 ForceSplits):
        # the BFS order fixes which leaf id each forced node splits (root=0;
        # the split at step k hands its right child leaf id k+1), so the
        # whole forcing schedule is three static arrays consumed by the
        # fused program's step loop; an invalid forced split flips the
        # carried `forcing` flag off (the abort_last_forced_split analog)
        self.forced_seq = None
        if self.forced_json is not None:
            self.forced_seq = self._build_forced_seq(config.num_leaves - 1)
        self._need_step_keys = (self.extra_on
                                or config.feature_fraction_bynode < 1.0)
        if self._need_step_keys:
            # independent streams, like the host learner's separate RNGs:
            # extra_seed drives random thresholds, feature_fraction_seed
            # drives by-node sampling — changing one never perturbs the other
            self._ekey = jax.random.PRNGKey(config.extra_seed)
            self._bkey = jax.random.PRNGKey(config.feature_fraction_seed + 7)
        # when set (FusedDataParallelTreeLearner), _train_tree_impl runs as
        # the per-shard body of a shard_map over this mesh axis: rows are
        # sharded, histograms are psum-ed over ICI after each chunked local
        # accumulation, and everything derived from histograms (gains, split
        # choices, leaf values) is replicated-by-construction
        self.axis: Optional[str] = None
        # voting mode: keep histograms local, vote top-k features, psum
        # only voted columns (set by FusedVotingParallelTreeLearner)
        self.voting: bool = False
        # u32-lane packing of the packed row matrix (the pack32 block in
        # _pack_rows); the stream path above keeps bin-dtype columns
        self.pack32 = True
        # tree_layout=sorted (docs/performance.md): the packed row matrix
        # is (re)built leaf-ordered by a separate jitted pre-pass per tree
        # — dispatched under the layout_apply telemetry span so its cost
        # tiles the iteration wall — and then carried through the fused
        # program, which applies the permutation delta of each split
        # physically to only that leaf's slice. (Not donated: no output
        # has its shape, so jit could not alias it and would only warn
        # "Some donated buffers were not usable" at every compile.)
        self._srows_dummy = jnp.zeros((1, 1), jnp.uint32)
        self._layout_jit = jax.jit(self._build_sorted_impl,
                                   static_argnames=("has_mask",))
        self._train_jit = jax.jit(
            self._train_tree_impl, static_argnames=("has_mask",))
        self.last_row_leaf: Optional[jax.Array] = None

    def _build_forced_seq(self, nodes: int):
        """Flatten the forced-split JSON into per-step (leaf, feature, bin)
        arrays in BFS order. Truncates at the first unmappable node."""
        fl, ff, ft = [], [], []
        q = [(self.forced_json, 0)]
        while q and len(fl) < nodes:
            node, leaf = q.pop(0)
            fb = self._forced_bin(node)
            if fb is None:
                break
            k, thr_bin = fb
            step = len(fl)
            fl.append(leaf)
            ff.append(k)
            ft.append(thr_bin)
            for key, child in (("left", leaf), ("right", step + 1)):
                ch = node.get(key)
                if (isinstance(ch, dict) and "feature" in ch
                        and "threshold" in ch):
                    q.append((ch, child))
        if not fl:
            return None
        on = np.zeros(nodes, dtype=bool)
        on[:len(fl)] = True
        pad = nodes - len(fl)
        return (np.asarray(fl + [0] * pad, np.int32),
                np.asarray(ff + [0] * pad, np.int32),
                np.asarray(ft + [0] * pad, np.int32), on)

    # device-layout hooks (overridden by FusedDataParallelTreeLearner) ----
    def _place_binned(self, hx: np.ndarray) -> None:
        """Upload the row-major binned matrix plus a column-major copy for
        cheap feature-column reads while partitioning (the analog of
        CUDAColumnData next to CUDARowData,
        reference: src/io/cuda/cuda_column_data.cpp). Under
        ``tree_layout=sorted`` the partition decodes the split feature from
        the sorted window itself, so the column-major copy would be N*C
        dead bytes of HBM — a tiny placeholder keeps the jit signature."""
        self.hx_rows = jnp.asarray(hx)
        if self.layout == "sorted":
            self.x_cols = jnp.zeros((1, 1), self.hx_rows.dtype)
        else:
            self.x_cols = jnp.asarray(np.ascontiguousarray(hx.T))

    # packed row-matrix layout -------------------------------------------
    def _window(self, N: int) -> int:
        """Chunk window of the while-loop'd row passes (shared by the
        training program and the sorted-layout pre-pass, whose pad row
        count must match)."""
        return min(self.chunk, _next_pow2(N))

    def _packed_meta(self, has_mask: bool):
        """Static column layout of the packed row matrix, in bin-dtype
        columns after the C binned columns: (gh_cols, q_cols, mask_col).

        * non-quant: 2 f32 grad/hess values bitcast to 8 (uint8) / 4
          (uint16) columns; the bagging mask rides one more column.
        * quant + sorted layout: the int8 (g_q, h_q) pair rides 2 uint8 /
          1 uint16 column(s) (+ mask column) so the physically reordered
          buffer carries everything the histogram pass reads.
        * quant + gather layout: nothing extra — gq/hq/mask are gathered
          by row index alongside the bins (the historical layout).
        """
        u8 = self.hx_rows.dtype == jnp.uint8
        if self.quant:
            if self.layout == "sorted":
                return 0, (2 if u8 else 1), bool(has_mask)
            return 0, 0, False
        return (8 if u8 else 4), 0, bool(has_mask)

    def _pack_rows(self, grad, hess, row_mask, x_rows, gq, hq,
                   has_mask: bool):
        """Pack the binned rows plus their per-row channels into ONE
        row-major matrix in the bin dtype, bitcast to u32 lanes (pack32):
        the histogram pass then runs ONE random gather per row window
        instead of two (the 8 B gh gather pays a random access of its
        own beside the row fetch), and one u32 element carrying 4 binned
        uint8 columns (2 uint16) cuts the hot pass's element count ~4x
        (2x); lanes decode with one bitcast after the fetch (reference
        analog: cuda_row_data.hpp:32-117 packs rows by bit width for the
        same reason). Costs: one streaming repack pass per tree
        (`layout_device_ms` 18.767 on higgs-train, 30.41 on
        istella-s-train; ledger, PR 32) and a second resident copy of the
        binned matrix, ~N*(C+8) bytes."""
        gh_cols, q_cols, mask_col = self._packed_meta(has_mask)
        parts = [x_rows]
        if gh_cols:
            gh2 = jnp.stack([grad, hess], axis=1)           # [N, 2] f32
            if x_rows.dtype == jnp.uint16:
                ghb = lax.bitcast_convert_type(gh2, jnp.uint16)   # [N,2,2]
            else:
                ghb = lax.bitcast_convert_type(gh2, jnp.uint8)    # [N,2,4]
            parts.append(ghb.reshape(ghb.shape[0], -1))
        if q_cols:
            if x_rows.dtype == jnp.uint16:
                parts.append(lax.bitcast_convert_type(
                    jnp.stack([gq, hq], axis=1), jnp.uint16)[:, None])
            else:
                parts.append(jnp.stack(
                    [lax.bitcast_convert_type(gq, jnp.uint8),
                     lax.bitcast_convert_type(hq, jnp.uint8)], axis=1))
        if mask_col:
            parts.append(row_mask.astype(x_rows.dtype)[:, None])
        packed = parts[0] if len(parts) == 1 else jnp.concatenate(parts,
                                                                  axis=1)
        if self.pack32:
            lane_n = 4 if packed.dtype == jnp.uint8 else 2
            P0 = packed.shape[1]
            padc = (-P0) % lane_n
            if padc:
                packed = jnp.concatenate(
                    [packed, jnp.zeros((packed.shape[0], padc),
                                       packed.dtype)], axis=1)
            packed = lax.bitcast_convert_type(
                packed.reshape(packed.shape[0], (P0 + padc) // lane_n,
                               lane_n), jnp.uint32)          # [N, P32]
        return packed

    def _build_sorted_impl(self, grad, hess, row_mask, x_rows, gq, hq, *,
                           has_mask: bool):
        """The ``tree_layout=sorted`` pre-pass: (re)build the physically
        leaf-ordered packed row buffer for one tree. Each tree starts from
        the identity permutation, so this is a pure streaming repack (no
        gather); gradients change every iteration, which is why the buffer
        cannot persist across trees. The W trailing pad rows let every
        window read in the fused program be a clamp-free dynamic slice
        (the same invariant as the permutation buffer's).

        The payload is WORD-MAJOR, ``[SW, N + W]``: word w of the row at
        position p is ``[w, p]``, so a window of W rows is a slice along
        the minor axis. A TPU keeps a narrow 2-D array with its long axis
        minor whatever its shape says, and the partition and histogram
        loops of the tree program read it so; held as ``[N + W, SW]``, the
        split loop's carry and the copy-back loop chose row-major and XLA
        converted the WHOLE buffer between the two twice a split (1,940 of
        ``istella-s-train``'s 3,990 device ms at 57 words; none at
        ``higgs-train``'s 9; PERF.md section 6, PR 36). With the long axis
        minor in the shape itself there is one layout to choose, and
        ``tests/test_aot_v5e.py`` compiles the tree program for a v5e and
        fails on any instruction of the split loop that produces the whole
        payload."""
        with _scope("layout_apply"):
            packed = self._pack_rows(grad, hess, row_mask, x_rows, gq, hq,
                                     has_mask)
            W = self._window(x_rows.shape[0])
            return jnp.pad(packed.T, ((0, 0), (0, W)))

    def _pick_chunk(self) -> int:
        """Chunk window for the while-loop'd row passes: small enough that a
        deep (small) leaf doesn't pay a huge padded window of gather/scan
        work, large enough that root-sized passes don't drown in per-trip
        overhead.

        Sized off HALF the average leaf population N/num_leaves, not N:
        padding waste across one tree is ~num_leaves * W/2 rows against
        ~N*log2(L) total row-touches, so a window near the deep-leaf size
        keeps the waste near a tenth of the rows touched where an
        N-scaled window pads several times that. Inside one compiled
        program extra while-loop trips cost only loop control, not kernel
        launches (`partition_us_per_trip` 149.92 and 142.68; ledger, PR
        32). The two cells resolve W = 32,768 (10.5M rows) and 8,192
        (3.41M); W has not been swept on the benchmark.

        The learners that shard rows size it off their LOCAL rows
        (``n_loc``) with a lower floor (``min_chunk``): per-shard leaf
        populations are n_dev-times smaller, so a wide window is mostly
        padding. Window size cannot change quantized results (integer
        accumulation is window-invariant); stream and hbm residencies
        MUST agree on W per grid — it is the accumulation-order contract
        the stream mirrors replay."""
        n = int(getattr(self, "n_loc", self.num_data))   # this shard's rows
        cap = max(int(self.config.tpu_rows_per_block) * 16, 1 << 12)
        per_leaf = n // max(self.config.num_leaves, 8)
        return min(max(_next_pow2(max(per_leaf // 2, 1)), self.min_chunk),
                   cap)

    # ------------------------------------------------------------------
    def train_device(self, grad: jax.Array, hess: jax.Array,
                     row_mask: Optional[jax.Array] = None) -> DeviceTree:
        if self.residency == "stream":
            rec = self._train_tree_stream(grad, hess, row_mask)
            self.last_row_leaf = rec.row_leaf
            return rec
        fmask = self._feature_mask()
        mask = row_mask if row_mask is not None else jnp.ones(1, dtype=bool)
        if self.quant:
            from ..ops.hist_pallas import quantize_gradients
            self._qkey, sub = jax.random.split(self._qkey)
            gq, hq, gs, hs = quantize_gradients(
                grad, hess, sub, self.config.num_grad_quant_bins,
                self.config.stochastic_rounding)
        else:
            gq = hq = jnp.zeros(1, jnp.int8)
            gs = hs = jnp.float32(1.0)
        if self._need_step_keys:
            self._ekey, e = jax.random.split(self._ekey)
            self._bkey, b = jax.random.split(self._bkey)
            ekey = jnp.stack([e, b])            # [2, 2]: extra / by-node
        else:
            ekey = jnp.zeros((2, 2), jnp.uint32)
        if self.layout == "sorted":
            # the leaf-ordered packed buffer is rebuilt per tree; the span
            # makes its (streaming-repack) cost tile the iteration wall —
            # the in-program per-split permutation-apply rides the tree
            # span like the rest of the fused program
            with self.telemetry.phase("layout_apply"):
                srows = self._layout_jit(grad, hess, mask, self.hx_rows,
                                         gq, hq,
                                         has_mask=row_mask is not None)
        else:
            srows = self._srows_dummy
        rec = self._train_jit(
            grad, hess, mask, fmask, self.hx_rows, self.x_cols, srows,
            gq, hq, gs, hs, ekey, has_mask=row_mask is not None)
        self.last_row_leaf = rec.row_leaf
        return rec

    def train(self, grad, hess, row_mask=None) -> Tree:
        """Host-Tree interface (used by tests / non-bench paths)."""
        return self.materialize(self.train_device(grad, hess, row_mask))

    # ------------------------------------------------------------------
    def materialize_batch(self, recs) -> list:
        """Fetch MANY DeviceTrees in one transfer: each field is stacked
        across trees on device, so the D2H cost is one buffer per field
        instead of one per (tree, field) — ~16 round-trips instead of
        ~16*T."""
        if not recs:
            return []
        stacked = {k: jnp.stack([getattr(r, k) for r in recs])
                   for k in _HOST_FIELDS}
        h = jax.device_get(stacked)
        return [self._tree_from_host({k: v[i] for k, v in h.items()})
                for i in range(len(recs))]

    def materialize(self, rec: DeviceTree) -> Tree:
        """Fetch a DeviceTree and build the host Tree model (one transfer;
        row_leaf stays on device — it is O(N))."""
        # graftlint: disable=R1 — THE materialization boundary of the fused
        # learner: one compact O(leaves) struct transfer per tree builds
        # the host model; scores already updated on device, so this is the
        # only per-tree D2H of the sync-free path
        h = jax.device_get({k: getattr(rec, k) for k in _HOST_FIELDS})
        return self._tree_from_host(h)

    def work_counts(self, host) -> dict:
        """The telemetry's per-tree work counts from ``(DeviceTree.work,
        num_leaves)`` fetched to the host: realised splits, the rows and
        ``while`` trips of the partition passes, and of the histogram
        passes (the root's plus the smaller child's of every split) and,
        under the Pallas kernel, ``hist_row_tiles``: each window's kernel
        call's padded rows times its feature tiles, summed (a learner that
        shards columns leaves it out). Python ints: 10.5M rows x 254 splits
        do not fit int32."""
        work, num_leaves = host
        n = int(getattr(self, "n_loc", self.num_data))   # this shard's rows
        w = self._window(n)
        splits = max(int(num_leaves) - 1, 0)
        part, small = np.asarray(work[:splits], np.int64).T

        def trips(rows):
            return int(((rows + w - 1) // w).sum())
        out = {"splits": splits,
               "partition_rows": int(part.sum()),
               "partition_trips": trips(part),
               "hist_rows": n + int(small.sum()),
               "hist_trips": -(-n // w) + trips(small)}
        if self.hist_impl == "pallas" \
                and getattr(self, "feat_axis", None) is None:
            from ..ops.hist_pallas import row_tiles
            out["hist_row_tiles"] = out["hist_trips"] * row_tiles(
                w, int(self.hx_rows.shape[1]), self.Bb)
        return out

    def _tree_from_host(self, h) -> Tree:
        L = int(h["num_leaves"])
        nodes = max(L - 1, 0)
        tree = Tree(max_leaves=self.config.num_leaves)
        tree.num_leaves = max(L, 1)
        mt_codes = {"None": 0, "Zero": 1, "NaN": 2}
        for k in range(nodes):
            fi = int(h["node_feature"][k])
            j = self.dataset.used_features[fi]
            mapper = self.dataset.mappers[j]
            tree.split_feature.append(j)
            tree.split_feature_inner.append(fi)
            thr_bin = int(h["node_threshold"][k])
            tree.threshold_bin.append(thr_bin)
            tree.threshold_real.append(mapper.bin_to_value(thr_bin))
            tree.default_left.append(bool(h["node_default_left"][k]))
            tree.missing_type.append(mt_codes[mapper.missing_type])
            tree.left_child.append(int(h["node_left"][k]))
            tree.right_child.append(int(h["node_right"][k]))
            tree.split_gain.append(float(h["node_gain"][k]))
            is_cat = bool(h["node_is_cat"][k])
            tree.is_categorical.append(is_cat)
            bits = np.asarray(h["node_cat_bits"][k], dtype=np.uint32)
            tree.cat_bitset.append(bits)
            tree.cat_bitset_real.append(
                self._cat_bitset_real(fi, bits) if is_cat
                else np.zeros(8, np.uint32))
            tree.internal_value.append(float(h["node_value"][k]))
            tree.internal_weight.append(float(h["node_weight"][k]))
            tree.internal_count.append(int(h["node_count"][k]))
        Lb = tree.max_leaves
        tree.leaf_value[:Lb] = h["leaf_value"][:Lb]
        tree.leaf_weight[:Lb] = h["leaf_weight"][:Lb]
        tree.leaf_count[:Lb] = h["leaf_count"][:Lb].astype(np.int64)
        tree.leaf_depth[:Lb] = h["leaf_depth"][:Lb]
        tree.leaf_parent[:Lb] = h["leaf_parent_node"][:Lb]
        return tree

    # ------------------------------------------------------------------
    # the fused program
    # ------------------------------------------------------------------
    def _train_tree_impl(self, grad, hess, row_mask, fmask, x_rows, x_cols,
                         srows, gq, hq, gs, hs, ekey, *, has_mask: bool):
        """One whole tree as a single XLA program.

        Design notes for the ``fori_loop`` body (the per-split step):

        * No ``lax.cond``: an un-splittable step is expressed by masking —
          the partition/histogram loops get a zero row count (zero trips)
          and every state write lands on a dump row (index ``L`` / ``NODES``)
          instead of branching. This keeps the loop body straight-line and
          lets XLA alias the large carried buffers in place (a cond joining
          two 20+ MB states forced copies).
        * Per-leaf and per-node bookkeeping lives in a few consolidated
          matrices (``leaf_f``/``leaf_i``/``node_f``/``node_i``) so one split
          costs a handful of dynamic-update-slices instead of ~30 one-column
          kernels — per-split fixed cost is mostly kernel-launch count.
        * Both children's best-split scans run in one vmapped call.
        """
        cfg = self.config
        N = x_rows.shape[0]       # LOCAL rows (== num_data unless sharded)
        F = self.num_features
        B = self.B
        L = cfg.num_leaves
        NODES = max(L - 1, 1)
        W = min(self.chunk, _next_pow2(N))
        p = self.params
        max_depth = cfg.max_depth
        # x_rows [N, C] (bundled when EFB active) / x_cols [C, N] arrive as
        # jit ARGUMENTS: a closed-over matrix would be inlined into the HLO
        # as a dense constant (300+ MB at HIGGS size)
        C = x_rows.shape[1]
        Bb = self.Bb                    # bins per stored column
        bundled = self.bundled
        num_bins = self.num_bins_arr
        default_bins = self.default_bins_arr
        missing_types = self.missing_types_arr
        is_cat_arr = self.is_categorical_arr
        has_cat = self.has_categorical
        mono_on = self.mono_on
        mono_arr = self.mono_arr
        # monotone 'intermediate' runs IN-PROGRAM: sibling-output child
        # bounds + the cross-leaf constraint propagation as a vectorized
        # per-split state update over the leaf_f bounds columns, with eager
        # re-scans of tightened leaves (reference:
        # monotone_constraints.hpp:560-850 IntermediateLeafConstraints)
        inter = mono_on and self.mono_method == "intermediate"
        NPW_N = (NODES + 31) // 32 if inter else 1
        with _scope("tree_init"):
            lane = jnp.arange(W, dtype=jnp.int32)
            bin_iota = jnp.arange(Bb, dtype=x_rows.dtype)
        quant = self.quant
        qexact = self.quant_exact
        # physical row layout (docs/performance.md). gather: grad+hess (and
        # the bagging mask) are PACKED INTO the binned row matrix and the
        # histogram pass gathers one packed row per visit (_pack_rows has
        # the full story + measured history). sorted: the packed matrix
        # arrives PRE-BUILT and leaf-ordered in ``srows`` (the layout_apply
        # pre-pass) and is carried through the split loop, which applies
        # each split's permutation delta physically to only that leaf's
        # slice — the histogram pass then reads contiguous streams at
        # stream bandwidth instead of issuing row gathers.
        layout_sorted = self.layout == "sorted"
        gh_cols, q_cols, mask_col = self._packed_meta(has_mask)
        pack32 = self.pack32
        if layout_sorted:
            packed_rows = None          # rows live in the carried srows
            SW = srows.shape[0]         # word-major: [SW, N + W]
        else:
            with _scope("tree_init"), _scope("pack_rows"):
                packed_rows = self._pack_rows(grad, hess, row_mask, x_rows,
                                              gq, hq, has_mask)

        def unpack(prow):
            """``[W, SW]`` u32 lanes -> bin-dtype columns (no-op when
            pack32 is off)."""
            if pack32:
                return lax.bitcast_convert_type(
                    prow, x_rows.dtype).reshape(prow.shape[0], -1)
            return prow

        def srow_slice(buf, start):
            """Contiguous W-row window of the (N+W padded) sorted payload
            — a dynamic-slice DMA, the sorted layout's whole point. The
            payload is word-major (_build_sorted_impl), and so is the
            window: ``[SW, W]``, rows on the minor axis. The partition
            network moves it as it is; the two decoders transpose the
            window (1.9 MB at 57 words, never the buffer)."""
            # same pad invariant as perm_slice: starts stay <= N
            assert buf.shape == (SW, N + W)
            return lax.dynamic_slice(buf, (0, start), (SW, W))

        def perm_slice(perm, start):
            """Contiguous W-row window of the (N+W padded) permutation —
            a dynamic-slice DMA, not a gather."""
            # every start is <= N and the buffer carries one full window of
            # padding, so the dynamic_slice clamp can never fire
            assert perm.shape[0] == N + W
            return lax.dynamic_slice(perm, (start,), (W,))

        def chunk_hist(perm, srows_c, begin, count, acc, c):
            """Histogram of the leaf rows at positions
            begin+cW : begin+(c+1)W — a permutation gather under the
            gather layout, a contiguous window DMA under sorted."""
            if layout_sorted:
                rows = None
                prow = unpack(srow_slice(srows_c, begin + c * W).T)
            else:
                rows = perm_slice(perm, begin + c * W)
                with _scope("hist_gather"):
                    prow = unpack(packed_rows[rows])    # [W, C(+gh+mask)]
            valid = (c * W + lane) < count
            bins = prow[:, :C]
            if quant:
                if layout_sorted:
                    # int8 levels decoded out of the sorted payload
                    if x_rows.dtype == jnp.uint16:
                        qw = lax.bitcast_convert_type(prow[:, C], jnp.int8)
                        gq_w, hq_w = qw[:, 0], qw[:, 1]
                    else:
                        gq_w = lax.bitcast_convert_type(prow[:, C],
                                                        jnp.int8)
                        hq_w = lax.bitcast_convert_type(prow[:, C + 1],
                                                        jnp.int8)
                    if mask_col:
                        valid = valid & (prow[:, C + q_cols] > 0)
                else:
                    with _scope("hist_gather"):
                        gq_w, hq_w = gq[rows], hq[rows]
                        if has_mask:
                            valid = valid & row_mask[rows]
                qscale = jnp.stack([gs, hs, jnp.float32(1.0)])
                if self.hist_impl == "pallas":
                    from ..ops.hist_pallas import hist_pallas_q, pack_ghq8
                    live = jnp.clip(count - c * W, 0, W)
                    ghq = pack_ghq8(gq_w, hq_w, valid)
                    hist_i = hist_pallas_q(bins, ghq, Bb, live)
                    if qexact:          # raw level sums; scaled post-psum
                        return acc + hist_i
                    return acc + hist_i.astype(jnp.float32) * qscale
                gsc = jnp.float32(1.0) if qexact else gs
                hsc = jnp.float32(1.0) if qexact else hs
                g = jnp.where(valid, gq_w.astype(jnp.float32) * gsc, 0.0)
                h = jnp.where(valid, hq_w.astype(jnp.float32) * hsc, 0.0)
                gh = jnp.stack([g, h, valid.astype(jnp.float32)], axis=1)
                onehot = (bins[:, :, None] == bin_iota).astype(jnp.bfloat16)
                part = gh_contract(gh, onehot.reshape(W, C * Bb),
                                   self.hist_precision)
                return acc + part.reshape(HIST_C, C, Bb).transpose(1, 2, 0)
            if has_mask:
                valid = valid & (prow[:, C + gh_cols] > 0)
            ghr = lax.bitcast_convert_type(
                prow[:, C:C + gh_cols].reshape(W, 2, gh_cols // 2),
                jnp.float32)                            # [W, 2]
            if self.hist_impl == "pallas":
                from ..ops.hist_pallas import hist_pallas, pack_gh8
                live = jnp.clip(count - c * W, 0, W)
                gh8 = pack_gh8(ghr[:, 0], ghr[:, 1], valid)
                return acc + hist_pallas(bins, gh8, Bb, live)
            g = jnp.where(valid, ghr[:, 0], 0.0)
            h = jnp.where(valid, ghr[:, 1], 0.0)
            gh = jnp.stack([g, h, valid.astype(jnp.float32)], axis=1)
            onehot = (bins[:, :, None] == bin_iota).astype(jnp.bfloat16)
            part = gh_contract(gh, onehot.reshape(W, C * Bb),
                               self.hist_precision)
            return acc + part.reshape(HIST_C, C, Bb).transpose(1, 2, 0)

        # rows a leaf holds, exactly, on the 1-D data mesh: the psum-ed
        # float32 count channel rounds past 2^24 rows (a bin, a node) and
        # the larger child's count is its parent's minus the smaller's, so a
        # leaf under a node of that size could miss its true count by a few
        # rows (996,173 read for 996,177 at 20M rows: test_mesh_cell.py).
        # Each shard keeps its own in-bag counts as integers instead (a
        # shard's float32 sums stay exact: at most its rows), and one psum
        # of them at the end of the tree gives the model's leaf and node
        # counts
        count_rows = self.axis is not None and not self.voting \
            and getattr(self, "feat_axis", None) is None

        def leaf_hist(perm, srows_c, begin, count):
            # jax.named_scope labels below tag the traced ops so profiler
            # windows (obs/profile.py) show the same histogram/partition/
            # split phase structure the host-side telemetry reports

            def body(st):
                c, acc = st
                return c + 1, chunk_hist(perm, srows_c, begin, count, acc, c)

            acc_dtype = (jnp.int32 if qexact and self.hist_impl == "pallas"
                         else jnp.float32)
            with _scope("histogram"):
                nch = (count + W - 1) // W
                _, hist = lax.while_loop(
                    lambda st: st[0] < nch, body,
                    (jnp.int32(0), jnp.zeros((C, Bb, HIST_C), acc_dtype)))
                # every column's bins hold each in-bag row once
                rows = jnp.sum(hist[0, :, 2]).astype(jnp.int32) \
                    if count_rows else None
            if self.axis is not None and not self.voting:
                # the one collective per split: local chunk loops may run
                # different trip counts per shard (local leaf sizes differ),
                # but every shard reaches this psum exactly once per step.
                # In quant_exact mode the reduction is over raw integer level
                # sums — order-independent, hence deterministic for any shard
                # count (reference: the 16/32-bit integer ReduceScatter at
                # data_parallel_tree_learner.cpp:283-298).
                # Voting mode keeps histograms LOCAL: the collective moves
                # into best_of as a top-k vote + psum of only the voted
                # columns (reference: voting_parallel_tree_learner.cpp).
                with _scope("hist_allreduce"):
                    hist = lax.psum(hist, self.axis)
            if qexact and not self.voting:
                with _scope("histogram"):
                    hist = hist.astype(jnp.float32) * jnp.stack(
                        [gs, hs, jnp.float32(1.0)])
            # voting + quant_exact: keep RAW level sums — the exact integer
            # reduction happens per voted column inside best_of, scales after
            return hist, rows

        extra_on = self.extra_on
        contri = self.contri_arr
        nb_m1 = self.nb_minus1_arr
        # interaction constraints, in-program (reference: col_sampler.hpp
        # interaction sets): each leaf carries a bitmask of features used on
        # its path; a feature is allowed iff some group contains path+{f}
        ic_on = self.ic_groups is not None
        if ic_on:
            PW = (F + 31) // 32
            gb = np.zeros((len(self.ic_groups), PW), np.uint32)
            gm = np.zeros((len(self.ic_groups), F), bool)
            for gi, g in enumerate(self.ic_groups):
                for f in g:
                    gb[gi, f // 32] |= np.uint32(1) << np.uint32(f % 32)
                    gm[gi, f] = True
            group_bits = jnp.asarray(gb)
            group_member = jnp.asarray(gm)
        else:
            PW = 1
        bynode_frac = float(cfg.feature_fraction_bynode)
        bynode_on = bynode_frac < 1.0

        def node_fmask(path_bits, rkey):
            """Per-leaf effective feature mask: interaction-set filtering +
            by-node sampling (reference: col_sampler.hpp GetByNode)."""
            m = fmask
            if ic_on:
                subset = jnp.all((path_bits[None, :] & ~group_bits) == 0,
                                 axis=1)                       # [G]
                # union of the groups containing the path; the empty path is
                # a subset of every group, so the root gets the union of ALL
                # groups — features outside every group are never usable
                # (matches the host learner's _node_fmask)
                m = m & jnp.any(subset[:, None] & group_member, axis=0)
            if bynode_on:
                r = jax.random.uniform(rkey, (F,))
                r = jnp.where(m, r, -jnp.inf)
                avail = jnp.sum(m.astype(jnp.int32))
                k = jnp.maximum(jnp.ceil(bynode_frac * avail), 1.0)
                rank = jnp.argsort(jnp.argsort(-r))
                m = m & (rank < k.astype(jnp.int32))
            return m

        voting = self.voting
        vote_k = int(getattr(self, "vote_k", 0)) if voting else 0
        # feature-parallel mode: rows replicated, COLUMNS sharded over this
        # axis; histograms need no collective at all — the per-split
        # traffic is one all_gather of per-shard best-split tuples (the
        # SyncUpGlobalBestSplit analog) plus a psum broadcast of the
        # winning feature's column for the partition
        # (reference: src/treelearner/feature_parallel_tree_learner.cpp)
        fax = getattr(self, "feat_axis", None)

        def best_of_feat(hist, pg, ph, pc, pout, lo, hi, depth, rkey, fm):
            """Feature-sharded best split: local scan over this shard's
            column block, then an all_gather of the D local winners and a
            replicated argmax. Tie-break matches the serial argmax exactly
            (first max in global feature order)."""
            C_loc = hist.shape[0]
            off = lax.axis_index(fax) * C_loc

            def sl(arr):
                # shards tile the padded feature axis exactly, so the
                # per-shard slice start can never clamp
                assert arr.shape[0] % C_loc == 0
                return lax.dynamic_slice_in_dim(arr, off, C_loc, axis=0)

            mono_l = sl(mono_arr)
            cons = (mono_l, lo, hi) if mono_on else None
            rand_t = None
            if extra_on:
                # replicated draw over the GLOBAL feature axis, sliced
                # locally. Drawn at the REAL feature count, then padded:
                # F here is the shard-padded program width, and a
                # (padded,)-shaped draw is a DIFFERENT prng stream than
                # the serial learner's (real,)-shaped one — the splits
                # would be legitimate but never comparable to serial
                # (pre-existing divergence unmasked by the ISSUE-8 combo
                # test rework). Pad columns get threshold 0: their fmask
                # is False and nb_minus1 is 1, so they can never win.
                rF = getattr(self, "_real_F", F)
                draw = jax.random.randint(rkey, (rF,), 0, 1 << 30)
                if rF != F:
                    draw = jnp.concatenate(
                        [draw, jnp.zeros(F - rF, draw.dtype)])
                rand_t = sl(draw % nb_m1)
            gain, thr, dl, lg, lh, lc, bits = per_feature_best(
                hist, pg, ph, pc, pout, sl(num_bins), sl(default_bins),
                sl(missing_types), sl(is_cat_arr), sl(fm), p, has_cat,
                constraints=cons, rand_thresholds=rand_t)
            parent_gain = leaf_gain(pg, ph, p, pc, pout)
            shift = parent_gain + p.min_gain_to_split
            mult = sl(contri) if contri is not None else None
            if mono_on and self.mono_penalty > 0:
                from ..ops.split import monotone_split_penalty
                mp = jnp.where(mono_l != 0,
                               monotone_split_penalty(depth,
                                                      self.mono_penalty),
                               1.0)
                mult = mp if mult is None else mult * mp
            if mult is not None:
                gain = jnp.where(jnp.isfinite(gain),
                                 (gain - shift) * mult + shift, gain)
            fl = jnp.argmax(gain, axis=0).astype(jnp.int32)
            lout_l = calculate_leaf_output(lg[fl], lh[fl], p, lc[fl], pout)
            rout_l = calculate_leaf_output(pg - lg[fl], ph - lh[fl], p,
                                           pc - lc[fl], pout)
            if mono_on:
                lout_l = jnp.clip(lout_l, lo, hi)
                rout_l = jnp.clip(rout_l, lo, hi)
            fields = (gain[fl], off + fl, thr[fl],
                      dl[fl].astype(jnp.int32),
                      sl(is_cat_arr)[fl].astype(jnp.int32), bits[fl],
                      lg[fl], lh[fl], lc[fl], lout_l, rout_l)
            with _scope("hist_allreduce"):        # [D, ...] each
                gathered = [lax.all_gather(x, fax) for x in fields]
            win = jnp.argmax(gathered[0], axis=0).astype(jnp.int32)
            gw = gathered[0][win]
            g = gw - shift
            ok = jnp.isfinite(gw) & (g > 0.0)
            if max_depth > 0:
                ok = ok & (depth < max_depth)
            return (jnp.where(ok, g, K_MIN_SCORE), gathered[1][win],
                    gathered[2][win], gathered[3][win].astype(bool),
                    gathered[4][win].astype(bool), gathered[5][win],
                    gathered[6][win], gathered[7][win], gathered[8][win],
                    gathered[9][win], gathered[10][win])

        def best_of(hist, pg, ph, pc, pout, lo, hi, depth, rkey, fm):
            """Best split for one leaf, with the max_depth guard.
            Returns (gain, feat, thr, dl, cat, bits, lg, lh, lc, lout, rout).

            Voting mode (reference:
            src/treelearner/voting_parallel_tree_learner.cpp:151-184
            GlobalVoting + CopyLocalHistogram): ``hist`` is this shard's
            LOCAL histogram; each shard scans it against its local parent
            sums, proposes its top-k features, the votes all_gather, and
            only the voted columns psum — O(D·k·B) bytes on the wire per
            split instead of O(F·B) — before one global scan whose results
            scatter back into full-F arrays so the downstream argmax/
            penalty/monotone code is identical in all modes."""
            if fax is not None:
                return best_of_feat(hist, pg, ph, pc, pout, lo, hi, depth,
                                    rkey, fm)
            cons = (mono_arr, lo, hi) if mono_on else None
            rand_t = None
            if extra_on:
                # rkey is replicated, so every shard draws the same
                # thresholds: votes are scored by the same randomized gain
                # the final voted scan uses
                rand_t = jax.random.randint(rkey, (F,), 0, 1 << 30) % nb_m1
            if voting:
                ltr = jnp.sum(hist[0], axis=0)    # local parent sums (RAW
                # level sums in quant_exact mode — same units as hist)
                if bundled:
                    from ..ops.histogram import unbundle_hist
                    hist = unbundle_hist(hist, self.ub_src, self.ub_kind,
                                         ltr[0], ltr[1], ltr[2])
                if quant and qexact:
                    qsc = jnp.stack([gs, hs, jnp.float32(1.0)])
                    hist_s = hist.astype(jnp.float32) * qsc
                    lt = ltr.astype(jnp.float32) * qsc
                else:
                    hist_s, lt = hist, ltr
                lgain, *_ = per_feature_best(
                    hist_s, lt[0], lt[1], lt[2], jnp.float32(0.0), num_bins,
                    default_bins, missing_types, is_cat_arr, fm, p, has_cat,
                    rand_thresholds=rand_t)
                _, local_top = lax.top_k(lgain, vote_k)
                with _scope("hist_allreduce"):
                    votes = lax.all_gather(local_top.astype(jnp.int32),
                                           self.axis, tiled=True)     # [D*k]
                    # in quant_exact mode this psum reduces raw integer level
                    # sums (exact, order-independent — the voted-column
                    # analog of the full-histogram integer reduction in
                    # leaf_hist); scales apply after
                    hist_v = lax.psum(hist[votes], self.axis)
                if quant and qexact:
                    hist_v = hist_v.astype(jnp.float32) * qsc
                cons_v = (mono_arr[votes], lo, hi) if mono_on else None
                gain_v, thr_v, dl_v, lg_v, lh_v, lc_v, bits_v = \
                    per_feature_best(
                        hist_v, pg, ph, pc, pout, num_bins[votes],
                        default_bins[votes], missing_types[votes],
                        is_cat_arr[votes], fm[votes], p, has_cat,
                        constraints=cons_v,
                        rand_thresholds=(rand_t[votes]
                                         if rand_t is not None else None))
                # scatter voted results back to [F] (duplicate votes write
                # identical values)
                gain = jnp.full((F,), K_MIN_SCORE,
                                jnp.float32).at[votes].set(gain_v)
                thr = jnp.zeros((F,), jnp.int32).at[votes].set(thr_v)
                dl = jnp.zeros((F,), bool).at[votes].set(dl_v)
                lg = jnp.zeros((F,), jnp.float32).at[votes].set(lg_v)
                lh = jnp.zeros((F,), jnp.float32).at[votes].set(lh_v)
                lc = jnp.zeros((F,), jnp.float32).at[votes].set(lc_v)
                bits = jnp.zeros((F, 8), jnp.uint32).at[votes].set(bits_v)
            else:
                if bundled:
                    from ..ops.histogram import unbundle_hist
                    hist = unbundle_hist(hist, self.ub_src, self.ub_kind,
                                         pg, ph, pc)
                gain, thr, dl, lg, lh, lc, bits = per_feature_best(
                    hist, pg, ph, pc, pout, num_bins, default_bins,
                    missing_types, is_cat_arr, fm, p, has_cat,
                    constraints=cons, rand_thresholds=rand_t)
            parent_gain = leaf_gain(pg, ph, p, pc, pout)
            shift = parent_gain + p.min_gain_to_split
            mult = contri
            if mono_on and self.mono_penalty > 0:
                # depth-dependent monotone split penalty (reference:
                # serial_tree_learner.cpp:998)
                from ..ops.split import monotone_split_penalty
                mp = jnp.where(mono_arr != 0,
                               monotone_split_penalty(depth,
                                                      self.mono_penalty),
                               1.0)
                mult = mp if mult is None else mult * mp
            if mult is not None:
                # feature_contri / monotone penalty scale the post-shift
                # gain (reference: feature_histogram.hpp:174)
                gain = jnp.where(jnp.isfinite(gain),
                                 (gain - shift) * mult + shift, gain)
            f = jnp.argmax(gain, axis=0).astype(jnp.int32)
            g = gain[f] - shift
            ok = jnp.isfinite(gain[f]) & (g > 0.0)
            if max_depth > 0:
                ok = ok & (depth < max_depth)
            lout = calculate_leaf_output(lg[f], lh[f], p, lc[f], pout)
            rout = calculate_leaf_output(pg - lg[f], ph - lh[f], p,
                                         pc - lc[f], pout)
            if mono_on:
                lout = jnp.clip(lout, lo, hi)
                rout = jnp.clip(rout, lo, hi)
            return (jnp.where(ok, g, K_MIN_SCORE), f, thr[f], dl[f],
                    is_cat_arr[f], bits[f], lg[f], lh[f], lc[f], lout, rout)

        best_children = jax.vmap(best_of,
                                 in_axes=(0, 0, 0, 0, 0, 0, 0, None, 0, 0))

        # ------------------------------------------------------ state init
        # consolidated per-leaf/per-node state; row L / row NODES is the dump
        # row that masked-off writes land on
        # leaf_f columns: sum_g, sum_h, cnt, out, bgain, blg, blh, blc,
        #                 blout, brout, mono_min, mono_max
        # leaf_i columns: begin, count, depth, parent, is_left, bfeat, bthr,
        #                 bdl, bcat
        # node_f columns: gain, value, weight, count
        # node_i columns: feature, threshold, default_left, is_cat, left, right
        # W rows of padding let every window read be a clamped-free
        # dynamic slice; pad rows point at row 0 and are always masked
        with _scope("tree_init"):
            perm0 = jnp.concatenate([jnp.arange(N, dtype=jnp.int32),
                                     jnp.zeros(W, jnp.int32)])
        hist_root, rows_root = leaf_hist(perm0, srows, jnp.int32(0),
                                         jnp.int32(N))
        with _scope("tree_init"):
            totals = jnp.sum(hist_root[0], axis=0)
            if fax is not None and self.axis is not None:
                # 2-D data x feature execution: hist_root[0] is each feature
                # shard's LOCAL column 0, so the f32 bin-sum above adds the
                # same rows in a different (bin-grouping) order per shard —
                # ulp-divergent parent sums would make the per-shard scans
                # disagree. Broadcast shard 0's totals so every shard scans
                # with bit-identical aggregates (exact under quantization,
                # and the contract the stream mirror replays).
                fidx = lax.axis_index(fax)
                totals = lax.psum(
                    jnp.where(fidx == 0, totals, jnp.zeros_like(totals)), fax)
            if voting:
                # local root hist: global parent sums need their own
                # (tiny) psum
                totals = lax.psum(totals, self.axis)
                if quant and qexact:
                    # raw level sums -> gradient units (voting defers scaling
                    # until after its collectives; see leaf_hist)
                    totals = totals.astype(jnp.float32) * jnp.stack(
                        [gs, hs, jnp.float32(1.0)])
            root_out = calculate_leaf_output(totals[0], totals[1], p,
                                             totals[2], 0.0)
            neg_inf = jnp.float32(-jnp.inf)
            pos_inf = jnp.float32(jnp.inf)
            # ekey carries TWO independent streams: [0] extra_trees random
            # thresholds, [1] by-node column sampling (separate seeds, like the
            # host learner's _extra_rng vs _col_rng)
            need_keys = extra_on or bynode_on
            xkey, bkey = ekey[0], ekey[1]
            root_key = jax.random.fold_in(xkey, NODES) if need_keys else xkey
            if ic_on or bynode_on:
                fm0 = node_fmask(jnp.zeros(PW, jnp.uint32),
                                 jax.random.fold_in(bkey, NODES))
            else:
                fm0 = fmask
        with _scope("split_scan"):
            (bg0, bf0, bt0, bdl0, bcat0, bbits0, blg0, blh0, blc0, blout0,
             brout0) = best_of(hist_root, totals[0], totals[1], totals[2],
                               root_out, neg_inf, pos_inf, jnp.int32(0),
                               root_key, fm0)

        with _scope("tree_init"):
            iota_l1 = jnp.arange(L + 1, dtype=jnp.int32)
            f32 = jnp.float32
            i32 = jnp.int32
            leaf_f = jnp.zeros((L + 1, 12), f32)
            leaf_f = leaf_f.at[:, 4].set(K_MIN_SCORE) \
                           .at[:, 10].set(-jnp.inf).at[:, 11].set(jnp.inf)
            leaf_f = leaf_f.at[0].set(jnp.stack(
                [totals[0], totals[1], totals[2], root_out, bg0, blg0, blh0,
                 blc0, blout0, brout0, neg_inf, pos_inf]))
            leaf_i = jnp.zeros((L + 1, 9), i32)
            # inactive leaves carry out-of-range begins so the final
            # position -> leaf step (position_leaf) never matches them
            leaf_i = leaf_i.at[:, 0].set(N + iota_l1).at[:, 3].set(-1)
            leaf_i = leaf_i.at[0].set(jnp.stack(
                [i32(0), i32(N), i32(0), i32(-1), i32(0), bf0, bt0,
                 bdl0.astype(i32), bcat0.astype(i32)]))
            leaf_bits = jnp.zeros((L + 1, 8), jnp.uint32).at[0].set(bbits0)
            node_f = jnp.zeros((NODES + 1, 4), f32)
            node_i = jnp.zeros((NODES + 1, 8), i32).at[:, 4:6].set(~0)
            node_bits = jnp.zeros((NODES + 1, 8), jnp.uint32)
            state = dict(
                perm=perm0,
                perm_buf=jnp.zeros(N + W, jnp.int32),
                leaf_f=leaf_f, leaf_i=leaf_i, leaf_bits=leaf_bits,
                node_f=node_f, node_i=node_i, node_bits=node_bits,
                hist=jnp.zeros((L + 1, C, Bb, HIST_C),
                               f32).at[0].set(hist_root),
                num_leaves=jnp.int32(1),
            )
            if layout_sorted:
                # the leaf-ordered payload + its partition double buffer ride
                # the carry so each split's permutation delta applies in place
                state["srows"] = srows
                state["srows_buf"] = jnp.zeros_like(srows)
            if count_rows:
                state["leaf_rows"] = jnp.zeros(L + 1, i32).at[0].set(
                    rows_root)
                state["node_rows"] = jnp.zeros(NODES + 1, i32)
            if ic_on:
                state["path"] = jnp.zeros((L + 1, PW), jnp.uint32)
            if inter:
                # per-leaf bin-space boxes ([lo, hi) per feature, root = full
                # range), per-leaf ancestor-node bitsets, the stale-scan marks,
                # and node parent/side pointers for the up-walk
                state["box_lo"] = jnp.zeros((L + 1, F), jnp.int32)
                state["box_hi"] = jnp.zeros((L + 1, F),
                                            jnp.int32).at[0].set(num_bins)
                state["npath"] = jnp.zeros((L + 1, NPW_N), jnp.uint32)
                state["stale"] = jnp.zeros(L + 1, bool)
                state["node_par"] = jnp.full(NODES + 1, -1, jnp.int32)
                state["node_side"] = jnp.zeros(NODES + 1, jnp.int32)

            forced = self.forced_seq
            if forced is not None:
                f_leaf = jnp.asarray(forced[0])
                f_feat = jnp.asarray(forced[1])
                f_thr = jnp.asarray(forced[2])
                f_on = jnp.asarray(forced[3])
                state["forcing"] = jnp.asarray(True)

        # ------------------------------------------------------ split step
        def split_step(k, st):
            with _scope("leaf_select"):
                if inter:
                    # eager re-scan of every leaf whose bounds the previous
                    # split's propagation tightened (the host learner re-scans
                    # them inside apply_split; here the re-scan runs at the
                    # start of the next step — before the argmax, so the
                    # choice sees only fresh gains). Loop trips are derived
                    # from replicated state, so every shard runs the same
                    # number of (collective-bearing, under voting) re-scans.
                    def rescan_cond(rst):
                        return jnp.any(rst[3][:L])

                    def rescan_body(rst):
                        lf_c, li_c, lb_c, stale_c = rst
                        rl = jnp.argmax(stale_c[:L]).astype(jnp.int32)
                        lfr = lf_c[rl]
                        lir = li_c[rl]
                        if need_keys:
                            rk = jax.random.fold_in(
                                jax.random.fold_in(xkey, NODES + 1),
                                k * (L + 1) + rl)
                        else:
                            rk = xkey
                        if ic_on or bynode_on:
                            cp = (st["path"][rl] if ic_on
                                  else jnp.zeros(PW, jnp.uint32))
                            fm_l = node_fmask(cp, jax.random.fold_in(
                                jax.random.fold_in(bkey, NODES + 1),
                                k * (L + 1) + rl))
                        else:
                            fm_l = fmask
                        (rg, rf, rt, rdl, rcat, rbits, rlg, rlh, rlc, rlout,
                         rrout) = best_of(st["hist"][rl], lfr[0], lfr[1],
                                          lfr[2], lfr[3], lfr[10], lfr[11],
                                          lir[2], rk, fm_l)
                        new_lf = jnp.stack([lfr[0], lfr[1], lfr[2], lfr[3],
                                            rg, rlg, rlh, rlc, rlout, rrout,
                                            lfr[10], lfr[11]])
                        new_li = jnp.stack([lir[0], lir[1], lir[2], lir[3],
                                            lir[4], rf, rt,
                                            rdl.astype(jnp.int32),
                                            rcat.astype(jnp.int32)])
                        return (lf_c.at[rl].set(new_lf),
                                li_c.at[rl].set(new_li),
                                lb_c.at[rl].set(rbits),
                                stale_c.at[rl].set(False))

                    leaf_f, leaf_i, leaf_bits, stale = lax.while_loop(
                        rescan_cond, rescan_body,
                        (st["leaf_f"], st["leaf_i"], st["leaf_bits"],
                         st["stale"]))
                else:
                    leaf_f, leaf_i = st["leaf_f"], st["leaf_i"]
                    leaf_bits = st["leaf_bits"]
                leaf = jnp.argmax(leaf_f[:L, 4]).astype(jnp.int32)
                forcing_next = None
                fon = use_f = None
                if forced is not None:
                    # gather the forced split's stats from the forced leaf's
                    # histogram; if it is invalid (no positive gain / depth),
                    # forcing aborts and THIS step falls back to the argmax
                    # best split, so an abort costs no split budget (matching
                    # the
                    # serial ForceSplits abort_last_forced_split behavior)
                    fon = f_on[k] & st["forcing"]
                    fleaf = f_leaf[k]
                    flf = leaf_f[fleaf]
                    fli = leaf_i[fleaf]
                    hist_leaf = st["hist"][fleaf]
                    if bundled:
                        from ..ops.histogram import unbundle_hist
                        histF = unbundle_hist(hist_leaf, self.ub_src,
                                              self.ub_kind, flf[0], flf[1],
                                              flf[2])
                    else:
                        histF = hist_leaf
                    fk = f_feat[k]
                    res = gather_threshold_split(
                        histF[fk], flf[0], flf[1], flf[2], flf[3], fk,
                        f_thr[k],
                        num_bins[fk], default_bins[fk], missing_types[fk],
                        is_cat_arr[fk], p,
                        bounds=(flf[10], flf[11]) if mono_on else None)
                    fok = res.gain > 0.0
                    if max_depth > 0:
                        fok = fok & (fli[2] < max_depth)
                    forcing_next = st["forcing"] & jnp.where(f_on[k], fok,
                                                             True)
                    use_f = fon & fok
                    leaf = jnp.where(use_f, fleaf, leaf)
                lf = leaf_f[leaf]
                li = leaf_i[leaf]
                ok = lf[4] > 0.0

                # the chosen split: the leaf's stored best, unless this step is
                # a (valid) forced one — then the gathered fixed split
                bgain = lf[4]
                feat = li[5]
                thrv, dlv, catv = li[6], li[7].astype(bool), li[8].astype(bool)
                bitsv = leaf_bits[leaf]
                blg, blh, blc = lf[5], lf[6], lf[7]
                blout, brout = lf[8], lf[9]
                if forced is not None:
                    ok = jnp.where(use_f, True, ok)
                    bgain = jnp.where(use_f, res.gain, bgain)
                    feat = jnp.where(use_f, fk, feat)
                    thrv = jnp.where(use_f, f_thr[k], thrv)
                    dlv = jnp.where(use_f, res.default_left, dlv)
                    catv = jnp.where(use_f, res.is_categorical, catv)
                    bitsv = jnp.where(use_f, res.cat_bitset, bitsv)
                    blg = jnp.where(use_f, res.left_sum_g, blg)
                    blh = jnp.where(use_f, res.left_sum_h, blh)
                    blc = jnp.where(use_f, res.left_count, blc)
                    blout = jnp.where(use_f, res.left_output, blout)
                    brout = jnp.where(use_f, res.right_output, brout)

                begin = li[0]
                count_eff = jnp.where(ok, li[1], 0)
                srows_cur = st["srows"] if layout_sorted else None
                if layout_sorted:
                    # the split feature's bin value is decoded from the sorted
                    # window itself inside pbody — no column gather, and no
                    # column-major matrix at all (x_cols is a placeholder)
                    col = None
                    colidx = self.bcol[feat] if bundled else feat
                elif fax is not None:
                    # the winning feature's column lives on ONE shard: psum
                    # broadcasts it for the (row-replicated) partition — the
                    # analog of the reference's best-split partition broadcast
                    # (feature_parallel_tree_learner.cpp SyncUp + split apply)
                    C_loc_p = x_cols.shape[0]
                    f_loc = feat - lax.axis_index(fax) * C_loc_p
                    owned = (f_loc >= 0) & (f_loc < C_loc_p)
                    col_l = x_cols[jnp.clip(f_loc, 0, C_loc_p - 1)]
                    # psum in the native bin dtype: exactly one shard is
                    # nonzero, so no overflow — and the wire moves 1-2 B per
                    # row instead of 4 (pbody casts to i32 as it reads)
                    with _scope("hist_allreduce"):
                        col = lax.psum(
                            jnp.where(owned, col_l, jnp.zeros_like(col_l)),
                            fax)
                else:
                    col = x_cols[self.bcol[feat] if bundled else feat]  # [N]
                nch = (count_eff + W - 1) // W
                perm_in = st["perm"]

            # -- chunked stable partition into perm_buf ----------------
            # a trip's lefts belong at [lcur, lcur+nl) in lane order and its
            # rights at [rcur-nr, rcur) in REVERSED lane order: two
            # contiguous runs, which route_window compacts inside the
            # window and lands as masked window writes, the idiom of cbody
            # below — no scatter. Under the sorted layout the packed row
            # payload rides the same compactions into srows_buf: the
            # permutation delta of this split applied physically, over
            # only this leaf's slice
            def pbody(s):
                c, lcur, rcur, *bufs = s        # perm_buf(, srows_buf)
                with _scope("partition_decide"):
                    live = jnp.clip(count_eff - c * W, 0, W)
                    valid = lane < live
                    rows = perm_slice(perm_in, begin + c * W)
                    if layout_sorted:
                        dw = srow_slice(srows_cur, begin + c * W)
                        cv = jnp.take(unpack(dw.T), colidx,
                                      axis=1).astype(jnp.int32)
                    else:
                        cv = col[rows].astype(jnp.int32)
                    if bundled:
                        # rank-decode the feature's bin out of its bundle
                        # column
                        r = cv - self.boff[feat]
                        d = default_bins[feat]
                        in_r = (r >= 0) & (r < num_bins[feat] - 1)
                        cv = jnp.where(self.bsingle[feat], cv,
                                       jnp.where(in_r, r + (r >= d), d))
                    gl = decision_go_left(
                        cv, thrv, dlv, default_bins[feat],
                        missing_types[feat], num_bins[feat], catv,
                        bitsv) & valid
                    gr = ~gl & valid
                with _scope("partition_scatter"):
                    # rights fill backward from the slice end: stable within
                    # a chunk, chunk order reversed on the right side — a
                    # deterministic permutation, only affecting later gather
                    # order. rcur - nr >= begin, and the W-row tail pad
                    # absorbs either window
                    win = (rows, dw) if layout_sorted else (rows,)
                    bufs, nl, nr = route_window(bufs, win, gl, gr, lcur,
                                                rcur)
                    return (c + 1, lcur + nl, rcur - nr) + bufs

            with _scope("partition"):
                if layout_sorted:
                    _, lend, _, pbuf, sbuf = lax.while_loop(
                        lambda s: s[0] < nch, pbody,
                        (jnp.int32(0), begin, begin + count_eff,
                         st["perm_buf"], st["srows_buf"]))
                else:
                    _, lend, _, pbuf = lax.while_loop(
                        lambda s: s[0] < nch, pbody,
                        (jnp.int32(0), begin, begin + count_eff,
                         st["perm_buf"]))
                    sbuf = None
            with _scope("split_state"):
                left_count = lend - begin
                right_count = count_eff - left_count

            # copy the partitioned slice back into perm (chunked); both reads
            # and the write are contiguous-window DMAs, with the stale tail
            # of the last window re-written from perm itself. The sorted
            # payload copies back the same way — stream reads, stream write,
            # ``[SW, W]`` windows along the word-major payload's minor axis,
            # the layout every loop of the program holds it in
            # (_build_sorted_impl).
            def cbody(s):
                if layout_sorted:
                    c, pm, sr = s
                else:
                    c, pm = s
                # same window-pad invariant as perm_slice: starts stay
                # <= N, the W-row tail pad absorbs the last window
                assert pbuf.shape[0] == N + W
                start = begin + c * W
                valid = (c * W + lane) < count_eff
                vals = jnp.where(valid, perm_slice(pbuf, start),
                                 perm_slice(pm, start))
                pm = lax.dynamic_update_slice(pm, vals, (start,))
                if layout_sorted:
                    sw = jnp.where(valid, srow_slice(sbuf, start),
                                   srow_slice(sr, start))
                    sr = lax.dynamic_update_slice(sr, sw, (0, start))
                    return c + 1, pm, sr
                return c + 1, pm

            with _scope("partition_copyback"):
                if layout_sorted:
                    _, perm, srows_new = lax.while_loop(
                        lambda s: s[0] < nch, cbody,
                        (jnp.int32(0), perm_in, srows_cur))
                else:
                    _, perm = lax.while_loop(lambda s: s[0] < nch, cbody,
                                             (jnp.int32(0), perm_in))
                    srows_new = None

            with _scope("split_state"):
                # -- masked write indices (dump rows swallow no-op steps) --
                # nodes are indexed by the number of REALIZED splits, not the
                # loop counter: a no-op step (e.g. an aborted forced split)
                # must not leave a hole in the node array
                new_leaf = st["num_leaves"]
                nidx = new_leaf - 1
                wl = jnp.where(ok, leaf, L)
                wn = jnp.where(ok, new_leaf, L)
                wk = jnp.where(ok, nidx, NODES)

                # parent node's child pointer now points at node k
                pnode = li[3]
                was_left = li[4].astype(bool)
                safe_p = jnp.where((pnode >= 0) & ok, pnode, NODES)
                prow = st["node_i"][safe_p]
                prow = jnp.where(was_left, prow.at[4].set(nidx),
                                 prow.at[5].set(nidx))
                node_i = st["node_i"].at[safe_p].set(prow)

                # aggregates
                pg, ph, pc = lf[0], lf[1], lf[2]
                lg, lh, lc = blg, blh, blc
                rg, rh, rc = pg - lg, ph - lh, pc - lc
                lout, rout = blout, brout
                depth = li[2] + 1

                # which child's histogram is built (the smaller; the larger
                # comes by subtraction)
                if self.axis is None:
                    small_is_left = left_count <= right_count
                else:
                    # the side choice must be identical on every shard (each
                    # shard's local hist feeds one psum); local partition
                    # counts differ per shard, the scan's global (in-bag)
                    # counts do not
                    small_is_left = lc <= pc - lc
                sb = jnp.where(small_is_left, begin, begin + left_count)
                sc = jnp.where(small_is_left, left_count, right_count)

                # children's monotone bounds. basic: the mid of the two outputs
                # caps the subtree on the constrained side; intermediate: each
                # child is capped by its SIBLING's output — looser, recovered
                # accuracy is the method's point (reference:
                # UpdateConstraintsWithOutputs, monotone_constraints.hpp:545)
                pmin, pmax = lf[10], lf[11]
                mono_f = mono_arr[feat]
                if inter:
                    lcap, rcap = rout, lout
                else:
                    lcap = rcap = (lout + rout) * 0.5
                lmin = jnp.where(mono_f < 0, jnp.maximum(pmin, lcap), pmin)
                lmax = jnp.where(mono_f > 0, jnp.minimum(pmax, lcap), pmax)
                rmin = jnp.where(mono_f > 0, jnp.maximum(pmin, rcap), pmin)
                rmax = jnp.where(mono_f < 0, jnp.minimum(pmax, rcap), pmax)

                node_f = st["node_f"].at[wk].set(
                    jnp.stack([bgain, lf[3], ph, pc]))
                node_i = node_i.at[wk].set(jnp.stack(
                    [feat, thrv, dlv.astype(jnp.int32), catv.astype(jnp.int32),
                     ~leaf, ~new_leaf, count_eff, sc]))
                node_bits = st["node_bits"].at[wk].set(bitsv)

            # -- children histograms (smaller built, larger by subtraction)
            hist_small, rows_small = leaf_hist(perm, srows_new, sb, sc)
            with _scope("hist_subtract"):
                hist, hist_left, hist_right = write_children(
                    st["hist"], leaf, hist_small, small_is_left, wl, wn)

            # -- both children's best splits in one vmapped scan -------
            with _scope("split_scan"):
                if extra_on or bynode_on:
                    xstep = jax.random.fold_in(xkey, k)
                    bstep = jax.random.fold_in(bkey, k)
                    child_keys = jnp.stack([jax.random.fold_in(xstep, 0),
                                            jax.random.fold_in(xstep, 1)])
                else:
                    bstep = bkey
                    child_keys = jnp.zeros((2,) + xkey.shape, xkey.dtype)
                if ic_on:
                    # children inherit the path plus the feature just split on
                    pbit = jnp.where(
                        jnp.arange(PW, dtype=jnp.uint32)
                        == (feat // 32).astype(jnp.uint32),
                        jnp.left_shift(jnp.uint32(1),
                                       (feat % 32).astype(jnp.uint32)),
                        jnp.uint32(0))
                    child_path = st["path"][leaf] | pbit
                if ic_on or bynode_on:
                    cp = child_path if ic_on else jnp.zeros(PW, jnp.uint32)
                    fms = jnp.stack([
                        node_fmask(cp, jax.random.fold_in(bstep, 2)),
                        node_fmask(cp, jax.random.fold_in(bstep, 3))])
                else:
                    fms = jnp.broadcast_to(fmask, (2, F))
                (bg2, bf2, bt2, bdl2, bcat2, bbits2, blg2, blh2, blc2,
                 blout2, brout2) = best_children(
                    jnp.stack([hist_left, hist_right]),
                    jnp.stack([lg, rg]), jnp.stack([lh, rh]),
                    jnp.stack([lc, rc]), jnp.stack([lout, rout]),
                    jnp.stack([lmin, rmin]), jnp.stack([lmax, rmax]), depth,
                    child_keys, fms)

            with _scope("split_state"):
                i32 = jnp.int32
                lrow_f = jnp.stack([lg, lh, lc, lout, bg2[0], blg2[0], blh2[0],
                                    blc2[0], blout2[0], brout2[0], lmin, lmax])
                rrow_f = jnp.stack([rg, rh, rc, rout, bg2[1], blg2[1], blh2[1],
                                    blc2[1], blout2[1], brout2[1], rmin, rmax])
                lrow_i = jnp.stack([begin, left_count, depth, nidx, i32(1),
                                    bf2[0], bt2[0], bdl2[0].astype(i32),
                                    bcat2[0].astype(i32)])
                rrow_i = jnp.stack([begin + left_count, right_count, depth,
                                    nidx, i32(0), bf2[1], bt2[1],
                                    bdl2[1].astype(i32),
                                    bcat2[1].astype(i32)])

                if inter:
                    # -- intermediate constraint propagation ---------------
                    # The reference walks up from the new node; at every
                    # monotone numeric ancestor it tightens the bounds of
                    # leaves in the opposite subtree that stay contiguous to
                    # the split leaf, using the new children's outputs
                    # (GoUpToFindLeavesToUpdate / GoDownToFindLeavesToUpdate,
                    # monotone_constraints.hpp:560-850). Here the recursive
                    # down-walk collapses to vectorized [L] box tests: the
                    # contiguity pruning is interval overlap between each
                    # leaf's bin-space box and the split leaf's PRE-split box
                    # on the features crossed so far, and the use-left/right
                    # output choice is overlap with each child's range on the
                    # split feature. Tightened leaves are marked stale and
                    # eagerly re-scanned at the next step's start.
                    plo_vec = st["box_lo"][leaf]           # [F] pre-split box
                    phi_vec = st["box_hi"][leaf]
                    lo_col = st["box_lo"]                  # [L+1, F]
                    hi_col = st["box_hi"]
                    sf_lo = lo_col[:, feat]                # [L+1] on the new
                    sf_hi = hi_col[:, feat]                # split's feature
                    # active leaves whose cached best split is still viable:
                    # the reference skips leaves with best gain == kMinScore
                    # (e.g. at max_depth) — tightening a dead leaf's bounds
                    # only buys pointless re-scan loop trips (each bearing
                    # collectives under voting), and bounds can never turn an
                    # unsplittable leaf splittable (they only shrink gain)
                    splittable = leaf_f[:, 4] > K_MIN_SCORE
                    if max_depth > 0:
                        splittable &= leaf_i[:, 2] < max_depth
                    row_ok = (iota_l1 < L) & ok & splittable
                    npath_s = st["npath"]
                    BIGB = jnp.int32(1 << 30)

                    def wbody(wst):
                        a, child_left, crossed, keep, lf_c, stale_c = wst
                        g = node_i[a, 0]
                        t_a = node_i[a, 1]
                        is_num_a = node_i[a, 3] == 0
                        m_g = mono_arr[g]
                        opposite_ok = is_num_a & ~crossed[
                            g, child_left.astype(jnp.int32)]
                        in_sub = ((npath_s[:, a // 32]
                                   >> (a % 32).astype(jnp.uint32)) & 1) == 1
                        opp_side = jnp.where(child_left,
                                             lo_col[:, g] > t_a,
                                             hi_col[:, g] <= t_a + 1)
                        opp = in_sub & opp_side
                        # which child output applies to leaf M: the reference
                        # flips use_left/use_right only at sf-splits INSIDE the
                        # opposite subtree — in box terms, M keeps a side
                        # unless its own sf-range moved past the new threshold
                        # relative to the subtree ROOT's range (= the subtree
                        # extrema)
                        alo = jnp.min(jnp.where(opp, sf_lo, BIGB))
                        ahi = jnp.max(jnp.where(opp, sf_hi, -BIGB))
                        use_l = catv | (sf_lo <= thrv) | (sf_lo == alo)
                        use_r = catv | (sf_hi > thrv + 1) | (sf_hi == ahi)
                        both = use_l & use_r
                        lo_v = jnp.where(both, jnp.minimum(lout, rout),
                                         jnp.where(use_r, rout, lout))
                        hi_v = jnp.where(both, jnp.maximum(lout, rout),
                                         jnp.where(use_r, rout, lout))
                        cand = (opp & keep & row_ok
                                & opposite_ok & (m_g != 0))
                        update_max = jnp.where(m_g > 0, ~child_left,
                                               child_left)
                        cur_lo = lf_c[:, 10]
                        cur_hi = lf_c[:, 11]
                        new_hi = jnp.where(cand & update_max,
                                           jnp.minimum(cur_hi, lo_v), cur_hi)
                        new_lo = jnp.where(cand & ~update_max,
                                           jnp.maximum(cur_lo, hi_v), cur_lo)
                        changed = (new_hi < cur_hi) | (new_lo > cur_lo)
                        lf_c = lf_c.at[:, 10].set(new_lo).at[:, 11].set(new_hi)
                        stale_c = stale_c | changed
                        # record the crossing + the (one-sided) contiguity
                        # constraint this up-path entry imposes on leaves seen
                        # from higher ancestors: leaves past the crossed
                        # threshold in the crossing's direction are pruned
                        crossed = crossed.at[g, child_left.astype(
                            jnp.int32)].set(crossed[g, child_left.astype(
                                jnp.int32)] | opposite_ok)
                        entry_keep = jnp.where(child_left,
                                               lo_col[:, g] <= t_a,
                                               hi_col[:, g] > t_a + 1)
                        keep = keep & jnp.where(opposite_ok, entry_keep, True)
                        return (st["node_par"][a], st["node_side"][a] == 1,
                                crossed, keep, lf_c, stale_c)

                    a0 = jnp.where(ok, li[3], -1)
                    (_, _, _, _, leaf_f, stale) = lax.while_loop(
                        lambda wst: wst[0] >= 0, wbody,
                        (a0, li[4] == 1,
                         jnp.zeros((F, 2), bool),
                         jnp.ones(L + 1, bool), leaf_f, stale))

                out = dict(
                    perm=perm, perm_buf=pbuf,
                    leaf_f=leaf_f.at[wl].set(lrow_f).at[wn].set(rrow_f),
                    leaf_i=leaf_i.at[wl].set(lrow_i).at[wn].set(rrow_i),
                    leaf_bits=leaf_bits.at[wl].set(bbits2[0])
                                       .at[wn].set(bbits2[1]),
                    node_f=node_f, node_i=node_i, node_bits=node_bits,
                    hist=hist,
                    num_leaves=st["num_leaves"] + ok.astype(jnp.int32),
                )
                if layout_sorted:
                    out["srows"] = srows_new
                    out["srows_buf"] = sbuf
                if count_rows:
                    parent_rows = st["leaf_rows"][leaf]
                    large = parent_rows - rows_small
                    out["leaf_rows"] = st["leaf_rows"].at[wl].set(
                        jnp.where(small_is_left, rows_small, large)).at[
                        wn].set(jnp.where(small_is_left, large, rows_small))
                    out["node_rows"] = st["node_rows"].at[wk].set(
                        parent_rows)
                if forced is not None:
                    out["forcing"] = forcing_next
                if ic_on:
                    out["path"] = st["path"].at[wl].set(child_path) \
                                            .at[wn].set(child_path)
                if inter:
                    # children inherit the parent's box narrowed on the split
                    # feature (categorical splits scatter bins to both sides;
                    # keeping the parent box is conservative — matches the
                    # host learner's apply_split)
                    l_hi_box = jnp.where(catv, phi_vec,
                                         phi_vec.at[feat].set(thrv + 1))
                    r_lo_box = jnp.where(catv, plo_vec,
                                         plo_vec.at[feat].set(thrv + 1))
                    out["box_lo"] = st["box_lo"].at[wl].set(plo_vec) \
                                                .at[wn].set(r_lo_box)
                    out["box_hi"] = st["box_hi"].at[wl].set(l_hi_box) \
                                                .at[wn].set(phi_vec)
                    nbit = jnp.where(
                        jnp.arange(NPW_N, dtype=jnp.int32) == nidx // 32,
                        jnp.left_shift(jnp.uint32(1),
                                       (nidx % 32).astype(jnp.uint32)),
                        jnp.uint32(0))
                    child_npath = st["npath"][leaf] | nbit
                    out["npath"] = st["npath"].at[wl].set(child_npath) \
                                              .at[wn].set(child_npath)
                    out["stale"] = stale.at[wl].set(False).at[wn].set(False)
                    out["node_par"] = st["node_par"].at[wk].set(li[3])
                    out["node_side"] = st["node_side"].at[wk].set(li[4])
            return out

        if L > 1:
            state = lax.fori_loop(0, NODES, split_step, state)

        # -------------------------------------------------- row -> leaf id
        with _scope("row_leaf"):
            # position -> leaf is ops.partition.position_leaf, the one
            # statement of it for this epilogue and its two mirrors
            # (_stream_finalize_impl, fused_parallel._s2_final_body)
            pos_leaf = position_leaf(state["leaf_i"][:L, 0],
                                     state["leaf_i"][:L, 1], N)
            row_leaf = jnp.zeros(N, jnp.int32).at[
                state["perm"][:N]].set(pos_leaf)

            node_f = state["node_f"]
            node_i = state["node_i"]
            leaf_f = state["leaf_f"]
            leaf_i = state["leaf_i"]
            # an unsplittable tree contributes NOTHING — the reference turns
            # one-leaf trees into constant-0 trees (gbdt.cpp:408-436
            # AsConstantTree(0); the host learner matches); without this the
            # fused fast path would add the root's Newton step every round
            leaf_value_out = jnp.where(state["num_leaves"] > 1,
                                       leaf_f[:L, 3],
                                       jnp.zeros_like(leaf_f[:L, 3]))
            if quant and cfg.quant_train_renew_leaf:
                # re-fit leaf outputs with the full-precision gradient sums
                # (reference: GradientDiscretizer::RenewIntGradTreeOutput)
                gsum = jax.ops.segment_sum(grad, row_leaf, num_segments=L)
                hsum = jax.ops.segment_sum(hess, row_leaf, num_segments=L)
                if self.axis is not None:
                    gsum = lax.psum(gsum, self.axis)
                    hsum = lax.psum(hsum, self.axis)
                parent_out = node_f[jnp.clip(leaf_i[:L, 3], 0, NODES - 1), 1]
                renewed = calculate_leaf_output(gsum, hsum, p, leaf_f[:L, 2],
                                                parent_out)
                # renew only real trees: a one-leaf tree stays constant-0
                active = ((jnp.arange(L, dtype=jnp.int32)
                           < state["num_leaves"])
                          & (state["num_leaves"] > 1))
                leaf_value_out = jnp.where(active, renewed, leaf_value_out)
            # once a tree, so under this scope (tree_fixed_device_ms), not
            # under the per-split hist_allreduce
            counts = None
            if count_rows:
                counts = [c.astype(f32) for c in lax.psum(
                    (state["node_rows"][:NODES], state["leaf_rows"][:L]),
                    self.axis)]
            return DeviceTree(
                node_feature=node_i[:NODES, 0],
                node_threshold=node_i[:NODES, 1],
                node_default_left=node_i[:NODES, 2].astype(bool),
                node_is_cat=node_i[:NODES, 3].astype(bool),
                node_cat_bits=state["node_bits"][:NODES],
                node_left=node_i[:NODES, 4],
                node_right=node_i[:NODES, 5],
                node_gain=node_f[:NODES, 0],
                node_value=node_f[:NODES, 1],
                node_weight=node_f[:NODES, 2],
                node_count=node_f[:NODES, 3] if counts is None else counts[0],
                leaf_value=leaf_value_out,
                leaf_weight=leaf_f[:L, 1],
                leaf_count=leaf_f[:L, 2] if counts is None else counts[1],
                leaf_depth=leaf_i[:L, 2],
                leaf_parent_node=leaf_i[:L, 3],
                num_leaves=state["num_leaves"],
                row_leaf=row_leaf,
                work=node_i[:NODES, 6:8],
            )

    # ------------------------------------------------------------------
    # data_residency=stream: out-of-core tree build
    # ------------------------------------------------------------------
    # The binned matrix lives in host shards (data/stream.py); the device
    # keeps only the O(N)-scalar per-row state (grad/hess/mask, the
    # permutation, and — under the sorted layout — the physically ordered
    # gradient channels). Each tree is built by a host-driven loop of
    # small jitted kernels whose traced math replicates the fused
    # program's split step op-for-op for the supported option subset, and
    # whose histogram windows accumulate in the same W-chunk order — so
    # streamed trees are bit-identical to resident ones
    # (tests/test_stream.py). Row windows ride the double-buffered H2D
    # ring (ShardRing): the transfer of window k+1 is issued while the
    # device chews window k, instrumented by the h2d_prefetch/chunk_wait
    # telemetry phases. With a sampling mask (GOSS/bagging), windows are
    # COMPACTED host-side: only in-bag rows cross the link, the kernel
    # re-expands them into their window lanes, and the masked lanes'
    # exact-zero contributions keep bit-identity.

    def _stream_blockers(self, config: Config):
        """Fused-program options the multi-dispatch stream build does not
        replicate (config-only: runs from the base __init__)."""
        blockers = []
        if config.use_quantized_grad:
            blockers.append("use_quantized_grad")
        if config.forcedsplits_filename:
            blockers.append("forcedsplits_filename")
        if config.interaction_constraints:
            blockers.append("interaction_constraints")
        if config.extra_trees:
            blockers.append("extra_trees")
        if config.feature_fraction_bynode < 1.0:
            blockers.append("feature_fraction_bynode")
        if config.monotone_constraints and any(
                int(m) != 0 for m in config.monotone_constraints):
            blockers.append("monotone_constraints")
        if config.feature_contri:
            blockers.append("feature_contri")
        return blockers

    def _estimate_residency_bytes(self) -> int:
        """The fused hbm path pins the packed row matrix (bins + gh/mask
        channels) PLUS either the column-major copy (gather) or the
        per-tree sorted buffer + double buffer — ~2x the packed bytes."""
        item = 1 if self.max_num_bins <= 256 else 2
        C = self.num_features
        packed = self.num_data * (C * item + 9)
        return 2 * packed

    def _init_stream_jits(self) -> None:
        self._sj_init = jax.jit(self._stream_init_impl)
        self._sj_pick = jax.jit(self._stream_pick_impl)
        self._sj_part = jax.jit(self._stream_partition_impl)
        self._sj_chunk = jax.jit(self._stream_chunk_impl,
                                 static_argnames=("has_mask",))
        self._sj_finish = jax.jit(self._stream_finish_impl)
        self._sj_final = jax.jit(self._stream_finalize_impl)

    # -- traced pieces (shared by the jitted stream kernels) -----------
    def _stream_best_of(self, hist, pg, ph, pc, pout, depth, fm):
        """best_of of the fused program restricted to the stream-mode
        option subset (no voting/feature-sharding/bundle/extra/monotone/
        contri) — the surviving ops are replicated verbatim so gains,
        tie-breaks, and outputs match the resident program bit-for-bit."""
        p = self.params
        gain, thr, dl, lg, lh, lc, bits = per_feature_best(
            hist, pg, ph, pc, pout, self.num_bins_arr,
            self.default_bins_arr, self.missing_types_arr,
            self.is_categorical_arr, fm, p, self.has_categorical,
            constraints=None, rand_thresholds=None)
        parent_gain = leaf_gain(pg, ph, p, pc, pout)
        shift = parent_gain + p.min_gain_to_split
        f = jnp.argmax(gain, axis=0).astype(jnp.int32)
        g = gain[f] - shift
        ok = jnp.isfinite(gain[f]) & (g > 0.0)
        if self.config.max_depth > 0:
            ok = ok & (depth < self.config.max_depth)
        lout = calculate_leaf_output(lg[f], lh[f], p, lc[f], pout)
        rout = calculate_leaf_output(pg - lg[f], ph - lh[f], p,
                                     pc - lc[f], pout)
        return (jnp.where(ok, g, K_MIN_SCORE), f, thr[f], dl[f],
                self.is_categorical_arr[f], bits[f], lg[f], lh[f], lc[f],
                lout, rout)

    def _stream_chosen(self, state):
        """The pending split the argmax selects — the head of the fused
        split_step, recomputed identically by partition and finish so no
        host round-trip of split metadata can drift."""
        L = self.config.num_leaves
        leaf_f, leaf_i = state["leaf_f"], state["leaf_i"]
        leaf = jnp.argmax(leaf_f[:L, 4]).astype(jnp.int32)
        lf = leaf_f[leaf]
        li = leaf_i[leaf]
        ok = lf[4] > 0.0
        return leaf, lf, li, ok

    # -- jitted kernels -------------------------------------------------
    def _stream_init_impl(self, hist_root, fmask, gs, hs, ms):
        """State init of the fused program (root totals, root best split,
        consolidated leaf/node matrices), with the sorted-layout gradient
        channels riding the carry instead of the packed payload."""
        cfg = self.config
        N = self.num_data
        L = cfg.num_leaves
        NODES = max(L - 1, 1)
        W = self._window(N)
        p = self.params
        f32, i32 = jnp.float32, jnp.int32
        totals = jnp.sum(hist_root[0], axis=0)
        root_out = calculate_leaf_output(totals[0], totals[1], p,
                                         totals[2], 0.0)
        neg_inf = jnp.float32(-jnp.inf)
        pos_inf = jnp.float32(jnp.inf)
        (bg0, bf0, bt0, bdl0, bcat0, bbits0, blg0, blh0, blc0, blout0,
         brout0) = self._stream_best_of(hist_root, totals[0], totals[1],
                                        totals[2], root_out, jnp.int32(0),
                                        fmask)
        iota_l1 = jnp.arange(L + 1, dtype=i32)
        leaf_f = jnp.zeros((L + 1, 12), f32)
        leaf_f = leaf_f.at[:, 4].set(K_MIN_SCORE) \
                       .at[:, 10].set(-jnp.inf).at[:, 11].set(jnp.inf)
        leaf_f = leaf_f.at[0].set(jnp.stack(
            [totals[0], totals[1], totals[2], root_out, bg0, blg0, blh0,
             blc0, blout0, brout0, neg_inf, pos_inf]))
        leaf_i = jnp.zeros((L + 1, 9), i32)
        leaf_i = leaf_i.at[:, 0].set(N + iota_l1).at[:, 3].set(-1)
        leaf_i = leaf_i.at[0].set(jnp.stack(
            [i32(0), i32(N), i32(0), i32(-1), i32(0), bf0, bt0,
             bdl0.astype(i32), bcat0.astype(i32)]))
        leaf_bits = jnp.zeros((L + 1, 8), jnp.uint32).at[0].set(bbits0)
        state = dict(
            perm=jnp.concatenate([jnp.arange(N, dtype=i32),
                                  jnp.zeros(W, i32)]),
            perm_buf=jnp.zeros(N + W, i32),
            leaf_f=leaf_f, leaf_i=leaf_i, leaf_bits=leaf_bits,
            node_f=jnp.zeros((NODES + 1, 4), f32),
            node_i=jnp.zeros((NODES + 1, 6), i32).at[:, 4:6].set(~0),
            node_bits=jnp.zeros((NODES + 1, 8), jnp.uint32),
            hist=jnp.zeros((L + 1, self.num_features, self.Bb, HIST_C),
                           f32).at[0].set(hist_root),
            num_leaves=jnp.int32(1),
        )
        if self.layout == "sorted":
            state["gs"], state["hs"] = gs, hs
            state["gs_buf"] = jnp.zeros_like(gs)
            state["hs_buf"] = jnp.zeros_like(hs)
            if ms is not None:
                state["ms"] = ms
                state["ms_buf"] = jnp.zeros_like(ms)
        return state

    def _stream_pick_impl(self, state):
        leaf, lf, li, ok = self._stream_chosen(state)
        return leaf, ok, li[0], li[1], li[5]

    def _stream_partition_impl(self, state, cvals):
        """pbody + cbody of the fused split step, with the split feature's
        bin values arriving as the uploaded ``cvals`` buffer (slice-lane
        indexed, PV = pow2(count) >= nch*W) instead of a resident
        column/payload read. Also collects the per-lane go_left flags so
        the host can mirror the two-monotone-run placement (lefts
        ascending, rights reversed) onto its shard-side structures."""
        N = self.num_data
        W = self._window(N)
        PV = cvals.shape[0]
        # window-read invariants (the resident perm_slice/srow_slice
        # contracts): every start is begin + c*W <= begin + count <= N and
        # the carried buffers pad one full window past N, so no
        # dynamic_slice below can clamp; cvals is padded to a whole number
        # of windows so the c*W reads stay in range
        assert state["perm"].shape[0] == N + W
        assert state["perm_buf"].shape[0] == N + W
        assert PV % W == 0 and PV >= W
        lane = jnp.arange(W, dtype=jnp.int32)
        i32 = jnp.int32
        leaf, lf, li, ok = self._stream_chosen(state)
        feat = li[5]
        thrv, dlv, catv = li[6], li[7].astype(bool), li[8].astype(bool)
        bitsv = state["leaf_bits"][leaf]
        begin = li[0]
        count_eff = jnp.where(ok, li[1], 0)
        nch = (count_eff + W - 1) // W
        perm_in = state["perm"]
        sorted_mode = self.layout == "sorted"
        chans = [k for k in ("gs", "hs", "ms") if k in state]

        def pbody(s):
            c, lcur, rcur, pbuf, gbuf = s[:5]
            cbufs = list(s[5:])
            live = jnp.clip(count_eff - c * W, 0, W)
            valid = lane < live
            rows = lax.dynamic_slice(perm_in, (begin + c * W,), (W,))
            cv = lax.dynamic_slice(cvals, (c * W,), (W,)).astype(i32)
            gl = decision_go_left(
                cv, thrv, dlv, self.default_bins_arr[feat],
                self.missing_types_arr[feat], self.num_bins_arr[feat],
                catv, bitsv) & valid
            gbuf = lax.dynamic_update_slice(gbuf, gl, (c * W,))
            win = [rows]
            if sorted_mode:
                win += [lax.dynamic_slice(state[k], (begin + c * W,), (W,))
                        for k in chans]
            (pbuf, *cbufs), nl, nr = route_window(
                [pbuf] + cbufs, win, gl, ~gl & valid, lcur, rcur)
            return tuple([c + 1, lcur + nl, rcur - nr, pbuf, gbuf] + cbufs)

        init = [jnp.int32(0), begin, begin + count_eff,
                state["perm_buf"], jnp.zeros(PV, bool)]
        if sorted_mode:
            init += [state[k + "_buf"] for k in chans]
        out = lax.while_loop(lambda s: s[0] < nch, pbody, tuple(init))
        lend, pbuf, gbuf = out[1], out[3], out[4]
        cbufs = list(out[5:])
        left_count = lend - begin

        def cbody(s):
            c, pm = s[:2]
            cms = list(s[2:])
            start = begin + c * W
            valid = (c * W + lane) < count_eff
            vals = jnp.where(valid, lax.dynamic_slice(pbuf, (start,), (W,)),
                             lax.dynamic_slice(pm, (start,), (W,)))
            pm = lax.dynamic_update_slice(pm, vals, (start,))
            if sorted_mode:
                cms = [lax.dynamic_update_slice(
                    m, jnp.where(valid,
                                 lax.dynamic_slice(b, (start,), (W,)),
                                 lax.dynamic_slice(m, (start,), (W,))),
                    (start,))
                    for m, b in zip(cms, cbufs)]
            return tuple([c + 1, pm] + cms)

        cinit = [jnp.int32(0), perm_in]
        if sorted_mode:
            cinit += [state[k] for k in chans]
        cout = lax.while_loop(lambda s: s[0] < nch, cbody, tuple(cinit))
        new_state = dict(state)
        new_state["perm"] = cout[1]
        new_state["perm_buf"] = pbuf
        if sorted_mode:
            for k, m, b in zip(chans, cout[2:], cbufs):
                new_state[k] = m
                new_state[k + "_buf"] = b
        return new_state, gbuf, left_count

    def _stream_chunk_impl(self, acc, bins_up, pos, perm, gs, hs, ms,
                           grad, hess, row_mask, start, done, count, *,
                           has_mask: bool):
        """chunk_hist of the fused program with the window's bins uploaded
        (optionally compacted to the in-bag rows + their lane positions)
        while the gradient channels read device-resident state. Same
        values, same gh_contract/hist_pallas shapes, same ``acc + part``
        → bit-identical accumulation."""
        N = self.num_data
        W = self._window(N)
        C = self.num_features
        Bb = self.Bb
        # same pad invariant as the fused program's perm_slice/srow_slice:
        # start + done <= start + count <= N and the per-row buffers carry
        # a full window of tail padding, so the slices never clamp
        assert perm is None or perm.shape[0] == N + W
        assert gs is None or gs.shape[0] == N + W
        lane = jnp.arange(W, dtype=jnp.int32)
        if bins_up.shape[0] == W and pos is None:
            bins = bins_up
        else:
            # re-expand the compacted transfer into its window lanes;
            # out-of-bag lanes keep zero bins — their gh channels are
            # exactly 0.0 below, so each contributes the same exact +0.0
            # the resident program adds for masked rows
            bins = jnp.zeros((W, C), bins_up.dtype).at[pos].set(
                bins_up, mode="drop")
        valid = (done + lane) < count
        if self.layout == "sorted":
            g = lax.dynamic_slice(gs, (start + done,), (W,))
            h = lax.dynamic_slice(hs, (start + done,), (W,))
            if has_mask:
                valid = valid & (lax.dynamic_slice(
                    ms, (start + done,), (W,)) > 0)
        else:
            rows = lax.dynamic_slice(perm, (start + done,), (W,))
            g = grad[rows]
            h = hess[rows]
            if has_mask:
                valid = valid & row_mask[rows]
        if self.hist_impl == "pallas":
            from ..ops.hist_pallas import hist_pallas, pack_gh8
            live = jnp.clip(count - done, 0, W)
            gh8 = pack_gh8(g, h, valid)
            return acc + hist_pallas(bins, gh8, Bb, live)
        g0 = jnp.where(valid, g, 0.0)
        h0 = jnp.where(valid, h, 0.0)
        gh = jnp.stack([g0, h0, valid.astype(jnp.float32)], axis=1)
        bin_iota = jnp.arange(Bb, dtype=bins.dtype)
        onehot = (bins[:, :, None] == bin_iota).astype(jnp.bfloat16)
        part = gh_contract(gh, onehot.reshape(W, C * Bb),
                           self.hist_precision)
        return acc + part.reshape(HIST_C, C, Bb).transpose(1, 2, 0)

    def _stream_finish_impl(self, state, hist_small, left_count, fmask):
        """The tail of the fused split step: parent pointers, histogram
        subtraction, both children's best-split scans, consolidated state
        writes — everything after the row-touching loops."""
        cfg = self.config
        N = self.num_data
        F = self.num_features
        L = cfg.num_leaves
        NODES = max(L - 1, 1)
        i32 = jnp.int32
        leaf, lf, li, ok = self._stream_chosen(state)
        leaf_f, leaf_i = state["leaf_f"], state["leaf_i"]
        leaf_bits = state["leaf_bits"]
        bgain = lf[4]
        feat = li[5]
        thrv, dlv, catv = li[6], li[7].astype(bool), li[8].astype(bool)
        bitsv = leaf_bits[leaf]
        blg, blh, blc = lf[5], lf[6], lf[7]
        blout, brout = lf[8], lf[9]
        begin = li[0]
        count_eff = jnp.where(ok, li[1], 0)
        right_count = count_eff - left_count

        new_leaf = state["num_leaves"]
        nidx = new_leaf - 1
        wl = jnp.where(ok, leaf, L)
        wn = jnp.where(ok, new_leaf, L)
        wk = jnp.where(ok, nidx, NODES)

        pnode = li[3]
        was_left = li[4].astype(bool)
        safe_p = jnp.where((pnode >= 0) & ok, pnode, NODES)
        prow = state["node_i"][safe_p]
        prow = jnp.where(was_left, prow.at[4].set(nidx),
                         prow.at[5].set(nidx))
        node_i = state["node_i"].at[safe_p].set(prow)

        pg, ph, pc = lf[0], lf[1], lf[2]
        lg, lh, lc = blg, blh, blc
        rg, rh, rc = pg - lg, ph - lh, pc - lc
        lout, rout = blout, brout
        depth = li[2] + 1

        pmin, pmax = lf[10], lf[11]
        mono_f = self.mono_arr[feat]
        lcap = rcap = (lout + rout) * 0.5
        lmin = jnp.where(mono_f < 0, jnp.maximum(pmin, lcap), pmin)
        lmax = jnp.where(mono_f > 0, jnp.minimum(pmax, lcap), pmax)
        rmin = jnp.where(mono_f > 0, jnp.maximum(pmin, rcap), pmin)
        rmax = jnp.where(mono_f < 0, jnp.minimum(pmax, rcap), pmax)

        node_f = state["node_f"].at[wk].set(
            jnp.stack([bgain, lf[3], ph, pc]))
        node_i = node_i.at[wk].set(jnp.stack(
            [feat, thrv, dlv.astype(i32), catv.astype(i32),
             ~leaf, ~new_leaf]))
        node_bits = state["node_bits"].at[wk].set(bitsv)

        small_is_left = left_count <= right_count
        hist, hist_left, hist_right = write_children(
            state["hist"], leaf, hist_small, small_is_left, wl, wn)

        fms = jnp.broadcast_to(fmask, (2, F))
        best_children = jax.vmap(self._stream_best_of,
                                 in_axes=(0, 0, 0, 0, 0, None, 0))
        (bg2, bf2, bt2, bdl2, bcat2, bbits2, blg2, blh2, blc2,
         blout2, brout2) = best_children(
            jnp.stack([hist_left, hist_right]),
            jnp.stack([lg, rg]), jnp.stack([lh, rh]),
            jnp.stack([lc, rc]), jnp.stack([lout, rout]), depth, fms)

        lrow_f = jnp.stack([lg, lh, lc, lout, bg2[0], blg2[0], blh2[0],
                            blc2[0], blout2[0], brout2[0], lmin, lmax])
        rrow_f = jnp.stack([rg, rh, rc, rout, bg2[1], blg2[1], blh2[1],
                            blc2[1], blout2[1], brout2[1], rmin, rmax])
        lrow_i = jnp.stack([begin, left_count, depth, nidx, i32(1),
                            bf2[0], bt2[0], bdl2[0].astype(i32),
                            bcat2[0].astype(i32)])
        rrow_i = jnp.stack([begin + left_count, right_count, depth, nidx,
                            i32(0), bf2[1], bt2[1], bdl2[1].astype(i32),
                            bcat2[1].astype(i32)])

        out = dict(state)
        out["leaf_f"] = leaf_f.at[wl].set(lrow_f).at[wn].set(rrow_f)
        out["leaf_i"] = leaf_i.at[wl].set(lrow_i).at[wn].set(rrow_i)
        out["leaf_bits"] = leaf_bits.at[wl].set(bbits2[0]) \
                                    .at[wn].set(bbits2[1])
        out["node_f"] = node_f
        out["node_i"] = node_i
        out["node_bits"] = node_bits
        out["hist"] = hist
        out["num_leaves"] = state["num_leaves"] + ok.astype(i32)
        return out

    def _stream_finalize_impl(self, state):
        """row->leaf resolution + DeviceTree assembly (the fused
        program's epilogue, minus the quantized-leaf renewal the stream
        subset excludes)."""
        cfg = self.config
        N = self.num_data
        L = cfg.num_leaves
        NODES = max(L - 1, 1)
        # position -> leaf: the fused epilogue's own ops.partition helper
        pos_leaf = position_leaf(state["leaf_i"][:L, 0],
                                 state["leaf_i"][:L, 1], N)
        row_leaf = jnp.zeros(N, jnp.int32).at[
            state["perm"][:N]].set(pos_leaf)
        node_f = state["node_f"]
        node_i = state["node_i"]
        leaf_f = state["leaf_f"]
        leaf_i = state["leaf_i"]
        leaf_value_out = jnp.where(state["num_leaves"] > 1,
                                   leaf_f[:L, 3],
                                   jnp.zeros_like(leaf_f[:L, 3]))
        return DeviceTree(
            node_feature=node_i[:NODES, 0],
            node_threshold=node_i[:NODES, 1],
            node_default_left=node_i[:NODES, 2].astype(bool),
            node_is_cat=node_i[:NODES, 3].astype(bool),
            node_cat_bits=state["node_bits"][:NODES],
            node_left=node_i[:NODES, 4],
            node_right=node_i[:NODES, 5],
            node_gain=node_f[:NODES, 0],
            node_value=node_f[:NODES, 1],
            node_weight=node_f[:NODES, 2],
            node_count=node_f[:NODES, 3],
            leaf_value=leaf_value_out,
            leaf_weight=leaf_f[:L, 1],
            leaf_count=leaf_f[:L, 2],
            leaf_depth=leaf_i[:L, 2],
            leaf_parent_node=leaf_i[:L, 3],
            num_leaves=state["num_leaves"],
            row_leaf=row_leaf,
        )

    # -- the host-driven per-tree loop ----------------------------------
    def _stream_small_hist(self, state, grad, hess, row_mask, sb: int,
                           sc: int, payload, perm_host, mask_order):
        """One leaf's histogram via the window pump: host fetch (shard
        gather or payload memcpy, compacted to in-bag rows when a
        sampling mask is live), async device_put through the ring, jitted
        accumulate in the resident W-chunk order."""
        from ..data.stream import stream_windows
        N = self.num_data
        W = self._window(N)
        C = self.num_features
        nch = (sc + W - 1) // W
        dtype = self.sdata.shards[0].dtype
        compact = (mask_order is not None
                   and self.config.stream_goss_compact)
        acc = [jnp.zeros((C, self.Bb, HIST_C), jnp.float32)]
        has_mask = row_mask is not None
        gs = state.get("gs")
        hs = state.get("hs")
        ms = state.get("ms")
        sorted_mode = self.layout == "sorted"

        def fetch(c):
            lo = sb + c * W
            live = min(W, sc - c * W)
            if sorted_mode:
                lanes = np.arange(live)
                rows = None
            else:
                rows = perm_host[lo:lo + live]
                lanes = np.arange(live)
            if compact:
                inbag = (mask_order[lo:lo + live] if sorted_mode
                         else mask_order[rows])
                lanes = lanes[inbag]
                if rows is not None:
                    rows = rows[inbag]
            nsel = len(lanes)
            if not compact or nsel > (W * 7) // 8:
                buf = np.zeros((W, C), dtype=dtype)
                if sorted_mode:
                    buf[:live] = payload[lo:lo + live]
                else:
                    self.sdata.gather_rows(rows if not compact
                                           else perm_host[lo:lo + live],
                                           out=buf[:live])
                return (buf,)
            wc = max(_next_pow2(max(nsel, 1)), 256)
            buf = np.zeros((wc, C), dtype=dtype)
            pos = np.full(wc, W, np.int32)
            pos[:nsel] = lanes
            if nsel:
                if sorted_mode:
                    buf[:nsel] = payload[lo + lanes]
                else:
                    self.sdata.gather_rows(rows, out=buf[:nsel])
            return (buf, pos)

        def consume(c, bins_dev, *rest):
            pos_dev = rest[0] if rest else None
            acc[0] = self._sj_chunk(
                acc[0], bins_dev, pos_dev, state["perm"], gs, hs, ms,
                grad, hess, row_mask, jnp.int32(sb), jnp.int32(c * W),
                jnp.int32(sc), has_mask=has_mask)

        stream_windows(nch, fetch, consume, self.telemetry,
                       self.config.stream_prefetch_depth)
        return acc[0]

    def _train_tree_stream(self, grad, hess, row_mask) -> DeviceTree:
        """Grow one tree out-of-core: root histogram over all shards, then
        per split — pick (one small D2H), host column fetch + device
        partition, go_left mirror update, streamed small-child histogram,
        jitted finish. Breaking when no leaf has positive gain is exact:
        the remaining fused steps would all be masked no-ops."""
        cfg = self.config
        N = self.num_data
        W = self._window(N)
        NODES = max(cfg.num_leaves - 1, 1)
        fmask = self._feature_mask()
        has_mask = row_mask is not None
        mask_dev = row_mask if has_mask else None
        sorted_mode = self.layout == "sorted"

        # host-side per-tree state
        mask_host = None
        if has_mask and cfg.stream_goss_compact:
            # one D2H of the in-bag mask per tree drives window compaction
            # graftlint: disable=R1 — per-tree (not per-chunk) fetch; the
            # mask is the host-side input of the GOSS working-set shrink
            mask_host = np.asarray(jax.device_get(row_mask)).astype(bool)
        if sorted_mode:
            with self.telemetry.phase("layout_apply"):
                payload = self.sdata.dataset_order_copy()
                gs = jnp.concatenate([grad, jnp.zeros(W, jnp.float32)])
                hs = jnp.concatenate([hess, jnp.zeros(W, jnp.float32)])
                ms = (jnp.concatenate([row_mask.astype(jnp.float32),
                                       jnp.zeros(W, jnp.float32)])
                      if has_mask else None)
            perm_host = None
            mask_order = mask_host
        else:
            payload = None
            gs = hs = ms = None
            perm_host = np.arange(N, dtype=np.int64)
            mask_order = mask_host

        # root histogram over every shard window
        root_perm = jnp.concatenate([jnp.arange(N, dtype=jnp.int32),
                                     jnp.zeros(W, jnp.int32)])
        root_state = {"perm": root_perm}
        if sorted_mode:
            root_state.update(gs=gs, hs=hs)
            if ms is not None:
                root_state["ms"] = ms
        hist_root = self._stream_small_hist(
            root_state, grad, hess, mask_dev, 0, N, payload,
            np.arange(N, dtype=np.int64) if perm_host is None
            else perm_host, mask_order)
        state = self._sj_init(hist_root, fmask, gs, hs, ms)

        for _k in range(NODES if cfg.num_leaves > 1 else 0):
            # graftlint: disable=R1 — the stream mode's per-split sync:
            # the host must learn which leaf/feature to fetch from its
            # shards; this is the capacity-for-latency trade the mode IS
            pick = jax.device_get(self._sj_pick(state))
            leaf, ok, begin, count, feat = (int(pick[0]), bool(pick[1]),
                                            int(pick[2]), int(pick[3]),
                                            int(pick[4]))
            if not ok:
                break

            # split column values for the leaf slice: 1-2 B/row H2D
            pv = max(_next_pow2(max(count, 1)), W)
            dtype = self.sdata.shards[0].dtype
            with self.telemetry.phase("h2d_prefetch"):
                cv_host = np.zeros(pv, dtype=dtype)
                if sorted_mode:
                    cv_host[:count] = payload[begin:begin + count, feat]
                else:
                    cv_host[:count] = self.sdata.gather_col(
                        feat, perm_host[begin:begin + count])
                cvals = jax.device_put(cv_host)
            state, gbuf, left_cnt_dev = self._sj_part(state, cvals)
            # graftlint: disable=R1 — go_left + left count drive the host
            # mirror (payload/permutation) update; one small D2H per split
            gl, left_count = jax.device_get((gbuf, left_cnt_dev))
            gl = np.asarray(gl)[:count]
            left_count = int(left_count)
            # mirror the fused pbody placement: lefts stable ascending,
            # rights filled backward (reversed subsequence)
            if sorted_mode:
                sl = payload[begin:begin + count]
                payload[begin:begin + count] = np.concatenate(
                    [sl[gl], sl[~gl][::-1]])
                if mask_order is not None:
                    mo = mask_order[begin:begin + count]
                    mask_order[begin:begin + count] = np.concatenate(
                        [mo[gl], mo[~gl][::-1]])
            else:
                rs = perm_host[begin:begin + count]
                perm_host[begin:begin + count] = np.concatenate(
                    [rs[gl], rs[~gl][::-1]])

            right_count = count - left_count
            small_is_left = left_count <= right_count
            sb = begin if small_is_left else begin + left_count
            sc = left_count if small_is_left else right_count
            hist_small = self._stream_small_hist(
                state, grad, hess, mask_dev, sb, sc, payload, perm_host,
                mask_order)
            state = self._sj_finish(state, hist_small,
                                    jnp.int32(left_count), fmask)

        return self._sj_final(state)


# ---------------------------------------------------------------------------
# graftir IR contracts: the single-device fused programs carry no mesh, so
# their schedule clause is "collective-free"; what C2-C4 buy here is
# transfer-freedom, f64-freedom under the x64 retrace, and one-trace
# steady state (the ragged 900/703-row stream shards in the scenario
# inventory prove the pow2 bucketing keeps every kernel at one trace).
from ..analysis.ir.contracts import register_program

register_program(
    "FusedTreeLearner._train_tree_impl", collective_free=True,
    notes="whole-tree single-device program: split loop fused, no mesh")
for _k in ("init", "pick", "partition", "chunk", "finish", "finalize"):
    register_program(
        f"FusedTreeLearner._stream_{_k}_impl", collective_free=True,
        notes="host-streamed kernel; shard rows bucket to pow2 so ragged "
              "shards replay one trace")
del _k
