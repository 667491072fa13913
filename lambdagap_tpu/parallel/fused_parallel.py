"""Fused data-parallel learner: the whole-tree program under shard_map.

The multi-chip production path. The host-loop distributed learners
(``data_parallel.py``) re-introduce a D2H sync per split — exactly the
latency the fused learner exists to kill (models/fused_learner.py:8-11). Here
the ENTIRE leaf-wise tree build runs as one jitted shard_map program over the
``data`` mesh axis: rows are sharded, each shard runs the fused per-split
step on its local rows, and the only cross-shard traffic is one histogram
``psum`` per split (the TPU answer to the reference's
ReduceScatter+HistogramSumReducer,
reference: src/treelearner/data_parallel_tree_learner.cpp:283-298). The
best-split scan and leaf argmax run replicated on every shard from the
psum-ed histograms — identical inputs through identical arithmetic — which
subsumes SyncUpGlobalBestSplit (reference:
src/treelearner/parallel_tree_learner.h:209); zero per-split host syncs.

Sharding invariants the per-shard body maintains (see
FusedTreeLearner._train_tree_impl):

- ``perm`` / ``leaf_i`` begin/count are LOCAL (per-shard row partition);
- ``leaf_f`` aggregates, gains and chosen splits are GLOBAL (derived from
  psum-ed histograms — bit-identical across shards);
- the smaller-child choice uses the scan's global counts, never the local
  partition counts (shards must agree which side each psum describes);
- local chunk loops may run different trip counts per shard, but every
  shard reaches the per-split psum exactly once.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding

from ..config import Config
from ..data.dataset import BinnedDataset
from ..models.fused_learner import HIST_C, DeviceTree, FusedTreeLearner
from ..models.learner import _next_pow2
from ..ops.histogram import write_children
from ..ops.partition import position_leaf
from ..ops.split import (K_MIN_SCORE, calculate_leaf_output, leaf_gain,
                         per_feature_best)
from ..utils import log
from .mesh import shard_rows
from .sharding import (DATA_AXIS, FEATURE_AXIS, make_mesh, shard_map, spec,
                       specs)
from .multiprocess import global_array_from_local

_DEBUG_CHECKS = os.environ.get("LAMBDAGAP_DEBUG", "0") not in ("0", "",
                                                               "false")


class FusedDataParallelTreeLearner(FusedTreeLearner):
    """Rows sharded over the mesh; one whole tree per dispatch."""

    # this shard_map program keeps per-shard matrices device-resident;
    # stream x tree_learner=data now routes to Fused2DTreeLearner's
    # composed out-of-core program BEFORE this class is constructed, so
    # the opt-out only fires for pre-partitioned multi-process data
    # (process-local rows have no host-shard pump) — still a loud demote
    supports_stream = False
    min_chunk = 1 << 10

    def __init__(self, dataset: BinnedDataset, config: Config,
                 mesh: Optional[Mesh] = None) -> None:
        # mesh geometry first: the base-class init places the binned matrix
        # through _place_binned, which shards it directly (no host round-trip)
        self.proc_sharded = bool(getattr(dataset, "process_sharded", False))
        if self.proc_sharded:
            # pre_partition=true: this process holds only its own rows;
            # every process is padded to a common per-process block so the
            # global leading axis splits evenly over all devices
            # (reference: per-rank data with synced mappers,
            # src/io/dataset_loader.cpp:1072)
            self.mesh = mesh if mesh is not None else make_mesh(
                0, mesh_shape=config.mesh_shape)
            self._check_data_placement(config)
            self.n_dev = int(self.mesh.shape[DATA_AXIS])
            n_proc = jax.process_count()
            ldev = max(self.n_dev // n_proc, 1)
            max_cnt = int(np.max(dataset.global_row_counts))
            self.proc_pad = -(-max_cnt // ldev) * ldev
            self.n_pad = self.proc_pad * n_proc
            self.n_loc = self.proc_pad // ldev
            super().__init__(dataset, config)
            self.axis = DATA_AXIS
            real = np.zeros(self.proc_pad, dtype=bool)
            real[:dataset.num_data] = True
            self.real_mask = global_array_from_local(real, self.mesh,
                                                     spec("row_mask"))
        else:
            self.mesh = mesh if mesh is not None else make_mesh(
                config.tpu_num_devices, mesh_shape=config.mesh_shape)
            self._check_data_placement(config)
            self.n_dev = int(self.mesh.shape[DATA_AXIS])
            N = dataset.num_data
            pad = (-N) % self.n_dev
            self.n_pad = N + pad
            self.n_loc = self.n_pad // self.n_dev
            super().__init__(dataset, config)
            self.axis = DATA_AXIS

            # pad-row mask from shard_rows' explicit mask channel — the
            # one place padding is decided (ISSUE-8 satellite)
            self.real_mask = shard_rows(self.mesh,
                                        jnp.ones(N, dtype=bool))[1]

        # the whole-tree program as a shard_map body. check_vma off: the
        # replicated outputs (split structure, leaf values) are replicated
        # by construction from psum-ed histograms, but they share carried
        # state matrices with local values (leaf_i begin/count), which the
        # static replication tracker cannot see through.
        body = functools.partial(self._train_tree_impl, has_mask=True)
        qspec = spec("gq") if self.quant else spec("rep")
        # tree_layout=sorted: the leaf-ordered packed buffer is built by a
        # separate shard_map pre-pass (rows sharded, per-shard W pad rows
        # included in the global layout) and consumed by the training body
        # as one more row-sharded input; everything the per-split
        # permutation-apply touches is shard-local, so the histogram psum
        # stays the only collective per split
        srows_spec = spec("srows") if self.layout == "sorted" \
            else spec("rep")
        if self.layout == "sorted":
            self._layout_jit_dp = jax.jit(shard_map(
                functools.partial(self._build_sorted_impl, has_mask=True),
                mesh=self.mesh,
                in_specs=specs("grad", "hess", "row_mask", "x_rows")
                + (qspec, qspec),
                out_specs=spec("srows"), check_vma=False))
        in_specs = specs("grad", "hess", "row_mask", "fmask", "x_rows",
                         "x_cols") + (srows_spec, qspec, qspec) \
            + specs("scalar", "scalar", "ekey")
        out_specs = DeviceTree(**{
            f: spec("row_leaf") if f == "row_leaf" else spec("tree")
            for f in DeviceTree._fields})
        self._train_jit_dp = jax.jit(shard_map(
            body, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False))

    def _check_data_placement(self, config: Config) -> None:
        if int(self.mesh.shape.get(FEATURE_AXIS, 1)) > 1:
            log.fatal("the fused data/voting-parallel learners shard rows; "
                      "mesh_shape=%s places devices on the feature axis",
                      config.mesh_shape)

    # -- device-layout hooks -------------------------------------------
    def _place_binned(self, hx: np.ndarray) -> None:
        """Each shard's rows go from the host straight to its device (no
        whole-matrix staging on the first one). Under ``tree_layout=sorted``
        the program never reads the column-major copy (the serial learner's
        rule): a placeholder of one column a shard keeps the ``x_cols``
        spec sharding over the data axis, where the copy would be N*C dead
        bytes of every device."""
        if self.proc_sharded:
            pad = self.proc_pad - hx.shape[0]
            if pad:
                hx = np.pad(hx, ((0, pad), (0, 0)))
            self.hx_rows = global_array_from_local(hx, self.mesh,
                                                   spec("x_rows"))
            cols = np.zeros((1, self.proc_pad // self.n_loc), hx.dtype) \
                if self.layout == "sorted" else np.ascontiguousarray(hx.T)
            self.x_cols = global_array_from_local(cols, self.mesh,
                                                  spec("x_cols"))
            return
        pad = self.n_pad - hx.shape[0]
        if pad:
            hx = np.pad(hx, ((0, pad), (0, 0)))
        self.hx_rows = jax.device_put(
            hx, NamedSharding(self.mesh, spec("x_rows")))
        cols = np.zeros((1, self.n_dev), hx.dtype) \
            if self.layout == "sorted" else np.ascontiguousarray(hx.T)
        self.x_cols = jax.device_put(
            cols, NamedSharding(self.mesh, spec("x_cols")))

    # ------------------------------------------------------------------
    def _shard_vec(self, v: jax.Array) -> jax.Array:
        if self.proc_sharded:
            # v is this process's LOCAL rows (boosting state is per-rank,
            # like the reference's per-machine Boosting object). Pad and
            # split on device — no host round-trip on the per-tree hot path.
            if isinstance(v, jax.Array) and not v.is_fully_addressable:
                if v.sharding.is_fully_replicated:
                    # replicated global array (e.g. state that passed
                    # through a shard_map output): take this process's copy
                    v = v.addressable_data(0)
                else:
                    from ..utils import log
                    log.fatal(
                        "pre-partitioned boosting state must be rank-local "
                        "(or replicated), got a cross-process sharded array "
                        "%s", v.sharding)
            v = jnp.asarray(v)
            if v.shape[0] == self.n_pad and self.n_pad != self.proc_pad:
                # GLOBAL-length replicated state: take this rank's block
                # (rank blocks tile the global axis exactly, so the
                # dynamic-slice start can never clamp)
                assert self.n_pad % self.proc_pad == 0
                p = jax.process_index() * self.proc_pad
                v = lax.dynamic_slice_in_dim(v, p, self.proc_pad, axis=0)
            pad = self.proc_pad - v.shape[0]
            if pad:
                v = jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1))
            gshape = (self.n_pad,) + v.shape[1:]
            sharding = NamedSharding(self.mesh,
                                     spec("row_mask", ndim=v.ndim))
            p0 = jax.process_index() * self.proc_pad
            blocks = []
            for d, idx in sharding.addressable_devices_indices_map(
                    gshape).items():
                lo = (idx[0].start or 0) - p0
                blocks.append(jax.device_put(v[lo:lo + self.n_loc], d))
            return jax.make_array_from_single_device_arrays(
                gshape, sharding, blocks)
        return shard_rows(self.mesh, v)[0]

    def _check_shard_agreement(self, rec: DeviceTree) -> None:
        """LAMBDAGAP_DEBUG cross-shard divergence check. The tree record is
        nominally replicated — every shard derives it from identically
        psum-ed histograms — but ``check_vma=False`` on the shard_map means
        the static checker never proves it: a dropped psum on a new code
        path would silently corrupt training. Here each device's copy of
        the per-split decisions is compared bit-for-bit (the runtime analog
        of the reference's SyncUpGlobalBestSplit all-reduce agreeing on one
        winner, src/treelearner/parallel_tree_learner.h:209)."""
        from ..utils import log
        for name in ("node_feature", "node_threshold", "node_gain",
                     "leaf_value", "num_leaves"):
            arr = getattr(rec, name)
            shards = getattr(arr, "addressable_shards", None)
            if not shards:
                continue
            ref = np.asarray(shards[0].data)
            for s in shards[1:]:
                got = np.asarray(s.data)
                if not np.array_equal(ref, got, equal_nan=True):
                    bad = np.nonzero(ref != got)[0][:8] if ref.ndim else []
                    log.fatal(
                        "cross-shard divergence in %s on device %s "
                        "(first diverging indices %s): shards disagreed on "
                        "the split sequence — a collective is missing from "
                        "the fused program", name, s.device, list(bad))

    def train_device(self, grad: jax.Array, hess: jax.Array,
                     row_mask: Optional[jax.Array] = None) -> DeviceTree:
        fmask = self._feature_mask()
        g = self._shard_vec(grad)
        h = self._shard_vec(hess)
        if row_mask is None:
            m = self.real_mask
        elif self.proc_sharded:
            m = self._shard_vec(row_mask) & self.real_mask
        else:
            # in-bag + pad-row masks combine in shard_rows' mask channel
            m = shard_rows(self.mesh, row_mask, mask=row_mask)[1]
        if self.quant:
            from ..ops.hist_pallas import quantize_gradients
            self._qkey, sub = jax.random.split(self._qkey)
            gmax = hmax = None
            if self.proc_sharded and jax.process_count() > 1:
                # every rank holds different rows: agree on GLOBAL |grad| /
                # hess maxima before deriving quantization scales, else the
                # psum-ed int32 histograms would mix incompatible units
                from jax.experimental import multihost_utils
                # graftlint: disable=R1 — one cross-host max sync per TREE
                # (not per split); quantization scales must agree globally
                lm = np.asarray(
                    [float(jnp.max(jnp.abs(grad))), float(jnp.max(hess))],
                    np.float32)
                gm = np.asarray(multihost_utils.process_allgather(
                    lm)).reshape(-1, 2).max(axis=0)
                gmax = jnp.float32(max(float(gm[0]), 1e-12))
                hmax = jnp.float32(max(float(gm[1]), 1e-12))
            gq, hq, gs, hs = quantize_gradients(
                grad, hess, sub, self.config.num_grad_quant_bins,
                self.config.stochastic_rounding, gmax=gmax, hmax=hmax)
            gq, hq = self._shard_vec(gq), self._shard_vec(hq)
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
            with self.telemetry.phase("layout_apply"):
                srows = self._layout_jit_dp(g, h, m, self.hx_rows, gq, hq)
        else:
            srows = self._srows_dummy
        rec = self._train_jit_dp(g, h, m, fmask, self.hx_rows, self.x_cols,
                                 srows, gq, hq, gs, hs, ekey)
        if _DEBUG_CHECKS:
            self._check_shard_agreement(rec)
        # consumers (score update, leaf renewal) see an unpadded [N] leaf map
        if self.proc_sharded:
            # hand back this process's LOCAL rows: the booster's score
            # update stays rank-local (one D2H per tree, not per split).
            # leaf_value is localized too (replicated global -> this
            # process's copy) so downstream boosting state never becomes a
            # cross-process array.
            from .multiprocess import local_block
            rec = rec._replace(
                row_leaf=jnp.asarray(local_block(rec.row_leaf,
                                                 self.num_data)),
                leaf_value=jnp.asarray(rec.leaf_value.addressable_data(0)))
        else:
            rec = rec._replace(row_leaf=rec.row_leaf[:self.num_data])
        self.last_row_leaf = rec.row_leaf
        return rec


class FusedFeatureParallelTreeLearner(FusedTreeLearner):
    """Feature-parallel as ONE compiled whole-tree program (reference:
    src/treelearner/feature_parallel_tree_learner.cpp — every rank holds
    all rows, features are partitioned for histogram work, local best
    splits merge via SyncUpGlobalBestSplit, parallel_tree_learner.h:209):
    rows stay replicated, the binned matrix is sharded along the COLUMN
    axis, histograms and scans are shard-local, and the only per-split
    traffic is one all_gather of the D per-shard best-split tuples plus a
    psum broadcast of the winning feature's column for the partition —
    zero per-split host syncs (the host-loop variant in
    feature_parallel.py pays a D2H per split; this one does not)."""

    # the winning split's column lives on ONE shard and is psum-broadcast
    # for the (row-replicated) partition; the sorted layout's
    # decode-from-window shortcut cannot express that, so this learner
    # explicitly opts out and keeps the gather layout
    supports_sorted_layout = False
    supports_stream = False

    def __init__(self, dataset: BinnedDataset, config: Config,
                 mesh: Optional[Mesh] = None) -> None:
        from ..utils import log
        if config.enable_bundle:
            # EFB bundles are columns; feature ownership under a bundled
            # shard would decouple from feature ids. Keep ownership trivial
            # (feat // C_loc) — the config copy avoids mutating the caller
            import copy
            config = copy.copy(config)
            config.enable_bundle = False
            log.info("EFB bundling is disabled under the fused "
                     "feature-parallel learner (column ownership must "
                     "follow feature ids)")
        self.mesh = mesh if mesh is not None else make_mesh(
            config.tpu_num_devices, mesh_shape=config.mesh_shape,
            shard_axis=FEATURE_AXIS)
        if int(self.mesh.shape.get(DATA_AXIS, 1)) > 1:
            log.fatal("the fused feature-parallel learner shards columns; "
                      "mesh_shape=%s places devices on the data axis",
                      config.mesh_shape)
        self.n_dev = int(self.mesh.shape[FEATURE_AXIS])
        super().__init__(dataset, config)
        if self.forced_seq is not None:
            # unreachable via the factory (gbdt._create_learner routes
            # forced-splits configs to the fused data-parallel learner)
            log.fatal("forced splits are not supported by the fused "
                      "feature-parallel learner; use tree_learner=data")
        self.feat_axis = FEATURE_AXIS
        # pad the per-feature meta arrays to the sharded width so the
        # per-shard dynamic slices stay in range; padded features can
        # never win (fmask False, 2-bin histograms of zeros)
        Fp = self._Fp
        pad = Fp - self.num_features
        if pad:
            self._real_F = self.num_features
            self.num_features = Fp
            z = lambda a, v: jnp.concatenate(
                [a, jnp.full((pad,), v, a.dtype)])
            self.num_bins_arr = z(self.num_bins_arr, 2)
            self.default_bins_arr = z(self.default_bins_arr, 0)
            self.missing_types_arr = z(self.missing_types_arr, 0)
            self.is_categorical_arr = z(self.is_categorical_arr, False)
            self.mono_arr = z(self.mono_arr, 0)
            self.nb_minus1_arr = z(self.nb_minus1_arr, 1)
            if self.contri_arr is not None:
                self.contri_arr = z(self.contri_arr, 1.0)
        else:
            self._real_F = self.num_features

        def sharded(grad, hess, mask, fmask, xr, xc, srows, gq, hq, gs, hs,
                    ekey, *, has_mask):
            body = functools.partial(self._train_tree_impl,
                                     has_mask=has_mask)
            # the SAME registry rules as the data-parallel program: on this
            # (1, D) feature placement the per-row specs' data axis has
            # extent 1 (rows replicated) while x_rows/x_cols shard columns
            return shard_map(
                body, mesh=self.mesh,
                in_specs=specs("grad", "hess", "row_mask", "fmask",
                               "x_rows", "x_cols", "rep", "gq", "hq",
                               "scalar", "scalar", "ekey"),
                out_specs=DeviceTree(
                    *([spec("tree")] * len(DeviceTree._fields))),
                check_vma=False)(grad, hess, mask, fmask, xr, xc, srows,
                                 gq, hq, gs, hs, ekey)

        self._train_jit = jax.jit(sharded, static_argnames=("has_mask",))

    def _place_binned(self, hx: np.ndarray) -> None:
        C = hx.shape[1]
        pad = (-C) % self.n_dev
        if pad:
            hx = np.pad(hx, ((0, 0), (0, pad)))
        self._Fp = C + pad
        self.hx_rows = jax.device_put(
            jnp.asarray(hx), NamedSharding(self.mesh, spec("x_rows")))
        self.x_cols = jax.device_put(
            jnp.asarray(np.ascontiguousarray(hx.T)),
            NamedSharding(self.mesh, spec("x_cols")))

    def _feature_mask(self) -> jax.Array:
        # sample over the REAL features only (num_features is the padded
        # program width), then pad False so pad columns can never win
        saved = self.num_features
        self.num_features = self._real_F
        try:
            m = super()._feature_mask()
        finally:
            self.num_features = saved
        pad = self.num_features - m.shape[0]
        if pad > 0:
            m = jnp.concatenate([m, jnp.zeros(pad, dtype=bool)])
        return m


class FusedVotingParallelTreeLearner(FusedDataParallelTreeLearner):
    """Voting-parallel as ONE compiled whole-tree program (reference:
    src/treelearner/voting_parallel_tree_learner.cpp — GlobalVoting :151-175
    + CopyLocalHistogram/Allreduce :184): histograms stay shard-local, each
    split step all_gathers the shards' top-k feature votes and psums only
    the voted columns — O(D·top_k·B) bytes per split instead of O(F·B) —
    with zero per-split host syncs (the host-loop variant in
    voting_parallel.py pays a D2H per split; this one does not)."""

    def __init__(self, dataset: BinnedDataset, config: Config,
                 mesh: Optional[Mesh] = None) -> None:
        from ..utils import log
        super().__init__(dataset, config, mesh)
        if self.forced_seq is not None:
            # unreachable via the factory (gbdt._create_learner routes
            # forced-splits configs to the fused data-parallel learner);
            # guards direct construction
            log.fatal("forced splits need global histograms, which voting "
                      "keeps local; use the fused data-parallel learner")
        self.voting = True
        self.vote_k = max(1, min(int(config.top_k), self.num_features))
        if self.quant and self.quant_exact:
            # voting stores RAW integer level sums in the float32 per-leaf
            # histogram state until the voted-column psum (the full-histogram
            # paths scale immediately after their psum), so exactness is
            # bounded by the f32 integer range, not the int32 accumulator —
            # i.e. the one-hot limit regardless of the configured kernel
            from ..ops.hist_pallas import exact_accum_limit
            qb = config.num_grad_quant_bins
            self.quant_exact = (dataset.num_data * qb
                                < exact_accum_limit("onehot"))
            if not self.quant_exact:
                log.warning("quantized voting-parallel level sums may exceed "
                            "the float32-exact range (%d rows x %d levels); "
                            "using per-chunk scaled float32 accumulation",
                            dataset.num_data, qb)


class Fused2DTreeLearner(FusedTreeLearner):
    """The fused 2-D ``data x feature`` program (ISSUE 15): rows shard
    over the ``data`` mesh axis AND histogram columns shard over the
    ``feature`` axis, in ONE compiled whole-tree program.

    Per split the collectives are exactly the registry's decomposition:

    - one histogram ``psum`` over ``data`` — each device accumulates its
      row block's partial histogram for its column block; the psum
      completes every column block (reference: the ReduceScatter +
      HistogramSumReducer of data_parallel_tree_learner.cpp:283-298);
    - one ``all_gather`` over ``feature`` of the per-shard best-split
      tuples + a replicated argmax — the voting-parallel hybrid's
      SyncUpGlobalBestSplit (parallel_tree_learner.h:209);
    - one ``psum`` broadcast over ``feature`` of the winning feature's
      (row-sharded) column for the shard-local partition.

    Every array spec comes from parallel/sharding.py RULES — the same
    rules the 1-D learners run at degenerate geometries; this class is
    the registry's ``(dd, ff)`` consumer, so ``make_mesh`` no longer
    gates ``dd>1 && ff>1``. Selected by an explicit 2-D ``mesh_shape``
    ("4x2", "1x8", ...) — degenerate grids (dd=1 or ff=1) run the same
    program, which is what makes the bench's grid sweep one learner.

    ``data_residency=stream`` COMPOSES with the mesh (the stream x
    distributed cell flips from loud demotion to supported): per-host
    ``ShardedBinnedDataset`` shards feed the ShardRing with
    mesh-sharded ``device_put`` (one put lands each data block's window
    slice on its own device), and the per-tree build is the host-driven
    loop of small shard_map kernels in ``_train_tree_stream2d`` — the
    same kernels-as-the-fused-program mirror contract as the serial
    stream mode, so streamed 2-D trees are bit-identical to resident
    2-D trees on the same grid.
    """

    # the winning column reaches the partition via the feature-axis psum
    # broadcast; the sorted layout's decode-from-window shortcut cannot
    # express a column another shard owns
    supports_sorted_layout = False
    supports_stream = True
    min_chunk = 1 << 10

    def __init__(self, dataset: BinnedDataset, config: Config,
                 mesh: Optional[Mesh] = None) -> None:
        if config.enable_bundle:
            # EFB bundles are columns; ownership under a bundled shard
            # would decouple from feature ids (the fused feature-parallel
            # precedent). The config copy avoids mutating the caller.
            import copy
            config = copy.copy(config)
            config.enable_bundle = False
            log.info("EFB bundling is disabled under the fused 2-D "
                     "learner (column ownership must follow feature ids)")
        self.mesh = mesh if mesh is not None else make_mesh(
            config.tpu_num_devices, mesh_shape=config.mesh_shape)
        self.dd = int(self.mesh.shape[DATA_AXIS])
        self.ff = int(self.mesh.shape[FEATURE_AXIS])
        self.n_dev = self.dd * self.ff
        N = dataset.num_data
        self.n_pad = N + ((-N) % self.dd)
        self.n_loc = self.n_pad // self.dd
        super().__init__(dataset, config)
        if self.forced_seq is not None:
            log.fatal("forced splits need the full histogram of the "
                      "forced leaf on every shard; the 2-D mesh shards "
                      "histogram columns — use mesh_shape=%dx1",
                      self.n_dev)
        self.axis = DATA_AXIS
        self.feat_axis = FEATURE_AXIS
        # pad the per-feature metadata to the column-sharded width Fp so
        # per-shard dynamic slices stay in range; pad columns can never
        # win (fmask False, 2-bin histograms of zeros) — the fused
        # feature-parallel recipe
        if self.residency == "stream":
            C = self.num_features
            self._Fp = C + ((-C) % self.ff)
        Fp = self._Fp
        pad = Fp - self.num_features
        self._real_F = self.num_features
        if pad:
            self.num_features = Fp
            z = lambda a, v: jnp.concatenate(
                [a, jnp.full((pad,), v, a.dtype)])
            self.num_bins_arr = z(self.num_bins_arr, 2)
            self.default_bins_arr = z(self.default_bins_arr, 0)
            self.missing_types_arr = z(self.missing_types_arr, 0)
            self.is_categorical_arr = z(self.is_categorical_arr, False)
            self.mono_arr = z(self.mono_arr, 0)
            self.nb_minus1_arr = z(self.nb_minus1_arr, 1)
            if self.contri_arr is not None:
                self.contri_arr = z(self.contri_arr, 1.0)
        # pad-row mask (False pads -> exact-zero histogram contributions)
        real = np.zeros(self.n_pad, dtype=bool)
        real[:N] = True
        self.real_mask = jax.device_put(
            jnp.asarray(real), NamedSharding(self.mesh, spec("row_mask")))
        if self.residency == "stream":
            self._stream2d_setup()
            return

        body = functools.partial(self._train_tree_impl, has_mask=True)
        qspec = spec("gq") if self.quant else spec("rep")
        in_specs = specs("grad", "hess", "row_mask", "fmask", "x_rows",
                         "x_cols") + (spec("rep"), qspec, qspec) \
            + specs("scalar", "scalar", "ekey")
        out_specs = DeviceTree(**{
            f: spec("row_leaf") if f == "row_leaf" else spec("tree")
            for f in DeviceTree._fields})
        self._train_jit_2d = jax.jit(shard_map(
            body, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False))

    # -- device-layout hooks -------------------------------------------
    def _place_binned(self, hx: np.ndarray) -> None:
        rpad = self.n_pad - hx.shape[0]
        C = hx.shape[1]
        cpad = (-C) % self.ff
        self._Fp = C + cpad
        if rpad or cpad:
            hx = np.pad(hx, ((0, rpad), (0, cpad)))
        self.hx_rows = jax.device_put(
            jnp.asarray(hx), NamedSharding(self.mesh, spec("x_rows")))
        self.x_cols = jax.device_put(
            jnp.asarray(np.ascontiguousarray(hx.T)),
            NamedSharding(self.mesh, spec("x_cols")))

    def _feature_mask(self) -> jax.Array:
        # sample over the REAL features only, pad False (pad columns can
        # never win)
        saved = self.num_features
        self.num_features = self._real_F
        try:
            m = super()._feature_mask()
        finally:
            self.num_features = saved
        pad = self.num_features - m.shape[0]
        if pad > 0:
            m = jnp.concatenate([m, jnp.zeros(pad, dtype=bool)])
        return m

    def _shard_vec(self, v: jax.Array) -> jax.Array:
        pad = self.n_pad - v.shape[0]
        if pad:
            v = jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1))
        return jax.device_put(
            v, NamedSharding(self.mesh, spec("row_mask", ndim=v.ndim)))

    # ------------------------------------------------------------------
    def train_device(self, grad: jax.Array, hess: jax.Array,
                     row_mask: Optional[jax.Array] = None) -> DeviceTree:
        if self.residency == "stream":
            rec = self._train_tree_stream2d(grad, hess, row_mask)
            self.last_row_leaf = rec.row_leaf
            return rec
        fmask = self._feature_mask()
        if row_mask is None:
            m = self.real_mask
        else:
            m = self._shard_vec(row_mask) & self.real_mask
        if self.quant:
            from ..ops.hist_pallas import quantize_gradients
            self._qkey, sub = jax.random.split(self._qkey)
            gq, hq, gs, hs = quantize_gradients(
                grad, hess, sub, self.config.num_grad_quant_bins,
                self.config.stochastic_rounding)
            gq, hq = self._shard_vec(gq), self._shard_vec(hq)
        else:
            gq = hq = jnp.zeros(1, jnp.int8)
            gs = hs = jnp.float32(1.0)
        if self._need_step_keys:
            self._ekey, e = jax.random.split(self._ekey)
            self._bkey, b = jax.random.split(self._bkey)
            ekey = jnp.stack([e, b])
        else:
            ekey = jnp.zeros((2, 2), jnp.uint32)
        g = self._shard_vec(grad)
        h = self._shard_vec(hess)
        rec = self._train_jit_2d(
            g, h, m, fmask, self.hx_rows, self.x_cols,
            self._srows_dummy, gq, hq, gs, hs, ekey)
        rec = rec._replace(row_leaf=rec.row_leaf[:self.num_data])
        self.last_row_leaf = rec.row_leaf
        return rec

    # ------------------------------------------------------------------
    # data_residency=stream x 2-D mesh: the composed out-of-core path
    # ------------------------------------------------------------------
    # The binned matrix lives in host shards (ShardedBinnedDataset); the
    # devices keep only O(N)-scalar per-row state, sharded over ``data``.
    # Each tree is the host-driven loop of small shard_map kernels whose
    # traced math replicates the fused 2-D program's split step
    # op-for-op (the serial stream mode's mirror contract, composed with
    # the mesh): per-device window accumulation in the resident W-chunk
    # order, ONE psum over ``data`` per histogram, the feature-sharded
    # scan + all_gather of _s2_best_of, and per-data-shard partitions
    # whose go_left flags keep the per-shard host permutation mirrors in
    # lockstep. Row windows reach the devices through the ShardRing with
    # mesh shardings: one ``put`` lands every data block's slice on its
    # own device (the per-host H2D ring of ROADMAP item 1), under the
    # usual h2d_prefetch/chunk_wait phases; GOSS/bagging masks compact
    # each block's transfer independently.

    def _stream2d_setup(self) -> None:
        self._W2 = self._window(self.n_loc)
        self._bins_dtype = self.sdata.shards[0].dtype
        mesh = self.mesh
        self._ring_shardings = (
            NamedSharding(mesh, spec("win_bins")),
            NamedSharding(mesh, spec("win_cvals", ndim=2)))
        self._cvals_sharding = NamedSharding(mesh, spec("win_cvals",
                                                        ndim=2))
        self._acc_sharding = NamedSharding(mesh, spec("hist_grid"))
        self._vec_sharding = NamedSharding(mesh, spec("count"))
        base = np.concatenate([np.arange(self.n_loc, dtype=np.int32),
                               np.zeros(self._W2, np.int32)])
        self._perm0_2d = jax.device_put(
            jnp.asarray(np.tile(base, self.dd)),
            NamedSharding(mesh, spec("perm")))

    def _init_stream_jits(self) -> None:
        # called from the base stream early-return; the mesh is already
        # set (Fused2DTreeLearner.__init__ builds it before super())
        mesh = self.mesh
        st = dict(perm=spec("perm"), perm_buf=spec("perm"),
                  leaf_f=spec("rep"), leaf_i=spec("leaf_local", ndim=3),
                  leaf_bits=spec("rep"), node_f=spec("rep"),
                  node_i=spec("rep"), node_bits=spec("rep"),
                  hist=spec("hist_state", ndim=4), num_leaves=spec("rep"))
        R = spec("rep")
        grid = spec("hist_grid", ndim=4)
        bins = spec("win_bins", ndim=3)
        vec = spec("count")
        sm = functools.partial(shard_map, mesh=mesh, check_vma=False)
        self._sj2_chunk_full = jax.jit(sm(
            functools.partial(self._s2_chunk_body, compacted=False),
            in_specs=(grid, bins) + specs("perm", "grad", "hess",
                                          "row_mask")
            + (vec, R, vec),
            out_specs=grid))
        self._sj2_chunk_compact = jax.jit(sm(
            functools.partial(self._s2_chunk_body, compacted=True),
            in_specs=(grid, bins, spec("win_pos", ndim=2))
            + specs("perm", "grad", "hess", "row_mask") + (vec, R, vec),
            out_specs=grid))
        self._sj2_init = jax.jit(sm(
            self._s2_init_body, in_specs=(grid, spec("fmask")),
            out_specs=st))
        self._sj2_pick = jax.jit(sm(
            self._s2_pick_body, in_specs=(st,),
            out_specs=(R, R, R, R, R, spec("begin"), spec("count"))))
        self._sj2_part = jax.jit(sm(
            self._s2_part_body,
            in_specs=(st, spec("win_cvals", ndim=2)),
            out_specs=(st, spec("win_lanes", ndim=2), spec("count"))))
        self._sj2_finish = jax.jit(sm(
            self._s2_finish_body,
            in_specs=(st, grid, vec, spec("fmask")), out_specs=st))
        self._sj2_final = jax.jit(sm(
            self._s2_final_body, in_specs=(st,),
            out_specs=DeviceTree(**{
                f: spec("row_leaf") if f == "row_leaf" else spec("tree")
                for f in DeviceTree._fields if f != "work"})))

    # -- per-device kernel bodies (local views inside shard_map) --------
    def _s2_best_of(self, hist, pg, ph, pc, pout, depth, fm):
        """Feature-sharded best split of the 2-D program restricted to
        the stream option subset (no voting/extra/monotone/contri/
        bundle) — the surviving ops replicate ``best_of_feat`` verbatim
        so gains, tie-breaks and outputs match the resident 2-D program
        bit-for-bit."""
        p = self.params
        C_loc = hist.shape[0]
        off = lax.axis_index(FEATURE_AXIS) * C_loc

        def sl(arr):
            # shards tile the padded feature axis exactly: no clamp
            assert arr.shape[0] % C_loc == 0
            return lax.dynamic_slice_in_dim(arr, off, C_loc, axis=0)

        gain, thr, dl, lg, lh, lc, bits = per_feature_best(
            hist, pg, ph, pc, pout, sl(self.num_bins_arr),
            sl(self.default_bins_arr), sl(self.missing_types_arr),
            sl(self.is_categorical_arr), sl(fm), p, self.has_categorical,
            constraints=None, rand_thresholds=None)
        parent_gain = leaf_gain(pg, ph, p, pc, pout)
        shift = parent_gain + p.min_gain_to_split
        fl = jnp.argmax(gain, axis=0).astype(jnp.int32)
        lout_l = calculate_leaf_output(lg[fl], lh[fl], p, lc[fl], pout)
        rout_l = calculate_leaf_output(pg - lg[fl], ph - lh[fl], p,
                                       pc - lc[fl], pout)
        fields = (gain[fl], off + fl, thr[fl], dl[fl].astype(jnp.int32),
                  sl(self.is_categorical_arr)[fl].astype(jnp.int32),
                  bits[fl], lg[fl], lh[fl], lc[fl], lout_l, rout_l)
        gathered = [lax.all_gather(x, FEATURE_AXIS) for x in fields]
        win = jnp.argmax(gathered[0], axis=0).astype(jnp.int32)
        gw = gathered[0][win]
        g = gw - shift
        ok = jnp.isfinite(gw) & (g > 0.0)
        if self.config.max_depth > 0:
            ok = ok & (depth < self.config.max_depth)
        return (jnp.where(ok, g, K_MIN_SCORE), gathered[1][win],
                gathered[2][win], gathered[3][win].astype(bool),
                gathered[4][win].astype(bool), gathered[5][win],
                gathered[6][win], gathered[7][win], gathered[8][win],
                gathered[9][win], gathered[10][win])

    def _s2_chunk_body(self, acc, bins_up, *args, compacted: bool):
        """One window's histogram contribution per device: the uploaded
        bins block (optionally compacted to in-bag lanes) against the
        device-resident gradient channels — same kernels, same values,
        same ``acc + part`` order as the resident program's chunk_hist.
        Shards whose trip count ended (done >= count) leave their
        accumulator bit-untouched, exactly like the resident per-shard
        while_loop that never runs those trips."""
        if compacted:
            pos, perm, grad, hess, mask, begin, done, count = args
        else:
            perm, grad, hess, mask, begin, done, count = args
            pos = None
        from ..ops.histogram import gh_contract
        W = self._W2
        C_loc = acc.shape[1]
        lane = jnp.arange(W, dtype=jnp.int32)
        b = bins_up[0]
        if pos is not None:
            # re-expand the compacted transfer into its window lanes:
            # out-of-bag lanes keep zero bins — their gh channels are
            # exactly 0.0 below, so each contributes the same exact +0.0
            # the resident program adds for masked rows
            bins = jnp.zeros((W, C_loc), b.dtype).at[pos[0]].set(
                b, mode="drop")
        else:
            bins = b
        begin_s = begin[0]
        count_s = count[0]
        # same pad invariant as the resident perm windows: begin + done
        # <= begin + count <= n_loc and perm carries W tail pad rows
        assert perm.shape[0] == self.n_loc + W
        valid = (done + lane) < count_s
        rows = lax.dynamic_slice(perm, (begin_s + done,), (W,))
        g = grad[rows]
        h = hess[rows]
        valid = valid & mask[rows]
        if self.hist_impl == "pallas":
            from ..ops.hist_pallas import hist_pallas, pack_gh8
            live = jnp.clip(count_s - done, 0, W)
            gh8 = pack_gh8(g, h, valid)
            part = hist_pallas(bins, gh8, self.Bb, live)
        else:
            g0 = jnp.where(valid, g, 0.0)
            h0 = jnp.where(valid, h, 0.0)
            gh = jnp.stack([g0, h0, valid.astype(jnp.float32)], axis=1)
            bin_iota = jnp.arange(self.Bb, dtype=bins.dtype)
            onehot = (bins[:, :, None] == bin_iota).astype(jnp.bfloat16)
            part = gh_contract(gh, onehot.reshape(W, C_loc * self.Bb),
                               self.hist_precision)
            part = part.reshape(HIST_C, C_loc, self.Bb).transpose(1, 2, 0)
        return jnp.where(done < count_s, acc[0] + part, acc[0])[None]

    def _s2_init_body(self, acc, fmask):
        """State init of the 2-D program: ONE psum over ``data``
        completes every column block's root histogram, shard 0's totals
        broadcast over ``feature`` (the resident program's aggregate
        contract), feature-sharded root best split."""
        cfg = self.config
        N = self.n_loc
        L = cfg.num_leaves
        NODES = max(L - 1, 1)
        W = self._W2
        p = self.params
        f32, i32 = jnp.float32, jnp.int32
        hist_root = lax.psum(acc[0], DATA_AXIS)
        totals = jnp.sum(hist_root[0], axis=0)
        fidx = lax.axis_index(FEATURE_AXIS)
        totals = lax.psum(jnp.where(fidx == 0, totals,
                                    jnp.zeros_like(totals)), FEATURE_AXIS)
        root_out = calculate_leaf_output(totals[0], totals[1], p,
                                         totals[2], 0.0)
        neg_inf = jnp.float32(-jnp.inf)
        pos_inf = jnp.float32(jnp.inf)
        (bg0, bf0, bt0, bdl0, bcat0, bbits0, blg0, blh0, blc0, blout0,
         brout0) = self._s2_best_of(hist_root, totals[0], totals[1],
                                    totals[2], root_out, i32(0), fmask)
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
        return dict(
            perm=jnp.concatenate([jnp.arange(N, dtype=i32),
                                  jnp.zeros(W, i32)]),
            perm_buf=jnp.zeros(N + W, i32),
            leaf_f=leaf_f, leaf_i=leaf_i[None],
            leaf_bits=jnp.zeros((L + 1, 8), jnp.uint32).at[0].set(bbits0),
            node_f=jnp.zeros((NODES + 1, 4), f32),
            node_i=jnp.zeros((NODES + 1, 6), i32).at[:, 4:6].set(~0),
            node_bits=jnp.zeros((NODES + 1, 8), jnp.uint32),
            hist=jnp.zeros((L + 1, hist_root.shape[0], self.Bb, HIST_C),
                           f32).at[0].set(hist_root),
            num_leaves=jnp.int32(1),
        )

    def _s2_pick_body(self, state):
        """The pending split (replicated) plus every data shard's local
        begin/count — the one D2H the host loop pays per split."""
        L = self.config.num_leaves
        leaf_f = state["leaf_f"]
        leaf = jnp.argmax(leaf_f[:L, 4]).astype(jnp.int32)
        lf = leaf_f[leaf]
        li = state["leaf_i"][0, leaf]
        ok = lf[4] > 0.0
        return (leaf, ok, li[5], lf[7], lf[2],
                li[0][None], jnp.where(ok, li[1], 0)[None])

    def _s2_part_body(self, state, cvals):
        """pbody + cbody of the fused split step per data shard, with
        the split feature's bin values arriving as the uploaded per-block
        ``cvals`` rows. Returns the per-lane go_left flags and the local
        left count so the host mirrors the two-monotone-run placement
        onto each shard's permutation mirror."""
        from ..ops.partition import decision_go_left
        N = self.n_loc
        W = self._W2
        PV = cvals.shape[1]
        assert state["perm"].shape[0] == N + W
        assert state["perm_buf"].shape[0] == N + W
        assert PV % W == 0 and PV >= W
        lane = jnp.arange(W, dtype=jnp.int32)
        i32 = jnp.int32
        L = self.config.num_leaves
        leaf = jnp.argmax(state["leaf_f"][:L, 4]).astype(i32)
        lf = state["leaf_f"][leaf]
        li = state["leaf_i"][0, leaf]
        ok = lf[4] > 0.0
        feat = li[5]
        thrv, dlv, catv = li[6], li[7].astype(bool), li[8].astype(bool)
        bitsv = state["leaf_bits"][leaf]
        begin = li[0]
        count_eff = jnp.where(ok, li[1], 0)
        nch = (count_eff + W - 1) // W
        perm_in = state["perm"]
        cv_flat = cvals[0]

        def pbody(s):
            c, lcur, rcur, pbuf, gbuf = s
            live = jnp.clip(count_eff - c * W, 0, W)
            valid = lane < live
            rows = lax.dynamic_slice(perm_in, (begin + c * W,), (W,))
            cv = lax.dynamic_slice(cv_flat, (c * W,), (W,)).astype(i32)
            gl = decision_go_left(
                cv, thrv, dlv, self.default_bins_arr[feat],
                self.missing_types_arr[feat], self.num_bins_arr[feat],
                catv, bitsv) & valid
            cums = jnp.cumsum(gl.astype(i32))
            nl = cums[W - 1]
            prefix_valid = jnp.minimum(lane + 1, live)
            lpos = lcur + cums - 1
            rpos = rcur - (prefix_valid - cums)
            pos = jnp.where(gl, lpos, jnp.where(valid, rpos, N))
            pbuf = pbuf.at[pos].set(rows, mode="drop")
            gbuf = lax.dynamic_update_slice(gbuf, gl, (c * W,))
            return c + 1, lcur + nl, rcur - (live - nl), pbuf, gbuf

        _, lend, _, pbuf, gbuf = lax.while_loop(
            lambda s: s[0] < nch, pbody,
            (i32(0), begin, begin + count_eff, state["perm_buf"],
             jnp.zeros(PV, bool)))
        left_count = lend - begin

        def cbody(s):
            c, pm = s
            start = begin + c * W
            valid = (c * W + lane) < count_eff
            vals = jnp.where(valid,
                             lax.dynamic_slice(pbuf, (start,), (W,)),
                             lax.dynamic_slice(pm, (start,), (W,)))
            return c + 1, lax.dynamic_update_slice(pm, vals, (start,))

        _, perm = lax.while_loop(lambda s: s[0] < nch, cbody,
                                 (i32(0), perm_in))
        new_state = dict(state)
        new_state["perm"] = perm
        new_state["perm_buf"] = pbuf
        return new_state, gbuf[None], left_count[None]

    def _s2_finish_body(self, state, acc, left_counts, fmask):
        """The tail of the fused 2-D split step: the one histogram psum
        over ``data``, parent pointers, subtraction trick with the
        GLOBAL smaller-side choice, both children's feature-sharded
        scans, consolidated state writes."""
        cfg = self.config
        F = self.num_features
        L = cfg.num_leaves
        NODES = max(L - 1, 1)
        i32 = jnp.int32
        hist_small = lax.psum(acc[0], DATA_AXIS)
        leaf_f = state["leaf_f"]
        leaf_i_l = state["leaf_i"][0]
        leaf_bits = state["leaf_bits"]
        leaf = jnp.argmax(leaf_f[:L, 4]).astype(i32)
        lf = leaf_f[leaf]
        li = leaf_i_l[leaf]
        ok = lf[4] > 0.0
        bgain = lf[4]
        feat = li[5]
        thrv, dlv, catv = li[6], li[7].astype(bool), li[8].astype(bool)
        bitsv = leaf_bits[leaf]
        blg, blh, blc = lf[5], lf[6], lf[7]
        blout, brout = lf[8], lf[9]
        begin = li[0]
        count_eff = jnp.where(ok, li[1], 0)
        left_count = left_counts[0]
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

        # the side choice must be identical on every shard (each shard's
        # local partial fed the one psum); local partition counts differ
        # per shard, the scan's global (in-bag) counts do not
        small_is_left = lc <= pc - lc
        hist, hist_left, hist_right = write_children(
            state["hist"], leaf, hist_small, small_is_left, wl, wn)

        fms = jnp.broadcast_to(fmask, (2, F))
        best_children = jax.vmap(self._s2_best_of,
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
        out["leaf_i"] = leaf_i_l.at[wl].set(lrow_i).at[wn].set(
            rrow_i)[None]
        out["leaf_bits"] = leaf_bits.at[wl].set(bbits2[0]) \
                                    .at[wn].set(bbits2[1])
        out["node_f"] = node_f
        out["node_i"] = node_i
        out["node_bits"] = node_bits
        out["hist"] = hist
        out["num_leaves"] = state["num_leaves"] + ok.astype(i32)
        return out

    def _s2_final_body(self, state):
        """Per-shard row->leaf resolution + DeviceTree assembly (the 2-D
        program's epilogue; quantized-leaf renewal is excluded by the
        stream option subset)."""
        cfg = self.config
        N = self.n_loc
        L = cfg.num_leaves
        NODES = max(L - 1, 1)
        leaf_i_l = state["leaf_i"][0]
        # position -> leaf: the fused epilogue's own ops.partition helper
        # (a leaf empty on this shard holds no position)
        pos_leaf = position_leaf(leaf_i_l[:L, 0], leaf_i_l[:L, 1], N)
        row_leaf = jnp.zeros(N, jnp.int32).at[
            state["perm"][:N]].set(pos_leaf)
        node_f = state["node_f"]
        node_i = state["node_i"]
        leaf_f = state["leaf_f"]
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
            leaf_depth=leaf_i_l[:L, 2],
            leaf_parent_node=leaf_i_l[:L, 3],
            num_leaves=state["num_leaves"],
            row_leaf=row_leaf,
        )

    # -- the host-driven composed loop ----------------------------------
    def _s2_pump(self, perms, begins, counts, perm_dev, g, h, m, mask_np):
        """Histogram window pump over every data block at once: the host
        builds one stacked ``[dd, W, Fp]`` buffer per window (per-block
        shard gathers, compacted to in-bag rows when a sampling mask is
        live), ONE mesh-sharded ``device_put`` through the ring lands
        each block's slice on its own device, and the jitted chunk
        kernel accumulates per device in the resident W-chunk order."""
        from ..data.stream import stream_windows
        dd, W = self.dd, self._W2
        Fp = self.num_features
        rF = self._real_F
        n_loc = self.n_loc
        Nr = self.num_data
        dtype = self._bins_dtype
        nch = int(max(-(-int(c) // W) for c in counts)) if counts.max() \
            else 0
        acc = [jax.device_put(
            jnp.zeros((dd, Fp, self.Bb, HIST_C), jnp.float32),
            self._acc_sharding)]
        if nch == 0:
            return acc[0]
        bvec = jax.device_put(jnp.asarray(begins, jnp.int32),
                              self._vec_sharding)
        cvec = jax.device_put(jnp.asarray(counts, jnp.int32),
                              self._vec_sharding)

        def block_rows(d, rows_l, buf_rows):
            ds_rows = d * n_loc + rows_l
            real = ds_rows < Nr
            if real.any():
                buf_rows[real, :rF] = self.sdata.gather_rows(ds_rows[real])

        def fetch(c):
            sel = None
            if mask_np is not None:
                sel = []
                for d in range(dd):
                    lo = int(begins[d]) + c * W
                    live = min(W, int(counts[d]) - c * W)
                    if live <= 0:
                        sel.append((np.empty(0, np.int64),
                                    np.empty(0, np.int64)))
                        continue
                    rows_l = perms[d][lo:lo + live]
                    inb = mask_np[d][rows_l]
                    sel.append((rows_l[inb], np.arange(live)[inb]))
                nsel = max(len(s[0]) for s in sel)
                if nsel <= (W * 7) // 8:
                    wc = max(_next_pow2(max(nsel, 1)), 256)
                    buf = np.zeros((dd, wc, Fp), dtype=dtype)
                    pos = np.full((dd, wc), W, np.int32)
                    for d in range(dd):
                        rows_l, lanes = sel[d]
                        k = len(rows_l)
                        if k:
                            pos[d, :k] = lanes
                            block_rows(d, rows_l, buf[d, :k])
                    return (buf, pos)
            buf = np.zeros((dd, W, Fp), dtype=dtype)
            for d in range(dd):
                lo = int(begins[d]) + c * W
                live = min(W, int(counts[d]) - c * W)
                if live > 0:
                    block_rows(d, perms[d][lo:lo + live], buf[d, :live])
            return (buf,)

        def consume(c, bins_dev, *rest):
            done = jnp.int32(c * W)
            if rest:
                acc[0] = self._sj2_chunk_compact(
                    acc[0], bins_dev, rest[0], perm_dev, g, h, m, bvec,
                    done, cvec)
            else:
                acc[0] = self._sj2_chunk_full(
                    acc[0], bins_dev, perm_dev, g, h, m, bvec, done, cvec)

        stream_windows(nch, fetch, consume, self.telemetry,
                       self.config.stream_prefetch_depth,
                       shardings=self._ring_shardings)
        return acc[0]

    def _train_tree_stream2d(self, grad, hess, row_mask) -> DeviceTree:
        """Grow one tree out-of-core on the 2-D mesh: root histogram over
        all blocks, then per split — pick (one small D2H), per-block
        column fetch + per-shard device partition, go_left mirror
        update, streamed small-child histogram, jitted finish."""
        cfg = self.config
        dd, W = self.dd, self._W2
        n_loc = self.n_loc
        Nr = self.num_data
        NODES = max(cfg.num_leaves - 1, 1)
        fmask = self._feature_mask()
        if row_mask is None:
            m = self.real_mask
        else:
            m = self._shard_vec(row_mask) & self.real_mask
        g = self._shard_vec(grad)
        h = self._shard_vec(hess)
        mask_np = None
        if row_mask is not None and cfg.stream_goss_compact:
            # one D2H of the in-bag mask per tree drives window compaction
            # graftlint: disable=R1 — per-tree (not per-chunk) fetch; the
            # mask is the host-side input of the GOSS working-set shrink
            mask_np = np.asarray(jax.device_get(m)).reshape(dd, n_loc)
        perms = [np.arange(n_loc, dtype=np.int64) for _ in range(dd)]

        acc = self._s2_pump(perms, np.zeros(dd, np.int64),
                            np.full(dd, n_loc, np.int64),
                            self._perm0_2d, g, h, m, mask_np)
        state = self._sj2_init(acc, fmask)

        for _k in range(NODES if cfg.num_leaves > 1 else 0):
            # graftlint: disable=R1 — the composed stream mode's
            # per-split sync: the host must learn which leaf/feature to
            # fetch from its shards (and each data block's local slice);
            # the capacity-for-latency trade the mode IS
            pick = jax.device_get(self._sj2_pick(state))
            leaf, ok, feat = int(pick[0]), bool(pick[1]), int(pick[2])
            blc, pc = float(pick[3]), float(pick[4])
            begins = np.asarray(pick[5], np.int64)
            counts = np.asarray(pick[6], np.int64)
            if not ok:
                break

            # split column values per block slice: 1-2 B/row over the
            # link, pad rows bin 0 (exactly the resident hx padding)
            PV = max(_next_pow2(max(int(counts.max()), 1)), W)
            cv = np.zeros((dd, PV), dtype=self._bins_dtype)
            for d in range(dd):
                cnt = int(counts[d])
                if cnt:
                    rows_l = perms[d][int(begins[d]):int(begins[d]) + cnt]
                    ds_rows = d * n_loc + rows_l
                    real = ds_rows < Nr
                    if real.any():
                        cv[d, :cnt][real] = self.sdata.gather_col(
                            feat, ds_rows[real])
            with self.telemetry.phase("h2d_prefetch"):
                cvals = jax.device_put(cv, self._cvals_sharding)
            state, gbuf, lc_dev = self._sj2_part(state, cvals)
            # graftlint: disable=R1 — go_left + left counts drive the
            # per-shard host mirrors; one small D2H per split
            gl, lcs = jax.device_get((gbuf, lc_dev))
            lcs = np.asarray(lcs, np.int64)
            for d in range(dd):
                cnt = int(counts[d])
                b = int(begins[d])
                if cnt:
                    gld = np.asarray(gl[d])[:cnt]
                    rs = perms[d][b:b + cnt]
                    # mirror the fused pbody placement: lefts stable
                    # ascending, rights filled backward (reversed)
                    perms[d][b:b + cnt] = np.concatenate(
                        [rs[gld], rs[~gld][::-1]])

            # GLOBAL smaller side from the scan's in-bag counts (the
            # device f32 compare replayed on the fetched f32 values)
            small_is_left = np.float32(blc) <= np.float32(pc) \
                - np.float32(blc)
            if small_is_left:
                sb, sc = begins, lcs
            else:
                sb, sc = begins + lcs, counts - lcs
            acc = self._s2_pump(perms, sb, sc, state["perm"], g, h, m,
                                mask_np)
            state = self._sj2_finish(state, acc, lc_dev, fmask)

        rec = self._sj2_final(state)
        return rec._replace(row_leaf=rec.row_leaf[:Nr])


# ---------------------------------------------------------------------------
# graftir IR contracts (`python -m lambdagap_tpu.analysis --ir`): the
# declared collective schedule of every program this module jits, verified
# against the lowered jaxpr across all four virtual grids. Editing this
# file invalidates exactly these programs' cached verdicts.
from ..analysis.ir.contracts import all_gather, psum, register_program


def _hist_bytes(d):
    # per-shard leaf histogram: ceil(F/ff) features x bins x {g,h,cnt}
    return -(-d["features"] // d["ff"]) * d["bins"] * d["hist_item"]


def _rows_bytes(d):
    # the tree's exact row counts: one int32 a leaf and one a node
    return 4 * (d["leaves"] + max(d["leaves"] - 1, 1))


def _rowflag_bytes(d):
    # go-left partition flags: one byte per shard-resident row
    return -(-d["rows"] // d["dd"])


register_program(
    "FusedDataParallelTreeLearner._train_tree_impl",
    quant_int_reduction=True,
    step_collectives=(psum("data", 1, "leaf histogram", _hist_bytes),),
    setup_collectives=(psum("data", 3, "root histogram; leaf rows; node "
                            "rows", lambda d: _hist_bytes(d) + _rows_bytes(d)),),
    notes="one histogram psum per split step; splits are chosen locally "
          "on the replicated reduced histograms; once a tree, the shards' "
          "exact leaf and node row counts — no other wire traffic")

register_program(
    "FusedVotingParallelTreeLearner._train_tree_impl",
    step_collectives=(psum("data", 1, "voted histogram columns"),
                      all_gather("data", 1, "local top-k votes")),
    setup_collectives=(psum("data", 2, "root histogram + vote meta"),
                       all_gather("data", 1, "root votes")),
    notes="PV-Tree schedule: local votes gathered over data, then only "
          "the voted feature columns are psum-ed")

register_program(
    "FusedFeatureParallelTreeLearner.__init__.sharded",
    step_collectives=(
        psum("feature", 1, "go-left row flags", _rowflag_bytes),
        all_gather("feature", 11, "best-split tuple (11 fields)")),
    setup_collectives=(
        all_gather("feature", 11, "root best-split tuple"),),
    notes="rows replicated, features sharded: the winning split is "
          "all_gather-ed over feature and partition flags psum-ed so "
          "every shard keeps the full row->leaf map")

register_program(
    "Fused2DTreeLearner._train_tree_impl",
    quant_int_reduction=True,
    step_collectives=(
        psum("data", 1, "leaf histogram", _hist_bytes),
        psum("feature", 1, "go-left row flags", _rowflag_bytes),
        all_gather("feature", 11, "best-split tuple (11 fields)")),
    setup_collectives=(
        psum("data", 1, "root histogram", _hist_bytes),
        psum("feature", 1, "per-feature meta", lambda d: d["features"]),
        all_gather("feature", 11, "root best-split tuple")),
    notes="the PR 15 invariant: three logical collectives per split step "
          "— hist psum over data, row-flag psum over feature, best-split "
          "all_gather over feature (11 eqns = 11 tuple fields) — with "
          "payload bytes grid-invariant-by-formula over 1x8/2x4/4x2/8x1")

# streaming split-step bodies: the split loop is driven from host, so each
# body's collectives sit at loop depth 0 (= the whole program IS one step)
register_program(
    "Fused2DTreeLearner._s2_init_body",
    setup_collectives=(
        psum("data", 1, "root histogram", _hist_bytes),
        psum("feature", 1, "per-feature meta", lambda d: d["features"]),
        all_gather("feature", 11, "root best-split tuple")))
register_program(
    "Fused2DTreeLearner._s2_finish_body",
    setup_collectives=(
        psum("data", 1, "sibling-subtracted child histogram", _hist_bytes),
        all_gather("feature", 11, "best-split tuple")))
register_program("Fused2DTreeLearner._s2_chunk_body", collective_free=True,
                 max_traces=2,
                 notes="full + compact payload layouts are two programs")
register_program("Fused2DTreeLearner._s2_pick_body", collective_free=True)
register_program("Fused2DTreeLearner._s2_part_body", collective_free=True)
register_program("Fused2DTreeLearner._s2_final_body", collective_free=True)
