"""GBDT boosting orchestration.

TPU re-implementation of the reference's GBDT class
(reference: src/boosting/gbdt.{h:37,cpp} — Init :73-129, TrainOneIter
:346-454, BoostFromAverage :321, UpdateScore :495-524, eval :476-493).

Scores live on device as ``[K, N]`` float32. The training-score update never
traverses trees: the learner's partition already knows every row's leaf, so
adding a tree is one gather + scatter-add (the analog of
``ScoreUpdater::AddScore`` going through ``AddScoreByLeaf``,
reference: src/boosting/score_updater.hpp:21-110).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..data.dataset import BinnedDataset
from ..metrics.base import Metric, create_metrics
from ..objectives.base import ObjectiveFunction, create_objective
from ..ops.predict import (RoutingTree, _round_depth, build_forest_blocks,
                           forest_to_arrays, predict_forest,
                           predict_forest_leaf, predict_tree_binned,
                           route_tree_binned, routing_tree_from_host,
                           tree_to_arrays)
from ..ops.predict_tensor import (build_tree_tiles, predict_forest_leaf_tensor,
                                  predict_forest_tensor)
from ..guard.nonfinite import NULL_GUARD, TrainGuard
from ..obs.telemetry import NULL_TELEMETRY, TrainTelemetry, device_scope
from ..utils import log
from .learner import SerialTreeLearner
from .sample_strategy import create_sample_strategy
from .tree import Tree

K_EPSILON = 1e-15


def _fused_mode_enabled(mode) -> bool:
    """tpu_fused_learner truthiness ('auto' counts as enabled; the serial
    branch additionally gates 'auto' on the backend)."""
    return mode == "auto" or mode in ("1", "true", "on", "yes", True)


def _demote_advanced_monotone(cfg, where: str) -> None:
    """advanced needs per-threshold dense bound arrays rebuilt per affected
    leaf (host-orchestrated only); basic and intermediate run in-program."""
    if (cfg.monotone_constraints
            and cfg.monotone_constraints_method == "advanced"):
        log.warning("monotone_constraints_method=advanced is not available "
                    "on %s; using 'intermediate' (basic and intermediate "
                    "run in-program)", where)
        cfg.monotone_constraints_method = "intermediate"


def _cegb_requested(cfg) -> bool:
    """Any CEGB penalty configured — the learner-routing predicate
    (reference: src/treelearner/cost_effective_gradient_boosting.hpp)."""
    return cfg.cegb_tradeoff > 0 and (
        cfg.cegb_penalty_split > 0
        or cfg.cegb_penalty_feature_coupled
        or cfg.cegb_penalty_feature_lazy)


@functools.partial(jax.jit, static_argnames=("k",))
def _score_update(scores, leaf_values, row_leaf, k: int):
    """scores[k] += leaf_values[row_leaf]: the zero-sync path's score update
    as ONE program, so that its ops carry the ``score_update`` scope (an
    eagerly dispatched op is compiled from a cache that knows no name
    stack, and a profiler trace then shows an anonymous jit_gather)."""
    with device_scope("score_update"):
        return scores.at[k].add(leaf_values[row_leaf])


@functools.partial(jax.jit, static_argnames=("k", "has_categorical"))
def _valid_tree_score(scores, x_binned, tree: RoutingTree, leaf_values,
                      default_bins, missing_types, num_bins, k: int,
                      has_categorical: bool):
    """scores[k] += the tree's value of every row of a watched set: ONE
    program a set's shape, whose compile key holds no property of the tree
    (``ops.predict.route_tree_binned``), fed the device-resident record the
    iteration already holds. A program of its own name: ``grad_device_ms``
    and ``tree_device_ms`` select theirs by name."""
    with device_scope("valid_score"):
        return scores.at[k].add(route_tree_binned(
            x_binned, tree, leaf_values, default_bins, missing_types,
            num_bins, has_categorical))


@functools.partial(jax.jit, static_argnames=("num_leaves",))
def _add_tree_score(score, perm, leaf_begin, leaf_count, leaf_values,
                    num_leaves: int):
    """score[perm[i]] += leaf_value[leaf containing position i]."""
    del leaf_count
    N = score.shape[0]
    order = jnp.argsort(leaf_begin)
    sorted_begin = leaf_begin[order]
    which = jnp.searchsorted(sorted_begin, jnp.arange(N, dtype=leaf_begin.dtype),
                             side="right") - 1
    pos_leaf = order[which]
    vals = leaf_values[pos_leaf]
    return score.at[perm].add(vals)


def dispatch_forest_predict(cfg, x, forest, tree_class, num_class: int,
                            max_depth: int, binned: bool,
                            early_stop_freq: int = 0,
                            early_stop_margin: float = 0.0,
                            blocks=None, has_linear: bool = False):
    """Route a whole-forest score dispatch through the configured traversal
    engine (``predict_engine``): the tensorized [rows x trees] engine
    (ops.predict_tensor) or the sequential per-tree reference scan
    (ops.predict). Both return bit-identical [num_class, N] float32;
    ``blocks`` are pre-sliced tree tiles/blocks from the booster or serve
    caches (either engine consumes the same layout). ``has_linear`` turns
    on the per-leaf dot-product payload in the traversal carry (linear
    trees; raw rows only — binned linear replay stays host-side).

    ``predict_engine=compiled`` rides the tensor branch here: this entry
    point serves the training-side replay paths (binned rows, refit,
    training score rebuilds), which traverse the TRAINING-shaped tables
    the infer compiler does not model — the compiled artifact takes over
    in GBDT.predict_raw and the serve cache, the raw serving shapes it
    exists for (docs/serving.md "Compiled forest artifacts")."""
    if cfg.predict_engine in ("tensor", "compiled"):
        return predict_forest_tensor(
            x, forest, tree_class, num_class, max_depth, binned,
            early_stop_freq, early_stop_margin,
            tree_tile=cfg.predict_tree_tile, tiles=blocks,
            has_linear=has_linear)
    return predict_forest(x, forest, tree_class, num_class, max_depth,
                          binned, early_stop_freq, early_stop_margin,
                          blocks=blocks, has_linear=has_linear)


def dispatch_forest_leaf(cfg, x, forest, max_depth: int, binned: bool,
                         blocks=None):
    """Engine-routed leaf-index dispatch ([T, N] int32), same contract as
    :func:`dispatch_forest_predict` (compiled rides the tensor branch: the
    artifact renumbers nodes but never leaves, so leaf indices are already
    engine-invariant)."""
    if cfg.predict_engine in ("tensor", "compiled"):
        return predict_forest_leaf_tensor(
            x, forest, max_depth, binned,
            tree_tile=cfg.predict_tree_tile, tiles=blocks)
    return predict_forest_leaf(x, forest, max_depth, binned, blocks=blocks)


def _finalize_tree(tree: "Tree", shrinkage: float, bias: float) -> "Tree":
    """Shrinkage + boost-from-average bias fold shared by every FUSED
    materialization path (reference: Tree::Shrinkage + Tree::AddBias,
    gbdt.cpp:415-421).

    The leaf multiply is rounded in float32: the fused fast path already
    added ``f32(leaf_value * shrinkage)`` into the device training scores
    before this tree ever materialized, and auto-resume replays scores
    from the serialized leaf values — a float64 multiply here would
    disagree with the device product by 1 ulp and silently break
    kill-and-resume byte-identity (tests/test_guard.py)."""
    lv32 = (tree.leaf_value[:tree.num_leaves].astype(np.float32)
            * np.float32(shrinkage)).astype(np.float32)
    tree.apply_shrinkage(shrinkage)
    tree.leaf_value[:tree.num_leaves] = lv32.astype(np.float64)
    if abs(bias) > K_EPSILON:
        tree.leaf_value[:tree.num_leaves] += bias
        tree.internal_value = [v + bias for v in tree.internal_value]
        if getattr(tree, "is_linear", False):
            tree.leaf_const[:tree.num_leaves] += bias
    return tree


class _LazyTree:
    """A trained tree still resident on device (fused learner); materializes
    to a host :class:`Tree` on first access."""

    __slots__ = ("learner", "rec", "shrinkage", "bias")

    def __init__(self, learner, rec, shrinkage: float, bias: float) -> None:
        self.learner = learner
        self.rec = rec
        self.shrinkage = shrinkage
        self.bias = bias

    def materialize(self) -> "Tree":
        return _finalize_tree(self.learner.materialize(self.rec),
                              self.shrinkage, self.bias)


class GBDT:
    """Gradient Boosting Decision Tree booster."""

    average_output = False   # True for RF (reference: rf.hpp average_output_)

    def __init__(self, config: Config, train_set: Optional[BinnedDataset]) -> None:
        self.config = config
        self.train_set = train_set
        self.iter_ = 0
        self.models: List[Tree] = []           # flat: iter-major, class-minor
        self.best_iteration = -1
        self.shrinkage_rate = config.learning_rate
        # predict caches + model generation id. The generation bumps on any
        # in-place mutation of the served forest (refit, set_leaf_output,
        # shuffle); serve's CompiledForestCache and the device-forest cache
        # below key on it so stale compiled forests can never be served.
        self.generation = 0
        self._fast_cache = None
        self._forest_cache = None

        self.objective: Optional[ObjectiveFunction] = create_objective(config)
        self.num_class = self.objective.num_class if self.objective else config.num_class
        self.num_tree_per_iteration = max(self.num_class, 1)

        self.train_metrics: List[Metric] = []
        self.valid_sets: List[Tuple[str, BinnedDataset]] = []
        self.valid_binned: List[jax.Array] = []
        self.valid_metrics: List[List[Metric]] = []
        self.valid_scores: List[jax.Array] = []
        self.telemetry: TrainTelemetry = NULL_TELEMETRY
        self.guard: TrainGuard = NULL_GUARD
        self.last_iteration_skipped = False

        if train_set is not None:
            self._setup_training(train_set)

    # ------------------------------------------------------------------
    def _setup_training(self, ds: BinnedDataset) -> None:
        self.num_data = ds.num_data
        if self.objective is not None:
            if self.config.linear_tree and self.objective.is_renew_tree_output:
                # (reference: config check "Cannot use regression_l1
                # objective when fitting linear trees")
                log.fatal("Cannot use the %s objective with linear_tree",
                          self.objective.name)
            self.objective.init(ds.metadata, ds.num_data)
        self.telemetry = TrainTelemetry.from_config(self.config)
        self.guard = TrainGuard.from_config(self.config)
        self.learner = self._create_learner(ds)
        # learners that host-orchestrate (SerialTreeLearner) record their
        # histogram/split/partition sub-phases through this handle; the
        # fused whole-tree program shows the same structure in profiler
        # windows via jax.named_scope instead
        self.learner.telemetry = self.telemetry
        self.sample_strategy = create_sample_strategy(
            self.config, ds.num_data,
            label=None if ds.metadata.label is None else np.asarray(ds.metadata.label),
            query_boundaries=ds.metadata.query_boundaries)
        K, N = self.num_tree_per_iteration, ds.num_data
        init = jnp.zeros((K, N), dtype=jnp.float32)
        if ds.metadata.init_score is not None:
            s = np.asarray(ds.metadata.init_score, dtype=np.float32)
            init = jnp.asarray(s.reshape(K, N) if s.size == K * N
                               else np.tile(s, (K, 1)))
            self.has_init_score = True
        else:
            self.has_init_score = False
        self.scores = init
        if self.config.is_provide_training_metric:
            self.train_metrics = create_metrics(self.config, ds.metadata, N)
        self._meta = ds.feature_arrays()
        if self.config.boosting == "rf":
            self.shrinkage_rate = 1.0

    def _forced_splits_data_parallel(self, ds, tl: str):
        """forcedsplits need a GLOBAL histogram of the forced leaf; voting
        keeps histograms shard-local and feature-parallel shards them by
        column — the full-histogram-psum learner honors the schedule."""
        log.warning("forcedsplits_filename with tree_learner=%s: training "
                    "with the fused data-parallel learner (full-histogram "
                    "psum per split) so forced splits apply", tl)
        if _cegb_requested(self.config):
            log.warning("cegb (cegb_tradeoff) is not applied by the fused "
                        "tree_learner=data learner")
        from ..parallel.fused_parallel import FusedDataParallelTreeLearner
        return FusedDataParallelTreeLearner(ds, self.config)

    def _route_fused_2d(self, ds: BinnedDataset, tl: str):
        """Route distributed training onto the fused 2-D data x feature
        learner (ISSUE 15) when either

        - ``mesh_shape`` names BOTH axes explicitly ("4x2", "1x8",
          "8x1", wildcard "0x2"): one program for every grid is what
          makes the bench's dd x ff sweep comparable and elastic resume
          across grid shapes byte-identical; or
        - ``data_residency=stream`` (or a pre-sharded dataset) is
          combined with ``tree_learner=data``: the composed out-of-core
          mode — the stream x distributed cell this learner flips from
          loud demotion to supported (docs/capability-matrix.md).

        Returns None when the 1-D learner dispatch below should run.
        """
        cfg = self.config
        if tl not in ("data", "voting", "feature"):
            return None
        s = str(cfg.mesh_shape).strip().lower().replace("*", "x")
        explicit_2d = "x" in s
        if not explicit_2d:
            from ..data.stream import ShardedBinnedDataset
            wants_stream = (cfg.data_residency == "stream"
                            or isinstance(ds, ShardedBinnedDataset))
            if not (wants_stream and tl == "data"):
                return None
        if not _fused_mode_enabled(cfg.tpu_fused_learner):
            if explicit_2d:
                log.fatal("mesh_shape=%s is a 2-D data x feature grid, "
                          "which only the fused learner executes; keep "
                          "tpu_fused_learner enabled or set one "
                          "mesh_shape extent implicit ('%s')",
                          cfg.mesh_shape, s.split("x")[0])
            return None
        if cfg.forcedsplits_filename:
            # forced splits need the forced leaf's FULL histogram on
            # every shard; the 2-D mesh shards histogram columns
            return self._forced_splits_data_parallel(ds, tl)
        not_applied = []
        if _cegb_requested(cfg):
            not_applied.append("cegb")
        if not_applied:
            log.warning("%s are not applied by the fused 2-D "
                        "tree_learner=%s learner", ", ".join(not_applied),
                        tl)
        from ..parallel.fused_parallel import Fused2DTreeLearner
        return Fused2DTreeLearner(ds, self.config)

    def _create_learner(self, ds: BinnedDataset):
        """Learner dispatch (reference: TreeLearner::CreateTreeLearner,
        src/treelearner/tree_learner.cpp — (tree_learner, device) -> class).

        For serial training the whole-tree-on-device FusedTreeLearner is the
        production path (auto on accelerators); the host-orchestrated
        SerialTreeLearner remains for debugging / explicit opt-out."""
        tl = self.config.tree_learner
        if getattr(ds, "process_sharded", False):
            # pre_partition=true multi-process data: only the fused
            # data-parallel learner consumes process-local row blocks
            # (reference: pre-partitioned loading feeds the distributed
            # learners, src/io/dataset_loader.cpp:1072)
            cfg = self.config
            if tl not in ("serial", "data"):
                log.fatal("pre-partitioned multi-process training supports "
                          "tree_learner=data (got %r)", tl)
            if cfg.linear_tree:
                log.warning("linear_tree is not supported with "
                            "pre_partition=true (pre-partitioned "
                            "multi-process training); training "
                            "constant-leaf trees")
                cfg.linear_tree = False
            _demote_advanced_monotone(
                cfg, "the fused data-parallel learner")
            not_applied = []
            if _cegb_requested(cfg):
                not_applied.append("cegb")
            if not_applied:
                log.warning("%s are not applied by pre_partition=true "
                            "training", ", ".join(not_applied))
            from ..parallel.fused_parallel import FusedDataParallelTreeLearner
            return FusedDataParallelTreeLearner(ds, self.config)
        if tl == "serial":
            cfg = self.config
            if cfg.linear_tree:
                # linear leaves are first-class on the fused learner (the
                # MXU-batched leaf solve, docs/linear-trees.md); demote the
                # combos the batched path cannot express LOUDLY before any
                # program compiles
                from .linear_leaf import resolve_linear_config
                resolve_linear_config(cfg)
            mode = cfg.tpu_fused_learner
            use_fused = (jax.default_backend() != "cpu" if mode == "auto"
                         else _fused_mode_enabled(mode))
            # niche tree options live on the host-orchestrated learner (the
            # same shape as the reference's CUDA learner deferring
            # unsupported combos to the CPU path)
            host_only = []
            if (cfg.monotone_constraints
                    and cfg.monotone_constraints_method == "advanced"):
                # advanced needs the per-threshold dense bound arrays
                # rebuilt per affected leaf — host-orchestrated only
                # (basic AND intermediate run inside the fused program,
                # incl. intermediate's cross-leaf propagation + re-scans)
                host_only.append("monotone_constraints_method=advanced")
            if _cegb_requested(cfg):
                host_only.append("cegb")
            if use_fused and host_only:
                log.warning("Using the host-driven serial learner for: %s "
                            "— on a high-latency device link this path "
                            "pays one host sync per split instead of the "
                            "fused whole-tree program's zero",
                            ", ".join(host_only))
                use_fused = False
            if cfg.use_quantized_grad and not use_fused:
                log.warning("use_quantized_grad is only implemented by the "
                            "fused device learner; training runs in full "
                            "precision")
            if use_fused:
                from .fused_learner import FusedTreeLearner
                return FusedTreeLearner(ds, self.config)
            return SerialTreeLearner(ds, self.config)
        if self.config.linear_tree:
            log.warning("linear_tree is not supported with tree_learner=%s; "
                        "training constant-leaf trees", tl)
            self.config.linear_tree = False
        if self.config.interaction_constraints and not (
                tl in ("data", "voting", "feature")
                and _fused_mode_enabled(self.config.tpu_fused_learner)):
            # only the fused data-parallel program filters features by the
            # per-leaf path in-program; the host-loop distributed learners
            # do not, and silently dropping a constraint is worse than
            # failing
            log.fatal("interaction_constraints with tree_learner=%s require "
                      "the fused learner (keep tpu_fused_learner enabled "
                      "on data/voting/feature) or tree_learner=serial", tl)
        if tl in ("data", "voting", "feature") and _fused_mode_enabled(
                self.config.tpu_fused_learner):
            _demote_advanced_monotone(self.config,
                                      "the fused distributed learners")
        learner_2d = self._route_fused_2d(ds, tl)
        if learner_2d is not None:
            return learner_2d
        if tl == "data":
            # the fused whole-tree shard_map program is the production
            # multi-chip path (one psum per split, zero per-split host
            # syncs); the host-loop learner is the explicit opt-out
            # (tpu_fused_learner=0). Options the chosen learner does not
            # apply are warned, not silently swallowed.
            cfg = self.config
            not_applied = []
            if _cegb_requested(cfg):
                not_applied.append("cegb")
            if _fused_mode_enabled(cfg.tpu_fused_learner):
                if not_applied:
                    log.warning("%s are not applied by tree_learner=data",
                                ", ".join(not_applied))
                from ..parallel.fused_parallel import \
                    FusedDataParallelTreeLearner
                return FusedDataParallelTreeLearner(ds, self.config)
            # host-loop learner: per-node sampling also unsupported
            if cfg.feature_fraction_bynode < 1.0:
                not_applied.append("feature_fraction_bynode")
            if not_applied:
                log.warning("%s are not applied by the host-loop "
                            "tree_learner=data", ", ".join(not_applied))
        if tl == "voting" and _fused_mode_enabled(
                self.config.tpu_fused_learner):
            # fused voting: whole-tree program with per-split top-k vote +
            # voted-column psum; combinations it cannot express fall back
            # to the host-loop voting learner below
            cfg = self.config
            if cfg.forcedsplits_filename:
                return self._forced_splits_data_parallel(ds, tl)
            host_only = []
            if _cegb_requested(cfg):
                host_only.append("cegb")
            if host_only:
                if cfg.interaction_constraints:
                    # the host-loop voting learner does not filter features
                    # by interaction set; dropping a constraint silently is
                    # worse than failing
                    log.fatal("interaction_constraints with "
                              "tree_learner=voting cannot be combined "
                              "with %s", ", ".join(host_only))
                log.info("Using the host-loop voting learner for: %s",
                         ", ".join(host_only))
            else:
                from ..parallel.fused_parallel import \
                    FusedVotingParallelTreeLearner
                return FusedVotingParallelTreeLearner(ds, self.config)
        if tl == "feature" and _fused_mode_enabled(
                self.config.tpu_fused_learner):
            cfg = self.config
            if cfg.forcedsplits_filename:
                return self._forced_splits_data_parallel(ds, tl)
            if _cegb_requested(cfg):
                log.warning("cegb (cegb_tradeoff) is not applied by "
                            "tree_learner=feature")
            from ..parallel.fused_parallel import \
                FusedFeatureParallelTreeLearner
            return FusedFeatureParallelTreeLearner(ds, self.config)
        from ..parallel import (DataParallelTreeLearner,
                                FeatureParallelTreeLearner,
                                VotingParallelTreeLearner)
        cls = {"data": DataParallelTreeLearner,
               "feature": FeatureParallelTreeLearner,
               "voting": VotingParallelTreeLearner}[tl]
        return cls(ds, self.config)

    def add_valid_set(self, ds: BinnedDataset, name: str) -> None:
        self.valid_sets.append((name, ds))
        self.valid_binned.append(jnp.asarray(ds.binned))
        self.valid_metrics.append(create_metrics(self.config, ds.metadata, ds.num_data))
        K = self.num_tree_per_iteration
        init = jnp.zeros((K, ds.num_data), dtype=jnp.float32)
        if ds.metadata.init_score is not None:
            s = np.asarray(ds.metadata.init_score, dtype=np.float32)
            init = jnp.asarray(s.reshape(K, ds.num_data) if s.size == K * ds.num_data
                               else np.tile(s, (K, 1)))
        self.valid_scores.append(init)
        # replay existing model onto the new valid set (one batched dispatch)
        if self.models:
            vi = len(self.valid_sets) - 1
            trees = self.host_models
            forest, depth = forest_to_arrays(trees, feature_meta=self._meta,
                                             use_inner_feature=True)
            if any(getattr(t, "is_linear", False) for t in trees):
                if ds.raw is None:
                    log.fatal("Valid set %r needs the raw feature matrix "
                              "retained to replay a linear_tree model", name)
                self.valid_scores[vi] = self._replay_linear_forest(
                    trees, forest, depth, self.valid_binned[vi], ds.raw,
                    self.valid_scores[vi])
                return
            tree_class = jnp.asarray(
                [i % K for i in range(len(trees))], jnp.int32)
            self.valid_scores[vi] = self.valid_scores[vi] + \
                dispatch_forest_predict(self.config, self.valid_binned[vi],
                                        forest, tree_class, K, depth,
                                        binned=True)

    def _replay_linear_forest(self, trees, forest, depth, binned, raw,
                              scores) -> jax.Array:
        """Add a linear-tree forest's outputs to ``scores`` (constant-leaf
        replay would silently diverge from predict()).

        The adds run PER TREE in forest order, each tree's float64 host
        outputs rounded to f32 before its device add — the exact addition
        sequence training used (`_update_train_score` adds one f32 tree at
        a time), so snapshot resume replays scores bit-identically. A
        single summed-in-f64 add would differ by ulps and silently break
        kill-and-resume byte-identity (the PR 6 drift class)."""
        from .tree import linear_leaf_outputs
        K = self.num_tree_per_iteration
        # one leaf-index fetch for the whole forest being replayed
        # (resume/valid attach — no hot function reaches this path, so R1
        # never fired here; the suppression this comment used to carry was
        # inert from birth and R14 flagged it)
        leaf_T = np.asarray(jax.device_get(dispatch_forest_leaf(
            self.config, binned, forest, depth, binned=True)))
        for i, t in enumerate(trees):
            add = linear_leaf_outputs(t, raw, leaf_T[i])
            scores = scores.at[i % K].add(
                jnp.asarray(add.astype(np.float32)))
        return scores

    # ------------------------------------------------------------------
    def boosting(self) -> Tuple[jax.Array, jax.Array]:
        """Compute gradients at current scores
        (reference: GBDT::Boosting, gbdt.cpp:222-237)."""
        return self.objective.get_gradients_fast(self.scores)

    def train_one_iter(self, grad: Optional[jax.Array] = None,
                       hess: Optional[jax.Array] = None) -> bool:
        """One boosting iteration. Returns True when training should stop
        (no splittable leaves), mirroring gbdt.cpp:346-454."""
        cfg = self.config
        tel = self.telemetry
        tel.begin_iteration(self.iter_)
        # crash fault point + skip_tree restore capture (a no-op when DART
        # already captured the pre-dropout state for this iteration)
        self.guard.begin_iteration(self)
        self.last_iteration_skipped = False
        init_scores = [0.0] * self.num_tree_per_iteration
        if grad is None or hess is None:
            if self.objective is None:
                log.fatal("No objective and no custom gradients provided")
            # boost from average once, before the first gradient computation
            if not self.models and not self.has_init_score \
                    and cfg.boost_from_average:
                init_obj = self.objective
                ts = self.train_set
                if (getattr(ts, "process_sharded", False)
                        and getattr(ts, "global_label", None) is not None):
                    # the init score must come from GLOBAL label stats or
                    # each rank bakes a different constant into tree 0
                    # (reference: BoostFromAverage syncs over Network)
                    from ..data.dataset import Metadata
                    from ..objectives.base import create_objective
                    md_g = Metadata()
                    md_g.label = ts.global_label
                    md_g.weight = ts.global_weight
                    if getattr(ts, "global_group", None) is not None:
                        md_g.set_group(ts.global_group)
                    init_obj = create_objective(cfg)
                    init_obj.init(md_g, len(ts.global_label))
                for k in range(self.num_tree_per_iteration):
                    init = init_obj.boost_from_score(k)
                    if abs(init) > K_EPSILON:
                        init_scores[k] = init
                        self.scores = self.scores.at[k].add(init)
                        for vi in range(len(self.valid_scores)):
                            self.valid_scores[vi] = self.valid_scores[vi].at[k].add(init)
                        log.info("Start training from score %f", init)
            with tel.phase("gradients"):
                grad, hess = self.boosting()
            if self.objective.work_counts:
                # host constants of the ranking objectives' bucketing: no
                # device array rides along, so nothing is read
                tel.defer_counts((), lambda _: self.objective.work_counts)
        if self.valid_sets:
            tel.defer_counts((), lambda _: self._valid_counts())
        grad, hess = self.guard.admit_gradients(self, grad, hess)

        with tel.phase("sampling"):
            grad, hess, mask = self.sample_strategy.sample(self.iter_, grad,
                                                           hess)

        from .fused_learner import FusedTreeLearner
        fast = (isinstance(self.learner, FusedTreeLearner)
                and type(self) is GBDT
                and not cfg.linear_tree
                and (self.objective is None
                     or not self.objective.is_renew_tree_output))
        if fast:
            # zero-sync path: the tree stays on device; host Tree objects are
            # materialized lazily (save/predict). The "no more splittable
            # leaves" stop check is skipped to avoid a per-iteration D2H —
            # converged training just appends constant trees.
            for k in range(self.num_tree_per_iteration):
                with tel.phase("tree"):
                    rec = self.learner.train_device(grad[k], hess[k],
                                                    row_mask=mask)
                with tel.phase("score_update"):
                    # the L-value multiply stays a dispatch of its own:
                    # inside the program it could contract with the add
                    # into one rounding, and kill-and-resume rebuilds the
                    # scores from leaf values rounded on their own
                    lv = rec.leaf_value * self.shrinkage_rate
                    self.scores = _score_update(self.scores, lv,
                                                rec.row_leaf, k)
                if rec.work is not None:
                    tel.defer_counts((rec.work, rec.num_leaves),
                                     self.learner.work_counts)
                # drop the O(N) row->leaf map from the kept record: at
                # 10.5M rows x 500 trees it would pin ~21 GB of HBM that
                # materialization never reads (the work counts go with it)
                rec = rec._replace(row_leaf=None, work=None)
                lazy = _LazyTree(self.learner, rec, self.shrinkage_rate,
                                 init_scores[k])
                self.models.append(lazy)
                if self.valid_sets:
                    # the watched sets take the same ``lv`` from the same
                    # device record: nothing is materialised, nothing read
                    with tel.phase("eval"):
                        routed = RoutingTree(*(getattr(rec, f) for f
                                               in RoutingTree._fields))
                        for vi in range(len(self.valid_sets)):
                            self._route_valid(vi, routed, lv, k)
            self.iter_ += 1
            tel.end_iteration(sync=self.scores)
            self.last_iteration_skipped = self.guard.end_iteration(self)
            return False

        should_continue = False
        for k in range(self.num_tree_per_iteration):
            with tel.phase("tree"):
                tree = self.learner.train(grad[k], hess[k], row_mask=mask)
            if tree.num_leaves > 1:
                should_continue = True
                if cfg.linear_tree and type(self) is GBDT \
                        and type(self.learner) in (SerialTreeLearner,
                                                   FusedTreeLearner):
                    self._fit_linear_tree(tree, k, grad[k], hess[k])
                if self.objective is not None and self.objective.is_renew_tree_output:
                    self._renew_tree_output(tree, k, mask)
                tree.apply_shrinkage(self.shrinkage_rate)
                with tel.phase("score_update"):
                    self._update_train_score(tree, k)
                with tel.phase("eval"):
                    for vi in range(len(self.valid_sets)):
                        self._add_valid_tree_score(vi, tree, k)
                if abs(init_scores[k]) > K_EPSILON:
                    self._tree_add_bias(tree, init_scores[k], k)
            else:
                if len(self.models) < self.num_tree_per_iteration:
                    if self.objective is not None and not cfg.boost_from_average \
                            and not self.has_init_score:
                        init_scores[k] = self.objective.boost_from_score(k)
                        self.scores = self.scores.at[k].add(init_scores[k])
                        for vi in range(len(self.valid_scores)):
                            self.valid_scores[vi] = \
                                self.valid_scores[vi].at[k].add(init_scores[k])
                    tree.leaf_value[0] = init_scores[k]
            self.models.append(tree)

        if not should_continue:
            tel.end_iteration(sync=self.scores)
            if self.guard.end_iteration(self):
                # non-finite gradients made every leaf unsplittable: this is
                # a skipped iteration, not convergence — keep training
                self.last_iteration_skipped = True
                return False
            log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            if len(self.models) > self.num_tree_per_iteration:
                del self.models[-self.num_tree_per_iteration:]
            return True
        self.iter_ += 1
        tel.end_iteration(sync=self.scores)
        self.last_iteration_skipped = self.guard.end_iteration(self)
        return False

    def _guard_state_capture(self) -> dict:
        """Restore point for guard_nonfinite=skip_tree: scores are immutable
        jax arrays, so holding the old references IS the snapshot (no
        copies). DART extends this with its dropout bookkeeping."""
        return {"scores": self.scores,
                "valid_scores": list(self.valid_scores),
                "n_models": len(self.models),
                "iter": self.iter_,
                "shrinkage": self.shrinkage_rate}

    def _guard_state_restore(self, st: dict) -> None:
        self.scores = st["scores"]
        self.valid_scores[:] = st["valid_scores"]
        del self.models[st["n_models"]:]
        self.iter_ = st["iter"]
        self.shrinkage_rate = st["shrinkage"]

    def _host_leaf_index(self, tree: Tree) -> np.ndarray:
        """Per-row leaf assignment from the serial learner's partition."""
        perm = np.asarray(jax.device_get(self.learner.last_perm))
        begins = self.learner.last_leaf_begin
        counts = self.learner.last_leaf_count
        leaf_idx = np.zeros(self.num_data, dtype=np.int32)
        for leaf in range(tree.num_leaves):
            b, c = int(begins[leaf]), int(counts[leaf])
            leaf_idx[perm[b:b + c]] = leaf
        return leaf_idx

    def _linear_raw_dev(self) -> jax.Array:
        """Device copy of the linear_tree-retained raw matrix, uploaded
        once per training run (the moment accumulation reads it every
        tree)."""
        raw = self.train_set.raw
        cache = getattr(self, "_linear_raw_cache", None)
        if cache is None or cache[0] is not raw:
            self._linear_raw_cache = (raw, jnp.asarray(raw))
        return self._linear_raw_cache[1]

    def _fit_linear_tree(self, tree: Tree, k: int, grad, hess) -> None:
        """Fit linear leaf models over the raw features of the leaf paths:
        MXU-batched moment accumulation + ONE stacked solve per tree
        (models/linear_leaf.py; reference:
        LinearTreeLearner::CalculateLinear host loop replaced wholesale —
        both the serial and the fused learner land here, so their linear
        trees are bit-identical by construction)."""
        from .linear_leaf import (fit_linear_leaves_batched,
                                  numeric_feature_mask)
        ds = self.train_set
        if ds.raw is None:
            log.warning("linear_tree needs the retained raw matrix; "
                        "skipping linear fit")
            return
        numeric = numeric_feature_mask(ds)
        if getattr(self.learner, "last_row_leaf", None) is not None:
            # fused learner: the device row->leaf map IS the membership
            leaf_dev = self.learner.last_row_leaf
            # graftlint: disable=R1 — one O(N) map fetch per tree: the
            # host mirror drives the linear score update + resume replay
            # (exact f64 leaf outputs), opt-in linear_tree path
            leaf_idx = np.asarray(jax.device_get(leaf_dev))
        else:
            # graftlint: disable=R1 — serial learner: the leaf permutation
            # is the membership source; ONE transfer per tree
            perm = np.asarray(jax.device_get(self.learner.last_perm))
            begins = self.learner.last_leaf_begin
            counts = self.learner.last_leaf_count
            leaf_idx = np.zeros(self.num_data, dtype=np.int32)
            for leaf in range(tree.num_leaves):
                b, c = int(begins[leaf]), int(counts[leaf])
                leaf_idx[perm[b:b + c]] = leaf
            leaf_dev = jnp.asarray(leaf_idx)
        fit_linear_leaves_batched(tree, self._linear_raw_dev(), leaf_dev,
                                  grad, hess, self.config.linear_lambda,
                                  numeric, self.config.num_leaves)
        # cache the per-row leaf map for the score update (saves a second
        # full-permutation D2H per iteration)
        self._linear_leaf_idx = leaf_idx

    def _tree_add_bias(self, tree: Tree, bias: float, k: int) -> None:
        """Fold the boost-from-average init into the first tree
        (reference: Tree::AddBias via gbdt.cpp:421)."""
        tree.leaf_value[:tree.num_leaves] += bias
        tree.internal_value = [v + bias for v in tree.internal_value]
        if getattr(tree, "is_linear", False):
            tree.leaf_const[:tree.num_leaves] += bias

    def _tree(self, i: int) -> Tree:
        m = self.models[i]
        if isinstance(m, _LazyTree):
            m = m.materialize()
            self.models[i] = m
        return m

    def _materialize_lazy(self, idx=None) -> None:
        """Materialize every (requested) device-resident tree in ONE batched
        transfer (fused learner's materialize_batch) instead of per-tree
        round-trips — the difference between one and hundreds of D2H syncs
        when predicting from a freshly trained model."""
        want = range(len(self.models)) if idx is None else idx
        lazy = [i for i in want if isinstance(self.models[i], _LazyTree)]
        if len(lazy) <= 1:
            return
        learner = self.models[lazy[0]].learner
        if not hasattr(learner, "materialize_batch"):
            return
        same = [i for i in lazy if self.models[i].learner is learner]
        trees = learner.materialize_batch([self.models[i].rec for i in same])
        for i, t in zip(same, trees):
            m = self.models[i]
            self.models[i] = _finalize_tree(t, m.shrinkage, m.bias)

    @property
    def host_models(self) -> List[Tree]:
        self._materialize_lazy()
        return [self._tree(i) for i in range(len(self.models))]

    def _update_train_score(self, tree: Tree, k: int) -> None:
        if getattr(tree, "is_linear", False):
            from .tree import linear_leaf_outputs
            leaf_idx = (self._linear_leaf_idx
                        if getattr(self, "_linear_leaf_idx", None) is not None
                        else self._host_leaf_index(tree))
            self._linear_leaf_idx = None
            add = linear_leaf_outputs(tree, self.train_set.raw, leaf_idx)
            self.scores = self.scores.at[k].add(
                jnp.asarray(add.astype(np.float32)))
            return
        if getattr(self.learner, "last_row_leaf", None) is not None:
            # fused learner: leaf membership is row_leaf (device)
            lv = jnp.asarray(
                np.asarray(tree.leaf_value[:tree.max_leaves], np.float32))
            self.scores = self.scores.at[k].add(
                lv[self.learner.last_row_leaf])
            return
        lv = jnp.asarray(tree.leaf_value[:tree.num_leaves], dtype=jnp.float32)
        if hasattr(self.learner, "update_scores"):   # distributed learners
            self.scores = self.scores.at[k].set(
                self.learner.update_scores(self.scores[k], lv))
            return
        self.scores = self.scores.at[k].set(_add_tree_score(
            self.scores[k], self.learner.last_perm,
            jnp.asarray(self.learner.last_leaf_begin, dtype=jnp.int32),
            jnp.asarray(self.learner.last_leaf_count, dtype=jnp.int32),
            lv, tree.num_leaves))

    def _valid_counts(self) -> Dict[str, int]:
        """Host constants of the watched sets for the iteration record's
        ``counts``: sets and rows."""
        return {"valid_sets": len(self.valid_sets),
                "valid_rows": sum(ds.num_data for _, ds in self.valid_sets)}

    def _route_valid(self, vi: int, routed: RoutingTree, leaf_values,
                     k: int) -> None:
        """valid_scores[vi][k] += the tree's value of the set's rows."""
        if getattr(self, "_route_meta", None) is None:
            self._route_meta = (
                tuple(jnp.asarray(self._meta[name]) for name in
                      ("default_bins", "missing_types", "num_bins")),
                bool(self._meta["is_categorical"].any()))
        meta, has_categorical = self._route_meta
        self.valid_scores[vi] = _valid_tree_score(
            self.valid_scores[vi], self.valid_binned[vi], routed,
            leaf_values, *meta, k=k, has_categorical=has_categorical)

    def _route_host_tree(self, tree: Tree, sign: float = 1.0):
        """A host Tree as ``_valid_tree_score`` takes it: the routing fields
        and the float32 leaf values, padded to the configuration's
        ``num_leaves`` so that no tree's depth or size is a compile key."""
        routed = routing_tree_from_host(tree, self.config.num_leaves)
        lv = np.zeros(routed.node_left.shape[0] + 1, np.float32)
        n = max(tree.num_leaves, 1)
        lv[:n] = sign * np.asarray(tree.leaf_value[:n], np.float32)
        return routed, lv

    def _add_valid_tree_score(self, vi: int, tree: Tree, k: int) -> None:
        """A host Tree's values added to a watched set's scores (the
        branches that grow host trees: DART, RF, ``linear_tree``, renewing
        objectives, the host-loop learners)."""
        if getattr(tree, "is_linear", False):
            from ..ops.predict import predict_leaf_index_binned
            from .tree import linear_leaf_outputs
            vraw = self.valid_sets[vi][1].raw
            if vraw is None:
                log.warning("Valid set %r has no retained raw matrix; "
                            "linear-tree eval falls back to constant leaf "
                            "values (metrics will not match predict())",
                            self.valid_sets[vi][0])
            else:
                arrs = tree_to_arrays(tree, feature_meta=self._meta,
                                      use_inner_feature=True)
                # graftlint: disable=R1 — linear-tree valid-set eval must
                # gather raw feature rows per leaf on the host; one
                # transfer per tree per valid set, opt-in linear_tree path
                leaf_idx = np.asarray(jax.device_get(
                    predict_leaf_index_binned(
                        self.valid_binned[vi], arrs,
                        _round_depth(tree.max_depth + 1))))
                add = linear_leaf_outputs(tree, vraw, leaf_idx)
                self.valid_scores[vi] = self.valid_scores[vi].at[k].add(
                    jnp.asarray(add.astype(np.float32)))
                return
        self._route_valid(vi, *self._route_host_tree(tree), k)

    def _renew_tree_output(self, tree: Tree, k: int, mask) -> None:
        """L1-family leaf refit by weighted percentile of residuals
        (reference: RenewTreeOutput path in gbdt.cpp:412 +
        regression_objective.hpp percentiles)."""
        # graftlint: disable=R1 — the L1-family leaf refit (RenewTreeOutput)
        # is a host percentile pass over residuals by design, once per tree
        # on the opt-in renew path; score + mask ride ONE batched transfer
        score, mask_np = (None if a is None else np.asarray(a)
                          for a in jax.device_get((self.scores[k], mask)))
        if getattr(self.learner, "last_row_leaf", None) is not None:
            # fused learner: leaf membership from row_leaf
            # graftlint: disable=R1 — same renew pass: leaf membership is
            # consumed by the host percentile refit, one transfer per tree
            row_leaf = np.asarray(jax.device_get(self.learner.last_row_leaf))
            for leaf in range(tree.num_leaves):
                rows = np.nonzero(row_leaf == leaf)[0]
                if mask_np is not None:
                    rows = rows[mask_np[rows]]
                if len(rows):
                    tree.leaf_value[leaf] = self.objective.renew_tree_output(
                        rows, score)
            return
        # graftlint: disable=R1 — same renew pass, host-loop learners: the
        # leaf permutation feeds the host percentile refit, once per tree
        perm = np.asarray(jax.device_get(self.learner.last_perm))
        begins = self.learner.last_leaf_begin
        counts = self.learner.last_leaf_count
        distributed = begins.ndim == 2     # [D, L] per-shard layout
        n_loc = getattr(self.learner, "n_loc", 0)
        for leaf in range(tree.num_leaves):
            if distributed:
                parts = []
                for d in range(begins.shape[0]):
                    b, c = int(begins[d, leaf]), int(counts[d, leaf])
                    parts.append(perm[d * n_loc + b: d * n_loc + b + c] + d * n_loc)
                rows = np.concatenate(parts) if parts else np.empty(0, np.int64)
                rows = rows[rows < self.num_data]
            else:
                rows = perm[int(begins[leaf]): int(begins[leaf]) + int(counts[leaf])]
            if mask_np is not None:
                rows = rows[mask_np[rows]]
            if len(rows) == 0:
                continue
            tree.leaf_value[leaf] = self.objective.renew_tree_output(rows, score)

    # ------------------------------------------------------------------
    # continued training / refit
    # ------------------------------------------------------------------
    def resume_from(self, trees: List[Tree]) -> None:
        """Continue training from a loaded model's trees: keep the tree list
        and replay their scores onto the train/valid sets in one batched
        dispatch (reference: Boosting::CreateBoosting(type, filename) +
        GBDT::ResetTrainingData, src/boosting/boosting.cpp:34 / gbdt.cpp;
        Python engine.py:109 init_model)."""
        import copy
        from .tree import rebind_to_dataset
        K = self.num_tree_per_iteration
        if len(trees) % K != 0:
            log.fatal("init_model has %d trees, not a multiple of "
                      "num_tree_per_iteration=%d", len(trees), K)
        if self.train_set is None:
            log.fatal("resume_from needs a training dataset")
        # deep-copy: rebinding mutates bin-space (and, for missing-type
        # mismatches, raw-space) fields — the caller's trees stay pristine
        trees = [copy.deepcopy(t) for t in trees]
        for t in trees:
            rebind_to_dataset(t, self.train_set)
        self.models = list(trees)
        self.iter_ = len(trees) // K
        forest, depth = forest_to_arrays(trees, feature_meta=self._meta,
                                         use_inner_feature=True)
        tree_class = jnp.asarray([i % K for i in range(len(trees))], jnp.int32)
        if any(getattr(t, "is_linear", False) for t in trees):
            # linear trees predict leaf_const + leaf_coeff·x, not leaf_value;
            # replaying with constant leaves would silently train all later
            # gradients against wrong scores. Replay host-side on raw rows.
            # (valid sets are added AFTER resume in engine.py/cli.py; their
            # linear replay lives in add_valid_set)
            if type(self) is not GBDT:
                # DART's dropout replays dropped trees with constant leaf
                # values — resumed linear trees would corrupt scores on the
                # first drop; RF averaging has the same blind spot
                log.fatal("Continued training from a linear_tree model is "
                          "only supported with boosting=gbdt")
            if self.train_set.raw is None or any(
                    ds.raw is None for _, ds in self.valid_sets):
                log.fatal("Continued training from a linear_tree model needs "
                          "the raw feature matrix retained on every dataset "
                          "(train a linear_tree Dataset or disable "
                          "init_model)")
            self.scores = self._replay_linear_forest(
                trees, forest, depth, jnp.asarray(self.train_set.binned),
                self.train_set.raw, self.scores)
            for vi, (_, vds) in enumerate(self.valid_sets):
                self.valid_scores[vi] = self._replay_linear_forest(
                    trees, forest, depth, self.valid_binned[vi], vds.raw,
                    self.valid_scores[vi])
            return
        self.scores = self.scores + dispatch_forest_predict(
            self.config, jnp.asarray(self.train_set.binned), forest,
            tree_class, K, depth, binned=True)
        for vi in range(len(self.valid_sets)):
            self.valid_scores[vi] = self.valid_scores[vi] + \
                dispatch_forest_predict(self.config, self.valid_binned[vi],
                                        forest, tree_class, K, depth,
                                        binned=True)

    def refit(self, data: np.ndarray, label: np.ndarray, weight=None,
              group=None, decay_rate: Optional[float] = None) -> None:
        """Refit the leaf values of the existing trees on new data, keeping
        the tree structures (reference: GBDT::RefitTree in gbdt.cpp +
        SerialTreeLearner::FitByExistingTree; CLI task=refit,
        application.cpp:254-290). New leaf outputs are the regularized
        Newton step over the rows landing in each leaf
        (feature_histogram.hpp:198 CalculateSplittedLeafOutput), blended by
        ``refit_decay_rate``."""
        from ..data.dataset import Metadata
        self.invalidate_predict_cache()     # leaf values change in place
        cfg = self.config
        decay = cfg.refit_decay_rate if decay_rate is None else float(decay_rate)
        X = np.ascontiguousarray(np.asarray(data, dtype=np.float32))
        N = X.shape[0]
        K = self.num_tree_per_iteration
        trees = self.host_models
        if not trees:
            log.fatal("refit needs a trained model")
        if any(getattr(t, "is_linear", False) for t in trees):
            # refit rewrites leaf_value only; predict() would keep preferring
            # the stale linear payload. Drop it so the refitted constant
            # leaves actually drive predictions.
            log.warning("refit drops linear-leaf models; the refitted trees "
                        "predict with constant leaf values")
            for t in trees:
                t.is_linear = False
        md = Metadata()
        md.label = np.asarray(label, dtype=np.float32).reshape(-1)
        if weight is not None:
            md.weight = np.asarray(weight, dtype=np.float32).reshape(-1)
        md.set_group(group)
        md.check(N)
        obj = create_objective(cfg)
        if obj is None:
            log.fatal("refit requires a built-in objective")
        obj.init(md, N)

        forest, depth = forest_to_arrays(trees, use_inner_feature=False)
        leaf_of = np.asarray(jax.device_get(dispatch_forest_leaf(
            self.config, jnp.asarray(X), forest, depth,
            binned=False)))   # [T, N]

        l1, l2 = cfg.lambda_l1, cfg.lambda_l2
        mds = cfg.max_delta_step

        def newton_out(sg, sh):
            num = (-np.sign(sg) * np.maximum(np.abs(sg) - l1, 0.0)
                   if l1 > 0 else -sg)
            out = num / (sh + l2 + K_EPSILON)
            if mds > 0:
                out = np.clip(out, -mds, mds)
            return out

        scores = jnp.zeros((K, N), dtype=jnp.float32)
        for it in range(len(trees) // K):
            grad, hess = obj.get_gradients(scores)
            g = np.asarray(jax.device_get(grad))
            h = np.asarray(jax.device_get(hess))
            for k in range(K):
                ti = it * K + k
                t = trees[ti]
                L = t.num_leaves
                lf = leaf_of[ti]
                sg = np.bincount(lf, weights=g[k], minlength=L)[:L]
                sh = np.bincount(lf, weights=h[k], minlength=L)[:L]
                new_out = newton_out(sg, sh) * t.shrinkage
                old = t.leaf_value[:L].copy()
                t.leaf_value[:L] = decay * old + (1.0 - decay) * new_out
                scores = scores.at[k].add(
                    jnp.asarray(t.leaf_value[lf].astype(np.float32)))

    # ------------------------------------------------------------------
    # evaluation
    # ------------------------------------------------------------------
    def _converted_scores(self, raw: jax.Array) -> np.ndarray:
        out = self.objective.convert_output(raw) if self.objective else raw
        out = np.asarray(jax.device_get(out)).astype(np.float64)
        return out[0] if self.num_tree_per_iteration == 1 else out

    def eval_train(self) -> List[Tuple[str, str, float, bool]]:
        return self._eval_sets([("training", self.train_metrics,
                                 self.scores)])

    def eval_valid(self) -> List[Tuple[str, str, float, bool]]:
        return self._eval_sets([
            (name, self.valid_metrics[vi], self.valid_scores[vi])
            for vi, (name, _) in enumerate(self.valid_sets)])

    def _eval_sets(self, sets) -> List[Tuple[str, str, float, bool]]:
        """Every metric of every ``(name, metrics, raw device scores)``. A
        metric with a device form (``Metric.eval_device``) is dispatched
        where the scores live, and all such values of the call come back in
        ONE read of 4 bytes each; a set is read back whole only for a
        metric without one. The bytes read go on the iteration record as
        ``counts.eval_d2h_bytes``."""
        slots, pending, d2h = [], [], 0
        for name, metrics, raw in sets:
            if not metrics:
                continue
            host = None
            on_device = (self.objective.convert_output(raw)
                         if self.objective else raw)
            for m in metrics:
                dev = m.eval_device(on_device)
                if dev is not None:
                    names, values = dev
                    pending.append(values)
                    slots.append((name, m, names, len(pending) - 1))
                    continue
                if host is None:
                    host = self._converted_scores(raw)
                    d2h += raw.size * raw.dtype.itemsize
                slots.append((name, m, m.eval(host), None))
        if not slots:
            return []
        # the evaluation's one read: the metric values, 4 bytes each
        values = jax.device_get(pending)
        d2h += sum(v.nbytes for v in values)
        self.telemetry.add_counts({"eval_d2h_bytes": d2h})
        res = []
        for name, m, got, at in slots:
            pairs = got if at is None else zip(got, values[at].tolist())
            for mname, val in pairs:
                res.append((name, mname, float(val), m.greater_is_better))
        return res

    # ------------------------------------------------------------------
    # prediction
    # ------------------------------------------------------------------
    def _model_slice(self, start_iteration: int, num_iteration: int):
        K = self.num_tree_per_iteration
        end = len(self.models) if num_iteration < 0 else min(
            len(self.models), (start_iteration + num_iteration) * K)
        return list(range(start_iteration * K, end))

    def _check_predict_shape(self, data: np.ndarray) -> np.ndarray:
        """A matrix with fewer columns than the model's max split feature
        would silently mis-gather (clipped indices); fail loudly unless
        predict_disable_shape_check pads the missing columns with NaN
        (reference: c_api predict shape check + the override flag,
        include/LightGBM/config.h predict_disable_shape_check)."""
        key = len(self.models)
        cached = getattr(self, "_need_feats", None)
        if cached is None or cached[0] != key:
            need = 1 + max(
                (max(t.split_feature[:t.num_internal], default=0)
                 for t in (self._tree(i) for i in range(key))),
                default=0) if self.models else 0
            self._need_feats = (key, need)
        need = self._need_feats[1]
        if data.ndim != 2:
            log.fatal("predict expects a 2-D matrix, got shape %s",
                      (data.shape,))
        if data.shape[1] >= need:
            return data
        if not self.config.predict_disable_shape_check:
            log.fatal("The number of features in data (%d) is less than the "
                      "model needs (%d); set predict_disable_shape_check="
                      "true to pad missing features with NaN",
                      data.shape[1], need)
        pad = np.full((data.shape[0], need - data.shape[1]), np.nan,
                      dtype=data.dtype)
        return np.concatenate([data, pad], axis=1)

    def invalidate_predict_cache(self) -> None:
        """Drop every cached predict-side view of the forest and bump the
        model generation. Must be called by anything that mutates tree
        payloads in place (refit, set_leaf_output, shuffle_models);
        structural changes (train/rollback/resume) are covered by the
        model-count component of the cache keys."""
        self._fast_cache = None
        self._forest_cache = None
        self._compiled_cache = None
        self._pstream_cache = None
        self.generation += 1

    def _device_forest(self, idx, trees):
        """Device-resident stacked forest (+ pre-sliced tree blocks) for the
        raw-feature predict paths, cached on the booster: the forest is
        immutable between calls, so re-slicing and re-uploading it per
        predict call (ADVICE round 5, predict.py:313) was pure waste.
        Returns (forest, depth, tree_class, blocks)."""
        cfg = self.config
        key = (self.generation, len(self.models), idx[0], idx[-1], len(idx),
               cfg.predict_engine, cfg.predict_tree_tile)
        cache = getattr(self, "_forest_cache", None)
        if cache is None or cache[0] != key:
            K = self.num_tree_per_iteration
            forest, depth = forest_to_arrays(trees, use_inner_feature=False)
            tree_class = jnp.asarray([i % K for i in idx], jnp.int32)
            if cfg.predict_engine in ("tensor", "compiled"):
                blocks = build_tree_tiles(forest, tree_class,
                                          cfg.predict_tree_tile)
            else:
                blocks = build_forest_blocks(forest, tree_class)
            self._forest_cache = (key, (forest, depth, tree_class, blocks))
        return self._forest_cache[1]

    def _compiled_forest(self, start_iteration: int, num_iteration: int,
                         es_freq: int = 0):
        """Cached compiled-forest view (lambdagap_tpu.infer) for the raw
        serving path: the forest is lowered ONCE — pruned, merged,
        palette-quantized, blocked — and the CompiledForest holds the
        device-resident buffers across predict calls, like _device_forest
        does for the training-shaped tables."""
        cfg = self.config
        key = (self.generation, len(self.models), start_iteration,
               num_iteration, es_freq,
               float(cfg.pred_early_stop_margin), cfg.infer_quant,
               cfg.infer_prune, cfg.infer_merge_trees,
               cfg.infer_node_block_kb)
        cache = getattr(self, "_compiled_cache", None)
        if cache is None or cache[0] != key:
            from ..infer import CompiledForest, compile_forest
            artifact = compile_forest(self, start_iteration, num_iteration)
            self._compiled_cache = (key, CompiledForest(
                artifact, early_stop_freq=es_freq,
                early_stop_margin=float(cfg.pred_early_stop_margin)))
        return self._compiled_cache[1]

    def _fast_forest(self, idx, trees):
        """Cached flat forest for the native low-latency predictor; None
        when the native lib is unavailable."""
        from ..native import FastForest, get_lib
        if get_lib() is None:
            return None
        key = (len(self.models), idx[0], idx[-1], len(idx))
        cache = getattr(self, "_fast_cache", None)
        if cache is None or cache[0] != key:
            K = self.num_tree_per_iteration
            self._fast_cache = (key, FastForest(trees, [i % K for i in idx],
                                                K))
        return self._fast_cache[1]

    def predict_raw(self, data: np.ndarray, start_iteration: int = 0,
                    num_iteration: int = -1) -> np.ndarray:
        """Raw scores for new data [N, D] -> [N] or [N, K].

        The whole forest runs in one jitted dispatch (stacked TreeArrays +
        scan; the analog of GBDT::Predict over inlined trees, reference:
        include/LightGBM/tree.h:130-141)."""
        data = np.asarray(data, dtype=np.float32)
        data = self._check_predict_shape(data)
        K = self.num_tree_per_iteration
        N = data.shape[0]
        idx = self._model_slice(start_iteration, num_iteration)
        if not idx:
            res = np.zeros((K, N), dtype=np.float32)
            return res[0] if K == 1 else res.T
        self._materialize_lazy(idx)
        trees = [self._tree(i) for i in idx]
        # margin-based prediction early stop, classification only
        # (reference: src/boosting/prediction_early_stop.cpp)
        # freq counts boosting iterations; trees are iter-major, so the
        # per-tree check interval is freq*K (keeps checks on iteration
        # boundaries — all classes equally updated)
        es_freq = (self.config.pred_early_stop_freq * K
                   if self.config.pred_early_stop and self.objective is not None
                   and self.objective.name in ("binary", "multiclass",
                                               "multiclassova") else 0)
        has_linear = any(getattr(t, "is_linear", False) for t in trees)
        if (N <= max(int(self.config.tpu_fast_predict_rows), 512)
                and not has_linear and es_freq == 0):
            # serving-shaped call: threaded native host traversal, no jit
            # dispatch (reference: src/c_api.cpp:63 SingleRowPredictorInner
            # + the OpenMP row loop of Predictor). The threshold is a
            # config knob: on a healthy chip the device forest wins earlier
            # than on a throttled one (bench measures both sides)
            # (reference: src/c_api.cpp:63)
            ff = self._fast_forest(idx, trees)
            if ff is not None and data.shape[1] > ff.max_feat:
                res = ff.predict(data).astype(np.float32).T      # [K, N]
                if self.average_output:
                    res = res / max(1, len(idx) // max(K, 1))
                return res[0] if K == 1 else res.T
        if self.config.predict_engine == "compiled":
            # serving-shaped path: the infer compiler lowers the forest
            # once (pruned/merged/quantized node blocks); traversal +
            # forest-order accumulation stay bit-identical to the engines
            # below, so averaging/conversion here is shared unchanged
            cf = self._compiled_forest(start_iteration, num_iteration,
                                       es_freq)
            res = np.asarray(jax.device_get(
                cf.predict(jnp.asarray(data))))
            if self.average_output:
                res = res / max(1, len(idx) // max(K, 1))
            return res[0] if K == 1 else res.T
        forest, depth, tree_class, blocks = self._device_forest(idx, trees)
        # linear forests ride the SAME device dispatch: the traversal carry
        # accumulates each leaf's dot product from the padded coefficient
        # tables stacked into the forest arrays (ops/linear.py), so serve's
        # compiled buckets and this path stay bit-identical
        out = dispatch_forest_predict(
            self.config, jnp.asarray(data), forest, tree_class, K,
            depth, binned=False, early_stop_freq=es_freq,
            early_stop_margin=float(self.config.pred_early_stop_margin),
            blocks=blocks, has_linear=has_linear)
        res = np.asarray(jax.device_get(out))
        if self.average_output:
            n_iters = max(1, len(idx) // max(K, 1))
            res = res / n_iters
        return res[0] if K == 1 else res.T

    def predict_leaf(self, data: np.ndarray, start_iteration: int = 0,
                     num_iteration: int = -1) -> np.ndarray:
        """Leaf index per (row, tree) (reference: predict_leaf_index path)."""
        data = np.asarray(data, dtype=np.float32)
        data = self._check_predict_shape(data)
        idx = self._model_slice(start_iteration, num_iteration)
        if not idx:
            return np.zeros((data.shape[0], 0), np.int32)
        self._materialize_lazy(idx)
        trees = [self._tree(i) for i in idx]
        forest, depth, _, blocks = self._device_forest(idx, trees)
        ys = dispatch_forest_leaf(self.config, jnp.asarray(data), forest,
                                  depth, binned=False, blocks=blocks)
        return np.asarray(jax.device_get(ys)).astype(np.int32).T

    def predict_contrib(self, data: np.ndarray, start_iteration: int = 0,
                        num_iteration: int = -1) -> np.ndarray:
        """SHAP feature contributions: [N, F+1] per class, last column the
        expected value, rows summing to the raw prediction (reference:
        Tree::PredictContrib / TreeSHAP, src/io/tree.cpp; native kernel in
        native/treeshap.cpp)."""
        from .shap import tree_shap_accumulate, tree_shap_linear
        data = np.asarray(data, dtype=np.float64)
        data = np.ascontiguousarray(self._check_predict_shape(data))
        N, F_data = data.shape
        K = self.num_tree_per_iteration
        idx = self._model_slice(start_iteration, num_iteration)
        self._materialize_lazy(idx)
        trees = [self._tree(i) for i in idx]
        max_f = max((f for t in trees
                     for f in t.split_feature[:t.num_internal]), default=-1)
        if max_f >= F_data:
            log.fatal("pred_contrib input has %d features but the model "
                      "splits on feature %d", F_data, max_f)
        phi = np.zeros((K, N, F_data + 1), dtype=np.float64)
        for pos, i in enumerate(idx):
            t = trees[pos]
            if getattr(t, "is_linear", False):
                # coefficient-attribution split (arXiv:1802.05640): the
                # structural TreeSHAP runs over leaf CONSTANTS, the
                # linear terms attribute directly to their features —
                # rows still sum to the raw prediction (models/shap.py)
                tree_shap_linear(t, data, phi[i % K])
            else:
                tree_shap_accumulate(t, data, phi[i % K])
        if self.average_output:
            phi /= max(1, len(idx) // max(K, 1))
        if K == 1:
            return phi[0]
        return phi.transpose(1, 0, 2).reshape(N, K * (F_data + 1))

    def predict(self, data: np.ndarray, raw_score: bool = False,
                start_iteration: int = 0, num_iteration: int = -1) -> np.ndarray:
        raw = self.predict_raw(data, start_iteration, num_iteration)
        if raw_score or self.objective is None:
            return raw
        stacked = raw.T if raw.ndim == 2 else raw[None, :]
        if raw.shape[0] <= 512:
            # serving-size batch: transform on host, no device dispatch
            conv = np.asarray(self.objective.convert_output_np(
                np.asarray(stacked)))
        else:
            conv = np.asarray(jax.device_get(
                self.objective.convert_output(jnp.asarray(stacked))))
        return conv[0] if self.num_tree_per_iteration == 1 else conv.T

    def predict_stream(self, data, start_iteration: int = 0,
                       num_iteration: int = -1, raw_score: bool = False,
                       pred_contrib: bool = False, window_rows: int = 0,
                       out: Optional[np.ndarray] = None,
                       signal_source=None, throttle=None,
                       stats_out: Optional[dict] = None) -> np.ndarray:
        """Warehouse-scale out-of-core batch scoring (infer/stream.py):
        pumps host/memmap/file/ShardedBinnedDataset row windows through
        the double-buffered H2D ring into the configured predict engine
        and streams scores back through the D2H score ring — bit-identical
        to :meth:`predict_raw` (``raw_score=True``) / :meth:`predict` on
        every engine, window split and mesh grid. ``out`` (e.g. an
        ``np.memmap``) receives rows in place; ``signal_source`` (a
        SignalPlane) arms the co-tenant throttle; ``stats_out`` receives
        the run report (windows, phase totals, throttle snapshot)."""
        from ..infer.stream import predict_stream as _predict_stream
        return _predict_stream(
            self, data, start_iteration=start_iteration,
            num_iteration=num_iteration, raw_score=raw_score,
            pred_contrib=pred_contrib, window_rows=window_rows, out=out,
            signal_source=signal_source, throttle=throttle,
            stats_out=stats_out)

    # ------------------------------------------------------------------
    # serialization
    # ------------------------------------------------------------------
    @property
    def feature_names(self) -> List[str]:
        if self.train_set is not None:
            return self.train_set.feature_names
        return getattr(self, "_feature_names",
                       [f"Column_{i}" for i in range(self.max_feature_idx + 1)])

    def objective_string(self) -> str:
        if self.objective is None:
            return getattr(self, "_objective_string", "custom")
        name = self.objective.name
        if name == "binary":
            return f"binary sigmoid:{self.config.sigmoid:g}"
        if name == "multiclass":
            return f"multiclass num_class:{self.num_class}"
        if name == "multiclassova":
            return (f"multiclassova num_class:{self.num_class} "
                    f"sigmoid:{self.config.sigmoid:g}")
        if name == "lambdarank":
            return "lambdarank"
        if name == "regression" and getattr(self.objective, "sqrt", False):
            return "regression sqrt"
        return name

    def feature_infos(self) -> List[str]:
        """Per-feature value ranges (reference: Dataset feature_infos /
        bin.h:224 bin_info_string)."""
        if self.train_set is None:
            return getattr(self, "_feature_infos", [])
        out = []
        for m in self.train_set.mappers:
            if m.is_trivial:
                out.append("none")
            elif m.bin_type == "categorical":
                cats = [str(c) for c in m.bin_2_categorical[1:]]
                out.append(":".join(cats) if cats else "none")
            else:
                out.append(f"[{m.min_val:g}:{m.max_val:g}]")
        return out

    def save_model_to_string(self, start_iteration: int = 0,
                             num_iteration: int = -1,
                             importance_type: int = 0) -> str:
        from .model_text import save_model_to_string
        return save_model_to_string(self, start_iteration, num_iteration,
                                    importance_type)

    def save_model(self, filename: str, start_iteration: int = 0,
                   num_iteration: int = -1, importance_type: int = 0) -> None:
        # atomic (tmp + fsync + rename): a crash mid-save must never leave a
        # torn model file that a later load or auto-resume trusts
        from ..guard.snapshot import atomic_write_text
        atomic_write_text(filename,
                          self.save_model_to_string(start_iteration,
                                                    num_iteration,
                                                    importance_type))

    @classmethod
    def from_model_string(cls, text: str, config: Optional[Config] = None):
        """Load a saved model for prediction / continued training
        (reference: GBDT::LoadModelFromString, gbdt_model_text.cpp)."""
        from .model_text import load_model_from_string
        header, trees = load_model_from_string(text)
        cfg = config or Config()
        obj_str = header.get("objective", "regression").split(" ")[0]
        params = {"objective": obj_str} if obj_str != "custom" else {}
        for tok in header.get("objective", "").split(" ")[1:]:
            if ":" in tok:
                k, v = tok.split(":", 1)
                params[k] = v
            elif tok == "sqrt":
                params["reg_sqrt"] = True
        if "num_class" in header:
            params["num_class"] = int(header["num_class"])
        cfg.update(params)
        booster = cls(cfg, None)
        booster.models = trees
        booster.iter_ = len(trees) // booster.num_tree_per_iteration
        booster.max_feature_idx = int(header.get("max_feature_idx", 0))
        if header.get("average_output"):
            booster.average_output = True
        booster._feature_names = header.get("feature_names", "").split()
        booster._feature_infos = header.get("feature_infos", "").split()
        booster._objective_string = header.get("objective", "custom")
        return booster

    @classmethod
    def from_model_file(cls, filename: str, config: Optional[Config] = None):
        with open(filename) as f:
            return cls.from_model_string(f.read(), config)

    # ------------------------------------------------------------------
    @property
    def num_iterations_trained(self) -> int:
        return self.iter_

    def rollback_one_iter(self) -> None:
        """(reference: GBDT::RollbackOneIter, gbdt.cpp:456) — drop the last
        iteration's trees and subtract their score contributions."""
        if self.iter_ <= 0:
            return
        for k in range(self.num_tree_per_iteration):
            tree = self._tree(len(self.models) - self.num_tree_per_iteration + k)
            if getattr(tree, "is_linear", False):
                # subtracting constant leaf values would silently corrupt
                # the scores a linear tree updated with its dot products
                log.fatal("rollback_one_iter is not supported for "
                          "linear_tree models")
            # subtract contribution by re-adding with negated leaf values
            arrs = tree_to_arrays(tree, feature_meta=self._meta,
                                  use_inner_feature=True)
            arrs = arrs._replace(leaf_value=-arrs.leaf_value)
            depth = _round_depth(tree.max_depth + 1)
            self.scores = self.scores.at[k].add(
                predict_tree_binned(self.learner.x_binned, arrs, depth))
            for vi in range(len(self.valid_sets)):
                self._route_valid(vi, *self._route_host_tree(tree, -1.0), k)
        del self.models[-self.num_tree_per_iteration:]
        self.iter_ -= 1


# graftir IR contract
from ..analysis.ir.contracts import register_program

register_program(
    "gbdt._add_tree_score", collective_free=True,
    notes="score accumulation after each tree; device-resident add")
register_program(
    "gbdt._valid_tree_score", collective_free=True,
    notes="a watched set's rows routed through one tree from its device "
          "record: two matrix products a row block, no property of the "
          "tree in the compile key")
register_program(
    "gbdt._score_update", collective_free=True,
    notes="zero-sync path's score update: leaf values gathered by the "
          "fused program's row->leaf map")
