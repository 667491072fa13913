"""Device-resident compiled forest with padding-bucket executables.

The one-shot predict path converts the forest to device arrays on every
call; serving amortizes that to zero: ``CompiledForestCache`` stacks the
booster's trees into :class:`~lambdagap_tpu.ops.predict.TreeArrays` blocks
ONCE (they stay resident in HBM), and routes every request batch through a
small set of fixed padding buckets (default 1/8/64/512/4096 rows) so
arbitrary request sizes always hit an already-compiled XLA executable —
the serving analog of the reference's ``SingleRowPredictorInner`` keeping
one predictor object warm per booster (reference: src/c_api.cpp:63), but
for whole padded device batches.

Caches are keyed by ``(model_generation, start_iteration, num_iteration)``;
any in-place mutation of the booster bumps its generation
(``GBDT.invalidate_predict_cache``), so a stale compiled forest can never
be served.

Numerics: a bucket dispatch runs the exact device ops of the one-shot
``GBDT.predict_raw`` device branch (same stacked blocks, same scan, same
elementwise transform), and rows are independent under ``vmap``, so padded
batches return bit-identical outputs to a direct ``Booster.predict`` that
takes the device path.
"""
from __future__ import annotations

import threading
import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.predict import (TREE_BLOCK, build_forest_blocks, forest_to_arrays,
                           predict_forest)
from ..ops.predict_tensor import build_tree_tiles, predict_forest_tensor
from ..utils import log

# powers chosen so the jump between buckets wastes at most ~8x padding on
# pathological sizes while keeping the compiled-executable set tiny
DEFAULT_BUCKETS = (1, 8, 64, 512, 4096)


def _plan(buckets, n: int):
    """Greedy (rows, bucket) decomposition shared by the per-model cache
    and the cross-model pack: full buckets dispatch unpadded, a padded
    dispatch is only taken when its bucket is at most 2x the remaining
    rows (or nothing smaller fits)."""
    out = []
    rem = n
    while rem > 0:
        b_pad = next((b for b in buckets if b >= rem), None)
        b_full = next((b for b in reversed(buckets) if b <= rem), None)
        if b_pad is not None and (b_full is None or b_pad <= 2 * rem):
            out.append((rem, b_pad))
            rem = 0
        else:
            out.append((b_full, b_full))
            rem -= b_full
    return out


class CompiledForestCache:
    """One booster generation, compiled for serving.

    Parameters
    ----------
    gbdt: models.gbdt.GBDT — the loaded booster.
    buckets: padded batch sizes to pre-compile (sorted, deduped).
    start_iteration / num_iteration: forest slice, as in ``predict``.
    generation: serving generation id stamped on every response.
    stats: optional ``ServeStats`` for cache accounting.
    artifact_store: optional ``infer.ArtifactStore`` — under
        ``predict_engine=compiled`` the build consults it by source key
        before paying a local forest compile (a fleet peer may have
        shipped the artifact already) and publishes local compiles into
        it; admissions vs local compiles are counted in ``ServeStats``.
    """

    def __init__(self, gbdt, buckets: Optional[Sequence[int]] = None,
                 start_iteration: int = 0, num_iteration: int = -1,
                 generation: int = 0, stats=None,
                 tree_block: int = TREE_BLOCK,
                 artifact_store=None) -> None:
        self.gbdt = gbdt
        self.generation = int(generation)
        self.start_iteration = int(start_iteration)
        self.num_iteration = int(num_iteration)
        self.stats = stats
        bl = tuple(sorted({int(b) for b in (buckets or DEFAULT_BUCKETS)
                           if int(b) > 0}))
        if not bl:
            raise ValueError("serve needs at least one positive bucket size")
        self.buckets = bl
        self.key = (getattr(gbdt, "generation", 0),
                    self.start_iteration, self.num_iteration)

        idx = gbdt._model_slice(start_iteration, num_iteration)
        gbdt._materialize_lazy(idx)
        trees = [gbdt._tree(i) for i in idx]
        # linear forests compile like constant ones: the padded per-leaf
        # coefficient tables ride the stacked TreeArrays and the traversal
        # carry accumulates each leaf's dot product on device
        # (docs/linear-trees.md), so every bucket/registry/router/frontend
        # path serves linear models bit-identically to device predict
        self.has_linear = any(getattr(t, "is_linear", False) for t in trees)
        self.idx = idx
        self.num_class = gbdt.num_tree_per_iteration
        # matrix width the compiled executables expect: 1 + max split
        # feature. Wider request rows are truncated (trailing columns can
        # never be gathered by any node), narrower ones are padded by the
        # server under predict_disable_shape_check.
        self.width = max(1, 1 + max(
            (max(t.split_feature[:t.num_internal], default=0)
             for t in trees), default=0)) if trees else 1
        self._tree_block = tree_block
        # traversal engine: the tensorized [rows x trees] engine is the
        # serving default (predict_engine=tensor); the sequential scan
        # stays selectable for differential testing. Both are bit-identical
        # (ops/predict_tensor.py contract), so the serve-vs-predict parity
        # guarantee above holds under either engine.
        self.engine = gbdt.config.predict_engine
        self._tree_tile = int(gbdt.config.predict_tree_tile)
        if idx and self.engine != "compiled":
            forest, depth = forest_to_arrays(trees, use_inner_feature=False)
            tree_class = jnp.asarray([i % self.num_class for i in idx],
                                     jnp.int32)
            self._forest = jax.device_put(forest)
            self._depth = depth
            self._tree_class = tree_class
            if self.engine == "tensor":
                self._blocks = build_tree_tiles(self._forest, tree_class,
                                                self._tree_tile)
            else:
                self._blocks = build_forest_blocks(self._forest, tree_class,
                                                   tree_block)
        else:
            self._forest = None
            self._depth = 8
            self._tree_class = jnp.zeros(0, jnp.int32)
            self._blocks = None
        cfg = gbdt.config
        obj = gbdt.objective
        # margin-based prediction early stop, same gating as predict_raw
        self._es_freq = (cfg.pred_early_stop_freq * self.num_class
                         if cfg.pred_early_stop and obj is not None
                         and obj.name in ("binary", "multiclass",
                                          "multiclassova") else 0)
        self._es_margin = float(cfg.pred_early_stop_margin)
        self._n_iters = max(1, len(idx) // max(self.num_class, 1))
        # compiled engine: serve the infer/ artifact instead of the
        # training-shaped tables. The artifact is content-addressed, so a
        # replica whose store already holds this model's compile (shipped
        # over the wire by a peer) skips the lowering entirely — that
        # admission-vs-local split is the compile_shared_total metric.
        self.artifact = None
        self.artifact_hash = None
        self._compiled = None
        if idx and self.engine == "compiled":
            from ..infer import CompiledForest, compile_forest, source_key_of
            art = None
            if artifact_store is not None:
                art = artifact_store.get(
                    source_key_of(gbdt, start_iteration, num_iteration))
            if art is not None:
                if stats is not None:
                    stats.record_compile_shared()
            else:
                art = compile_forest(gbdt, start_iteration, num_iteration)
                if artifact_store is not None:
                    artifact_store.put(art)
                if stats is not None:
                    stats.record_compile_local()
            self.artifact = art
            self.artifact_hash = art.hash
            self._compiled = CompiledForest(
                art, early_stop_freq=self._es_freq,
                early_stop_margin=self._es_margin)
        self._warm: set = set()
        self._warm_lock = threading.Lock()
        self.build_time_s = 0.0
        if stats is not None:
            stats.record_forest_build()

    @property
    def hbm_bytes(self) -> int:
        """Resident device bytes of this compiled forest: the stacked node
        tables plus the engine's tile/block slices. The registry charges
        this against ``serve_hbm_budget_mb`` for LRU eviction; executable
        code size is not counted (XLA does not expose it), so the budget
        governs the dominant term — the forest arrays themselves."""
        total = 0
        for obj in (self._forest, self._blocks, self._tree_class):
            for leaf in jax.tree_util.tree_leaves(obj):
                total += getattr(leaf, "nbytes", 0)
        if self._compiled is not None:
            total += self._compiled.nbytes
        return int(total)

    # ------------------------------------------------------------------
    def bucket_of(self, n: int) -> int:
        """Smallest pre-compiled bucket holding ``n`` rows (requests larger
        than the top bucket are chunked by the caller)."""
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def plan(self, n: int):
        """Greedy decomposition of ``n`` rows into (rows, bucket) dispatches.

        Full buckets dispatch unpadded; a padded dispatch is only taken
        when its bucket is at most 2x the remaining rows (or nothing
        smaller fits), so padding waste per batch stays under 2x instead
        of the up-to-8x a naive round-up to the next bucket can cost
        between sparse bucket sizes."""
        return _plan(self.buckets, n)

    def _dispatch(self, xb: np.ndarray, raw_score: bool) -> jax.Array:
        """One padded bucket through the compiled forest: [num_class, B]."""
        if self._compiled is not None:
            out = self._compiled.predict(jnp.asarray(xb))
        elif self.engine == "tensor":
            out = predict_forest_tensor(
                jnp.asarray(xb), self._forest, self._tree_class,
                self.num_class, self._depth, binned=False,
                early_stop_freq=self._es_freq,
                early_stop_margin=self._es_margin,
                tree_tile=self._tree_tile, tiles=self._blocks,
                has_linear=self.has_linear)
        else:
            out = predict_forest(
                jnp.asarray(xb), self._forest, self._tree_class,
                self.num_class, self._depth, binned=False,
                early_stop_freq=self._es_freq,
                early_stop_margin=self._es_margin,
                tree_block=self._tree_block, blocks=self._blocks,
                has_linear=self.has_linear)
        if self.gbdt.average_output:
            out = out / self._n_iters
        obj = self.gbdt.objective
        if not raw_score and obj is not None:
            out = obj.convert_output(out)
        return out

    def predict(self, X: np.ndarray, raw_score: bool = False,
                record: bool = True) -> np.ndarray:
        """Predict [N, width] float32 rows; returns [N] (one class) or
        [N, K], matching ``Booster.predict`` semantics bit-for-bit on the
        device path. N is chunked by the largest bucket, each chunk padded
        up to its bucket with zero rows that are sliced off after."""
        X = np.ascontiguousarray(np.asarray(X, dtype=np.float32))
        if X.ndim != 2:
            raise ValueError(f"serve predict expects 2-D rows, got {X.shape}")
        N = X.shape[0]
        K = self.num_class
        if (self._forest is None and self._compiled is None) or N == 0:
            res = np.zeros((K, N), dtype=np.float32)
            return res[0] if K == 1 else res.T
        parts = []
        lo = 0
        for n, b in self.plan(N):
            chunk = X[lo:lo + n]
            lo += n
            if n < b:
                chunk = np.concatenate(
                    [chunk, np.zeros((b - n, X.shape[1]), np.float32)])
            with self._warm_lock:        # parallel batch workers share this
                hit = b in self._warm
                if not hit:
                    self._warm.add(b)
            if record and self.stats is not None:
                self.stats.record_cache(hit, bucket=b)
            if not hit and self.stats is not None:
                self.stats.record_bucket_compile(b)
            out = self._dispatch(chunk, raw_score)
            # graftlint: disable=R1 — the terminal D2H of the response is
            # inherent to serving: results must reach the client as numpy
            parts.append(np.asarray(jax.device_get(out))[:, :n])
        res = parts[0] if len(parts) == 1 else np.concatenate(parts, axis=1)
        return res[0] if K == 1 else res.T

    def warm(self) -> float:
        """Compile + execute every bucket once on zero rows so the first
        real request of any size hits a warm executable. Returns the time
        spent (also kept as ``build_time_s``); warm dispatches do not count
        toward hit/miss stats."""
        t0 = time.perf_counter()
        for b in self.buckets:
            self.predict(np.zeros((b, self.width), np.float32), record=False)
        self.build_time_s = time.perf_counter() - t0
        log.info("serve: warmed %d padding buckets %s in %.2fs "
                 "(generation %d, %d trees, %s engine)", len(self.buckets),
                 list(self.buckets), self.build_time_s, self.generation,
                 len(self.idx), self.engine)
        return self.build_time_s


class ModelPack:
    """Padding buckets extended ACROSS models (serve_pack_models).

    The per-model cache pads a request batch up to a bucket so it hits a
    warm executable; at millions-of-tenants scale the dispatch COUNT is
    the bottleneck — a mixed FairQueue batch touching M tiny per-tenant
    models still costs M dispatches. A ModelPack fuses the resident
    compiled models into ONE :class:`infer.engine.PackedForests`
    executable: the mixed batch concatenates into one padded bucket with a
    per-row model id, the O(trees) traversal + accumulation dispatches
    ONCE, and only the per-model averaging/objective conversion (cheap
    elementwise on the [K, n_i] score slices) runs per member afterwards.

    Bit-identity: each row's raw scores out of the packed dispatch are
    value-identical to its member cache serving the row alone (masked
    foreign trees contribute exact ``+0.0``; see PackedForests), and the
    averaging/conversion here reuses the member's own ``_dispatch`` tail
    ops — ``tests/test_infer.py`` asserts equality across the pack.

    Members must be compiled-engine caches without prediction early stop;
    the registry rebuilds packs whenever membership or any member's
    generation changes (the pack key is the (name, generation) tuple set).
    """

    def __init__(self, members, buckets: Optional[Sequence[int]] = None,
                 stats=None) -> None:
        from ..infer import PackedForests
        if not members:
            raise ValueError("ModelPack needs at least one member cache")
        for name, c in members.items():
            if c._compiled is None:
                raise ValueError(
                    f"model {name!r} has no compiled forest (pack members "
                    "need predict_engine=compiled and a nonempty tree slice)")
            if c._es_freq:
                raise ValueError(
                    f"model {name!r} uses prediction early stop; packs "
                    "cannot replay a per-model tree-count stop")
        self.members = dict(members)
        self.stats = stats
        self.packed = PackedForests(
            {n: c._compiled for n, c in self.members.items()})
        self.width = self.packed.width
        bl = tuple(sorted({int(b) for b in (buckets or DEFAULT_BUCKETS)
                           if int(b) > 0}))
        self.buckets = bl or DEFAULT_BUCKETS
        self.key = frozenset((n, c.key) for n, c in self.members.items())
        self._warm: set = set()
        self._warm_lock = threading.Lock()

    @property
    def hbm_bytes(self) -> int:
        return int(self.packed.nbytes)

    def predict_mixed(self, parts, record: bool = True):
        """parts: list of ``(model_name, X [n_i, >=width_i], raw_score)``.
        Returns one output per part, each matching what the member cache's
        ``predict`` would have returned — but the whole mixed batch pays
        ONE traversal dispatch per padded bucket instead of one per model.
        """
        Xs, rms, ns = [], [], []
        for name, X, _raw in parts:
            X = np.ascontiguousarray(np.asarray(X, dtype=np.float32))
            if X.ndim != 2:
                raise ValueError(
                    f"serve predict expects 2-D rows, got {X.shape}")
            if X.shape[1] > self.width:
                X = X[:, :self.width]
            elif X.shape[1] < self.width:
                # a member model never gathers past its own width, so the
                # pad value is unreachable for this row's trees
                X = np.concatenate(
                    [X, np.full((X.shape[0], self.width - X.shape[1]),
                                np.nan, np.float32)], axis=1)
            Xs.append(X)
            rms.append(np.full(X.shape[0],
                               self.packed.model_index[name], np.int32))
            ns.append(X.shape[0])
        X = np.concatenate(Xs)
        rm = np.concatenate(rms)
        N = X.shape[0]
        outs = []
        lo = 0
        for n, b in _plan(self.buckets, N):
            xb, rb = X[lo:lo + n], rm[lo:lo + n]
            lo += n
            if n < b:
                xb = np.concatenate(
                    [xb, np.zeros((b - n, self.width), np.float32)])
                rb = np.concatenate([rb, np.zeros(b - n, np.int32)])
            with self._warm_lock:
                hit = b in self._warm
                if not hit:
                    self._warm.add(b)
            if record and self.stats is not None:
                self.stats.record_cache(hit, bucket=b)
            if not hit and self.stats is not None:
                self.stats.record_bucket_compile(b)
            outs.append(self.packed.predict(xb, rb)[:, :n])
        raw = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
        res = []
        lo = 0
        for (name, _X, raw_score), n in zip(parts, ns):
            c = self.members[name]
            K = c.num_class
            out = raw[:K, lo:lo + n]
            lo += n
            # the member cache's _dispatch tail, op for op (bit-identity)
            if c.gbdt.average_output:
                out = out / c._n_iters
            obj = c.gbdt.objective
            if not raw_score and obj is not None:
                out = obj.convert_output(out)
            # graftlint: disable=R1 — the terminal D2H of the response is
            # inherent to serving: results must reach the client as numpy
            part = np.asarray(jax.device_get(out))
            res.append(part[0] if K == 1 else part.T)
        return res

    def warm(self) -> float:
        """Pre-compile every pack bucket (zero rows, model 0)."""
        name = next(iter(self.members))
        t0 = time.perf_counter()
        for b in self.buckets:
            self.predict_mixed(
                [(name, np.zeros((b, self.width), np.float32), True)],
                record=False)
        return time.perf_counter() - t0
