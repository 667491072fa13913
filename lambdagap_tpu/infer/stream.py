"""predict_stream: warehouse-scale out-of-core batch scoring (ISSUE 18).

The reference serves two production shapes: low-latency online predict
(serve/ + the compiled forest) and offline scoring of billions of rows —
backfills, feature materialization, ``pred_contrib`` exports. Until now
the out-of-core machinery (data/stream.py ShardRing + ShardedBinnedDataset)
existed only on the TRAIN path and the compiled forest was tuned for
small serve batches: nothing could score a dataset that does not fit HBM.
This module is the missing driver ("Out-of-Core GPU Gradient Boosting",
arXiv:2005.09148 — host staging with overlapped transfers; row-window
sizing per the large-batch tiling argument of arXiv:1706.08359):

* host/memmap row windows pump through the factored
  :class:`~lambdagap_tpu.data.stream.WindowPump` (bounded async H2D ring,
  ``h2d_prefetch``/``chunk_wait`` phases) into ONE jitted per-window
  scoring program (:func:`_window_scorer` — the compiled-forest engine,
  falling back to the tensor/scan engines where compiled demotes);
* scores ride back through a second bounded ring (:class:`ScoreRing`,
  ``copy_to_host_async`` under the new ``d2h_scores`` phase), so score
  readback overlaps the NEXT window's traversal — both directions of the
  link are measured, not hoped;
* with a 2-D registry mesh configured (``mesh_shape``), window rows shard
  over the WHOLE flattened grid (sharding-registry rules ``pred_win`` /
  ``pred_scores``) under ``shard_map`` — scoring is collective-free, so
  1x8, 2x4 and 8x1 all run the one program and the bits cannot depend on
  the grid;
* ragged final windows pad to pow2 row buckets (rounded to the device
  count), so the trace set is bounded (graftir contract below) and a
  known-length run pre-warms every bucket before the pump opens —
  zero steady-state compiles, asserted by tools/batch_gate.py;
* co-tenancy: :class:`CoTenantThrottle` consumes the SignalPlane's
  goodput-knee signals (obs/signals.py) and throttles the pump's
  window-ISSUE rate with bounded backoff (guard/backoff.py), so a
  backfill soaks leftover capacity while interactive p99 is protected.

Scores are bit-identical to resident ``GBDT.predict_raw`` on every
engine, every shard raggedness and every grid shape: all three engines
are strictly per-row (traversal + per-row forest-order accumulation +
per-row early stop), so window splits, pad rows and row-sharding cannot
perturb any real row's bits (tests/test_predict_stream.py pins the full
matrix).
"""
from __future__ import annotations

import functools
import time
from collections import deque
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.ir.contracts import register_program
from ..data.stream import ShardedBinnedDataset, WindowPump
from ..guard.backoff import Backoff
from ..obs.profile import ProfileWindow
from ..obs.telemetry import NULL_TELEMETRY, TrainTelemetry
from ..parallel.sharding import make_mesh, shard_map, sharding, spec
from ..utils import log


# ---------------------------------------------------------------------------
# the D2H score ring
# ---------------------------------------------------------------------------
class ScoreRing:
    """Bounded async D2H ring for per-window score tiles — the mirror
    image of the H2D ShardRing. ``put`` issues ``copy_to_host_async`` on
    a window's device scores (non-blocking: the copy queues behind the
    window's compute), ``wait_ready`` materializes the OLDEST slot on the
    host. Both sides run under the ``d2h_scores`` phase, so the blocking
    residual of ``wait_ready`` is the measured un-overlap of the score
    readback (~0 when the ring hid the D2H behind the next window's
    traversal), exactly like ``chunk_wait`` measures the H2D side."""

    def __init__(self, depth: int = 2, telemetry=NULL_TELEMETRY) -> None:
        self.depth = max(int(depth), 1)
        self.telemetry = telemetry
        self._slots: deque = deque()

    def __len__(self) -> int:
        return len(self._slots)

    @property
    def full(self) -> bool:
        return len(self._slots) >= self.depth

    def put(self, key, scores: jax.Array) -> None:
        with self.telemetry.phase("d2h_scores"):
            if hasattr(scores, "copy_to_host_async"):
                scores.copy_to_host_async()
            self._slots.append((key, scores))

    def wait_ready(self):
        """(key, host_scores) of the oldest slot."""
        key, scores = self._slots.popleft()
        with self.telemetry.phase("d2h_scores"):
            # graftlint: disable=R1 — score-ring-slot completion sync:
            # this fetch is the instrument that MEASURES D2H overlap
            # (d2h_scores residual ~ 0 when copy_to_host_async already
            # landed the tile); it is the one legitimate sync of the
            # batch-scoring consume path
            host = np.asarray(jax.device_get(scores))
        return key, host


# ---------------------------------------------------------------------------
# the co-tenant throttle
# ---------------------------------------------------------------------------
class CoTenantThrottle:
    """Window-issue throttle driven by the SignalPlane's goodput signals
    (the first SignalPlane consumer OUTSIDE the autoscaler).

    ``signal_source`` is a SignalPlane (its ``snapshot()`` is read per
    check), or any callable returning a signals dict with a ``goodput``
    block. The batch job yields when the serve fleet is pressured:
    offered load at/past the measured knee (``knee_margin`` at or under
    ``knee_margin`` headroom) or goodput below the fleet's own
    ``good_ratio`` target. Each pressured check arms one bounded-backoff
    delay (guard/backoff.py — deterministic jitter, hard cap) and sleeps
    it BEFORE the next window is fetched/issued, so in-flight windows
    still land while the pump stops feeding the link; one healthy check
    resets the backoff clock, so the backfill re-soaks leftover capacity
    as soon as the interactive load backs off. The object is the
    :class:`~lambdagap_tpu.data.stream.WindowPump` ``gate`` callable.
    """

    def __init__(self, signal_source, *, knee_margin: float = 0.1,
                 backoff: Optional[Backoff] = None,
                 sleep: Callable[[float], None] = time.sleep) -> None:
        self._source = signal_source
        self.knee_margin = float(knee_margin)
        self.backoff = backoff if backoff is not None else Backoff(
            base_s=0.05, factor=2.0, max_s=2.0, jitter=0.1, seed=18)
        self._sleep = sleep
        self.checks = 0
        self.waits = 0
        self.waited_s = 0.0
        self.engaged = False

    def _signals(self) -> Optional[dict]:
        src = self._source
        if src is None:
            return None
        try:
            snap = src.snapshot() if hasattr(src, "snapshot") else src()
        except Exception as e:  # a dead signal plane must not kill the job
            log.warning("predict_stream throttle: signal source failed "
                        "(%s); running unthrottled this window", e)
            return None
        return snap if isinstance(snap, dict) else None

    def __call__(self) -> None:
        sig = self._signals()
        if sig is None:
            return
        good = sig.get("goodput") or {}
        self.checks += 1
        knee = float(good.get("knee_rps", 0.0) or 0.0)
        margin = float(good.get("knee_margin", 0.0) or 0.0)
        frac = float(good.get("good_fraction", 1.0))
        ratio = float(good.get("good_ratio", 0.9))
        pressured = (knee > 0.0 and margin <= self.knee_margin) \
            or frac < ratio
        if pressured:
            delay = self.backoff.note_failure()
            self.engaged = True
            self.waits += 1
            self.waited_s += delay
            self._sleep(delay)
        else:
            self.backoff.note_success()
            self.engaged = False

    def snapshot(self) -> dict:
        return {"checks": self.checks, "waits": self.waits,
                "waited_s": round(self.waited_s, 6),
                "engaged": self.engaged,
                "backoff": self.backoff.snapshot()}


# ---------------------------------------------------------------------------
# the jitted per-window scoring program
# ---------------------------------------------------------------------------
def _window_scorer(x, *, local):
    """The ONE per-window scoring program: ``local`` is the engine closure
    (compiled forest / tensor / scan dispatch + averaging + optional
    objective conversion) built by :func:`_build_scorer`; under a 2-D mesh
    this body runs per shard inside ``shard_map`` on its local rows. The
    program is strictly per-row — no collectives — which is what makes
    1x8/2x4/8x1 grids and every window split bit-identical."""
    return local(x)


register_program(
    "stream._window_scorer",
    collective_free=True,
    max_traces=2,
    notes="predict_stream per-window scoring (infer/stream.py): the "
          "window body must stay transfer-free (I2 — a host round-trip "
          "inside it would serialize every window of a warehouse-scale "
          "pass against the chip) and collective-free (per-row scoring; "
          "grid-invariance of the bits depends on it). Ragged final "
          "windows pad to pow2 row buckets, so a scenario sees at most "
          "two distinct traces: the steady window shape and one tail "
          "bucket (I4).")


def _pow2_bucket(rows: int, cap: int, mult: int) -> int:
    """Next pow2 at or above ``rows``, capped at ``cap`` and rounded up to
    a multiple of ``mult`` (the flattened device count): the bounded
    bucket set that keeps the trace count logarithmic in the window size
    while every bucket stays evenly row-shardable."""
    b = 1
    while b < rows:
        b <<= 1
    b = min(b, cap)
    b = -(-b // max(mult, 1)) * max(mult, 1)
    return max(b, mult, 1)


def _build_scorer(gb, idx, trees, es_freq: int, mesh, binned: bool,
                  has_linear: bool, raw_score: bool,
                  start_iteration: int, num_iteration: int):
    """The cached jitted scorer ``[bucket, F] -> [K, bucket]`` (final
    scores: averaged + objective-converted unless ``raw_score``). The
    engine tables ride the closure — the scorer is cached per booster
    generation (see ``GBDT.predict_stream``), so steady windows replay
    one trace per bucket shape."""
    from ..models.gbdt import dispatch_forest_predict
    cfg = gb.config
    K = gb.num_tree_per_iteration
    n_iters = max(1, len(idx) // max(K, 1))
    engine = cfg.predict_engine
    if binned and engine == "compiled":
        # the infer artifact models raw serving rows, not the training
        # bin tables — same demotion the resident replay paths take
        # (dispatch_forest_predict routes predict_engine=compiled onto
        # the tensor branch for binned rows)
        log.warning("predict_stream: predict_engine=compiled scores "
                    "binned windows through the tensor engine "
                    "(bit-identical; the compiled artifact serves raw "
                    "rows)")
    if not binned and engine == "compiled":
        cf = gb._compiled_forest(start_iteration, num_iteration, es_freq)
        base = cf.predict
    elif binned:
        from ..ops.predict_tensor import build_tree_tiles
        from ..ops.predict import build_forest_blocks, forest_to_arrays
        forest, depth = forest_to_arrays(trees, feature_meta=gb._meta,
                                         use_inner_feature=True)
        tree_class = jnp.asarray([i % K for i in idx], jnp.int32)
        if engine in ("tensor", "compiled"):
            blocks = build_tree_tiles(forest, tree_class,
                                      cfg.predict_tree_tile)
        else:
            blocks = build_forest_blocks(forest, tree_class)

        def base(x):
            return dispatch_forest_predict(
                cfg, x, forest, tree_class, K, depth, binned=True,
                early_stop_freq=es_freq,
                early_stop_margin=float(cfg.pred_early_stop_margin),
                blocks=blocks, has_linear=False)
    else:
        forest, depth, tree_class, blocks = gb._device_forest(idx, trees)

        def base(x):
            return dispatch_forest_predict(
                cfg, x, forest, tree_class, K, depth, binned=False,
                early_stop_freq=es_freq,
                early_stop_margin=float(cfg.pred_early_stop_margin),
                blocks=blocks, has_linear=has_linear)

    average = bool(gb.average_output) and n_iters > 1
    convert = (None if raw_score or gb.objective is None
               else gb.objective.convert_output)

    def local(x):
        out = base(x)
        if average:
            # same IEEE f32 division the resident path applies on the
            # host — elementwise, so per-window application is exact
            out = out / jnp.float32(n_iters)
        if convert is not None:
            out = convert(out)
        return out

    fn = functools.partial(_window_scorer, local=local)
    if mesh is None:
        return jax.jit(fn)
    # registry-mesh execution: window rows shard over the WHOLE flattened
    # grid (pred_win), score tiles ride back the same way (pred_scores) —
    # scoring has no collectives, so every dd x ff factorization runs
    # this one program on its local rows
    return jax.jit(shard_map(fn, mesh=mesh,
                             in_specs=(spec("pred_win", 2),),
                             out_specs=spec("pred_scores", 2),
                             check_vma=False))


# ---------------------------------------------------------------------------
# row sources
# ---------------------------------------------------------------------------
class _MatrixSource:
    """Dense host matrix (ndarray or np.memmap): windows are row slices,
    cast to f32 one window at a time — a memmap never materializes as a
    full float copy."""

    binned = False

    def __init__(self, gb, data) -> None:
        if getattr(data, "ndim", None) != 2:
            log.fatal("predict_stream expects a 2-D matrix, got shape %s",
                      (getattr(data, "shape", None),))
        self.data = gb._check_predict_shape(data)
        self.n_rows: Optional[int] = int(self.data.shape[0])
        self.n_cols: Optional[int] = int(self.data.shape[1])
        self.dtype = np.float32

    def blocks(self, window_rows: int):
        for lo in range(0, self.data.shape[0], window_rows):
            yield np.ascontiguousarray(
                self.data[lo:lo + window_rows], dtype=np.float32)


class _FileSource:
    """Text data file (csv/tsv/libsvm) read block-wise through the
    loader's bounded-memory machinery — one window of parsed rows
    resident at a time, column handling identical to the resident
    ``Booster.predict(path)`` parse."""

    binned = False

    def __init__(self, gb, path: str) -> None:
        self.gb = gb
        self.path = str(path)
        self.n_rows: Optional[int] = None     # unknown until EOF
        self.n_cols: Optional[int] = None
        self.dtype = np.float32

    def blocks(self, window_rows: int):
        from ..data.loader import iter_predict_blocks
        for blk in iter_predict_blocks(self.path, self.gb.config,
                                       block_rows=window_rows):
            yield np.ascontiguousarray(
                self.gb._check_predict_shape(blk), dtype=np.float32)


class _ShardedSource:
    """A ShardedBinnedDataset sharing the model's training bin layout:
    windows are dataset-order ``row_block`` copies (sequential memcpys
    across shard boundaries — the prefetch-friendly path), traversed
    through the inner-feature binned tables."""

    binned = True

    def __init__(self, gb, ds: ShardedBinnedDataset) -> None:
        if gb._meta is None:
            log.fatal("predict_stream on a binned dataset needs the "
                      "training feature metadata (an in-session trained "
                      "booster); a loaded model scores raw matrices or "
                      "files")
        if len(ds.used_features) != len(gb.train_set.used_features):
            log.fatal("predict_stream: dataset bin layout (%d used "
                      "features) does not match the model's training "
                      "layout (%d); build the dataset with "
                      "reference=train_set",
                      len(ds.used_features),
                      len(gb.train_set.used_features))
        self.ds = ds
        self.n_rows: Optional[int] = int(ds.num_data)
        self.n_cols: Optional[int] = int(ds.shards[0].shape[1])
        self.dtype = ds.shards[0].dtype

    def blocks(self, window_rows: int):
        n = self.ds.num_data
        for lo in range(0, n, window_rows):
            yield self.ds.row_block(lo, min(lo + window_rows, n))


def _as_source(gb, data):
    import os
    if isinstance(data, ShardedBinnedDataset):
        return _ShardedSource(gb, data)
    if isinstance(data, (str, os.PathLike)):
        return _FileSource(gb, data)
    return _MatrixSource(gb, np.asarray(data) if not isinstance(
        data, np.ndarray) else data)


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------
def predict_stream(gb, data, *, start_iteration: int = 0,
                   num_iteration: int = -1, raw_score: bool = False,
                   pred_contrib: bool = False, window_rows: int = 0,
                   out: Optional[np.ndarray] = None,
                   signal_source=None,
                   throttle: Optional[CoTenantThrottle] = None,
                   stats_out: Optional[dict] = None) -> np.ndarray:
    """Score ``data`` out-of-core through the double-ring window pump.

    ``data`` is a dense host matrix (ndarray/np.memmap), a text data file
    path, or a :class:`ShardedBinnedDataset` sharing the model's bin
    layout. Returns exactly what the resident predict returns —
    ``[N]``/``[N, K]`` scores (``raw_score`` bit-identical to
    ``predict_raw``), or the ``[N, F+1]``/``[N, K*(F+1)]`` SHAP matrix
    with ``pred_contrib`` — assembled window by window; ``out`` (e.g. an
    ``np.memmap``) receives the rows in place for results larger than
    host RAM. ``signal_source``/``throttle`` arm the co-tenant gate;
    ``stats_out`` (a dict) receives the run report: windows, buckets,
    phase totals (``h2d_prefetch``/``chunk_wait``/``d2h_scores``),
    per-window telemetry records and the throttle snapshot.
    """
    cfg = gb.config
    src = _as_source(gb, data)
    K = gb.num_tree_per_iteration
    idx = gb._model_slice(start_iteration, num_iteration)
    if not idx:
        n = src.n_rows or 0
        res = np.zeros((K, n), dtype=np.float32)
        return res[0] if K == 1 else res.T
    gb._materialize_lazy(idx)
    trees = [gb._tree(i) for i in idx]
    has_linear = any(getattr(t, "is_linear", False) for t in trees)
    if src.binned and has_linear:
        log.fatal("predict_stream: linear-leaf forests traverse raw rows "
                  "(the per-leaf dot product needs raw features); score a "
                  "matrix or file source instead of a binned dataset")

    gate = throttle
    if gate is None and signal_source is not None \
            and cfg.predict_stream_throttle != "off":
        gate = CoTenantThrottle(
            signal_source, knee_margin=cfg.predict_stream_knee_margin,
            backoff=Backoff(base_s=cfg.predict_stream_backoff_s,
                            factor=2.0,
                            max_s=cfg.predict_stream_backoff_max_s,
                            jitter=0.1, seed=18))
    elif gate is not None and cfg.predict_stream_throttle == "off":
        gate = None

    if pred_contrib:
        return _contrib_stream(gb, src, idx, trees, window_rows, out,
                               gate, stats_out)

    es_freq = (cfg.pred_early_stop_freq * K
               if cfg.pred_early_stop and gb.objective is not None
               and gb.objective.name in ("binary", "multiclass",
                                         "multiclassova") else 0)
    mesh = (make_mesh(mesh_shape=cfg.mesh_shape) if cfg.mesh_shape
            else None)
    n_dev = int(mesh.devices.size) if mesh is not None else 1
    cap = int(window_rows or cfg.predict_stream_window_rows)
    cap = _pow2_bucket(cap, cap, n_dev)
    if src.n_rows is not None:
        # a small call never pays a full window of padding: the steady
        # window is itself pow2-bucketed against the total row count
        W = min(cap, _pow2_bucket(src.n_rows, cap, n_dev))
    else:
        W = cap
    depth = int(cfg.predict_stream_depth or cfg.stream_prefetch_depth)

    scorer = _cached_scorer(gb, idx, trees, es_freq, mesh, src.binned,
                            has_linear, raw_score, start_iteration,
                            num_iteration)
    ring_shardings = ([sharding(mesh, "pred_win", 2)] if mesh is not None
                      else None)
    tel = TrainTelemetry.from_config(cfg)
    if stats_out is not None and not tel.enabled:
        # a caller asking for the run report wants the overlap measured:
        # force a private telemetry instance on (no JSONL out, config
        # ring/warmup defaults) even when the training knob is off
        tel = TrainTelemetry(enabled=True,
                             ring=getattr(cfg, "telemetry_ring", 256),
                             warmup=getattr(cfg, "telemetry_warmup", 2))
    # profiler window keyed to the stream window index (the inference
    # analog of profile_start_iter; docs/observability.md)
    pw = ProfileWindow(
        start_iter=getattr(cfg, "profile_stream_start_window", -1),
        n_iters=getattr(cfg, "profile_stream_n_windows", 1),
        out_dir=getattr(cfg, "profile_dir", ""), unit="stream_window")
    t_start = time.perf_counter()
    metas: dict = {}
    buckets: set = set()

    def _prepare(blk: np.ndarray, is_tail: bool) -> np.ndarray:
        w = blk.shape[0]
        if w == W:
            return blk
        if src.n_rows is not None:
            b = _pow2_bucket(w, W, n_dev)
        else:
            # unknown-length source (files): the tail pads to the steady
            # window shape, which is already traced — zero late compiles
            b = W
        if b == w:
            return blk
        buf = np.zeros((b, blk.shape[1]), dtype=blk.dtype)
        buf[:w] = blk
        return buf

    def _windows():
        lo = 0
        c = 0
        for blk in src.blocks(W):
            w = blk.shape[0]
            tail = src.n_rows is not None and lo + w >= src.n_rows
            host = _prepare(blk, tail)
            buckets.add(int(host.shape[0]))
            metas[c] = (lo, w)
            yield c, (host,)
            lo += w
            c += 1

    # pre-warm the bucket set before any window record opens: a ragged
    # tail's first (and only) appearance is the LAST window — compiling
    # there would be a steady-state compile. With the length known the
    # bucket set is known up front; warming it costs one tiny dispatch
    # per extra bucket and keeps the pumped pass compile-free.
    if src.n_rows is not None and src.n_rows > 0:
        tail = src.n_rows % W or W
        warm = {W, _pow2_bucket(tail, W, n_dev)}
        for b in sorted(warm):
            dummy = np.zeros((b, src.n_cols), dtype=src.dtype)
            if ring_shardings is not None:
                dev = jax.device_put(dummy, ring_shardings[0])
            else:
                dev = jax.device_put(dummy)
            # deliberate warmup sync, not steady state: the bucket traces
            # must land BEFORE the pump opens (a compile under a window
            # record would be a steady-state compile).
            scorer(dev).block_until_ready()

    res = None
    if out is None and src.n_rows is not None:
        res = np.empty((K, src.n_rows), dtype=np.float32)
    parts: list = []                     # unknown-length assembly
    rows_done = 0

    def _write(host: np.ndarray, lo: int, w: int) -> None:
        nonlocal rows_done
        tile = host[:, :w]
        if out is not None:
            if out.ndim == 1:
                out[lo:lo + w] = tile[0]
            else:
                out[lo:lo + w] = tile.T
        elif res is not None:
            res[:, lo:lo + w] = tile
        else:
            parts.append((lo, np.array(tile)))
        rows_done += w

    pump = WindowPump(_windows(), telemetry=tel, depth=depth,
                      shardings=ring_shardings, gate=gate)
    sring = ScoreRing(depth=depth, telemetry=tel)

    def _drain_one() -> None:
        key, host = sring.wait_ready()
        lo, w = metas.pop(key)
        _write(host, lo, w)

    n_windows = 0
    try:
        tel.begin_iteration(0)
        for key, bufs in pump:
            pw.on_tick(n_windows)
            scores = scorer(bufs[0])
            sring.put(key, scores)
            if sring.full:
                _drain_one()
            tel.end_iteration(sync=None)
            n_windows += 1
            tel.begin_iteration(n_windows)
        while len(sring):
            _drain_one()
        tel.end_iteration(sync=None)
        # device-complete by construction: every window's scores were
        # drained through ScoreRing.wait_ready above
        wall = time.perf_counter() - t_start
        if stats_out is not None:
            n_scored = rows_done
            stats_out.update({
                "rows": int(n_scored),
                "windows": n_windows,
                "window_rows": W,
                "buckets": sorted(buckets),
                "depth": depth,
                "engine": cfg.predict_engine,
                "mesh": ([int(mesh.shape[a]) for a in mesh.axis_names]
                         if mesh is not None else None),
                "wall_s": round(wall, 6),
                "rows_per_s": round(n_scored / wall, 3)
                if wall > 0 else None,
                "phases": {k: round(v, 6) for k, v in tel.totals.items()},
                "records": list(tel.records),
                "throttle": gate.snapshot() if gate is not None else None,
            })
    finally:
        pw.close(n_windows)
        tel.close()

    if out is not None:
        return out
    if res is None:
        n = sum(p[1].shape[1] for p in parts)
        res = np.empty((K, n), dtype=np.float32)
        for lo, tile in parts:
            res[:, lo:lo + tile.shape[1]] = tile
    return res[0] if K == 1 else res.T


def _cached_scorer(gb, idx, trees, es_freq, mesh, binned, has_linear,
                   raw_score, start_iteration, num_iteration):
    """One scorer per (model slice, engine, geometry): cached on the
    booster like the other predict-side views, so repeated
    ``predict_stream`` calls replay the warmed traces instead of paying a
    fresh jit cache (the C4 retrace-freedom story depends on this)."""
    cfg = gb.config
    geom = (tuple(int(mesh.shape[a]) for a in mesh.axis_names)
            if mesh is not None else None)
    key = (gb.generation, len(gb.models), idx[0], idx[-1], len(idx),
           cfg.predict_engine, es_freq, bool(binned), bool(raw_score),
           geom, cfg.predict_tree_tile)
    cache = getattr(gb, "_pstream_cache", None)
    if cache is None or cache[0] != key:
        gb._pstream_cache = (key, _build_scorer(
            gb, idx, trees, es_freq, mesh, binned, has_linear, raw_score,
            start_iteration, num_iteration))
    return gb._pstream_cache[1]


def _contrib_stream(gb, src, idx, trees, window_rows, out, gate,
                    stats_out):
    """``pred_contrib`` on the same window driver: per-window ``[W, F+1]``
    SHAP tiles (tree_shap/tree_shap_linear, models/shap.py) written
    straight into ``out`` — the warehouse-scale export path (an
    ``np.memmap`` out keeps the full [N, K*(F+1)] matrix off host RAM).
    Host-side compute, so only the throttle and windowing ride along —
    there is no device ring to overlap."""
    from ..models.shap import tree_shap_accumulate, tree_shap_linear
    if src.binned:
        log.fatal("predict_stream(pred_contrib=True) needs raw feature "
                  "rows (matrix or file source); TreeSHAP attributes raw "
                  "split values")
    cfg = gb.config
    K = gb.num_tree_per_iteration
    W = int(window_rows or cfg.predict_stream_window_rows)
    n_iters = max(1, len(idx) // max(K, 1))
    t_start = time.perf_counter()
    parts: list = []
    lo = 0
    n_windows = 0
    width = None
    for blk in src.blocks(W):
        if gate is not None:
            gate()
        data = np.ascontiguousarray(blk, dtype=np.float64)
        w, F = data.shape
        width = F
        phi = np.zeros((K, w, F + 1), dtype=np.float64)
        for pos, i in enumerate(idx):
            t = trees[pos]
            if getattr(t, "is_linear", False):
                tree_shap_linear(t, data, phi[i % K])
            else:
                tree_shap_accumulate(t, data, phi[i % K])
        if gb.average_output:
            phi /= n_iters
        tile = (phi[0] if K == 1
                else phi.transpose(1, 0, 2).reshape(w, K * (F + 1)))
        if out is not None:
            out[lo:lo + w] = tile
        else:
            parts.append(tile)
        lo += w
        n_windows += 1
    wall = time.perf_counter() - t_start
    if stats_out is not None:
        stats_out.update({
            "rows": lo, "windows": n_windows, "window_rows": W,
            "pred_contrib": True, "wall_s": round(wall, 6),
            "rows_per_s": round(lo / wall, 3) if wall > 0 else None,
            "throttle": gate.snapshot() if gate is not None else None,
        })
    if out is not None:
        return out
    if not parts:
        cols = (width or 0) + 1 if K == 1 else K * ((width or 0) + 1)
        return np.zeros((0, cols), dtype=np.float64)
    return np.concatenate(parts, axis=0)
