"""Training entry points: train() and cv().

(reference: python-package/lightgbm/engine.py — train :109, cv :627,
CVBooster :356.)
"""
from __future__ import annotations

import copy
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import numpy as np

from . import callback as callback_mod
from .basic import Booster, Dataset
from .callback import CallbackEnv, EarlyStopException
from .config import Config
from .utils import log


def train(params: Dict[str, Any], train_set: Dataset,
          num_boost_round: int = 100,
          valid_sets: Optional[List[Dataset]] = None,
          valid_names: Optional[List[str]] = None,
          feval: Optional[Callable] = None,
          init_model: Optional[Union[str, Booster]] = None,
          keep_training_booster: bool = False,
          callbacks: Optional[List[Callable]] = None,
          resume: str = "") -> Booster:
    """Train a booster (reference: engine.py:109).

    ``resume="auto"`` (or the ``resume=auto`` parameter) continues from the
    latest valid crash-safe snapshot for ``output_model`` — model trees,
    sampling RNG, DART state and early-stopping bests are all restored, so
    the resumed run is bit-consistent with an uninterrupted one
    (docs/robustness.md)."""
    from .guard import snapshot as guard_snapshot
    params = dict(params)
    cfg = Config.from_params(params)
    if "num_iterations" not in {Config.canonical_name(k) for k in params}:
        cfg.num_iterations = num_boost_round
    num_boost_round = cfg.num_iterations

    booster = Booster(params=params, train_set=train_set)
    resumed_state: Optional[Dict[str, Any]] = None
    if (resume or cfg.resume) == "auto":
        found = guard_snapshot.latest_snapshot(cfg.output_model)
        if found is not None:
            snap_path, model_str, resumed_state = found
            if init_model is not None:
                log.warning("resume=auto found snapshot %s; init_model is "
                            "ignored", snap_path)
                init_model = None
            from .models.model_text import load_model_from_string
            _, trees = load_model_from_string(model_str)
            booster._booster.resume_from(trees)
            guard_snapshot.restore_state(booster._booster, resumed_state)
            log.info("Resumed from snapshot %s (%d completed iterations)",
                     snap_path, booster._booster.iter_)
    if init_model is not None:
        from .models.model_text import load_model_from_string
        if isinstance(init_model, Booster):
            model_str = init_model.model_to_string()
        else:
            with open(init_model) as f:
                model_str = f.read()
        _, trees = load_model_from_string(model_str)
        booster._booster.resume_from(trees)

    valid_sets = valid_sets or []
    valid_names = valid_names or []
    valid_contains_train = False
    for i, vs in enumerate(valid_sets):
        name = valid_names[i] if i < len(valid_names) else f"valid_{i}"
        if vs is train_set:
            valid_contains_train = True
            booster._booster.config.is_provide_training_metric = True
            from .metrics.base import create_metrics
            booster._booster.train_metrics = create_metrics(
                booster.config, train_set.construct(booster.config).metadata,
                train_set.construct(booster.config).num_data)
            booster._train_name = name
            continue
        booster.add_valid(vs, name)

    cbs = list(callbacks or [])
    if cfg.early_stopping_round > 0 and valid_sets:
        cbs.append(callback_mod.early_stopping(
            cfg.early_stopping_round, cfg.first_metric_only,
            verbose=cfg.verbosity >= 1,
            min_delta=cfg.early_stopping_min_delta))
    if cfg.verbosity >= 1 and cfg.metric_freq > 0:
        cbs.append(callback_mod.log_evaluation(cfg.metric_freq))
    cbs_before = [cb for cb in cbs if getattr(cb, "before_iteration", False)]
    cbs_after = [cb for cb in cbs if not getattr(cb, "before_iteration", False)]
    for group in (cbs_before, cbs_after):
        group.sort(key=lambda cb: getattr(cb, "order", 0))

    # early-stopping bookkeeping rides in the snapshot sidecar so a resumed
    # run keeps counting patience from the recorded best, not from scratch
    es_state = next((cb.state for cb in cbs_after
                     if getattr(cb, "is_early_stopping", False)), None)
    if resumed_state is not None and es_state is not None \
            and resumed_state.get("early_stop"):
        es_state.update(resumed_state["early_stop"])

    telemetry = booster._booster.telemetry
    start_iteration = booster._booster.iter_ if resumed_state is not None else 0
    evals: List[Tuple[str, str, float, bool]] = []
    for i in range(start_iteration, num_boost_round):
        env0 = CallbackEnv(model=booster, params=params, iteration=i,
                           begin_iteration=0, end_iteration=num_boost_round,
                           evaluation_result_list=[], telemetry=telemetry)
        for cb in cbs_before:
            cb(env0)
        stop = booster.update()
        if cfg.snapshot_freq > 0 and (i + 1) % cfg.snapshot_freq == 0:
            # periodic crash-safe snapshots (reference: gbdt.cpp:252-256;
            # atomic write + state sidecar, guard/snapshot.py)
            guard_snapshot.write_training_snapshot(
                booster._booster, cfg.output_model, early_stop=es_state,
                faults=booster._booster.guard.plan,
                keep=cfg.guard_snapshot_keep)

        evals = []
        with telemetry.phase("eval"):
            if valid_contains_train:
                name = getattr(booster, "_train_name", "training")
                evals.extend((name, m, v, g)
                             for (_, m, v, g) in booster._booster.eval_train())
            evals.extend(booster._booster.eval_valid())
            if feval is not None:
                evals.extend(_run_feval(feval, booster, train_set,
                                        valid_sets, valid_names,
                                        valid_contains_train))
        env = CallbackEnv(model=booster, params=params, iteration=i,
                          begin_iteration=0, end_iteration=num_boost_round,
                          evaluation_result_list=evals, telemetry=telemetry)
        try:
            for cb in cbs_after:
                cb(env)
        except EarlyStopException as e:
            booster.best_iteration = e.best_iteration + 1
            for d, m, v, _ in e.best_score:
                booster.best_score.setdefault(d, {})[m] = v
            break
        if stop:
            break
    if booster.best_iteration < 0:
        for d, m, v, _ in evals if num_boost_round > 0 else []:
            booster.best_score.setdefault(d, {})[m] = v
    # flush the run log + unhook jax.monitoring; records stay readable on
    # booster._booster.telemetry (and keep accumulating if the caller keeps
    # training the booster by hand)
    telemetry.close()
    return booster


def _run_feval(feval, booster, train_set, valid_sets, valid_names,
               include_train) -> List[Tuple[str, str, float, bool]]:
    out = []
    fevals = feval if isinstance(feval, (list, tuple)) else [feval]
    gb = booster._booster
    datasets = []
    if include_train:
        datasets.append((getattr(booster, "_train_name", "training"),
                         gb._converted_scores(gb.scores), gb.train_set))
    for vi, (name, ds) in enumerate(gb.valid_sets):
        datasets.append((name, gb._converted_scores(gb.valid_scores[vi]), ds))
    for name, preds, ds in datasets:
        for f in fevals:
            res = f(preds, ds)
            res_list = res if isinstance(res, list) else [res]
            for mname, val, greater in res_list:
                out.append((name, mname, val, greater))
    return out


class CVBooster:
    """Container of per-fold boosters (reference: engine.py:356)."""

    def __init__(self) -> None:
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def append(self, booster: Booster) -> None:
        self.boosters.append(booster)

    def __getattr__(self, name):
        def handler(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]
        return handler


def _make_n_folds(full_data: Dataset, nfold: int, params: Dict,
                  seed: int, stratified: bool, shuffle: bool):
    cfg = Config.from_params(params)
    ds = full_data.construct(cfg)
    num_data = ds.num_data
    rng = np.random.RandomState(seed)
    if ds.metadata.query_boundaries is not None:
        # group-aware folds: split whole queries
        nq = ds.metadata.num_queries
        q_idx = rng.permutation(nq) if shuffle else np.arange(nq)
        qb = ds.metadata.query_boundaries
        folds_q = np.array_split(q_idx, nfold)
        for fq in folds_q:
            test_rows = np.concatenate(
                [np.arange(qb[q], qb[q + 1]) for q in fq]) if len(fq) else np.array([], int)
            train_rows = np.setdiff1d(np.arange(num_data), test_rows)
            yield train_rows, test_rows
        return
    if stratified and ds.metadata.label is not None:
        label = np.asarray(ds.metadata.label)
        idx_by_class = [np.nonzero(label == c)[0] for c in np.unique(label)]
        folds = [[] for _ in range(nfold)]
        for idxs in idx_by_class:
            if shuffle:
                idxs = rng.permutation(idxs)
            for fi, part in enumerate(np.array_split(idxs, nfold)):
                folds[fi].append(part)
        for fi in range(nfold):
            test_rows = np.sort(np.concatenate(folds[fi]))
            train_rows = np.setdiff1d(np.arange(num_data), test_rows)
            yield train_rows, test_rows
        return
    idx = rng.permutation(num_data) if shuffle else np.arange(num_data)
    for part in np.array_split(idx, nfold):
        test_rows = np.sort(part)
        train_rows = np.setdiff1d(np.arange(num_data), test_rows)
        yield train_rows, test_rows


def cv(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True, shuffle: bool = True,
       metrics=None, feval=None, init_model=None,
       seed: int = 0, callbacks=None, eval_train_metric: bool = False,
       return_cvbooster: bool = False) -> Dict[str, List[float]]:
    """Cross-validation (reference: engine.py:627)."""
    params = dict(params)
    if metrics is not None:
        params["metric"] = metrics
    cfg = Config.from_params(params)
    if "num_iterations" not in {Config.canonical_name(k) for k in params}:
        cfg.num_iterations = num_boost_round
    num_boost_round = cfg.num_iterations

    if folds is None:
        folds = list(_make_n_folds(train_set, nfold, params, seed,
                                   stratified and cfg.objective in
                                   ("binary", "multiclass", "multiclassova"),
                                   shuffle))
    elif hasattr(folds, "split"):
        # sklearn splitter objects (KFold & friends)
        ds = train_set.construct(cfg)
        X_idx = np.zeros((ds.num_data, 1))
        y = (np.asarray(ds.metadata.label)
             if ds.metadata.label is not None else None)
        groups = None
        if ds.metadata.query_boundaries is not None:
            qb = ds.metadata.query_boundaries
            groups = np.searchsorted(qb, np.arange(ds.num_data),
                                     side="right") - 1
        folds = list(folds.split(X_idx, y, groups))

    cvbooster = CVBooster()
    fold_data = []
    for train_rows, test_rows in folds:
        tr = train_set.subset(train_rows)
        te = train_set.subset(test_rows)
        b = Booster(params=params, train_set=tr)
        if eval_train_metric:
            b._booster.config.is_provide_training_metric = True
            from .metrics.base import create_metrics
            tds = tr.construct(b.config)
            b._booster.train_metrics = create_metrics(
                b.config, tds.metadata, tds.num_data)
        b.add_valid(te, "valid")
        fold_data.append(b)
        cvbooster.append(b)

    results: Dict[str, List[float]] = {}
    cbs = list(callbacks or [])
    if cfg.early_stopping_round > 0:
        best = [float("inf")]
        best_iter = [0]
    else:
        best = best_iter = None
    first_metric: Optional[str] = None

    for i in range(num_boost_round):
        agg: Dict[Tuple[str, str, bool], List[float]] = {}
        for b in fold_data:
            b.update()
            evals = list(b._booster.eval_valid())
            if eval_train_metric:
                evals.extend(("train", m, v, g)
                             for (_, m, v, g) in b._booster.eval_train())
            for (d, m, v, g) in evals:
                agg.setdefault((d, m, g), []).append(v)
        stop_now = False
        if first_metric is None:
            # early stopping tracks the FIRST configured metric on the
            # validation folds (reference: engine.py cv + _agg_cv_result)
            for (d, m, g) in agg:
                if d == "valid":
                    first_metric = m
                    break
        for (d, m, g), vals in agg.items():
            mean, std = float(np.mean(vals)), float(np.std(vals))
            results.setdefault(f"{d} {m}-mean", []).append(mean)
            results.setdefault(f"{d} {m}-stdv", []).append(std)
            if best is not None and d == "valid" and m == first_metric:
                score = -mean if g else mean
                if score < best[0]:
                    best[0] = score
                    best_iter[0] = i
                elif i - best_iter[0] >= cfg.early_stopping_round:
                    stop_now = True
        if stop_now:
            cvbooster.best_iteration = best_iter[0] + 1
            for key in results:
                results[key] = results[key][:best_iter[0] + 1]
            break
    if return_cvbooster:
        results["cvbooster"] = cvbooster
    return results
