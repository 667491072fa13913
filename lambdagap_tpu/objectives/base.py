"""Objective function interface + factory.

TPU analog of the reference's ``ObjectiveFunction`` + ``CreateObjectiveFunction``
(reference: include/LightGBM/objective_function.h:19,98,
src/objective/objective_function.cpp:20-108). Objectives hold device-resident
label/weight arrays and expose a jit-compiled gradient computation; scores are
laid out class-major ``[K, N]`` like the reference's flat ``score[class*N+i]``.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..data.dataset import Metadata
from ..obs.telemetry import device_scope
from ..utils import log

K_EPSILON = 1e-15


class ObjectiveFunction:
    name = "base"
    num_model_per_iteration = 1
    # host-side constants an objective adds to every iteration record's
    # ``counts`` (the ranking objectives' bucketing); None: nothing
    work_counts: Optional[Dict[str, int]] = None

    def __init__(self, config: Config) -> None:
        self.config = config
        self.num_data = 0
        self.label: Optional[jax.Array] = None
        self.weight: Optional[jax.Array] = None
        self.label_np: Optional[np.ndarray] = None
        self.weight_np: Optional[np.ndarray] = None

    # -- lifecycle -----------------------------------------------------
    def init(self, metadata: Metadata, num_data: int) -> None:
        self.num_data = num_data
        if metadata.label is None:
            log.fatal("Objective %s requires labels", self.name)
        self.label_np = np.asarray(metadata.label, dtype=np.float32)
        self.label = jnp.asarray(self.label_np)
        if metadata.weight is not None:
            self.weight_np = np.asarray(metadata.weight, dtype=np.float32)
            self.weight = jnp.asarray(self.weight_np)

    # -- core ----------------------------------------------------------
    def get_gradients(self, scores: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """scores: [K, N] -> (grad, hess) each [K, N]."""
        raise NotImplementedError

    # jnp-array attributes read by get_gradients; subclasses declare them
    # so the jitted wrapper can pass them as ARGUMENTS (closing over device
    # arrays would inline them into the HLO as N-scale constants, see
    # fused_learner notes)
    _GRAD_ARRAY_FIELDS: Tuple[str, ...] = ()

    def get_gradients_fast(self, scores: jax.Array
                           ) -> Tuple[jax.Array, jax.Array]:
        """Jitted gradient computation for the boosting loop: eager
        ``get_gradients`` pays one dispatch per jnp op, which at ~1 ms per
        op over a remote-device link dwarfs the arithmetic. Falls back to
        the eager path for objectives that don't declare their array
        fields (e.g. the rank family, which jits internally)."""
        fields = tuple(f for f in self._GRAD_ARRAY_FIELDS
                       if getattr(self, f, None) is not None)
        if not fields:
            return self.get_gradients(scores)
        if getattr(self, "_grad_jit", None) is None:
            def fn(scores, *arrs):
                saved = [getattr(self, f) for f in fields]
                for f, a in zip(fields, arrs):
                    setattr(self, f, a)
                try:
                    with device_scope("gradients"):
                        return self.get_gradients(scores)
                finally:
                    for f, s in zip(fields, saved):
                        setattr(self, f, s)
            self._grad_jit = jax.jit(fn)
        return self._grad_jit(scores, *[getattr(self, f) for f in fields])

    def boost_from_score(self, class_id: int) -> float:
        """Initial score (reference: BoostFromScore per objective)."""
        return 0.0

    def convert_output(self, scores: jax.Array) -> jax.Array:
        """Raw score -> output space (e.g. sigmoid/exp/softmax)."""
        return scores

    def convert_output_np(self, scores):
        """Host (numpy) transform for serving-size batches — must match
        ``convert_output`` (the fast-predict path avoids any device
        dispatch, like the reference's single-row predictor). The default
        delegates to the jax version so a subclass overriding only
        ``convert_output`` can never diverge; subclasses with non-identity
        transforms provide a pure-numpy override."""
        if type(self).convert_output is ObjectiveFunction.convert_output:
            return scores
        import numpy as _np
        return _np.asarray(jax.device_get(
            self.convert_output(jax.numpy.asarray(scores))))

    # -- leaf renewal (L1 family) ---------------------------------------
    @property
    def is_renew_tree_output(self) -> bool:
        return False

    def renew_tree_output(self, leaf_rows: np.ndarray, score: np.ndarray) -> float:
        """Recompute one leaf's output from its rows (host-side; reference:
        RenewTreeOutput with residual_getter + weighted percentile)."""
        raise NotImplementedError

    # -- misc ----------------------------------------------------------
    @property
    def is_constant_hessian(self) -> bool:
        return False

    @property
    def num_class(self) -> int:
        return 1

    def to_string(self) -> str:
        return self.name


_REGISTRY: Dict[str, Type[ObjectiveFunction]] = {}


def register_objective(cls: Type[ObjectiveFunction]) -> Type[ObjectiveFunction]:
    _REGISTRY[cls.name] = cls
    return cls


def create_objective(config: Config) -> Optional[ObjectiveFunction]:
    """(reference: ObjectiveFunction::CreateObjectiveFunction,
    src/objective/objective_function.cpp:20)"""
    name = config.objective
    if name == "none":
        return None
    if name not in _REGISTRY:
        log.fatal("Unknown objective: %s", name)
    return _REGISTRY[name](config)


def weighted_percentile(values: np.ndarray, weights: Optional[np.ndarray],
                        alpha: float) -> float:
    """Weighted percentile matching the reference's PercentileFun /
    WeightedPercentileFun (reference: src/objective/regression_objective.hpp:23-87)."""
    n = len(values)
    if n == 0:
        return 0.0
    if n <= 1:
        return float(values[0])
    order = np.argsort(values)
    v = values[order]
    if weights is None:
        pos = alpha * (n - 1)
        lo = int(np.floor(pos))
        hi = min(lo + 1, n - 1)
        frac = pos - lo
        return float(v[lo] * (1 - frac) + v[hi] * frac)
    w = weights[order].astype(np.float64)
    cum = np.cumsum(w) - w[0]
    total = float(np.sum(w))
    threshold = alpha * (total - w[0])
    idx = int(np.searchsorted(cum, threshold, side="right")) - 1
    idx = max(0, min(idx, n - 2))
    if cum[idx + 1] - cum[idx] > 0:
        frac = (threshold - cum[idx]) / (cum[idx + 1] - cum[idx])
    else:
        frac = 0.0
    return float(v[idx] * (1 - frac) + v[idx + 1] * frac)


# graftir IR contract
from ..analysis.ir.contracts import register_program

register_program(
    "ObjectiveFunction.get_gradients_fast.fn", collective_free=True,
    notes="jitted gradient wrapper shared by the array-field objectives; "
          "one trace per boosting run")
