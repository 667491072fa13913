"""HBM-aware model placement: bin-pack models onto replicas by design.

A request landing on a replica that evicted its model pays a
readmission (compile or load, then warm) before it is served; what that
costs has not been measured on a chip.
With every replica admitting every model under its own
``serve_hbm_budget_mb``, WHICH model is resident WHERE is decided by
arrival order — the one thing production traffic does not control. This
module decides it deliberately:

- :func:`plan_placement` — a deterministic greedy bin-pack: models in
  descending traffic order (hottest first — the model whose readmission
  would hurt most gets first pick of the budget), each assigned
  ``spread`` preferred replicas, chosen to fit the per-replica byte
  budget while balancing assigned traffic. A model too big for any
  remaining budget still gets the emptiest replica: the registry admits
  over-budget models anyway (one model is the floor), so the plan
  mirrors that reality instead of leaving the model homeless.
- :func:`plan_from_fleet` — the adapter from the fleet metric plane
  (obs/fleet.py merged snapshot: per-model requests as traffic,
  registry hbm bytes per copy) to the planner's inputs.

The plan is actuated in two places (serve/autonomics.py): the router
routes a model's traffic to its preferred replicas
(``Router.set_placement`` — requests land where the forest lives) and
the controller ``prefetch``-es newly preferred models so the readmission
compile happens off the request path. Placement is a PREFERENCE, not a
partition: failover still reaches every live replica, and a replica
asked for a non-resident model still serves it (paying the cliff the
plan exists to avoid).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence


def plan_placement(models: Dict[str, Dict], replicas: Sequence[str],
                   budget_bytes: int = 0, spread: int = 1
                   ) -> Dict[str, List[str]]:
    """model -> preferred replica names.

    ``models``: name -> ``{"bytes": per-copy device bytes,
    "traffic": request weight}`` (missing keys read as 0).
    ``budget_bytes`` is the PER-REPLICA residency budget (0 = unlimited:
    pure traffic balancing). ``spread`` preferred replicas per model
    (capped at the replica count). Deterministic: ties break on name.
    """
    names = [str(r) for r in replicas]
    if not names or not models:
        return {}
    spread = max(1, min(int(spread), len(names)))
    remaining = {r: float(budget_bytes) for r in names}
    traffic_load = {r: 0.0 for r in names}
    order = sorted(models,
                   key=lambda m: (-float(models[m].get("traffic", 0)),
                                  -float(models[m].get("bytes", 0)), m))
    plan: Dict[str, List[str]] = {}
    for model in order:
        need = float(models[model].get("bytes", 0))
        share = float(models[model].get("traffic", 0)) / spread
        chosen: List[str] = []
        for _ in range(spread):
            fits = [r for r in names
                    if r not in chosen
                    and (budget_bytes <= 0 or remaining[r] >= need)]
            pool = fits or [r for r in names if r not in chosen]
            if not pool:
                break
            # least assigned traffic wins; budget headroom then name
            # break ties — hot models spread across cold replicas
            pick = min(pool, key=lambda r: (traffic_load[r],
                                            -remaining[r], r))
            chosen.append(pick)
            traffic_load[pick] += share
            if budget_bytes > 0:
                remaining[pick] -= need
        plan[model] = chosen
    return plan


def plan_from_fleet(fleet_snap: Dict, replicas: Sequence[str],
                    budget_bytes: int = 0, spread: int = 1
                    ) -> Dict[str, List[str]]:
    """The planner fed from a fleet snapshot (obs/fleet.py): traffic is
    each model's merged request count, per-copy bytes come from the
    merged registry (summed resident bytes / resident replica count; a
    model evicted everywhere reports 0 bytes and simply packs last among
    equals — its first placement pays one compile, after which real
    bytes flow back through the next scrape)."""
    merged = (fleet_snap or {}).get("merged") or {}
    registry = merged.get("registry") or {}
    per_model = merged.get("per_model") or {}
    models: Dict[str, Dict] = {}
    for name, m in (registry.get("models") or {}).items():
        copies = max(int(m.get("resident_replicas", 0)), 1)
        models[name] = {
            "bytes": float(m.get("hbm_bytes", 0)) / copies,
            "traffic": float((per_model.get(name) or {}).get("requests", 0)),
        }
    # a model evicted EVERYWHERE at scrape time reports 0 bytes; packing
    # it as free would co-locate cold models with the hot one (the exact
    # churn placement exists to stop). Estimate unknowns at the fleet's
    # mean per-copy size — forests in one fleet are similar, and one
    # over-reservation beats an oscillating plan.
    known = [m["bytes"] for m in models.values() if m["bytes"] > 0]
    if known:
        est = sum(known) / len(known)
        for m in models.values():
            if m["bytes"] <= 0:
                m["bytes"] = est
    return plan_placement(models, replicas, budget_bytes=budget_bytes,
                          spread=spread)


def plan_changes(old: Optional[Dict[str, List[str]]],
                 new: Dict[str, List[str]]) -> Dict[str, List[str]]:
    """model -> replicas NEWLY preferred by ``new`` (the prefetch
    work-list; models whose preference set only shrank need no
    actuation — eviction happens lazily under the budget)."""
    old = old or {}
    out: Dict[str, List[str]] = {}
    for model, names in new.items():
        fresh = [r for r in names if r not in (old.get(model) or ())]
        if fresh:
            out[model] = fresh
    return out
