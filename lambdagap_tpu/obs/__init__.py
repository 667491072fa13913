"""lambdagap_tpu.obs — unified training/serving observability (graftscope).

The telemetry subsystem the perf work reports through (docs/observability.md):

- :mod:`.telemetry` — :class:`TrainTelemetry`: named per-iteration phase
  spans (gradients, sampling, histogram, split, partition, tree,
  score_update, eval) with exclusive-time accounting, a bounded ring buffer
  of per-iteration records, and aggregate reservoirs. Device-complete
  timing is taken ONCE per iteration boundary (a single
  ``block_until_ready``), so no host sync lands inside hot paths.
- :mod:`.events` — JSONL structured run log (run header, one record per
  iteration, compile/swap/error events).
- :mod:`.xla_watch` — recompile & transfer watchdog over ``jax.monitoring``
  events; sorts every compile into fresh (XLA ran) or loaded (the
  persistent cache served it), per program; warns when a steady-state
  iteration triggers a fresh compile (the graftlint-R2 hazard class,
  caught at runtime).
- :mod:`.profile` — ``jax.profiler`` capture windows driven by the
  ``profile_start_iter`` / ``profile_n_iters`` / ``profile_dir`` knobs.
- :mod:`.prom` — Prometheus text exposition for ``TrainTelemetry``, the
  serve layer's ``ServeStats``, and the merged fleet plane.
- :mod:`.reservoir` — the bounded uniform sample shared by training and
  serving percentiles, with the lifted-aggregate merge the fleet plane
  sums distributions with.
- :mod:`.trace` — distributed request tracing (graftscope v2): trace
  contexts minted at the frontend, one span per hop of the serve stack,
  parent-linked trees that tile the client-observed wall, and the
  per-process flight recorder (bounded span/event ring, atomic dumps on
  fault/SIGTERM/interval).
- :mod:`.fleet` — the fleet metric plane: scrape every replica's stats,
  merge counters exactly and latency reservoirs weight-correctly into
  one fleet snapshot + one ``prometheus fleet`` exposition.
- :mod:`.signals` — derived control signals (online goodput-knee,
  residency/eviction pressure, per-replica health timeline): the inputs
  ROADMAP item 2's revival/placement/autoscaling loop consumes.

Everything is inert unless enabled (``telemetry=true`` / ``telemetry_out=``;
``serve_trace_sample>0`` for tracing): the off path records nothing and
registers no ``jax.monitoring`` hooks.
"""
from __future__ import annotations

from .reservoir import MergedReservoir, Reservoir, merge_states  # noqa: F401
from .telemetry import NULL_TELEMETRY, TrainTelemetry  # noqa: F401
from .trace import (RECORDER, FlightRecorder, SpanRecorder,  # noqa: F401
                    TraceContext, start_trace, validate_tree)

__all__ = ["Reservoir", "MergedReservoir", "merge_states",
           "TrainTelemetry", "NULL_TELEMETRY", "TraceContext",
           "SpanRecorder", "FlightRecorder", "RECORDER", "start_trace",
           "validate_tree"]
