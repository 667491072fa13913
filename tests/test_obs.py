"""lambdagap_tpu.obs (graftscope): phase spans, ring buffer, JSONL schema,
recompile watchdog (fresh compiles and cache loads), Prometheus export,
serve `stats` line.

The ISSUE-4 acceptance surface: per-iteration phase spans must tile the
measured iteration wall (±10%), the emitted JSONL must validate against
the documented schema, the telemetry-off path must add zero records and
zero jax.monitoring hooks, and the watchdog must fire on a forced
steady-state recompile.
"""
import io
import json
import os
import re

import numpy as np
import pytest

import lambdagap_tpu as lgb
from lambdagap_tpu.obs import events, prom
from lambdagap_tpu.obs.telemetry import NULL_TELEMETRY, TrainTelemetry


def _data(n=500, d=10, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    y = (X[:, 0] + 0.2 * rng.randn(n) > 0).astype(np.float32)
    return X, y


def _train(extra=None, n=500, rounds=8, valid=False):
    X, y = _data(n)
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              **(extra or {})}
    kwargs = {}
    if valid:
        Xv, yv = _data(200, seed=1)
        kwargs["valid_sets"] = [lgb.Dataset(Xv, label=yv)]
    return lgb.train(params, lgb.Dataset(X, label=y),
                     num_boost_round=rounds, **kwargs)


# -- phase spans --------------------------------------------------------
def test_phase_spans_sum_to_iteration_wall():
    b = _train({"telemetry": True}, rounds=8)
    tel = b._booster.telemetry
    recs = list(tel.records)
    assert len(recs) == 8
    # skip iteration 0: boost-from-average + compiles land in untracked
    # gaps there; steady-state iterations must tile the wall within 10%
    for rec in recs[1:]:
        span_sum = sum(v for k, v in rec["phases"].items() if k != "eval")
        wall = rec["wall_s"]
        # phases are sub-intervals of the wall window, so the sum can
        # never meaningfully exceed it; the lower bound is the ±10% gate
        assert span_sum <= wall * 1.05 + 1e-3, (rec, span_sum)
        assert span_sum >= wall * 0.90 - 1e-3, (rec, span_sum)


def test_phase_records_cover_expected_phases():
    b = _train({"telemetry": True}, valid=True)
    rec = list(b._booster.telemetry.records)[-1]
    # serial learner on CPU: sub-phases recorded inside the tree span
    for phase in ("gradients", "sampling", "histogram", "split",
                  "partition", "tree", "score_update", "eval",
                  "device_wait"):
        assert phase in rec["phases"], rec["phases"]
    assert rec["iter"] == 7


# -- ring buffer --------------------------------------------------------
def test_ring_buffer_eviction():
    b = _train({"telemetry": True, "telemetry_ring": 4}, rounds=10)
    tel = b._booster.telemetry
    assert tel.iterations == 10
    recs = list(tel.records)
    assert len(recs) == 4
    assert [r["iter"] for r in recs] == [6, 7, 8, 9]


# -- JSONL schema -------------------------------------------------------
def test_jsonl_schema_roundtrip(tmp_path):
    out = str(tmp_path / "run.jsonl")
    _train({"telemetry_out": out}, rounds=5)
    lines = [ln for ln in open(out) if ln.strip()]
    objs = [json.loads(ln) for ln in lines]       # every record parses
    assert objs[0]["type"] == "run_header"
    assert objs[0]["schema_version"] == events.SCHEMA_VERSION
    assert objs[0]["params"]["num_leaves"] == 7
    iters = [o for o in objs if o["type"] == "iteration"]
    assert [o["iter"] for o in iters] == list(range(5))
    for o in iters:
        assert set(o) >= {"iter", "phases", "compiles", "transfers",
                          "wall_s"}
        assert o["compiles"]["total"] >= 0
    assert events.validate_file(out) == []


def test_jsonl_validator_rejects_bad_records(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('{"type":"iteration","iter":0}\nnot json\n')
    errs = events.validate_file(str(p))
    assert any("run_header" in e for e in errs)
    assert any("not JSON" in e for e in errs)
    assert any("missing" in e for e in errs)
    assert events.validate_file.__module__ == "lambdagap_tpu.obs.events"


# -- telemetry-off path -------------------------------------------------
def test_off_path_no_records_no_hooks():
    from jax._src import monitoring as m
    before = (len(m.get_event_listeners()),
              len(m.get_event_duration_listeners()))
    b = _train(rounds=3)
    tel = b._booster.telemetry
    assert not tel.enabled
    assert len(tel.records) == 0 and tel.iterations == 0
    after = (len(m.get_event_listeners()),
             len(m.get_event_duration_listeners()))
    assert before == after
    # and the enabled path unhooks again at close (engine.train closes)
    b2 = _train({"telemetry": True}, rounds=3)
    assert b2._booster.telemetry.enabled
    final = (len(m.get_event_listeners()),
             len(m.get_event_duration_listeners()))
    assert final == before


@pytest.mark.parametrize("public_api", [True, False],
                         ids=["jax_unregister", "older_jax"])
def test_watchdog_uninstall_removes_both_listeners(public_api, monkeypatch):
    """install/uninstall leave jax.monitoring as they found it, through
    jax's own unregister functions and, on a jax that has none, by taking
    the callbacks out of its listener lists."""
    import jax.monitoring
    from jax._src import monitoring as m
    from lambdagap_tpu.obs.xla_watch import XlaWatchdog
    if not public_api:
        monkeypatch.delattr(jax.monitoring, "unregister_event_listener")
        monkeypatch.delattr(jax.monitoring,
                            "unregister_event_duration_listener")
    before = (list(m.get_event_listeners()),
              list(m.get_event_duration_listeners()))
    dogs = [XlaWatchdog(), XlaWatchdog()]
    for dog in dogs:
        dog.install()
    assert len(m.get_event_listeners()) == len(before[0]) + 2
    for dog in dogs:
        dog.uninstall()
        dog.uninstall()                       # idempotent
        assert not dog.installed
    assert (list(m.get_event_listeners()),
            list(m.get_event_duration_listeners())) == before


def _host_annotations(trace_dir):
    """(name, iter) of every ``lg_*`` annotation in a profiler trace."""
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return [(ev.name, dict(ev.stats).get("iter"))
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for ev in line.events
            if ev.name.startswith("lg_")]


@pytest.mark.parametrize("telemetry", [True, False], ids=["on", "off"])
def test_spans_reach_the_profilers_clock(telemetry, tmp_path, monkeypatch):
    """With telemetry on the program itself annotates the profiler's
    timeline: one ``lg_iter`` per iteration and one ``lg_phase:<name>`` per
    span, device_wait included, each with the iteration number. Off: not
    one annotation, not one span object."""
    import jax
    from lambdagap_tpu.obs import telemetry as tmod
    built = []
    init = tmod._Span.__init__
    monkeypatch.setattr(tmod._Span, "__init__", lambda self, *a: (
        built.append(a[1]), init(self, *a))[1])
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        b = _train({"telemetry": telemetry, "tpu_fused_learner": 1,
                    "tree_layout": "sorted"}, rounds=3)
    finally:
        jax.profiler.stop_trace()
    found = _host_annotations(str(tmp_path))
    if not telemetry:
        assert found == [] and built == []
        assert not hasattr(b._booster.telemetry, "_annotation")
        return
    assert [it for name, it in found if name == tmod.ITER_ANNOTATION] \
        == [0, 1, 2]
    for it in range(3):
        phases = [name[len(tmod.PHASE_ANNOTATION):] for name, i in found
                  if i == it and name.startswith(tmod.PHASE_ANNOTATION)]
        assert phases[:6] == ["gradients", "sampling", "tree",
                              "layout_apply", "score_update",
                              "device_wait"], phases
    assert set(built) <= set(tmod.PHASES)


# -- Prometheus ---------------------------------------------------------
_PROM_HEADER = re.compile(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+$")
_PROM_SAMPLE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (\S+)$")


def test_prometheus_output_parses_line_by_line():
    from lambdagap_tpu.serve.stats import ServeStats
    b = _train({"telemetry": True}, rounds=4)
    stats = ServeStats()
    stats.record_request(0.001, 0.002, 0.004, rows=3)
    stats.record_cache(True, bucket=8)
    # labeled per-model/per-tenant + registry forms (ISSUE 9) must pass
    # the same line grammar
    stats.record_request(0.001, 0.001, 0.003, rows=2, model="default",
                         tenant="acme corp")
    stats.record_timeout(model="default", tenant="acme corp")
    stats.record_eviction(model="default")
    stats.record_readmission(model="default")
    snapshot = stats.snapshot()
    snapshot["registry"] = {"registered_models": 2, "resident_models": 1,
                            "hbm_bytes_resident": 4096,
                            "hbm_budget_bytes": 8192,
                            "models": {"default": {"resident": True},
                                       "b": {"resident": False}}}
    text = prom.render(telemetry=b._booster.telemetry,
                       serve_snapshot=snapshot)
    lines = [ln for ln in text.splitlines() if ln]
    assert len(lines) > 40
    for ln in lines:
        if ln.startswith("#"):
            assert _PROM_HEADER.match(ln), f"bad header line: {ln!r}"
            continue
        m = _PROM_SAMPLE.match(ln)
        assert m, f"unparseable sample line: {ln!r}"
        float(m.group(3))            # value parses as a float
        if m.group(2):               # labels parse as key="value" pairs
            assert re.fullmatch(
                r'\{([a-zA-Z_][a-zA-Z0-9_]*="[^"]*")'
                r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\}', m.group(2))
    # spot-check names and a labeled sample
    assert "lambdagap_train_phase_seconds_total{phase=\"tree\"}" in text
    assert "lambdagap_serve_requests_total 2" in text
    assert "lambdagap_serve_latency_ms{quantile=\"p99\"}" in text
    # the ISSUE-9 labeled forms
    assert 'lambdagap_serve_model_requests_total{model="default"} 1' in text
    assert 'lambdagap_serve_tenant_shed_total{tenant="acme corp"} 1' in text
    assert ('lambdagap_serve_tenant_latency_ms{quantile="p50",'
            'tenant="acme corp"}') in text
    assert "lambdagap_serve_evictions_total 1" in text
    assert 'lambdagap_serve_registry_model_resident{model="b"} 0' in text
    assert "lambdagap_serve_registry_hbm_budget_bytes 8192" in text


_PROM_LABELS_ESCAPED = re.compile(
    r'\{([a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\\\|\\"|\\n)*")'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\\\|\\"|\\n)*")*\}')


def test_prometheus_hostile_label_values_escaped():
    """Model/tenant names are user-supplied strings; the exposition must
    escape backslash/quote/newline per the format spec, so a hostile name
    can neither break a sample line nor inject one (ISSUE 12)."""
    from lambdagap_tpu.serve.stats import ServeStats
    stats = ServeStats()
    evil_model = 'm"x\\y\nz'
    evil_tenant = '\\"end\n# HELP fake_metric injected'
    stats.record_request(0.001, 0.002, 0.003, rows=1, model=evil_model,
                         tenant=evil_tenant)
    stats.record_timeout(model=evil_model, tenant=evil_tenant)
    snapshot = stats.snapshot()
    snapshot["registry"] = {"registered_models": 1, "resident_models": 1,
                            "hbm_bytes_resident": 1, "hbm_budget_bytes": 0,
                            "models": {evil_model: {"resident": True}}}
    text = prom.render_serve(snapshot)
    for ln in [ln for ln in text.splitlines() if ln]:
        if ln.startswith("#"):
            assert _PROM_HEADER.match(ln), f"bad header line: {ln!r}"
            continue
        m = _PROM_SAMPLE.match(ln)
        assert m, f"unparseable sample line: {ln!r}"
        float(m.group(3))
        if m.group(2):
            assert _PROM_LABELS_ESCAPED.fullmatch(m.group(2)), \
                f"label values not exposition-escaped: {ln!r}"
    # escaped forms present; the injection attempt never starts a line
    assert '\\"end\\n# HELP' in text
    assert not any(ln.startswith("# HELP fake_metric")
                   for ln in text.splitlines())


def test_prometheus_router_exposition_parses_and_labels():
    snap = {"failovers": 3, "rejected_no_replica": 1,
            "replicas": {"r0": {"routed": 10, "inflight": 2,
                                "health": "ok", "dead": False},
                         "r1": {"routed": 4, "inflight": 0,
                                "health": "dead", "dead": True}}}
    text = prom.render_router(snap)
    for ln in [ln for ln in text.splitlines() if ln]:
        if ln.startswith("#"):
            assert _PROM_HEADER.match(ln), ln
        else:
            assert _PROM_SAMPLE.match(ln), ln
    assert "lambdagap_router_failovers_total 3" in text
    assert 'lambdagap_router_replica_routed_total{replica="r0"} 10' in text
    assert ('lambdagap_router_replica_health{replica="r1",state="dead"} 1'
            in text)
    assert ('lambdagap_router_replica_health{replica="r1",state="ok"} 0'
            in text)


# -- recompile watchdog -------------------------------------------------
def test_watchdog_fires_on_steady_state_recompile():
    import jax
    import jax.numpy as jnp
    tel = TrainTelemetry(enabled=True, warmup=1)
    try:
        tel.begin_iteration(5)                  # > warmup: steady state
        with tel.phase("tree"):
            # a brand-new jitted callable forces a fresh backend compile
            jax.jit(lambda x: x * 3 + 1)(jnp.ones(13, jnp.float32))
        tel.end_iteration()
    finally:
        tel.close()
    rec = list(tel.records)[-1]
    assert rec["compiles"]["total"] >= 1
    assert rec["compiles"]["steady"] >= 1
    assert rec["compiles"]["by_phase"].get("tree", 0) >= 1
    assert tel.watchdog.steady_compiles >= 1


def test_watchdog_quiet_during_warmup():
    import jax
    import jax.numpy as jnp
    tel = TrainTelemetry(enabled=True, warmup=10)
    try:
        tel.begin_iteration(0)
        jax.jit(lambda x: x - 7)(jnp.ones(11, jnp.float32))
        tel.end_iteration()
    finally:
        tel.close()
    rec = list(tel.records)[-1]
    assert rec["compiles"]["total"] >= 1
    assert rec["compiles"]["steady"] == 0


# -- serve stats line ---------------------------------------------------
def test_serve_loop_stats_lines():
    from lambdagap_tpu.serve import serve_loop
    b = _train(rounds=3)
    X, _ = _data(4)
    server = b.as_server()
    try:
        lines = ["\t".join(str(v) for v in X[0]),
                 "stats", "stats json",
                 "\t".join(str(v) for v in X[1])]
        out, stats = io.StringIO(), io.StringIO()
        n = serve_loop(server, lines, out, stats_stream=stats)
    finally:
        server.close()
    assert n == 2
    text = stats.getvalue()
    assert "lambdagap_serve_requests_total" in text
    # the JSON snapshot rides the same stream after the exposition
    snap = json.loads(text[text.index("\n{") + 1:])
    assert "latency_ms" in snap and "generation" in snap
    # predictions untouched by the stats lines
    assert len(out.getvalue().strip().splitlines()) == 2


# -- the watchdog's fresh compiles and cache loads -----------------------
@pytest.fixture
def persistent_cache(tmp_path):
    """A persistent compile cache of this test's own that keeps every
    program, put back as it was afterwards."""
    import jax
    from jax._src import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()


def test_the_watchdog_tells_a_fresh_compile_from_a_cache_load(
        persistent_cache):
    import jax
    import jax.numpy as jnp
    from lambdagap_tpu.obs.xla_watch import XlaWatchdog

    def watched_probe(x):
        return jnp.sin(x) * 3.0 + 1.0

    x = jnp.arange(7.0)
    dog = XlaWatchdog()
    dog.install()
    try:
        jax.jit(watched_probe)(x).block_until_ready()
        first = dog.totals()
        jax.clear_caches()            # the next call asks the disk cache
        jax.jit(watched_probe)(x).block_until_ready()
    finally:
        dog.uninstall()
    t = dog.totals()
    name = "jit(watched_probe)"
    assert first["fresh_by_program"][name]["n"] == 1
    assert name not in first["loaded_by_program"]
    assert t["fresh_by_program"][name]["n"] == 1
    assert t["loaded_by_program"][name]["n"] == 1
    assert t["fresh"] + t["loaded"] == t["compiles"]
    assert t["fresh_secs"] + t["load_secs"] == pytest.approx(
        t["compile_secs"], rel=1e-12)
    for kind in ("fresh", "loaded"):
        by = t[kind + "_by_program"]
        assert sum(v["n"] for v in by.values()) == t[kind]


def test_a_cache_hit_belongs_to_the_thread_that_asked():
    """The cache's events and the compile they belong to fire on one
    thread; a compile on another thread in between stays fresh."""
    import threading
    from lambdagap_tpu.obs import xla_watch as xw
    dog = xw.XlaWatchdog()
    compile_event = "/jax/core/compile/backend_compile_duration"
    dog._on_event(xw._CACHE_REQUEST)
    dog._on_event(xw._CACHE_HIT)
    other = threading.Thread(target=dog._on_duration,
                             args=(compile_event, 2.0),
                             kwargs={"fun_name": "jit(elsewhere)"})
    other.start()
    other.join()
    dog._on_duration(xw._CACHE_RETRIEVAL, 0.25)
    dog._on_duration(compile_event, 0.5, fun_name="jit(here)")
    dog._on_duration(compile_event, 1.0, fun_name="jit(here)")
    t = dog.totals()
    assert (t["fresh"], t["loaded"], t["compiles"]) == (2, 1, 3)
    assert (t["fresh_secs"], t["load_secs"], t["compile_secs"]) == \
        (3.0, 0.5, 3.5)
    assert t["fresh_by_program"] == {"jit(elsewhere)": {"n": 1, "secs": 2.0},
                                     "jit(here)": {"n": 1, "secs": 1.0}}
    assert t["loaded_by_program"] == {"jit(here)": {"n": 1, "secs": 0.5}}


def test_the_record_carries_the_runs_compiles(tmp_path):
    """Each record's ``compiles.run`` is what came before the first record
    plus the records' own deltas up to it, and the log validates."""
    out = str(tmp_path / "run.jsonl")
    b = _train({"telemetry_out": out}, rounds=4)
    recs = list(b._booster.telemetry.records)
    assert [r["iter"] for r in recs] == [0, 1, 2, 3]
    ints = ("total", "fresh", "loaded")
    secs = (("secs", "secs"), ("fresh_secs", "fresh_secs"),
            ("load_secs", "load_secs"))
    first = recs[0]["compiles"]
    before = {k: first["run"][k] - first[k] for k in ints}
    before.update({k: first["run"][k] - first[d] for k, d in secs})
    assert first["total"] > 0 and first["fresh"] > 0
    for k in range(len(recs)):
        c = recs[k]["compiles"]
        run = c["run"]
        for f in ints:
            assert run[f] == before[f] + sum(
                r["compiles"][f] for r in recs[:k + 1])
        for f, d in secs:
            assert run[f] == pytest.approx(before[f] + sum(
                r["compiles"][d] for r in recs[:k + 1]), abs=1e-5)
        assert c["fresh"] + c["loaded"] == c["total"]
        assert run["fresh"] + run["loaded"] == run["total"]
        assert run["fresh_secs"] + run["load_secs"] == pytest.approx(
            run["secs"], abs=1e-5)
        for kind, n in (("fresh", run["fresh"]), ("loaded", run["loaded"])):
            assert sum(v["n"] for v in run[kind + "_by_program"].values()) \
                == n
    assert events.validate_file(out) == []


def test_the_benchmark_reads_set_ups_compiles_from_the_windows_records():
    """What the benchmark's training window does: warm up, note the
    watchdog's totals,
    run the window, hand the readers the window's records alone. The two
    set-up metrics read those totals, split into fresh and loaded."""
    from benchmark.readers import program_record
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              "telemetry": True}
    bst = lgb.Booster(params, lgb.Dataset(X, label=y))
    tel = bst._booster.telemetry
    for _ in range(2):
        bst.update()
    setup = tel.watchdog.totals()
    for _ in range(2):
        bst.update()
    tel.close()
    window = list(tel.records)[-2:]
    got = {f: program_record.read({"reduction": "before_window", "field": f},
                                  {"records": window})
           for f in ("fresh_secs", "load_secs")}
    assert setup["compiles"] > 0
    assert got["fresh_secs"] == pytest.approx(setup["fresh_secs"], abs=1e-5)
    assert got["load_secs"] == pytest.approx(setup["load_secs"], abs=1e-5)
    assert got["fresh_secs"] + got["load_secs"] == pytest.approx(
        setup["compile_secs"], abs=1e-5)


# the reader's own cases (benchmark/tests, outside tier-1) run here too
from benchmark.tests.test_program_record import *  # noqa: E402,F401,F403


# -- shared reservoir ---------------------------------------------------
def test_reservoir_shared_between_obs_and_serve():
    from lambdagap_tpu.obs.reservoir import Reservoir
    from lambdagap_tpu.serve import stats as serve_stats
    assert serve_stats._Reservoir is Reservoir
    r = Reservoir(cap=10, seed=3)
    for i in range(1000):
        r.add(float(i))
    assert len(r.vals) == 10 and r.seen == 1000
    p = r.percentiles()
    assert 0.0 <= p["p50"] <= 999.0 and p["max"] <= 999.0


def test_null_telemetry_is_inert():
    assert not NULL_TELEMETRY.enabled
    NULL_TELEMETRY.begin_iteration(0)
    with NULL_TELEMETRY.phase("tree"):
        pass
    NULL_TELEMETRY.end_iteration()
    NULL_TELEMETRY.close()
    assert len(NULL_TELEMETRY.records) == 0
    assert NULL_TELEMETRY.summary() == {"enabled": False}


# -- profiler window knobs ---------------------------------------------
def test_profile_window_toggles(tmp_path):
    from lambdagap_tpu.obs.profile import ProfileWindow
    pw = ProfileWindow(start_iter=2, n_iters=2, out_dir=str(tmp_path))
    assert pw.enabled
    assert pw.on_iteration_start(0) is None
    assert pw.on_iteration_start(2) == "start"
    assert pw.on_iteration_start(3) is None
    assert pw.on_iteration_start(4) == "stop"
    assert pw.done
    # and the whole window rides an actual training run without error
    b = _train({"profile_start_iter": 1, "profile_n_iters": 1,
                "profile_dir": str(tmp_path / "t")}, rounds=4)
    assert b._booster.telemetry.enabled


def test_telemetry_off_by_default_in_config():
    from lambdagap_tpu.config import Config
    cfg = Config()
    assert not cfg.telemetry and cfg.telemetry_out == ""
    cfg2 = Config.from_params({"telemetry": "true", "telemetry_ring": 8})
    assert cfg2.telemetry and cfg2.telemetry_ring == 8
    with pytest.raises(RuntimeError):
        Config.from_params({"telemetry_ring": 0})
