"""graftscope v2 (ISSUE 12): distributed request tracing, the fleet
metric plane, derived control signals, and the flight recorder.

The acceptance surface: one traced request through the serve stack must
produce a parent-linked span tree that tiles the client-observed wall
(schema-validated by ``obs.events.validate_file``); the fleet snapshot
over >= 2 replicas must equal the merge of the per-replica snapshots
(counter sums exact, reservoir quantiles consistent); sampling off must
add ZERO records; and a flight-recorder dump must be a valid JSONL the
postmortem tooling can render.
"""
import json
import os
import re
import time

import numpy as np
import pytest

import lambdagap_tpu as lgb
from lambdagap_tpu.obs import events, fleet, prom, signals, trace
from lambdagap_tpu.obs.reservoir import Reservoir, merge_states


@pytest.fixture(autouse=True)
def _clean_recorder():
    """Every test starts from an empty, sampling-off process recorder."""
    trace.RECORDER.configure(sample=0.0)
    trace.RECORDER.reset()
    yield
    trace.RECORDER.configure(sample=0.0)
    trace.RECORDER.close()
    trace.RECORDER.reset()


@pytest.fixture(scope="module")
def booster():
    rng = np.random.RandomState(0)
    X = rng.randn(500, 8).astype(np.float32)
    y = (X[:, 0] - 0.3 * X[:, 1] > 0).astype(np.float32)
    b = lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1,
                   "tpu_fast_predict_rows": 0},
                  lgb.Dataset(X, label=y), num_boost_round=4)
    return b, X


def _traced_submit(target, x, result_timeout=30.0):
    """Submit one traced request and record the client root span; returns
    the trace id. The root ends where the future RESOLVED (``t_done`` of
    ``serve.batcher.ResolvedAtFuture``), not where this thread was next
    given a core: beside five other tier-1 workers that wake-up took 4.6 ms
    of a 7.6 ms request, which no span of the serve stack can own."""
    ctx = trace.start_trace()
    t0_wall, t0 = time.time(), time.perf_counter()
    fut = target.submit(x, trace=ctx)
    fut.result(result_timeout)
    trace.RECORDER.record("client_request", ctx, t0_wall,
                          fut.t_done - t0,
                          span_id=ctx.span_id, parent="")
    return ctx.trace_id


# -- trace context ------------------------------------------------------
def test_trace_context_ids_wire_roundtrip():
    ctx = trace.start_trace()
    assert len(ctx.trace_id) == 16 and len(ctx.span_id) == 16
    child = ctx.child()
    assert child.trace_id == ctx.trace_id
    assert child.span_id != ctx.span_id
    wire = child.to_wire()
    back = trace.TraceContext.from_wire(wire)
    assert back.trace_id == ctx.trace_id
    assert back.span_id == child.span_id
    # hostile wire values degrade to untraced, never raise
    for bad in (None, "x", 7, [], {"id": 1}, {"id": "a"}, {"parent": "b"}):
        assert trace.TraceContext.from_wire(bad) is None


def test_sampling_knob():
    rec = trace.SpanRecorder(ring=64)
    rec.sample = 0.0
    assert rec.maybe_trace() is None
    rec.sample = 1.0
    ctx = rec.maybe_trace()
    assert ctx is not None and ctx.sampled


# -- in-process span tree ----------------------------------------------
def test_span_tree_tiles_served_request(booster):
    b, X = booster
    server = b.as_server(max_delay_ms=0.5)
    try:
        tid = _traced_submit(server, X[0])
    finally:
        server.close()
    spans = trace.RECORDER.spans(tid)
    names = {s["name"] for s in spans}
    assert {"client_request", "serve_request", "queue_wait",
            "registry_get", "dispatch"} <= names
    # parent-linked + containment + coverage within tolerance: the PR 4
    # span-sum≈wall discipline, applied to a request
    assert trace.validate_tree(spans, tid) == []
    # every span record passes the events schema
    for s in spans:
        assert events.validate_record(s) == []


def test_untraced_requests_add_zero_records(booster):
    b, X = booster
    server = b.as_server(max_delay_ms=0.5)
    try:
        for i in range(4):
            server.predict(X[i])
    finally:
        server.close()
    assert trace.RECORDER.tail() == []
    assert trace.RECORDER.n_spans == 0


def test_span_jsonl_schema_roundtrip(booster, tmp_path):
    b, X = booster
    out = str(tmp_path / "spans.jsonl")
    trace.RECORDER.configure(out=out)
    server = b.as_server(max_delay_ms=0.5)
    try:
        tid = _traced_submit(server, X[0])
    finally:
        server.close()
        trace.RECORDER.close()
    assert events.validate_file(out) == []
    recs, truncated = events.read_file(out)
    assert not truncated
    assert recs[0]["type"] == "run_header"
    spans = [r for r in recs if r["type"] == "span"]
    assert {s["trace"] for s in spans} == {tid}
    assert trace.validate_tree(spans, tid) == []


def test_registry_readmission_visible_per_request(booster):
    b, X = booster
    server = b.as_server(buckets=(8,), max_delay_ms=0.5)
    try:
        server.predict(X[:4])
        bytes0 = server.registry.entry("default").bytes
        server.registry.hbm_budget_bytes = int(1.5 * bytes0)
        server.add_model("b", b._booster)     # evicts "default"
        assert not server.registry.entry("default").resident
        tid = _traced_submit(server, X[0])    # pays the readmission
    finally:
        server.close()
    spans = trace.RECORDER.spans(tid)
    get_span = next(s for s in spans if s["name"] == "registry_get")
    assert get_span["attrs"].get("readmitted") is True
    assert get_span["attrs"]["build_s"] > 0
    # the nested compile share is its own span under registry_get
    readmit = next(s for s in spans if s["name"] == "registry_readmit")
    assert readmit["parent"] == get_span["span"]
    assert trace.validate_tree(spans, tid) == []


# -- over the wire ------------------------------------------------------
def test_frontend_trace_minting_and_cross_hop_tree(booster):
    from lambdagap_tpu.serve import FrontendClient, ServeFrontend
    b, X = booster
    server = b.as_server(max_delay_ms=0.5)
    fe = ServeFrontend(server).start()
    client = FrontendClient("127.0.0.1", fe.port)
    try:
        # minted at the FrontendClient per serve_trace_sample
        trace.RECORDER.configure(sample=1.0)
        client.predict(X[0])
        trace.RECORDER.configure(sample=0.0)
        # the client's root and the frontend's span are recorded by done
        # callbacks, which run after predict() has been woken: wait for
        # them, not for a fixed time
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and not (
                {"client_request", "frontend"}
                <= {s["name"] for s in trace.RECORDER.spans()}):
            time.sleep(0.01)
        spans = trace.RECORDER.spans()
        tid = spans[0]["trace"]
        names = {s["name"] for s in spans}
        assert {"client_request", "frontend", "serve_request",
                "queue_wait", "dispatch", "encode"} <= names
        assert trace.validate_tree(spans, tid) == []
        root = next(s for s in spans if s["name"] == "client_request")
        assert root["parent"] is None
    finally:
        client.close()
        fe.close()
        server.close()


def test_routed_span_tree_carries_route_hop(booster):
    from lambdagap_tpu.serve import LocalReplica, Router
    b, X = booster
    servers = [b.as_server(max_delay_ms=0.5) for _ in range(2)]
    router = Router([LocalReplica(f"r{i}", s)
                     for i, s in enumerate(servers)], own_replicas=True)
    try:
        tid = _traced_submit(router, X[0])
    finally:
        router.close()
    spans = trace.RECORDER.spans(tid)
    names = {s["name"] for s in spans}
    assert {"client_request", "route", "serve_request", "queue_wait",
            "dispatch"} <= names
    route = next(s for s in spans if s["name"] == "route")
    assert route["attrs"]["replica"] in ("r0", "r1")
    assert route["attrs"]["failovers"] == 0
    assert trace.validate_tree(spans, tid) == []


# -- fleet metric plane -------------------------------------------------
def test_reservoir_state_and_merge_weight_correct():
    a, b = Reservoir(cap=100, seed=1), Reservoir(cap=100, seed=2)
    for v in (1.0, 2.0, 3.0):
        a.add(v)
    for v in (10.0, 20.0):
        b.add(v)
    m = merge_states([a.state(), b.state()])
    assert m.seen == 5
    p = m.percentiles()
    assert p["max"] == 20.0
    assert p["p50"] == 3.0               # 3rd of 5 equally weighted values
    # weights follow seen, not kept: a reservoir that SAW 300 but kept 3
    # outweighs one that saw 2, 100:1 per kept value
    heavy = {"seen": 300, "vals": [1.0, 2.0, 3.0]}
    light = {"seen": 2, "vals": [10.0, 20.0]}
    p = merge_states([heavy, light]).percentiles()
    assert p["p50"] == 2.0 and p["p95"] == 3.0
    # units survive scaling; downsample keeps quantiles
    r = Reservoir(cap=4096, seed=3)
    for i in range(4096):
        r.add(float(i))
    st = r.state(scale=2.0, max_vals=64)
    assert len(st["vals"]) == 64 and st["seen"] == 4096
    assert st["vals"][0] == 0.0 and st["vals"][-1] == 2.0 * 4095


def test_fleet_snapshot_equals_manual_merge(booster):
    from lambdagap_tpu.serve import LocalReplica, Router
    b, X = booster
    servers = [b.as_server(max_delay_ms=0.5) for _ in range(2)]
    router = Router([LocalReplica(f"r{i}", s)
                     for i, s in enumerate(servers)], own_replicas=True)
    try:
        # traffic directly per replica so both have distinct counters
        for i in range(3):
            servers[0].predict(X[i], tenant="acme")
        for i in range(5):
            servers[1].predict(X[i], tenant="zed")
        manual = [s.stats_snapshot(reservoirs=True) for s in servers]
        snap = router.fleet_snapshot()
        merged = snap["merged"]
        # counter sums exact
        for key in ("requests", "rows", "errors", "timeouts", "rejected",
                    "swaps", "evictions", "readmissions"):
            assert merged[key] == sum(m[key] for m in manual), key
        assert merged["requests"] == 8
        assert merged["replica_count"] == 2
        # reservoir quantiles consistent: the fleet plane's quantiles ARE
        # the deterministic merge of the per-replica states
        expect = merge_states(
            [m["reservoirs"]["latency_ms"] for m in manual]).percentiles()
        assert merged["latency_ms"] == expect
        # label-preserving tenant rollup
        assert merged["per_tenant"]["acme"]["requests"] == 3
        assert merged["per_tenant"]["zed"]["requests"] == 5
        # registry rollup counts residency per replica
        models = merged["registry"]["models"]
        assert models["default"]["resident_replicas"] == 2
        assert snap["replicas"] == ["r0", "r1"]
    finally:
        router.close()


def test_prometheus_fleet_verb_single_server(booster):
    import io
    from lambdagap_tpu.serve import serve_loop
    b, X = booster
    server = b.as_server()
    try:
        server.predict(X[0])
        out, stats = io.StringIO(), io.StringIO()
        serve_loop(server, ["prometheus fleet"], out, stats_stream=stats)
        text = stats.getvalue()
    finally:
        server.close()
    assert "lambdagap_fleet_replicas 1" in text
    assert "lambdagap_serve_requests_total 1" in text


# -- prometheus fleet exposition: hostile labels ------------------------
_HEADER = re.compile(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+$")
_SAMPLE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})? (\S+)$")
# exposition-format label values: escaped backslash/quote/newline only
_LABELS = re.compile(
    r'\{([a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\\\|\\"|\\n)*")'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\\\|\\"|\\n)*")*\}')


def test_prometheus_fleet_hostile_labels():
    from lambdagap_tpu.serve.stats import ServeStats
    hostile_model = 'mo"del\\v1\nprod'
    hostile_tenant = 'acme "corp"\\'
    stats = [ServeStats(), ServeStats()]
    for i, st in enumerate(stats):
        st.record_request(0.001, 0.002, 0.004 + i * 0.001, rows=2,
                          model=hostile_model, tenant=hostile_tenant)
        st.record_eviction(model=hostile_model)
    snaps = [st.snapshot(reservoirs=True) for st in stats]
    for snap in snaps:
        snap["registry"] = {"registered_models": 1, "resident_models": 1,
                            "hbm_bytes_resident": 128,
                            "hbm_budget_bytes": 0,
                            "models": {hostile_model: {"resident": True,
                                                       "builds": 1,
                                                       "hbm_bytes": 128}}}
    merged = fleet.merge_snapshots(snaps)
    router_snap = {"failovers": 0, "rejected_no_replica": 0,
                   "replicas": {'r"0\n': {"routed": 2, "inflight": 0,
                                          "health": "ok", "dead": False}}}
    text = prom.render_fleet(merged, router=router_snap)
    for ln in [ln for ln in text.splitlines() if ln]:
        if ln.startswith("#"):
            assert _HEADER.match(ln), f"bad header: {ln!r}"
            continue
        m = _SAMPLE.match(ln)
        assert m, f"unparseable sample: {ln!r}"
        float(m.group(3))
        if m.group(2):
            assert _LABELS.fullmatch(m.group(2)), f"bad labels: {ln!r}"
    # the hostile names render escaped, not raw
    assert 'mo\\"del\\\\v1\\nprod' in text
    assert "\nprod" not in text.replace("\\nprod", "")
    assert merged["per_model"][hostile_model]["requests"] == 2


# -- events durability --------------------------------------------------
def test_validate_file_tolerates_torn_final_line(tmp_path):
    p = tmp_path / "torn.jsonl"
    hdr = json.dumps(events.run_header({}))
    span = json.dumps({"type": "span", "trace": "t" * 16, "span": "s" * 16,
                       "parent": None, "name": "dispatch", "t0": 1.0,
                       "dur": 0.5})
    # SIGKILL mid-write: the final line has no trailing newline
    p.write_text(hdr + "\n" + span + "\n" + span[: len(span) // 2])
    assert events.validate_file(str(p)) == []
    recs, truncated = events.read_file(str(p))
    assert truncated
    assert [r["type"] for r in recs] == ["run_header", "span"]
    # a COMPLETE bad line (newline-terminated) is still an error
    p2 = tmp_path / "bad.jsonl"
    p2.write_text(hdr + "\nnot json\n")
    assert any("not JSON" in e for e in events.validate_file(str(p2)))


# -- flight recorder + postmortem ---------------------------------------
def test_flight_recorder_dump_and_postmortem(tmp_path, booster):
    import importlib.util
    b, X = booster
    dump = str(tmp_path / "proc.flight")
    server = b.as_server(max_delay_ms=0.5)
    fr = trace.FlightRecorder(dump, params={"who": "test"})
    try:
        tid = _traced_submit(server, X[0])
        trace.RECORDER.event("test_marker", detail="before-dump")
        fr.dump(reason="test")
    finally:
        server.close()
    assert events.validate_file(dump) == []
    recs, _trunc = events.read_file(dump)
    assert recs[0]["type"] == "run_header"
    assert recs[0]["params"]["reason"] == "test"
    assert any(r.get("type") == "span" and r.get("trace") == tid
               for r in recs)
    assert any(r.get("event") == "test_marker" for r in recs)
    # the postmortem renderer names the process and its last span
    spec = importlib.util.spec_from_file_location(
        "postmortem", os.path.join(os.path.dirname(__file__), "..",
                                   "tools", "postmortem.py"))
    pm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pm)
    sources = pm.load([dump])
    merged = pm.merge(sources)
    text = pm.render(sources, merged)
    assert "last span of proc.flight" in text
    assert trace.RECORDER.proc in text


def test_postmortem_tolerates_torn_flight_dump(tmp_path, booster):
    """ISSUE 13 satellite: one SIGKILL-torn dump (truncated final JSON,
    even mid-byte-sequence garbage) must NOT abort the merged timeline —
    the intact sources still render, the torn one reports truncation,
    exactly like obs/events.read_file's torn-final-line contract."""
    import importlib.util
    b, X = booster
    good = str(tmp_path / "r1.flight")
    server = b.as_server(max_delay_ms=0.5)
    fr = trace.FlightRecorder(good, params={"who": "survivor"})
    try:
        _traced_submit(server, X[0])
        fr.dump(reason="test")
    finally:
        server.close()
    # tear a copy of the good dump mid-record, then corrupt the tail
    # with bytes that are not valid UTF-8 (a half-recovered disk)
    raw = open(good, "rb").read()
    torn = str(tmp_path / "r0.flight")
    with open(torn, "wb") as f:
        f.write(raw[: int(len(raw) * 0.6)] + b"\xe2\x82")
    spec = importlib.util.spec_from_file_location(
        "postmortem", os.path.join(os.path.dirname(__file__), "..",
                                   "tools", "postmortem.py"))
    pm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pm)
    sources = pm.load([torn, good])      # must not raise
    by_path = {os.path.basename(p): (r, t) for p, r, t in sources}
    assert by_path["r0.flight"][1] is True         # truncation reported
    assert by_path["r1.flight"][1] is False
    merged = pm.merge(sources)
    assert merged                         # the intact source's records
    text = pm.render(sources, merged)
    assert "TRUNCATED" in text
    assert "last span of r1.flight" in text
    # the torn dump's parseable prefix still contributes evidence
    assert any(src == "r0.flight" for _t, src, _r in merged)
    # and main() exits 0 on the same inputs (truncation != failure)
    assert pm.main([torn, good]) == 0


def test_postmortem_skips_structurally_torn_records(tmp_path):
    """Records that parse but lost fields (interior corruption) degrade
    to best-effort rendering, never a KeyError abort."""
    import importlib.util
    import json as _json
    path = str(tmp_path / "weird.flight")
    with open(path, "w") as f:
        f.write(_json.dumps({"type": "run_header", "schema_version": 1,
                             "time_unix": 1.0, "params": "torn"}) + "\n")
        f.write(_json.dumps({"type": "span", "t0": 2.0}) + "\n")
        f.write(_json.dumps({"type": "span", "trace": "t", "span": "s",
                             "name": "dispatch", "t0": "garbage"}) + "\n")
        f.write(_json.dumps({"type": "event", "event": "x",
                             "time_unix": 3.0}) + "\n")
    spec = importlib.util.spec_from_file_location(
        "postmortem", os.path.join(os.path.dirname(__file__), "..",
                                   "tools", "postmortem.py"))
    pm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pm)
    sources = pm.load([path])
    merged = pm.merge(sources)
    # the numeric-t0 span and the event survive; the garbage-t0 one is
    # skipped rather than crashing the sort
    assert [r.get("type") for _t, _s, r in merged] == ["span", "event"]
    text = pm.render(sources, merged)
    assert "dispatch" not in text         # the torn span was dropped
    assert "!x" in text


def test_flight_recorder_periodic_dump(tmp_path):
    dump = str(tmp_path / "tick.flight")
    rec = trace.SpanRecorder(ring=64, proc="ticker")
    fr = trace.FlightRecorder(dump, recorder=rec, interval_s=0.05)
    fr.install()
    try:
        rec.event("heartbeat")
        deadline = time.monotonic() + 30.0      # the ticker's own pace,
        while fr.dumps < 2 and time.monotonic() < deadline:  # not ours
            time.sleep(0.02)
        assert os.path.exists(dump)
        assert fr.dumps >= 2
        assert events.validate_file(dump) == []
    finally:
        fr.close()


# -- signal plane -------------------------------------------------------
def _fake_fleet_snap(t, requests, timeouts=0, rejected=0, evictions=0,
                     readmissions=0, health="ok"):
    return {
        "type": "fleet_snapshot", "time_unix": t,
        "replicas": ["r0"],
        "router": {"replicas": {"r0": {"health": health, "dead": False}}},
        "merged": {"requests": requests, "timeouts": timeouts,
                   "rejected": rejected, "errors": 0,
                   "evictions": evictions, "readmissions": readmissions,
                   "registry": {"registered_models": 2,
                                "resident_models": 1,
                                "hbm_bytes_resident": 100,
                                "hbm_budget_bytes": 200,
                                "models": {"m": {"resident_replicas": 1,
                                                 "replicas": 1,
                                                 "builds": 3,
                                                 "hbm_bytes": 100}}}},
    }


def test_signal_plane_schema_and_knee():
    plane = signals.SignalPlane(alpha=0.5, good_ratio=0.9)
    t = 1000.0
    requests = 0
    # ramp at healthy goodput: the knee should track the offered rate up
    for rate in (100, 100, 200, 200, 400, 400):
        t += 1.0
        requests += rate
        tick = plane.update(_fake_fleet_snap(t, requests))
        assert signals.validate_signals(tick) == []
        assert events.validate_record(tick) == []
    good_knee = tick["goodput"]["knee_rps"]
    assert good_knee > 150
    assert -1e-9 <= tick["goodput"]["knee_margin"] <= 1.0
    # saturation: offered rises but half the requests shed -> the knee
    # stops rising and the margin collapses
    timeouts = 0
    for _ in range(4):
        t += 1.0
        requests += 800
        timeouts += 400
        tick = plane.update(_fake_fleet_snap(t, requests,
                                             timeouts=timeouts))
    assert tick["goodput"]["good_fraction"] < 0.9
    assert tick["goodput"]["knee_margin"] < 0.2
    # residency block carries the per-model placement inputs
    res = tick["residency"]
    assert res["resident_models"] == 1
    assert res["per_model"]["m"]["resident_replicas"] == 1
    # health timeline recorded the steady state once (no flapping noise)
    assert tick["health"]["current"] == {"r0": "ok"}
    assert len(tick["health"]["transitions"]) == 1


def test_health_timeline_records_transitions():
    tl = signals.HealthTimeline(ring=8)
    assert tl.note("r0", "ok", t=1.0)
    assert not tl.note("r0", "ok", t=2.0)       # no transition, no entry
    assert tl.note("r0", "degraded", t=3.0)
    assert tl.note("r0", "dead", t=4.0)
    snap = tl.snapshot()
    assert snap["current"] == {"r0": "dead"}
    assert [e["state"] for e in snap["transitions"]] == \
        ["ok", "degraded", "dead"]


def test_router_signals_via_scraper(booster):
    from lambdagap_tpu.serve import (FleetScraper, LocalReplica, Router,
                                     SignalPlane)
    b, X = booster
    servers = [b.as_server(max_delay_ms=0.5) for _ in range(2)]
    router = Router([LocalReplica(f"r{i}", s)
                     for i, s in enumerate(servers)], own_replicas=True)
    try:
        with pytest.raises(ValueError):
            router.signals()             # no plane attached yet
        scraper = FleetScraper(router, signals=SignalPlane())
        router.attach_scraper(scraper)
        for i in range(3):
            router.predict(X[i], timeout=30)
        scraper.scrape()
        tick = router.signals()
        assert signals.validate_signals(tick) == []
        assert tick["health"]["current"]["r0"] == "ok"
        assert router.fleet_snapshot()["merged"]["requests"] == 3
    finally:
        router.close()
