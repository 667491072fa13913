"""Fleet-shaped serving (ISSUE 9): multi-model registry under an HBM
budget, weighted tenant fairness + admission quotas, the health-aware
replica router with failover, the newline-JSON socket frontend, and the
open-loop load generator.

The acceptance surface: LRU eviction re-admits with exactly ONE recompile
and the generation preserved; a hot tenant cannot starve the others; a
killed replica strands NO accepted future; malformed frontend frames
answer an error and the connection survives; and every fleet path stays
bit-identical to the device predict (the parity test in test_serve.py is
extended with the same guarantee).
"""
import json
import socket
import threading
import time

import numpy as np
import pytest
from sklearn.datasets import make_classification

import lambdagap_tpu as lgb
from lambdagap_tpu.serve import (FairQueue, ForestServer, FrontendClient,
                                 LocalReplica, RemoteReplica,
                                 ReplicaUnavailable, Request, Router,
                                 ServeFrontend, ServeOverloaded,
                                 arrival_times, run_open_loop)
from lambdagap_tpu.serve.batcher import MicroBatcher

DEVICE_PARAMS = {"verbose": -1, "tpu_fast_predict_rows": 0}


def _train(rows=1200, feats=10, rounds=8, leaves=15, seed=0, **extra):
    X, y = make_classification(rows, feats, n_informative=6,
                               random_state=seed)
    X = X.astype(np.float32)
    params = {"objective": "binary", "num_leaves": leaves, **DEVICE_PARAMS,
              **extra}
    return lgb.train(params, lgb.Dataset(X, label=y),
                     num_boost_round=rounds), X


# -- registry: LRU eviction + re-admission ------------------------------
def test_registry_lru_eviction_and_readmission_under_budget():
    b, X = _train()
    b2, _ = _train(rounds=5, leaves=7, seed=3)
    ref = b.predict(X[:600])
    ref2 = b2.predict(X[:600])
    s = ForestServer(b, buckets=(8, 64))
    try:
        default_bytes = s.registry.entry("default").bytes
        assert default_bytes > 0
        # budget fits ~one forest: admitting m2 must evict default (LRU)
        s.registry.hbm_budget_bytes = default_bytes + 128
        s.add_model("m2", b2._booster)
        snap = s.registry.snapshot()
        assert snap["models"]["default"]["resident"] is False
        assert snap["models"]["m2"]["resident"] is True
        assert snap["hbm_bytes_resident"] <= s.registry.hbm_budget_bytes

        # touching the evicted model re-admits it (ONE recompile, the
        # generation preserved) and evicts the other side
        got = s.predict(X[:64])
        assert np.array_equal(got, ref[:64])
        entry = s.registry.entry("default")
        assert entry.generation == 0              # generation preserved
        assert entry.builds == 2                  # install + exactly 1 readmit
        stats = s.stats_snapshot()
        assert stats["evictions"] == 2            # default, then m2
        assert stats["readmissions"] == 1
        assert stats["registry"]["models"]["m2"]["resident"] is False

        # the ping-ponged model still serves bit-identically
        got2 = s.predict(X[:64], model="m2")
        assert np.array_equal(got2, ref2[:64])
        assert s.stats_snapshot()["readmissions"] == 2
    finally:
        s.close()


def test_registry_concurrent_readmission_single_flight():
    """Eight threads hitting an evicted model concurrently must trigger
    exactly ONE recompile (single-flight), not eight."""
    b, X = _train()
    b2, _ = _train(rounds=4, leaves=7, seed=5)
    ref = b.predict(X[:600])
    s = ForestServer(b, buckets=(8,))
    try:
        s.registry.hbm_budget_bytes = s.registry.entry("default").bytes + 128
        s.add_model("m2", b2._booster)            # evicts default
        assert not s.registry.entry("default").resident
        outs, errs = [None] * 8, []

        def hit(i):
            try:
                outs[i] = s.predict(X[8 * i:8 * i + 8])
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=hit, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not errs
        for i in range(8):
            assert np.array_equal(outs[i], ref[8 * i:8 * i + 8])
        assert s.registry.entry("default").builds == 2
        assert s.stats_snapshot()["readmissions"] == 1
    finally:
        s.close()


def test_registry_swap_non_default_model_and_unknown_model_errors():
    b, X = _train()
    b2, _ = _train(rounds=4, leaves=7, seed=7)
    ref2 = b2.predict(X[:600])
    s = ForestServer(b, buckets=(8,))
    try:
        with pytest.raises(ValueError, match="unknown serve model"):
            s.submit(X[:4], model="nope")
        s.add_model("m2", b._booster)
        gen = s.swap(b2._booster, model="m2")
        assert gen == 1
        got = s.predict(X[:8], model="m2")
        assert np.array_equal(got, ref2[:8])
        assert s.generation == 0                  # default untouched
        with pytest.raises(ValueError, match="already registered"):
            s.add_model("m2", b._booster)
    finally:
        s.close()


# -- tenant fairness + admission -----------------------------------------
def test_fair_queue_weighted_interleave_under_flood():
    x = np.zeros((1, 2), np.float32)
    q = FairQueue(maxsize=0)
    for _ in range(100):
        q.try_put(Request(x, tenant="hog"))
    for _ in range(10):
        q.try_put(Request(x, tenant="mouse"))
    order = [q.get_nowait().tenant for _ in range(110)]
    mouse_pos = [i for i, t in enumerate(order) if t == "mouse"]
    # equal weights: the flooded lane cannot push the mouse to the back —
    # its 10 requests all clear within the first ~21 dequeues (FIFO would
    # start them at 100)
    assert max(mouse_pos) <= 21, mouse_pos


def test_fair_queue_respects_weights():
    x = np.zeros((1, 2), np.float32)
    q = FairQueue(maxsize=0, weights={"gold": 3.0})
    for _ in range(90):
        q.try_put(Request(x, tenant="gold"))
        q.try_put(Request(x, tenant="base"))
    first = [q.get_nowait().tenant for _ in range(40)]
    gold = first.count("gold")
    # 3:1 weights -> ~30 of the first 40 dequeues are gold
    assert 26 <= gold <= 34, first


def test_tenant_admission_quota_rejects_hot_tenant_only():
    x = np.zeros((1, 2), np.float32)
    q = FairQueue(maxsize=10, max_share=0.5)
    for _ in range(5):
        assert q.try_put(Request(x, tenant="hot")) == "ok"
    assert q.try_put(Request(x, tenant="hot")) == "quota"
    for _ in range(5):                            # others still admitted
        assert q.try_put(Request(x, tenant="cold")) == "ok"
    assert q.try_put(Request(x, tenant="cold")) == "full"


def test_batcher_fairness_under_hot_tenant_flood():
    """Integration: a hot tenant floods a bounded batcher; the quiet
    tenant's requests are neither starved (fair dequeue) nor rejected
    (admission quota bounds the hog, not the fleet)."""
    served = []
    gate = threading.Event()

    def run(batch):
        gate.wait(10)
        time.sleep(0.001)
        for r in batch:
            served.append(r.tenant)
            r.future.set_result(0.0)

    mb = MicroBatcher(run, max_batch=1, max_delay_ms=0.0, workers=1,
                      max_queue=64, tenant_max_share=0.75)
    x = np.zeros((1, 2), np.float32)
    hog_futs, hog_rejected = [], 0
    for _ in range(60):
        try:
            hog_futs.append(mb.submit(x, tenant="hog"))
        except ServeOverloaded:
            hog_rejected += 1
    mouse_futs = [mb.submit(x, tenant="mouse") for _ in range(6)]
    gate.set()
    for f in mouse_futs + hog_futs:
        f.result(timeout=30)
    mb.close()
    assert hog_rejected > 0                       # quota charged the hog
    mouse_pos = [i for i, t in enumerate(served) if t == "mouse"]
    # fair dequeue: all mouse requests served within the first ~2x their
    # count + the hog's head start, nowhere near the flood's tail
    assert max(mouse_pos) <= 20, mouse_pos
    snap = mb.stats.snapshot() if mb.stats else None
    assert snap is None                           # raw batcher: no stats


def test_server_per_tenant_stats_and_prometheus_labels():
    b, X = _train()
    with b.as_server(buckets=(8,)) as s:
        s.predict(X[:8], tenant="acme")
        s.predict(X[:8], tenant="acme")
        s.predict(X[:8], tenant="zeta")
        snap = s.stats_snapshot()
        text = s.prometheus()
    assert snap["per_tenant"]["acme"]["requests"] == 2
    assert snap["per_tenant"]["zeta"]["rows"] == 8
    assert snap["per_model"]["default"]["requests"] == 3
    assert "p99" in snap["per_tenant"]["acme"]["latency_ms"]
    assert 'lambdagap_serve_tenant_requests_total{tenant="acme"} 2' in text
    assert 'lambdagap_serve_model_requests_total{model="default"} 3' in text
    assert 'lambdagap_serve_registry_model_resident{model="default"} 1' \
        in text


# -- router ---------------------------------------------------------------
def test_router_prefers_ok_over_degraded_and_skips_draining():
    b, X = _train()
    ref = b.predict(X[:600])
    s1, s2, s3 = (ForestServer(b, buckets=(8,)) for _ in range(3))
    r = Router([LocalReplica("a", s1), LocalReplica("b", s2),
                LocalReplica("c", s3)])
    try:
        s2.health.note_error()                    # b: degraded
        s3.close()                                # c: draining
        for i in range(6):
            got = r.predict(X[i:i + 1], timeout=30)
            assert np.array_equal(got, ref[i:i + 1])
        snap = r.snapshot()
        assert snap["replicas"]["a"]["routed"] == 6
        assert snap["replicas"]["b"]["routed"] == 0
        assert snap["replicas"]["c"]["routed"] == 0
        # no ok replica left: degraded serves rather than rejecting
        s1.close()
        got = r.predict(X[:1], timeout=30)
        assert np.array_equal(got, ref[:1])
        assert r.snapshot()["replicas"]["b"]["routed"] == 1
    finally:
        for s in (s1, s2, s3):
            s.close()
        r.close()


def test_router_kill_replica_mid_load_strands_nothing(tmp_path):
    """The R8 acceptance at fleet level: SIGKILL-equivalent death of a
    remote replica (socket torn mid-flight) must fail over or fail every
    accepted request — zero hangs — and the fleet keeps serving."""
    b, X = _train()
    ref = b.predict(X[:600])
    victim = ForestServer(b, buckets=(1, 8, 64), max_delay_ms=5.0)
    survivor = ForestServer(b, buckets=(1, 8, 64))
    fe = ServeFrontend(victim).start()
    r = Router([RemoteReplica("victim", "127.0.0.1", fe.port),
                LocalReplica("survivor", survivor)])
    try:
        futs = [r.submit(X[i % 600][None, :]) for i in range(50)]
        fe.close()                                # the kill, mid-load
        victim.close()
        results = [f.result(timeout=30) for f in futs]   # NOTHING hangs
        for i, res in enumerate(results):
            assert np.array_equal(res.values, ref[i % 600:i % 600 + 1])
        # post-kill requests route to the survivor
        got = r.predict(X[:8], timeout=30)
        assert np.array_equal(got, ref[:8])
        snap = r.snapshot()
        assert snap["replicas"]["victim"]["dead"] is True
        assert snap["replicas"]["survivor"]["routed"] >= 1
        assert snap["replicas"]["victim"]["inflight"] == 0
    finally:
        survivor.close()
        r.close()


def test_router_rejects_when_no_replica_lives():
    b, X = _train()
    s = ForestServer(b, buckets=(8,))
    r = Router([LocalReplica("only", s)])
    s.close()
    with pytest.raises(ReplicaUnavailable, match="no live replica"):
        r.submit(X[:1]).result(timeout=10)
    assert r.snapshot()["rejected_no_replica"] == 1
    r.close()


def test_router_fleet_surface_swap_stats_health(tmp_path):
    b, X = _train()
    b2, _ = _train(rounds=5, leaves=7, seed=9)
    ref2 = b2.predict(X[:600])
    path = str(tmp_path / "v2.txt")
    b2.save_model(path)
    s1, s2 = ForestServer(b, buckets=(8,)), ForestServer(b, buckets=(8,))
    r = Router([LocalReplica("a", s1), LocalReplica("b", s2)],
               own_replicas=True)
    try:
        assert r.health.state() == "ok"
        assert r.models() == ["default"]
        gen = r.swap(path)                        # fleet-wide rollout
        assert gen == 1
        for s in (s1, s2):
            assert s.generation == 1
        got = r.predict(X[:8], timeout=30)
        assert np.array_equal(got, ref2[:8])
        snap = r.stats_snapshot()
        assert set(snap["replicas"]) == {"a", "b"}
        assert snap["router"]["failovers"] == 0
        prom = r.prometheus()
        assert 'lambdagap_router_replica_health{replica="a",state="ok"} 1' \
            in prom
    finally:
        r.close()


# -- frontend wire protocol ----------------------------------------------
def test_frontend_roundtrip_predict_swap_stats_models(tmp_path):
    b, X = _train()
    b2, _ = _train(rounds=5, leaves=7, seed=11)
    ref = b.predict(X[:600])
    ref2 = b2.predict(X[:600])
    path = str(tmp_path / "v2.txt")
    b2.save_model(path)
    with ForestServer(b, buckets=(1, 8, 64)) as s, ServeFrontend(s) as fe:
        with FrontendClient("127.0.0.1", fe.port) as c:
            got = c.predict(X[:37])
            assert np.array_equal(got, np.asarray(ref[:37], np.float32))
            assert c.health() == "ok"
            assert c.models() == ["default"]
            st = c.stats()
            assert st["requests"] == 1
            assert "lambdagap_serve_requests_total" in c.prometheus()
            gen = c.swap(path)
            assert gen == 1
            got2 = c.predict(X[:8])
            assert np.array_equal(got2, np.asarray(ref2[:8], np.float32))


def test_frontend_malformed_frames_answer_errors_and_survive():
    b, X = _train()
    with ForestServer(b, buckets=(8,)) as s, ServeFrontend(s) as fe:
        sock = socket.create_connection(("127.0.0.1", fe.port), timeout=10)
        f = sock.makefile("rwb")

        def call(payload: bytes) -> dict:
            f.write(payload + b"\n")
            f.flush()
            return json.loads(f.readline())

        r = call(b"this is not json")
        assert r["ok"] is False and "malformed" in r["error"]
        r = call(b'{"op": "conjure", "id": 1}')
        assert r["ok"] is False and r["id"] == 1
        assert "unknown op" in r["error"]
        r = call(b'{"op": "predict", "id": 2}')   # no x
        assert r["ok"] is False and r["id"] == 2
        r = call(b'{"op": "predict", "id": 3, "x": "wat"}')
        assert r["ok"] is False and r["id"] == 3
        r = call(b'{"op": "predict", "id": 4, "x": [[0.5, 0.5]], '
                 b'"model": "ghost"}')
        assert r["ok"] is False and r["kind"] == "ValueError"
        # the connection survived all of it: a real request still serves
        row = json.dumps({"op": "predict", "id": 5,
                          "x": X[:1].tolist()}).encode()
        r = call(row)
        assert r["ok"] is True and r["id"] == 5
        assert r["generation"] == 0
        sock.close()


def test_frontend_client_dead_socket_resolves_pending():
    b, X = _train()
    s = ForestServer(b, buckets=(8,), max_delay_ms=50.0)
    fe = ServeFrontend(s).start()
    c = FrontendClient("127.0.0.1", fe.port)
    futs = [c.submit(X[i][None, :]) for i in range(4)]
    fe.close()                                    # socket dies under them
    for fut in futs:
        try:
            fut.result(timeout=10)                # value (already served)…
        except (ReplicaUnavailable, ConnectionError):
            pass                                  # …or the transport error
    # all four may have been served before the close: the client knows the
    # socket is dead once its reader has seen the end of the stream, which
    # is its own thread's business, not a matter of time
    c._reader.join(timeout=30)
    assert not c._reader.is_alive() and not c.alive
    with pytest.raises(ReplicaUnavailable):
        c.submit(X[:1])
    c.close()
    s.close()


def test_serve_loop_model_routing_and_health_verbs():
    import io
    from lambdagap_tpu.serve import serve_loop
    b, X = _train()
    b2, _ = _train(rounds=4, leaves=7, seed=13)
    ref = b.predict(X[:600])
    ref2 = b2.predict(X[:600])
    s = ForestServer(b, buckets=(1, 8))
    s.add_model("b", b2._booster)
    lines = ["\t".join(f"{v:.8g}" for v in X[0]),
             "model=b",
             "\t".join(f"{v:.8g}" for v in X[0]),
             "health",
             "model=",
             "\t".join(f"{v:.8g}" for v in X[0])]
    out, stats = io.StringIO(), io.StringIO()
    try:
        n = serve_loop(s, lines, out, stats_stream=stats)
    finally:
        s.close()
    assert n == 3
    rows = [float(ln) for ln in out.getvalue().splitlines()]
    # row 1 default, row 2 model b, row 3 default again — and the text
    # round-trip of the INPUT row costs precision, so compare against a
    # predict of the same parsed row, not the original matrix
    x_rt = np.array([[float(f"{v:.8g}") for v in X[0]]], np.float32)
    assert rows[0] == float(f"{b.predict(x_rt)[0]:.10g}") or np.isclose(
        rows[0], ref[0], rtol=1e-5)
    assert np.isclose(rows[1], ref2[0], rtol=1e-4)
    assert np.isclose(rows[2], rows[0])
    assert stats.getvalue().strip() == "ok"


# -- open-loop load generator --------------------------------------------
def test_arrival_times_deterministic_and_seeded():
    u = arrival_times(100.0, 5, kind="uniform")
    np.testing.assert_allclose(u, [0.01, 0.02, 0.03, 0.04, 0.05])
    p1 = arrival_times(100.0, 50, kind="poisson", seed=7)
    p2 = arrival_times(100.0, 50, kind="poisson", seed=7)
    np.testing.assert_array_equal(p1, p2)
    assert not np.array_equal(p1, arrival_times(100.0, 50, kind="poisson",
                                                seed=8))
    with pytest.raises(ValueError, match="unknown arrival"):
        arrival_times(10.0, 5, kind="exact")


def test_open_loop_goodput_counts_separate_from_throughput():
    """A submit that always succeeds but answers LATE must count toward
    throughput and not goodput — the two-number honesty the closed-loop
    bench could not express."""
    from concurrent.futures import Future

    def slow_submit(x, model=None, tenant=None):
        fut = Future()

        def later():
            time.sleep(0.05)                      # 50 ms > 10 ms deadline
            fut.set_result(type("R", (), {"values": np.zeros(1)})())
        threading.Thread(target=later, daemon=True).start()
        return fut

    X = np.zeros((4, 3), np.float32)
    res = run_open_loop(slow_submit, X, rate_rps=200.0, n_requests=30,
                        deadline_ms=10.0, arrival="uniform", seed=1)
    assert res["counts"]["ok"] == 30
    assert res["counts"]["good"] == 0
    assert res["counts"]["late"] == 30
    assert res["goodput_rps"] == 0.0
    assert res["throughput_rps"] > 0.0


def test_open_loop_against_live_server_tenant_breakdown():
    b, X = _train()
    with b.as_server(buckets=(1, 8, 64), max_delay_ms=1.0) as s:
        res = run_open_loop(s.submit, X, rate_rps=400.0, n_requests=160,
                            deadline_ms=250.0,
                            tenants={"gold": 3.0, "base": 1.0}, seed=5)
    c = res["counts"]
    assert c["ok"] == 160 and c["rejected"] == 0
    offered = {t: d["offered"] for t, d in res["per_tenant"].items()}
    assert offered["gold"] + offered["base"] == 160
    assert offered["gold"] > offered["base"] * 2   # seeded 3:1 mix
    assert res["per_tenant"]["gold"]["latency_ms"]["p99"] > 0
