"""obs/costplane.py (graftmeter): the analytic FLOP/byte/HBM ledger.

The ISSUE-19 acceptance surface: jit entry points produce ledger entries
with nonzero bytes-accessed and peak-HBM, measured walls join into
per-phase fraction-of-roofline, the disarmed path records nothing, and
the COSTS.json document round-trips with the documented schema.
"""
import json

import numpy as np
import pytest

import lambdagap_tpu as lgb
from lambdagap_tpu.obs import prom
from lambdagap_tpu.obs.costplane import PLANE, CostPlane, SCHEMA_VERSION


def _data(n=500, d=10, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    y = (X[:, 0] + 0.2 * rng.randn(n) > 0).astype(np.float32)
    return X, y


def _train(extra=None, n=500, rounds=4):
    X, y = _data(n)
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              **(extra or {})}
    return lgb.train(params, lgb.Dataset(X, label=y),
                     num_boost_round=rounds)


@pytest.fixture(autouse=True)
def _fresh_plane():
    # PLANE is the process-global singleton: isolate every test
    PLANE.reset()
    yield
    PLANE.reset()
    PLANE.enabled = False
    PLANE.out_path = ""
    PLANE._peaks_override = ""


def _arm(plane=PLANE, **over):
    plane.enabled = True
    for k, v in over.items():
        setattr(plane, k, v)


# -- capture on real programs -------------------------------------------
def test_serial_train_populates_ledger_and_walls():
    b = _train({"cost_plane": True, "telemetry": True})
    programs = {e["program"] for e in PLANE.entries.values()}
    for p in ("train.serial.histogram", "train.serial.split",
              "train.serial.partition"):
        assert p in programs, programs
    for e in PLANE.entries.values():
        assert e["bytes_accessed"] > 0, e
        assert e["peak_hbm_bytes"] > 0, e
        assert e["memory_source"] in ("compiled", "analytic")
    # telemetry close() joined the per-phase walls into the plane
    attr = PLANE.attribution()
    assert any("wall_s" in rec for rec in attr["phases"].values()), attr
    assert b.predict(_data(50)[0]).shape == (50,)


def test_device_predict_captures_engine_and_wall():
    b = _train({"cost_plane": True, "tpu_fast_predict_rows": 0})
    X, _ = _data(1200)
    out = b.predict(X)
    assert out.shape == (1200,)
    predict_entries = [e for e in PLANE.entries.values()
                       if e["program"].startswith("predict.")]
    assert predict_entries, PLANE.entries.keys()
    assert all(e["bytes_accessed"] > 0 and e["peak_hbm_bytes"] > 0
               for e in predict_entries)
    assert PLANE.walls.get("predict", {}).get("seconds", 0.0) > 0


def test_observed_call_counts_and_captures_once():
    import jax
    import jax.numpy as jnp
    _arm()
    fn = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((32, 16), jnp.float32)
    b = jnp.ones((16, 8), jnp.float32)
    for _ in range(3):
        out = PLANE.observed_call("test.matmul", fn, (a, b), bucket=32,
                                  phase="test")
    assert out.shape == (32, 8)
    assert PLANE.calls["test.matmul|32"] == 3
    assert list(PLANE.entries) == ["test.matmul|32"]  # captured once
    e = PLANE.entries["test.matmul|32"]
    assert e["flops"] > 0 and e["bytes_accessed"] > 0
    assert e["peak_hbm_bytes"] >= e["arg_bytes"] + e["out_bytes"]
    assert e["arithmetic_intensity"] > 0
    # a second padding bucket is a distinct executable
    PLANE.observed_call("test.matmul", fn,
                        (jnp.ones((64, 16)), jnp.ones((16, 8))), bucket=64)
    assert "test.matmul|64" in PLANE.entries


def test_capture_failure_is_swallowed_and_not_retried():
    _arm()
    calls = []

    def plain(x):  # not jitted: .trace is missing, capture must fail soft
        calls.append(x)
        return x * 2

    assert PLANE.observed_call("test.plain", plain, (21,)) == 42
    assert PLANE.observed_call("test.plain", plain, (21,)) == 42
    assert calls == [21, 21]                 # dispatch untouched
    assert PLANE.entries == {}
    assert "test.plain|" in PLANE._attempted  # failed capture never retried


# -- disarmed path ------------------------------------------------------
def test_disarmed_plane_records_nothing():
    assert not PLANE.enabled
    assert PLANE.observed_call("x", lambda: 7, ()) == 7
    PLANE.record_host("x", flops=1, bytes_accessed=1, peak_hbm_bytes=1)
    PLANE.note_wall("x", 1.0)
    with PLANE.wall("x"):
        pass
    assert PLANE.entries == {} and PLANE.calls == {} and PLANE.walls == {}
    b = _train()                             # cost_plane defaults off
    assert PLANE.entries == {} and not PLANE.enabled
    assert b.predict(_data(50)[0]).shape == (50,)


# -- peaks --------------------------------------------------------------
def test_peaks_override_and_fallback():
    _arm(_peaks_override="197e12:819e9:17e9")
    p = PLANE.peaks()
    assert (p["name"], p["flops"], p["bandwidth"], p["hbm"]) == \
        ("override", 197e12, 819e9, 17e9)
    _arm(_peaks_override="not:numbers:here")
    p = PLANE.peaks()                        # bad spec falls back to table
    assert p["name"] != "override" and p["flops"] > 0
    _arm(_peaks_override="")
    p = PLANE.peaks()                        # CPU container row, unmeasured
    assert p["name"] == "cpu-container" and p["measured"] is False


def test_unknown_device_kind_gets_no_peaks(monkeypatch):
    """A device the table does not name must not borrow another device's
    peaks: no roofline numbers, and the document says why."""
    class _Dev:
        platform, device_kind = "tpu", "TPU v9 hypothetical"
    monkeypatch.setattr("jax.devices", lambda *a: [_Dev()])
    _arm()
    p = PLANE.peaks()
    assert p["name"] == "unknown" and p["flops"] is None
    assert p["device_kind"] == "TPU v9 hypothetical"
    PLANE.entries["p|1"] = {"program": "p", "bucket": "1", "phase": "ph",
                            "flops": 2e9, "bytes_accessed": 1e9,
                            "peak_hbm_bytes": 10}
    PLANE.calls["p|1"] = 2
    PLANE.note_wall("ph", 8.0)
    attr = PLANE.attribution()
    assert "TPU v9 hypothetical" in attr["roofline"]
    rec = attr["phases"]["ph"]
    assert rec["flops_total"] == 4e9 and rec["wall_s"] == 8.0
    assert not {"roofline_s", "bound", "fraction_of_roofline"} & set(rec)
    from lambdagap_tpu.obs import prom
    monkeypatch.setattr("jax.device_count", lambda: 1)
    text = prom.render_costplane()           # exposition survives it
    assert "phase_roofline_fraction{" not in text
    assert "phase_wall_seconds" in text


# -- attribution math ---------------------------------------------------
def test_attribution_roofline_join():
    _arm(_peaks_override="1e9:1e9:1e9")
    PLANE.entries["p|1"] = {"program": "p", "bucket": "1", "phase": "ph",
                            "flops": 2e9, "bytes_accessed": 1e9,
                            "peak_hbm_bytes": 10}
    PLANE.calls["p|1"] = 2
    PLANE.note_wall("ph", 8.0)
    rec = PLANE.attribution()["phases"]["ph"]
    # 2 calls x 2e9 flops / 1e9 flop/s = 4s; 2 x 1e9 B / 1e9 B/s = 2s
    assert rec["bound"] == "flop"
    assert rec["roofline_s"] == pytest.approx(4.0)
    assert rec["wall_s"] == pytest.approx(8.0)
    assert rec["fraction_of_roofline"] == pytest.approx(0.5)
    assert rec["calls"] == 2


def test_wall_span_bracket():
    _arm()
    with PLANE.wall("w"):
        pass
    assert PLANE.walls["w"]["calls"] == 1
    assert PLANE.walls["w"]["seconds"] >= 0
    with pytest.raises(RuntimeError):
        with PLANE.wall("err"):
            raise RuntimeError("boom")
    assert "err" not in PLANE.walls          # failed bracket not noted


# -- host entries / export ----------------------------------------------
def test_record_host_entry():
    _arm()
    PLANE.record_host("predict.shap", flops=1e6, bytes_accessed=2e6,
                      peak_hbm_bytes=3_000_000, phase="predict_shap",
                      bucket=100)
    PLANE.record_host("predict.shap", flops=9e9, bytes_accessed=9e9,
                      peak_hbm_bytes=9, bucket=100)  # first write wins
    e = PLANE.entries["predict.shap|100"]
    assert e["memory_source"] == "host_analytic"
    assert e["flops"] == 1e6 and e["peak_hbm_bytes"] == 3_000_000
    assert PLANE.calls["predict.shap|100"] == 2


def test_to_json_schema_and_write(tmp_path):
    _arm(out_path=str(tmp_path / "COSTS.json"))
    PLANE.record_host("p", flops=1.0, bytes_accessed=2.0, peak_hbm_bytes=3,
                      phase="ph", bucket=4)
    PLANE.note_wall("ph", 0.5, calls=2)
    doc = json.loads((tmp_path / "COSTS.json").read_text()) \
        if PLANE.write() else None
    assert doc is not None
    assert doc["schema_version"] == SCHEMA_VERSION
    for k in ("backend", "device_kind", "num_devices", "peaks", "entries",
              "walls", "attribution"):
        assert k in doc, k
    assert doc["entries"]["p|4"]["calls"] == 1
    assert doc["walls"]["ph"] == {"seconds": 0.5, "calls": 2}
    assert "ph" in doc["attribution"]["phases"]


def test_by_program_maxima_over_buckets():
    _arm()
    PLANE.record_host("p", flops=5.0, bytes_accessed=100.0,
                      peak_hbm_bytes=10, bucket=1)
    PLANE.record_host("p", flops=1.0, bytes_accessed=300.0,
                      peak_hbm_bytes=7, bucket=2)
    PLANE.record_host("q", flops=2.0, bytes_accessed=50.0,
                      peak_hbm_bytes=99, bucket=1)
    byp = PLANE.by_program()
    assert byp["p"] == {"bytes_accessed": 300.0, "peak_hbm_bytes": 10.0,
                        "flops": 5.0, "calls": 2}
    assert byp["q"]["peak_hbm_bytes"] == 99.0


def test_train_traffic_per_iteration():
    _arm()
    assert PLANE.train_traffic(10) is None   # empty ledger
    PLANE.record_host("t", flops=40.0, bytes_accessed=80.0,
                      peak_hbm_bytes=1, phase="histogram", bucket=1)
    PLANE.record_host("u", flops=10.0, bytes_accessed=20.0,
                      peak_hbm_bytes=1, phase="predict", bucket=1)  # not train
    t = PLANE.train_traffic(4)
    assert t == {"programs": 1, "bytes_per_iter": 20.0,
                 "flops_per_iter": 10.0}
    assert PLANE.train_traffic(0) is None


# -- prom exposition ----------------------------------------------------
def test_prom_render_costplane():
    assert prom.render_costplane() == ""     # disarmed -> empty
    _arm()
    PLANE.record_host("p.x", flops=1e6, bytes_accessed=2e6,
                      peak_hbm_bytes=3_000_000, phase="ph", bucket=128)
    PLANE.note_wall("ph", 0.25)
    text = prom.render_costplane()
    for metric in ("lambdagap_cost_program_flops",
                   "lambdagap_cost_program_bytes_accessed",
                   "lambdagap_cost_program_peak_hbm_bytes",
                   "lambdagap_cost_program_calls_total",
                   "lambdagap_cost_phase_roofline_seconds",
                   "lambdagap_cost_phase_wall_seconds"):
        assert metric in text, metric
    assert 'program="p.x"' in text and 'bucket="128"' in text


def test_configure_arms_without_clearing():
    cfg_on = type("C", (), {"cost_plane": True, "cost_plane_out": "",
                            "cost_plane_memory": "analytic",
                            "cost_plane_peaks": ""})()
    plane = CostPlane()
    plane.configure(cfg_on)
    assert plane.enabled and plane.memory_mode == "analytic"
    plane.record_host("p", flops=1, bytes_accessed=1, peak_hbm_bytes=1)
    plane.configure(cfg_on)                  # reconfigure keeps the ledger
    assert "p|" in plane.entries
    cfg_off = type("C", (), {"cost_plane": False, "cost_plane_out": "",
                             "cost_plane_memory": "compiled",
                             "cost_plane_peaks": ""})()
    plane.configure(cfg_off)
    assert not plane.enabled
