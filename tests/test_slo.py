"""SLO engine, time-series retention, and the unified dashboard.

Three layers, mirroring how the plane is built:

- Unit: burn-rate math, breach -> recover hysteresis, and the
  bounded-memory contract of TimeSeriesStore, all on hand-fed samples
  with explicit wall clocks (no sleeps, no threads).
- Integration: a real ReplicaGroup under an injected ``latency:``
  fault clause drives the full loop — breach with auto-triage
  (offending series + correlated timeline events), recovery with a
  measured MTTR, the episode visible to mttr_report and the
  ``raydp_slo_*`` Prometheus families.
- Surface: the ``/debug/dashboard`` route and client-mode
  ``dashboard_report()`` parity (a remote driver sees the same
  document shape the in-process driver builds).
"""
import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from raydp_tpu.telemetry import events as events_mod
from raydp_tpu.telemetry import dashboard as dash_mod
from raydp_tpu.telemetry import render_prometheus, serve_prometheus
from raydp_tpu.telemetry.slo import (
    Objective,
    SloConfig,
    SloEngine,
    default_objectives,
)
from raydp_tpu.telemetry.timeseries import (
    TimeSeriesConfig,
    TimeSeriesSampler,
    TimeSeriesStore,
    flatten_view,
)
from raydp_tpu.utils.profiling import metrics


@pytest.fixture(autouse=True)
def _fresh_metrics():
    metrics.reset()
    yield
    metrics.reset()


T = 1_000_000.0  # arbitrary wall-clock origin for hand-fed samples


def _store(capacity=128, max_series=64):
    return TimeSeriesStore(
        TimeSeriesConfig(
            interval_s=0.1, capacity=capacity, max_series=max_series
        )
    )


# ---------------------------------------------------------------------
# TimeSeriesStore: bounded memory, windows, kill switch
# ---------------------------------------------------------------------


def test_ring_capacity_bounds_samples():
    store = _store(capacity=8, max_series=16)
    for i in range(100):
        store.record("a", float(i), wall=T + i)
    st = store.stats()
    assert st["samples"] == 8
    assert store.last("a") == 99.0
    # the window holds only the retained tail
    vals = [v for _, v in store.window("a", 1000.0, now=T + 100)]
    assert vals == [float(i) for i in range(92, 100)]


def test_series_cap_sheds_cardinality_not_history():
    store = _store(capacity=8, max_series=16)
    store.record("a", 1.0, wall=T)
    for i in range(20):
        store.record(f"s{i}", 1.0, wall=T)
    st = store.stats()
    assert st["series"] == 16
    assert st["dropped_series"] == 5
    # new series are rejected ...
    assert store.record("another", 1.0, wall=T) is False
    # ... but existing series keep updating
    assert store.record("a", 42.0, wall=T + 1) is True
    assert store.last("a") == 42.0
    st = store.stats()
    assert st["memory_bytes_est"] == st["samples"] * 120 + 16 * 300


def test_windowed_queries():
    store = _store()
    for i in range(10):
        store.record("c", float(i * 10), wall=T + i)  # cumulative
        store.record("v", float(i + 1), wall=T + i)
    now = T + 9
    assert store.rate("c", 100.0, now=now) == pytest.approx(10.0)
    assert store.avg("v", 100.0, now=now) == pytest.approx(5.5)
    assert store.max_value("v", 100.0, now=now) == 10.0
    assert store.percentile("v", 1.0, 100.0, now=now) == 10.0
    # trailing-window cutoff: only the last 3 samples
    assert store.avg("v", 2.5, now=now) == pytest.approx(9.0)
    # counter reset clamps to quiescent, never negative
    store.record("c", 0.0, wall=T + 10)
    assert store.rate("c", 100.0, now=T + 10) == 0.0
    # matching: exact and prefix
    assert store.matching("v") == ["v"]
    assert store.matching("nope") == []
    store.record("wr/1", 1.0, wall=T)
    store.record("wr/2", 1.0, wall=T)
    assert store.matching("wr/*") == ["wr/1", "wr/2"]


def test_flatten_view_merges_aggregate_and_driver():
    timer = {
        "count": 2, "total_s": 1.0, "mean_s": 0.5,
        "p50_s": 0.4, "p90_s": 0.5, "p99_s": 0.5,
    }
    timer_drv = {
        "count": 1, "total_s": 0.9, "mean_s": 0.9,
        "p50_s": 0.9, "p90_s": 0.9, "p99_s": 0.9,
    }
    view = {
        "workers": {},
        "aggregate": {
            "counters": {"c": 2.0},
            "gauges": {"g": 1.0},
            "timer/t": timer,
            "meter/m": {"total": 10.0, "per_sec": 5.0},
        },
        "driver": {
            "counters": {"c": 3.0},
            "gauges": {"g": 4.0},
            "timer/t": timer_drv,
            "meter/m": {"total": 2.0, "per_sec": 1.0},
        },
    }
    flat = flatten_view(view)
    assert flat["c"] == 5.0                      # counters sum
    assert flat["g"] == 5.0                      # gauges sum
    assert flat["t/p99_s"] == 0.9                # percentiles take max
    assert flat["t/count"] == 3                  # counts sum
    assert flat["m/per_sec"] == 6.0              # meter stats sum
    assert flat["m/total"] == 12.0


def test_sampler_kill_switch(monkeypatch):
    sampler = TimeSeriesSampler(config=TimeSeriesConfig(interval_s=0.1))
    metrics.gauge_set("serve/batch_fill", 0.5)
    assert sampler.sample(wall=T) > 0
    monkeypatch.setenv("RAYDP_TPU_TIMESERIES", "0")
    assert sampler.sample(wall=T + 1) == 0      # live-checked, no thread
    monkeypatch.delenv("RAYDP_TPU_TIMESERIES")
    assert sampler.sample(wall=T + 2) > 0


def test_slo_kill_switch(monkeypatch):
    store = _store()
    store.record("x", 10.0, wall=T)
    eng = SloEngine(
        store=store,
        objectives=[Objective(name="x", series="x", threshold=1.0)],
    )
    monkeypatch.setenv("RAYDP_TPU_SLO", "0")
    assert eng.evaluate(now=T + 1) == []


# ---------------------------------------------------------------------
# Burn-rate math and hysteresis (hand-fed, deterministic clocks)
# ---------------------------------------------------------------------


def _engine(store, objectives, **cfg):
    base = dict(
        interval_s=0.1, short_window_s=10.0, long_window_s=40.0,
        budget=0.25, burn_threshold=1.0, recovery_evals=2,
    )
    base.update(cfg)
    return SloEngine(
        store=store, config=SloConfig(**base), objectives=objectives
    )


def test_value_signal_burn_rates_and_breach():
    store = _store()
    obj = Objective(
        name="lat", series="lat/p99_s", signal="value", op="gt",
        threshold=0.1,
    )
    eng = _engine(store, [obj])
    for i in range(10):
        store.record("lat/p99_s", 0.01, wall=T + i)
    assert eng.evaluate(now=T + 10) == []       # healthy: no transition
    for i in range(10, 20):
        store.record("lat/p99_s", 0.5, wall=T + i)
    # short window (10 s): all 10 samples bad -> fraction 1.0, burn 4
    # long window (40 s): 10 of 20 bad -> fraction 0.5, burn 2
    burns = eng.burn_rates(obj, T + 20)
    assert burns["short"] == pytest.approx(4.0)
    assert burns["long"] == pytest.approx(2.0)
    trs = eng.evaluate(now=T + 20)
    assert [t["kind"] for t in trs] == ["breach"]
    attrs = trs[0]["event"]["attrs"]
    assert attrs["objective"] == "lat"
    assert attrs["top_series"][0]["series"] == "lat/p99_s"
    assert eng.status()["lat"]["status"] == "breached"
    # exported state: gauges + breach counter
    snap = metrics.snapshot()
    assert snap["gauges"]["slo/status/lat"] == 1.0
    assert snap["counters"]["slo/breaches/lat"] == 1


def test_recovery_hysteresis_with_streak_reset():
    store = _store()
    obj = Objective(name="lat", series="lat/p99_s", threshold=0.1)
    eng = _engine(store, [obj])
    for i in range(10, 20):
        store.record("lat/p99_s", 0.5, wall=T + i)
    assert [t["kind"] for t in eng.evaluate(now=T + 20)] == ["breach"]
    # half-good short window still burns -> streak stays at zero
    for i in range(20, 25):
        store.record("lat/p99_s", 0.01, wall=T + i)
    assert eng.evaluate(now=T + 25) == []
    # fully good window: first quiet eval is NOT yet a recovery
    for i in range(25, 35):
        store.record("lat/p99_s", 0.01, wall=T + i)
    assert eng.evaluate(now=T + 35) == []
    trs = eng.evaluate(now=T + 36)              # second quiet eval
    assert [t["kind"] for t in trs] == ["recovered"]
    assert trs[0]["mttr_s"] == pytest.approx(16.0)
    st = eng.status()["lat"]
    assert st["status"] == "ok"
    assert st["last_mttr_s"] == pytest.approx(16.0)


def test_no_data_counts_toward_recovery_never_breach():
    store = _store()
    obj = Objective(name="lat", series="lat/p99_s", threshold=0.1)
    eng = _engine(store, [obj])
    assert eng.evaluate(now=T) == []            # empty store: no breach
    for i in range(10, 20):
        store.record("lat/p99_s", 0.5, wall=T + i)
    assert [t["kind"] for t in eng.evaluate(now=T + 20)] == ["breach"]
    # jump past all retained samples: windows are empty (torn-down
    # plane) and the open episode must close, not wedge forever
    assert eng.evaluate(now=T + 500) == []
    assert [t["kind"] for t in eng.evaluate(now=T + 501)] == [
        "recovered"
    ]


def test_rate_signal_sums_matching_series():
    store = _store()
    obj = Objective(
        name="restarts", series="wr/*", signal="rate", op="gt",
        threshold=0.5,
    )
    eng = _engine(store, [obj])
    # two series each growing at 0.3/s: individually under, summed over
    for i in range(10):
        store.record("wr/1", 0.3 * i, wall=T + i)
        store.record("wr/2", 0.3 * i, wall=T + i)
    burns = eng.burn_rates(obj, T + 9)
    assert burns["short"] == pytest.approx(1.0 / 0.25)
    assert [t["kind"] for t in eng.evaluate(now=T + 9)] == ["breach"]


def test_lt_objective_floors():
    store = _store()
    obj = Objective(
        name="fill_floor", series="serve/batch_fill", signal="value",
        op="lt", threshold=0.3,
    )
    eng = _engine(store, [obj])
    for i in range(10):
        store.record("serve/batch_fill", 0.5, wall=T + i)
    assert eng.evaluate(now=T + 9) == []        # above the floor: fine
    for i in range(10, 20):
        store.record("serve/batch_fill", 0.1, wall=T + i)
    assert [t["kind"] for t in eng.evaluate(now=T + 20)] == ["breach"]


def test_default_objectives_cover_the_flywheel():
    names = {o.name for o in default_objectives()}
    assert names == {
        "serve_p99", "serve_shed_rate", "worker_stalls",
        "worker_restart_rate", "gang_restart_rate",
        "arbiter_starvation", "ingest_starvation",
    }


# ---------------------------------------------------------------------
# Event ring drop accounting
# ---------------------------------------------------------------------


def test_event_ring_eviction_is_counted():
    cap = events_mod._ring.maxlen
    for i in range(cap + 3):
        events_mod.emit("test/fill", i=i)
    dropped = metrics.snapshot()["counters"].get("events/dropped", 0)
    assert dropped >= 3


# ---------------------------------------------------------------------
# Live loop: injected latency fault -> breach -> triage -> recovery
# ---------------------------------------------------------------------


def _make_model():
    # Nested so cloudpickle ships it by value — a replica subprocess
    # cannot import this test module by name.
    def model(payloads, bucket):
        return [float(sum(p)) for p in payloads]

    return model


def test_injected_latency_fault_breach_and_recovery(monkeypatch):
    from raydp_tpu.serve import ReplicaGroup

    monkeypatch.setenv(
        "RAYDP_TPU_FAULT_PLAN", "latency:nth=0,delay=0.8,replica=0"
    )
    sampler = TimeSeriesSampler(
        config=TimeSeriesConfig(
            interval_s=0.05, capacity=512, max_series=512
        )
    )
    eng = SloEngine(
        store=sampler.store,
        config=SloConfig(
            interval_s=0.05, short_window_s=1.0, long_window_s=6.0,
            budget=0.2, burn_threshold=1.0, recovery_evals=2,
        ),
        objectives=[
            o for o in default_objectives() if o.name == "serve_p99"
        ],
    )
    group = ReplicaGroup(
        replicas=1, model_fn=_make_model(), label="slo-smoke",
        max_batch=1, slo_ms=10_000, restart_backoff_s=0.1,
    )
    with group.start():
        # the armed clause stalls the first request 0.8 s — well past
        # the 50 ms serve_p99 threshold
        group.predict([1, 2, 3])
        breach = None
        deadline = time.time() + 20
        while time.time() < deadline and breach is None:
            sampler.sample()
            for tr in eng.evaluate():
                if tr["kind"] == "breach":
                    breach = tr
            time.sleep(0.05)
        assert breach is not None, "no breach within deadline"
        attrs = breach["event"]["attrs"]
        assert attrs["objective"] == "serve_p99"
        # auto-triage: the offending series is named ...
        assert any(
            row["series"] == "serve/latency/p99_s"
            for row in attrs["top_series"]
        )
        # ... alongside the correlated timeline (spawn/ready events
        # from the replica bring-up land inside the short window)
        assert isinstance(attrs["correlated"], list)

        # dilute the rolling p99 below the one slow observation, then
        # let the short window drain
        for i in range(150):
            group.predict([i, i])
        recovered = None
        deadline = time.time() + 30
        while time.time() < deadline and recovered is None:
            sampler.sample()
            for tr in eng.evaluate():
                if tr["kind"] == "recovered":
                    recovered = tr
            time.sleep(0.05)
        assert recovered is not None, "no recovery within deadline"
        assert recovered["mttr_s"] > 0

    # the episode is a first-class MTTR entry on the event timeline
    report = events_mod.mttr_report(events_mod.local_events())
    episodes = [
        ep
        for job in report.values()
        for ep in job.get("episodes", [])
        if ep.get("start_kind") == "slo/breach"
        and ep.get("end_kind") == "slo/recovered"
    ]
    assert episodes, report
    assert episodes[0]["repair_s"] == pytest.approx(
        recovered["mttr_s"], abs=0.01
    )

    # and the raydp_slo_* families expose the whole episode
    text = render_prometheus(
        {"workers": {}, "aggregate": {}, "driver": metrics.snapshot()}
    )
    assert 'raydp_slo_breaches_total{objective="serve_p99"' in text
    assert 'raydp_slo_status{objective="serve_p99"' in text
    assert 'raydp_slo_burn_rate{objective="serve_p99"' in text


# ---------------------------------------------------------------------
# Dashboard: document, renderer, /debug/dashboard route
# ---------------------------------------------------------------------

_SECTIONS = (
    "train", "etl", "serve", "control", "slo", "jobs", "events",
    "timeseries",
)


def test_dashboard_document_and_renderer():
    metrics.counter_add("serve/requests", 5)
    metrics.counter_add("serve/replies", 5)
    metrics.gauge_set("serve/batch_fill", 0.75)
    metrics.timer("train/step").observe(0.004)
    dash = dash_mod.local_dashboard()
    for section in _SECTIONS:
        assert section in dash, section
    assert dash["serve"]["requests"] == 5
    assert dash["serve"]["batch_fill"] == 0.75
    assert dash["train"]["steps"] == 1
    assert dash["train"]["step_p50_ms"] == 4.0
    text = dash_mod.format_dashboard(dash)
    assert "serve" in text and "step_p50_ms" in text


def test_debug_dashboard_route():
    metrics.counter_add("serve/requests", 7)
    srv = serve_prometheus(
        lambda: render_prometheus(
            {"workers": {}, "aggregate": {}, "driver": metrics.snapshot()}
        ),
        0,
        host="127.0.0.1",
    )
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/debug/dashboard", timeout=10
        ) as resp:
            dash = json.loads(resp.read().decode("utf-8"))
        for section in _SECTIONS:
            assert section in dash, section
        assert dash["serve"]["requests"] == 7
    finally:
        srv.close()


def test_dashboard_cli_offline(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv("RAYDP_TPU_TELEMETRY_DIR", str(tmp_path))
    events_mod.emit("slo/breach", objective="serve_p99", value=0.5)
    events_mod.emit("slo/recovered", objective="serve_p99", mttr_s=2.5)
    assert dash_mod.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "slo/breach" in out
    assert "slo/recovered" in out


# ---------------------------------------------------------------------
# Client-mode parity: the remote driver sees the same document
# ---------------------------------------------------------------------


@pytest.fixture()
def session():
    import raydp_tpu

    s = raydp_tpu.init(app_name="slo-dashboard-test", num_workers=2)
    yield s
    raydp_tpu.stop()


def test_dashboard_report_client_parity(session):
    local = session.cluster.dashboard_report()
    for section in _SECTIONS:
        assert section in local, section
    addr = session.cluster.master.address
    script = (
        "import json, raydp_tpu\n"
        f"s = raydp_tpu.connect({addr!r})\n"
        "report = s.cluster.dashboard_report()\n"
        "out = {'sections': sorted(report), "
        "'serve': sorted(report.get('serve', {}))}\n"
        "raydp_tpu.stop()\n"
        "print('RESULT ' + json.dumps(out))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=180,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = next(
        l for l in proc.stdout.splitlines() if l.startswith("RESULT ")
    )
    remote = json.loads(line[len("RESULT "):])
    assert set(_SECTIONS) <= set(remote["sections"])
    assert remote["serve"] == sorted(local["serve"])
