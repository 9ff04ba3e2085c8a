"""Anomaly sentinels, the step-time histogram and its export, the
loader's wait counter, and the gang-coordinated trace capture."""
import gzip
import json
import threading
import time
import urllib.request

import numpy as np
import pandas as pd
import pytest

from raydp_tpu.telemetry import device_profiler as dp
from raydp_tpu.utils.profiling import Histogram, metrics


@pytest.fixture(autouse=True)
def _fresh_registry():
    metrics.reset()
    yield
    metrics.reset()


def _fit_df(n_rows=4096, n_feat=6, seed=3):
    rs = np.random.RandomState(seed)
    x = rs.rand(n_rows, n_feat).astype(np.float32)
    w = rs.rand(n_feat, 1).astype(np.float32)
    df = pd.DataFrame(x, columns=[f"f{i}" for i in range(n_feat)])
    df["label"] = (x @ w).astype(np.float32)
    return df, [f"f{i}" for i in range(n_feat)]


def _estimator(cols, **kw):
    from raydp_tpu.models.mlp import MLP
    from raydp_tpu.train.estimator import JAXEstimator

    defaults = dict(
        model=MLP(hidden=(16,), out_dim=1),
        loss="mse",
        num_epochs=2,
        batch_size=256,
        feature_columns=cols,
        label_column="label",
        epoch_mode="stream",
    )
    defaults.update(kw)
    return JAXEstimator(**defaults)


# -- what a stream fit reports ------------------------------------------------

def test_stream_fit_history_keys_and_step_histogram():
    """An epoch's history entry holds what was measured and nothing
    inferred; ``hist/train/step_seconds`` counted every step."""
    df, cols = _fit_df()
    est = _estimator(cols)
    history = est.fit_on_df(df)
    keys = {"epoch", "train_loss", "time_s", "samples", "samples_per_sec"}
    assert [set(h) for h in history] == [keys, keys]
    steps = 2 * (len(df) // 256)
    assert int(est._state.step) == steps
    snap = metrics.snapshot()
    assert snap["hist/train/step_seconds"]["count"] == steps
    assert snap["timer/train/step"]["count"] == steps

    df_eval, _ = _fit_df(n_rows=512, seed=4)
    history = _estimator(cols, num_epochs=1).fit_on_df(df, df_eval)
    assert {k for k in history[-1] if not k.startswith("eval_")} == keys
    assert any(k.startswith("eval_") for k in history[-1])


# -- ingest wait counter vs ingest/wait spans ---------------------------------

def test_ingest_wait_counter_matches_ingest_wait_spans():
    """The loader's starvation is recorded twice from one interval: the
    counter ``ingest/wait_seconds`` (autoscaler, SLO, the benchmark's
    ``infeed.wait_share``) and the ``ingest/wait`` spans a profile
    shows. With the producer as the bottleneck both see its sleeps."""
    from raydp_tpu.data.loader import _background
    from raydp_tpu.telemetry.spans import recorder

    def slow_producer():
        for i in range(8):
            time.sleep(0.02)
            yield i

    recorder.clear()
    source, stop = _background(slow_producer(), depth=1)
    consumed = list(source)
    stop.set()
    assert consumed == list(range(8))
    waits = [s for s in recorder.spans() if s.name == "ingest/wait"]
    recorder.clear()
    # One span per item, and one for the end-of-stream marker.
    assert len(waits) == 9
    counter = metrics.snapshot()["counters"]["ingest/wait_seconds"]
    assert counter > 0.05  # 8 × 20ms producer sleeps, minus pipelining
    assert counter == pytest.approx(sum(s.duration_s for s in waits))


# -- anomaly sentinels -------------------------------------------------------

def test_nan_sentinel_fires_flight_bundle(tmp_path, monkeypatch):
    monkeypatch.setenv("RAYDP_TPU_POSTMORTEM_DIR", str(tmp_path))
    from raydp_tpu.telemetry import latest_bundle

    sentinel = dp.AnomalySentinel(check_every=1, cooldown_s=60.0)
    assert sentinel.check_loss(1.5, step=1) is False
    assert sentinel.check_loss(float("nan"), step=2) is True
    assert [t["kind"] for t in sentinel.tripped] == ["nan_loss"]
    bundle = latest_bundle(str(tmp_path))
    assert bundle is not None
    with open(bundle) as f:
        doc = json.load(f)
    assert "anomaly:nan_loss" in json.dumps(doc)
    # Cooldown: the counter keeps counting, but no second bundle/event.
    assert sentinel.check_loss(float("inf"), step=3) is False
    assert len(sentinel.tripped) == 1
    counters = metrics.snapshot()["counters"]
    assert counters["anomalies/nan_loss"] == 2


def test_nan_grad_norm_sentinel():
    sentinel = dp.AnomalySentinel(check_every=1, cooldown_s=0.0)
    assert sentinel.check_grad_norm(float("inf"), step=4) is True
    assert metrics.snapshot()["counters"]["anomalies/nan_grad_norm"] == 1


def test_step_regression_detector_and_cooldown():
    sentinel = dp.AnomalySentinel(
        check_every=1, cooldown_s=60.0,
        regression_factor=2.5, regression_min_steps=8,
    )
    # Below min history: even a huge step must not trip.
    assert sentinel.observe_step(1.0, step=0) is False
    for i in range(10):
        sentinel.observe_step(0.01, step=i + 1)
    assert not [t for t in sentinel.tripped
                if t["kind"] == "step_regression"]
    assert sentinel.observe_step(0.2, step=20) is True
    # Cooldown gates the event, the counter still counts.
    assert sentinel.observe_step(0.25, step=21) is False
    trips = [t for t in sentinel.tripped if t["kind"] == "step_regression"]
    assert len(trips) == 1
    assert metrics.snapshot()["counters"]["anomalies/step_regression"] == 2


def test_training_nan_trips_sentinel(tmp_path, monkeypatch):
    """End-to-end: a NaN planted in the labels surfaces as a NaN loss,
    the sampled check catches it, and a flight bundle lands."""
    monkeypatch.setenv("RAYDP_TPU_POSTMORTEM_DIR", str(tmp_path))
    monkeypatch.setenv("RAYDP_TPU_SENTINEL_EVERY", "1")
    from raydp_tpu.telemetry import latest_bundle

    df, cols = _fit_df(n_rows=1024)
    df.loc[5, "label"] = np.nan
    est = _estimator(cols, num_epochs=1)
    est.fit_on_df(df)
    assert est._sentinel is not None
    kinds = {t["kind"] for t in est._sentinel.tripped}
    assert "nan_loss" in kinds or "nan_grad_norm" in kinds
    assert metrics.snapshot()["counters"].get(
        "anomalies/nan_loss", 0
    ) + metrics.snapshot()["counters"].get(
        "anomalies/nan_grad_norm", 0
    ) >= 1
    assert latest_bundle(str(tmp_path)) is not None


# -- histogram + export ------------------------------------------------------

def test_histogram_buckets_cumulative():
    h = Histogram(buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 5
    assert s["sum"] == pytest.approx(56.05)
    assert s["buckets"]["0.1"] == 1
    assert s["buckets"]["1.0"] == 3
    assert s["buckets"]["10.0"] == 4
    assert s["buckets"]["+Inf"] == 5


def test_prometheus_histogram_rendering():
    from raydp_tpu.telemetry import render_prometheus

    metrics.histogram("train/step_seconds").observe(0.002)
    metrics.histogram("train/step_seconds").observe(0.5)
    snap = {"workers": {"w0": metrics.snapshot()}}
    text = render_prometheus(snap)
    assert "raydp_step_seconds_bucket" in text
    assert 'le="+Inf"' in text
    assert "raydp_step_seconds_sum" in text
    assert "raydp_step_seconds_count" in text
    # Bucket counts are cumulative and end at the total count.
    inf_lines = [
        ln for ln in text.splitlines()
        if ln.startswith("raydp_step_seconds_bucket") and '+Inf' in ln
    ]
    assert inf_lines and inf_lines[0].rstrip().endswith("2")


def test_hist_merge_across_workers():
    from raydp_tpu.telemetry.shipping import ClusterTelemetry

    ct = ClusterTelemetry()
    for wid in ("w0", "w1"):
        ct.apply(wid, {"hist/train/step_seconds": {
            "sum": 1.0, "count": 2, "buckets": {"0.1": 1, "+Inf": 2},
        }})
    agg = ct.merged()["aggregate"]["hist/train/step_seconds"]
    assert agg == {"sum": 2.0, "count": 4.0,
                   "buckets": {"0.1": 2.0, "+Inf": 4.0}}


def test_anomaly_prometheus_family():
    from raydp_tpu.telemetry import render_prometheus

    metrics.counter_add("anomalies/nan_loss", 2)
    text = render_prometheus({"workers": {"w0": metrics.snapshot()}})
    assert 'raydp_anomalies_total{kind="nan_loss",worker="w0"} 2' in text


# -- gang capture ------------------------------------------------------------

def test_capture_local_trace_archive(tmp_path):
    payload = dp.capture_trace_archive(seconds=0.2, rank=7)
    assert payload["rank"] == 7
    assert payload["wall_stop"] > payload["wall_start"]
    assert len(payload["zip"]) > 0
    dest = tmp_path / "unpacked"
    dp.unpack_trace_archive(payload, str(dest))
    # jax on CPU writes a gzipped Chrome trace under plugins/profile.
    events = dp._load_jax_chrome_events(str(dest))
    assert isinstance(events, list)


def test_merge_rank_traces_two_local_captures(tmp_path):
    payloads = [
        dp.capture_trace_archive(seconds=0.2, rank=r) for r in (0, 1)
    ]
    merged = dp.merge_rank_traces(payloads, str(tmp_path / "merged"))
    assert merged["ranks"] == [0, 1]
    with open(merged["merged_trace"]) as f:
        doc = json.load(f)
    names = {
        e["args"]["name"]
        for e in doc["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    assert any(n.startswith("rank 0") for n in names), names
    assert any(n.startswith("rank 1") for n in names), names
    # Raw per-rank xplane dirs are kept for TensorBoard.
    assert (tmp_path / "merged" / "rank-0").is_dir()
    assert (tmp_path / "merged" / "rank-1").is_dir()


def test_gang_capture_two_rank_spmd(tmp_path):
    """2-rank gang: one ProfileRequest fan-out yields ONE merged
    Perfetto file with spans from every rank (acceptance criterion)."""
    from raydp_tpu.spmd.job import SPMDJob

    def busy(ctx):
        import jax
        import jax.numpy as jnp

        f = jax.jit(lambda x: (x @ x).sum())
        x = jnp.ones((128, 128))
        t0 = time.time()
        while time.time() - t0 < 4.0:
            f(x).block_until_ready()
        return ctx.rank

    job = SPMDJob(
        "gangprof", world_size=2,
        env={"JAX_PLATFORMS": "cpu"}, timeout=120.0,
    )
    job.start()
    try:
        results = {}
        t = threading.Thread(
            target=lambda: results.update(r=job.run(busy, timeout=120.0)),
            daemon=True,
        )
        t.start()
        time.sleep(0.5)
        merged = job.capture_profile(
            seconds=1.5, out_dir=str(tmp_path / "gang")
        )
        t.join(timeout=120.0)
    finally:
        job.stop()
    assert results.get("r") == [0, 1]
    assert merged.get("errors") is None or not merged["errors"]
    with open(merged["merged_trace"]) as f:
        doc = json.load(f)
    procs = {
        e["args"]["name"]
        for e in doc["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    assert any("rank 0" in p for p in procs), procs
    assert any("rank 1" in p for p in procs), procs


# -- /debug/profile endpoint -------------------------------------------------

def test_debug_profile_endpoint():
    from raydp_tpu.telemetry import serve_prometheus

    calls = []

    def fake_profile(seconds):
        calls.append(seconds)
        return {"dir": "/tmp/x", "seconds": seconds}

    server = serve_prometheus(
        lambda: "# empty\n", 0, profile=fake_profile
    )
    try:
        base = f"http://127.0.0.1:{server.port}"
        with urllib.request.urlopen(
            f"{base}/debug/profile?seconds=0.5", timeout=10
        ) as resp:
            body = json.loads(resp.read())
        assert body["seconds"] == 0.5
        assert calls == [0.5]
        # Clamped to the max window.
        with urllib.request.urlopen(
            f"{base}/debug/profile?seconds=99999", timeout=10
        ) as resp:
            json.loads(resp.read())
        assert calls[-1] <= 120.0
        # Non-numeric → 400, not a stack trace.
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                f"{base}/debug/profile?seconds=abc", timeout=10
            )
        assert err.value.code == 400
    finally:
        server.close()
