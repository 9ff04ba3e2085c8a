"""Profiling subsystem: registry math, ingest/train instrumentation
actually records, jax trace writes a profile."""
import os

import numpy as np
import pytest

from raydp_tpu.utils.profiling import (
    MetricsRegistry,
    StepTimer,
    ThroughputMeter,
    annotate,
    metrics,
    trace,
)


def test_step_timer_percentiles():
    t = StepTimer()
    for v in [0.01, 0.02, 0.03, 0.04, 1.0]:  # 1.0 = the compile outlier
        t.observe(v)
    s = t.summary()
    assert s["count"] == 5
    assert s["p50_s"] == 0.03
    assert s["p99_s"] == 1.0
    assert abs(s["mean_s"] - 0.22) < 1e-9


def test_throughput_meter():
    import time

    m = ThroughputMeter()
    m.add(100)
    time.sleep(0.01)
    m.add(100)
    assert m.total == 200
    assert m.rate() > 0


def test_throughput_meter_concurrent_adds():
    """Regression: pre-telemetry ThroughputMeter did ``self.total += n``
    unlocked, so concurrent ingest threads (loader prefetch + consumer)
    lost increments. 8 threads × 10k adds must land exactly."""
    import threading

    m = ThroughputMeter()
    n_threads, n_adds = 8, 10_000

    def hammer():
        for _ in range(n_adds):
            m.add(1)

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert m.total == n_threads * n_adds
    s = m.summary()
    assert s["total"] == n_threads * n_adds
    assert s["per_sec"] > 0


def test_registry_snapshot_and_reset():
    r = MetricsRegistry()
    r.counter_add("a", 2)
    r.counter_add("a", 3)
    with r.timer("t").time():
        pass
    r.meter("m").add(7)
    snap = r.snapshot()
    assert snap["counters"]["a"] == 5
    assert snap["timer/t"]["count"] == 1
    assert snap["meter/m"]["total"] == 7
    r.reset()
    assert r.snapshot()["counters"] == {}


def test_training_records_metrics():
    """Driving the estimator populates ingest + train metrics."""
    import pandas as pd

    from raydp_tpu.models.mlp import taxi_fare_regressor
    from raydp_tpu.train.estimator import JAXEstimator

    metrics.reset()
    rng = np.random.default_rng(0)
    df = pd.DataFrame(rng.random((256, 4)), columns=list("abcd"))
    df["y"] = df.a * 2 + df.b

    est = JAXEstimator(
        model=taxi_fare_regressor(),
        loss="mse",
        num_epochs=2,
        batch_size=64,
        feature_columns=list("abcd"),
        label_column="y",
        epoch_mode="stream",  # exercise the instrumented loader path
    )
    est.fit_on_df(df)
    snap = metrics.snapshot()
    # Eight batches of 64 over the two epochs: the loader meters rows.
    assert snap["meter/ingest/rows"]["total"] == 8 * 64
    assert snap["meter/ingest/bytes"]["per_sec"] > 0
    assert snap["counters"]["train/epochs"] == 2
    assert snap["meter/train/samples"]["total"] == 512


def test_trace_writes_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    with trace(str(tmp_path)):
        with annotate("matmul"):
            x = jnp.ones((64, 64))
            jax.block_until_ready(x @ x)
    found = [
        f
        for root, _, files in os.walk(tmp_path)
        for f in files
        if f.endswith((".xplane.pb", ".trace.json.gz"))
    ]
    assert found, f"no profile artifacts under {tmp_path}"
