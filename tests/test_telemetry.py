"""Telemetry plane: spans, heartbeat-shipped metrics, export surface.

Covers the three layers end to end, all on the CPU backend with no real
accelerator:

* span primitives — nesting/ordering/ids on one thread, trace isolation
  across threads, error status, ring-buffer bounds;
* shipping — ``MetricsShipper`` delta encoding, ``ClusterTelemetry``
  merge/aggregate/tombstone semantics;
* export — Prometheus text exposition (golden + line-level parse),
  JSONL span logs written by a real estimator run;
* acceptance — a live two-worker cluster whose workers record metrics
  that arrive at the master via heartbeats, survive a worker being
  written off, and render as scrape-ready exposition text.
"""
import json
import os
import re
import threading
import time

import pytest

from raydp_tpu.telemetry import (
    ClusterTelemetry,
    MetricsShipper,
    SpanRecorder,
    flush_spans,
    render_prometheus,
)
from raydp_tpu.utils.profiling import MetricsRegistry


# ---------------------------------------------------------------------
# Spans


def test_span_nesting_and_ordering():
    rec = SpanRecorder()
    with rec.span("epoch", epoch=0) as epoch:
        with rec.span("step", step=0) as s0:
            pass
        with rec.span("step", step=1) as s1:
            pass
    done = rec.drain()
    # Finish order: children land before the parent.
    assert [s.name for s in done] == ["step", "step", "epoch"]
    # Start order is the seq: parent first, then its steps.
    assert epoch.seq < s0.seq < s1.seq
    assert s0.parent_id == epoch.span_id
    assert s1.parent_id == epoch.span_id
    # One trace, rooted at the epoch.
    assert {s.trace_id for s in (epoch, s0, s1)} == {epoch.span_id}
    assert epoch.parent_id is None
    for s in done:
        assert s.duration_s is not None and s.duration_s >= 0
        assert s.status == "ok"


def test_span_error_status_propagates_and_stack_unwinds():
    rec = SpanRecorder()
    with pytest.raises(ValueError):
        with rec.span("outer"):
            with rec.span("inner"):
                raise ValueError("boom")
    inner, outer = rec.drain()
    assert inner.status == "error" and outer.status == "error"
    # Stack fully unwound: the next span starts a fresh trace.
    with rec.span("fresh") as fresh:
        pass
    assert fresh.parent_id is None


def test_spans_on_other_threads_start_fresh_traces():
    rec = SpanRecorder()
    seen = {}

    def worker():
        with rec.span("producer") as sp:
            seen["producer"] = sp

    with rec.span("consumer") as consumer:
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    # Deliberately NOT parented under the consumer's open span.
    assert seen["producer"].parent_id is None
    assert seen["producer"].trace_id != consumer.trace_id


def test_event_is_zero_duration_and_buffered():
    rec = SpanRecorder()
    ev = rec.event("worker/registered", worker_id="w0")
    assert ev.kind == "event"
    assert ev.duration_s == 0.0
    d = ev.to_dict()
    assert d["attrs"] == {"worker_id": "w0"}
    assert d["pid"] == os.getpid()
    assert [s.span_id for s in rec.spans()] == [ev.span_id]


def test_ring_buffer_is_bounded():
    rec = SpanRecorder(capacity=8)
    for i in range(20):
        with rec.span("s", i=i):
            pass
    kept = rec.drain()
    assert len(kept) == 8
    # Oldest evicted, newest retained, order preserved.
    assert [s.attrs["i"] for s in kept] == list(range(12, 20))


# ---------------------------------------------------------------------
# Shipping


def test_shipper_delta_only_ships_changed_sections():
    reg = MetricsRegistry()
    shipper = MetricsShipper(reg)
    reg.counter_add("tasks", 2)
    reg.meter("rows").add(100)
    first = shipper.delta()
    assert first["counters"] == {"tasks": 2}
    assert first["meter/rows"]["total"] == 100
    # Quiescent registry → empty delta → heartbeat ships no payload.
    assert shipper.delta() == {}
    # Only the touched section reappears.
    reg.counter_add("tasks", 3)
    second = shipper.delta()
    assert set(second) == {"counters"}
    assert second["counters"] == {"tasks": 5}  # cumulative, not increment
    # full() always carries everything (worker-exit final ship).
    assert set(shipper.full()) >= {"counters", "meter/rows"}


def test_shipper_rollback_reships_lost_delta():
    """A delta whose heartbeat failed in transport must re-ship on the
    next beat even if the registry went quiescent in between."""
    reg = MetricsRegistry()
    shipper = MetricsShipper(reg)
    reg.counter_add("tasks", 4)
    lost = shipper.delta()
    assert lost["counters"] == {"tasks": 4}
    # Without rollback a quiescent registry would now ship nothing, ever.
    shipper.rollback(lost)
    retry = shipper.delta()
    assert retry["counters"] == {"tasks": 4}
    assert shipper.delta() == {}
    shipper.rollback({})  # no-op on an empty delta


def test_cluster_telemetry_merge_aggregate_and_tombstone():
    ct = ClusterTelemetry()
    ct.apply("w0", {"counters": {"tasks": 3},
                    "timer/step": {"count": 4, "total_s": 0.4,
                                   "mean_s": 0.1, "p50_s": 0.1,
                                   "p90_s": 0.1, "p99_s": 0.1}})
    ct.apply("w1", {"counters": {"tasks": 5},
                    "timer/step": {"count": 6, "total_s": 1.2,
                                   "mean_s": 0.2, "p50_s": 0.2,
                                   "p90_s": 0.3, "p99_s": 0.3}})
    # A later delta overwrites w0's counters section (cumulative values).
    ct.apply("w0", {"counters": {"tasks": 7}})
    view = ct.merged()
    assert view["workers"]["w0"]["counters"]["tasks"] == 7
    agg = view["aggregate"]
    assert agg["counters"]["tasks"] == 12
    # Timers: counts/totals sum, mean recomputed, percentiles are the
    # cross-worker max (straggler view).
    assert agg["timer/step"]["count"] == 10
    assert abs(agg["timer/step"]["total_s"] - 1.6) < 1e-9
    assert abs(agg["timer/step"]["mean_s"] - 0.16) < 1e-9
    assert agg["timer/step"]["p99_s"] == 0.3

    # Crash path: tombstone retains the last-shipped data.
    ct.tombstone("w1")
    view = ct.merged()
    assert view["workers"]["w1"]["tombstone"] is True
    assert view["workers"]["w1"]["counters"]["tasks"] == 5
    assert view["aggregate"]["counters"]["tasks"] == 12  # still counted

    # Graceful path: final full snapshot merges then tombstones.
    ct.apply("w0", {"counters": {"tasks": 9}}, final=True)
    w0 = ct.merged()["workers"]["w0"]
    assert w0["tombstone"] is True and w0["counters"]["tasks"] == 9


def test_cluster_telemetry_events_ring():
    ct = ClusterTelemetry(max_events=4)
    for i in range(6):
        ct.event("worker/registered", worker_id=f"w{i}")
    evs = ct.events()
    assert len(evs) == 4
    assert [e["worker_id"] for e in evs] == ["w2", "w3", "w4", "w5"]
    assert all("wall_time" in e for e in evs)


# ---------------------------------------------------------------------
# Export: Prometheus


# One exposition sample line: name{labels} value
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? (NaN|[-+0-9.eE]+)$"
)


def _parseable(text: str) -> bool:
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        if not _SAMPLE_RE.match(line):
            return False
    return True


def test_render_prometheus_golden():
    view = {
        "workers": {
            "w0": {
                "counters": {"worker/tasks": 3},
                "meter/ingest/rows": {"total": 512, "per_sec": 1024.0,
                                      "elapsed_s": 0.5},
                "timer/train/step": {"count": 4, "total_s": 0.4,
                                     "mean_s": 0.1, "p50_s": 0.1,
                                     "p90_s": 0.12, "p99_s": 0.2},
            },
            "w1": {"counters": {"worker/tasks": 1}, "tombstone": True,
                   "updated_wall": 1234.5},
        },
        "aggregate": {"counters": {"worker/tasks": 4}},
        "driver": {"counters": {"train/epochs": 2}},
    }
    text = render_prometheus(view)
    lines = text.splitlines()
    assert _parseable(text)
    assert 'raydp_worker_up{worker="w0"} 1' in lines
    assert 'raydp_worker_up{worker="w1"} 0' in lines
    # The driver has no liveness gauge — it is not a worker.
    assert 'raydp_worker_up{worker="driver"}' not in text
    assert 'raydp_counter_total{name="worker/tasks",worker="w0"} 3' in lines
    assert 'raydp_counter_total{name="train/epochs",worker="driver"} 2' \
        in lines
    assert 'raydp_meter_units_total{name="ingest/rows",worker="w0"} 512' \
        in lines
    assert ('raydp_meter_units_per_second{name="ingest/rows",worker="w0"}'
            " 1024") in lines
    assert ('raydp_timer_seconds{name="train/step",quantile="0.99",'
            'worker="w0"} 0.2') in lines
    assert 'raydp_timer_seconds_count{name="train/step",worker="w0"} 4' \
        in lines
    # The aggregate must NOT render: PromQL sum() would double-count.
    assert text.count('name="worker/tasks"') == 2
    # TYPE metadata precedes each family's samples.
    assert lines.index("# TYPE raydp_worker_up gauge") \
        < lines.index('raydp_worker_up{worker="w0"} 1')
    # Deterministic: same view → identical text (scrape diffing works).
    assert render_prometheus(view) == text


def test_render_prometheus_escapes_label_values():
    text = render_prometheus(
        {"workers": {'w"0\n': {"counters": {"a": 1}}}}
    )
    assert '\\"' in text and "\\n" in text
    assert _parseable(text)


def test_render_prometheus_empty_view():
    assert render_prometheus({"workers": {}}) == ""


# ---------------------------------------------------------------------
# Export: JSONL span log from a real training run


def test_estimator_writes_nested_span_log(tmp_path, monkeypatch):
    """An estimator epoch flushes a spans-<pid>.jsonl shard where step
    spans nest under their epoch span and chunk spans closed before
    being consumed."""
    import numpy as np
    import pandas as pd

    from raydp_tpu.models.mlp import taxi_fare_regressor
    from raydp_tpu.telemetry import recorder
    from raydp_tpu.train.estimator import JAXEstimator

    monkeypatch.setenv("RAYDP_TPU_TELEMETRY_DIR", str(tmp_path))
    recorder.clear()  # spans from earlier tests must not pollute the log

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
        epoch_mode="stream",
    )
    est.fit_on_df(df)

    log = tmp_path / f"spans-{os.getpid()}.jsonl"
    assert log.exists()
    records = [json.loads(line) for line in log.read_text().splitlines()]
    epochs = [r for r in records if r["name"] == "train/epoch"]
    steps = [r for r in records if r["name"] == "train/step"]
    assert len(epochs) == 2
    assert len(steps) == 8  # 256 rows / 64 batch × 2 epochs
    epoch_ids = {e["span_id"]: e for e in epochs}
    for s in steps:
        assert s["parent_id"] in epoch_ids
        parent = epoch_ids[s["parent_id"]]
        assert s["attrs"]["epoch"] == parent["attrs"]["epoch"]
        assert s["trace_id"] == parent["trace_id"]
        assert s["seq"] > parent["seq"]
        assert s["duration_s"] >= 0
    # Loader chunk spans are present and never parent under steps (they
    # close before yielding — generator-suspension discipline).
    chunks = [r for r in records if r["name"] == "ingest/chunk"]
    assert chunks
    step_ids = {s["span_id"] for s in steps}
    assert all(c["parent_id"] not in step_ids for c in chunks)


def test_flush_spans_is_noop_without_dir(monkeypatch):
    from raydp_tpu.telemetry import recorder

    monkeypatch.delenv("RAYDP_TPU_TELEMETRY_DIR", raising=False)
    rec_before = len(recorder.spans())
    with_span = recorder.span
    with with_span("kept"):
        pass
    assert flush_spans() is None
    # Buffer intact: nothing was drained into the void.
    assert len(recorder.spans()) == rec_before + 1


# ---------------------------------------------------------------------
# Acceptance: live two-worker cluster, heartbeat-shipped metrics


def _poll(predicate, timeout_s=25.0, interval_s=0.5):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        value = predicate()
        if value:
            return value
        time.sleep(interval_s)
    return predicate()


def test_two_worker_cluster_ships_merges_and_survives_death(tmp_path):
    """The ISSUE acceptance path: worker-side registries reach the
    master over heartbeats, merge per worker id, tombstone on death with
    data retained, and the whole view renders as parseable exposition
    text plus JSONL logs on shutdown."""
    import raydp_tpu

    # Nested so cloudpickle ships it by value — the worker subprocess
    # cannot import this test module.
    def _record_worker_metrics(ctx):
        from raydp_tpu.utils.profiling import metrics

        metrics.meter("ingest/rows").add(1000)
        t = metrics.timer("train/step")
        for v in (0.01, 0.02, 0.05):
            t.observe(v)
        return "recorded"

    os.environ["RAYDP_TPU_TELEMETRY_DIR"] = str(tmp_path)
    s = raydp_tpu.init(app_name="telemetry-acceptance", num_workers=2)
    try:
        workers = sorted(w.worker_id for w in s.cluster.alive_workers())
        assert len(workers) == 2
        for wid in workers:
            assert s.cluster.submit(
                _record_worker_metrics, worker_id=wid, timeout=30.0
            ) == "recorded"

        def shipped():
            view = s.cluster.metrics_snapshot()
            ok = all(
                "meter/ingest/rows" in view["workers"].get(w, {})
                for w in workers
            )
            return view if ok else None

        # Heartbeats beat every 2s; both deltas must land well inside 25s.
        view = _poll(shipped)
        assert view, f"metrics never arrived: {s.cluster.metrics_snapshot()}"
        for wid in workers:
            wv = view["workers"][wid]
            assert wv["meter/ingest/rows"]["total"] == 1000
            timer = wv["timer/train/step"]
            assert timer["count"] == 3
            assert timer["p50_s"] == 0.02
            assert timer["p99_s"] == 0.05
        agg = view["aggregate"]
        assert agg["meter/ingest/rows"]["total"] == 2000
        assert agg["timer/train/step"]["count"] == 6
        assert agg["timer/train/step"]["p99_s"] == 0.05

        # Kill one worker: its view tombstones but the data survives.
        victim = workers[0]
        s.cluster.master.mark_worker_dead(victim, reason="test kill")
        view = _poll(
            lambda: (
                v := s.cluster.metrics_snapshot()
            )["workers"][victim].get("tombstone") and v
        )
        assert view["workers"][victim]["tombstone"] is True
        assert view["workers"][victim]["meter/ingest/rows"]["total"] == 1000
        assert view["aggregate"]["meter/ingest/rows"]["total"] == 2000
        names = [e["name"] for e in view["events"]]
        assert "worker/registered" in names and "worker/dead" in names

        # Exposition renders and parses line by line.
        text = s.cluster.prometheus_metrics()
        assert _parseable(text)
        assert f'raydp_worker_up{{worker="{victim}"}} 0' in text
        assert 'name="ingest/rows"' in text
    finally:
        raydp_tpu.stop()
        os.environ.pop("RAYDP_TPU_TELEMETRY_DIR", None)
    # Shutdown flushed the driver-side logs.
    events_log = tmp_path / "events.jsonl"
    assert events_log.exists()
    logged = [json.loads(l) for l in events_log.read_text().splitlines()]
    assert any(e["name"] == "worker/dead" for e in logged)


# ---------------------------------------------------------------------
# Marker hygiene


def test_telemetry_tests_run_in_tier1():
    """The telemetry suites themselves run under the tier-1 gate
    (``-m 'not slow'``): no slow markers there. Other files that merely
    use ``raydp_tpu.telemetry`` may keep a test that is truly slow."""
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    suites = ("test_telemetry.py", "test_tracing.py", "test_device_plane.py",
              "test_profiling.py", "test_query_profiling.py")
    offenders = []
    for fname in suites:
        text = open(os.path.join(tests_dir, fname), encoding="utf-8").read()
        if re.search(r"pytest\.mark\.slow|pytestmark\s*=.*slow", text):
            offenders.append(fname)
    assert not offenders, (
        f"telemetry tests must stay in tier-1, found slow markers in: "
        f"{offenders}"
    )
