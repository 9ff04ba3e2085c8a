"""Distributed tracing v2: propagation, Chrome export, analysis.

Covers the tracing layers end to end, all on the CPU backend:

* context propagation — ``current_context`` resolution order,
  ``propagated`` save/restore across threads, process-level context,
  traceparent wire round-trip, RPC envelope inject/extract;
* RPC round-trip — a client call and the server-side handler span
  share one trace_id;
* dropped-span accounting — ``recorder.dropped`` plus the
  ``raydp_spans_dropped_total`` exposition family;
* Chrome-trace export — golden synthetic shards with known
  cross-process clock offsets: stable event fields, alignment,
  process/thread metadata;
* analyzer — critical path, per-rank step skew, data-vs-compute split
  on a synthetic trace, and the CLI;
* acceptance — a live two-worker cluster plus an estimator fit under
  ``RAYDP_TPU_TELEMETRY_DIR``: one shared trace_id across driver,
  master, and both workers in the merged Chrome trace, and an analyzer
  report with a critical path and a per-rank skew table.
"""
import json
import os
import threading

import pytest
import time

from raydp_tpu.telemetry import (
    SpanRecorder,
    TraceContext,
    chrome_trace,
    render_prometheus,
)
from raydp_tpu.telemetry import analyze
from raydp_tpu.telemetry import propagation as prop


# ---------------------------------------------------------------------
# Context propagation


def test_current_context_follows_innermost_open_span():
    rec = SpanRecorder()
    assert rec.current_context() is None
    with rec.span("outer") as outer:
        assert rec.current_context() == outer.context()
        with rec.span("inner") as inner:
            assert rec.current_context() == inner.context()
        assert rec.current_context() == outer.context()
    assert rec.current_context() is None


def test_propagated_parents_producer_thread_under_consumer_span():
    """The loader pattern: a producer thread joins the consumer's trace
    via an explicitly captured context."""
    rec = SpanRecorder()
    seen = {}

    def producer(ctx):
        with rec.propagated(ctx):
            with rec.span("producer") as sp:
                seen["sp"] = sp
        # Restored: ambient override gone once the block exits.
        assert rec.current_context() is None

    with rec.span("consumer") as consumer:
        t = threading.Thread(target=producer, args=(rec.current_context(),))
        t.start()
        t.join()
    assert seen["sp"].parent_id == consumer.span_id
    assert seen["sp"].trace_id == consumer.trace_id


def test_propagated_nests_and_restores():
    rec = SpanRecorder()
    a = TraceContext("t", "a")
    b = TraceContext("t", "b")
    with rec.propagated(a):
        assert rec.current_context() == a
        with rec.propagated(b):
            assert rec.current_context() == b
        assert rec.current_context() == a
        # An open span beats the ambient context.
        with rec.span("s") as sp:
            assert rec.current_context() == sp.context()
    assert rec.current_context() is None


def test_process_context_is_default_parent_on_any_thread():
    rec = SpanRecorder()
    job = TraceContext("job-trace", "job-root")
    rec.set_process_context(job)
    seen = {}

    def worker():
        with rec.span("on-thread") as sp:
            seen["sp"] = sp

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert seen["sp"].parent_id == "job-root"
    assert seen["sp"].trace_id == "job-trace"
    # A thread-level override wins over the process context.
    with rec.propagated(TraceContext("other", "o1")):
        with rec.span("override") as sp:
            assert sp.trace_id == "other"
    rec.set_process_context(None)
    with rec.span("fresh") as sp:
        assert sp.parent_id is None


def test_traceparent_wire_round_trip_and_tolerance():
    ctx = TraceContext("1a.2b-3", "1a.2b-7")
    header = prop.to_traceparent(ctx)
    assert header == "1a.2b-3;1a.2b-7"
    assert prop.from_traceparent(header) == ctx
    assert prop.to_traceparent(None) is None
    for bad in (None, "", "no-separator", ";x", "x;", 42):
        assert prop.from_traceparent(bad) is None


def test_env_for_child_round_trip():
    ctx = TraceContext("t1", "s1")
    env = prop.env_for_child(ctx)
    assert env == {prop.TRACEPARENT_ENV: "t1;s1"}
    assert prop.context_from_env(env) == ctx
    assert prop.context_from_env({}) is None


def test_inject_copies_and_extract_recovers():
    from raydp_tpu.telemetry import recorder, span

    with span("caller") as caller:
        original = {"a": 1}
        req = prop.inject(original)
        assert "traceparent" not in original  # copy, not mutation
        assert prop.extract(req) == caller.context()
        # An explicit caller-provided traceparent wins.
        pinned = prop.inject({"traceparent": "t;s"})
        assert prop.extract(pinned) == TraceContext("t", "s")
    assert prop.extract({"no": "header"}) is None
    assert prop.extract("not-a-mapping") is None
    assert prop.inject(None) is None
    recorder.drain()  # keep the global ring clean for other tests


# ---------------------------------------------------------------------
# RPC round-trip: one trace_id across the wire


def test_rpc_handler_span_joins_caller_trace():
    from raydp_tpu.cluster.rpc import RpcClient, RpcServer
    from raydp_tpu.telemetry import recorder, span

    seen = {}

    def handler(request):
        # Handler runs on a grpc pool thread with an empty stack — its
        # span must still join the caller's trace via the envelope.
        with span("rpc/handler") as sp:
            seen["handler"] = sp
        return {"echo": request.get("x")}

    server = RpcServer("raydp.TraceTest", {"Do": handler})
    client = RpcClient(server.address, "raydp.TraceTest")
    try:
        with span("rpc/caller") as caller:
            reply = client.call("Do", {"x": 7}, timeout=10.0)
        assert reply == {"echo": 7}
        assert seen["handler"].trace_id == caller.trace_id
        assert seen["handler"].parent_id == caller.span_id
        # Without a caller span (and no ambient), the handler span is a
        # fresh root — nothing leaked from the previous call's context.
        recorder.set_process_context(None)
        client.call("Do", {"x": 8}, timeout=10.0)
        assert seen["handler"].parent_id is None
    finally:
        client.close()
        server.stop()
        recorder.drain()


# ---------------------------------------------------------------------
# Dropped-span accounting


def test_dropped_spans_are_counted():
    rec = SpanRecorder(capacity=2)
    for i in range(5):
        with rec.span("s", i=i):
            pass
    assert rec.dropped == 3
    assert [s.attrs["i"] for s in rec.spans()] == [3, 4]
    # A flush empties the ring but the drop count is cumulative.
    rec.drain()
    with rec.span("s", i=5):
        pass
    assert rec.dropped == 3


def test_dropped_counter_renders_as_dedicated_family():
    view = {
        "workers": {
            "w0": {"counters": {"spans/dropped": 3, "worker/tasks": 9}},
        }
    }
    text = render_prometheus(view)
    assert 'raydp_spans_dropped_total{worker="w0"} 3' in text.splitlines()
    # Routed out of the generic counter family, not double-exported.
    assert 'name="spans/dropped"' not in text
    assert 'raydp_counter_total{name="worker/tasks",worker="w0"} 9' in text


# ---------------------------------------------------------------------
# Chrome-trace export golden


def _mk(pid, offset, name, span_id, parent, trace, start, dur,
        kind="span", tid=1, **attrs):
    """A span record whose aligned wall-clock start is ``start``: the
    process's monotonic clock is ``offset`` behind wall time."""
    return {
        "name": name,
        "span_id": span_id,
        "trace_id": trace,
        "parent_id": parent,
        "seq": int(span_id.split("-")[-1]),
        "start_wall": start,
        "start_mono": start - offset,
        "duration_s": dur,
        "status": "ok",
        "kind": kind,
        "attrs": attrs,
        "pid": pid,
        "tid": tid,
    }


def _golden_records():
    # Driver pid 1 (mono offset 1000s), workers pid 2/3 with wildly
    # different monotonic epochs — alignment must still interleave them
    # correctly on one timeline.
    recs = [
        _mk(1, 1000.0, "cluster/job", "a-1", None, "a-1", 1000.0, 0.0,
            kind="event"),
        _mk(1, 1000.0, "train/fit", "a-2", "a-1", "a-1", 1000.1, 10.0),
        _mk(2, 2000.0, "worker/task", "b-1", "a-2", "a-1", 1000.2, 9.0,
            worker_id="w0"),
        _mk(3, 3000.0, "worker/task", "c-1", "a-2", "a-1", 1000.2, 9.9,
            worker_id="w1"),
        _mk(2, 2000.0, "ingest/chunk", "b-9", "b-1", "a-1", 1000.3, 0.05),
    ]
    for i in range(4):
        recs.append(_mk(2, 2000.0, "train/step", f"b-{2 + i}", "b-1",
                        "a-1", 1001.0 + i, 0.1, step=i))
        recs.append(_mk(3, 3000.0, "train/step", f"c-{2 + i}", "c-1",
                        "a-1", 1001.0 + i, 0.2, step=i))
    return recs


def _write_shards(records, directory):
    by_pid = {}
    for rec in records:
        by_pid.setdefault(rec["pid"], []).append(rec)
    for pid, recs in by_pid.items():
        path = os.path.join(str(directory), f"spans-{pid}.jsonl")
        with open(path, "w", encoding="utf-8") as f:
            for rec in recs:
                f.write(json.dumps(rec) + "\n")


def test_chrome_trace_aligns_clocks_across_shards(tmp_path):
    _write_shards(_golden_records(), tmp_path)
    # Malformed tail (writer died mid-append) must not be fatal.
    with open(tmp_path / "spans-2.jsonl", "a", encoding="utf-8") as f:
        f.write('{"name": "torn wri')
    records = chrome_trace.load_span_records(str(tmp_path))
    assert len(records) == 13
    offsets = chrome_trace.clock_offsets(records)
    assert offsets == {1: 1000.0, 2: 2000.0, 3: 3000.0}
    # Sorted by *aligned* start: the job root first, despite shards
    # having incomparable raw monotonic values.
    assert [r["span_id"] for r in records[:3]] == ["a-1", "a-2", "b-1"]
    start, end = chrome_trace.aligned_interval(records[1], offsets)
    assert abs(start - 1000.1) < 1e-9 and abs(end - 1010.1) < 1e-9


def test_chrome_trace_golden_event_fields(tmp_path):
    trace = chrome_trace.to_chrome_trace(_golden_records())
    assert trace["displayTimeUnit"] == "ms"
    events = trace["traceEvents"]

    meta = [e for e in events if e["ph"] == "M"]
    names = {(e["name"], e["pid"]): e["args"]["name"] for e in meta}
    assert names[("process_name", 1)] == "driver"
    assert names[("process_name", 2)] == "worker w0"
    assert names[("process_name", 3)] == "worker w1"
    assert ("thread_name", 1) in names

    complete = {e["args"]["span_id"]: e for e in events if e["ph"] == "X"}
    fit = complete["a-2"]
    assert set(fit) == {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                        "args"}
    # Timeline is base-relative µs: fit starts 0.1s after the root.
    assert abs(fit["ts"] - 1e5) < 1.0
    assert abs(fit["dur"] - 10e6) < 1.0
    # Cross-process alignment: worker w1's first step sits 1.0s in.
    step = complete["c-2"]
    assert abs(step["ts"] - 1e6) < 1.0
    assert step["args"]["parent_id"] == "c-1"
    assert step["args"]["trace_id"] == "a-1"
    assert step["args"]["step"] == 0

    instants = [e for e in events if e["ph"] == "i"]
    assert [e["name"] for e in instants] == ["cluster/job"]
    assert instants[0]["ts"] == 0.0

    # Deterministic: same records → identical JSON (golden stability).
    assert chrome_trace.to_chrome_trace(_golden_records()) == trace


def test_write_chrome_trace_merges_shards(tmp_path):
    _write_shards(_golden_records(), tmp_path)
    out = chrome_trace.write_chrome_trace(str(tmp_path))
    assert out == str(tmp_path / "trace.json")
    loaded = json.load(open(out, encoding="utf-8"))
    assert {e["pid"] for e in loaded["traceEvents"]} == {1, 2, 3}


# ---------------------------------------------------------------------
# Analyzer


def test_analyzer_critical_path_and_skew_on_synthetic_trace():
    report = analyze.analyze_records(_golden_records())
    assert report["num_spans"] == 13
    assert report["num_processes"] == 3
    assert report["trace_id"] == "a-1"
    # Critical path descends into the last-finishing child at each hop:
    # the straggler worker w1 (9.9s task), then its last step.
    path = [(hop["name"], hop["process"]) for hop in report["critical_path"]]
    assert path == [
        ("cluster/job", "driver"),
        ("train/fit", "driver"),
        ("worker/task", "worker w1"),
        ("train/step", "worker w1"),
    ]
    assert report["critical_path"][0]["start_s"] == 0.0

    ranks = report["step_skew"]["ranks"]
    assert ranks["worker w0"]["steps"] == 4
    assert ranks["worker w0"]["p50_s"] == 0.1
    assert ranks["worker w1"]["p50_s"] == 0.2
    assert report["step_skew"]["slowest"] == "worker w1"
    assert report["step_skew"]["fastest"] == "worker w0"
    assert report["step_skew"]["skew_p50"] == 2.0

    split = report["data_compute"]
    assert abs(split["worker w0"]["data_s"] - 0.05) < 1e-9
    assert abs(split["worker w0"]["compute_s"] - 0.4) < 1e-9
    assert abs(split["worker w0"]["data_frac"] - 0.1111) < 1e-3

    text = analyze.format_report(report)
    assert "critical path:" in text
    assert "per-rank step skew:" in text
    assert "slowest: worker w1 (p50 skew 2.0x vs worker w0)" in text
    assert "data-wait vs compute:" in text


def test_analyze_cli(tmp_path, capsys):
    _write_shards(_golden_records(), tmp_path)
    chrome_out = tmp_path / "out" / "trace.json"
    rc = analyze.main(["--chrome", str(chrome_out), str(tmp_path)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "critical path:" in out
    assert "per-rank step skew:" in out
    assert chrome_out.exists()
    assert analyze.main([]) == 2  # usage error


# ---------------------------------------------------------------------
# Acceptance: two workers + estimator fit → one distributed trace


def test_two_worker_fit_produces_single_distributed_trace(tmp_path):
    """The ISSUE acceptance path: a two-worker run under
    RAYDP_TPU_TELEMETRY_DIR yields one merged Chrome trace whose driver,
    master, and worker spans all share the job trace_id, and the
    analyzer reports a critical path plus a per-rank skew table."""
    import numpy as np
    import pandas as pd

    import raydp_tpu
    from raydp_tpu.models.mlp import taxi_fare_regressor
    from raydp_tpu.telemetry import recorder
    from raydp_tpu.train.estimator import JAXEstimator

    # Nested so cloudpickle ships it by value.
    def _worker_steps(ctx):
        import time as _t

        from raydp_tpu.telemetry import flush_spans
        from raydp_tpu.telemetry import span as _span

        for i in range(3):
            with _span("train/step", step=i):
                _t.sleep(0.005)
        flush_spans()  # synchronous: shard exists when the RPC returns
        return "stepped"

    os.environ["RAYDP_TPU_TELEMETRY_DIR"] = str(tmp_path)
    recorder.clear()  # spans from earlier tests must not pollute shards
    s = raydp_tpu.init(app_name="tracing-acceptance", num_workers=2)
    try:
        workers = sorted(w.worker_id for w in s.cluster.alive_workers())
        assert len(workers) == 2
        for wid in workers:
            assert s.cluster.submit(
                _worker_steps, worker_id=wid, timeout=30.0
            ) == "stepped"

        rng = np.random.default_rng(0)
        df = pd.DataFrame(rng.random((128, 4)), columns=list("abcd"))
        df["y"] = df.a * 2 + df.b
        est = JAXEstimator(
            model=taxi_fare_regressor(),
            loss="mse",
            num_epochs=1,
            batch_size=64,
            feature_columns=list("abcd"),
            label_column="y",
            epoch_mode="stream",
        )
        est.fit_on_df(df)

        # Live report straight off the driver.
        live = s.cluster.trace_report()
        assert live is not None and live["num_spans"] > 0

        # Worker rings flush on 2s heartbeats; wait until both workers'
        # task spans (which carry the worker_id labels the analyzer
        # groups by) have landed before tearing the cluster down.
        def _tasks_flushed():
            recs = chrome_trace.load_span_records(str(tmp_path))
            ids = {
                r["attrs"].get("worker_id")
                for r in recs
                if r["name"] == "worker/task"
            }
            return ids >= set(workers)

        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline and not _tasks_flushed():
            time.sleep(0.5)
    finally:
        raydp_tpu.stop()
        os.environ.pop("RAYDP_TPU_TELEMETRY_DIR", None)

    records = chrome_trace.load_span_records(str(tmp_path))
    roots = [r for r in records if r["name"] == "cluster/job"]
    assert len(roots) == 1
    trace_id = roots[0]["trace_id"]

    # Driver + master (in-process) + both worker subprocesses all wrote
    # spans, and every process participates in the job trace.
    pids = {r["pid"] for r in records}
    assert len(pids) >= 3
    for pid in pids:
        assert any(
            r["trace_id"] == trace_id for r in records if r["pid"] == pid
        ), f"pid {pid} recorded no spans in the job trace"

    tasks = [r for r in records if r["name"] == "worker/task"]
    assert {t["attrs"]["worker_id"] for t in tasks} >= set(workers)
    assert all(t["trace_id"] == trace_id for t in tasks)
    # Worker-side steps parented under their RPC task span → same trace.
    worker_pids = pids - {roots[0]["pid"]}
    worker_steps = [
        r for r in records
        if r["name"] == "train/step" and r["pid"] in worker_pids
    ]
    assert len(worker_steps) >= 6
    assert all(r["trace_id"] == trace_id for r in worker_steps)
    # Driver-side estimator spans joined the same trace via the
    # process-level job context.
    fits = [r for r in records if r["name"] == "train/fit"]
    assert fits and all(r["trace_id"] == trace_id for r in fits)

    # One merged Chrome trace, dominated by the single job trace.
    out = chrome_trace.write_chrome_trace(str(tmp_path))
    trace = json.load(open(out, encoding="utf-8"))
    spans_x = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert {e["pid"] for e in spans_x} == pids
    in_job = [e for e in spans_x if e["args"].get("trace_id") == trace_id]
    assert len(in_job) / len(spans_x) > 0.9

    report = analyze.analyze_records(records)
    assert report["trace_id"] == trace_id
    assert report["critical_path"]
    assert report["critical_path"][0]["name"] == "cluster/job"
    ranks = report["step_skew"]["ranks"]
    assert sum(label.startswith("worker") for label in ranks) >= 2
    text = analyze.format_report(report)
    assert "critical path:" in text
    assert "per-rank step skew:" in text
    assert "slowest:" in text


# ---------------------------------------------------------------------
# The profiler bridge: every span() is also a jax.profiler annotation


def _tiny_fit_estimator(rows=256, batch=64):
    import numpy as np
    import pandas as pd

    from raydp_tpu.models.mlp import taxi_fare_regressor
    from raydp_tpu.train.estimator import JAXEstimator

    rng = np.random.default_rng(0)
    df = pd.DataFrame(rng.random((rows, 4)), columns=list("abcd"))
    df["y"] = df.a * 2 + df.b
    est = JAXEstimator(
        model=taxi_fare_regressor(), loss="mse", num_epochs=1,
        batch_size=batch, feature_columns=list("abcd"), label_column="y",
        epoch_mode="stream",
    )
    return est, df


def _host_annotations(trace_dir):
    """``[(name, start_ns, end_ns, stats, line index)]`` of the host
    plane of the newest profile under ``trace_dir``."""
    import glob
    import warnings

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"
    )))[-1]
    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:CPU"):
                continue
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats), i))
    return out


def test_fit_spans_land_on_the_profile_nested_as_the_parent_links(tmp_path):
    import jax

    from raydp_tpu.telemetry import recorder

    est, df = _tiny_fit_estimator()
    est.fit_on_df(df)  # compile outside the profile
    recorder.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        est.fit_on_df(df)
    finally:
        jax.profiler.stop_trace()
    notes = _host_annotations(str(tmp_path))
    by_name = {}
    for note in notes:
        by_name.setdefault(note[0], []).append(note)

    steps = sorted(by_name["train/step"], key=lambda n: n[1])
    assert len(steps) == 4
    # Step annotations: the profiler groups device ops under step_num, the
    # optimizer step (the first fit took steps 0-3).
    assert [s[3]["step_num"] for s in steps] == [4, 5, 6, 7]
    assert [s[3]["step"] for s in steps] == [0, 1, 2, 3]
    assert all(s[3]["_r"] == 1 for s in steps)
    for name, at_least in (("infeed/put", 4), ("ingest/wait", 4),
                           ("train/loss_fetch", 1), ("train/epoch_end", 1),
                           ("train/epoch", 1), ("train/fit", 1),
                           ("df/from_pandas", 1)):
        assert len(by_name.get(name, [])) >= at_least, name
    assert by_name["train/epoch"][0][3] == {"epoch": 0, "mode": "stream"}

    # Nesting in the profile follows the recorder's parent links: a span
    # and its parent recorded on one thread are one annotation inside the
    # other, on one line.
    spans = {s.span_id: s for s in recorder.spans()}
    fit = by_name["train/fit"][0]
    checked = 0
    for sp in spans.values():
        parent = spans.get(sp.parent_id)
        if parent is None or parent.tid != sp.tid or sp.kind != "span":
            continue
        assert any(
            o[4] == i[4] and o[1] <= i[1] and i[2] <= o[2]
            for i in by_name[sp.name] for o in by_name[parent.name]
        ), (sp.name, parent.name)
        checked += 1
    assert checked >= 10
    # train/epoch > train/step, infeed/put, ingest/wait; the loss fetch
    # and the epoch's tail follow the epoch span inside train/fit.
    epoch = by_name["train/epoch"][0]
    for name in ("train/step", "infeed/put", "ingest/wait"):
        assert all(epoch[1] <= n[1] and n[2] <= epoch[2]
                   for n in by_name[name]), name
    fetch, tail = by_name["train/loss_fetch"][0], by_name["train/epoch_end"][0]
    assert epoch[2] <= fetch[1] <= fetch[2] <= tail[1] <= tail[2] <= fit[2]


def test_spans_without_a_profile_are_the_seeds():
    """No profile running: what the recorder holds of the spans the seed
    had is unchanged in names, attrs and order (``step_num`` is the
    annotation's, not an attr), and the new ones are there beside them."""
    from raydp_tpu.telemetry import recorder

    est, df = _tiny_fit_estimator()
    recorder.clear()
    est.fit_on_df(df)
    main = threading.get_ident()
    mine = [s for s in recorder.spans() if s.tid == main and s.kind == "span"]
    new = {"infeed/put", "ingest/wait", "train/loss_fetch", "train/epoch_end",
           "df/action", "df/from_pandas", "mesh/build", "train/init_state",
           "train/build_steps", "train/first_dispatch"}
    seeds = [(s.name, s.attrs) for s in mine if s.name not in new]
    assert seeds == [
        ("train/step", {"epoch": 0, "step": 0}),
        ("train/step", {"epoch": 0, "step": 1}),
        ("train/step", {"epoch": 0, "step": 2}),
        ("train/step", {"epoch": 0, "step": 3}),
        ("train/epoch", {"epoch": 0, "mode": "stream"}),
        ("train/fit", {"epochs": 1}),
    ]
    assert {s.name for s in mine} >= new - {"df/action"}
    assert all(s.end_mono is not None and s.status == "ok" for s in mine)


def test_a_span_does_not_import_jax():
    """A process that has not imported jax does not import it because of
    a span. (``import raydp_tpu`` imports jax today, so the recorder's
    module is loaded by path here, as a leaner worker would have it.)"""
    import subprocess
    import sys

    code = (
        "import importlib.util, sys\n"
        "spec = importlib.util.spec_from_file_location(\n"
        "    'spans', 'raydp_tpu/telemetry/spans.py')\n"
        "spans = importlib.util.module_from_spec(spec)\n"
        "sys.modules['spans'] = spans\n"
        "spec.loader.exec_module(spans)\n"
        "assert 'jax' not in sys.modules, 'imported with the recorder'\n"
        "r = spans.SpanRecorder()\n"
        "with r.span('worker/task', step_num=3, op='x'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'imported by a span'\n"
        "assert [s.name for s in r.spans()] == ['worker/task']\n"
        "assert r.spans()[0].attrs == {'op': 'x'}\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=repo, capture_output=True,
        text=True, timeout=120,
    )
    assert done.returncode == 0, done.stderr


def test_span_opened_before_the_profile_starts_is_not_in_it(tmp_path):
    """The benchmark starts its profile from an epoch-end callback, inside
    ``train/epoch_end``: that annotation is simply not in the profile."""
    import jax

    rec = SpanRecorder()
    with rec.span("train/epoch_end", epoch=0):
        jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("train/epoch", epoch=1):
            pass
    finally:
        jax.profiler.stop_trace()
    names = [n[0] for n in _host_annotations(str(tmp_path))]
    assert "train/epoch" in names and "train/epoch_end" not in names
    assert [s.name for s in rec.spans()] == ["train/epoch_end", "train/epoch"]


def test_span_closed_after_the_profile_stops_is_harmless(tmp_path):
    import jax

    rec = SpanRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("train/epoch", epoch=0):
            pass
        cm = rec.span("train/epoch_end", epoch=0)
        cm.__enter__()
    finally:
        jax.profiler.stop_trace()
    cm.__exit__(None, None, None)
    with rec.span("train/epoch", epoch=1):
        pass
    assert [s.name for s in rec.spans()] == [
        "train/epoch", "train/epoch_end", "train/epoch"
    ]
    assert all(s.end_mono is not None for s in rec.spans())
    names = [n[0] for n in _host_annotations(str(tmp_path))]
    assert names.count("train/epoch") == 1


# ---------------------------------------------------------------------
# The train step's scopes: every op of the compiled step falls into a part


def _compiled_step_text(config_name, mode, monkeypatch):
    """Compiled HLO text of the tiny configuration's train step, built by
    the benchmark's builder through ``JAXEstimator`` (stream: the jitted
    step; scan: the jitted epoch of two steps)."""
    import sys

    import jax
    import numpy as np

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (os.path.join(repo, "tests", "benchmark"),
              os.path.join(repo, "benchmark")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import bench_tree
    import harness

    import raydp_tpu.train.estimator as estimator_module
    from raydp_tpu.parallel import MeshSpec

    # The guard wraps the jitted function in a plain one; lower the jitted.
    monkeypatch.setattr(estimator_module, "_guard_compile", lambda f, _: f)
    sizes = dict(bench_tree.TINY_CONFIGS[config_name])
    if "vocab_sizes" in sizes:
        # Eight tables (the published model has 26): with four, the loss
        # alone is an eighth of the tiny step's instructions.
        sizes["vocab_sizes"] = sizes["vocab_sizes"] + [20, 30, 40, 60]
    model = harness.load_module(os.path.join(
        repo, "benchmark", "configs", sizes["builder"] + ".py"
    ))
    traffic = {"seq_len": 16, "per_chip_batch": 8}
    mesh = MeshSpec(dp=1)
    est = estimator_module.JAXEstimator(
        **model.estimator_kwargs(sizes, traffic, mesh), batch_size=8,
        mesh=mesh, seed=1, epoch_mode=mode,
    )
    x = model.check_batch(sizes, traffic, 1)
    y = np.zeros(len(x), est.label_dtype)
    est._init_state(x)
    key = jax.random.PRNGKey(0)
    if mode == "stream":
        xd, yd = est._shard_batch(x, y)
        lowered = est._train_step.lower(est._state, xd, yd, key)
    else:
        lowered = est._build_epoch_fn(2, len(x) // 2).lower(
            est._state, x, y, key
        )
    return sizes["builder"], lowered.compile().as_text()


@pytest.mark.parametrize("mode", ["stream", "scan"])
@pytest.mark.parametrize("config_name", ["bert_tiny", "dlrm_tiny"])
def test_compiled_step_splits_into_parts(config_name, mode, monkeypatch):
    import re

    builder, text = _compiled_step_text(config_name, mode, monkeypatch)
    import program_trace

    assert "part:update" in text and "part:grad_norm" in text
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "parts", builder + ".json")) as f:
        rules = program_trace.compile_rules(json.load(f))
    # Scope paths start at the jitted function; a parameter's name
    # (``state.params[...]``) and a reducer's body (``reduce_sum``) are not
    # operations of the step with a scope.
    scopes = [s for s in re.findall(r'op_name="([^"]*)"', text)
              if s.startswith("jit(")]
    if mode == "scan":
        # The step is the body of the epoch's scan; the loop's own
        # plumbing and the epoch's shuffle are not the step's.
        body = re.compile(r"^jit\([^)]*\)/while/body/closed_call/")
        scopes = [s for s in scopes if body.match(s)]
    counts = {}
    for scope in scopes:
        part = program_trace.part_of(scope, rules)
        counts[part] = counts.get(part, 0) + 1
    named = {part for _, part in rules}
    assert set(counts) - {"rest"} == named, counts
    assert counts.get("rest", 0) < sum(counts.values()) / 5, counts
    # Backward work lands with its part: the transposed paths match too.
    backward = [s for s in scopes if "transpose(jvp(" in s]
    assert backward and any(
        program_trace.part_of(s, rules) != "rest" for s in backward
    )
