"""A cluster stage accounted for from inside (ISSUE 34): the worker's own
stamps ride the task reply, ``_StageRecorder`` partitions a stage's wall
along its critical path into submit, transit, load, exec and driver
seconds, and the worker's task bodies can be placed inside the driver's
``stage/envelope`` spans with no clock in common."""
import json
import time

import numpy as np
import pandas as pd
import pytest

import raydp_tpu
import raydp_tpu.dataframe as rdf
from raydp_tpu.dataframe import executor as E
from raydp_tpu.dataframe.scheduler import resolve
from raydp_tpu.telemetry import recorder
from raydp_tpu.telemetry.progress import stage_store

PARTS = ("submit_s", "transit_s", "load_s", "exec_s", "driver_s")


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    import os

    shards = tmp_path_factory.mktemp("telemetry")
    os.environ["RAYDP_TPU_TELEMETRY_DIR"] = str(shards)
    s = raydp_tpu.init(app_name="stagepart", num_workers=2,
                       memory_per_worker="256MB")
    s.shards = str(shards)
    yield s
    raydp_tpu.stop()
    os.environ.pop("RAYDP_TPU_TELEMETRY_DIR", None)


def _refs(rows=4000, parts=4):
    df = rdf.from_pandas(
        pd.DataFrame({"k": np.arange(rows) % 8, "v": np.arange(rows) * 1.0}),
        num_partitions=parts,
    )
    return df._executor, df.to_object_refs()


def _last_stage():
    return stage_store.get(stage_store.last_id())


def _run_narrow(ex, refs, fn=lambda t: t):
    return resolve(ex.map_partitions(refs, fn))


def _run_exchange(ex, refs):
    def split(t):
        half = t.num_rows // 2
        return [t.slice(0, half), t.slice(half)]

    return ex.exchange(refs, split, 2)


@pytest.mark.parametrize("kind", ["narrow", "exchange", "streaming"])
def test_parts_sum_to_wall(session, monkeypatch, kind):
    """The five parts are a partition of the stage's wall: they sum to
    it (within 1%), none is negative, and ``queue_s`` is transit + load,
    whether the stage has one round, two (an exchange: split, merge) or
    streams."""
    monkeypatch.setenv("RAYDP_TPU_STREAMING",
                       "1" if kind == "streaming" else "0")
    ex, refs = _refs()
    (_run_exchange if kind == "exchange" else _run_narrow)(ex, refs)
    s = _last_stage()
    assert s.executor == "cluster"
    assert s.op == ("exchange" if kind == "exchange" else "map_partitions")
    values = [getattr(s, k) for k in PARTS]
    assert all(v >= 0.0 for v in values), s.to_dict()
    assert sum(values) == pytest.approx(s.wall_s, rel=0.01)
    assert s.queue_s == pytest.approx(s.transit_s + s.load_s)
    assert 0.0 < s.exec_s <= s.wall_s
    assert s.upstream_s <= s.driver_s + 1e-9
    assert set(PARTS) | {"upstream_s", "queue_s"} <= set(s.to_dict())
    # Both workers ran tasks, one envelope each a round.
    assert sum(s.workers.values()) == (6 if kind == "exchange" else 4)


def test_parallel_bodies_do_not_zero_the_queue(session, monkeypatch):
    """Four 100 ms bodies on two workers: the old formula (wall less the
    task seconds of ALL workers) takes off the other worker's bodies
    too, so it reads under the measured ``queue_s`` — 0 on a quiet host;
    the measured one is transit + load of the critical envelope, and
    ``exec_s`` is that envelope's bodies (their union), not what the
    workers spent together."""
    monkeypatch.setenv("RAYDP_TPU_STREAMING", "0")
    task_seconds = []
    task_meta = E._StageRecorder._task_meta

    def spy(self, rnd, index, worker_id, exec_s, stamps=None):
        task_seconds.append(exec_s)
        return task_meta(self, rnd, index, worker_id, exec_s, stamps)

    monkeypatch.setattr(E._StageRecorder, "_task_meta", spy)
    ex, refs = _refs()

    def body(t):
        import time as _t

        _t.sleep(0.1)
        return t

    _run_narrow(ex, refs, body)
    s = _last_stage()
    assert len(task_seconds) == 4 and min(task_seconds) >= 0.1
    # One envelope's two bodies, never all four: the other worker's two
    # (at least 0.2 s of task seconds) are not in it — side by side or one
    # after the other (a ThreadPoolExecutor starts a second thread only
    # if none looked idle at the submit).
    assert 0.09 <= s.exec_s <= sum(task_seconds) - 0.19
    assert s.queue_s == pytest.approx(s.transit_s + s.load_s)
    assert s.queue_s > 0.0
    assert sum(getattr(s, k) for k in PARTS) == pytest.approx(
        s.wall_s, rel=0.01
    )
    # The old reading differs from the measured one by driver_s + exec_s
    # less ALL task seconds: under it while the driver's own share of the
    # wall stays below the other worker's 0.2 s.
    old = max(0.0, s.wall_s - s.submit_s - sum(task_seconds))
    assert old < s.queue_s


@pytest.mark.parametrize("where", ["body", "load"])
def test_sleep_lands_in_its_own_part(session, monkeypatch, where):
    """A body that sleeps shows in ``exec_s``; a fn whose unpickling
    sleeps shows in ``load_s``; neither shows in the other."""
    monkeypatch.setenv("RAYDP_TPU_STREAMING", "0")
    ex, refs = _refs()

    def slow_body(t):
        import time as _t

        _t.sleep(0.08)
        return t

    def _wake_slowly(seconds):
        import time as _t

        _t.sleep(seconds)
        return lambda t: t

    class SlowToLoad:
        def __call__(self, t):
            return t

        def __reduce__(self):
            return (_wake_slowly, (0.08,))

    _run_narrow(ex, refs)
    _run_narrow(ex, refs, slow_body if where == "body" else SlowToLoad())
    s = _last_stage()
    slept, other = (
        (s.exec_s, s.load_s) if where == "body" else (s.load_s, s.exec_s)
    )
    assert slept >= 0.075, s.to_dict()
    assert other < 0.04, s.to_dict()


def test_stage_close_lists_every_envelope(session, monkeypatch):
    """``stage/close`` opens with the stage's parts and, for each envelope
    of both rounds of an exchange, the worker's interval and its bodies
    relative to ``recv`` — in a form a profiler annotation can carry (its
    attrs travel inside its name: no ``,``, ``=`` or ``#``)."""
    monkeypatch.setenv("RAYDP_TPU_STREAMING", "0")
    ex, refs = _refs()
    recorder.clear()
    _run_exchange(ex, refs)
    spans = recorder.spans()
    close = [sp for sp in spans if sp.name == "stage/close"][-1]
    envelopes = {
        sp.attrs["env"]: sp for sp in spans if sp.name == "stage/envelope"
    }
    text = close.attrs["envelopes"]
    assert not set(text) & set(",=#")
    listed = [item.split(":") for item in text.split(";")]
    assert len(listed) == 4
    for env, worker, worker_us, bodies in listed:
        sp = envelopes[int(env)]
        assert worker == sp.attrs["worker"]
        assert int(worker_us) == sp.attrs["worker_us"]
        assert (sp.end_mono - sp.start_mono) * 1e6 > int(worker_us)
        for body in bodies.split("+"):
            start, end = (int(v) for v in body.split("-"))
            assert 0 <= start <= end <= int(worker_us)
    # The close span's parts are the record's (its own duration comes
    # on top of driver_us).
    s = stage_store.get(close.attrs["stage"])
    assert close.attrs["exec_us"] == round(s.exec_s * 1e6)
    assert close.attrs["transit_us"] == round(s.transit_s * 1e6)
    assert close.attrs["driver_us"] <= round(s.driver_s * 1e6) + 1
    assert E.format_envelopes([
        {"env": 8, "worker": "host:3,x", "recv": 5.0, "ret": 5.5,
         "bodies": []},
    ]) == "8:host_3_x:500000:"


def test_explain_analyze_prints_the_partition(session, monkeypatch):
    monkeypatch.setenv("RAYDP_TPU_STREAMING", "0")
    df = rdf.from_pandas(
        pd.DataFrame({"k": np.arange(400) % 5, "v": np.arange(400.0)}),
        num_partitions=4,
    )
    text = df.groupBy("k").count().explain(analyze=True)
    line = next(ln for ln in text.splitlines() if "[cluster]" in ln)
    for word in ("submit", "transit", "load", "exec", "driver"):
        assert f"{word} " in line
    assert "dispatch" not in line and "queue" not in line


def test_every_cluster_record_is_a_partition(session):
    """After the runs above: in every retained cluster record the five
    parts sum to the wall, ``queue_s`` is transit + load and ``exec_s``
    fits inside the wall; a local record carries no partition."""
    records = [s for s in stage_store.recent(512) if s.executor == "cluster"]
    assert len(records) >= 8
    for s in records:
        assert sum(getattr(s, k) for k in PARTS) == pytest.approx(
            s.wall_s, rel=0.01, abs=1e-6
        )
        assert s.queue_s == pytest.approx(s.transit_s + s.load_s)
        assert s.exec_s <= s.wall_s


def test_worker_task_parents_under_the_envelope(session):
    """``worker/task`` (and ``worker/task_load``) still land in the
    driver's trace — under ``stage/envelope`` now, which is under
    ``df/stage`` — and the analyzer's critical path still names the
    stage."""
    from raydp_tpu.telemetry import analyze, chrome_trace, flush_spans

    _run_narrow(*_refs())
    flush_spans()
    workers = {w.worker_id for w in session.cluster.alive_workers()}

    def stage_tasks():
        """(records, the worker-side spans whose parent is an envelope
        of a stage). An ingest's put is an envelope too, under the job's
        root and no stage."""
        records = chrome_trace.load_span_records(session.shards)
        by_id = {r["span_id"]: r for r in records}

        def parent(r):
            return by_id.get(r["parent_id"]) or {"name": None}

        return records, by_id, [
            r for r in records
            if r["name"] in ("worker/task", "worker/task_load")
            and parent(r)["name"] == "stage/envelope"
            and parent(parent(r))["name"] == "df/stage"
        ]

    deadline = time.monotonic() + 20.0
    records, by_id, tasks = stage_tasks()
    while time.monotonic() < deadline and (
        {t["attrs"]["worker_id"] for t in tasks} != workers
    ):
        time.sleep(0.5)  # worker rings flush on 2 s heartbeats
        records, by_id, tasks = stage_tasks()
    assert {t["name"] for t in tasks} == {"worker/task", "worker/task_load"}
    assert {t["attrs"]["worker_id"] for t in tasks} == workers
    for t in tasks:
        envelope = by_id[t["parent_id"]]
        assert envelope["attrs"]["worker"] == t["attrs"]["worker_id"]
        assert envelope["pid"] != t["pid"]
        assert t["trace_id"] == envelope["trace_id"]
    # The analyzer over the stage's own subtree (the job's root span is
    # open as long as the session is): the path starts at the stage and
    # descends into what it waited for last, its close.
    stage_id = by_id[tasks[-1]["parent_id"]]["parent_id"]
    subtree = {stage_id}
    for r in sorted(records, key=lambda r: r["start_wall"]):
        if r["parent_id"] in subtree:
            subtree.add(r["span_id"])
    report = analyze.analyze_records(
        [r for r in records if r["span_id"] in subtree]
    )
    path = [p["name"] for p in report["critical_path"]]
    assert path[0] == "df/stage" and path[1] in (
        "stage/close", "stage/envelope"
    )
    assert json.dumps(report)  # serialisable, as the CLI prints it
