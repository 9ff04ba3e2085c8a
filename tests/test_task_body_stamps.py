"""An ETL task's body and the hand-off accounted for from inside (ISSUE
65): a worker stamps a body's fetches, puts and registrations where
``WorkerContext`` does them, the stamps ride the task's reply,
``_StageRecorder`` sums them over all the stage's bodies into
``StageStats``, and the loader's ``_materialize`` runs under
``handoff/*`` spans of its own."""
import threading
import time
from concurrent.futures import Future

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import raydp_tpu
import raydp_tpu.dataframe as rdf
from raydp_tpu.cluster import worker_main as wm
from raydp_tpu.cluster.cluster import TaskSpec, task_stamps
from raydp_tpu.data.ml_dataset import MLDataset
from raydp_tpu.dataframe import executor as E
from raydp_tpu.dataframe.scheduler import PendingPartition
from raydp_tpu.telemetry import recorder
from raydp_tpu.telemetry.progress import StageStats, stage_store

PARTS = ("submit_s", "transit_s", "load_s", "exec_s", "driver_s")
BODY = ("fetch_s", "put_s", "register_s")
HANDOFF = ("handoff/materialize", "handoff/await_blocks", "handoff/fetch",
           "handoff/convert")


@pytest.fixture(scope="module")
def session():
    s = raydp_tpu.init(app_name="bodyparts", num_workers=2,
                       memory_per_worker="256MB")
    yield s
    raydp_tpu.stop()


def _frame(rows=4000, parts=4):
    return rdf.from_pandas(
        pd.DataFrame({"k": np.arange(rows) % 8, "v": np.arange(rows) * 1.0}),
        num_partitions=parts,
    )


def _records_since(first):
    return [s for s in (stage_store.get(i)
                        for i in range(first + 1, stage_store.last_id() + 1))
            if s is not None and s.executor == "cluster"]


# -------------------------------------- (a) the stamps reach StageStats

@pytest.mark.parametrize("action", ["count", "map_batches"])
def test_a_stage_holds_what_its_bodies_are_made_of(session, monkeypatch,
                                                   action):
    """Every body of the stage is in the sums, the three stamped parts are
    inside the bodies, and the stage's wall is partitioned as before."""
    monkeypatch.setenv("RAYDP_TPU_STREAMING", "0")
    df = _frame()
    first = stage_store.last_id()
    if action == "count":
        assert len(df.groupBy("k").count().to_pandas()) == 8
    else:
        assert len(df.map_batches(lambda t: t).to_pandas()) == 4000
    records = _records_since(first)
    assert records
    for s in records:
        tasks = sum(s.workers.values())
        assert s.tasks_stamped == tasks > 0
        assert all(getattr(s, k) > 0.0 for k in BODY), s.to_dict()
        assert sum(getattr(s, k) for k in BODY) <= s.body_s
        # Sums over ALL bodies: at least the critical envelope's union.
        assert s.body_s >= s.exec_s - 1e-9
        assert sum(getattr(s, k) for k in PARTS) == pytest.approx(
            s.wall_s, rel=0.01)
        d = s.to_dict()
        assert {*BODY, "body_s", "tasks_stamped"} <= set(d)
        assert d["tasks_stamped"] == tasks
    shown = stage_store.snapshot()["stages"][-1]
    assert shown["body_s"] == round(records[-1].body_s, 6)


def test_stage_close_carries_no_new_attr(session, monkeypatch):
    """The sums are work, not wall: ``stage/close`` keeps the parent's
    attrs to the name."""
    monkeypatch.setenv("RAYDP_TPU_STREAMING", "0")
    recorder.clear()
    _frame().groupBy("k").count().to_pandas()
    close = [sp for sp in recorder.spans() if sp.name == "stage/close"][-1]
    assert set(close.attrs) == {
        "stage", "op", "submit_us", "transit_us", "load_us", "exec_us",
        "driver_us", "envelopes",
    }


def test_explain_analyze_prints_the_parts_of_a_cluster_stage(session,
                                                             monkeypatch):
    monkeypatch.setenv("RAYDP_TPU_STREAMING", "0")
    text = _frame(400).groupBy("k").count().explain(analyze=True, quiet=True)
    line = next(ln for ln in text.splitlines() if "[cluster]" in ln)
    for word in ("fetch", "compute", "put", "register"):
        assert f"{word} " in line
    assert "task bod" in line
    for word in ("submit", "transit", "load", "exec", "driver"):
        assert f"{word} " in line


# ------------------------------------- (b) side by side, and outside

def test_two_tasks_of_one_envelope_keep_their_parts_apart(session):
    """Both tasks go to one worker in one envelope and run side by side on
    its pool; one sleeps inside a fetch. The other's fetch does not grow,
    and neither list holds the other's stamps."""
    df = _frame(800, parts=2)
    ref = df.to_object_refs()[0]
    worker = sorted(w.worker_id for w in session.cluster.alive_workers())[0]

    def body(ctx, ref, nap):
        import time as _t

        # The worker runs ``worker_main`` as its ``__main__``: that copy's
        # thread-local holds the open body.
        import __main__ as _wm

        if nap:
            with _wm._stamped("fetch"):
                _t.sleep(nap)
        table = ctx.get_table(ref)
        return ctx.put_table(table, holder=True).num_rows

    seen = {}

    def sink(index, worker_id, exec_s, stamps):
        seen[index] = (worker_id, stamps)

    futures = session.cluster.submit_batch(
        [TaskSpec(body, (ref, 0.2), worker_id=worker),
         TaskSpec(body, (ref, 0.0), worker_id=worker)],
        meta_sink=sink,
    )
    assert [f.result(timeout=60) for f in futures] == [400, 400]
    (w0, slow), (w1, fast) = seen[0], seen[1]
    assert w0 == w1 == worker and slow["env"] == fast["env"]

    def total(stamps, kind):
        return sum(b - a for k, a, b in stamps["parts"] if k == kind)

    for stamps in (slow, fast):
        kinds = [k for k, _, _ in stamps["parts"]]
        # The nap and the fetch that follows it are ONE interval.
        assert kinds == ["fetch", "put", "register"]
        assert all(stamps["start"] <= a <= b <= stamps["end"]
                   for _, a, b in stamps["parts"])
    assert total(slow, "fetch") >= 0.2
    assert total(fast, "fetch") < 0.1
    assert fast["end"] - fast["start"] < 0.15
    # Side by side: the fast body ended while the slow one slept.
    assert fast["end"] < slow["end"]


class _Store:
    node_id = "n0"

    def put_arrow_table(self, table, owner=None):
        return ("ref", table.num_rows, owner)

    def put(self, data, owner=None):
        return ("ref", len(data), owner)


class _Master:
    def __init__(self):
        self.calls = []

    def call(self, method, payload):
        self.calls.append(method)
        return {}


def test_a_call_outside_any_body_records_nothing():
    master = _Master()
    ctx = wm.WorkerContext("w9", "n0", _Store(), master)
    table = pa.table({"a": [1, 2, 3]})
    assert getattr(wm._body, "parts", None) is None
    assert ctx.put_table(table) == ("ref", 3, "w9")
    assert ctx.put_bytes(b"abcd") == ("ref", 4, "w9")
    assert master.calls == ["RegisterObject", "RegisterObject"]
    assert getattr(wm._body, "parts", None) is None
    # Inside a body the same calls are stamped, one interval a run of a
    # kind; another thread's body is another list.
    other = {}

    def elsewhere():
        with wm._task_body() as parts:
            ctx.put_bytes(b"x")
            other["parts"] = parts

    with wm._task_body() as parts:
        ctx.put_table(table)
        t = threading.Thread(target=elsewhere)
        t.start()
        t.join()
        ctx.put_bytes(b"abcd")
    assert [k for k, _, _ in parts] == ["put", "register", "put", "register"]
    assert [k for k, _, _ in other["parts"]] == ["put", "register"]
    assert all(a <= b for _, a, b in parts)
    assert getattr(wm._body, "parts", None) is None


# --------------------------------------------- (c) replies without stamps

BODY_S = 0.0004


def _reply(t, parts=None, failed=False):
    """One task's reply on the driver's own clock, so the record it lands
    in is a partition like any other in the store."""
    if failed:
        return {"ok": False}
    res = {"start": t + 0.0003, "end": t + 0.0003 + BODY_S}
    if parts is not None:
        res["parts"] = [[k, t + a, t + b] for k, a, b in parts]
    return res


@pytest.mark.parametrize("replies, stamped, fetch", [
    # An older worker: start and end, no parts.
    ([{}, {}], 0, 0.0),
    # One of two tasks stamped; a kind this driver does not know is
    # left out and raises nothing.
    ([{"parts": [("fetch", 0.0003, 0.0004), ("spill", 0.0004, 0.0005)]},
      {}], 1, 0.0001),
    # A failed task's reply has no stamps at all.
    ([{"failed": True}, {"failed": True}], 0, 0.0),
])
def test_a_reply_without_the_stamps_leaves_the_fields_empty(replies, stamped,
                                                            fetch):
    rec = E._StageRecorder("old_worker", [], "cluster", total_tasks=2)
    sink = rec.round()
    t = time.perf_counter()
    envelope = {"env": 1, "send": t, "recv": t + 0.0002, "ret": t + 0.0008,
                "reply": t + 0.001}
    for i, kw in enumerate(replies):
        sink(i, "w0", BODY_S, task_stamps(envelope, _reply(t, **kw)))
    time.sleep(0.002)
    rec.finish([])
    rec.close()
    s = stage_store.get(rec.stage_id)
    assert sum(s.workers.values()) == 2
    assert s.tasks_stamped == stamped < 2
    assert s.fetch_s == pytest.approx(fetch)
    assert s.put_s == s.register_s == 0.0
    assert s.body_s == pytest.approx(BODY_S * stamped)
    assert sum(getattr(s, k) for k in PARTS) == pytest.approx(s.wall_s)
    assert all(getattr(s, k) >= 0.0 for k in PARTS) and s.exec_s <= s.wall_s


def test_a_record_without_the_fields_still_builds():
    s = StageStats(stage_id=1, op="x", executor="local")
    assert (s.fetch_s, s.put_s, s.register_s, s.body_s) == (0.0,) * 4
    assert s.tasks_stamped == 0 and s.to_dict()["tasks_stamped"] == 0


# ------------------------------------------- (d) the hand-off's spans

def _block(lo, hi):
    idx = np.arange(lo, hi, dtype=np.float64)
    return pa.table({"a": idx, "b": idx * 2, "y": idx % 2})


def _handoff_spans():
    return [sp for sp in recorder.spans() if sp.name in HANDOFF]


def test_materialize_runs_under_spans_of_its_own():
    """A dataset over a block that lands 50 ms after the consumer began
    to wait for it: the wait is ``handoff/await_blocks``, the rest
    ``handoff/fetch`` and ``handoff/convert``, all inside
    ``handoff/materialize``; a second ``_materialize`` (the columns are
    cached) opens none."""
    waiting = threading.Event()

    class Awaited(Future):
        """Says when a consumer asks for its result: the wait has begun
        (under the span, which is open by then), whatever the machine's
        load did to the thread's start."""

        def result(self, timeout=None):
            waiting.set()
            return super().result(timeout)

    futs = [Awaited(), Awaited()]

    def land():
        assert waiting.wait(30)
        time.sleep(0.05)
        futs[0].set_result(_block(0, 60))
        futs[1].set_result(_block(60, 100))

    ds = MLDataset([PendingPartition(f, i, "etl") for i, f in enumerate(futs)],
                   num_shards=1)
    loader = ds.to_jax(["a", "b"], "y", batch_size=16, rank=0, shuffle=True,
                       device=None, prefetch=0)
    recorder.clear()
    threading.Thread(target=land, daemon=True).start()
    cols = loader._materialize()
    assert sorted(cols) == ["a", "b", "y"] and len(cols["a"]) == 100
    spans = {sp.name: sp for sp in _handoff_spans()}
    assert sorted(spans) == sorted(HANDOFF)
    outer = spans["handoff/materialize"]
    assert spans["handoff/await_blocks"].duration_s >= 0.05
    for name in HANDOFF[1:]:
        sp = spans[name]
        assert sp.tid == outer.tid
        assert outer.start_mono <= sp.start_mono
        assert sp.end_mono <= outer.end_mono
        assert sp.parent_id == outer.span_id
    assert (spans["handoff/await_blocks"].end_mono
            <= spans["handoff/fetch"].start_mono
            <= spans["handoff/convert"].start_mono)
    assert outer.attrs == {"rank": 0, "blocks": 2, "rows": 100,
                           "bytes": 3 * 100 * 8}
    assert spans["handoff/await_blocks"].attrs["pending"] == 2
    recorder.clear()
    assert loader._materialize() is cols
    assert _handoff_spans() == []


def test_a_dataset_whose_plan_exists_awaits_nothing():
    ds = MLDataset([_block(0, 50), _block(50, 100)], num_shards=2)
    recorder.clear()
    loader = ds.to_jax(["a"], "y", batch_size=10, rank=1, shuffle=False,
                       device=None, prefetch=0)
    batches = list(loader)
    assert len(batches) == 5
    names = [sp.name for sp in _handoff_spans()]
    assert sorted(names) == ["handoff/convert", "handoff/fetch",
                             "handoff/materialize"]
    # A second epoch reads the staged matrix: none of the four.
    recorder.clear()
    assert len(list(loader)) == 5
    assert _handoff_spans() == []
