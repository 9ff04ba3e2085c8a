"""Serving plane tests: continuous batching, HTTP degradation, and the
zero-dropped-request failover contract.

Unit layers (RequestQueue, ServeFrontend with stub groups, fault-plan
grammar) run in-process; the end-to-end layers spawn real replica
subprocesses through ReplicaGroup and exercise the supervised failover
paths — serve_kill mid-traffic, SIGTERM drain mid-batch — against real
RPC, matching how test_fault_tolerance.py treats the training plane.
"""
import json
import os
import signal
import threading
import time
import urllib.error
import urllib.request

import pytest

from raydp_tpu.control import ClusterBusyError
from raydp_tpu.fault.plan import FaultPlanError, parse_plan
from raydp_tpu.serve import (
    QueueFullError,
    ReplicaGroup,
    RequestCancelled,
    RequestQueue,
    ServeFrontend,
    ServeRequest,
)
from raydp_tpu.utils.profiling import metrics


@pytest.fixture(autouse=True)
def _fresh_metrics():
    metrics.reset()
    yield
    metrics.reset()


def _counter(name):
    return metrics.snapshot()["counters"].get(name, 0)


# ---------------------------------------------------------------------
# RequestQueue: buckets, shedding, continuous assembly, at-most-once
# ---------------------------------------------------------------------


def test_bucket_selection():
    q = RequestQueue(buckets=[4, 16])
    assert q.bucket_for(1) == 4
    assert q.bucket_for(4) == 4
    assert q.bucket_for(5) == 16
    # the last bucket absorbs oversize requests
    assert q.bucket_for(100) == 16


def test_queue_overflow_sheds_with_eta():
    q = RequestQueue(max_depth=2, slo_ms=10, max_batch=4)
    q.submit(ServeRequest([1]))
    q.submit(ServeRequest([2]))
    with pytest.raises(QueueFullError) as ei:
        q.submit(ServeRequest([3]))
    assert ei.value.queue_depth == 2
    assert ei.value.eta_s is not None and ei.value.eta_s > 0
    snap = metrics.snapshot()["counters"]
    assert snap["serve/rejected"] == 1
    assert snap["serve/requests"] == 2


def test_batch_assembly_groups_by_bucket():
    q = RequestQueue(max_depth=16, slo_ms=30, max_batch=4,
                     buckets=[4, 16])
    short = [ServeRequest([1, 2]) for _ in range(3)]
    long = ServeRequest(list(range(10)))
    for r in short:
        q.submit(r)
    q.submit(long)
    first = q.next_batch(wait_timeout=0.5)
    assert [r.request_id for r in first] == [r.request_id for r in short]
    assert all(r.attempts == 1 for r in first)
    second = q.next_batch(wait_timeout=0.5)
    assert [r.request_id for r in second] == [long.request_id]


def test_complete_is_at_most_once():
    q = RequestQueue(max_depth=4)
    req = ServeRequest([1])
    assert q.complete(req, result=1.0) is True
    assert q.complete(req, result=2.0) is False
    assert req.wait() == 1.0
    snap = metrics.snapshot()["counters"]
    assert snap["serve/dup_replies"] == 1
    assert snap["serve/replies"] == 1


def test_requeue_goes_to_front_in_order():
    q = RequestQueue(max_depth=16, slo_ms=1, max_batch=1)
    newer = ServeRequest([9])
    q.submit(newer)
    a, b = ServeRequest([1]), ServeRequest([2])
    assert q.requeue([a, b]) == 2
    order = [q.next_batch(0.2)[0].request_id for _ in range(3)]
    assert order == [a.request_id, b.request_id, newer.request_id]
    assert metrics.snapshot()["counters"]["serve/requeued"] == 2


def test_requeue_cancels_expired_and_skips_replied():
    q = RequestQueue(max_depth=16)
    expired = ServeRequest([1], timeout_s=0.0)
    answered = ServeRequest([2])
    q.complete(answered, result="done")
    assert q.requeue([expired, answered]) == 0
    assert q.depth() == 0
    with pytest.raises(RequestCancelled, match="expired during failover"):
        expired.wait()


def test_close_cancels_pending():
    q = RequestQueue(max_depth=4)
    req = ServeRequest([1])
    q.submit(req)
    q.close()
    with pytest.raises(RequestCancelled):
        req.wait()
    with pytest.raises(QueueFullError):
        q.submit(ServeRequest([2]))


# ---------------------------------------------------------------------
# Fault-plan grammar: serve_kill and latency clauses
# ---------------------------------------------------------------------


def test_parse_serve_kill_clause():
    (c,) = parse_plan("serve_kill:replica=1,request=5,code=7")
    assert (c.kind, c.replica, c.request, c.code) == ("serve_kill", 1, 5, 7)
    assert c.matches_replica(1)
    assert not c.matches_replica(0)
    assert not c.matches_replica(None)


def test_parse_latency_clause():
    (c,) = parse_plan("latency:nth=3,delay=0.25")
    assert (c.kind, c.nth, c.delay) == ("latency", 3, 0.25)
    # no replica target: matches every replica
    assert c.matches_replica(0) and c.matches_replica(None)


@pytest.mark.parametrize("plan", [
    "serve_kill:replica=0",            # missing request=
    "latency:nth=3",                   # missing delay=
    "serve_kill:replica=0,request=x",  # non-numeric
    "latency:nth=1,delay=0.1,rank=0",  # key not allowed for kind
])
def test_bad_serve_clauses_rejected(plan):
    with pytest.raises(FaultPlanError):
        parse_plan(plan)


# ---------------------------------------------------------------------
# ServeFrontend degradation paths (stub groups, no subprocesses)
# ---------------------------------------------------------------------


class _ShedGroup:
    def __init__(self, exc):
        self._exc = exc

    def submit(self, payload, timeout_s=None, request_id=None):
        raise self._exc

    def stats(self):
        return {"stub": True}


class _EchoGroup:
    def submit(self, payload, timeout_s=None, request_id=None):
        req = ServeRequest(payload, timeout_s=timeout_s,
                           request_id=request_id)
        req.attempts = 1
        req.result = sum(payload)
        req.replied = True
        req.done.set()
        return req

    def stats(self):
        return {"replicas_alive": 1}


def test_frontend_queue_full_is_429_with_retry_after():
    fe = ServeFrontend(_ShedGroup(
        QueueFullError("serving queue full", queue_depth=7, eta_s=2.3)
    ))
    status, payload, headers = fe.handle_predict({"inputs": [1]})
    assert status == 429
    assert payload["queue_depth"] == 7
    assert headers["Retry-After"] == "3"  # ceil(2.3)


def test_frontend_cluster_busy_is_429_with_retry_after():
    fe = ServeFrontend(_ShedGroup(
        ClusterBusyError("no capacity", queue_depth=3, eta_s=7.5)
    ))
    status, payload, headers = fe.handle_predict({"inputs": [1]})
    assert status == 429
    assert payload["queue_depth"] == 3
    assert payload["eta_s"] == 7.5
    assert headers["Retry-After"] == "8"


def test_frontend_shed_without_eta_defaults_to_one_second():
    fe = ServeFrontend(_ShedGroup(QueueFullError("closed")))
    status, _, headers = fe.handle_predict({"inputs": [1]})
    assert status == 429
    assert headers["Retry-After"] == "1"


def test_frontend_missing_inputs_is_400():
    status, payload, _ = ServeFrontend(_EchoGroup()).handle_predict({})
    assert status == 400


def test_frontend_deadline_expiry_is_504():
    class _Stuck:
        def submit(self, payload, timeout_s=None, request_id=None):
            return ServeRequest(payload, timeout_s=0.05)

        def stats(self):
            return {}

    status, payload, _ = ServeFrontend(_Stuck()).handle_predict(
        {"inputs": [1]}
    )
    assert status == 504


def test_frontend_http_roundtrip():
    fe = ServeFrontend(_EchoGroup()).start()
    try:
        base = f"http://127.0.0.1:{fe.port}"
        req = urllib.request.Request(
            f"{base}/predict",
            data=json.dumps({"inputs": [1, 2, 3]}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=5) as resp:
            body = json.loads(resp.read())
        assert body["result"] == 6
        assert body["id"]
        with urllib.request.urlopen(f"{base}/serve/stats", timeout=5) as r:
            assert json.loads(r.read())["replicas_alive"] == 1
        with urllib.request.urlopen(f"{base}/livez", timeout=5) as r:
            assert json.loads(r.read())["alive"] is True
    finally:
        fe.close()


def test_frontend_http_429_carries_retry_after_header():
    fe = ServeFrontend(_ShedGroup(
        QueueFullError("full", queue_depth=5, eta_s=4.0)
    )).start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{fe.port}/predict",
            data=json.dumps({"inputs": [1]}).encode(),
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=5)
        assert ei.value.code == 429
        assert ei.value.headers["Retry-After"] == "4"
        assert json.loads(ei.value.read())["queue_depth"] == 5
    finally:
        fe.close()


# ---------------------------------------------------------------------
# End-to-end: real replica subprocesses
# ---------------------------------------------------------------------


def _make_model(delay_s=0.0):
    # Nested so cloudpickle ships it by value — a replica subprocess
    # cannot import this test module by name.
    def model(payloads, bucket):
        if delay_s:
            time.sleep(delay_s)
        return [float(sum(p)) for p in payloads]

    return model


def _wait_all_alive(group, timeout_s=60.0):
    """``start()`` returns while the replicas are still coming up, and
    the first to register can serve a whole test alone: a test that
    counts on every replica (or kills a particular one) waits here."""
    deadline = time.monotonic() + timeout_s
    while group.stats()["replicas_alive"] < group.replicas:
        assert time.monotonic() < deadline, group.stats()
        time.sleep(0.05)


def _submit_and_wait_all(group, n, length=3):
    reqs = [group.submit([i] * length) for i in range(n)]
    return [r.wait(timeout=60.0) for r in reqs]


def test_group_end_to_end_batches_and_stats():
    with ReplicaGroup(
        replicas=2, model_fn=_make_model(), label="t-serve",
        max_batch=4, slo_ms=25, restart_backoff_s=0.1,
    ).start() as group:
        _wait_all_alive(group)
        results = _submit_and_wait_all(group, 24)
        assert results == [float(i * 3) for i in range(24)]
        stats = group.stats()
        assert stats["replicas_alive"] == 2
        assert stats["accepted"] == 24
        assert stats["replies"] == 24
        assert stats["errors"] == 0
        assert stats["batch_fill"] > 0
        assert stats["latency_p50_s"] > 0
        assert set(stats["per_replica"]) == {"0", "1"}


def test_serve_kill_failover_drops_nothing(monkeypatch):
    monkeypatch.setenv(
        "RAYDP_TPU_FAULT_PLAN", "serve_kill:replica=0,request=3"
    )
    with ReplicaGroup(
        replicas=2, model_fn=_make_model(), label="t-kill",
        max_batch=4, slo_ms=25, restart_backoff_s=0.1, max_restarts=3,
    ).start() as group:
        _wait_all_alive(group)
        results = _submit_and_wait_all(group, 40)
        # zero drops: every accepted request got exactly one reply
        assert results == [float(i * 3) for i in range(40)]
        # the kill really happened and the in-flight batch was retried
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            stats = group.stats()
            if stats["restarts"] >= 1 and stats["replicas_alive"] == 2:
                break
            time.sleep(0.2)
        assert stats["restarts"] >= 1, stats
        assert stats["requeued"] >= 1, stats
        assert stats["dup_replies"] == 0, stats
        # self-healed: the killed lineage respawned within its budget
        assert stats["replicas_alive"] == 2, stats
        assert stats["dead_lineages"] == 0, stats
        assert stats["replies"] == 40, stats


def test_latency_clause_stalls_request(monkeypatch):
    monkeypatch.setenv(
        "RAYDP_TPU_FAULT_PLAN", "latency:nth=0,delay=0.6,replica=0"
    )
    with ReplicaGroup(
        replicas=1, model_fn=_make_model(), label="t-lat",
        max_batch=1, slo_ms=10, restart_backoff_s=0.1,
    ).start() as group:
        t0 = time.monotonic()
        assert group.predict([1, 1]) == 2.0
        assert time.monotonic() - t0 >= 0.5
        # the clause fires once; later requests are fast again
        t1 = time.monotonic()
        assert group.predict([2, 2]) == 4.0
        assert time.monotonic() - t1 < 0.5


def test_sigterm_drains_in_flight_batch():
    with ReplicaGroup(
        replicas=2, model_fn=_make_model(delay_s=0.3), label="t-drain",
        max_batch=4, slo_ms=25, restart_backoff_s=0.1,
    ).start() as group:
        _wait_all_alive(group)
        # the counter is the process's, not this group's: read a delta
        before = _counter("serve/batches")
        reqs = [group.submit([i]) for i in range(12)]
        # wait until a replica is actually mid-batch, then SIGTERM it
        slot = group._slots[0]
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if _counter("serve/batches") - before >= 1:
                break
            time.sleep(0.02)
        victim = slot.proc
        os.kill(victim.pid, signal.SIGTERM)
        # every request still gets its reply: the in-flight batch
        # finishes inside the drain window, refused batches requeue
        results = [r.wait(timeout=60.0) for r in reqs]
        assert results == [float(i) for i in range(12)]
        # the drained process exited cleanly (status 0), not killed
        assert victim.wait(timeout=30.0) == 0
        snap = metrics.snapshot()["counters"]
        assert snap.get("serve/errors", 0) == 0
        assert snap["serve/replies"] == 12


# ---------------------------------------------------------------------
# correlation headers, phase provenance, cold-start null guards
# ---------------------------------------------------------------------


class _PhasedGroup:
    """Echo stub whose replies carry a phase decomposition."""

    def submit(self, payload, timeout_s=None, request_id=None):
        req = ServeRequest(payload, timeout_s=timeout_s,
                           request_id=request_id)
        req.attempts = 1
        req.result = sum(payload)
        req.phases = {"queue_wait": 0.01, "linger": 0.002,
                      "execute": 0.03, "reply": 0.008,
                      "padding_waste": 0.004, "total": 0.05}
        req.replied = True
        req.done.set()
        return req

    def stats(self):
        return {"replicas_alive": 1}


def test_predict_response_carries_request_id_and_phases():
    fe = ServeFrontend(_PhasedGroup())
    status, payload, headers = fe.handle_predict(
        {"inputs": [1, 2], "id": "req-abc"}
    )
    assert status == 200
    assert headers["X-RayDP-Request-Id"] == "req-abc"
    assert payload["id"] == "req-abc"
    phases = payload["phases"]
    four = (phases["queue_wait"] + phases["linger"]
            + phases["execute"] + phases["reply"])
    assert four == pytest.approx(phases["total"])


def test_predict_echoes_incoming_traceparent():
    fe = ServeFrontend(_PhasedGroup())
    status, _, headers = fe.handle_predict(
        {"inputs": [1]}, headers={"Traceparent": "trace01;span02"}
    )
    assert status == 200
    assert headers["traceparent"] == "trace01;span02"
    assert "X-RayDP-Request-Id" in headers


def test_predict_504_carries_request_id_and_event():
    from raydp_tpu.telemetry import events as _events

    class _Stuck:
        def submit(self, payload, timeout_s=None, request_id=None):
            return ServeRequest(payload, timeout_s=0.05,
                                request_id=request_id)

        def stats(self):
            return {}

    status, payload, headers = ServeFrontend(_Stuck()).handle_predict(
        {"inputs": [1], "id": "slow-1"}
    )
    assert status == 504
    assert headers["X-RayDP-Request-Id"] == "slow-1"
    timeouts = [e for e in _events.local_events()
                if e["name"] == "serve/timeout"]
    assert timeouts
    assert timeouts[-1]["attrs"]["request_id"] == "slow-1"


def test_predict_429_echoes_client_supplied_id():
    fe = ServeFrontend(_ShedGroup(QueueFullError("full", 5, 1.0)))
    _, _, headers = fe.handle_predict({"inputs": [1], "id": "mine"})
    assert headers["X-RayDP-Request-Id"] == "mine"
    assert headers["Retry-After"] == "1"


def test_cold_group_stats_are_null_not_nan():
    group = ReplicaGroup(replicas=1, model_fn=_make_model(),
                         label="t-cold")
    stats = group.stats()  # zero replies ever: nulls, no KeyError
    assert stats["latency_p50_s"] is None
    assert stats["latency_p99_s"] is None
    assert stats["per_replica"] == {}
    for phase in ("queue_wait", "linger", "execute", "reply"):
        assert stats["phases"][phase]["mean_s"] is None
        assert stats["phases"][phase]["p99_s"] is None
    # the whole document survives JSON (no NaN/Inf leaks)
    json.dumps(stats, allow_nan=False)


def test_cold_serve_stats_http_is_200():
    group = ReplicaGroup(replicas=1, model_fn=_make_model(),
                         label="t-cold-http")
    fe = ServeFrontend(group).start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{fe.port}/serve/stats", timeout=5
        ) as resp:
            doc = json.loads(resp.read())
        assert doc["latency_p99_s"] is None
        assert doc["replies"] == 0
    finally:
        fe.close()


def test_cold_queue_eta_is_positive_before_any_reply():
    q = RequestQueue(max_depth=1, slo_ms=25, max_batch=4)
    # EWMA is SLO-seeded: the very first shed carries a usable ETA
    assert q.shed_eta_s() > 0
    q.submit(ServeRequest([1]))
    with pytest.raises(QueueFullError) as ei:
        q.submit(ServeRequest([2]))
    assert ei.value.eta_s is not None and ei.value.eta_s > 0
    from raydp_tpu.serve.frontend import retry_after_s
    assert retry_after_s(ei.value) >= 1
