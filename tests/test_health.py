"""Cluster health plane: watchdog, flight recorder, logs, /healthz.

Covers the health layers bottom-up, all on the CPU backend:

* progress tracking — in-flight op bookkeeping, oldest-op attribution,
  concurrent-op counting;
* watchdog — synthetic stall detected and attributed to its component
  with attrs, ``watchdog/stalls`` counted once per episode, recovery
  clearing the flag on the next check;
* flight recorder — bounded ring semantics, bundle round-trip, a
  crashed subprocess and a SIGTERM'd subprocess each leaving a
  parseable postmortem bundle with all-thread stacks, and the CLI;
* structured logs — a record emitted inside an open span carries that
  span's trace_id/span_id through the JSONL shard, WARNING+ mirrored
  into the flight ring;
* export surface — ``watchdog/stalls`` routed to the dedicated
  ``raydp_stalls_total`` family, and the multi-route debug server:
  ``/healthz`` flipping 200→503 while ``/metrics`` keeps serving,
  ``/debug/state`` and ``/debug/stacks``, idempotent ``close()``;
* acceptance — a live two-worker cluster with one rank wedged:
  ``Cluster.health_report()`` names the stalled worker and component
  long before the heartbeat timeout, the wedged worker's own
  ``/healthz`` answers 503 while its ``/metrics`` stays 200, and
  killing it leaves a postmortem bundle holding the task's flight
  events and an all-thread stack dump.
"""
import glob
import json
import logging
import os
import re
import signal
import subprocess
import sys
import textwrap
import time
import urllib.error
import urllib.request

from raydp_tpu.telemetry import flight_recorder, logs, watchdog
from raydp_tpu.telemetry import render_prometheus, serve_prometheus, span
from raydp_tpu.utils.profiling import metrics


# ---------------------------------------------------------------------
# Progress tracking


def test_tracker_attributes_oldest_op_and_counts_concurrency():
    pt = watchdog.ProgressTracker()
    old = pt.begin("train/step", step=1)
    time.sleep(0.02)
    young = pt.begin("train/step", step=2)
    other = pt.begin("rpc", method="Ping")
    snap = pt.snapshot()
    assert set(snap) == {"train/step", "rpc"}
    assert snap["train/step"]["count"] == 2
    # The OLDEST op is the stall candidate; its attrs win.
    assert snap["train/step"]["attrs"] == {"step": 1}
    assert snap["train/step"]["age_s"] >= snap["rpc"]["age_s"]
    for token in (old, young, other):
        pt.end(token)
    assert pt.snapshot() == {}


def test_tracker_inflight_ends_on_exception():
    pt = watchdog.ProgressTracker()
    try:
        with pt.inflight("ingest/chunk", epoch=0):
            raise RuntimeError("boom")
    except RuntimeError:
        pass
    assert pt.snapshot() == {}


# ---------------------------------------------------------------------
# Watchdog


def test_watchdog_detects_attributes_and_recovers_stall():
    pt = watchdog.ProgressTracker()
    seen = []
    wd = watchdog.Watchdog(
        progress=pt, interval_s=999.0, stall_after_s=0.05,
        on_stall=lambda c, info: seen.append((c, info)), dump_bundles=False,
    )
    before = (metrics.snapshot().get("counters") or {}).get(
        watchdog.STALL_COUNTER, 0
    )
    token = pt.begin("train/step", epoch=3, step=41)
    time.sleep(0.1)
    health = wd.check()
    assert health["healthy"] is False
    assert "train/step" in health["stalls"]
    assert health["stalls"]["train/step"]["attrs"] == {"epoch": 3, "step": 41}
    assert health["stalls"]["train/step"]["age_s"] >= 0.05
    assert seen and seen[0][0] == "train/step"

    # Same episode on the next check: no second count, no second callback.
    wd.check()
    after = (metrics.snapshot().get("counters") or {}).get(
        watchdog.STALL_COUNTER, 0
    )
    assert after == before + 1
    assert len(seen) == 1

    # The op finishing clears the flag on the next check.
    pt.end(token)
    health = wd.check()
    assert health["healthy"] is True and health["stalls"] == {}
    names = [e["name"] for e in flight_recorder.recorder.tail()
             if e["kind"] == "watchdog"]
    assert "stall" in names and "recovered" in names


def test_watchdog_new_component_is_a_fresh_episode():
    pt = watchdog.ProgressTracker()
    wd = watchdog.Watchdog(progress=pt, interval_s=999.0,
                           stall_after_s=0.01, dump_bundles=False)
    a = pt.begin("rpc")
    time.sleep(0.03)
    assert set(wd.check()["stalls"]) == {"rpc"}
    b = pt.begin("worker/task")
    time.sleep(0.03)
    assert set(wd.check()["stalls"]) == {"rpc", "worker/task"}
    pt.end(a)
    pt.end(b)
    assert wd.check()["healthy"] is True


def test_per_op_stall_threshold_raises_never_lowers():
    pt = watchdog.ProgressTracker()
    wd = watchdog.Watchdog(progress=pt, interval_s=999.0,
                           stall_after_s=0.02, dump_bundles=False)
    # An expected-long bracket (whole task body, first-step compile)
    # raises its own threshold: not a stall at the global one.
    long_op = pt.begin("worker/task", stall_after_s=60.0)
    # An override BELOW the global threshold must not sharpen it.
    short_op = pt.begin("rpc", stall_after_s=0.001)
    time.sleep(0.05)
    health = wd.check()
    assert "worker/task" not in health["stalls"]
    assert "rpc" in health["stalls"]
    pt.end(long_op)
    pt.end(short_op)
    assert wd.check()["healthy"] is True


def test_watchdog_flapping_component_dumps_one_bundle_per_cooldown(
    tmp_path, monkeypatch
):
    monkeypatch.setenv(flight_recorder.POSTMORTEM_DIR_ENV, str(tmp_path))
    pt = watchdog.ProgressTracker()
    wd = watchdog.Watchdog(progress=pt, interval_s=999.0,
                           stall_after_s=0.01, bundle_cooldown_s=3600.0)
    # Flap: stall → recover → stall again, three episodes back-to-back.
    for _ in range(3):
        token = pt.begin("spmd/func")
        time.sleep(0.02)
        wd.check()
        pt.end(token)
        wd.check()
    bundles = [f for f in os.listdir(tmp_path)
               if f.startswith("postmortem-")]
    assert len(bundles) == 1  # rate-limited, not one per flap


def test_module_health_live_when_no_watchdog_running(monkeypatch):
    monkeypatch.setattr(watchdog, "_watchdog", None)
    monkeypatch.setenv(watchdog.WATCHDOG_STALL_ENV, "3600")
    with watchdog.inflight("train/step"):
        health = watchdog.health()
    assert health["healthy"] is True
    assert health["stall_after_s"] == 3600.0


def test_watchdog_stall_dumps_postmortem_bundle(tmp_path, monkeypatch):
    monkeypatch.setenv(flight_recorder.POSTMORTEM_DIR_ENV, str(tmp_path))
    pt = watchdog.ProgressTracker()
    wd = watchdog.Watchdog(progress=pt, interval_s=999.0, stall_after_s=0.01)
    with pt.inflight("spmd/func", rank=0):
        time.sleep(0.03)
        wd.check()
    path = flight_recorder.latest_bundle(str(tmp_path))
    assert path is not None
    bundle = flight_recorder.read_bundle(path)
    assert bundle["schema"] == "raydp-postmortem-v1"
    assert "watchdog stall: spmd/func" in bundle["reason"]
    assert bundle["stacks"]  # all-thread dump present


# ---------------------------------------------------------------------
# Flight recorder


def test_flight_ring_is_bounded_keeping_the_tail():
    ring = flight_recorder.FlightRecorder(capacity=16)
    for i in range(40):
        ring.record("state", f"evt-{i}")
    assert len(ring) == 16
    names = [e["name"] for e in ring.tail()]
    assert names[0] == "evt-24" and names[-1] == "evt-39"
    assert [e["name"] for e in ring.tail(3)] == [
        "evt-37", "evt-38", "evt-39"
    ]


def test_dump_bundle_roundtrip(tmp_path):
    flight_recorder.record("train", "epoch_start", epoch=7)
    path = flight_recorder.dump_bundle("unit test", directory=str(tmp_path))
    assert path is not None and os.path.exists(path)
    bundle = flight_recorder.read_bundle(path)
    assert bundle["schema"] == "raydp-postmortem-v1"
    assert bundle["reason"] == "unit test"
    assert bundle["pid"] == os.getpid()
    assert any(e["name"] == "epoch_start" for e in bundle["events"])
    assert any("MainThread" in label for label in bundle["stacks"])


_CRASH_SCRIPT = textwrap.dedent("""\
    from raydp_tpu.telemetry import flight_recorder as fr

    fr.install(component="worker")
    fr.record("task", "start", worker_id="w9")
    raise RuntimeError("deliberate crash for test")
""")

_SIGTERM_SCRIPT = textwrap.dedent("""\
    import sys
    import time

    from raydp_tpu.telemetry import flight_recorder as fr

    fr.install(component="worker")
    fr.record("task", "start", worker_id="w9")
    print("READY", flush=True)
    time.sleep(60)
""")


def _child_env(postmortem_dir):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env[flight_recorder.POSTMORTEM_DIR_ENV] = str(postmortem_dir)
    return env


def test_crashed_subprocess_leaves_postmortem_bundle(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-c", _CRASH_SCRIPT],
        env=_child_env(tmp_path), capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode != 0
    assert "deliberate crash" in proc.stderr  # chained to the prev hook
    path = flight_recorder.latest_bundle(str(tmp_path))
    assert path is not None
    bundle = flight_recorder.read_bundle(path)
    assert bundle["reason"] == "unhandled exception"
    assert bundle["component"] == "worker"
    assert "RuntimeError: deliberate crash" in bundle["exception"]
    assert any(
        e["name"] == "start" and e.get("attrs", {}).get("worker_id") == "w9"
        for e in bundle["events"]
    )
    assert bundle["stacks"]


def test_sigterm_subprocess_dumps_bundle_then_dies_by_signal(tmp_path):
    proc = subprocess.Popen(
        [sys.executable, "-c", _SIGTERM_SCRIPT],
        env=_child_env(tmp_path), stdout=subprocess.PIPE, text=True,
    )
    try:
        assert proc.stdout.readline().strip() == "READY"
        proc.terminate()
        rc = proc.wait(timeout=30)
    finally:
        proc.kill()
    # The handler re-delivers SIGTERM after dumping: kill semantics hold.
    assert rc == -signal.SIGTERM
    path = flight_recorder.latest_bundle(str(tmp_path))
    assert path is not None
    bundle = flight_recorder.read_bundle(path)
    assert bundle["reason"] == "SIGTERM"
    assert any(e["name"] == "sigterm" for e in bundle["events"])
    assert any("MainThread" in label for label in bundle["stacks"])


def test_postmortem_dir_is_capped_oldest_deleted_first(
    tmp_path, monkeypatch
):
    monkeypatch.setenv(flight_recorder.POSTMORTEM_KEEP_ENV, "3")
    paths = [
        flight_recorder.dump_bundle(f"retention-{i}",
                                    directory=str(tmp_path))
        for i in range(6)
    ]
    assert all(paths)
    kept = {f for f in os.listdir(tmp_path) if f.endswith(".json")}
    assert len(kept) == 3
    assert {os.path.basename(p) for p in paths[-3:]} == kept


_SIGTERM_LOCKED_SCRIPT = textwrap.dedent("""\
    import time

    from raydp_tpu.telemetry import flight_recorder as fr

    fr.install(component="worker")
    fr.record("task", "start", worker_id="w9")
    # SIGTERM interrupting the exact frame that holds the ring lock
    # (the heartbeat loop records constantly): the handler must stay
    # lock-free or the process wedges inside it until SIGKILL.
    fr.recorder._mu.acquire()
    print("READY", flush=True)
    time.sleep(60)
""")


def test_sigterm_while_main_thread_holds_ring_lock_still_dumps(tmp_path):
    proc = subprocess.Popen(
        [sys.executable, "-c", _SIGTERM_LOCKED_SCRIPT],
        env=_child_env(tmp_path), stdout=subprocess.PIPE, text=True,
    )
    try:
        assert proc.stdout.readline().strip() == "READY"
        proc.terminate()
        rc = proc.wait(timeout=30)  # deadlock ⇒ TimeoutExpired here
    finally:
        proc.kill()
    assert rc == -signal.SIGTERM
    path = flight_recorder.latest_bundle(str(tmp_path))
    assert path is not None
    bundle = flight_recorder.read_bundle(path)
    assert bundle["reason"] == "SIGTERM"
    assert any(e["name"] == "sigterm" for e in bundle["events"])
    assert any("MainThread" in label for label in bundle["stacks"])


def test_flight_recorder_cli(tmp_path, capsys):
    assert flight_recorder.main([str(tmp_path)]) == 0
    assert "no postmortem bundles" in capsys.readouterr().out
    flight_recorder.dump_bundle("cli test", directory=str(tmp_path))
    assert flight_recorder.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "reason:    cli test" in out
    assert "threads captured:" in out


# ---------------------------------------------------------------------
# Trace-correlated structured logs


def test_log_inside_span_carries_trace_id(tmp_path):
    handler = logs.install(directory=str(tmp_path))
    assert handler is not None
    log = logging.getLogger("raydp_tpu.tests.health")
    log.setLevel(logging.INFO)
    try:
        log.info("outside any span")
        with span("health/logtest") as sp:
            log.info("inside the span")
            log.warning("warned inside the span")
        trace_id, span_id = sp.trace_id, sp.span_id
    finally:
        logs.uninstall()

    records = {r["message"]: r for r in logs.read_records(str(tmp_path))}
    assert "trace_id" not in records["outside any span"]
    inside = records["inside the span"]
    assert inside["trace_id"] == trace_id
    assert inside["span_id"] == span_id
    assert inside["level"] == "INFO" and inside["pid"] == os.getpid()
    # WARNING+ mirrored into the flight ring for postmortem bundles.
    assert any(
        e["kind"] == "log"
        and e.get("attrs", {}).get("message") == "warned inside the span"
        for e in flight_recorder.recorder.tail()
    )


def test_logs_install_captures_info_with_unconfigured_root(tmp_path):
    # A process that never configured logging has the root logger at
    # WARNING: without install() lowering it, INFO records would be
    # filtered at the logger and never reach the JSONL handler.
    root = logging.getLogger()
    prev = root.level
    root.setLevel(logging.WARNING)
    try:
        assert logs.install(directory=str(tmp_path)) is not None
        log = logging.getLogger("raydp_tpu.tests.rootlevel")  # NOTSET
        log.info("info reaches the shard")
        logs.uninstall()
        assert root.level == logging.WARNING  # uninstall restored it
        msgs = [r["message"] for r in logs.read_records(str(tmp_path))]
        assert "info reaches the shard" in msgs
    finally:
        logs.uninstall()
        root.setLevel(prev)


def test_logs_install_is_idempotent_and_noop_without_dir(tmp_path, monkeypatch):
    monkeypatch.delenv("RAYDP_TPU_TELEMETRY_DIR", raising=False)
    assert logs.install() is None
    h1 = logs.install(directory=str(tmp_path))
    try:
        assert logs.install(directory=str(tmp_path)) is h1
        root_handlers = logging.getLogger().handlers
        assert root_handlers.count(h1) == 1
    finally:
        logs.uninstall()
    assert h1 not in logging.getLogger().handlers


def test_logs_install_follows_a_new_directory(tmp_path):
    """The handler outlives the session that installed it
    (``Cluster.start``); the next session's records belong in ITS
    telemetry directory, not in the first one's."""
    first, second = tmp_path / "first", tmp_path / "second"
    log = logging.getLogger("raydp_tpu.tests.redirect")
    log.setLevel(logging.INFO)
    h1 = logs.install(directory=str(first))
    try:
        log.info("to the first")
        h2 = logs.install(directory=str(second))
        assert h2 is not h1
        log.info("to the second")
        handlers = logging.getLogger().handlers
        assert h1 not in handlers and handlers.count(h2) == 1
    finally:
        logs.uninstall()
    assert [r["message"] for r in logs.read_records(str(first))] == \
        ["to the first"]
    assert [r["message"] for r in logs.read_records(str(second))] == \
        ["to the second"]


# ---------------------------------------------------------------------
# Export surface


def test_render_prometheus_routes_stalls_to_dedicated_family():
    text = render_prometheus(
        {"workers": {"w0": {"counters": {"watchdog/stalls": 3.0,
                                         "tasks/completed": 5.0}}}}
    )
    assert 'raydp_stalls_total{worker="w0"} 3' in text
    assert 'raydp_counter_total{name="tasks/completed",worker="w0"} 5' \
        in text
    # Not double-reported under the generic counter family.
    assert "watchdog/stalls" not in text


def _get(url, timeout=5.0):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read().decode("utf-8")
    except urllib.error.HTTPError as err:
        return err.code, err.read().decode("utf-8")


def test_debug_server_routes_and_healthz_flip():
    state = {"healthy": True, "stalls": {}}
    server = serve_prometheus(
        lambda: "fake_metric 1\n", 0, host="127.0.0.1",
        health=lambda: dict(state),
    )
    try:
        assert server.port != 0  # ephemeral port resolved
        base = f"http://127.0.0.1:{server.port}"

        code, body = _get(base + "/metrics")
        assert code == 200 and body == "fake_metric 1\n"

        code, body = _get(base + "/healthz")
        assert code == 200 and json.loads(body)["healthy"] is True

        code, body = _get(base + "/livez")
        assert code == 200 and json.loads(body)["alive"] is True

        # Wedge: /healthz flips 503 while /metrics keeps serving.
        state["healthy"] = False
        state["stalls"] = {"train/step": {"age_s": 99.0}}
        code, body = _get(base + "/healthz")
        assert code == 503
        assert json.loads(body)["stalls"]["train/step"]["age_s"] == 99.0
        code, _ = _get(base + "/metrics")
        assert code == 200
        # /livez is the liveness target precisely because it ignores
        # stall state: a long-but-healthy op must not get the pod killed.
        code, body = _get(base + "/livez")
        assert code == 200 and json.loads(body)["alive"] is True

        code, body = _get(base + "/debug/state")
        assert code == 200
        debug = json.loads(body)
        assert debug["pid"] == os.getpid()
        assert debug["health"]["healthy"] is False
        assert isinstance(debug["flight"], list)

        code, body = _get(base + "/debug/stacks")
        assert code == 200 and "MainThread" in body

        code, _ = _get(base + "/nope")
        assert code == 404
    finally:
        server.close()
        server.close()  # idempotent: shutdown paths overlap in practice


# ---------------------------------------------------------------------
# Acceptance: live cluster with a wedged worker


def test_acceptance_wedged_worker_health_report_healthz_and_postmortem(
    tmp_path, monkeypatch
):
    import raydp_tpu
    from raydp_tpu.cluster.master import HEARTBEAT_TIMEOUT_S

    postmortem = tmp_path / "postmortem"
    # Tight thresholds so the stall fires in seconds; LocalLauncher
    # merges os.environ into worker subprocess envs, so the knobs reach
    # every rank. DEBUG_PORT=0: each worker logs its ephemeral port.
    monkeypatch.setenv(watchdog.WATCHDOG_STALL_ENV, "1")
    # worker/task is a whole-body bracket and uses the LONG threshold
    # (a healthy task may run for minutes); tighten it too so the wedge
    # fires in seconds.
    monkeypatch.setenv(watchdog.WATCHDOG_LONG_STALL_ENV, "1")
    monkeypatch.setenv(watchdog.WATCHDOG_INTERVAL_ENV, "0.2")
    monkeypatch.setenv(flight_recorder.POSTMORTEM_DIR_ENV, str(postmortem))
    monkeypatch.setenv("RAYDP_TPU_DEBUG_PORT", "0")

    def wedge(ctx):
        time.sleep(120.0)
        return "never"

    s = raydp_tpu.init(app_name="health-acceptance", num_workers=2)
    try:
        cl = s.cluster
        workers = sorted(w.worker_id for w in cl.alive_workers())
        assert len(workers) == 2
        victim = workers[0]
        cl.submit_async(wedge, worker_id=victim, timeout=300.0, retries=0)

        # (a) health_report names the wedged worker + component well
        # before the heartbeat timeout would declare it dead.
        report = None
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            report = cl.health_report()
            if victim in report["stalled_workers"]:
                break
            time.sleep(0.5)
        assert report is not None
        assert victim in report["stalled_workers"], report
        assert report["healthy"] is False
        victim_info = report["workers"][victim]
        assert "worker/task" in victim_info["stalls"]
        # The wedge stalls the task, not the heartbeat thread: the flag
        # arrived on a live beat, far inside the death-detection window.
        assert victim_info["heartbeat_age_s"] < HEARTBEAT_TIMEOUT_S / 2
        assert victim not in report["dead_workers"]
        # The peer may still be inside the warm-up task every worker
        # gets at start (pandas' import, over the 1 s threshold on a
        # loaded host): that one ends, the wedge does not.
        healthy_peer = workers[1]
        deadline = time.monotonic() + 30.0
        while (report["workers"][healthy_peer]["stalls"]
               and time.monotonic() < deadline):
            time.sleep(0.5)
            report = cl.health_report()
        assert not report["workers"][healthy_peer]["stalls"]
        assert victim in report["stalled_workers"], report

        # (b) the wedged process's own endpoint: /healthz 503 while
        # /metrics keeps serving. Port comes from the worker's log line.
        log_path = os.path.join(cl._log_dir, f"{victim}.log")
        port = None
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and port is None:
            with open(log_path, "r", errors="replace") as f:
                m = re.search(
                    r"telemetry debug endpoint on [\d.]+:(\d+)", f.read()
                )
            if m:
                port = int(m.group(1))
            else:
                time.sleep(0.5)
        assert port is not None, f"no debug endpoint line in {log_path}"
        code, body = _get(f"http://127.0.0.1:{port}/healthz")
        assert code == 503
        assert "worker/task" in json.loads(body)["stalls"]
        code, body = _get(f"http://127.0.0.1:{port}/metrics")
        assert code == 200 and "raydp_" in body

        # (c) killing the wedged rank leaves a postmortem bundle with
        # the task's flight events and an all-thread stack dump.
        victim_pid = victim_info["pid"]
        proc = cl._procs[victim]
        proc.terminate()
        proc.wait(timeout=30)
        pattern = str(postmortem / f"postmortem-{victim_pid}-*.json")
        bundles = []
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and not bundles:
            bundles = glob.glob(pattern)
            time.sleep(0.2)
        assert bundles, f"no bundle matching {pattern}"
        bundle = flight_recorder.read_bundle(
            max(bundles, key=os.path.getmtime)
        )
        assert bundle["reason"] == "SIGTERM"
        assert bundle["component"] == "worker"
        assert bundle["stacks"]
        names = {(e["kind"], e["name"]) for e in bundle["events"]}
        assert ("task", "start") in names  # the wedged task's last act
    finally:
        raydp_tpu.stop()
