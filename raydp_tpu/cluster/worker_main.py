"""ETL worker process entry point.

Role parity with the reference's executor backend
(reference: core/.../executor/RayCoarseGrainedExecutorBackend.scala:38-262):
a separately spawned process that registers with the AppMaster (with
retries, :58-81), runs tasks shipped from the driver, heartbeats, and
exits on Stop or on master disappearance.

Tasks are cloudpickled callables ``fn(worker_ctx, *args)`` (the MPI
subsystem's function-shipping design, reference:
python/raydp/mpi/mpi_worker.py:75-96). Results return inline; large Arrow
results go through the shm object store and return ObjectRefs.
"""
from __future__ import annotations

import argparse
import atexit
import contextlib
import logging
import os
import sys
import threading
import time
import traceback
from collections import OrderedDict

import cloudpickle

from raydp_tpu import fault as _fault
from raydp_tpu.cluster.rpc import RpcClient, RpcServer
from raydp_tpu.store.object_store import ObjectStore
from raydp_tpu.telemetry import MetricsShipper, flush_spans, span
from raydp_tpu.telemetry import accounting as _acct
from raydp_tpu.telemetry import flight_recorder as _flight
from raydp_tpu.telemetry import logs as _logs
from raydp_tpu.telemetry import propagation as trace_prop
from raydp_tpu.telemetry import watchdog as _watchdog
from raydp_tpu.utils.profiling import metrics

logger = logging.getLogger(__name__)

WORKER_SERVICE = "raydp.Worker"
REGISTER_RETRIES = 3
# Completed-task replies kept for duplicate-delivery detection. Sized
# for the realistic retry window (seconds), not task history.
_DEDUP_CAPACITY = 1024
# A duplicate that arrives while the original is still executing waits
# this long for the first execution to finish before giving up.
_DEDUP_WAIT_S = 300.0

# The task body open on this thread: the list its ``WorkerContext`` calls
# stamp into. Thread-local, because an envelope's tasks run side by side
# on the worker's pool and their parts must not mix.
_body = threading.local()


@contextlib.contextmanager
def _task_body():
    """Opens this thread's task body (at its ``start``) and yields the
    list that rides the task's reply beside ``start`` and ``end``:
    ``[kind, t0, t1]`` (``perf_counter``) for each ``fetch``, ``put`` and
    ``register`` the body made through its ``WorkerContext``. What is
    left of the body is its compute: never stamped."""
    parts = _body.parts = []
    try:
        yield parts
    finally:
        _body.parts = None


@contextlib.contextmanager
def _stamped(kind: str):
    """One ``perf_counter`` pair around a part of the open task body;
    calls of one kind that follow one another are one interval (a
    counting body's eight fetches). Outside a body (the resolving of
    ``data_refs`` before it, a profile's ``put_bytes``) it records
    nothing."""
    parts = getattr(_body, "parts", None)
    if parts is None:
        yield
        return
    t0 = time.perf_counter()
    try:
        yield
    finally:
        t1 = time.perf_counter()
        if parts and parts[-1][0] == kind:
            parts[-1][2] = t1
        else:
            parts.append([kind, t0, t1])


class WorkerContext:
    """Handed to every shipped task as its first argument."""

    def __init__(self, worker_id: str, node_id: str, store: ObjectStore,
                 master: RpcClient):
        self.worker_id = worker_id
        self.node_id = node_id
        self.store = store
        self._master = master
        from raydp_tpu.store.resolver import ObjectResolver

        self.resolver = ObjectResolver(store, self._object_meta)

    def _object_meta(self, object_id: str):
        reply = self._master.call("GetObjectMeta", {"object_id": object_id})
        return reply.get("ref"), reply.get("agent")

    def put_table(self, table, holder: bool = False):
        """Store an Arrow table; returns ObjectRef.

        Owned by this worker by default (dies with it); ``holder=True``
        writes it holder-owned up front (ingest data that must survive pool
        shrinks). The ref is registered in the master's object directory so
        owner lifetime is enforced cluster-wide (reference: executor-side
        Ray.put with optional owner, ObjectStoreWriter.scala:58-79).
        """
        from raydp_tpu.store.object_store import OWNER_HOLDER

        owner = OWNER_HOLDER if holder else self.worker_id
        with _stamped("put"):
            ref = self.store.put_arrow_table(table, owner=owner)
        return self._register(ref)

    def put_bytes(self, data) -> "ObjectRef":
        with _stamped("put"):
            ref = self.store.put(data, owner=self.worker_id)
        return self._register(ref)

    def _register(self, ref):
        """Enter ``ref`` in the master's object directory: a round trip
        the caller waits for."""
        with _stamped("register"):
            self._master.call("RegisterObject", {"ref": ref})
        return ref

    def get_table(self, ref):
        """Read an Arrow table from anywhere in the cluster: local shm
        zero-copy, or a gRPC pull from the owning node's store agent."""
        with _stamped("fetch"):
            return self.resolver.get_arrow_table(ref)

    def get_bytes(self, ref):
        return self.resolver.get_bytes(ref)


class Worker:
    def __init__(self, worker_id: str, master_address: str, node_id: str,
                 resources: dict, bind_host: str = "127.0.0.1"):
        self.worker_id = worker_id
        self.node_id = node_id
        self.resources = resources
        # Generous default timeout: control RPCs (RegisterObject) must
        # survive a driver process saturated by a big shuffle on a small
        # host — a slow master is not a dead master.
        self.master = RpcClient(
            master_address, "raydp.AppMaster", timeout=120.0
        )
        self.store: ObjectStore = None  # namespace learned at registration
        self.ctx: WorkerContext = None
        self._stop_event = threading.Event()
        # Tasks in flight right now. A worker mid-task must never decide
        # the master is gone and exit: on a core-starved host (one CPU,
        # many shuffle processes) heartbeat round-trips stall for tens of
        # seconds precisely WHILE tasks run, and a mid-task exit cancels
        # the in-flight RunTask on the driver side.
        self._busy = 0
        self._busy_lock = threading.Lock()
        # Monotonic count of tasks this process has started (single and
        # batched alike) — the index the fault plan's kill task= clause
        # matches against.
        self._task_seq = 0
        # At-most-once execution for id-carrying tasks: request_id ->
        # {"done": Event, "reply": dict | None, "error": str | None}.
        # A client reconnect retry that re-delivers an envelope this
        # process already saw waits for (or returns) the first
        # execution's outcome instead of running the fn twice. Bounded:
        # oldest entries age out past _DEDUP_CAPACITY.
        self._dedup: "OrderedDict[str, dict]" = OrderedDict()
        self._dedup_lock = threading.Lock()
        # Telemetry: each heartbeat carries the registry sections that
        # changed since the previous beat (delta-encoded snapshot).
        self._shipper = MetricsShipper()
        # The RPC server is up before registration completes, and the master
        # lists this worker ALIVE the moment RegisterWorker returns — so a
        # task can arrive while ctx is still being built. Gate on readiness.
        self._ready = threading.Event()
        # Batched tasks run concurrently on this pool (pyarrow releases
        # the GIL for the heavy kernels, so same-worker tasks in one
        # envelope keep the intra-worker parallelism that per-partition
        # RPCs used to get from separate gRPC handler threads).
        self._task_pool = None
        self._task_pool_lock = threading.Lock()
        self._server = RpcServer(
            WORKER_SERVICE,
            {
                "RunTask": self._on_run_task,
                "RunTaskBatch": self._on_run_task_batch,
                "Ping": lambda req: {"pong": True, "worker_id": self.worker_id},
                "Stop": self._on_stop,
                "ProfileRequest": self._on_profile,
            },
            host=bind_host,
        )

    def register(self) -> None:
        # A host pod's worker may be up before the driver pod's master
        # listens (the store agent waits the same way).
        self.master.wait_ready(timeout=30.0)
        last_exc = None
        for attempt in range(REGISTER_RETRIES):
            try:
                reply = self.master.call(
                    "RegisterWorker",
                    {
                        "worker_id": self.worker_id,
                        "address": self._server.address,
                        "pid": os.getpid(),
                        "node_id": self.node_id,
                        "resources": self.resources,
                    },
                )
                namespace = reply["namespace"]
                self.store = ObjectStore(
                    namespace=namespace, node_id=self.node_id
                )
                from raydp_tpu.store.object_store import (
                    set_current_resolver,
                    set_current_store,
                )

                set_current_store(self.store)
                self.ctx = WorkerContext(
                    self.worker_id, self.node_id, self.store, self.master
                )
                set_current_resolver(self.ctx.resolver)
                self._ready.set()
                return
            except Exception as exc:
                last_exc = exc
                time.sleep(0.5 * (attempt + 1))
        raise RuntimeError(
            f"worker {self.worker_id} failed to register after "
            f"{REGISTER_RETRIES} attempts: {last_exc}"
        )

    def _on_run_task(self, req: dict) -> dict:
        rid = req.get("request_id")
        if rid is None:
            return self._execute_task(req)
        with self._dedup_lock:
            entry = self._dedup.get(rid)
            owner = entry is None
            if owner:
                entry = {
                    "done": threading.Event(), "reply": None, "error": None,
                }
                self._dedup[rid] = entry
                while len(self._dedup) > _DEDUP_CAPACITY:
                    self._dedup.popitem(last=False)
            else:
                self._dedup.move_to_end(rid)
        if not owner:
            # Re-delivery of an envelope this process already has:
            # return the first execution's outcome (waiting it out if
            # still in flight) — never run the fn a second time.
            metrics.counter_add("worker/dup_tasks")
            if not entry["done"].wait(timeout=_DEDUP_WAIT_S):
                raise RuntimeError(
                    f"duplicate delivery of task {rid}: original "
                    f"execution still in flight after {_DEDUP_WAIT_S:.0f}s"
                )
            if entry["error"] is not None:
                raise RuntimeError(entry["error"])
            return entry["reply"]
        try:
            reply = self._execute_task(req)
        except Exception as exc:
            entry["error"] = f"{type(exc).__name__}: {exc}"
            entry["done"].set()
            raise
        entry["reply"] = reply
        entry["done"].set()
        return reply

    def _execute_task(self, req: dict) -> dict:
        # The worker's own stamps (``perf_counter``): handler entered,
        # body start and end, the body's fetches, puts and registrations
        # (``_task_body``), handler about to return. They ride the
        # reply beside ``exec_s`` so the driver partitions a stage's
        # wall from inside (``_StageRecorder``) with no extra RPC.
        recv = time.perf_counter()
        # Busy goes up FIRST: between this handler starting and fn
        # deserializing, the heartbeat thread must already see the task
        # — an exit decision in that setup window would cancel it.
        with self._busy_lock:
            self._busy += 1
        try:
            if not self._ready.wait(timeout=15.0):
                raise RuntimeError(
                    "worker context not ready (registration hung)"
                )
            with span("worker/task_load", worker_id=self.worker_id):
                fn = cloudpickle.loads(req["fn"])
                args = req.get("args", ())
                kwargs = req.get("kwargs", {})
                # data_args travel the data plane: the envelope carries
                # refs, the tables are resolved here (zero-copy from
                # local shm when co-located with the submitter, chunked
                # agent fetch if not).
                data = self._resolve_data_refs(req.get("data_refs", ()))
            self._fault_task_hook()
            metrics.counter_add("worker/tasks")
            _flight.record("task", "start", worker_id=self.worker_id)
            # RpcServer already installed the caller's traceparent as
            # this handler thread's ambient context, so this span — and
            # any span the task body opens — lands in the driver's
            # job trace, under the submitting envelope span. The inflight
            # bracket is the watchdog's stall signal: a wedged task
            # body shows up as component "worker/task" — at the long-op
            # threshold, since a healthy task may run for minutes.
            start = time.perf_counter()
            with _watchdog.inflight(
                "worker/task", worker_id=self.worker_id,
                stall_after_s=_watchdog.long_stall_s(),
            ):
                with span("worker/task", worker_id=self.worker_id), \
                        _task_body() as parts:
                    result = fn(self.ctx, *args, *data, **kwargs)
            end = time.perf_counter()
            _flight.record("task", "end", worker_id=self.worker_id)
            exec_s = self._task_ran(start, end)
            return {
                "result": result, "exec_s": exec_s,
                "start": start, "end": end, "parts": parts,
                "recv": recv, "ret": time.perf_counter(),
            }
        except Exception:
            # Let RpcServer._wrap serialize the failure uniformly.
            raise
        finally:
            with self._busy_lock:
                self._busy -= 1

    def _task_ran(self, start: float, end: float) -> float:
        """One body interval, measured once: it bills the submitting
        job (RpcServer._wrap installed the caller's job scope, so
        host-CPU task seconds go to the job that sent the task, not to
        this worker's own identity) and feeds the ``worker/task`` timer
        the master's straggler attribution reads."""
        exec_s = end - start
        _acct.add_usage(_acct.TASK_SECONDS, exec_s)
        metrics.timer("worker/task").observe(exec_s)
        return exec_s

    def _resolve_data_refs(self, refs):
        return [self.ctx.get_table(r) for r in refs]

    def _fault_task_hook(self) -> None:
        """Fault-plan hook at each task start (kill worker=…,task=K)."""
        with self._busy_lock:
            seq = self._task_seq
            self._task_seq += 1
        if _fault.active():
            _fault.on_task(self.worker_id, seq)

    def _pool(self):
        with self._task_pool_lock:
            if self._task_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                self._task_pool = ThreadPoolExecutor(
                    max_workers=max(4, os.cpu_count() or 4),
                    thread_name_prefix=f"{self.worker_id}-task",
                )
            return self._task_pool

    def _on_run_task_batch(self, req: dict) -> dict:
        """One envelope, many tasks (the driver's submit_batch).

        Each distinct fn arrives once in ``fns``; tasks reference it by
        slot. Tasks run concurrently on the worker task pool and each
        reports per-task ``{"ok": ...}`` so one bad partition fails only
        its own future, not its siblings in the envelope. The reply
        carries the envelope's ``recv``/``ret`` stamps, each task its
        body's ``start``/``end`` and ``parts``.
        """
        recv = time.perf_counter()
        with self._busy_lock:
            self._busy += 1
        try:
            if not self._ready.wait(timeout=15.0):
                raise RuntimeError(
                    "worker context not ready (registration hung)"
                )
            with span("worker/task_load", worker_id=self.worker_id):
                fns = [cloudpickle.loads(b) for b in req["fns"]]
            tasks = req.get("tasks", ())
            metrics.counter_add("worker/tasks", len(tasks))
            metrics.counter_add("worker/task_batches")
            _flight.record("task", "batch_start", worker_id=self.worker_id,
                           tasks=len(tasks))
            # Task-pool threads don't inherit this handler thread's
            # propagated traceparent — re-propagate it so per-task spans
            # still parent under the driver's envelope span. The job
            # scope crosses the same thread boundary the same way.
            batch_ctx = trace_prop.current_context()
            batch_job = _acct.current_job()

            def run_one(task: dict) -> dict:
                try:
                    fn = fns[task["fn"]]
                    args = task.get("args", ())
                    kwargs = task.get("kwargs", {})
                    with trace_prop.propagated(batch_ctx), \
                            _acct.job_scope(batch_job):
                        data = ()
                        if task.get("data_refs"):
                            with span("worker/task_load",
                                      worker_id=self.worker_id):
                                data = self._resolve_data_refs(
                                    task["data_refs"]
                                )
                        self._fault_task_hook()
                        start = time.perf_counter()
                        with span("worker/task", worker_id=self.worker_id), \
                                _task_body() as parts:
                            value = fn(self.ctx, *args, *data, **kwargs)
                        end = time.perf_counter()
                        exec_s = self._task_ran(start, end)
                    return {"ok": True, "value": value, "exec_s": exec_s,
                            "start": start, "end": end, "parts": parts}
                except Exception as exc:
                    return {
                        "ok": False,
                        "error": f"{type(exc).__name__}: {exc}",
                        "traceback": traceback.format_exc(),
                    }

            with _watchdog.inflight(
                "worker/task", worker_id=self.worker_id,
                stall_after_s=_watchdog.long_stall_s(),
            ):
                if len(tasks) == 1:
                    results = [run_one(tasks[0])]
                else:
                    results = list(self._pool().map(run_one, tasks))
            _flight.record("task", "batch_end", worker_id=self.worker_id,
                           tasks=len(tasks))
            return {"results": results, "recv": recv,
                    "ret": time.perf_counter()}
        finally:
            with self._busy_lock:
                self._busy -= 1

    def _on_profile(self, req: dict) -> dict:
        """Gang trace capture on this ETL worker. Runs on the RPC
        handler thread, concurrent with any in-flight tasks — the trace
        samples them live. The zip ships through the shm object store
        when the worker is registered (``{"ref": ...}``); inline bytes
        are the pre-registration fallback."""
        from raydp_tpu.telemetry import device_profiler

        seconds = float(req.get("seconds", 3.0))
        _flight.record("profile", "start", worker_id=self.worker_id,
                       seconds=seconds)
        payload = device_profiler.capture_trace_archive(seconds)
        payload["worker_id"] = self.worker_id
        if self._ready.is_set():
            try:
                blob = payload.pop("zip")
                payload["ref"] = self.ctx.put_bytes(blob)
            except Exception:
                payload["zip"] = blob  # store unavailable: inline
        _flight.record("profile", "end", worker_id=self.worker_id)
        return payload

    def _on_stop(self, req: dict) -> dict:
        # Register the objects this worker still owns with the master before
        # exit? No — ownership semantics: non-transferred objects die with
        # the worker; the master unlinks them on WorkerStopped/death.
        self._stop_event.set()
        return {"stopping": True}

    def _serve_debug(self):
        """Per-worker /healthz + /debug endpoints when
        RAYDP_TPU_DEBUG_PORT is set (0 = ephemeral, logged). The wedged
        process answering 503 here while /metrics keeps serving is the
        per-process face of the health plane."""
        from raydp_tpu.telemetry import (
            DEBUG_PORT_ENV,
            render_prometheus,
            serve_prometheus,
        )

        port = os.environ.get(DEBUG_PORT_ENV)
        if port is None:
            return None
        try:
            return serve_prometheus(
                lambda: render_prometheus(
                    {"workers": {self.worker_id: metrics.snapshot()}}
                ),
                int(port),
            )
        except Exception:
            logger.exception("worker debug endpoint failed to start")
            return None

    def run(self) -> None:
        self.register()
        _flight.record("state", "registered", worker_id=self.worker_id)
        debug_server = self._serve_debug()
        missed = 0
        beat_index = 0
        while not self._stop_event.wait(2.0):
            # Fault-plan hook: hb_stall silences this worker's beats so
            # the master's liveness monitor sees a partitioned host.
            if _fault.active() and _fault.on_heartbeat(
                beat_index, worker=self.worker_id
            ):
                beat_index += 1
                continue
            beat_index += 1
            beat = {"worker_id": self.worker_id}
            # Refresh resource gauges (RSS, HBM, store occupancy) so the
            # delta below ships them to the master's merged view.
            try:
                from raydp_tpu.utils.profiling import sample_resource_gauges

                sample_resource_gauges()
            except Exception:
                pass
            delta = self._shipper.delta()
            if delta:
                beat["metrics"] = delta
            # Ship stall flags so the master's health_report() names
            # this worker and the stuck component while the task RPC is
            # still open (long before any heartbeat timeout: a wedged
            # task does not stop THIS thread).
            health = _watchdog.health()
            if not health.get("healthy", True):
                beat["health"] = {"stalls": health.get("stalls", {})}
            reply = self.master.try_call("Heartbeat", beat, timeout=8.0)
            # Shard spans continuously (no-op without a telemetry dir):
            # the driver's live trace_report() sees worker spans at
            # heartbeat latency, and a later SIGKILL loses ≤1 beat.
            flush_spans()
            with self._busy_lock:
                busy = self._busy > 0
            if reply is None:
                _flight.record("heartbeat", "missed", missed=missed + 1)
                # Failed beats must not eat their metrics delta: re-ship
                # the sections on the next beat.
                self._shipper.rollback(delta)
                # Transient master hiccups — including a driver process
                # saturated by a big shuffle on a small host — are
                # absorbed; only a sustained outage means exit. And never
                # while a task is executing: a starved master during a
                # shuffle is the NORM on small hosts, and exiting here
                # cancels the very task the driver is waiting on.
                missed += 1
                if missed >= 8 and not busy:
                    logger.warning(
                        "worker %s: master unreachable for %d beats; exiting",
                        self.worker_id, missed,
                    )
                    break
                if missed >= 60:
                    # Hard cap even while busy: with the driver truly
                    # gone AND the task wedged (user-code deadlock),
                    # nothing else can ever kill this process — without
                    # a bound it would orphan forever with its shm
                    # segments. 60 beats ≈ several minutes of sustained
                    # outage, far beyond any GIL stall.
                    logger.error(
                        "worker %s: master unreachable for %d beats with "
                        "a task still in flight; exiting to avoid an "
                        "immortal orphan", self.worker_id, missed,
                    )
                    break
                continue
            missed = 0
            if not reply.get("known", False):
                if busy:
                    # The master wrote us off (its monitor starved while
                    # our heartbeats queued) but the driver's task RPC to
                    # us is still open — finish it; the result makes it
                    # back on that same channel. Exit once idle.
                    logger.warning(
                        "worker %s: master disowned us mid-task; finishing "
                        "in-flight work before exiting", self.worker_id,
                    )
                    continue
                # Master explicitly wrote us off — exit now (parity with
                # executor exit on AppMaster disconnect).
                logger.warning("worker %s: master disowned us; exiting",
                               self.worker_id)
                break
        # Final snapshot, not a delta: a clean exit must leave the master's
        # tombstoned view complete even if the last few deltas were lost.
        self.master.try_call(
            "WorkerStopped",
            {"worker_id": self.worker_id, "metrics": self._shipper.full()},
            timeout=2.0,
        )
        _flight.record("state", "stopping", worker_id=self.worker_id)
        # Tail spans of a clean exit (the atexit hook is a backstop for
        # paths that bypass run(), e.g. a registration failure).
        flush_spans()
        if debug_server is not None:
            debug_server.close()
        with self._task_pool_lock:
            if self._task_pool is not None:
                self._task_pool.shutdown(wait=False)
        self._server.stop()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--worker-id", required=True)
    parser.add_argument("--master", required=True)
    parser.add_argument("--node-id", default="node-0")
    parser.add_argument("--cores", type=float, default=1.0)
    parser.add_argument("--memory", type=float, default=0.0)
    parser.add_argument("--bind-host", default="127.0.0.1")
    args = parser.parse_args(argv)

    logging.basicConfig(
        level=logging.INFO,
        format=f"[{args.worker_id}] %(levelname)s %(message)s",
    )
    # Join the driver's job trace (RAYDP_TPU_TRACEPARENT in our launch
    # env) before any span is recorded; flush tail spans on interpreter
    # exit so clean shutdowns never lose the last buffer. The job
    # identity (RAYDP_TPU_JOB) is adopted the same way, so usage this
    # process emits outside any RPC scope still bills correctly.
    trace_prop.adopt_env_context()
    _acct.adopt_env_job()
    # Health plane: black box (crash/SIGTERM postmortem bundles),
    # trace-stamped JSONL logs, and the progress watchdog.
    _flight.install(component="worker")
    _logs.install()
    _watchdog.ensure_started()
    atexit.register(flush_spans)
    worker = Worker(
        args.worker_id,
        args.master,
        args.node_id,
        {"cpu": args.cores, "memory": args.memory},
        bind_host=args.bind_host,
    )
    try:
        worker.run()
    except Exception:
        traceback.print_exc()
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
