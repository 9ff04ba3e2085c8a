"""SPMD job worker process.

One rank of the gang launched by :class:`raydp_tpu.spmd.job.SPMDJob`.
Registers with the driver, then executes shipped functions on a dedicated
runner thread in strict ``func_id`` order (the reference's TaskRunner with
monotonic-id check, reference: python/raydp/mpi/mpi_worker.py:63-96).

Functions receive an :class:`SPMDWorkerContext`; for multi-host TPU work
they call ``ctx.init_jax_distributed()`` which wires ``jax.distributed``
to the driver-provisioned rank-0 coordinator, after which XLA collectives
span the whole gang — the role MPI collectives play in the reference.
"""
from __future__ import annotations

import atexit
import contextlib
import logging
import os
import queue
import sys
import threading
import time
import traceback
from typing import Optional

import cloudpickle

from raydp_tpu import fault as _fault
from raydp_tpu.cluster.rpc import RpcClient, RpcServer
from raydp_tpu.spmd.job import (
    DRIVER_SERVICE,
    ENV_COORDINATOR,
    ENV_DRIVER_ADDR,
    ENV_JOB_NAME,
    ENV_PROCS_PER_NODE,
    ENV_RANK,
    ENV_WORLD_SIZE,
    WORKER_SERVICE,
)
from raydp_tpu.telemetry import MetricsShipper, flush_spans, span
from raydp_tpu.telemetry import accounting as _acct
from raydp_tpu.telemetry import flight_recorder as _flight
from raydp_tpu.telemetry import logs as _logs
from raydp_tpu.telemetry import propagation as trace_prop
from raydp_tpu.telemetry import watchdog as _watchdog
from raydp_tpu.utils.compile_cache import ensure_compile_cache
from raydp_tpu.utils.net import local_ip

logger = logging.getLogger(__name__)


class SPMDWorkerContext:
    """First argument to every shipped function
    (reference: WorkerContext, mpi/mpi_worker.py:45-60)."""

    def __init__(self, job_name: str, rank: int, world_size: int,
                 local_rank: int, node_ip: str, coordinator_address: str):
        self.job_name = job_name
        self.rank = rank
        self.world_size = world_size
        self.local_rank = local_rank
        self.node_ip = node_ip
        self.coordinator_address = coordinator_address
        self._jax_initialized = False

    def init_jax_distributed(self) -> None:
        """Join the gang's jax.distributed coordination service; after this
        ``jax.devices()`` spans all ranks' chips and pjit collectives run
        over ICI/DCN. Idempotent per process."""
        if self._jax_initialized:
            return
        import jax

        jax.distributed.initialize(
            coordinator_address=self.coordinator_address,
            num_processes=self.world_size,
            process_id=self.rank,
        )
        self._jax_initialized = True


class SPMDWorker:
    def __init__(self):
        self.job_name = os.environ[ENV_JOB_NAME]
        self.rank = int(os.environ[ENV_RANK])
        self.world_size = int(os.environ[ENV_WORLD_SIZE])
        procs_per_node = int(os.environ.get(ENV_PROCS_PER_NODE, "1"))
        self.ctx = SPMDWorkerContext(
            self.job_name,
            self.rank,
            self.world_size,
            local_rank=self.rank % procs_per_node,
            node_ip=local_ip(),
            coordinator_address=os.environ[ENV_COORDINATOR],
        )
        driver_addr = os.environ[ENV_DRIVER_ADDR]
        self.driver = RpcClient(driver_addr, DRIVER_SERVICE)
        self._queue: "queue.Queue[Optional[dict]]" = queue.Queue()
        self._stop_event = threading.Event()
        self._last_func_id = 0
        # Mirror the driver's multi-host binding: a remote driver must be
        # able to reach this rank's service across the network.
        multihost = not driver_addr.startswith("127.0.0.1")
        self._server = RpcServer(
            WORKER_SERVICE,
            {
                "RunFunction": self._on_run_function,
                "Stop": self._on_stop,
                "ProfileRequest": self._on_profile,
                "Preempt": self._on_preempt,
            },
            host="0.0.0.0" if multihost else "127.0.0.1",
        )
        self._advertise = (
            f"{self.ctx.node_ip}:{self._server.port}" if multihost
            else self._server.address
        )

    def _on_run_function(self, req: dict) -> dict:
        self._queue.put(req)
        return {"queued": req["func_id"]}

    def _on_stop(self, req: dict) -> dict:
        self._stop_event.set()
        self._queue.put(None)
        return {"stopping": True}

    def _on_preempt(self, req: dict) -> dict:
        """Scheduler-driven preemption notice (driver ``Preempt`` RPC).

        Sets the same in-process drain flag a SIGTERM would: the
        training loop finishes the in-flight step, writes an emergency
        checkpoint, and raises PreemptionError. Delivered over RPC
        because ``jax.distributed`` replaces the Python SIGTERM handler
        with TSL's preemption notifier once initialized."""
        grace = req.get("grace_s")
        _fault.request_preemption(
            grace_s=float(grace) if grace is not None else None
        )
        return {"preempting": True, "rank": self.rank}

    def _on_profile(self, req: dict) -> dict:
        """Gang-coordinated trace capture: runs ON the RPC handler
        thread, concurrent with whatever shipped function the runner
        thread is executing — that concurrency is the point: the trace
        window samples live training, it does not pause it."""
        from raydp_tpu.telemetry import device_profiler

        seconds = float(req.get("seconds", 3.0))
        _flight.record("profile", "start", rank=self.rank,
                       seconds=seconds)
        payload = device_profiler.capture_trace_archive(
            seconds, rank=self.rank
        )
        _flight.record("profile", "end", rank=self.rank,
                       nbytes=len(payload.get("zip") or b""))
        return payload

    def _payload_blob(self, item: dict, key: str) -> Optional[bytes]:
        """Bytes for ``key`` (``fn`` / ``args``) of a queued dispatch.

        Inline payloads ride the envelope as before; oversize payloads
        arrive as ``<key>_ref`` and are pulled back from the driver's
        staging store in bounded chunks — the same FetchObjectChunk
        protocol (and chunk-size env) the cross-host data plane uses,
        so a seq-16384 closure never has to fit one RPC message.
        """
        blob = item.get(key)
        if blob is not None:
            return blob
        object_id = item.get(f"{key}_ref")
        if object_id is None:
            return None
        from raydp_tpu.store.resolver import _fetch_chunk_bytes
        from raydp_tpu.utils.profiling import metrics as _metrics

        chunk = max(1024 * 1024, _fetch_chunk_bytes())
        reply = self.driver.call(
            "FetchObjectChunk",
            {"object_id": object_id, "offset": 0, "length": chunk},
            timeout=120.0,
        )
        total = int(reply["size"])
        first = reply["data"]
        buf = bytearray(total)
        buf[: len(first)] = first
        offset = len(first)
        while offset < total:
            part = self.driver.call(
                "FetchObjectChunk",
                {"object_id": object_id, "offset": offset, "length": chunk},
                timeout=120.0,
            )["data"]
            if not part:
                raise RuntimeError(
                    f"short read fetching dispatch blob {object_id}: "
                    f"{offset}/{total} bytes"
                )
            buf[offset: offset + len(part)] = part
            offset += len(part)
        expect = int(item.get(f"{key}_size") or total)
        if offset != expect:
            raise RuntimeError(
                f"dispatch blob {object_id} size mismatch: fetched "
                f"{offset}, expected {expect}"
            )
        _metrics.counter_add("spmd/blob_fetches")
        _metrics.counter_add("spmd/blob_fetch_bytes", total)
        return bytes(buf)

    def _runner(self) -> None:
        while not self._stop_event.is_set():
            item = self._queue.get()
            if item is None:
                return
            func_id = item["func_id"]
            if func_id <= self._last_func_id:
                # Duplicate delivery — the driver's ids only move forward.
                continue
            self._last_func_id = func_id
            value, error = None, None
            # The RunFunction handler only enqueues; THIS thread does the
            # work — so RPC-level ambient context does not cover it. The
            # traceparent key travels in the queued request instead, and
            # the execution span parents under the driver's
            # spmd/dispatch span.
            ctx = trace_prop.extract(item)
            scope = (
                trace_prop.propagated(ctx)
                if ctx is not None
                else contextlib.nullcontext()
            )
            # The driver's job rides the queued request the same way —
            # usage the function emits bills to the submitting job even
            # when it differs from this gang's env-adopted default.
            jctx = _acct.extract(item)
            job_scope = (
                _acct.job_scope(jctx)
                if jctx is not None
                else contextlib.nullcontext()
            )
            _flight.record("func", "start", rank=self.rank,
                           func_id=func_id)
            # A wedged shipped function (collective waiting on a dead
            # peer is the classic) is attributed as "spmd/func" — at the
            # long-op threshold: a shipped function is often a whole
            # training loop, and healthy minutes-long runs must not
            # read as stalls.
            with scope, job_scope, _watchdog.inflight(
                "spmd/func", rank=self.rank, func_id=func_id,
                stall_after_s=_watchdog.long_stall_s(),
            ), span(
                "spmd/func", rank=self.rank, func_id=func_id
            ) as sp:
                try:
                    fn = cloudpickle.loads(self._payload_blob(item, "fn"))
                    args_blob = self._payload_blob(item, "args")
                    args = (
                        cloudpickle.loads(args_blob)
                        if args_blob is not None
                        else ()
                    )
                    value = fn(self.ctx, *args)
                except Exception:
                    error = traceback.format_exc()
                    sp.status = "error"
            _flight.record("func", "end", rank=self.rank,
                           func_id=func_id,
                           **({"status": "error"} if error else {}))
            reply = self.driver.try_call(
                "FuncResult",
                {
                    "func_id": func_id,
                    "rank": self.rank,
                    "value": value,
                    "error": error,
                },
                timeout=10.0,
            )
            if reply is None:
                logger.warning(
                    "rank %d: driver unreachable posting result %d; exiting",
                    self.rank, func_id,
                )
                self._stop_event.set()
                return

    def _heartbeat(self) -> None:
        """Detect a dead driver while idle — without this, a SIGKILLed
        driver would orphan the whole gang (and the chips it holds)
        forever; result-posting only notices mid-function.

        Each beat also ships the registry sections that changed since the
        previous one (delta-encoded ``metrics.snapshot()``), so the driver's
        ``SPMDJob.metrics_snapshot()`` sees per-rank step timers and
        throughput without a second RPC channel."""
        shipper = MetricsShipper()
        missed = 0
        # Compile-time accounting for everything this rank jits; the
        # counters ride the same metric deltas as the step timers.
        from raydp_tpu.utils.profiling import (
            install_compile_listener,
            metrics,
            sample_resource_gauges,
        )

        install_compile_listener()
        beat_index = 0
        last_mono = time.monotonic()
        while not self._stop_event.wait(5.0):
            # Fault-plan hook: an hb_stall clause silences this rank's
            # beats without touching the socket — the driver-side
            # liveness view sees exactly what a partitioned host
            # produces: nothing.
            if _fault.active() and _fault.on_heartbeat(
                beat_index, rank=self.rank
            ):
                beat_index += 1
                continue
            beat_index += 1
            beat = {"rank": self.rank}
            # HBM used/peak + host RSS for this rank, refreshed per beat.
            try:
                sample_resource_gauges()
            except Exception:
                pass
            # HBM-byte-seconds: the occupancy gauge is a point sample;
            # integrating gauge × dt at beat cadence turns it into a
            # meterable quantity the job ledger can bill (memory held,
            # not just memory touched).
            now_mono = time.monotonic()
            hbm = metrics.gauge_value("hbm/used_bytes")
            if hbm:
                _acct.add_usage(
                    _acct.HBM_BYTE_SECONDS, hbm * (now_mono - last_mono)
                )
            last_mono = now_mono
            delta = shipper.delta()
            if delta:
                beat["metrics"] = delta
            # Stall flags ride the Ping: the driver's
            # SPMDJob.health_report() names this rank and the stuck
            # component while the function is still "running".
            health = _watchdog.health()
            if not health.get("healthy", True):
                beat["health"] = {"stalls": health.get("stalls", {})}
            # Shard this rank's spans continuously (no-op without a
            # telemetry dir) so a driver-side trace_report sees them live.
            flush_spans()
            if self.driver.try_call("Ping", beat, timeout=5.0) is None:
                _flight.record("heartbeat", "missed", missed=missed + 1)
                shipper.rollback(delta)  # re-ship the delta next beat
                missed += 1
                if missed >= 3:
                    logger.warning(
                        "rank %d: driver unreachable for %d beats; exiting",
                        self.rank, missed,
                    )
                    self._stop_event.set()
                    self._queue.put(None)
                    return
            else:
                missed = 0

    def _serve_debug(self):
        """Per-rank /healthz + /debug endpoints when
        RAYDP_TPU_DEBUG_PORT is set (0 = ephemeral, logged)."""
        from raydp_tpu.telemetry import (
            DEBUG_PORT_ENV,
            render_prometheus,
            serve_prometheus,
        )
        from raydp_tpu.utils.profiling import metrics

        port = os.environ.get(DEBUG_PORT_ENV)
        if port is None:
            return None
        try:
            return serve_prometheus(
                lambda: render_prometheus(
                    {"workers": {f"rank-{self.rank}": metrics.snapshot()}}
                ),
                int(port),
            )
        except Exception:
            logger.exception("rank debug endpoint failed to start")
            return None

    def run(self) -> int:
        self.driver.call(
            "RegisterWorker",
            {
                "rank": self.rank,
                "address": self._advertise,
                "host": self.ctx.node_ip,
                "pid": os.getpid(),
            },
        )
        _flight.record("state", "registered", rank=self.rank)
        debug_server = self._serve_debug()
        runner = threading.Thread(target=self._runner, daemon=True)
        runner.start()
        threading.Thread(target=self._heartbeat, daemon=True).start()
        self._stop_event.wait()
        runner.join(timeout=2.0)
        _flight.record("state", "stopping", rank=self.rank)
        flush_spans()  # tail spans of a clean stop (atexit is backstop)
        if debug_server is not None:
            debug_server.close()
        self._server.stop()
        self.driver.close()
        return 0


def main() -> int:
    logging.basicConfig(
        level=logging.INFO,
        format=f"[spmd-{os.environ.get(ENV_RANK, '?')}] %(levelname)s %(message)s",
    )
    ensure_compile_cache()
    # Join the driver's job trace before any span is recorded, and its
    # job identity before any usage is billed; flush tail spans on
    # interpreter exit.
    trace_prop.adopt_env_context()
    _acct.adopt_env_job()
    # Health plane: crash/SIGTERM postmortem bundles, trace-stamped
    # JSONL logs, progress watchdog.
    _flight.install(component="spmd-worker")
    # After the flight recorder's SIGTERM dump handler: a preemption
    # notice must drain the step and write an emergency checkpoint, not
    # dump-and-die. The drain path still produces a postmortem bundle if
    # the grace deadline force-exits.
    _fault.install_sigterm_drain()
    _logs.install()
    _watchdog.ensure_started()
    atexit.register(flush_spans)
    try:
        return SPMDWorker().run()
    except Exception:
        traceback.print_exc()
        # Best-effort failure report so the driver fails fast rather than
        # timing out (reference: mpirun watcher failed_callback,
        # mpi/mpi_job.py:265-271).
        try:
            RpcClient(
                os.environ[ENV_DRIVER_ADDR], DRIVER_SERVICE
            ).try_call(
                "JobFailed",
                {"reason": f"rank {os.environ.get(ENV_RANK)}: "
                           f"{traceback.format_exc(limit=3)}"},
                timeout=2.0,
            )
        except Exception:
            pass
        return 1


if __name__ == "__main__":
    sys.exit(main())
