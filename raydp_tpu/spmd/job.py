"""SPMD host-process job runner — driver side.

Capability parity with the reference's MPI-on-Ray subsystem
(reference: python/raydp/mpi/mpi_job.py:119-426, __init__.py:36-91):
launch a gang of ``world_size`` host processes, ship cloudpickled
functions to every rank, collect per-rank results, stop/restart the gang.

TPU-first differences from the reference:

* No mpirun. On a TPU pod each host runs exactly the processes we spawn;
  process launch is direct (subprocess per rank locally; a
  ``script_prepare_fn`` hook customizes the launch command for ssh/pod
  launchers, the reference's ``mpi_script_prepare_fn`` extension point,
  reference: mpi/mpi_job.py:239-248).
* The collective fabric available inside shipped functions is
  ``jax.distributed`` + XLA collectives over ICI/DCN, not MPI. The driver
  provisions the rank-0 coordinator address and every
  :class:`~raydp_tpu.spmd.worker_main.SPMDWorkerContext` exposes
  ``init_jax_distributed()``.
* One wire protocol: the same pickle-over-gRPC transport as the rest of
  the control plane (the reference runs a second protobuf service just
  for MPI, reference: mpi/network/network_pb2_grpc.py).
"""
from __future__ import annotations

import logging
import os
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import cloudpickle

from raydp_tpu.cluster.rpc import RpcClient, RpcServer
from raydp_tpu.telemetry import ClusterTelemetry, span
from raydp_tpu.telemetry import accounting as _acct
from raydp_tpu.telemetry import events as _events
from raydp_tpu.telemetry import flight_recorder as _flight
from raydp_tpu.telemetry import watchdog as _watchdog
from raydp_tpu.utils.net import find_free_port
from raydp_tpu.utils.profiling import CompileError
from raydp_tpu.utils.profiling import metrics as _metrics

logger = logging.getLogger(__name__)

DRIVER_SERVICE = "raydp.SPMDDriver"
WORKER_SERVICE = "raydp.SPMDWorker"

# Env vars carrying gang identity to worker processes (the reference ships
# these via mpirun's environment, reference: mpi/constants.py:20-28,
# mpi/mpi_job.py:250-258).
ENV_JOB_NAME = "RAYDP_SPMD_JOB_NAME"
ENV_RANK = "RAYDP_SPMD_RANK"
ENV_WORLD_SIZE = "RAYDP_SPMD_WORLD_SIZE"
ENV_DRIVER_ADDR = "RAYDP_SPMD_DRIVER_ADDR"
ENV_COORDINATOR = "RAYDP_SPMD_COORDINATOR"
ENV_PROCS_PER_NODE = "RAYDP_SPMD_PROCS_PER_NODE"
# Registration-barrier tuning (driver side). The soft window resets on
# every new rank registration; alive-but-slow workers (cold imports on a
# busy host) are waited on up to the hard cap.
ENV_REGISTER_TIMEOUT = "RAYDP_SPMD_REGISTER_TIMEOUT"
ENV_REGISTER_HARD_TIMEOUT = "RAYDP_SPMD_REGISTER_HARD_TIMEOUT"
# Dispatch-payload shipping policy. Payloads (fn closure + scatter blob)
# above the inline cap leave the RPC envelope and travel the chunked
# shm-store fetch path instead — the fix for the seq-16384
# dense-attention dispatch 500s, where a jaxpr-laden closure blew the
# one-envelope ceiling. The hard cap is the fail-fast guard: anything
# bigger than the transport can ever carry raises a structured
# CompileError instead of timing out against a wedged channel.
ENV_INLINE_CAP = "RAYDP_TPU_RPC_INLINE_CAP_MB"
ENV_PAYLOAD_HARD_CAP = "RAYDP_TPU_RPC_PAYLOAD_HARD_CAP_MB"
_DEFAULT_INLINE_CAP_MB = 64.0
_DEFAULT_HARD_CAP_MB = 448.0  # headroom under the 512 MB gRPC ceiling


def _env_mb(name: str, default_mb: float) -> int:
    raw = os.environ.get(name)
    try:
        mb = float(raw) if raw else default_mb
    except ValueError:
        mb = default_mb
    return int(mb * 1024 * 1024)


class SPMDJobError(RuntimeError):
    pass


class SPMDJobContext:
    """Handed to ``script_prepare_fn`` so users can customize the launch
    (reference: MPIJobContext, mpi/mpi_job.py:91-116)."""

    def __init__(self, job_name: str, world_size: int, hosts: List[str],
                 num_procs_per_node: int):
        self.job_name = job_name
        self.world_size = world_size
        self._hosts = hosts
        self._num_procs_per_node = num_procs_per_node
        self._env: Dict[str, str] = {}

    @property
    def hosts(self) -> List[str]:
        return self._hosts

    @property
    def num_procs_per_node(self) -> int:
        return self._num_procs_per_node

    @property
    def env(self) -> Dict[str, str]:
        return self._env

    def add_env(self, key: str, value: str) -> None:
        self._env[key] = value

    def add_envs(self, envs: Dict[str, str]) -> None:
        self._env.update(envs)


class _FuncResults:
    """Barrier collecting one result per rank for a shipped function
    (reference: FunctionResults, mpi/mpi_job.py:82-88)."""

    def __init__(self, func_id: int, world_size: int):
        self.func_id = func_id
        self.results: List[Any] = [None] * world_size
        self.errors: List[Optional[str]] = [None] * world_size
        self._remaining = world_size
        self._lock = threading.Lock()
        self.done = threading.Event()

    def post(self, rank: int, value: Any, error: Optional[str]) -> None:
        with self._lock:
            self.results[rank] = value
            self.errors[rank] = error
            self._remaining -= 1
            if self._remaining == 0:
                self.done.set()


class SPMDJob:
    """A restartable gang of SPMD host processes.

    Lifecycle mirrors the reference MPIJob: ``start()`` brings up the gang
    and blocks until every rank registers; ``run(fn)`` ships ``fn`` to all
    ranks and returns rank-ordered results; ``stop()`` tears the gang down;
    ``start()`` again relaunches (restartability tested by the reference at
    python/raydp/tests/test_mpi.py:28-56).
    """

    def __init__(
        self,
        job_name: str,
        world_size: int,
        num_procs_per_node: int = 1,
        script_prepare_fn: Optional[Callable[[SPMDJobContext], List[str]]] = None,
        env: Optional[Dict[str, str]] = None,
        timeout: float = 30.0,
        hosts: Optional[List[str]] = None,
        coordinator_port: Optional[int] = None,
        register_hard_timeout: Optional[float] = None,
    ):
        """``timeout`` is the registration barrier's SOFT window (resets
        on progress); ``register_hard_timeout`` caps how long ranks that
        are alive-but-slow are waited on past it. Default ``None`` keeps
        the historical ``max(10 × soft, 300)`` — pass a small value so a
        wedged rank fails a short-timeout job in seconds, not minutes.
        The env vars (``RAYDP_SPMD_REGISTER_TIMEOUT`` /
        ``RAYDP_SPMD_REGISTER_HARD_TIMEOUT``) still override both, same
        precedence as the soft window's."""
        if world_size < 1:
            raise ValueError("world_size must be >= 1")
        self.job_name = job_name
        self.world_size = world_size
        self.num_procs_per_node = num_procs_per_node
        self.script_prepare_fn = script_prepare_fn
        self.base_env = dict(env or {})
        self.timeout = timeout
        self.register_hard_timeout = register_hard_timeout
        self.hosts = hosts or ["127.0.0.1"]
        self.coordinator_port = coordinator_port
        self._multihost = any(
            h not in ("127.0.0.1", "localhost") for h in self.hosts
        )

        self._server: Optional[RpcServer] = None
        self._procs: List[subprocess.Popen] = []
        self._worker_addrs: Dict[int, str] = {}
        self._worker_hosts: Dict[int, str] = {}
        self._stubs: Dict[int, RpcClient] = {}
        self._register_barrier = threading.Event()
        self._func_id = 0
        self._inflight: Optional[_FuncResults] = None
        self._lock = threading.Lock()
        self._started = False
        self._failed: Optional[str] = None
        # Ranks that registered in the most recent start() attempt —
        # survives the stop() cleanup so a supervisor can size an
        # elastic relaunch to the hosts that actually showed up.
        self.last_registered: Optional[int] = None
        self._gen = 0  # incarnation counter scoping watcher threads
        self._stopping = False
        self._log_paths: List[str] = []
        self._trace_ctx = None
        self._owns_trace_ctx = False
        self._job_ctx: Optional[_acct.JobContext] = None
        self._owns_job_ctx = False
        # Control-plane lease held by start() for standalone gangs
        # (None when a supervisor such as fit_spmd already admitted
        # this job, or when the arbiter is disabled).
        self._sched_lease = None
        # Driver-local staging store for oversize dispatch payloads:
        # blobs above the inline cap are parked here and ranks pull
        # them through the chunked FetchObjectChunk path.
        self._blob_store = None
        # Per-rank metrics merged from heartbeat-shipped deltas; survives
        # gang restarts (ranks keep their keys across incarnations).
        self.telemetry = ClusterTelemetry()
        # Watchdog stall flags shipped on rank Pings (empty = healthy).
        # Guarded by its own lock, NOT self._lock: Ping handlers must
        # never contend with dispatch bookkeeping.
        self._health_lock = threading.Lock()
        self._rank_health: Dict[str, dict] = {}
        # Monotonic timestamp of each rank's last Ping — health_report()
        # ages ranks out against it (late / dead vocabulary shared with
        # Cluster.health_report).
        self._rank_beats: Dict[str, float] = {}

    def rank_nodes(self) -> List[str]:
        """Node (host) of every rank — ranks fill hosts in order,
        ``num_procs_per_node`` per host. Feed to
        ``MLDataset(rank_nodes=...)`` for locality-preferring shard plans."""
        return [
            self.hosts[(r // self.num_procs_per_node) % len(self.hosts)]
            for r in range(self.world_size)
        ]

    # ------------------------------------------------------------------ start

    def start(self) -> "SPMDJob":
        if self._started:
            raise SPMDJobError(f"job {self.job_name} already started")
        self._failed = None
        self._stopping = False
        self._gen += 1
        gen = self._gen
        self._register_barrier.clear()
        self._worker_addrs.clear()
        self._worker_hosts.clear()

        # Multi-host gangs must reach the driver across the network: bind
        # all interfaces and advertise the routable IP, not loopback.
        from raydp_tpu.utils.net import local_ip

        bind_host = "0.0.0.0" if self._multihost else "127.0.0.1"
        # The driver doubles as a store agent for dispatch blobs: ranks
        # pull oversize fn/args payloads via the same chunked
        # FetchObjectChunk protocol the data plane uses cross-host.
        from raydp_tpu.store.agent import agent_handlers
        from raydp_tpu.store.object_store import ObjectStore

        if self._blob_store is None:
            self._blob_store = ObjectStore()
        self._server = RpcServer(
            DRIVER_SERVICE,
            {
                "RegisterWorker": self._on_register_worker,
                "FuncResult": self._on_func_result,
                "JobFailed": self._on_job_failed,
                "Ping": self._on_ping,
                "FetchObjectChunk": agent_handlers(self._blob_store)[
                    "FetchObjectChunk"
                ],
            },
            host=bind_host,
        )
        advertise = local_ip() if self._multihost else "127.0.0.1"
        driver_addr = f"{advertise}:{self._server.port}"
        coordinator = f"{self.hosts[0]}:{self._pick_coordinator_port()}"
        ctx = SPMDJobContext(
            self.job_name, self.world_size, self.hosts, self.num_procs_per_node
        )
        ctx.add_envs(self.base_env)
        prefix: List[str] = []
        if self.script_prepare_fn is not None:
            prefix = list(self.script_prepare_fn(ctx) or [])

        # Gang trace context: reuse the driver's ambient context when one
        # exists (an SPMD job inside a Cluster joins the cluster's job
        # trace); a standalone job mints its own root.
        from raydp_tpu.telemetry import propagation as trace_prop

        self._trace_ctx = trace_prop.current_context()
        self._owns_trace_ctx = self._trace_ctx is None
        if self._trace_ctx is None:
            self._trace_ctx = trace_prop.mint_context(
                "spmd/job", job=self.job_name, world_size=self.world_size
            )
            trace_prop.set_process_context(self._trace_ctx)
        # Job identity, same reuse-or-mint shape: a gang launched under
        # an ambient JobContext (fit_spmd, a cluster pipeline) bills its
        # chip-seconds there; a standalone gang is its own accounting
        # root. Ranks inherit it via RAYDP_TPU_JOB below.
        self._job_ctx = _acct.current_job()
        self._owns_job_ctx = self._job_ctx is None
        if self._job_ctx is None:
            self._job_ctx = _acct.mint_job(
                self.job_name, world_size=self.world_size
            )
            _acct.set_process_job(self._job_ctx)
        # Control-plane admission (doc/scheduling.md): a gang acquires
        # capacity BEFORE spawning ranks, blocking in the admission
        # queue when the cluster is full. No-op when the arbiter is
        # disabled or a supervisor (fit_spmd) already holds this job's
        # lease; raises ClusterBusyError on shed/timeout.
        from raydp_tpu.control import get_arbiter

        self._sched_lease = get_arbiter().ensure_admitted(
            self._job_ctx, slots=self.world_size, label=self.job_name,
            on_preempt=self.request_preemption,
        )

        log_dir = os.path.join(
            "/tmp/raydp_tpu", "spmd", f"{self.job_name}-{os.getpid()}"
        )
        os.makedirs(log_dir, exist_ok=True)
        self._log_paths = []
        for rank in range(self.world_size):
            env = dict(os.environ)
            env.update(ctx.env)
            env.update(
                {
                    ENV_JOB_NAME: self.job_name,
                    ENV_RANK: str(rank),
                    ENV_WORLD_SIZE: str(self.world_size),
                    ENV_DRIVER_ADDR: driver_addr,
                    ENV_COORDINATOR: coordinator,
                    ENV_PROCS_PER_NODE: str(self.num_procs_per_node),
                    **trace_prop.env_for_child(self._trace_ctx),
                    **_acct.env_for_child(self._job_ctx),
                }
            )
            cmd = prefix + [sys.executable, "-m", "raydp_tpu.spmd.worker_main"]
            # Capture each rank's output so bring-up failures can show it
            # (the reference forwards mpirun output to the driver's stdout,
            # reference: mpi/utils.py:68-80; files keep it available after
            # the fact too, per SURVEY §5.5 per-process log files).
            log_path = os.path.join(log_dir, f"rank-{rank}.log")
            self._log_paths.append(log_path)
            with open(log_path, "ab") as logf:
                proc = subprocess.Popen(
                    cmd, env=env, stdout=logf, stderr=subprocess.STDOUT
                )
            self._procs.append(proc)
            threading.Thread(
                target=self._watch_proc, args=(proc, rank, gen), daemon=True
            ).start()

        self._await_registration()
        if self._failed:
            # A rank crashed during bring-up; the barrier was released by
            # _fail so this raises immediately, not after the timeout.
            self.stop()
            raise SPMDJobError(
                f"job {self.job_name} failed: {self._failed}"
                + self._log_tails()
            )
        for rank, addr in self._worker_addrs.items():
            self._stubs[rank] = RpcClient(addr, WORKER_SERVICE, timeout=None)
        self.last_registered = len(self._worker_addrs)
        self._started = True
        _events.emit(
            "gang/launch", job=self._job_ctx, gang=self.job_name,
            world_size=self.world_size, registered=self.last_registered,
            gen=self._gen,
        )
        return self

    def _await_registration(self) -> None:
        """Progress-aware registration barrier. A fixed wall timeout fails
        spuriously when cold worker imports contend for CPU (two parallel
        cold JAX/grpc imports on a busy one-core host can take minutes),
        so: the soft window (``timeout``, env ``RAYDP_SPMD_REGISTER_
        TIMEOUT``) resets whenever a new rank registers, and workers that
        are still *alive* are waited on past it up to the hard cap
        (constructor ``register_hard_timeout``, env
        ``RAYDP_SPMD_REGISTER_HARD_TIMEOUT`` overriding, default
        ``max(10×soft, 300)``s). Dead-without-registering ranks fail fast
        via the process watcher. Failure messages carry each rank's log
        tail."""
        soft, hard = self._registration_timeouts()
        start_t = time.monotonic()
        deadline = start_t + soft
        seen = 0
        while not self._register_barrier.wait(1.0):
            now = time.monotonic()
            got = len(self._worker_addrs)
            if got > seen:
                seen = got
                deadline = now + soft  # progress resets the soft window
                continue
            if now < deadline:
                continue
            alive = all(p.poll() is None for p in self._procs)
            if alive and now < start_t + hard:
                continue  # slow but alive: cold imports on a loaded host
            tails = self._log_tails()
            self.last_registered = got
            self.stop()
            raise SPMDJobError(
                f"job {self.job_name}: only {got}/{self.world_size} ranks "
                f"registered within {now - start_t:.0f}s "
                f"(soft={soft:.0f}s hard={hard:.0f}s, "
                f"workers alive={alive})" + tails
            )

    def _registration_timeouts(self) -> "tuple[float, float]":
        """(soft, hard) windows for the registration barrier. Env vars
        keep precedence over constructor values (same pattern as the
        soft window: a deployed job can be retuned without code)."""
        soft = float(os.environ.get(ENV_REGISTER_TIMEOUT) or self.timeout)
        hard_env = os.environ.get(ENV_REGISTER_HARD_TIMEOUT)
        if hard_env:
            hard = float(hard_env)
        elif self.register_hard_timeout is not None:
            hard = float(self.register_hard_timeout)
        else:
            hard = max(10.0 * soft, 300.0)
        return soft, hard

    def _log_tails(self, limit: int = 2000) -> str:
        """Last ``limit`` bytes of every rank's captured output, formatted
        for inclusion in an error message ('' when nothing captured)."""
        parts = []
        for rank, path in enumerate(getattr(self, "_log_paths", [])):
            try:
                with open(path, "rb") as f:
                    f.seek(0, os.SEEK_END)
                    f.seek(max(0, f.tell() - limit))
                    text = f.read().decode("utf-8", "replace").strip()
            except OSError:
                continue
            if text:
                parts.append(f"--- rank {rank} ({path}) ---\n{text}")
        if not parts:
            return ""
        return "\nworker logs:\n" + "\n".join(parts)

    def _pick_coordinator_port(self) -> int:
        """jax.distributed coordinator port. Probing only proves a port is
        free on THIS machine, so it is used only when rank 0 runs here;
        multi-host launches take ``coordinator_port`` (default 8476)."""
        if self.coordinator_port is not None:
            return self.coordinator_port
        if self.hosts[0] in ("127.0.0.1", "localhost"):
            return find_free_port()
        return 8476

    def _watch_proc(self, proc: subprocess.Popen, rank: int, gen: int) -> None:
        """A rank exiting nonzero fails the whole gang (the reference's
        mpirun watcher thread, reference: mpi/utils.py:53-66). Scoped to
        one incarnation: a rank reaped by stop() (or outliving into a
        restarted gang) must not poison the next one."""
        code = proc.wait()
        if code not in (0, None) and gen == self._gen and not self._stopping:
            _events.emit(
                "rank/dead", job=self._job_ctx, gang=self.job_name,
                rank=rank, rc=code, gen=gen,
            )
            self._fail(f"rank {rank} exited with code {code}")

    def _fail(self, reason: str) -> None:
        self._failed = reason
        _flight.record("error", "spmd_fail", job=self.job_name,
                       reason=str(reason)[:200])
        _events.emit(
            "gang/failed", job=self._job_ctx, gang=self.job_name,
            reason=str(reason)[:200],
        )
        logger.warning("SPMD job %s failed: %s", self.job_name, reason)
        self._register_barrier.set()  # wake a start() still waiting
        inflight = self._inflight
        if inflight is not None:
            inflight.done.set()

    # ----------------------------------------------------------- rpc handlers

    def _on_register_worker(self, req: dict) -> dict:
        rank = req["rank"]
        self._worker_addrs[rank] = req["address"]
        self._worker_hosts[rank] = req["host"]
        if len(self._worker_addrs) == self.world_size:
            self._register_barrier.set()
        return {"ok_rank": rank}

    def _on_func_result(self, req: dict) -> dict:
        inflight = self._inflight
        if inflight is None or req["func_id"] != inflight.func_id:
            return {"stale": True}
        inflight.post(req["rank"], req.get("value"), req.get("error"))
        return {"stale": False}

    def _on_job_failed(self, req: dict) -> dict:
        self._fail(req.get("reason", "worker-reported failure"))
        return {}

    def _on_ping(self, req: dict) -> dict:
        rank_key = f"rank-{req.get('rank', '?')}"
        delta = req.get("metrics")
        if delta:
            self.telemetry.apply(rank_key, delta)
        # Unconditional: a beat without a health payload means the
        # rank's watchdog sees no stall (recovery clears the flag).
        with self._health_lock:
            self._rank_health[rank_key] = (
                (req.get("health") or {}).get("stalls") or {}
            )
            self._rank_beats[rank_key] = time.monotonic()
        return {"pong": True, "gen": self._gen}

    def metrics_snapshot(self) -> dict:
        """Merged per-rank metrics view (heartbeat-shipped deltas)."""
        return self.telemetry.merged()

    def capture_profile(
        self, seconds: float = 3.0, out_dir: Optional[str] = None
    ) -> dict:
        """Gang-coordinated trace capture: every rank starts a
        ``jax.profiler`` trace at (nearly) the same wall instant, records
        for ``seconds``, and ships the trace directory back as a zip;
        the driver merges them into one clock-aligned Perfetto file
        (``merged_trace.json`` under ``out_dir``).

        The fan-out uses one thread per rank so the start skew is RPC
        latency, not ``world_size × seconds``. Capture runs on each
        rank's RPC handler thread — concurrent with the shipped function
        on the runner thread, so it samples live training."""
        if not self._started:
            raise SPMDJobError("job not started")
        from raydp_tpu.telemetry import device_profiler

        payloads: Dict[int, dict] = {}
        errors: Dict[int, str] = {}

        def _one(rank: int, stub: RpcClient) -> None:
            try:
                payloads[rank] = stub.call(
                    "ProfileRequest", {"seconds": seconds},
                    timeout=seconds + 30.0,
                )
            except Exception as exc:  # partial gang still merges
                errors[rank] = str(exc)

        threads = [
            threading.Thread(target=_one, args=(rank, stub), daemon=True)
            for rank, stub in sorted(self._stubs.items())
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=seconds + 60.0)
        if not payloads:
            raise SPMDJobError(
                f"profile capture failed on every rank: {errors}"
            )
        ordered = [payloads[r] for r in sorted(payloads)]
        merged = device_profiler.merge_rank_traces(ordered, out_dir)
        if errors:
            merged["errors"] = errors
        _flight.record("profile", "merged", job=self.job_name,
                       ranks=len(ordered))
        return merged

    def resource_report(self) -> dict:
        """Per-rank resource accounting from the shipped gauges: host
        RSS, device HBM used/peak, plus XLA compile counters — the
        training-side face of the query-profiling plane. Ranks that have
        not yet shipped gauges appear with empty dicts."""
        view = self.telemetry.merged()
        ranks = {}
        for rid, sections in sorted((view.get("workers") or {}).items()):
            gauges = sections.get("gauges") or {}
            counters = sections.get("counters") or {}
            ranks[rid] = {
                "rss_bytes": gauges.get("mem/rss_bytes", 0),
                "rss_peak_bytes": gauges.get("mem/rss_peak_bytes", 0),
                "hbm_used_bytes": gauges.get("hbm/used_bytes", 0),
                "hbm_peak_bytes": gauges.get("hbm/peak_bytes", 0),
                "compiles": counters.get("compile/count", 0),
                "compile_seconds": counters.get("compile/seconds", 0.0),
                "compile_failures": counters.get("compile/failures", 0),
            }
        agg = view.get("aggregate") or {}
        agg_gauges = agg.get("gauges") or {}
        agg_counters = agg.get("counters") or {}
        return {
            "ranks": ranks,
            "totals": {
                "rss_bytes": agg_gauges.get("mem/rss_bytes", 0),
                "hbm_used_bytes": agg_gauges.get("hbm/used_bytes", 0),
                "hbm_peak_bytes": agg_gauges.get("hbm/peak_bytes", 0),
                "compiles": agg_counters.get("compile/count", 0),
                "compile_seconds": agg_counters.get(
                    "compile/seconds", 0.0
                ),
            },
        }

    def usage_report(self) -> dict:
        """Per-job usage folded from the gang's heartbeat-shipped
        counters (chip-seconds, task-seconds, bytes moved, …) — the SPMD
        face of :func:`raydp_tpu.telemetry.accounting.usage_report`."""
        return _acct.usage_report(self.telemetry.merged())

    # Beats arrive every ~5 s (spmd/worker_main._heartbeat); a rank quiet
    # for half this window is late, for the whole window dead — the
    # vocabulary of Cluster.health_report's heartbeat ageing.
    PING_TIMEOUT_S = 30.0

    def health_report(self) -> dict:
        """Gang health: per-rank stall flags shipped on Pings, plus job
        failure state (parity with ``Cluster.health_report``).

        Ranks are aged against their last Ping: silent for half
        ``PING_TIMEOUT_S`` → late, for all of it → dead. Ranks whose
        index falls outside the current world size (an elastic restart
        shrank the gang) are *departed* — reported as such, never
        lingering as healthy members of a gang they left."""
        now = time.monotonic()
        with self._health_lock:  # Pings insert keys concurrently
            snapshot = dict(self._rank_health)
            beats = dict(self._rank_beats)
        ranks: Dict[str, dict] = {}
        departed: List[str] = []
        for rid in sorted(snapshot):
            try:
                idx = int(rid.rsplit("-", 1)[1])
            except (IndexError, ValueError):
                idx = -1
            if 0 <= idx < self.world_size:
                ranks[rid] = dict(snapshot[rid])
            else:
                departed.append(rid)
        stalled = sorted(rid for rid, stalls in ranks.items() if stalls)
        # A rank that never beat yet (gang just launched) ages from now.
        dead = sorted(
            rid for rid in ranks
            if now - beats.get(rid, now) > self.PING_TIMEOUT_S
        )
        late = sorted(
            rid for rid in ranks
            if rid not in dead
            and now - beats.get(rid, now) > self.PING_TIMEOUT_S / 2
        )
        return {
            "healthy": not (stalled or dead or late) and not self._failed,
            "ranks": ranks,
            "stalled_ranks": stalled,
            "dead_ranks": dead,
            "late_ranks": late,
            "departed_ranks": departed,
            "failed": self._failed,
            "world_size": self.world_size,
        }

    # -------------------------------------------------------------------- run

    def run(
        self,
        fn: Callable[..., Any],
        timeout: Optional[float] = None,
        per_rank_args: Optional[List[tuple]] = None,
    ) -> List[Any]:
        """Ship ``fn(worker_context, *args)`` to every rank; return
        rank-ordered results (reference: MPIJob.run, mpi/mpi_job.py:321-335).

        ``per_rank_args`` scatters: rank ``r`` receives only
        ``per_rank_args[r]`` — large per-rank payloads (data shards) are
        serialized once per rank, not world× to every rank."""
        if not self._started:
            raise SPMDJobError("job not started")
        if self._failed:
            raise SPMDJobError(f"job {self.job_name} failed: {self._failed}")
        if per_rank_args is not None and len(per_rank_args) != self.world_size:
            raise ValueError(
                f"per_rank_args has {len(per_rank_args)} entries for "
                f"world_size {self.world_size}"
            )
        # The lock covers only the inflight-slot claim: holding it across
        # the send loop + gang wait (minutes) would block every other
        # _lock user for the whole dispatch. A second concurrent run()
        # now fails fast instead of silently queueing behind the lock.
        with self._lock:
            if self._inflight is not None:
                raise SPMDJobError(
                    f"job {self.job_name} already has function "
                    f"{self._inflight.func_id} in flight; SPMDJob.run() "
                    f"is one-at-a-time"
                )
            self._func_id += 1
            func_id = self._func_id
            results = _FuncResults(func_id, self.world_size)
            self._inflight = results
        _flight.record("dispatch", "start", job=self.job_name,
                       func_id=func_id)
        staged_ids: List[str] = []
        try:
            # A gang that never reports back (rank wedged in a
            # collective) is attributed as "spmd/dispatch" on the driver
            # — pair it with health_report()'s per-rank flags to see
            # WHICH rank. The dispatch legitimately runs until its own
            # deadline, so the stall threshold is raised to match it.
            with _watchdog.inflight(
                "spmd/dispatch", job=self.job_name, func_id=func_id,
                stall_after_s=timeout or max(self.timeout, 60.0),
            ), span("spmd/dispatch", job=self.job_name,
                    func_id=func_id, world_size=self.world_size):
                fn_blob = cloudpickle.dumps(fn)
                inline_cap = _env_mb(
                    ENV_INLINE_CAP, _DEFAULT_INLINE_CAP_MB
                )
                hard_cap = _env_mb(
                    ENV_PAYLOAD_HARD_CAP, _DEFAULT_HARD_CAP_MB
                )
                for rank, stub in self._stubs.items():
                    payload: Dict[str, Any] = {"func_id": func_id}
                    blobs = {"fn": fn_blob}
                    nbytes = len(fn_blob)
                    if per_rank_args is not None:
                        blob = cloudpickle.dumps(tuple(per_rank_args[rank]))
                        blobs["args"] = blob
                        nbytes += len(blob)
                    if nbytes > hard_cap:
                        # Fail fast with the structured error a
                        # supervisor can act on — not a wedged channel
                        # followed by a timeout (retrying an
                        # over-the-ceiling payload is deterministic
                        # waste, so retryable=False).
                        raise CompileError(
                            f"dispatch payload for rank {rank} is "
                            f"{nbytes} bytes, over the "
                            f"{ENV_PAYLOAD_HARD_CAP} hard cap of "
                            f"{hard_cap} bytes",
                            label=f"{self.job_name}/func{func_id}",
                            duration_s=0.0,
                            payload_bytes=nbytes,
                            retryable=False,
                        )
                    if nbytes > inline_cap:
                        # Oversize payload: stage in the driver-local
                        # store; the envelope carries only refs and the
                        # rank pulls the bytes back in bounded chunks.
                        for key, blob in blobs.items():
                            ref = self._blob_store.put(blob)
                            staged_ids.append(ref.object_id)
                            payload[f"{key}_ref"] = ref.object_id
                            payload[f"{key}_size"] = len(blob)
                        _metrics.counter_add("spmd/oversize_dispatches")
                        _metrics.counter_add("spmd/staged_bytes", nbytes)
                        send_bytes = 4096
                    else:
                        payload.update(blobs)
                        send_bytes = nbytes
                    # Deadline sized to the bytes actually riding THIS
                    # envelope (refs make it constant) at a worst-case
                    # ~10 MB/s over DCN, on top of the control default —
                    # NOT the whole-job timeout, which would let the
                    # serial send loop hide failures for world×timeout.
                    try:
                        stub.call(
                            "RunFunction", payload,
                            timeout=10.0 + send_bytes / 10e6,
                        )
                    except Exception as exc:
                        if nbytes <= inline_cap:
                            raise
                        # The guard still tripped on an oversize
                        # dispatch: surface it as the structured
                        # compile failure (payload size + server-side
                        # failure class) instead of a generic RPC error.
                        code = getattr(exc, "code", None)
                        raise CompileError(
                            f"oversize dispatch to rank {rank} failed "
                            f"after staging ({nbytes} bytes): {exc}",
                            label=f"{self.job_name}/func{func_id}",
                            duration_s=0.0,
                            payload_bytes=nbytes,
                            server_exception=(
                                str(code()) if callable(code)
                                else type(exc).__name__
                            ),
                            retryable=True,
                        ) from exc
                if not results.done.wait(timeout or max(self.timeout, 60.0)):
                    raise SPMDJobError(
                        f"function {func_id} timed out on job "
                        f"{self.job_name}"
                    )
                if self._failed:
                    raise SPMDJobError(
                        f"job {self.job_name} failed mid-function: "
                        f"{self._failed}"
                    )
                errors = [
                    f"rank {i}: {e}" for i, e in enumerate(results.errors) if e
                ]
                if errors:
                    raise SPMDJobError(
                        f"function failed on {len(errors)} rank(s):\n"
                        + "\n".join(errors)
                    )
                return results.results
        finally:
            self._inflight = None
            # Staged blobs are per-dispatch; every rank has either
            # fetched them or failed by now.
            for object_id in staged_ids:
                try:
                    self._blob_store.delete(object_id)
                except Exception:
                    pass

    def request_preemption(self) -> None:
        """Deliver a preemption notice to every live rank (driver side)
        — the scheduler's victim-teardown hook.

        Primary delivery is the worker RPC plane (``Preempt``): each
        rank's handler sets the in-process drain flag, so the rank
        finishes its in-flight step, writes an emergency checkpoint,
        and raises :class:`~raydp_tpu.fault.PreemptionError` — exactly
        the path an injected slice preemption takes. RPC rather than
        SIGTERM because ``jax.distributed`` installs its own SIGTERM
        handler (TSL's preemption notifier) over the Python drain
        handler once a rank initializes, eating the signal. SIGTERM is
        kept as the fallback for ranks not yet registered. Ranks
        already gone are skipped; the whole call is advisory and never
        raises."""
        _events.emit(
            "preempt/request", job=self._job_ctx, gang=self.job_name,
            source="scheduler", gen=self._gen,
        )
        _flight.record("supervisor", "preempt_notice", job=self.job_name,
                       ranks=len(self._procs))
        notified = set()
        for rank, stub in list(self._stubs.items()):
            try:
                if stub.try_call("Preempt", {}, timeout=5.0) is not None:
                    notified.add(rank)
            except Exception:
                pass
        import signal as _signal

        for rank, proc in enumerate(self._procs):
            if rank in notified or proc.poll() is not None:
                continue
            try:
                proc.send_signal(_signal.SIGTERM)
            except OSError:
                pass

    def get_rank_addresses(self) -> List[str]:
        """Host of each rank, rank-ordered (reference: mpi_job.py:337-339)."""
        return [self._worker_hosts[r] for r in range(self.world_size)]

    # ------------------------------------------------------------------- stop

    def stop(self) -> None:
        """Stop workers, reap processes; the job can be start()ed again
        (reference: MPIJob.stop/_reset, mpi/mpi_job.py:341-398)."""
        self._stopping = True
        if self._started:
            _events.emit(
                "gang/teardown", job=self._job_ctx, gang=self.job_name,
                world_size=self.world_size, gen=self._gen,
            )
        for stub in self._stubs.values():
            try:
                stub.call("Stop", {}, timeout=2.0)
            except Exception:
                pass
            stub.close()
        deadline = time.time() + 5.0
        for proc in self._procs:
            try:
                proc.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        if self._server is not None:
            self._server.stop()
        self._server = None
        if self._blob_store is not None:
            try:
                self._blob_store.destroy()
            except Exception:
                pass
            self._blob_store = None
        self._procs = []
        self._stubs = {}
        self._worker_addrs = {}
        self._worker_hosts = {}
        self._inflight = None
        self._started = False
        if self._owns_trace_ctx and self._trace_ctx is not None:
            from raydp_tpu.telemetry import propagation as trace_prop

            if trace_prop.process_context() == self._trace_ctx:
                trace_prop.set_process_context(None)
        self._trace_ctx = None
        self._owns_trace_ctx = False
        if self._sched_lease is not None:
            try:
                self._sched_lease.release()
            except Exception:
                pass
            self._sched_lease = None
        if self._owns_job_ctx and self._job_ctx is not None:
            if _acct.process_job() == self._job_ctx:
                _acct.set_process_job(None)
        self._job_ctx = None
        self._owns_job_ctx = False

    def __enter__(self) -> "SPMDJob":
        if not self._started:
            self.start()
        return self

    def __exit__(self, exc_type, exc_val, exc_tb) -> None:
        self.stop()

    def __del__(self):
        if self._started:
            try:
                self.stop()
            except Exception:
                pass


def create_spmd_job(
    job_name: str,
    world_size: int,
    num_procs_per_node: int = 1,
    script_prepare_fn: Optional[Callable[[SPMDJobContext], List[str]]] = None,
    env: Optional[Dict[str, str]] = None,
    timeout: float = 30.0,
    hosts: Optional[List[str]] = None,
    coordinator_port: Optional[int] = None,
    placement_strategy: Optional[str] = None,
    placement_group=None,
) -> SPMDJob:
    """Create (but do not start) an SPMD job — the reference's
    ``create_mpi_job`` entry point (reference: mpi/__init__.py:36-91).

    The MPI-flavor dispatch (OpenMPI/IntelMPI/MPICH) collapses away: there
    is one launcher, and ``script_prepare_fn`` covers launcher
    customization. ``placement_strategy``/``placement_group`` reserve one
    bundle per gang host over the cluster's nodes and derive ``hosts``
    from the assignment (the reference reserves a STRICT_SPREAD group and
    discovers node IPs with peer actors — mpi/mpi_job.py:193-223).
    """
    pg = placement_group
    if hosts is None and (placement_strategy is not None or pg is not None):
        from raydp_tpu.cluster import placement as pl
        from raydp_tpu.context import current_session

        session = current_session()
        nodes = (
            session.cluster.master.nodes
            if session is not None and hasattr(session.cluster, "master")
            and hasattr(session.cluster.master, "nodes")
            else pl.detect_nodes()
        )
        n_hosts = -(-world_size // num_procs_per_node)
        if pg is None:
            bundles = [{"cpu": float(num_procs_per_node)}] * n_hosts
            pg = pl.place(bundles, placement_strategy, nodes)
        addr_of = {n.node_id: n.address for n in nodes}
        hosts = [
            addr_of.get(b.node_id, "127.0.0.1") for b in pg.bundles[:n_hosts]
        ]
    job = SPMDJob(
        job_name=job_name,
        world_size=world_size,
        num_procs_per_node=num_procs_per_node,
        script_prepare_fn=script_prepare_fn,
        env=env,
        timeout=timeout,
        hosts=hosts,
        coordinator_port=coordinator_port,
    )
    job.placement_group = pg
    return job
