"""Profiling & metrics: step timers, throughput counters, XLA traces.

New subsystem relative to the reference (SURVEY §5.1: tracing/profiling
is *absent* there — only an ad-hoc ``_timed`` contextmanager in the DLRM
notebook). Here it is first-class because the north-star metrics
(samples/sec/chip, ingest GB/s) need measurement built into the
framework:

* :class:`MetricsRegistry` — process-wide named counters + timers;
  ingest and training both report here; ``snapshot()`` for dashboards.
* :class:`StepTimer` — rolling per-step wall times with percentiles
  (compile steps show up as outliers; ``p50`` is the steady state).
* :func:`trace` — ``jax.profiler`` trace context writing a TensorBoard-
  loadable profile (XLA ops, HBM, ICI collectives on real TPUs).
* :func:`annotate` — named trace region so host-side stages (gather,
  device_put) line up with device timelines.
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional

__all__ = [
    "MetricsRegistry",
    "StepTimer",
    "ThroughputMeter",
    "Histogram",
    "quantile_from_hist_summary",
    "metrics",
    "trace",
    "annotate",
    "install_compile_listener",
    "compile_records",
    "programs_built",
    "mark_ready",
    "ready_stamp",
    "enrich_compile_error",
    "local_devices_if_initialized",
    "sample_resource_gauges",
]


class StepTimer:
    """Rolling window of step durations. A per-timer lock covers the
    deque: observe() runs per step (not per row) so the cost is noise,
    and snapshot() from a monitoring thread must not race a mutating
    append (``sorted(deque)`` raises if mutated mid-iteration)."""

    def __init__(self, window: int = 1024):
        self.window = window
        self._times: "deque[float]" = deque(maxlen=window)
        self._total = 0.0
        self._count = 0
        self._mu = threading.Lock()

    def observe(self, seconds: float) -> None:
        with self._mu:
            self._times.append(seconds)
            self._total += seconds
            self._count += 1

    @contextlib.contextmanager
    def time(self) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.observe(time.perf_counter() - t0)

    def percentile(self, q: float) -> float:
        with self._mu:
            xs = sorted(self._times)
        if not xs:
            return 0.0
        i = min(len(xs) - 1, int(q / 100.0 * len(xs)))
        return xs[i]

    def summary(self) -> Dict[str, float]:
        with self._mu:
            xs = sorted(self._times)
            total, count = self._total, self._count

        def pct(q: float) -> float:
            if not xs:
                return 0.0
            return xs[min(len(xs) - 1, int(q / 100.0 * len(xs)))]

        return {
            "count": float(count),
            "total_s": total,
            "mean_s": total / max(1, count),
            "p50_s": pct(50),
            "p90_s": pct(90),
            "p99_s": pct(99),
        }


class ThroughputMeter:
    """Counts units (rows, bytes) against wall time since first record.

    Locked like StepTimer: ``add()`` runs on training/ingest threads
    while ``summary()`` runs on the heartbeat thread shipping snapshots
    — an unlocked ``_units += units`` read-modify-write would drop
    updates under that concurrency, and ``rate()`` could pair a fresh
    ``_units`` with a stale ``_last``. One uncontended lock per CHUNK
    (callers meter per chunk/batch, not per row) is noise."""

    def __init__(self):
        self._units = 0.0
        self._start: Optional[float] = None
        self._last: Optional[float] = None
        self._mu = threading.Lock()

    def add(self, units: float) -> None:
        now = time.perf_counter()
        with self._mu:
            if self._start is None:
                self._start = now
            self._last = now
            self._units += units

    @property
    def total(self) -> float:
        with self._mu:
            return self._units

    def rate(self) -> float:
        with self._mu:
            return self._rate_locked()

    def _rate_locked(self) -> float:
        if self._start is None or self._last is None or self._last <= self._start:
            return 0.0
        return self._units / (self._last - self._start)

    def summary(self) -> Dict[str, float]:
        with self._mu:
            return {"total": self._units, "per_sec": self._rate_locked()}


class Histogram:
    """Fixed-bucket distribution, Prometheus-histogram shaped.

    Unlike :class:`StepTimer` (rolling window, percentiles over recent
    observations) a histogram is cumulative over the process lifetime,
    so cross-worker merging is exact (bucket counts sum) and scrape-side
    rate()/histogram_quantile() work. Buckets are upper bounds; counts
    are stored per-bucket and emitted cumulatively by :meth:`summary`.
    """

    # Step times span ~100µs (tiny CPU models) to minutes (first-step
    # compile); log-spaced bounds keep quantile error ≤ one bucket.
    DEFAULT_BUCKETS = (
        0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
        0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
    )

    def __init__(self, buckets: Optional[List[float]] = None):
        bounds = tuple(sorted(buckets)) if buckets else self.DEFAULT_BUCKETS
        self.bounds = bounds
        self._counts = [0] * (len(bounds) + 1)  # +1: the +Inf bucket
        self._sum = 0.0
        self._count = 0
        self._mu = threading.Lock()

    def observe(self, value: float) -> None:
        import bisect

        idx = bisect.bisect_left(self.bounds, value)
        with self._mu:
            self._counts[idx] += 1
            self._sum += value
            self._count += 1

    def summary(self) -> Dict[str, object]:
        """``{"sum", "count", "buckets": {"<le>": cumulative, ...,
        "+Inf": count}}`` — cumulative counts so the section merges
        across workers by plain stat-wise summation."""
        with self._mu:
            counts = list(self._counts)
            total, n = self._sum, self._count
        buckets: Dict[str, float] = {}
        running = 0
        for bound, c in zip(self.bounds, counts):
            running += c
            buckets[repr(bound)] = float(running)
        buckets["+Inf"] = float(n)
        return {"sum": total, "count": float(n), "buckets": buckets}

    def quantile(self, q: float) -> Optional[float]:
        """Exact-to-one-bucket quantile (linear interpolation inside
        the containing bucket); ``None`` when nothing was observed, so
        cold-start readers see null instead of a fabricated 0."""
        return quantile_from_hist_summary(self.summary(), q)


def quantile_from_hist_summary(
    summary: Dict[str, object], q: float
) -> Optional[float]:
    """Quantile from a :meth:`Histogram.summary` dict (also works on a
    stat-wise *merged* summary, which is the point: cross-replica p99
    is computed after bucket counts sum, not max-of-summaries).

    Returns ``None`` on zero observations. Values landing in the +Inf
    bucket report the largest finite bound (tail is censored there).
    """
    try:
        count = float(summary.get("count", 0.0))  # type: ignore[union-attr]
        buckets = summary.get("buckets") or {}
    except AttributeError:
        return None
    if count <= 0 or not buckets:
        return None
    q = min(max(q, 0.0), 1.0)
    rank = q * count
    finite = sorted(
        (float(le), float(c))
        for le, c in buckets.items()
        if le != "+Inf"
    )
    prev_bound, prev_cum = 0.0, 0.0
    for bound, cum in finite:
        if cum >= rank:
            span = cum - prev_cum
            if span <= 0:
                return bound
            frac = (rank - prev_cum) / span
            return prev_bound + frac * (bound - prev_bound)
        prev_bound, prev_cum = bound, cum
    # rank falls in the +Inf bucket: report the largest finite bound.
    return finite[-1][0] if finite else None


@dataclass
class MetricsRegistry:
    """Named counters/timers/meters; one process-wide instance at
    :data:`metrics`."""

    _lock: threading.Lock = field(default_factory=threading.Lock)
    _counters: Dict[str, float] = field(default_factory=dict)
    _timers: Dict[str, StepTimer] = field(default_factory=dict)
    _meters: Dict[str, ThroughputMeter] = field(default_factory=dict)
    _gauges: Dict[str, float] = field(default_factory=dict)
    _hists: Dict[str, Histogram] = field(default_factory=dict)

    def counter_add(self, name: str, value: float = 1.0) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0.0) + value

    def gauge_set(self, name: str, value: float) -> None:
        """Point-in-time value (RSS, HBM in use, store occupancy)."""
        with self._lock:
            self._gauges[name] = float(value)

    def gauge_value(self, name: str) -> Optional[float]:
        """Last value set on a gauge (None when never set) — read path
        for integrators (HBM-byte-seconds accumulates gauge × dt)."""
        with self._lock:
            return self._gauges.get(name)

    def gauge_max(self, name: str, value: float) -> None:
        """Watermark gauge: keeps the max ever observed."""
        with self._lock:
            prev = self._gauges.get(name)
            if prev is None or value > prev:
                self._gauges[name] = float(value)

    def timer(self, name: str) -> StepTimer:
        with self._lock:
            if name not in self._timers:
                self._timers[name] = StepTimer()
            return self._timers[name]

    def meter(self, name: str) -> ThroughputMeter:
        with self._lock:
            if name not in self._meters:
                self._meters[name] = ThroughputMeter()
            return self._meters[name]

    def histogram(
        self, name: str, buckets: Optional[List[float]] = None
    ) -> Histogram:
        with self._lock:
            if name not in self._hists:
                self._hists[name] = Histogram(buckets)
            return self._hists[name]

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            out: Dict[str, Dict[str, float]] = {
                "counters": dict(self._counters)
            }
            # Omitted when empty so pre-gauge snapshot shapes (and the
            # exposition goldens built on them) are unchanged.
            if self._gauges:
                out["gauges"] = dict(self._gauges)
            for name, t in self._timers.items():
                out[f"timer/{name}"] = t.summary()
            for name, m in self._meters.items():
                out[f"meter/{name}"] = m.summary()
            for name, h in self._hists.items():
                out[f"hist/{name}"] = h.summary()
            return out

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._timers.clear()
            self._meters.clear()
            self._gauges.clear()
            self._hists.clear()


metrics = MetricsRegistry()


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """Capture a ``jax.profiler`` trace (TensorBoard ``profile`` plugin
    format: XLA ops, fusion names, HBM/ICI activity on TPU)."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Named region on the host timeline (shows up alongside device ops
    in the captured trace)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


# -- XLA compile accounting ------------------------------------------------
#
# jax reports what it does to build a program through ``jax.monitoring``:
# a scalar when Python's trace of a function, its lowering to MLIR or the
# backend's compile STARTS, a duration when it ends, and plain events from
# the persistent cache. The listener below keeps one record per program
# the process built and counters by kind; ``train/estimator.py`` marks
# where start-up ended. Together with the recorder's retained spans
# (``telemetry/spans.py``) that is the process's start-up record.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

#: Program records kept; later ones are counted in
#: ``compile/records_dropped``.
MAX_COMPILE_RECORDS = 512

_PHASES = {TRACE_EVENT: "trace", LOWER_EVENT: "lower", BACKEND_EVENT: "backend"}
_COMPILE_LISTENER_INSTALLED = False


class _CompileLog:
    """What the listener keeps. Per thread: the phases jax has entered and
    not left (a phase inside another adds its seconds to its own kind and
    takes them off the outer one's, so each second is counted once), the
    trace and lowering seconds no program record has taken yet, and what
    the cache said since the backend phase began."""

    def __init__(self):
        self._mu = threading.Lock()
        self._tls = threading.local()
        self.records: List[Dict[str, object]] = []
        self.built = 0
        self.ready_stamp: Optional[float] = None

    def clear(self) -> None:
        """Forget the records and the ready stamp (tests)."""
        with self._mu:
            self.records = []
            self.ready_stamp = None

    def _thread(self):
        tls = self._tls
        if not hasattr(tls, "open"):
            tls.open = []  # [event, seconds of the phases inside it]
            tls.trace_s = tls.lower_s = tls.retrieval_s = 0.0
            tls.cache = "uncached"
        return tls

    def entered(self, event: str) -> None:
        tls = self._thread()
        tls.open.append([event, 0.0])
        if event == BACKEND_EVENT:
            tls.cache, tls.retrieval_s = "uncached", 0.0

    def left(self, event: str, duration: float, fun_name: str) -> None:
        tls = self._thread()
        # No entry for an event fed without its start (tests do).
        inside = 0.0
        if tls.open and tls.open[-1][0] == event:
            inside = tls.open.pop()[1]
        if tls.open:
            tls.open[-1][1] += duration
        own = max(0.0, duration - inside)
        kind = _PHASES[event]
        if kind == "trace":
            tls.trace_s += own
        elif kind == "lower":
            tls.lower_s += own
        else:
            kind = "cache_load" if tls.cache == "hit" else "backend"
            metrics.counter_add("compile/count")
            self._record(tls, fun_name, own)
        metrics.counter_add(f"compile/{kind}_seconds", own)
        metrics.counter_add("compile/seconds", own)

    def _record(self, tls, fun_name: str, backend_s: float) -> None:
        from raydp_tpu.telemetry.spans import recorder

        # Who paid: the innermost span open on the building thread.
        owner = recorder.current_span()
        record = {
            "fun_name": fun_name, "trace_s": tls.trace_s,
            "lower_s": tls.lower_s, "backend_s": backend_s,
            "cache": tls.cache, "retrieval_s": tls.retrieval_s,
            "t_end": time.perf_counter(),
            "owner": owner.name if owner is not None else None,
        }
        tls.trace_s = tls.lower_s = tls.retrieval_s = 0.0
        tls.cache = "uncached"
        with self._mu:
            self.built += 1
            kept = len(self.records) < MAX_COMPILE_RECORDS
            if kept:
                self.records.append(record)
        if not kept:
            metrics.counter_add("compile/records_dropped")

    def cache_said(self, event: str) -> None:
        hit = event == CACHE_HIT_EVENT
        self._thread().cache = "hit" if hit else "miss"
        metrics.counter_add(
            "compile/cache_hits" if hit else "compile/cache_misses"
        )

    def retrieved(self, seconds: float) -> None:
        self._thread().retrieval_s += seconds


_compile_log = _CompileLog()


def install_compile_listener() -> bool:
    """Hear what jax does to build programs (``jax.monitoring``) on every
    process that calls this — driver, SPMD ranks — without wrapping a
    ``jax.jit`` site. Idempotent; False when the running jax has no
    monitoring hooks.

    Counters, in seconds of the phase itself (a trace inside a trace or a
    compile inside a trace is counted once, under its own kind):
    ``compile/trace_seconds`` (Python tracing the function),
    ``compile/lower_seconds`` (jaxpr to MLIR), ``compile/backend_seconds``
    (XLA's and Mosaic's compile of a program the persistent cache did not
    hold), ``compile/cache_load_seconds`` (the same event where the cache
    held it: the read and the load), and ``compile/seconds``, their sum.
    ``compile/count`` counts backend events, ``compile/cache_hits`` and
    ``compile/cache_misses`` the cache's own. What the cache SAVED
    (``compile_time_saved_sec``) is not time spent and is not heard.

    One record per program: see :func:`compile_records`."""
    global _COMPILE_LISTENER_INSTALLED
    if _COMPILE_LISTENER_INSTALLED:
        return True
    try:
        from jax import monitoring as _mon

        def _on_scalar(event: str, value: float, **kw) -> None:
            if event in _PHASES:
                _compile_log.entered(event)

        def _on_duration(event: str, duration: float, **kw) -> None:
            if event in _PHASES:
                _compile_log.left(
                    event, float(duration), str(kw.get("fun_name", "?"))
                )
            elif event == CACHE_RETRIEVAL_EVENT:
                _compile_log.retrieved(float(duration))

        def _on_event(event: str, **kw) -> None:
            if event in (CACHE_HIT_EVENT, CACHE_MISS_EVENT):
                _compile_log.cache_said(event)

        _mon.register_scalar_listener(_on_scalar)
        _mon.register_event_duration_secs_listener(_on_duration)
        _mon.register_event_listener(_on_event)
    except Exception:
        return False
    _COMPILE_LISTENER_INSTALLED = True
    return True


def compile_records() -> List[Dict[str, object]]:
    """The programs this process built since the listener was installed,
    oldest first, at most ``MAX_COMPILE_RECORDS``. Each: ``fun_name``
    (jax's name of the program), ``trace_s`` and ``lower_s`` (what the
    thread traced and lowered since its previous program: an abstract
    trace, ``jax.eval_shape``, is billed to the next program built),
    ``backend_s`` (the backend event: a compile, or on a cache hit the read
    and load), ``cache`` (``hit``, ``miss``: compiled and written, or
    ``uncached``: the cache is off or did not take the program),
    ``retrieval_s`` (the cache's read, part of ``backend_s``), ``t_end``
    (``perf_counter``) and ``owner``, the innermost span open on the
    building thread when the backend event arrived, or None."""
    with _compile_log._mu:
        return [dict(r) for r in _compile_log.records]


def programs_built() -> int:
    """How many programs the listener has heard, dropped records too."""
    with _compile_log._mu:
        return _compile_log.built


def mark_ready(stamp: float) -> None:
    """An epoch that paid for a program ended at ``stamp``
    (``perf_counter``): the gauge ``train/ready_seconds`` is that moment
    counted from the first import of ``raydp_tpu``
    (``raydp_tpu.IMPORTED_AT``). Its last value is where start-up ended."""
    from raydp_tpu import IMPORTED_AT

    with _compile_log._mu:
        _compile_log.ready_stamp = stamp
    metrics.gauge_set("train/ready_seconds", stamp - IMPORTED_AT)


def ready_stamp() -> Optional[float]:
    """``perf_counter`` of the last :func:`mark_ready`, None before it."""
    with _compile_log._mu:
        return _compile_log.ready_stamp


class CompileError(RuntimeError):
    """Structured compile or dispatch failure.

    Carries what a supervisor needs to decide what to do, instead of a
    bare string: ``label`` (which jitted step or shipped function),
    ``duration_s`` (how long the compile ran), ``payload_bytes`` (size
    of the argument payload), ``server_exception`` (the failure class
    the receiving side reported, for a dispatch that died in
    transport), ``xla_detail`` (the compiler's own message), and
    ``retryable`` — a compiler diagnostic is deterministic and never
    retryable; a staged dispatch that failed in transport can be.
    """

    def __init__(
        self,
        message: str,
        *,
        label: str,
        duration_s: float,
        server_exception: Optional[str] = None,
        payload_bytes: Optional[int] = None,
        xla_detail: str = "",
        retryable: bool = False,
    ):
        super().__init__(message)
        self.label = label
        self.duration_s = duration_s
        self.server_exception = server_exception
        self.payload_bytes = payload_bytes
        self.xla_detail = xla_detail
        self.retryable = retryable


def enrich_compile_error(
    exc: BaseException,
    duration_s: float,
    label: str,
    payload_bytes: Optional[int] = None,
) -> "CompileError":
    """Wrap a first-dispatch failure in a :class:`CompileError` that
    says which step failed, how long the compile ran and what the
    compiler said. Chain with ``raise ... from exc`` at the call site
    to keep the original traceback."""
    detail = str(exc).strip()
    err = CompileError(
        f"XLA compilation failed in {label!r} after {duration_s:.1f}s"
        f" ({type(exc).__name__}).\n"
        f"Compiler said: {detail or '(empty message)'}",
        label=label,
        duration_s=duration_s,
        payload_bytes=payload_bytes,
        xla_detail=detail,
    )
    metrics.counter_add("compile/failures")
    # Timeline correlation: the failure lands in /debug/events next to
    # whatever gang churn it caused (lazy import — telemetry.events
    # imports this module's registry).
    try:
        from raydp_tpu.telemetry import events as _tl_events

        _tl_events.emit(
            "compile/failed",
            label=label,
            duration_s=round(duration_s, 3),
            **({"payload_bytes": payload_bytes} if payload_bytes else {}),
        )
    except Exception:
        pass
    return err


def local_devices_if_initialized() -> list:
    """This process's devices, or ``[]`` when it holds no backend.

    ``jax.local_devices()`` CREATES a backend where none exists. In a
    ``fit_spmd`` or serving driver that takes the chip away from the
    rank or replica that needs it; in a rank it breaks the later
    ``jax.distributed.initialize``. Telemetry that only wants to look
    at devices therefore asks whether a backend exists first (jax 0.9
    has no public spelling of that question)."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return []
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return []
    return jax.local_devices()


def sample_resource_gauges(registry: Optional[MetricsRegistry] = None) -> None:
    """Refresh the resource-accounting gauges on ``registry`` (default:
    the process registry): host RSS current/peak, per-process device HBM
    in-use/peak summed over local devices, and shm object-store
    occupancy when a store is live in this process. Called from worker
    heartbeats / SPMD pings / driver snapshots — cheap enough for a 2s
    cadence (one procfs read + dict lookups)."""
    reg = registry if registry is not None else metrics
    from raydp_tpu.utils.memory import host_rss_bytes

    rss, peak = host_rss_bytes()
    if rss:
        reg.gauge_set("mem/rss_bytes", rss)
        reg.gauge_max("mem/rss_peak_bytes", peak)
    used = hwm = 0
    have = False
    for dev in local_devices_if_initialized():
        stats = dev.memory_stats()
        if not stats:
            continue
        have = True
        used += int(stats.get("bytes_in_use", 0) or 0)
        hwm += int(
            stats.get("peak_bytes_in_use", 0)
            or stats.get("bytes_in_use", 0)
            or 0
        )
    if have:
        reg.gauge_set("hbm/used_bytes", used)
        reg.gauge_max("hbm/peak_bytes", hwm)
    try:
        from raydp_tpu.store.object_store import get_current_store

        store = get_current_store()
        if store is not None:
            occ = store.occupancy_bytes()
            reg.gauge_set("store/occupancy_bytes", occ)
            reg.gauge_max("store/occupancy_peak_bytes", occ)
    except Exception:
        pass
