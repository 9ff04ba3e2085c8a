"""Device performance plane: where does a training step's time go?

Four cooperating pieces (ISSUE 7; the accelerator-side half of the
observability stack — spans/health/query profiling cover the host):

* **Step-phase accounting** — :class:`StepPhaseAccumulator` splits each
  step's wall time into ``input_wait`` (blocked on the next host
  batch), ``dispatch`` (host-side shard/device_put + jit enqueue),
  ``compute`` (device time observed through the donated-buffer block:
  with ``donate_argnums`` the next dispatch cannot return before the
  previous step's state buffers free, so steady-state call time IS
  device step time) and ``collective`` (estimated from HLO cost
  analysis; zero on single-device backends). Fractions sum to ~1.0 by
  construction — the denominator is the measured loop wall.
* **MFU / roofline** — :func:`note_compiled` runs
  ``jitted.lower(...).cost_analysis()`` once at ``_guard_compile``
  time (one extra trace, never a second XLA compile) and registers
  analytical FLOPs/bytes per compiled function; combined with measured
  step time this yields a live ``mfu`` gauge (→ ``raydp_mfu``) and a
  compute-vs-memory-vs-input-bound classification
  (:func:`classify_fractions`).
* **Gang-coordinated trace capture** — :func:`capture_trace_archive`
  runs the single-process ``utils/profiling.trace`` (jax.profiler) for
  N seconds and zips the result; drivers fan a ``ProfileRequest`` RPC
  to every rank/worker simultaneously and :func:`merge_rank_traces`
  aligns the per-rank Chrome traces + span shards into ONE
  Perfetto-loadable JSON (same clock-offset idiom as chrome_trace.py).
* **Anomaly sentinels** — :class:`AnomalySentinel` checks loss /
  global grad-norm finiteness on a sampled cadence (a per-step
  ``float()`` would sync host↔device and serialize the infeed
  pipeline) and flags step-time regressions against a rolling median;
  both emit flight-recorder events and ``anomalies/*`` counters
  (→ ``raydp_anomalies_total``).

Kill switch: ``RAYDP_TPU_DEVICE_PLANE=0`` disables phase accounting,
cost analysis and sentinels (capture stays available — it is explicit,
not ambient). Overhead with the plane ON is measured in bench.py
(``device_plane_overhead``, budget <5%).
"""
from __future__ import annotations

import glob
import gzip
import io
import json
import os
import tempfile
import threading
import time
import zipfile
from collections import deque
from typing import Any, Dict, List, Optional

from raydp_tpu.utils.profiling import local_devices_if_initialized, metrics

__all__ = [
    "enabled",
    "device_peaks",
    "note_compiled",
    "get_cost",
    "StepPhaseAccumulator",
    "classify_fractions",
    "AnomalySentinel",
    "capture_local_trace",
    "capture_trace_archive",
    "merge_rank_traces",
    "unpack_trace_archive",
]

_ENABLE_ENV = "RAYDP_TPU_DEVICE_PLANE"
_SENTINEL_EVERY_ENV = "RAYDP_TPU_SENTINEL_EVERY"
_SENTINEL_COOLDOWN_ENV = "RAYDP_TPU_SENTINEL_COOLDOWN_S"
_REGRESSION_FACTOR_ENV = "RAYDP_TPU_STEP_REGRESSION_FACTOR"
_REGRESSION_MIN_ENV = "RAYDP_TPU_STEP_REGRESSION_MIN_STEPS"


def enabled() -> bool:
    return os.environ.get(_ENABLE_ENV, "1") not in ("0", "false", "no")


# -- device peaks (roofline ceilings) ---------------------------------------

# device_kind substring → (peak dense bf16 FLOP/s, HBM bytes/s) per chip.
# Public numbers; good to the precision a live MFU gauge needs. A CPU has
# no entry and reports no MFU; an accelerator that matches no entry is an
# error, so a new chip cannot run with a silently missing roofline.
_DEVICE_PEAKS = (
    ("v6e", 918e12, 1640e9),
    ("v5p", 459e12, 2765e9),
    ("v5e", 197e12, 819e9),
    ("v5 lite", 197e12, 819e9),
    ("v4", 275e12, 1228e9),
    ("v3", 123e12, 900e9),
    ("v2", 45e12, 700e9),
)


def device_peaks() -> Dict[str, Optional[float]]:
    """``{"flops_per_sec", "mem_bw", "devices", "kind"}`` for the local
    devices — peak numbers are PER HOST (per-chip peak × local device
    count), matching the per-process step accounting that divides by
    them. All-None in a process that holds no backend; no peaks on
    CPU; ``ValueError`` for an accelerator ``_DEVICE_PEAKS`` does not
    list."""
    out: Dict[str, Optional[float]] = {
        "flops_per_sec": None, "mem_bw": None, "devices": None, "kind": None,
    }
    devs = local_devices_if_initialized()
    if not devs:
        return out
    kind = devs[0].device_kind
    out["devices"] = float(len(devs))
    out["kind"] = kind
    if devs[0].platform == "cpu":
        return out
    for tag, flops, bw in _DEVICE_PEAKS:
        if tag in kind.lower():
            out["flops_per_sec"] = flops * len(devs)
            out["mem_bw"] = bw * len(devs)
            return out
    raise ValueError(
        f"no peak FLOP/s and bandwidth entry for device_kind {kind!r} "
        f"(platform {devs[0].platform!r}); add it to _DEVICE_PEAKS in "
        "raydp_tpu/telemetry/device_profiler.py"
    )


# -- per-compiled-function cost registry ------------------------------------

_cost_mu = threading.Lock()
_costs: Dict[str, Dict[str, float]] = {}


def note_compiled(label: str, jitted, args, kwargs) -> None:
    """Register analytical FLOPs/bytes for ``label`` (called once from
    ``_guard_compile`` after the first successful dispatch). Never
    raises; a backend without cost analysis just leaves the label
    unregistered."""
    if not enabled():
        return
    with _cost_mu:
        if label in _costs:
            return
    from raydp_tpu.utils.profiling import cost_analysis_summary

    cost = cost_analysis_summary(jitted, args, kwargs)
    if cost is None:
        return
    with _cost_mu:
        _costs[label] = cost
    metrics.gauge_set(f"cost/{label}/flops", cost["flops"])
    metrics.gauge_set(f"cost/{label}/bytes", cost["bytes"])


def get_cost(label: str) -> Optional[Dict[str, float]]:
    with _cost_mu:
        cost = _costs.get(label)
        return dict(cost) if cost else None


def clear_costs() -> None:
    """Test hook: forget registered analyses (labels are global)."""
    with _cost_mu:
        _costs.clear()


# -- step-phase accounting ---------------------------------------------------

def classify_fractions(
    fractions: Dict[str, float],
    intensity: Optional[float] = None,
    balance: Optional[float] = None,
) -> str:
    """Bound-ness verdict from phase fractions (+ roofline when known).

    ``input-bound`` / ``collective-bound`` come straight from the
    measured fractions; the compute-vs-memory call needs the roofline:
    arithmetic intensity (FLOPs/byte of the step) against the machine
    balance (peak FLOPs / memory bandwidth). Without peaks (CPU) a
    dominant compute fraction reports ``compute-bound`` and a dominant
    dispatch fraction ``host-bound``."""
    inp = fractions.get("input_wait_frac", 0.0)
    coll = fractions.get("collective_frac", 0.0)
    comp = fractions.get("compute_frac", 0.0)
    disp = fractions.get("dispatch_frac", 0.0)
    if inp >= 0.35 and inp >= comp:
        return "input-bound"
    if coll >= 0.25 and coll >= comp:
        return "collective-bound"
    if intensity is not None and balance is not None and balance > 0:
        return "compute-bound" if intensity >= balance else "memory-bound"
    return "compute-bound" if comp >= disp else "host-bound"


class StepPhaseAccumulator:
    """Per-epoch phase totals for one training loop.

    The infeed generator reports ``note_input_wait`` (blocked pulling
    the next host batch) and ``note_dispatch`` (shard + device_put
    time); the step loop reports ``step(call_s)`` with the jitted-call
    wall time. The call time is split host/device by the
    donated-buffer-block argument: the running MINIMUM call time is the
    pure enqueue cost (a dispatch that did not block on the device),
    everything above it is device time the host waited out. Collective
    time is estimated from the step's HLO cost analysis
    (``collective_bytes / ici_bw``) and capped by the device share.
    """

    def __init__(self, label: str = "train_step"):
        self.label = label
        self._pending_wait = 0.0
        self._pending_dispatch = 0.0
        self._min_call: Optional[float] = None
        self._mu = threading.Lock()
        self._hist = metrics.histogram("train/step_seconds")
        self.reset_epoch()
        self.total_steps = 0

    def reset_epoch(self) -> None:
        self.epoch_phases = {
            "input_wait_s": 0.0, "dispatch_s": 0.0,
            "compute_s": 0.0, "collective_s": 0.0,
        }
        self.epoch_steps = 0

    # Called from the infeed generator (same thread as the step loop).
    def note_input_wait(self, seconds: float) -> None:
        self._pending_wait += max(0.0, seconds)

    def note_dispatch(self, seconds: float) -> None:
        self._pending_dispatch += max(0.0, seconds)

    def step(self, call_s: float) -> None:
        """Fold one completed step: pending infeed phases + the jitted
        call's wall time."""
        call_s = max(0.0, call_s)
        self._hist.observe(call_s)
        if self._min_call is None or call_s < self._min_call:
            self._min_call = call_s
        host_enqueue = min(self._min_call, call_s)
        device_s = call_s - host_enqueue
        coll_s = 0.0
        cost = get_cost(self.label)
        if cost and cost.get("collective_bytes"):
            peaks = device_peaks()
            bw = peaks.get("mem_bw")
            if bw:
                # ICI sits within ~an order of HBM bw; using HBM bw as
                # the divisor keeps this a lower-bound estimate.
                coll_s = min(device_s, cost["collective_bytes"] / bw)
        ph = self.epoch_phases
        ph["input_wait_s"] += self._pending_wait
        ph["dispatch_s"] += self._pending_dispatch + host_enqueue
        ph["compute_s"] += device_s - coll_s
        ph["collective_s"] += coll_s
        self._pending_wait = 0.0
        self._pending_dispatch = 0.0
        self.epoch_steps += 1
        self.total_steps += 1

    def epoch_summary(self, reset: bool = True) -> Dict[str, Any]:
        """Totals + fractions for the epoch; updates the live gauges
        (``phase/*_frac``, ``mfu``, ``roofline/*``) and cumulative
        ``phase/*_seconds`` counters, then (by default) resets the
        epoch window."""
        ph = dict(self.epoch_phases)
        steps = self.epoch_steps
        wall = sum(ph.values())
        fractions = {
            "input_wait_frac": ph["input_wait_s"] / wall if wall else 0.0,
            "dispatch_frac": ph["dispatch_s"] / wall if wall else 0.0,
            "compute_frac": ph["compute_s"] / wall if wall else 0.0,
            "collective_frac": ph["collective_s"] / wall if wall else 0.0,
        }
        for name, value in ph.items():
            metrics.counter_add(f"phase/{name[:-2]}_seconds", value)
        for name, value in fractions.items():
            metrics.gauge_set(f"phase/{name}", round(value, 4))

        cost = get_cost(self.label)
        peaks = device_peaks()
        mfu = None
        intensity = None
        balance = None
        if cost and cost.get("bytes"):
            intensity = cost["flops"] / cost["bytes"]
            metrics.gauge_set("roofline/intensity_flops_per_byte",
                              round(intensity, 3))
        if peaks["flops_per_sec"] and peaks["mem_bw"]:
            balance = peaks["flops_per_sec"] / peaks["mem_bw"]
            metrics.gauge_set("roofline/machine_balance", round(balance, 3))
        if (
            cost and steps and wall
            and peaks["flops_per_sec"]
        ):
            mfu = (cost["flops"] * steps) / (wall * peaks["flops_per_sec"])
            metrics.gauge_set("mfu", round(mfu, 4))
        bound = classify_fractions(fractions, intensity, balance)
        out: Dict[str, Any] = {
            "steps": steps,
            "wall_s": round(wall, 6),
            "bound": bound,
            **{k: round(v, 6) for k, v in ph.items()},
            **{k: round(v, 4) for k, v in fractions.items()},
        }
        if mfu is not None:
            out["mfu"] = round(mfu, 4)
        if intensity is not None:
            out["intensity_flops_per_byte"] = round(intensity, 3)
        if reset:
            self.reset_epoch()
        return out


# -- anomaly sentinels -------------------------------------------------------

class AnomalySentinel:
    """NaN/Inf + step-time-regression detection for a training loop.

    Finiteness checks sync host↔device, so they run every
    ``check_every`` steps (``RAYDP_TPU_SENTINEL_EVERY``, default 64)
    rather than every step; a NaN persists once it appears, so the
    detection lag is bounded by the cadence. A NaN fires ONE
    flight-recorder bundle (cooldown-limited) — the bundle carries the
    event tail that explains what led up to it.

    The step-regression detector compares each step against the rolling
    median: ``duration > median × factor`` (default 2.5) with at least
    ``min_steps`` history flags a regression event (flight event +
    counter, no bundle — slow is not crashed), rate-limited by the same
    cooldown so a persistently degraded run doesn't spam one event per
    step.
    """

    def __init__(
        self,
        check_every: Optional[int] = None,
        cooldown_s: Optional[float] = None,
        regression_factor: Optional[float] = None,
        regression_min_steps: Optional[int] = None,
    ):
        def _env(name, cast, default):
            raw = os.environ.get(name)
            if raw is None:
                return default
            try:
                return cast(raw)
            except ValueError:
                return default

        self.check_every = (
            check_every if check_every is not None
            else max(1, _env(_SENTINEL_EVERY_ENV, int, 64))
        )
        self.cooldown_s = (
            cooldown_s if cooldown_s is not None
            else _env(_SENTINEL_COOLDOWN_ENV, float, 60.0)
        )
        self.regression_factor = (
            regression_factor if regression_factor is not None
            else _env(_REGRESSION_FACTOR_ENV, float, 2.5)
        )
        self.regression_min_steps = (
            regression_min_steps if regression_min_steps is not None
            else _env(_REGRESSION_MIN_ENV, int, 8)
        )
        self._recent: "deque[float]" = deque(maxlen=128)
        self._last_fire: Dict[str, float] = {}
        self.tripped: List[Dict[str, Any]] = []

    def _fire(self, kind: str, bundle: bool, **attrs: Any) -> bool:
        now = time.monotonic()
        last = self._last_fire.get(kind)
        metrics.counter_add(f"anomalies/{kind}")
        if last is not None and now - last < self.cooldown_s:
            return False
        self._last_fire[kind] = now
        self.tripped.append({"kind": kind, **attrs})
        from raydp_tpu.telemetry import flight_recorder as _flight

        _flight.record("anomaly", kind, **attrs)
        try:  # timeline correlation (lazy: events imports this module)
            from raydp_tpu.telemetry import events as _events

            _events.emit("sentinel/anomaly", kind=kind, **attrs)
        except Exception:
            pass
        if bundle:
            try:
                _flight.dump_bundle(f"anomaly:{kind}")
            except Exception:
                pass
        return True

    def wants_check(self, step: int) -> bool:
        """True on the steps whose loss/grad-norm should be synced."""
        return step % self.check_every == 0

    def check_loss(self, value: float, step: int, epoch: int = -1) -> bool:
        """``value`` is an already-synced float. Returns True when the
        NaN sentinel fired (bundle emitted)."""
        import math

        if math.isfinite(value):
            return False
        return self._fire(
            "nan_loss", bundle=True, step=step, epoch=epoch, value=str(value)
        )

    def check_grad_norm(self, value: float, step: int,
                        epoch: int = -1) -> bool:
        import math

        if math.isfinite(value):
            return False
        return self._fire(
            "nan_grad_norm", bundle=True, step=step, epoch=epoch,
            value=str(value),
        )

    def observe_step(self, duration_s: float, step: int,
                     epoch: int = -1) -> bool:
        """Feed one step duration; True when a regression event fired."""
        fired = False
        if len(self._recent) >= self.regression_min_steps:
            xs = sorted(self._recent)
            median = xs[len(xs) // 2]
            if median > 0 and duration_s > median * self.regression_factor:
                fired = self._fire(
                    "step_regression", bundle=False, step=step, epoch=epoch,
                    duration_s=round(duration_s, 6),
                    median_s=round(median, 6),
                    factor=round(duration_s / median, 2),
                )
        self._recent.append(duration_s)
        return fired


# -- gang-coordinated trace capture -----------------------------------------

def capture_local_trace(seconds: float, out_dir: Optional[str] = None,
                        ) -> Dict[str, Any]:
    """Run a ``jax.profiler`` trace in THIS process for ``seconds``
    (blocking the calling thread, not the training threads — jax traces
    whatever the process is doing), flush span shards into the same
    directory, and return ``{"dir", "wall_start", "wall_stop"}``.

    Builds on ``utils/profiling.trace`` (the single-process primitive);
    the gang path zips this directory per rank and merges driver-side.
    """
    from raydp_tpu.telemetry.export import flush_spans
    from raydp_tpu.utils.profiling import trace

    out_dir = out_dir or tempfile.mkdtemp(prefix="raydp-profile-")
    os.makedirs(out_dir, exist_ok=True)
    wall_start = time.time()
    with trace(out_dir):
        time.sleep(max(0.0, float(seconds)))
    wall_stop = time.time()
    try:
        flush_spans(out_dir)
    except Exception:
        pass
    return {"dir": out_dir, "wall_start": wall_start,
            "wall_stop": wall_stop}


def capture_trace_archive(seconds: float, rank: Any = None,
                          ) -> Dict[str, Any]:
    """ProfileRequest handler body: capture locally, zip the trace dir,
    return ``{"zip": bytes, "wall_start", "wall_stop", "rank", "pid"}``.
    The zip ships back through the RPC reply or the shm store; the
    local directory is removed."""
    import shutil

    info = capture_local_trace(seconds)
    out_dir = info["dir"]
    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as zf:
        for root, _dirs, files in os.walk(out_dir):
            for name in files:
                path = os.path.join(root, name)
                zf.write(path, os.path.relpath(path, out_dir))
    shutil.rmtree(out_dir, ignore_errors=True)
    return {
        "zip": buf.getvalue(),
        "wall_start": info["wall_start"],
        "wall_stop": info["wall_stop"],
        "rank": rank,
        "pid": os.getpid(),
    }


def unpack_trace_archive(payload: Dict[str, Any], dest: str) -> str:
    """Unpack one rank's archive into ``dest`` and return it."""
    os.makedirs(dest, exist_ok=True)
    with zipfile.ZipFile(io.BytesIO(payload["zip"])) as zf:
        zf.extractall(dest)
    return dest


def _load_jax_chrome_events(rank_dir: str) -> List[Dict[str, Any]]:
    """traceEvents from the jax profiler's ``*.trace.json.gz`` files
    under one rank's unpacked dir (the TensorBoard profile plugin
    writes them next to the xplane.pb)."""
    events: List[Dict[str, Any]] = []
    pattern = os.path.join(rank_dir, "plugins", "profile", "*",
                           "*.trace.json.gz")
    for path in sorted(glob.glob(pattern)):
        try:
            data = json.loads(gzip.open(path, "rb").read())
        except Exception:
            continue
        events.extend(data.get("traceEvents", []) or [])
    return events


def merge_rank_traces(
    payloads: List[Dict[str, Any]], out_dir: str,
) -> Dict[str, Any]:
    """Merge per-rank capture payloads into one Perfetto-loadable file.

    Each payload (from :func:`capture_trace_archive`) is unpacked under
    ``out_dir/rank-<n>/`` (kept — TensorBoard can open the raw xplane
    profiles). The merged Chrome trace combines, per rank:

    * the jax profiler's own Chrome events (XLA ops, runtime threads),
      shifted so each rank's first event lands at that rank's recorded
      capture wall-start — cross-rank alignment to RPC-skew precision;
    * the framework span shards captured in the window, aligned with
      the same per-pid wall/mono offsets ``chrome_trace.py`` uses.

    Rank pids are remapped into disjoint ranges and process names
    prefixed ``rank N:`` so every rank shows as its own process group.
    Returns ``{"merged_trace", "out_dir", "ranks"}``.
    """
    from raydp_tpu.telemetry.chrome_trace import (
        aligned_interval, clock_offsets, load_span_records, to_chrome_trace,
    )

    os.makedirs(out_dir, exist_ok=True)
    merged: List[Dict[str, Any]] = []
    base_wall = min(
        (p["wall_start"] for p in payloads if p.get("wall_start")),
        default=time.time(),
    )
    ranks: List[Any] = []
    for idx, payload in enumerate(payloads):
        rank = payload.get("rank")
        rank = idx if rank is None else rank
        ranks.append(rank)
        rank_dir = os.path.join(out_dir, f"rank-{rank}")
        unpack_trace_archive(payload, rank_dir)
        pid_base = (idx + 1) * 100000

        # jax profiler events: remap pids into this rank's range and
        # shift onto the shared wall clock.
        events = _load_jax_chrome_events(rank_dir)
        first_ts = min(
            (float(e["ts"]) for e in events if "ts" in e), default=None
        )
        shift = (
            (payload.get("wall_start", base_wall) - base_wall) * 1e6
            - (first_ts or 0.0)
        )
        for ev in events:
            ev = dict(ev)
            ev["pid"] = pid_base + int(ev.get("pid", 0)) % 100000
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                args = dict(ev.get("args") or {})
                args["name"] = f"rank {rank}: {args.get('name', '?')}"
                ev["args"] = args
            if "ts" in ev:
                ev["ts"] = float(ev["ts"]) + shift
            merged.append(ev)

        # framework spans recorded during the window: chrome_trace's
        # own converter (wall-aligned), pids remapped likewise.
        records = load_span_records(rank_dir)
        if records:
            offsets = clock_offsets(records)
            rank_base = min(
                aligned_interval(r, offsets)[0] for r in records
            )
            span_doc = to_chrome_trace(records)
            for ev in span_doc.get("traceEvents", []):
                ev = dict(ev)
                ev["pid"] = pid_base + 50000 + int(ev.get("pid", 0)) % 50000
                if ev.get("ph") == "M" and ev.get("name") == "process_name":
                    args = dict(ev.get("args") or {})
                    args["name"] = f"rank {rank} spans: " \
                                   f"{args.get('name', '?')}"
                    ev["args"] = args
                elif "ts" in ev:
                    # to_chrome_trace emits µs since the rank's own
                    # earliest span, whose wall time is directly
                    # comparable across ranks — re-base onto the merged
                    # window's origin.
                    ev["ts"] = float(ev["ts"]) + (
                        rank_base - base_wall
                    ) * 1e6
                merged.append(ev)

    out_path = os.path.join(out_dir, "merged_trace.json")
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"displayTimeUnit": "ns", "traceEvents": merged}, f)
    os.replace(tmp, out_path)
    return {"merged_trace": out_path, "out_dir": out_dir, "ranks": ranks}
