"""Anomaly sentinels for a training loop, and gang-coordinated trace
capture.

* **Anomaly sentinels** — :class:`AnomalySentinel` checks loss /
  global grad-norm finiteness on a sampled cadence (a per-step
  ``float()`` would sync host↔device and serialize the infeed
  pipeline) and flags step-time regressions against a rolling median;
  both emit flight-recorder events and ``anomalies/*`` counters
  (→ ``raydp_anomalies_total``). Every fit constructs one.
* **Gang-coordinated trace capture** — :func:`capture_trace_archive`
  runs the single-process ``utils/profiling.trace`` (jax.profiler) for
  N seconds and zips the result; drivers fan a ``ProfileRequest`` RPC
  to every rank/worker simultaneously and :func:`merge_rank_traces`
  aligns the per-rank Chrome traces + span shards into ONE
  Perfetto-loadable JSON (same clock-offset idiom as chrome_trace.py).

Where a step's time goes is read from that trace and the program's
spans (``train/step``, ``infeed/put``, ``train/loss_fetch``, ...,
bridged into it as profiler annotations), not inferred from host call
times: ``train/step`` is the time of the dispatch CALL, which blocks
while the device's queue is full and returns at once while it is not.
"""
from __future__ import annotations

import glob
import gzip
import io
import json
import os
import tempfile
import time
import zipfile
from collections import deque
from typing import Any, Dict, List, Optional

from raydp_tpu.utils.profiling import metrics

__all__ = [
    "AnomalySentinel",
    "capture_local_trace",
    "capture_trace_archive",
    "merge_rank_traces",
    "unpack_trace_archive",
]

_SENTINEL_EVERY_ENV = "RAYDP_TPU_SENTINEL_EVERY"
_SENTINEL_COOLDOWN_ENV = "RAYDP_TPU_SENTINEL_COOLDOWN_S"
_REGRESSION_FACTOR_ENV = "RAYDP_TPU_STEP_REGRESSION_FACTOR"
_REGRESSION_MIN_ENV = "RAYDP_TPU_STEP_REGRESSION_MIN_STEPS"


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
