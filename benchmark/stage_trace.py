"""A cluster stage of the ETL engine read from inside (PR 34): what a
stage's wall is made of, what the workers do while the loader waits, and
what the chip's idle time under the engine's spans sits next to.

Two sources, both the program's own:

* the engine's ``stage_store`` records, read in this process as
  ``jobs/etl_fit_jobs.py`` reads them: each ``cluster`` record holds the
  stage's wall partitioned along its critical path into ``submit_s``,
  ``transit_s``, ``load_s``, ``exec_s`` and ``driver_s`` (measured from the
  stamps driver and worker put on the task reply);
* the run's profile: the driver's ``stage/envelope`` span around each task
  envelope's RPC (attrs ``worker``, ``tasks``, ``env``) and the
  ``stage/close`` span of each stage, whose ``envelopes`` attr lists for
  each envelope the worker's ``ret - recv`` and its task bodies relative
  to ``recv``. Driver and worker need no clock in common: a worker's
  interval is put in the middle of its envelope's span (the NTP rule), so
  it is off by at most half that envelope's transit; the largest such
  bound is ``placement_error_ms_max``.

A program without these fields and spans (the parent of PR 34) gives an
empty summary: every reader returns ``None`` and raises nothing.

``summary(facts)`` is what the ``layers/`` readers call; it also writes
``benchmark_out/<cell>.stage_trace.json`` (the report) and
``<cell>.stage_trace.recorded.json.gz`` (what ``load_recorded`` reads).
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import statistics
import time
import warnings
from typing import Dict, List, Optional

import program_trace as pt
import trace_reduce as tr

ENVELOPE_SPAN, CLOSE_SPAN = "stage/envelope", "stage/close"
STAGE_SPAN, WAIT_SPAN = "df/stage", "ingest/wait"
PARTS = ("submit_s", "transit_s", "load_s", "exec_s", "driver_s")
KEPT_STATS = ("env", "worker", "tasks", "stage", "op", "envelopes")
GAP_NS = 1e6            # unattributed gaps shorter than this are summed only
RECORDED_MERGE_NS = 2e3  # busy intervals closer than this are one, recorded


# ------------------------------------------------------------- loading

def load_stages() -> List[dict]:
    """The retained ``cluster`` records of this process's ``stage_store``."""
    from raydp_tpu.telemetry.progress import stage_store

    return [s for s in stage_store.snapshot()["stages"]
            if s["executor"] == "cluster"]


def load_profile(path: str) -> dict:
    """``{"host": [[name, start_ns, duration_ns, stats, line]], "busy":
    [[start_ns, end_ns]], "window": [lo, hi]}`` of an ``.xplane.pb``: the
    program's spans and the benchmark's own (``bench/...``) of every host
    thread with the attrs this module reads, and chip 0's busy intervals
    (empty off the TPU)."""
    from jax.profiler import ProfileData

    host, ops, line_no = [], [], 0
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            chip = tr.DEVICE_PLANE.match(plane.name)
            if chip:
                if chip.group(1) == "0":
                    ops = [
                        (float(e.start_ns), float(e.start_ns + e.duration_ns))
                        for ln in plane.lines if ln.name == tr.OPS_LINE
                        for e in ln.events
                    ]
                continue
            if not plane.name.startswith("/host:"):
                continue
            for ln in plane.lines:
                line_no += 1
                for e in ln.events:
                    name = e.name
                    if not (name.startswith(tr.SPAN_PREFIX)
                            or pt.PROGRAM_SPAN.match(name)):
                        continue
                    stats = {}
                    if name in (ENVELOPE_SPAN, CLOSE_SPAN):
                        stats = {k: v for k, v in dict(e.stats).items()
                                 if k in KEPT_STATS}
                    host.append([name, float(e.start_ns),
                                 float(e.duration_ns), stats, line_no])
    return {"host": host, "busy": [list(iv) for iv in tr.union(ops)],
            "window": _window(host, ops)}


def _window(host, ops) -> List[float]:
    marks = [(s, s + d) for n, s, d, _, _ in host if n == tr.WINDOW_SPAN]
    marks = marks or [(s, s + d) for _, s, d, _, _ in host] or list(ops)
    if not marks:
        return [0.0, 0.0]
    return [min(a for a, _ in marks), max(b for _, b in marks)]


def save_recorded(profile: dict, stages: List[dict], path: str) -> None:
    """What ``reduce`` reads, as one small file: the spans, the stage
    records, and chip 0's busy intervals with neighbours closer than
    ``RECORDED_MERGE_NS`` merged (a step's operations follow each other
    within nanoseconds; the idle time moves by that much)."""
    busy: List[list] = []
    for a, b in profile["busy"]:
        if busy and a - busy[-1][1] < RECORDED_MERGE_NS:
            busy[-1][1] = max(busy[-1][1], b)
        else:
            busy.append([a, b])
    lo = profile["window"][0]
    slim = {
        "window": [0.0, profile["window"][1] - lo],
        "host": [[n, round(s - lo), round(d), st, ln]
                 for n, s, d, st, ln in profile["host"]],
        "busy": [[round(a - lo), round(b - lo)] for a, b in busy],
        "stages": stages,
    }
    with gzip.open(path, "wt") as f:
        json.dump(slim, f, separators=(",", ":"))


def load_recorded(path: str):
    """``(profile, stages)`` of a file ``save_recorded`` wrote."""
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    return data, data.get("stages", [])


# ------------------------------------------------- the stage's partition

def partition(stages: List[dict]) -> Optional[dict]:
    """Sums, shares and the fixed cost of the ``cluster`` records; ``None``
    when there is none, or the program does not partition a stage."""
    stages = [s for s in stages if s.get("executor", "cluster") == "cluster"
              and s.get("wall_s", 0.0) > 0.0]
    if not stages or any(k not in s for s in stages for k in PARTS):
        return None
    total = {k: sum(s[k] for s in stages) for k in PARTS}
    wall = sum(s["wall_s"] for s in stages)
    by_op: Dict[str, dict] = {}
    for s in stages:
        op = by_op.setdefault(
            s["op"], {"stages": 0, "wall_s": 0.0, **dict.fromkeys(PARTS, 0.0)}
        )
        op["stages"] += 1
        op["wall_s"] += s["wall_s"]
        for k in PARTS:
            op[k] += s[k]
    fixed = [s["wall_s"] - s["exec_s"] for s in stages]
    return {
        "stages": len(stages),
        "wall_s": wall,
        **total,
        "upstream_s": sum(s.get("upstream_s", 0.0) for s in stages),
        "queue_s": sum(s.get("queue_s", 0.0) for s in stages),
        "stage_wall_ms_median": 1e3 * statistics.median(
            s["wall_s"] for s in stages),
        "stage_fixed_ms": 1e3 * statistics.median(fixed),
        # The shares below are a composition: what shortens the bodies
        # lowers exec's share. The absolute body is here.
        "stage_exec_ms_median": 1e3 * statistics.median(
            s["exec_s"] for s in stages),
        "exec_share": 100.0 * total["exec_s"] / wall,
        "transit_share": 100.0 * total["transit_s"] / wall,
        "load_share": 100.0 * total["load_s"] / wall,
        "driver_share": 100.0 * (total["submit_s"] + total["driver_s"]) / wall,
        "sum_over_wall": sum(total.values()) / wall,
        "by_op": sorted(
            ({"op": op, **v} for op, v in by_op.items()),
            key=lambda v: -v["wall_s"],
        )[:12],
    }


# ------------------------------------------- the workers on the profile

def parse_envelopes(text) -> List[dict]:
    """The ``envelopes`` attr of a ``stage/close`` span (the engine's
    ``executor.format_envelopes``; this is its one parser): ``<env>:<worker>:<ret - recv>:<start>-
    <end>+...`` joined by ``;``, whole microseconds, bodies from ``recv``."""
    out = []
    for item in filter(None, str(text or "").split(";")):
        env, worker, dur, bodies = item.split(":")
        out.append({
            "env": int(env), "worker": worker, "worker_us": int(dur),
            "bodies_us": [tuple(int(v) for v in b.split("-", 1))
                          for b in bodies.split("+") if b],
        })
    return out


def place_workers(host) -> dict:
    """Every listed envelope's worker interval and bodies on the profile's
    clock, centred in its ``stage/envelope`` span. Returns the bodies as
    ``(start, end, worker)``, the worker intervals as ``(start, end,
    worker, env)``, how many envelopes were placed or had no span in the
    profile, and the largest error bound."""
    spans = {}
    for name, s, d, stats, _ in host:
        if name == ENVELOPE_SPAN and "env" in stats:
            spans[int(stats["env"])] = (s, d)
    bodies, intervals, missing, worst = [], [], 0, 0.0
    for name, _, _, stats, _ in host:
        if name != CLOSE_SPAN:
            continue
        for e in parse_envelopes(stats.get("envelopes")):
            if e["env"] not in spans:
                missing += 1
                continue
            start, dur = spans[e["env"]]
            slack = max(0.0, dur - e["worker_us"] * 1e3) / 2
            worst = max(worst, slack)
            recv = start + slack
            intervals.append((recv, recv + e["worker_us"] * 1e3, e["worker"],
                              e["env"]))
            bodies.extend((recv + a * 1e3, recv + b * 1e3, e["worker"])
                          for a, b in e["bodies_us"])
    return {"bodies": bodies, "intervals": intervals, "missing": missing,
            "placed": len(intervals), "placement_error_ms_max": worst * 1e-6}


def _spans(host, name, lo, hi):
    return tr.union(tr.clip(
        [(s, s + d) for n, s, d, _, _ in host if n == name], lo, hi))


def _split(intervals, running) -> dict:
    inside = pt._intersect(intervals, running)
    return {"seconds": tr.total(intervals) * 1e-9,
            "a_worker_ran_a_body_s": tr.total(inside) * 1e-9,
            "no_worker_ran_a_body_s":
                (tr.total(intervals) - tr.total(inside)) * 1e-9}


def reduce(profile: dict, stages: List[dict]):
    """``(summary, report)``: what the readers return, and the tables."""
    summary: dict = {}
    report: dict = {}
    parts = partition(stages)
    if parts:
        report["partition"] = parts
        for key in ("stage_fixed_ms", "exec_share", "transit_share",
                    "load_share", "driver_share"):
            summary[key] = parts[key]
    host = profile.get("host", [])
    lo, hi = profile.get("window", (0.0, 0.0))
    if hi <= lo or not host:
        return summary, report
    placed = place_workers(host)
    report["envelopes_placed"] = placed["placed"]
    report["envelopes_outside_the_profile"] = placed["missing"]
    report["placement_error_ms_max"] = placed["placement_error_ms_max"]
    if not placed["placed"]:
        return summary, report
    running = tr.union(tr.clip(
        [(a, b) for a, b, _ in placed["bodies"]], lo, hi))
    inside_worker = tr.union(tr.clip(
        [iv[:2] for iv in placed["intervals"]], lo, hi))
    report["window_s"] = (hi - lo) * 1e-9
    report["a_worker_ran_a_body_s"] = tr.total(running) * 1e-9
    report["a_worker_held_an_envelope_s"] = tr.total(inside_worker) * 1e-9
    by_worker: Dict[str, list] = {}
    for a, b, w in placed["bodies"]:
        by_worker.setdefault(w, []).append((a, b))
    report["body_s_by_worker"] = {
        w: tr.total(tr.union(tr.clip(iv, lo, hi))) * 1e-9
        for w, iv in sorted(by_worker.items())
    }

    # ---- the loader's wait: is a worker computing through it?
    waits = _spans(host, WAIT_SPAN, lo, hi)
    if waits:
        split = _split(waits, running)
        split["a_worker_held_an_envelope_s"] = tr.total(
            pt._intersect(waits, inside_worker)) * 1e-9
        split["no_body_by_open_span_s"] = _elsewhere(
            host, tr.subtract(waits, running), lo, hi)
        report["ingest_wait"] = split
        summary["wait_worker_busy_share"] = (
            100.0 * split["a_worker_ran_a_body_s"] / split["seconds"]
        )

    # ---- chip 0's idle time under the engine's spans
    busy = tr.union(tr.clip([tuple(iv) for iv in profile.get("busy", [])],
                            lo, hi))
    if busy:
        gaps = tr.subtract([(lo, hi)], busy)
        report["idle_s"] = tr.total(gaps) * 1e-9
        report["idle_under"] = {
            name: _split(pt._intersect(gaps, _spans(host, name, lo, hi)),
                         running)
            for name in (STAGE_SPAN, WAIT_SPAN)
        }
        report["unattributed"] = _unattributed(host, gaps, lo, hi)
    return summary, report


def _elsewhere(host, intervals, lo, hi) -> list:
    """``intervals`` (waits with no body running) by the program's span
    open on ANOTHER thread than the waiting one, the shortest-lived name
    first as ``program_trace`` gives idle gaps to spans; what no such span
    covers is ``(no span)``: driver work nothing names."""
    waiting = {ln for n, _, _, _, ln in host if n == WAIT_SPAN}
    merged: Dict[str, list] = {}
    for n, s, d, _, ln in host:
        if (ln not in waiting and pt.PROGRAM_SPAN.match(n)
                and not n.startswith(tr.SPAN_PREFIX)):
            merged.setdefault(n, []).append((s, s + d))
    merged = {n: tr.union(tr.clip(iv, lo, hi)) for n, iv in merged.items()}
    out, left = {}, list(intervals)
    for n in sorted(merged, key=lambda n: tr.total(merged[n])):
        mine = pt._intersect(left, merged[n])
        if mine:
            out[n] = tr.total(mine) * 1e-9
            left = tr.subtract(left, mine)
    if left:
        out["(no span)"] = tr.total(left) * 1e-9
    return sorted(([n, v] for n, v in out.items()), key=lambda nv: -nv[1])


def _unattributed(host, gaps, lo, hi) -> dict:
    """The idle time with no span of the program open on any thread, as
    ``program_trace`` counts it, and what each such gap is next to: the
    span that closed last before it, the one that opens first after it,
    and the benchmark's own spans open through it (none: the gap is
    outside a job's ETL, hand-off and loader waits — between two jobs)."""
    program = sorted(
        (s, s + d, n) for n, s, d, _, _ in host
        if pt.PROGRAM_SPAN.match(n) and not n.startswith(tr.SPAN_PREFIX)
    )
    bench = [(s, s + d, n) for n, s, d, _, _ in host
             if n.startswith(tr.SPAN_PREFIX) and n != tr.WINDOW_SPAN]
    covered = tr.union(tr.clip([(a, b) for a, b, _ in program], lo, hi))
    bare = tr.subtract(gaps, covered)
    by_pair: Dict[tuple, list] = {}
    for a, b in bare:
        if b - a < GAP_NS:
            continue
        before = max((p for p in program if p[1] <= a + 1e3),
                     key=lambda p: p[1], default=None)
        after = min((p for p in program if p[0] >= b - 1e3),
                    key=lambda p: p[0], default=None)
        mid = (a + b) / 2
        key = (before[2] if before else None, after[2] if after else None,
               ",".join(sorted({n for s, e, n in bench if s <= mid < e})))
        entry = by_pair.setdefault(key, [0, 0.0])
        entry[0] += 1
        entry[1] += (b - a) * 1e-9
    return {
        "seconds": tr.total(bare) * 1e-9,
        "gaps_over_1_ms": sum(v[0] for v in by_pair.values()),
        "by_neighbours": [
            {"closed_before": k[0], "opens_after": k[1],
             "bench_spans_open": k[2], "gaps": v[0], "seconds": v[1]}
            for k, v in sorted(by_pair.items(), key=lambda kv: -kv[1][1])
        ],
    }


# ------------------------------------------------------- for the readers

_CACHE: dict = {}


def summary(facts: dict) -> dict:
    """The summary of this run, ``{}`` where the program keeps no such
    record. Read once per process; the report goes to
    ``benchmark_out/<cell>.stage_trace.json``."""
    cell = facts["cell"]
    out_dir = os.path.join(os.path.dirname(cell.bench_dir), "benchmark_out")
    paths = sorted(glob.glob(os.path.join(
        out_dir, "trace", "plugins", "profile", "*", "*.xplane.pb"
    )))
    key = (paths[-1], os.path.getmtime(paths[-1])) if paths else None
    key = (key, cell.name)
    if key not in _CACHE:
        t0 = time.perf_counter()
        stages = load_stages()
        profile = load_profile(paths[-1]) if paths else {}
        result, report = reduce(profile, stages)
        report["read_s"] = time.perf_counter() - t0
        with open(os.path.join(
            out_dir, cell.name + ".stage_trace.json"
        ), "w") as f:
            json.dump(report, f, indent=1)
        if profile:
            save_recorded(profile, stages, os.path.join(
                out_dir, cell.name + ".stage_trace.recorded.json.gz"
            ))
        _CACHE.clear()
        _CACHE[key] = result
    return _CACHE[key]
