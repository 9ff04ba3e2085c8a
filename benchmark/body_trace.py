"""An ETL task's body and the hand-off read from inside (PR 65): what a
task body is made of, and what the loader's thread does between the last
stage's blocks and the first batch.

Two sources, both the program's own, both ``stage_trace``'s:

* the engine's ``stage_store`` records (``stage_trace.load_stages``): a
  ``cluster`` record whose workers stamp their bodies holds ``fetch_s``,
  ``put_s``, ``register_s`` and ``body_s`` — sums over ALL the stage's task
  bodies of the time inside ``WorkerContext.get_table``, the store's write,
  the ``RegisterObject`` round trip and the bodies whole — and
  ``tasks_stamped``, the bodies in the sums. A body's compute is the body
  less the three. These partition work, not wall: ``exec_s`` and the five
  parts of ``wall_s`` are ``stage_trace``'s.
* the run's profile in the form ``stage_trace`` reads: the spans
  ``handoff/materialize`` (the loader's ``shard_columns``), and under it
  ``handoff/await_blocks`` (the wait for the lazily run last stage),
  ``handoff/fetch`` and ``handoff/convert`` (the hand-off's OWN work), on
  the clock of ``ingest/wait``, ``train/fit`` and chip 0's busy intervals.
  ``stage_trace.summary`` has read this run's ``.xplane.pb`` and left its
  small form beside its report (busy intervals closer than 2 µs are one
  there): that is read here, the profile is not opened again.

The four body parts are absolute milliseconds, not shares: a share is a
composition, and what shortens one part raises the others'.

A program without these fields and spans (the parent of PR 65) gives an
empty summary: every reader returns ``None`` and raises nothing.

``summary(facts)`` is what the ``layers/`` readers call; it also writes
``benchmark_out/<cell>.body_trace.json`` (the report).
"""
from __future__ import annotations

import glob
import json
import os
import statistics
import time
from typing import Dict, List, Optional

import program_trace as pt
import stage_trace as st
import trace_reduce as tr

MATERIALIZE, AWAIT = "handoff/materialize", "handoff/await_blocks"
FETCH, CONVERT = "handoff/fetch", "handoff/convert"
FIT_SPAN = "train/fit"
STAMPED = ("fetch_s", "put_s", "register_s")
TASK_PARTS = ("fetch", "compute", "put", "register")
# What the loader's wait is split by, in this order: the spans the
# producer's thread opens one after the other.
UNDER_WAIT = (AWAIT, FETCH, CONVERT, "ingest/stage_matrix", "ingest/chunk")


# ------------------------------------------------------ a body's parts

def _task_parts(s: dict) -> Dict[str, float]:
    """One record's parts in seconds A TASK (a counting stage has one
    task: its body's own)."""
    n = s["tasks_stamped"]
    stamped = sum(s[k] for k in STAMPED)
    return {"fetch": s["fetch_s"] / n, "compute": (s["body_s"] - stamped) / n,
            "put": s["put_s"] / n, "register": s["register_s"] / n,
            "body": s["body_s"] / n}


def bodies(stages: List[dict]) -> Optional[dict]:
    """Medians a task, sums by op and shares of the ``cluster`` records
    with stamped bodies; ``None`` when there is none (no cluster stage, or
    workers that stamp nothing: the parent)."""
    stages = [s for s in stages if s.get("executor", "cluster") == "cluster"]
    stamped = [s for s in stages if s.get("tasks_stamped", 0) > 0]
    if not stamped:
        return None
    per_task = [_task_parts(s) for s in stamped]
    by_op: Dict[str, dict] = {}
    keys = ("body_s", *STAMPED, "exec_s", "wall_s")
    for s in stages:
        op = by_op.setdefault(s["op"], {
            "stages": 0, "tasks": 0, "tasks_stamped": 0,
            **dict.fromkeys(keys, 0.0),
        })
        op["stages"] += 1
        op["tasks"] += sum(s.get("workers", {}).values())
        op["tasks_stamped"] += s.get("tasks_stamped", 0)
        for k in keys:
            op[k] += s.get(k, 0.0)
    for op in by_op.values():
        op["compute_s"] = op["body_s"] - sum(op[k] for k in STAMPED)
    total = {k: sum(op[k] for op in by_op.values())
             for k in ("body_s", "compute_s", *STAMPED)}
    tasks = sum(op["tasks"] for op in by_op.values())
    tasks_stamped = sum(op["tasks_stamped"] for op in by_op.values())
    return {
        "stages": len(stages),
        "stages_stamped": len(stamped),
        "tasks": tasks,
        "tasks_stamped": tasks_stamped,
        "tasks_without_stamps": tasks - tasks_stamped,
        "task_ms_median": {
            k: 1e3 * statistics.median(p[k] for p in per_task)
            for k in (*TASK_PARTS, "body")
        },
        **total,
        "share_of_body": {
            k: 100.0 * total[k + "_s"] / total["body_s"] for k in TASK_PARTS
        } if total["body_s"] > 0.0 else {},
        "by_op": sorted(
            ({"op": op, **v} for op, v in by_op.items()),
            key=lambda v: -v["body_s"],
        )[:12],
    }


# ------------------------------------------------------- the hand-off

def _named(host, name, lo, hi, skip_lines=()):
    return tr.union(tr.clip(
        [(s, s + d) for n, s, d, _, ln in host
         if n == name and ln not in skip_lines], lo, hi))


def _by_open_span(intervals, host, lo, hi, running) -> dict:
    """``intervals`` (of the waiting thread) in seconds by the span open on
    ANOTHER thread, ``UNDER_WAIT``'s order; under ``handoff/await_blocks``
    also whether a worker ran a body (``running``: ``place_workers``)."""
    waiting = {ln for n, _, _, _, ln in host if n == st.WAIT_SPAN}
    out, left = {}, list(intervals)
    for name in UNDER_WAIT:
        mine = pt._intersect(left, _named(host, name, lo, hi, waiting))
        out[name] = tr.total(mine) * 1e-9
        if name == AWAIT:
            out[name + " a_worker_ran_a_body"] = tr.total(
                pt._intersect(mine, running)) * 1e-9
        left = tr.subtract(left, mine)
    out["(none of these)"] = tr.total(left) * 1e-9
    return out


def handoff(profile: dict):
    """``(summary, report)`` of the ``handoff/*`` spans in ``profile``;
    empty where it has none (the parent), or no window."""
    host = profile.get("host", [])
    lo, hi = profile.get("window", (0.0, 0.0))
    if hi <= lo or not any(n == MATERIALIZE for n, *_ in host):
        return {}, {}
    jobs = sum(1 for n, s, d, _, _ in host
               if n == FIT_SPAN and lo <= s + d <= hi)
    spans = {name: _named(host, name, lo, hi)
             for name in (MATERIALIZE, AWAIT, FETCH, CONVERT)}
    own = tr.union(spans[FETCH] + spans[CONVERT])
    seconds = {name: tr.total(iv) * 1e-9 for name, iv in spans.items()}
    seconds["materialize under none of the three"] = seconds[MATERIALIZE] - (
        seconds[AWAIT] + seconds[FETCH] + seconds[CONVERT])
    busy = tr.union(tr.clip([tuple(iv) for iv in profile.get("busy", [])],
                            lo, hi))
    gaps = tr.subtract([(lo, hi)], busy)
    running = tr.union(tr.clip(
        [(a, b) for a, b, _ in st.place_workers(host)["bodies"]], lo, hi))
    waits = _named(host, st.WAIT_SPAN, lo, hi)
    idle_waits = pt._intersect(gaps, waits)
    report = {
        "jobs": jobs,
        "window_s": (hi - lo) * 1e-9,
        "seconds": seconds,
        "idle_s": tr.total(gaps) * 1e-9,
        "idle_under_s": {
            name: tr.total(pt._intersect(gaps, iv)) * 1e-9
            for name, iv in spans.items()
        },
        "ingest_wait": {
            "seconds": tr.total(waits) * 1e-9,
            "by_open_span_s": _by_open_span(waits, host, lo, hi, running),
            "idle_s": tr.total(idle_waits) * 1e-9,
            "idle_by_open_span_s": _by_open_span(
                idle_waits, host, lo, hi, running),
        },
    }
    summary = {"handoff_idle_share":
               100.0 * tr.total(pt._intersect(gaps, own)) / (hi - lo)}
    if jobs:
        summary["materialize_ms"] = tr.total(own) * 1e-6 / jobs
        summary["await_ms"] = tr.total(spans[AWAIT]) * 1e-6 / jobs
        report["per_job_ms"] = {
            name: 1e3 * v / jobs for name, v in seconds.items()}
    return summary, report


def reduce(profile: dict, stages: List[dict]):
    """``(summary, report)``: what the readers return, and the tables."""
    summary, report = {}, {}
    parts = bodies(stages)
    if parts:
        report["bodies"] = parts
        for k in TASK_PARTS:
            summary[f"task_{k}_ms"] = parts["task_ms_median"][k]
    handoff_summary, handoff_report = handoff(profile)
    summary.update(handoff_summary)
    if handoff_report:
        report["handoff"] = handoff_report
    return summary, report


# ------------------------------------------------------- for the readers

_CACHE: dict = {}


def summary(facts: dict) -> dict:
    """The summary of this run, ``{}`` where the program keeps no such
    record. Read once per process; the report goes to
    ``benchmark_out/<cell>.body_trace.json``."""
    cell = facts["cell"]
    out_dir = os.path.join(os.path.dirname(cell.bench_dir), "benchmark_out")
    paths = sorted(glob.glob(os.path.join(
        out_dir, "trace", "plugins", "profile", "*", "*.xplane.pb"
    )))
    key = (paths[-1], os.path.getmtime(paths[-1])) if paths else None
    key = (key, cell.name)
    if key not in _CACHE:
        # ``stage_trace`` reads this profile (once a process) and leaves
        # its small form: its read is not in this one's ``read_s``.
        st.summary(facts)
        t0 = time.perf_counter()
        profile = st.load_recorded(os.path.join(
            out_dir, cell.name + ".stage_trace.recorded.json.gz"
        ))[0] if paths else {}
        result, report = reduce(profile, st.load_stages())
        report["read_s"] = time.perf_counter() - t0
        with open(os.path.join(
            out_dir, cell.name + ".body_trace.json"
        ), "w") as f:
            json.dump(report, f, indent=1)
        _CACHE.clear()
        _CACHE[key] = result
    return _CACHE[key]
