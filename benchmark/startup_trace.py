"""A process's start-up read from inside (PR 49): what ``setup_s`` is made
of, by the program's own record of it.

Three sources, all the program's own and all read in this process, after
the window:

* ``recorder.retained()``: the first finished spans named ``cluster/start``,
  ``mesh/build``, ``train/init_state``, ``train/build_steps``,
  ``train/first_dispatch`` and ``train/fit``, kept beside the span ring
  (one epoch of a few hundred steps turns the ring over);
* the gauge ``train/ready_seconds`` with its absolute stamp
  (``profiling.ready_stamp()``): the end of the last epoch that paid for a
  program, counted from the first import of ``raydp_tpu``: where start-up
  ended by the program's own rule. The benchmark's window opens one in-call
  warm-up epoch (``fit_window``) or one ETL check and one chunk
  (``etl_fit_jobs``) later;
* ``profiling.compile_records()``: one record per program the process
  built, with the seconds of Python's trace, the lowering and the backend
  event, what the persistent cache said, and the span that was open.

What ended by the ready stamp is start-up (a ``train/fit`` open across it is
cut there). A program record is kept when it ended by the stamp under a
span of the program: the reference check's programs come after the window,
its ``predict_step`` under a ``train/first_dispatch`` of its own, the plain
reference under none.

A program without this record (the parent of PR 49) gives an empty summary:
every reader returns ``None`` and raises nothing.

``summary(facts)`` is what the ``layers/setup.*`` readers call; it also
writes ``benchmark_out/<cell>.startup.json`` (the report).
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional, Tuple

FIT, INIT, DISPATCH = "train/fit", "train/init_state", "train/first_dispatch"
COVERING = ("cluster/start", "mesh/build", FIT)
OWNERS = ("train/", "df/", "ingest/", "cluster/", "mesh/")
KINDS = ("trace_s", "lower_s", "backend_compile_s", "cache_load_s")


# ------------------------------------------------------------- loading

def load() -> Optional[dict]:
    """The program's start-up record as plain data, None where the program
    keeps none: ``origin`` and ``ready`` (``perf_counter`` readings),
    ``ready_s`` (the gauge), ``spans`` (``[name, start, end, attrs]``),
    ``records`` and the two counters of what was dropped."""
    try:
        import raydp_tpu
        from raydp_tpu.telemetry import recorder
        from raydp_tpu.utils import profiling

        origin = raydp_tpu.IMPORTED_AT
        retained = recorder.retained()
        records = profiling.compile_records()
        ready = profiling.ready_stamp()
    except (ImportError, AttributeError):
        return None
    counters = profiling.metrics.snapshot().get("counters", {})
    return {
        "origin": origin, "ready": ready,
        "ready_s": profiling.metrics.gauge_value("train/ready_seconds"),
        "spans": [[s.name, s.start_mono, s.end_mono, dict(s.attrs)]
                  for s in retained],
        "records": records,
        "records_dropped": counters.get("compile/records_dropped", 0.0),
        "spans_dropped": counters.get("spans/dropped", 0.0),
    }


# ------------------------------------------------------------ reducing

def union_s(intervals: List[Tuple[float, float]]) -> float:
    """Seconds covered by at least one of the intervals."""
    total, hi = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > hi:
            total += b - max(a, hi)
            hi = b
    return total


def reduce(loaded: Optional[dict]) -> Tuple[dict, dict]:
    """``(result, report)``: the eight readings and the report that names
    every phase and every program. Both empty where there is no record or
    no epoch has paid for a program yet."""
    if not loaded or loaded.get("ready") is None:
        return {}, {}
    origin, ready = loaded["origin"], loaded["ready"]
    ready_s = loaded["ready_s"]

    phases = []
    for name, start, end, attrs in sorted(
        loaded["spans"], key=lambda s: s[1]
    ):
        if start >= ready:
            continue
        phases.append({
            "name": name, "attrs": attrs, "start_s": start - origin,
            "seconds": min(end, ready) - start, "cut": end > ready,
        })

    def phase_s(name: str, keep=lambda attrs: True) -> float:
        return sum(p["seconds"] for p in phases
                   if p["name"] == name and keep(p["attrs"]))

    covered = union_s([
        (max(p["start_s"], 0.0), p["start_s"] + p["seconds"])
        for p in phases if p["name"] in COVERING
    ])

    kept, left = [], {"after_ready": [], "no_program_span": []}
    for r in loaded["records"]:
        owner = r.get("owner") or ""
        if r["t_end"] > ready:
            left["after_ready"].append(r)
        elif not owner.startswith(OWNERS):
            left["no_program_span"].append(r)
        else:
            kept.append(r)

    by_owner: Dict[str, dict] = {}
    for r in kept:
        loaded_from_cache = r["cache"] == "hit"
        row = by_owner.setdefault(
            r["owner"], dict.fromkeys(KINDS, 0.0) | {"programs": []}
        )
        row["trace_s"] += r["trace_s"]
        row["lower_s"] += r["lower_s"]
        row["cache_load_s" if loaded_from_cache
            else "backend_compile_s"] += r["backend_s"]
        row["programs"].append({
            "fun_name": r["fun_name"], "cache": r["cache"],
            "kind": "cache_load" if loaded_from_cache else "backend_compile",
            "trace_s": r["trace_s"], "lower_s": r["lower_s"],
            "backend_s": r["backend_s"], "retrieval_s": r["retrieval_s"],
            "ended_s": r["t_end"] - origin,
        })
    totals = {k: sum(row[k] for row in by_owner.values()) for k in KINDS}
    cache = {c: sum(1 for r in kept if r["cache"] == c)
             for c in ("hit", "miss", "uncached")}

    result = {
        "ready_s": ready_s,
        "init_state_s": phase_s(INIT),
        "step_program_s": phase_s(
            DISPATCH, lambda attrs: attrs.get("label") != "init_state"
        ),
        "trace_lower_s": totals["trace_s"] + totals["lower_s"],
        "backend_compile_s": totals["backend_compile_s"],
        "cache_load_s": totals["cache_load_s"],
        "cache_miss_programs": cache["miss"],
        "unaccounted_s": max(0.0, ready_s - covered),
    }
    report = {
        "ready_s": ready_s,
        "phases": phases,
        "covered_by_cluster_mesh_fit_s": covered,
        "by_owner": by_owner,
        "kinds_s": totals,
        "records_total_s": sum(
            r["trace_s"] + r["lower_s"] + r["backend_s"] for r in kept
        ),
        "cache": cache,
        "left_over": {
            "unaccounted_s": result["unaccounted_s"],
            **{why: {
                "programs": len(rs),
                "seconds": sum(
                    r["trace_s"] + r["lower_s"] + r["backend_s"] for r in rs
                ),
                "names": sorted({r["fun_name"] for r in rs}),
            } for why, rs in left.items()},
            "records_dropped": loaded["records_dropped"],
            "spans_dropped": loaded["spans_dropped"],
        },
        "metrics": result,
    }
    return result, report


# ------------------------------------------------------- for the readers

_CACHE: dict = {}


def summary(facts: dict) -> dict:
    """The readings of this run, ``{}`` where the program keeps no such
    record. Reduced once per run; the report goes to
    ``benchmark_out/<cell>.startup.json``."""
    cell = facts["cell"]
    loaded = load()
    key = (cell.name, loaded and loaded["ready"])
    if key not in _CACHE:
        t0 = time.perf_counter()
        result, report = reduce(loaded)
        if report:
            report["read_s"] = time.perf_counter() - t0
            out_dir = os.path.join(
                os.path.dirname(cell.bench_dir), "benchmark_out"
            )
            with open(os.path.join(
                out_dir, cell.name + ".startup.json"
            ), "w") as f:
                json.dump(report, f, indent=1, default=str)
        _CACHE.clear()
        _CACHE[key] = result
    return _CACHE[key]
