"""The traced window read from the PROGRAM's side: the train step split by
model part, and the host's time and the chip's idle gaps by the program's
own spans (``raydp_tpu.telemetry.span``, which are ``jax.profiler``
annotations since PR 24). ``trace_reduce`` keeps reading the benchmark's
``bench/...`` spans; this module ignores them except ``bench/window``.

Where an operation's scope is (PR 24, first chip call, TPU v5 lite, jax
0.9.0): NOT in the event. An ``XLA Ops`` event carries three stats
(``device_offset_ps``, ``device_duration_ps``, ``Time Scale Multiplier``)
and its name is the HLO text without its ``metadata={...}`` tail. The
``op_name`` (``jit(train_step)/jvp(SequenceClassifier)/encoder/block_0/
attn/...``) is the ``tf_op`` stat of the event's METADATA record, shared
by every run of the operation, which ``jax.profiler.ProfileData`` does
not expose. So
the ``.xplane.pb`` is read here as what it is, a protobuf (tsl's
``xplane.proto``), by a reader of the wire format: no name is copied per
event, and a 256-step DLRM profile reads in seconds, not a minute.

``summary(facts)`` is what the ``layers/`` readers call; ``load_profile``,
``reduce_profile``, ``save_recorded`` and ``load_recorded`` are its parts.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import struct
import time
from typing import Dict, List

import trace_reduce as tr

PROGRAM_SPAN = re.compile(r"^[a-z_]+(/[a-z_0-9]+)+$")
# The stats of an operation's metadata record that hold its scope path and
# its category.
SCOPE_STAT = "tf_op"
CATEGORY_STAT = "hlo_category"
NAME_CHARS = 96    # of an operation's HLO text, as trace_reduce records
STEP_SPAN = "train/step"
PUT_SPANS = ("infeed/put", "ingest/device_put")
ACTION_SPANS = ("df/action", "df/from_pandas")
STAGE_SPAN = "df/stage"
LONG_GAP_NS = 50e6
RECORDED_STEPS = 4


# ------------------------------------------------- the protobuf wire format

def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, i, end):
    """``(field number, value)`` of the message in ``buf[i:end]``: an int
    for a varint, ``(start, end)`` for a length-delimited field, the raw
    bytes of a fixed one."""
    while i < end:
        tag, i = _varint(buf, i)
        kind = tag & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind == 1:
            value, i = bytes(buf[i:i + 8]), i + 8
        elif kind == 5:
            value, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield tag >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _stat(buf, span, stat_names):
    """One ``XStat`` as ``(name, value)``; a reference is resolved to the
    string it names, bytes to their length."""
    key = value = None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            value = v - (1 << 64) if f == 4 and v >> 63 else v
        elif f == 5:
            value = _text(buf, v)
        elif f == 6:
            value = v[1] - v[0]
        elif f == 7:
            value = stat_names.get(v, str(v))
    return key, value


def _map_value(buf, span):
    """The value of one entry of a protobuf map field."""
    for f, v in _fields(buf, *span):
        if f == 2:
            return v
    return None


def _plane(buf, span, want_line):
    """One ``XPlane``: ``(name, lines, metadata)`` with ``lines`` as
    ``[(name, [[metadata id, start_ns, duration_ns], ...])]`` and
    ``metadata`` as ``{id: (name, {stat: value})}``."""
    name, line_spans, meta_spans, stat_names = "", [], [], {}
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            line_spans.append(v)
        elif f == 4:
            meta_spans.append(v)
        elif f == 5:
            entry = dict(_fields(buf, *_map_value(buf, v)))
            stat_names[entry.get(1)] = _text(buf, entry[2])
    lines = []
    for lspan in line_spans:
        lname, t0, event_spans = "", 0, []
        for f, v in _fields(buf, *lspan):
            if f == 2:
                lname = _text(buf, v)
            elif f == 3:
                t0 = v
            elif f == 4:
                event_spans.append(v)
        if not want_line(name, lname):
            continue
        events = []
        for espan in event_spans:
            mid = off = dur = 0
            for f, v in _fields(buf, *espan):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = v
                elif f == 3:
                    dur = v
            events.append([mid, t0 + off / 1000.0, dur / 1000.0])
        lines.append((lname, events))
    metadata = {}
    if lines:
        for mspan in meta_spans:
            mid, mname, stats = None, "", {}
            for f, v in _fields(buf, *_map_value(buf, mspan)):
                if f == 1:
                    mid = v
                elif f == 2:
                    mname = _text(buf, v)
                elif f == 5:
                    key, value = _stat(buf, v, stat_names)
                    stats[key] = value
            metadata[mid] = (mname, stats)
    return name, lines, metadata


# ------------------------------------------------------------- loading

def load_profile(path: str) -> dict:
    """An ``.xplane.pb`` as plain lists. Device planes: chip 0's ``XLA
    Modules`` and ``XLA Ops`` lines; host planes: the program's spans and
    ``bench/window``, a line per thread. An event is ``[op, start_ns,
    duration_ns]`` with ``op`` an index into ``"ops"``, whose entries are
    ``[name cut to NAME_CHARS, scope path, category]``."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())

    def want_line(plane, line):
        chip = tr.DEVICE_PLANE.match(plane)
        if chip:
            return chip.group(1) == "0" and line in (
                tr.OPS_LINE, tr.MODULES_LINE
            )
        return plane.startswith("/host:")

    ops: List[list] = []
    index: Dict[tuple, int] = {}
    planes = []
    for f, span in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, lines, metadata = _plane(buf, span, want_line)
        device = bool(tr.DEVICE_PLANE.match(name))
        out = []
        for lname, events in lines:
            kept = []
            for mid, start, dur in events:
                mname, stats = metadata.get(mid, ("", {}))
                if not device and mname != tr.WINDOW_SPAN and (
                    mname.startswith(tr.SPAN_PREFIX)
                    or not PROGRAM_SPAN.match(mname)
                ):
                    continue
                key = (name, mid)
                if key not in index:
                    index[key] = len(ops)
                    ops.append([mname[:NAME_CHARS],
                                str(stats.get(SCOPE_STAT, "")),
                                str(stats.get(CATEGORY_STAT, ""))])
                kept.append([index[key], start, dur])
            if kept:
                out.append({"name": lname, "events": kept})
        if out:
            planes.append({"name": name, "lines": out})
    return {"ops": ops, "planes": planes}


def load_recorded(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def save_recorded(profile: dict, path: str,
                  steps: int = RECORDED_STEPS) -> None:
    """``profile`` cut to the first ``steps`` runs of its step program
    (their span becomes the window), in the form ``load_recorded`` reads."""
    lo, hi = _window(profile)
    runs = _step_runs(profile, lo, hi)[:steps]
    if runs:
        lo, hi = runs[0][0], runs[-1][1]
    used: Dict[int, int] = {}
    planes = []
    for plane in profile["planes"]:
        lines = []
        for ln in plane["lines"]:
            events = [
                [used.setdefault(op, len(used)), s, d]
                for op, s, d in ln["events"]
                if s < hi and s + d > lo
                and profile["ops"][op][0] != tr.WINDOW_SPAN
            ]
            if events:
                lines.append({"name": ln["name"], "events": events})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    ops = [profile["ops"][op] for op in sorted(used, key=used.get)]
    ops.append([tr.WINDOW_SPAN, "", ""])
    planes.append({"name": "/host:window", "lines": [
        {"name": "window", "events": [[len(ops) - 1, lo, hi - lo]]}
    ]})
    with gzip.open(path, "wt") as f:
        json.dump({"ops": ops, "planes": planes}, f, separators=(",", ":"))


# ----------------------------------------------------------- reduction

def _device_lines(profile: dict) -> Dict[str, list]:
    for plane in profile["planes"]:
        if tr.DEVICE_PLANE.match(plane["name"]):
            return {ln["name"]: ln["events"] for ln in plane["lines"]}
    return {}


def _host_lines(profile: dict) -> List[list]:
    return [
        ln["events"] for plane in profile["planes"]
        if not tr.DEVICE_PLANE.match(plane["name"])
        for ln in plane["lines"]
    ]


def _window(profile: dict):
    """``bench/window``; without one (a capture made outside the
    benchmark) the extent of chip 0's operations, else of the spans."""
    names = profile["ops"]
    marks = [
        (s, s + d) for events in _host_lines(profile)
        for op, s, d in events if names[op][0] == tr.WINDOW_SPAN
    ]
    if not marks:
        events = _device_lines(profile).get(tr.OPS_LINE) or [
            e for events in _host_lines(profile) for e in events
        ]
        marks = [(s, s + d) for _, s, d in events]
    if not marks:
        return 0.0, 0.0
    return min(a for a, _ in marks), max(b for _, b in marks)


def _step_runs(profile: dict, lo: float, hi: float):
    """The runs inside the window of the module that took most time, as
    ``trace_reduce.reduce_trace`` picks the step program."""
    modules = _device_lines(profile).get(tr.MODULES_LINE, [])
    by_module: Dict[int, float] = {}
    for op, _, d in modules:
        by_module[op] = by_module.get(op, 0.0) + d
    if not by_module:
        return []
    main = max(by_module, key=by_module.get)
    return tr.clip(
        sorted((s, s + d) for op, s, d in modules if op == main), lo, hi
    )


def _self_times(events):
    """``[(op, ns)]``: each instant of the union of ``events`` (sorted by
    start) given to the innermost event open at it, so nested or
    overlapping events still partition the busy time."""
    out = []
    stack: List[list] = []   # [end, op]
    cursor = 0.0

    def credit(until):
        nonlocal cursor
        if stack and until > cursor:
            out.append((stack[-1][1], until - cursor))
        cursor = max(cursor, until)

    for op, start, dur in events:
        while stack and stack[-1][0] <= start:
            credit(stack[-1][0])
            stack.pop()
        credit(start)
        cursor = max(cursor, start)
        stack.append([start + dur, op])
    while stack:
        credit(stack[-1][0])
        stack.pop()
    return out


def _intersect(a, b):
    """The parts of merged ``a`` that merged ``b`` covers, in one pass (a
    DLRM epoch leaves a quarter of a million gaps between operations)."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            out.append((max(lo, b[k][0]), min(hi, b[k][1])))
            k += 1
    return out


def part_of(scope: str, rules) -> str:
    for pattern, part in rules:
        if pattern.search(scope):
            return part
    return "rest"


def compile_rules(rules) -> list:
    return [(re.compile(pattern), part) for pattern, part in rules]


def reduce_profile(profile: dict, rules):
    """``(summary, report)`` of one traced window. ``summary`` holds what
    the readers return; ``report`` the tables a planner reads."""
    names = profile["ops"]
    lo, hi = _window(profile)
    window = hi - lo
    summary: dict = {"window_s": window * 1e-9}
    report: dict = {"window_s": window * 1e-9}
    if window <= 0:
        return summary, report
    rules = compile_rules(rules)

    # ---- host: the program's spans, by thread
    threads = []
    for events in _host_lines(profile):
        spans: Dict[str, list] = {}
        for op, s, d in events:
            name = names[op][0]
            if name != tr.WINDOW_SPAN and s < hi and s + d > lo:
                spans.setdefault(name, []).append(
                    (max(s, lo), min(s + d, hi))
                )
        if spans:
            threads.append(spans)
    have_spans = bool(threads)
    loop = max(
        threads, key=lambda t: (len(t.get(STEP_SPAN, [])), len(t)),
        default={},
    )

    def on_loop(*span_names) -> float:
        return tr.total(tr.union(
            [iv for n in span_names for iv in loop.get(n, [])]
        ))

    if have_spans:
        report["step_loop_thread_s"] = {
            n: tr.total(tr.union(iv)) * 1e-9 for n, iv in sorted(loop.items())
        }
        summary["put_share"] = 100.0 * on_loop(*PUT_SPANS) / window
        summary["dispatch_share"] = 100.0 * on_loop(STEP_SPAN) / window
        actions = tr.union(
            [iv for n in ACTION_SPANS for iv in loop.get(n, [])]
        )
        if actions:
            stages = tr.union(loop.get(STAGE_SPAN, []))
            summary["driver_share"] = (
                100.0 * tr.total(tr.subtract(actions, stages)) / window
            )

    # ---- device: chip 0
    lines = _device_lines(profile)
    ops = sorted(
        (e for e in lines.get(tr.OPS_LINE, []) if e[1] < hi and e[1] + e[2] > lo),
        key=lambda e: (e[1], -e[2]),
    )
    if not ops:
        return summary, report
    busy = tr.union(tr.clip([(s, s + d) for _, s, d in ops], lo, hi))

    runs = _step_runs(profile, lo, hi)
    starts = [e[1] for e in ops]
    by_part: Dict[str, float] = {}
    by_scope: Dict[str, float] = {}
    by_category: Dict[str, float] = {}
    part_cache: Dict[int, str] = {}
    for a, b in runs:
        # Operations of this run: they start inside it (a run's first
        # operation starts with the run).
        i, j = bisect.bisect_left(starts, a), bisect.bisect_left(starts, b)
        inside = [[op, s, min(s + d, b) - s] for op, s, d in ops[i:j]]
        for op, ns in _self_times(inside):
            _, scope, category = names[op]
            part = part_cache.get(op)
            if part is None:
                part = part_cache[op] = part_of(scope, rules)
            by_part[part] = by_part.get(part, 0.0) + ns
            key = part + " " + _scope_path(scope)
            by_scope[key] = by_scope.get(key, 0.0) + ns
            if category:
                by_category[category] = by_category.get(category, 0.0) + ns
    if runs:
        n = len(runs)
        parts = {part for _, part in rules} | {"rest"}
        summary["steps"] = n
        summary["parts_ms"] = {
            p: by_part.get(p, 0.0) / n * 1e-6 for p in sorted(parts)
        }
        summary["step_device_ms"] = sum(by_part.values()) / n * 1e-6
        report["parts_ms"] = summary["parts_ms"]
        report["step_device_ms"] = summary["step_device_ms"]
        report["steps"] = n
        ranked = sorted(by_scope.items(), key=lambda kv: -kv[1])
        report["scopes_ms"] = [
            [k, v / n * 1e-6] for k, v in ranked
            if not k.startswith("rest ")
        ][:60]
        report["rest_scopes_ms"] = [
            [k[5:], v / n * 1e-6] for k, v in ranked if k.startswith("rest ")
        ][:20]
        if by_category:
            report["hlo_category_ms"] = {
                k: v / n * 1e-6 for k, v in
                sorted(by_category.items(), key=lambda kv: -kv[1])
            }

    # ---- chip 0's idle gaps by the program's spans (any thread)
    gaps = tr.subtract([(lo, hi)], busy)
    merged: Dict[str, list] = {}
    for spans in threads:
        for n, iv in spans.items():
            merged.setdefault(n, []).extend(iv)
    merged = {n: tr.union(iv) for n, iv in merged.items()}
    # The shortest-lived name first, as reduce_trace does for the
    # benchmark's spans: a span nested in another describes a gap better.
    inner_first = sorted(merged, key=lambda n: tr.total(merged[n]))
    idle: Dict[str, float] = {}
    unclaimed = gaps
    for n in inner_first:
        mine = _intersect(unclaimed, merged[n])
        if mine:
            idle[n] = tr.total(mine)
            unclaimed = tr.subtract(unclaimed, mine)
    if unclaimed:
        idle["unattributed"] = tr.total(unclaimed)
    long_gaps = [{
        "at_s": (a - lo) * 1e-9, "seconds": (b - a) * 1e-9,
        "open_spans": sorted(n for n in merged if tr.clip(merged[n], a, b)),
    } for a, b in gaps if b - a > LONG_GAP_NS]
    report["idle_s"] = tr.total(gaps) * 1e-9
    report["idle_gaps_s"] = [
        [n, v * 1e-9] for n, v in sorted(idle.items(), key=lambda kv: -kv[1])
    ]
    report["gaps_over_50_ms"] = long_gaps
    if have_spans:
        summary["idle_unattributed_share"] = (
            100.0 * idle.get("unattributed", 0.0) / window
        )
    return summary, report


def _scope_path(scope: str) -> str:
    """A scope without its primitive and with block numbers folded:
    ``jit(train_step)/jvp(M)/encoder/block_3/attn/qkv/dot_general`` ->
    ``jvp(M)/encoder/block_N/attn/qkv``."""
    parts = scope.split("/")
    if parts and parts[0].startswith("jit("):
        parts = parts[1:]
    if len(parts) > 1:
        parts = parts[:-1]
    return re.sub(r"_\d+\b", "_N", "/".join(parts))


# ------------------------------------------------------- for the readers

_CACHE: dict = {}


def summary(facts: dict) -> dict:
    """The summary of this run's profile, ``{}`` when there is none. The
    profile is the one ``harness.Profiler`` wrote under
    ``<root>/benchmark_out/trace``; it is read once per process. Also
    writes the report to ``benchmark_out/<cell>.program_trace.json``."""
    cell = facts["cell"]
    out_dir = os.path.join(os.path.dirname(cell.bench_dir), "benchmark_out")
    paths = sorted(glob.glob(os.path.join(
        out_dir, "trace", "plugins", "profile", "*", "*.xplane.pb"
    )))
    if not paths:
        return {}
    key = (paths[-1], os.path.getmtime(paths[-1]), cell.name)
    if key not in _CACHE:
        rules_path = os.path.join(
            cell.bench_dir, "parts", cell.sizes["builder"] + ".json"
        )
        with open(rules_path) as f:
            rules = json.load(f)
        t0 = time.perf_counter()
        result, report = reduce_profile(load_profile(paths[-1]), rules)
        report["read_s"] = time.perf_counter() - t0
        with open(os.path.join(
            out_dir, cell.name + ".program_trace.json"
        ), "w") as f:
            json.dump(report, f, indent=1)
        _CACHE.clear()
        _CACHE[key] = result
    return _CACHE[key]


def part_ms(facts: dict, part: str):
    return summary(facts).get("parts_ms", {}).get(part)
