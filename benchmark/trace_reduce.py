"""From a ``jax.profiler`` trace to numbers: device busy and idle time, step
time on the device, collective time that nothing hides, the operations that
took most time, and the idle gaps by what the host was doing.

What a TPU v5 lite trace looks like (PR 22, first chip call): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
run of a jitted program, named ``jit_<fn>(<fingerprint>)``), ``XLA Ops``
(one event per HLO operation, named by its HLO text, ``%fusion.12 =
f32[...] fusion(...)``; they do not overlap) and ``Async XLA Ops`` (copies
and collectives in flight; they overlap the others). ``jax.profiler.
TraceAnnotation`` spans land on the thread's line of the ``/host:CPU`` plane
on the same clock. Times are nanoseconds from the start of the profile.

The benchmark marks its own host spans ``bench/<label>``; ``bench/window``
is the traced window every share is taken over.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench/"
WINDOW_SPAN = SPAN_PREFIX + "window"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)"
)


# ------------------------------------------------------------ loading

def load_xplane(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as plain lists:
    ``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    duration_ns], ...]}]}]}``. Host lines keep only the benchmark's spans."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        return {"planes": []}
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        chip = DEVICE_PLANE.match(plane.name)
        lines = []
        for line in plane.lines:
            if chip and int(chip.group(1)) > 0:
                # Of the other chips only the busy time is read: skip the
                # lines and the names (kilobytes of HLO text an event).
                if line.name != OPS_LINE:
                    continue
                events = [
                    ["", float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                ]
            else:
                events = [
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                    if chip or e.name.startswith(SPAN_PREFIX)
                ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_recorded(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def save_recorded(trace: dict, path: str, name_chars: int = 96) -> None:
    """Write ``trace`` in the form ``load_recorded`` reads, operation names
    cut to ``name_chars`` (the HLO text of one operation can run to
    kilobytes)."""
    slim = {"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": [
                [n[:name_chars], s, d] for n, s, d in ln["events"]
            ]} for ln in p["lines"]
        ]} for p in trace["planes"]
    ]}
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "wt") as f:
        json.dump(slim, f, separators=(",", ":"))


# ---------------------------------------------------- interval arithmetic

def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def total(merged: Sequence[Interval]) -> float:
    return sum(hi - lo for lo, hi in merged)


def clip(intervals: Sequence[Interval], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]):
    """The parts of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def overlap(a: Interval, merged: Sequence[Interval]) -> float:
    return total(clip(merged, a[0], a[1]))


# ------------------------------------------------------------- naming

def op_name(hlo_text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%").strip()


def op_group(hlo_text: str) -> str:
    """Operation kind and result shape, the key of the per-op totals:
    ``%fusion.12 = (f32[3072,768]{1,0:T(8,128)}, ...`` ->
    ``fusion f32[3072,768]``; 12 layers' copies of one fusion add up."""
    kind = re.sub(r"[.\d]+$", "", op_name(hlo_text))
    rest = hlo_text.split(" = ", 1)[1] if " = " in hlo_text else ""
    shape = re.match(r"\(*([a-z0-9]+\[[\d,]*\])", rest)
    return f"{kind} {shape.group(1)}" if shape else kind


def is_collective(hlo_text: str) -> bool:
    return bool(COLLECTIVE.match(op_name(hlo_text)))


# ------------------------------------------------------------ reduction

def _intervals(events):
    return [(s, s + d) for _, s, d in events]


def _lines(plane: dict) -> Dict[str, list]:
    return {ln["name"]: ln["events"] for ln in plane["lines"]}


def host_spans(trace: dict) -> Dict[str, List[Interval]]:
    """``label -> intervals`` of the benchmark's ``bench/<label>`` spans."""
    spans: Dict[str, List[Interval]] = {}
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for ln in plane["lines"]:
            for name, s, d in ln["events"]:
                if name.startswith(SPAN_PREFIX):
                    spans.setdefault(name[len(SPAN_PREFIX):], []).append(
                        (s, s + d)
                    )
    return spans


def reduce_trace(trace: dict, top: int = 10) -> dict:
    """Numbers of one traced window; ``{}`` when no operation ran on a
    device (no device plane, or an empty one).

    ``busy_s`` is the union of the ``XLA Ops`` intervals inside the window,
    averaged over the chips; everything else is chip 0's. ``steps`` counts
    the runs of the module that took most time (the train step), and
    ``step_device_ms`` is the busy time inside those runs, per run.
    ``exposed_collective_s`` is time in collective operations (either
    line) during which no other operation of ``XLA Ops`` runs."""
    devices = sorted(
        (int(DEVICE_PLANE.match(p["name"]).group(1)), _lines(p))
        for p in trace["planes"] if DEVICE_PLANE.match(p["name"])
    )
    devices = [(n, ln) for n, ln in devices if ln.get(OPS_LINE)]
    if not devices:
        return {}
    spans = host_spans(trace)
    if spans.get("window"):
        lo = min(a for a, _ in spans["window"])
        hi = max(b for _, b in spans["window"])
    else:
        every = [iv for _, ln in devices for iv in _intervals(ln[OPS_LINE])]
        lo, hi = min(a for a, _ in every), max(b for _, b in every)
    busy = [
        total(union(clip(_intervals(ln[OPS_LINE]), lo, hi)))
        for _, ln in devices
    ]
    lines = devices[0][1]
    ops = [e for e in lines[OPS_LINE] if lo < e[1] + e[2] and e[1] < hi]
    busy0 = union(clip(_intervals(ops), lo, hi))

    by_module: Dict[str, float] = {}
    for name, _, d in lines.get(MODULES_LINE, []):
        by_module[name] = by_module.get(name, 0.0) + d
    steps, step_ns = 0, 0.0
    if by_module:
        main = max(by_module, key=by_module.get)
        runs = clip(_intervals(
            [e for e in lines[MODULES_LINE] if e[0] == main]
        ), lo, hi)
        steps = len(runs)
        step_ns = sum(overlap(r, busy0) for r in runs)

    coll = union(clip(_intervals([
        e for ln in (OPS_LINE, ASYNC_LINE) for e in lines.get(ln, [])
        if is_collective(e[0])
    ]), lo, hi))
    others = union(clip(_intervals(
        [e for e in ops if not is_collective(e[0])]
    ), lo, hi))

    per_op: Dict[str, float] = {}
    for name, s, d in ops:
        key = op_group(name)
        per_op[key] = per_op.get(key, 0.0) + (min(s + d, hi) - max(s, lo))

    gaps = subtract([(lo, hi)], busy0)
    merged_spans = {
        k: union(v) for k, v in spans.items() if k != "window"
    }
    # Each idle gap goes to the host spans that cover it, the shortest-lived
    # label first: a span nested in another is the better description.
    inner_first = sorted(merged_spans, key=lambda k: total(merged_spans[k]))
    by_label: Dict[str, float] = {}
    for gap in gaps:
        unclaimed = [gap]
        for label in inner_first:
            mine = [
                iv for part in unclaimed
                for iv in clip(merged_spans[label], part[0], part[1])
            ]
            if mine:
                by_label[label] = by_label.get(label, 0.0) + total(mine)
                unclaimed = subtract(unclaimed, mine)
        if unclaimed:
            by_label["unattributed"] = (
                by_label.get("unattributed", 0.0) + total(unclaimed)
            )

    ns = 1e-9
    return {
        "chips": len(devices),
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy) / len(busy) * ns,
        "busy_s_per_chip": [b * ns for b in busy],
        "steps": steps,
        "step_device_ms": step_ns / steps * 1e-6 if steps else None,
        "collective_s": total(coll) * ns,
        "exposed_collective_s": total(subtract(coll, others)) * ns,
        "longest_gap_s": max((b - a for a, b in gaps), default=0.0) * ns,
        "device_ops": [
            [k, v * ns] for k, v in
            sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": [
            [k, v * ns] for k, v in
            sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
        ],
    }
