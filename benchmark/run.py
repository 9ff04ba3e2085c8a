"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` in this process on the accelerator of
this machine and prints, as the last line of stdout, one JSON object with
the keys ``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(and ``breakdown`` in a traced run). With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.
Earlier stdout lines carry what is worth keeping and is not judged; the
same goes to ``benchmark_out/<cell>.json`` in the checkout.

No TPU, or fewer chips than the cell asks for: exit code 3 and no result.
There is no CPU mode; the tests reach ``run_cell`` with ``platform="cpu"``
on a temporary tree of tiny sizes.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [p for p in (HERE, os.path.dirname(HERE)) if p not in sys.path]

import harness  # noqa: E402
import trace_reduce  # noqa: E402


class Context:
    """What a job gets: the cell, the run's arguments and the shared
    instruments. A job calls ``window_opens()`` when set-up is over."""

    def __init__(self, cell, seed, seconds, trace, platform, out_dir,
                 t_process):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.platform, self.out_dir = platform, out_dir
        self.harness, self.trace_reduce = harness, trace_reduce
        self.profiler = harness.Profiler(bool(trace), out_dir)
        self.compiles = harness.CompileCounter()
        self.notes: dict = {}
        self.setup_s = None
        self._t_process = t_process
        self._exits = []

    def start_cluster(self, workers: int) -> None:
        """The ETL cluster's CPU workers, stopped when the run ends. Call it
        before ``require_devices``: the workers are forked off a parent
        that holds no JAX backend (they run with ``JAX_PLATFORMS=cpu``)."""
        import raydp_tpu

        t0 = time.perf_counter()
        raydp_tpu.init(
            app_name="benchmark-" + self.cell.name.replace(".", "-"),
            num_workers=workers,
        )
        self.on_exit(raydp_tpu.stop)
        self.note("cluster_s", time.perf_counter() - t0)

    def require_devices(self):
        t0 = time.perf_counter()
        devices = harness.require_devices(self.cell.chips, self.platform)
        self.note("tpu_client_s", time.perf_counter() - t0)
        return devices

    def window_opens(self) -> None:
        self.setup_s = time.perf_counter() - self._t_process
        self.note("compiles_in_setup", self.compiles.summary())

    def note(self, key: str, value) -> None:
        self.notes[key] = value
        harness.log(f"{key}: {value}")

    def on_exit(self, fn) -> None:
        self._exits.append(fn)

    def close(self) -> None:
        while self._exits:
            self._exits.pop()()


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: int, platform: str = "tpu", t_process=None,
             flip_reference: bool = False) -> dict:
    """Run one cell; returns ``{"line": <the last line's object>, "notes":
    ...}``. ``flip_reference`` makes the reference check disagree (to show
    that ``correct`` can be false)."""
    cell = harness.load_cell(root, workload)
    out_dir = os.path.join(root, "benchmark_out")
    os.makedirs(out_dir, exist_ok=True)
    ctx = Context(cell, seed, seconds, trace, platform, out_dir,
                  time.perf_counter() if t_process is None else t_process)
    job = cell.part("jobs", cell.workload["job"])
    try:
        result = job.run(ctx)
        devices = result["facts"]["devices"]
        peak = harness.memory_peak_bytes(devices)
        ref_ok, ref_detail = harness.check_reference(
            cell, result["estimator"], seed, flip=flip_reference
        )
    finally:
        ctx.close()
    ctx.note("reference_check", ref_detail)

    checks = {
        "logits_match_reference": ref_ok,
        "losses_finite": result["failed"] == 0 and result["attempted"] > 0,
        "no_compile_in_window": result["compiles_in_window"] == 0,
        "device_is_the_cells": devices[0].platform == platform
        and len(devices) >= cell.chips,
        **result.get("checks", {}),
    }
    facts = result["facts"]
    facts["memory_peak_bytes"] = peak
    facts["peaks"] = harness.peaks_for(cell, devices[0].device_kind) if (
        platform == "tpu") else None
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    values = dict(result["end_to_end"], setup_s=ctx.setup_s)
    if trace:
        reduced = ctx.profiler.reduced
        metrics = harness.read_layers(cell, facts)
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
    else:
        metrics = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end()
        }
    line = {
        "correct": all(checks.values()),
        "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": metrics,
        "device": device,
    }
    if trace and ctx.profiler.reduced:
        line["breakdown"] = {
            "device_ops": ctx.profiler.reduced["device_ops"],
            "idle_gaps": ctx.profiler.reduced["idle_gaps"],
        }
    notes = {
        "workload": workload, "seed": seed, "seconds": seconds,
        "trace": trace, "checks": checks, "end_to_end": values,
        **ctx.notes, **result["notes"],
    }
    if trace:
        notes["trace_reduced"] = ctx.profiler.reduced
        notes["trace_wall_s"] = ctx.profiler.wall_s
        notes["trace_cost_s"] = ctx.profiler.cost_s
        bound = facts.get("roofline_bound_by")
        if bound:
            notes["roofline_bound_by"] = bound
        if ctx.profiler.trace:
            trace_reduce.save_recorded(
                ctx.profiler.trace,
                os.path.join(out_dir, workload + ".trace.json.gz"),
            )
    with open(os.path.join(out_dir, f"{workload}.trace{trace}.json"), "w") as f:
        json.dump({"line": line, "notes": notes}, f, indent=1, default=str)
    return {"line": line, "notes": notes}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if not (args.seconds > 0 and math.isfinite(args.seconds)):
        parser.error("--seconds must be positive")
    out = run_cell(
        os.path.dirname(HERE), args.workload, args.seed, args.seconds,
        args.trace, t_process=T_PROCESS,
    )
    print(json.dumps({"notes": out["notes"]}, default=str), flush=True)
    print(json.dumps(out["line"]), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    sys.exit(code)
