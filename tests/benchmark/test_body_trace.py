"""``benchmark/body_trace.py``: what a cluster task's body is made of, read
from the engine's records, the hand-off's spans read from the profile, and
the seven readers — on a hand-built profile and records, and through ONE
traced ``run_cell`` of the tiny ``etl_fit`` cell on the CPU."""
import importlib
import json
import os

import pytest

from bench_tree import BENCH_DIR

MS = 1e6  # ns
READERS = {
    "etl.job_task_fetch_ms": "task_fetch_ms",
    "etl.job_task_compute_ms": "task_compute_ms",
    "etl.job_task_put_ms": "task_put_ms",
    "etl.job_task_register_ms": "task_register_ms",
    "handoff.job_materialize_ms": "materialize_ms",
    "handoff.job_await_ms": "await_ms",
    "handoff.job_idle_share": "handoff_idle_share",
}
PR34 = (
    "etl.job_stage_fixed_ms", "etl.job_stage_exec_share",
    "etl.job_stage_transit_share", "etl.job_stage_load_share",
    "etl.job_stage_driver_share", "handoff.job_wait_worker_busy_share",
)
CELL = "dlrm_tiny.etl_fit"


@pytest.fixture(scope="module")
def bt(bench_modules):
    return importlib.import_module("body_trace")


def _stage(op, tasks, body, fetch, put, register, stamped=None, **extra):
    return {
        "op": op, "executor": "cluster", "wall_s": body + 0.002,
        "exec_s": body / tasks, "workers": {"w0": tasks},
        "body_s": body, "fetch_s": fetch, "put_s": put,
        "register_s": register,
        "tasks_stamped": tasks if stamped is None else stamped, **extra,
    }


def _synthetic():
    """Three counting stages of one task (bodies 4, 5 and 6 ms), a last
    stage of four tasks (40 ms of bodies) and a stage whose worker stamps
    nothing. One job: the fit runs 40-200 ms; the loader's thread awaits
    the blocks 50-90 ms, fetches 90-100, converts 100-120, all inside
    ``handoff/materialize`` 48-122; a second shard fetches 150-152 and
    converts 152-156 inside 150-156. The step loop waits 45-130 and
    148-160; a worker runs a body 60-80; the chip is busy 130-148 and
    160-200."""
    stages = [
        _stage("groupBy[a].agg", 1, 0.004, 0.0010, 0.0005, 0.0010),
        _stage("groupBy[b].agg", 1, 0.005, 0.0012, 0.0006, 0.0008),
        _stage("groupBy[c].agg", 1, 0.006, 0.0020, 0.0010, 0.0012),
        _stage("map", 4, 0.040, 0.004, 0.012, 0.004),
        _stage("old", 2, 0.0, 0.0, 0.0, 0.0, stamped=0),
        {"op": "local", "executor": "local", "wall_s": 0.5},
    ]
    host = [
        ["bench/window", 0.0, 210 * MS, {}, 1],
        ["train/fit", 40 * MS, 160 * MS, {}, 1],
        ["train/fit", -80 * MS, 60 * MS, {}, 1],  # closed before the window
        ["ingest/wait", 45 * MS, 85 * MS, {}, 1],
        ["ingest/wait", 148 * MS, 12 * MS, {}, 1],
        ["handoff/materialize", 48 * MS, 74 * MS, {}, 2],
        ["handoff/await_blocks", 50 * MS, 40 * MS, {}, 2],
        ["handoff/fetch", 90 * MS, 10 * MS, {}, 2],
        ["handoff/convert", 100 * MS, 20 * MS, {}, 2],
        ["ingest/stage_matrix", 122 * MS, 4 * MS, {}, 2],
        ["ingest/chunk", 126 * MS, 2 * MS, {}, 2],
        ["handoff/materialize", 150 * MS, 6 * MS, {}, 3],
        ["handoff/fetch", 150 * MS, 2 * MS, {}, 3],
        ["handoff/convert", 152 * MS, 4 * MS, {}, 3],
        ["stage/envelope", 55 * MS, 30 * MS,
         {"env": 1, "worker": "w0", "tasks": 1}, 4],
        ["stage/close", 86 * MS, 1 * MS, {
            "stage": 4, "op": "map", "envelopes": "1:w0:20000:0-20000",
        }, 4],
    ]
    profile = {"host": host, "window": [0.0, 210 * MS],
               "busy": [[130 * MS, 148 * MS], [160 * MS, 200 * MS]]}
    return profile, stages


def test_the_seven_numbers_by_hand(bt):
    profile, stages = _synthetic()
    summary, report = bt.reduce(profile, stages)
    # Medians over the four stamped records of the parts A TASK: the last
    # stage's are a quarter of its sums (1, 5.5, 3, 1 of 10 ms a task).
    assert summary["task_fetch_ms"] == pytest.approx((1.0 + 1.2) / 2)
    assert summary["task_put_ms"] == pytest.approx((0.6 + 1.0) / 2)
    assert summary["task_register_ms"] == pytest.approx((1.0 + 1.0) / 2)
    # compute: 1.5, 2.4, 1.8 and 5.0 ms.
    assert summary["task_compute_ms"] == pytest.approx((1.8 + 2.4) / 2)
    # One fit closes in the window: fetch 10 + 2, convert 20 + 4.
    assert summary["materialize_ms"] == pytest.approx(36.0)
    assert summary["await_ms"] == pytest.approx(40.0)
    # The chip is busy 130-148 and 160-200: idle under fetch or convert
    # 90-120 and 150-156, of a 210 ms window.
    assert summary["handoff_idle_share"] == pytest.approx(100 * 36 / 210)
    assert set(summary) == set(READERS.values())
    assert report["handoff"]["jobs"] == 1


def test_the_report_sums_by_op_and_counts_what_is_unstamped(bt):
    _, stages = _synthetic()
    parts = bt.bodies(stages)
    assert parts["stages"] == 5 and parts["stages_stamped"] == 4
    assert parts["tasks"] == 9 and parts["tasks_stamped"] == 7
    assert parts["tasks_without_stamps"] == 2
    assert parts["task_ms_median"]["body"] == pytest.approx(5.5)
    assert sum(parts["task_ms_median"][k] for k in bt.TASK_PARTS) == (
        pytest.approx(5.5, rel=0.1))
    assert [o["op"] for o in parts["by_op"]][0] == "map"
    last = parts["by_op"][0]
    assert last["tasks"] == 4 and last["body_s"] == pytest.approx(0.040)
    assert last["compute_s"] == pytest.approx(0.020)
    for op in parts["by_op"]:
        assert op["compute_s"] + sum(op[k] for k in bt.STAMPED) == (
            pytest.approx(op["body_s"]))
    assert sum(parts["share_of_body"].values()) == pytest.approx(100.0)
    assert parts["share_of_body"]["put"] == pytest.approx(
        100 * 0.0141 / 0.055)


def test_the_loaders_wait_is_split_by_the_span_open_elsewhere(bt):
    profile, _ = _synthetic()
    report = bt.handoff(profile)[1]
    assert report["seconds"] == pytest.approx({
        "handoff/materialize": 0.080, "handoff/await_blocks": 0.040,
        "handoff/fetch": 0.012, "handoff/convert": 0.024,
        "materialize under none of the three": 0.004,
    })
    assert report["per_job_ms"]["handoff/materialize"] == pytest.approx(80.0)
    wait = report["ingest_wait"]
    assert wait["seconds"] == pytest.approx(0.097)
    # 45-130: await 40 (a body ran 60-80: the envelope's 30 ms hold a
    # worker interval of 20, centred), fetch 10, convert 20, stage_matrix
    # 4, chunk 2, nothing 9; 148-160: fetch 2, convert 4, nothing 6.
    assert wait["by_open_span_s"] == pytest.approx({
        "handoff/await_blocks": 0.040,
        "handoff/await_blocks a_worker_ran_a_body": 0.020,
        "handoff/fetch": 0.012, "handoff/convert": 0.024,
        "ingest/stage_matrix": 0.004, "ingest/chunk": 0.002,
        "(none of these)": 0.015,
    })
    # Idle under the wait: all of 45-130, and 148-160 (the chip stops at
    # 148 and starts again at 160).
    assert wait["idle_s"] == pytest.approx(0.097)
    assert wait["idle_by_open_span_s"] == pytest.approx(
        wait["by_open_span_s"])
    assert report["idle_s"] == pytest.approx(0.152)
    assert report["idle_under_s"]["handoff/materialize"] == pytest.approx(
        0.080)


def test_a_span_on_the_waiting_thread_is_not_elsewhere(bt):
    profile, _ = _synthetic()
    host = [[n, s, d, st, 1 if n == "ingest/chunk" else ln]
            for n, s, d, st, ln in profile["host"]]
    wait = bt.handoff(dict(profile, host=host))[1]["ingest_wait"]
    assert wait["by_open_span_s"]["ingest/chunk"] == 0.0
    assert wait["by_open_span_s"]["(none of these)"] == pytest.approx(0.017)


def test_nothing_to_read_gives_nothing(bt):
    """No stamped record (the parent's workers), no ``handoff/*`` span (the
    parent's loader), no fit that closes in the window: no key, no error."""
    profile, stages = _synthetic()
    assert bt.reduce({}, []) == ({}, {})
    assert bt.bodies([]) is None
    old = [{k: v for k, v in s.items()
            if k not in (*bt.STAMPED, "body_s", "tasks_stamped")}
           for s in stages]
    assert bt.bodies(old) is None
    bare = dict(profile, host=[e for e in profile["host"]
                               if not e[0].startswith("handoff/")])
    assert bt.reduce(bare, old) == ({}, {})
    no_fit = dict(profile, host=[e for e in profile["host"]
                                 if e[0] != "train/fit"])
    summary, report = bt.handoff(no_fit)
    assert set(summary) == {"handoff_idle_share"}
    assert report["jobs"] == 0 and "per_job_ms" not in report


@pytest.mark.parametrize("metric", sorted(READERS))
def test_each_reader_returns_its_number(bench_modules, bt, monkeypatch,
                                        metric):
    """A reader is ``body_trace.summary`` under one key; a run with nothing
    to read (the parent's program) leaves the metric out (``None``)."""
    reader = bench_modules["harness"].load_module(
        os.path.join(BENCH_DIR, "layers", metric + ".py"))
    full = bt.reduce(*_synthetic())[0]
    monkeypatch.setattr(bt, "summary", lambda facts: full)
    assert reader.read({}) == pytest.approx(full[READERS[metric]])
    monkeypatch.setattr(bt, "summary", lambda facts: {})
    assert reader.read({}) is None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_the_benchmark_declares_the_metric_for_the_one_cell(real_bench,
                                                            metric):
    entry = next(m for m in real_bench["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == ["dlrm_kaggle.etl_fit"]
    assert entry["moves"] == "pipeline_rows_per_s"
    assert entry["better"] == "lower" and entry["source"] == "program_span"
    assert entry["layer"] == (
        "ETL engine" if metric.startswith("etl.") else "hand-off")
    assert entry["unit"] == ("%" if metric.endswith("_share") else "ms")


# -------------------------------------------------- through run_cell (CPU)

@pytest.fixture(scope="module")
def traced(bench_modules, tiny_tree):
    """ONE traced run of the tiny ``etl_fit`` cell; every test below reads
    it. The engine's store is the process's: records other test files left
    in it (a worker that stamps nothing, there on purpose) are not this
    run's."""
    from raydp_tpu.telemetry.progress import stage_store

    stage_store.clear()
    out = bench_modules["run"].run_cell(
        tiny_tree, CELL, seed=11, seconds=1.0, trace=1, platform="cpu")
    out_dir = os.path.join(tiny_tree, "benchmark_out")
    with open(os.path.join(out_dir, CELL + ".body_trace.json")) as f:
        out["body_trace"] = json.load(f)
    with open(os.path.join(out_dir, CELL + ".stage_trace.json")) as f:
        out["stage_trace"] = json.load(f)
    return out


@pytest.mark.parametrize("metric", sorted(READERS) + sorted(PR34))
def test_cpu_traced_run_reports_the_metric(traced, metric):
    """All seven, beside the six of PR 34, finite."""
    value = traced["line"]["metrics"][metric]["value"]
    assert value == value and abs(value) < float("inf")
    assert value >= 0.0
    assert traced["line"]["correct"] is True, traced["notes"]["checks"]


def test_cpu_traced_run_parts_sum_to_the_bodies(traced):
    parts = traced["body_trace"]["bodies"]
    assert parts["tasks_stamped"] == parts["tasks"] > 0
    assert parts["tasks_without_stamps"] == 0
    stamped = sum(parts[k] for k in ("fetch_s", "put_s", "register_s"))
    assert 0.0 < stamped < parts["body_s"]
    assert parts["compute_s"] + stamped == pytest.approx(parts["body_s"])
    for op in parts["by_op"]:
        assert op["compute_s"] >= 0.0
        assert op["compute_s"] + op["fetch_s"] + op["put_s"] + op[
            "register_s"] == pytest.approx(op["body_s"], abs=1e-5)
    assert sum(parts["share_of_body"].values()) == pytest.approx(100.0)
    # A record's four medians are the line's metrics.
    metrics = traced["line"]["metrics"]
    for k in ("fetch", "compute", "put", "register"):
        assert metrics[f"etl.job_task_{k}_ms"]["value"] == pytest.approx(
            parts["task_ms_median"][k])
    assert traced["body_trace"]["read_s"] >= 0.0


def test_cpu_traced_run_hand_off_fits_inside_materialize(traced):
    handoff = traced["body_trace"]["handoff"]
    assert handoff["jobs"] >= 1
    per_job = handoff["per_job_ms"]
    metrics = traced["line"]["metrics"]
    own = metrics["handoff.job_materialize_ms"]["value"]
    await_ms = metrics["handoff.job_await_ms"]["value"]
    assert own > 0.0
    assert own == pytest.approx(
        per_job["handoff/fetch"] + per_job["handoff/convert"])
    assert await_ms == pytest.approx(per_job["handoff/await_blocks"])
    assert own + await_ms <= per_job["handoff/materialize"] + 1e-6
    assert 0.0 <= metrics["handoff.job_idle_share"]["value"] <= 100.0
    wait = handoff["ingest_wait"]
    assert sum(
        v for k, v in wait["by_open_span_s"].items()
        if not k.endswith("a_worker_ran_a_body")
    ) == pytest.approx(wait["seconds"])


def test_cpu_traced_run_leaves_stage_trace_as_it_was(traced):
    """PR 34's report is written beside the new one and its partition still
    sums to the wall."""
    assert traced["stage_trace"]["partition"]["sum_over_wall"] == (
        pytest.approx(1.0, abs=0.01))
    assert traced["stage_trace"]["envelopes_placed"] > 0
