"""``benchmark/stage_trace.py``: a cluster stage's partition read from the
engine's records, the workers' bodies placed on the profile's clock, and
the six readers — on a synthetic profile, on the slice recorded on the
chip, and through ``run_cell`` on the CPU."""
import importlib
import json
import os

import pytest

from bench_tree import BENCH_DIR

MS = 1e6  # ns
SHARES = ("exec_share", "transit_share", "load_share", "driver_share")
READERS = {
    "etl.job_stage_fixed_ms": "stage_fixed_ms",
    "etl.job_stage_exec_share": "exec_share",
    "etl.job_stage_transit_share": "transit_share",
    "etl.job_stage_load_share": "load_share",
    "etl.job_stage_driver_share": "driver_share",
    "handoff.job_wait_worker_busy_share": "wait_worker_busy_share",
}


@pytest.fixture(scope="module")
def st(bench_modules):
    return importlib.import_module("stage_trace")


def _stage(op, wall, submit, transit, load, exec_, **extra):
    return {
        "op": op, "executor": "cluster", "wall_s": wall, "submit_s": submit,
        "transit_s": transit, "load_s": load, "exec_s": exec_,
        "driver_s": wall - submit - transit - load - exec_,
        "queue_s": transit + load, **extra,
    }


def _synthetic():
    """Two stages. Stage 1 (a counting stage): two envelopes of 10 ms,
    the workers hold them 6 ms, bodies 1-5 ms after ``recv``. Stage 2
    (the lazily run last one): one envelope of 40 ms, the worker holds it
    30 ms, two bodies. The loader waits from 60 to 100 ms; the chip is
    busy 100-120 ms only."""
    host = [
        ["bench/window", 0.0, 130 * MS, {}, 1],
        ["df/stage", 10 * MS, 14 * MS, {}, 1],
        ["stage/envelope", 11 * MS, 10 * MS,
         {"env": 1, "worker": "w0", "tasks": 1}, 2],
        ["stage/envelope", 11 * MS, 10 * MS,
         {"env": 2, "worker": "w1", "tasks": 1}, 3],
        ["stage/close", 22 * MS, 1 * MS, {
            "stage": 1, "op": "groupBy[c].agg",
            "envelopes": "1:w0:6000:1000-5000;2:w1:6000:1000-5000",
        }, 1],
        ["stage/envelope", 55 * MS, 40 * MS,
         {"env": 3, "worker": "w0", "tasks": 2}, 2],
        ["stage/close", 95 * MS, 1 * MS, {
            "stage": 2, "op": "map",
            "envelopes": "3:w0:30000:0-10000+15000-30000;9:w1:5:0-5",
        }, 2],
        ["bench/handoff", 50 * MS, 55 * MS, {}, 1],
        ["ingest/wait", 60 * MS, 40 * MS, {}, 4],
        ["train/step", 100 * MS, 2 * MS, {}, 4],
    ]
    stages = [
        _stage("groupBy[c].agg", 0.014, 0.001, 0.004, 0.002, 0.004),
        _stage("map", 0.050, 0.002, 0.010, 0.005, 0.025, upstream_s=0.003),
    ]
    profile = {"host": host, "busy": [[100 * MS, 120 * MS]],
               "window": [0.0, 130 * MS]}
    return profile, stages


def test_partition_shares_sum_to_100_and_fixed_cost_is_the_median(st):
    _, stages = _synthetic()
    parts = st.partition(stages)
    assert parts["stages"] == 2
    assert sum(parts[k] for k in SHARES) == pytest.approx(100.0, abs=1e-9)
    assert parts["exec_share"] == pytest.approx(100 * 0.029 / 0.064)
    assert parts["transit_share"] == pytest.approx(100 * 0.014 / 0.064)
    # wall - exec: 10 ms and 25 ms; the median of two is their mean.
    assert parts["stage_fixed_ms"] == pytest.approx(17.5)
    assert parts["stage_exec_ms_median"] == pytest.approx(14.5)
    assert parts["sum_over_wall"] == pytest.approx(1.0)
    assert [o["op"] for o in parts["by_op"]] == ["map", "groupBy[c].agg"]
    assert parts["by_op"][0]["stages"] == 1


def test_workers_are_placed_in_the_middle_of_their_envelopes(st):
    profile, _ = _synthetic()
    placed = st.place_workers(profile["host"])
    # Envelope 9 has no span in the profile (it left before the window).
    assert placed["placed"] == 3 and placed["missing"] == 1
    by_env = sorted(placed["intervals"])
    # 10 ms envelopes, 6 ms inside the worker: 2 ms either side.
    assert by_env[0][:2] == (13 * MS, 19 * MS)
    # 40 ms envelope, 30 ms inside the worker: 5 ms either side.
    assert by_env[2][:2] == (60 * MS, 90 * MS)
    assert placed["placement_error_ms_max"] == pytest.approx(5.0)
    assert sorted(b[:2] for b in placed["bodies"]) == [
        (14 * MS, 18 * MS), (14 * MS, 18 * MS),
        (60 * MS, 70 * MS), (75 * MS, 90 * MS),
    ]


def test_wait_and_idle_are_split_by_whether_a_worker_ran_a_body(st):
    profile, stages = _synthetic()
    summary, report = st.reduce(profile, stages)
    # ingest/wait 60-100 ms; bodies 60-70 and 75-90: 25 of 40 ms.
    assert summary["wait_worker_busy_share"] == pytest.approx(62.5)
    wait = report["ingest_wait"]
    assert wait["a_worker_ran_a_body_s"] == pytest.approx(0.025)
    assert wait["no_worker_ran_a_body_s"] == pytest.approx(0.015)
    assert wait["a_worker_held_an_envelope_s"] == pytest.approx(0.030)
    # The 15 ms of wait with no body running: 10 under the envelope (on
    # another thread), 1 under the stage's close, 4 under no span.
    assert dict(wait["no_body_by_open_span_s"]) == pytest.approx(
        {"stage/envelope": 0.010, "stage/close": 0.001, "(no span)": 0.004})
    # Chip 0 idles outside 100-120 ms: under df/stage 14 ms, of which a
    # body ran 4; under ingest/wait all 40 ms.
    under = report["idle_under"]
    assert under["df/stage"]["seconds"] == pytest.approx(0.014)
    assert under["df/stage"]["a_worker_ran_a_body_s"] == pytest.approx(0.004)
    assert under["ingest/wait"]["seconds"] == pytest.approx(0.040)
    assert report["body_s_by_worker"] == pytest.approx(
        {"w0": 0.029, "w1": 0.004})
    # No span of the program is open 0-10, 24-55 (the envelope opens at
    # 55) and 120-130 ms; each gap names its neighbours, and the
    # benchmark's spans open at its middle.
    bare = report["unattributed"]
    assert bare["seconds"] == pytest.approx(0.051)
    pairs = {(g["closed_before"], g["opens_after"]): g
             for g in bare["by_neighbours"]}
    assert pairs[("df/stage", "stage/envelope")]["seconds"] == pytest.approx(
        0.031)
    assert pairs[(None, "df/stage")]["bench_spans_open"] == ""
    assert pairs[("train/step", None)]["gaps"] == 1
    assert set(SHARES) <= set(summary)


def test_nothing_to_read_gives_nothing(st):
    """No cluster stage, a program that does not partition its stages (the
    parent of PR 34), no envelope in the profile: no key, and no error."""
    profile, stages = _synthetic()
    assert st.reduce({}, []) == ({}, {})
    assert st.partition([]) is None
    assert st.partition([dict(s, executor="local") for s in stages]) is None
    old = [{k: v for k, v in s.items() if k != "exec_s"} for s in stages]
    assert st.partition(old) is None
    bare = {"host": [e for e in profile["host"]
                     if not e[0].startswith("stage/")],
            "busy": profile["busy"], "window": profile["window"]}
    summary, report = st.reduce(bare, old)
    assert summary == {} and report["envelopes_placed"] == 0
    assert st.parse_envelopes("") == [] and st.parse_envelopes(None) == []


def test_reader_parses_what_the_engine_writes(st):
    """The engine formats the ``envelopes`` attr, the benchmark parses it."""
    from raydp_tpu.dataframe import executor as E

    text = E.format_envelopes([
        {"env": 4, "worker": "w3", "recv": 2.0, "ret": 2.25,
         "bodies": [(2.01, 2.02), (2.1, 2.2)]},
    ])
    assert st.parse_envelopes(text) == [
        {"env": 4, "worker": "w3", "worker_us": 250000,
         "bodies_us": [(10000, 20000), (100000, 200000)]},
    ]


def test_centred_placement_agrees_with_the_shared_clock(st, monkeypatch):
    """The reader puts an envelope's worker interval in the middle of its
    ``stage/envelope`` span and needs no clock in common. Here driver and
    workers share a host, so ``perf_counter`` is one clock for both and
    the true ``recv`` is known: the placement is off by at most half the
    envelope's transit, which is what ``placement_error_ms_max`` bounds."""
    import numpy as np
    import pandas as pd

    import raydp_tpu
    import raydp_tpu.dataframe as rdf
    from raydp_tpu.dataframe import executor as E
    from raydp_tpu.telemetry import recorder

    monkeypatch.setenv("RAYDP_TPU_STREAMING", "0")
    seen = {}
    task_meta = E._StageRecorder._task_meta

    def spy(self, rnd, index, worker_id, exec_s, stamps=None):
        seen[stamps["env"]] = stamps
        return task_meta(self, rnd, index, worker_id, exec_s, stamps)

    monkeypatch.setattr(E._StageRecorder, "_task_meta", spy)

    def split(t):
        half = t.num_rows // 2
        return [t.slice(0, half), t.slice(half)]

    raydp_tpu.init(app_name="placement", num_workers=2)
    try:
        df = rdf.from_pandas(
            pd.DataFrame({"k": np.arange(4000) % 8}), num_partitions=4)
        recorder.clear()
        df._executor.exchange(df.to_object_refs(), split, 2)
        spans = recorder.spans()
    finally:
        raydp_tpu.stop()
    # The recorder's spans in the profile's form, on perf_counter's clock.
    host = [[sp.name, sp.start_mono * 1e9, sp.duration_s * 1e9, sp.attrs,
             sp.tid] for sp in spans if sp.name.startswith("stage/")]
    placed = st.place_workers(host)
    assert placed["placed"] == 4 == len(seen) and placed["missing"] == 0
    worst = 0.0
    for recv, ret, worker, env in placed["intervals"]:
        t = seen[env]
        assert t["send"] <= t["recv"] <= t["ret"] <= t["reply"]
        transit = (t["reply"] - t["send"]) - (t["ret"] - t["recv"])
        assert abs(recv * 1e-9 - t["recv"]) <= transit / 2 + 5e-5
        assert ret - recv == pytest.approx((t["ret"] - t["recv"]) * 1e9,
                                           abs=1e3)
        worst = max(worst, transit / 2)
    assert placed["placement_error_ms_max"] == pytest.approx(
        worst * 1e3, abs=0.1)
    assert len(placed["bodies"]) == 6  # four splits, two merges


def test_recorded_form_round_trip(st, tmp_path):
    profile, stages = _synthetic()
    path = str(tmp_path / "slice.json.gz")
    st.save_recorded(profile, stages, path)
    again, stages_again = st.load_recorded(path)
    assert stages_again == stages
    assert st.reduce(again, stages_again)[0] == pytest.approx(
        st.reduce(profile, stages)[0])


@pytest.mark.parametrize("metric", sorted(READERS))
def test_each_reader_returns_its_number(bench_modules, st, monkeypatch,
                                        metric):
    """A reader is ``stage_trace.summary`` under one key; a run with
    nothing to read leaves the metric out (``None``)."""
    reader = bench_modules["harness"].load_module(
        os.path.join(BENCH_DIR, "layers", metric + ".py"))
    profile, stages = _synthetic()
    full = st.reduce(profile, stages)[0]
    monkeypatch.setattr(st, "summary", lambda facts: full)
    assert reader.read({}) == pytest.approx(full[READERS[metric]])
    monkeypatch.setattr(st, "summary", lambda facts: {})
    assert reader.read({}) is None


# ------------------------------------------------- recorded on the chip

def test_recorded_chip_slice_reproduces_perf_md(st):
    """The traced ``dlrm_kaggle.etl_fit`` run of PR 34 (seed 101, two
    jobs, the final tree): what PERF.md §5 quotes comes out of the
    recorded slice."""
    profile, stages = st.load_recorded(os.path.join(
        BENCH_DIR, "testdata", "dlrm_kaggle_etl_fit_stage.trace.json.gz"))
    summary, report = st.reduce(profile, stages)
    for key in READERS.values():
        assert isinstance(summary[key], float), key
    assert sum(summary[k] for k in SHARES) == pytest.approx(100.0, abs=0.1)
    parts = report["partition"]
    assert parts["sum_over_wall"] == pytest.approx(1.0, abs=0.01)
    assert all(s["exec_s"] <= s["wall_s"] for s in stages)
    assert all(s["queue_s"] == pytest.approx(s["transit_s"] + s["load_s"],
                                             abs=2e-6) for s in stages)
    assert report["envelopes_placed"] == 60  # 52 counting stages of one
    # envelope, the last stage's four of each of the two jobs
    assert report["placement_error_ms_max"] == pytest.approx(2.7131, abs=1e-3)
    assert set(report["idle_under"]) == {"df/stage", "ingest/wait"}
    assert report["unattributed"]["by_neighbours"]
    assert summary == pytest.approx(RECORDED, abs=5e-4)
    wait = report["ingest_wait"]
    assert wait["seconds"] == pytest.approx(0.4571, abs=1e-4)
    # Most of the wait with no body running is under no span of another
    # thread: the hand-off's own work after the last stage has closed.
    assert wait["no_body_by_open_span_s"][0][0] == "(no span)"
    assert wait["no_body_by_open_span_s"][0][1] == pytest.approx(
        0.2164, abs=1e-4)
    # Between two counting actions, and between two jobs.
    pairs = {(g["closed_before"], g["opens_after"]): g
             for g in report["unattributed"]["by_neighbours"]}
    assert pairs[("df/action", "df/stage")]["gaps"] == 52
    assert pairs[("train/fit", "df/from_pandas")][
        "bench_spans_open"] == ""


# What PERF.md §5 quotes for the recorded slice (my chip run, PR 34).
RECORDED = {
    "stage_fixed_ms": 1.92, "exec_share": 75.7987,
    "transit_share": 12.0124, "load_share": 1.6038,
    "driver_share": 10.5856, "wait_worker_busy_share": 32.6584,
}


# -------------------------------------------------- through run_cell (CPU)

def test_cpu_traced_run_reports_the_six_metrics(bench_modules, tiny_tree):
    """The tiny ``etl_fit`` cell on the CPU: the stage records and the host
    plane of the run's own profile are enough for all six."""
    run = bench_modules["run"]
    cell = "dlrm_tiny.etl_fit"
    out = run.run_cell(tiny_tree, cell, seed=7, seconds=1.0, trace=1,
                       platform="cpu")
    metrics = out["line"]["metrics"]
    for name in READERS:
        assert name in metrics, name
    shares = [metrics["etl.job_stage_" + k]["value"] for k in SHARES]
    assert sum(shares) == pytest.approx(100.0, abs=0.1)
    assert all(0.0 <= v <= 100.0 for v in shares)
    assert metrics["etl.job_stage_fixed_ms"]["value"] > 0.0
    assert 0.0 <= metrics[
        "handoff.job_wait_worker_busy_share"]["value"] <= 100.0
    out_dir = os.path.join(tiny_tree, "benchmark_out")
    with open(os.path.join(out_dir, cell + ".stage_trace.json")) as f:
        report = json.load(f)
    assert report["partition"]["sum_over_wall"] == pytest.approx(1.0, abs=0.01)
    assert report["envelopes_placed"] > 0
    assert report["placement_error_ms_max"] > 0.0
    assert os.path.exists(os.path.join(
        out_dir, cell + ".stage_trace.recorded.json.gz"))
    assert out["line"]["correct"] is True, out["notes"]["checks"]
