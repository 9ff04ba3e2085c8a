"""``benchmark/startup_trace.py``: the program's start-up record reduced to
the eight ``setup.*`` readings — on a synthetic record, and through one
traced ``run_cell`` of a tiny cell on the CPU."""
import importlib
import json
import os

import pytest

from bench_tree import BENCH_DIR

READERS = {
    "setup.ready_s": "ready_s",
    "setup.init_state_s": "init_state_s",
    "setup.step_program_s": "step_program_s",
    "setup.trace_lower_s": "trace_lower_s",
    "setup.backend_compile_s": "backend_compile_s",
    "setup.cache_load_s": "cache_load_s",
    "setup.cache_miss_programs": "cache_miss_programs",
    "setup.unaccounted_s": "unaccounted_s",
}
CELL = "dlrm_tiny.fit_staged"


@pytest.fixture(scope="module")
def su(bench_modules):
    return importlib.import_module("startup_trace")


def _record(name, owner, t_end, trace=0.0, lower=0.0, backend=0.0,
            cache="uncached"):
    return {"fun_name": name, "trace_s": trace, "lower_s": lower,
            "backend_s": backend, "cache": cache, "retrieval_s": 0.0,
            "t_end": t_end, "owner": owner}


def _synthetic():
    """Import at 100 s. A cluster in 1-5 s, the caller's client and data
    until 20 s, a warm-up fit from 20 s whose one epoch ends at 60 s (the
    ready stamp) and whose span closes at 60.5 s; the window's fit from
    62 s; the reference check's ``predict_step`` at 200 s."""
    t = 100.0
    spans = [
        ["cluster/start", t + 1, t + 5, {"workers": 2}],
        ["train/fit", t + 20, t + 60.5, {"epochs": 1}],
        ["mesh/build", t + 21, t + 22, {"devices": 1}],
        ["train/init_state", t + 22, t + 35,
         {"seed": 7, "sharded": True}],
        ["train/build_steps", t + 35, t + 38, {}],
        ["train/first_dispatch", t + 40, t + 50, {"label": "train_step"}],
        ["train/first_dispatch", t + 55, t + 56, {"label": "eval_step"}],
        ["train/fit", t + 62, t + 150, {"epochs": 3}],
        ["train/first_dispatch", t + 200, t + 204,
         {"label": "predict_step"}],
    ]
    records = [
        _record("jit(_threefry_seed)", "train/fit", t + 20.5, backend=0.1),
        _record("jit(<lambda>)", "train/init_state", t + 34, trace=2.0,
                lower=1.0, backend=9.0, cache="miss"),
        _record("jit(train_step)", "train/first_dispatch", t + 49,
                trace=3.0, lower=2.0, backend=4.0, cache="hit"),
        _record("jit(eval_step)", "train/first_dispatch", t + 56,
                trace=0.2, lower=0.1, backend=0.5, cache="hit"),
        _record("jit(generate)", None, t + 15, backend=1.0),
        _record("jit(predict_step)", "train/first_dispatch", t + 203,
                trace=1.0, backend=2.0, cache="hit"),
        _record("jit(reference)", None, t + 230, backend=20.0),
    ]
    return {"origin": t, "ready": t + 60.0, "ready_s": 60.0, "spans": spans,
            "records": records, "records_dropped": 0.0,
            "spans_dropped": 0.0}


def test_reduce_cuts_at_the_ready_stamp(su):
    result, report = su.reduce(_synthetic())
    assert result == {
        "ready_s": 60.0,
        "init_state_s": 13.0,
        "step_program_s": 11.0,       # predict_step's comes after
        "trace_lower_s": 8.3,
        "backend_compile_s": pytest.approx(9.1),   # the miss, the uncached
        "cache_load_s": 4.5,
        "cache_miss_programs": 1,
        # 60 less the cluster's 4 and the fit's 40 (the mesh is inside it)
        "unaccounted_s": 16.0,
    }
    phases = report["phases"]
    assert [p["name"] for p in phases][:3] == [
        "cluster/start", "train/fit", "mesh/build"
    ]
    assert len(phases) == 7  # the window's fit and predict_step are not
    fit = phases[1]
    assert fit["cut"] and fit["seconds"] == 40.0 and fit["start_s"] == 20.0
    assert report["cache"] == {"hit": 2, "miss": 1, "uncached": 1}
    left = report["left_over"]
    assert left["after_ready"]["names"] == [
        "jit(predict_step)", "jit(reference)"
    ]
    assert left["no_program_span"]["names"] == ["jit(generate)"]
    init = report["by_owner"]["train/init_state"]
    assert init["backend_compile_s"] == 9.0 and init["trace_s"] == 2.0
    assert [(p["fun_name"], p["kind"], p["cache"])
            for p in init["programs"]] == [
        ("jit(<lambda>)", "backend_compile", "miss")
    ]


@pytest.mark.parametrize("loaded", [None, {}, {"ready": None}])
def test_no_record_reads_as_nothing(su, loaded):
    """The parent of PR 49 keeps no such record; a process in which no
    epoch has ended has no ready stamp."""
    assert su.reduce(loaded) == ({}, {})


def test_union_counts_a_second_once(su):
    assert su.union_s([(0, 4), (2, 3), (3, 6), (10, 11)]) == 7


@pytest.fixture(scope="module")
def traced_run(bench_modules, tiny_tree):
    from raydp_tpu.telemetry import recorder
    from raydp_tpu.utils import profiling

    # The record is the process's, and this process has run other tests.
    recorder.clear()
    profiling._compile_log.clear()
    out = bench_modules["run"].run_cell(
        tiny_tree, CELL, seed=7, seconds=0.5, trace=1, platform="cpu"
    )
    with open(os.path.join(
        tiny_tree, "benchmark_out", CELL + ".startup.json"
    )) as f:
        return out, json.load(f)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_traced_tiny_cell_reports_it(traced_run, metric):
    out, report = traced_run
    assert out["line"]["correct"], out["notes"]["checks"]
    reading = out["line"]["metrics"][metric]
    assert reading["value"] >= 0.0
    assert reading["value"] == report["metrics"][READERS[metric]]


def test_the_readings_add_up(traced_run):
    out, report = traced_run
    m = {k: out["line"]["metrics"][name]["value"]
         for name, k in READERS.items()}
    assert m["init_state_s"] > 0 and m["step_program_s"] > 0
    assert m["ready_s"] >= (
        m["init_state_s"] + m["step_program_s"] + m["unaccounted_s"]
    )
    assert (m["trace_lower_s"] + m["backend_compile_s"] + m["cache_load_s"]
            == pytest.approx(report["records_total_s"]))
    assert m["trace_lower_s"] > 0 and m["backend_compile_s"] > 0
    # No persistent cache on the CPU: nothing loaded, nothing written.
    assert m["cache_load_s"] == 0 and m["cache_miss_programs"] == 0
    # The window opens one in-call warm-up epoch after the ready stamp.
    # (This process imported raydp_tpu long before run_cell's clock
    # started: both are counted from the warm-up fit's start here.)
    since_fit = m["ready_s"] - report["phases"][0]["start_s"]
    assert 0 < out["notes"]["end_to_end"]["setup_s"] - since_fit < 5.0


def test_the_report_names_phases_and_programs(traced_run):
    _, report = traced_run
    phases = report["phases"]
    starts = [p["start_s"] for p in phases]
    assert starts == sorted(starts)
    assert [p["name"] for p in phases] == [
        "train/fit", "mesh/build", "train/init_state", "train/build_steps",
        "train/first_dispatch",
    ]
    assert phases[-1]["attrs"] == {"label": "train_step"}
    owners = report["by_owner"]
    assert [p["fun_name"] for p in
            owners["train/first_dispatch"]["programs"]] == ["jit(train_step)"]
    init = owners["train/init_state"]["programs"]
    assert "jit(<lambda>)" in [p["fun_name"] for p in init]
    assert all(p["cache"] == "uncached" and p["kind"] == "backend_compile"
               for p in init)
    # The reference check's two programs came after the window.
    assert "jit(predict_step)" in report["left_over"]["after_ready"]["names"]


def test_every_reading_is_declared_for_every_cell(real_bench):
    cells = [w["name"] for w in real_bench["workloads"]]
    declared = {m["name"]: m for m in real_bench["per_layer"]
                if m["layer"] == "start-up"}
    assert sorted(declared) == sorted(READERS)
    for name, m in declared.items():
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["workloads"] == cells, name
        assert os.path.isfile(os.path.join(BENCH_DIR, "layers", name + ".py"))
