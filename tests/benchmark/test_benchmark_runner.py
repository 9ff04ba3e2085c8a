"""The runner end to end at tiny sizes on the CPU, through the override the
tests apply (``run_cell(platform="cpu")`` on a temporary tree); the command
itself fails without a TPU."""
import json
import os
import subprocess
import sys

import pytest

from bench_tree import REPO, TINY_CELLS, add_cell

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _check_line(line, cell_metrics):
    assert LINE_KEYS <= set(line) <= LINE_KEYS | {"breakdown"}
    assert set(line["device"]) >= DEVICE_KEYS
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) <= set(cell_metrics)
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"}
        assert isinstance(metric["value"], float)
    json.dumps(line)


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_cell_runs_end_to_end(bench_modules, tiny_tree, cell):
    run, harness = bench_modules["run"], bench_modules["harness"]
    loaded = harness.load_cell(tiny_tree, cell)
    out = run.run_cell(tiny_tree, cell, seed=3, seconds=1.0, trace=0,
                       platform="cpu")
    line = out["line"]
    names = [m["name"] for m in loaded.end_to_end()]
    _check_line(line, names)
    assert sorted(line["metrics"]) == sorted(names)
    assert line["correct"] is True, out["notes"]["checks"]
    assert all(out["notes"]["checks"].values())
    assert line["metrics"]["setup_s"]["value"] > 0
    if loaded.chips == 4:
        assert out["notes"]["end_to_end"]["train_samples_per_s"] > 0
        assert line["device"]["count"] >= 4


@pytest.mark.parametrize("cell", ["bert_tiny.fit", "dlrm_tiny.etl_fit"])
def test_traced_run_reports_per_layer_metrics(bench_modules, tiny_tree, cell):
    run, harness = bench_modules["run"], bench_modules["harness"]
    loaded = harness.load_cell(tiny_tree, cell)
    out = run.run_cell(tiny_tree, cell, seed=4, seconds=1.0, trace=1,
                       platform="cpu")
    line = out["line"]
    _check_line(line, [m["name"] for m in loaded.per_layer()])
    assert line["correct"] is True, out["notes"]["checks"]
    # The CPU trace has no TPU plane: the trace's readers find nothing to
    # read and their metrics are left out, the host's are there.
    assert line["metrics"], "host-side per-layer metrics are reported"
    assert "step.device_ms" not in line["metrics"]
    assert "busy_s" not in line["device"]
    assert not set(line["metrics"]) & {"setup_s", "train_samples_per_s"}


def test_one_stalled_epoch_does_not_move_the_rate(bench_modules):
    """The chip showed single epochs stalled by seconds on the host; a
    run's rate is the median of its epochs' readings."""
    job = bench_modules["harness"].load_module(
        os.path.join(REPO, "benchmark", "jobs", "fit_window.py")
    )
    ends = [13.0, 16.0, 19.0, 25.1, 28.1, 31.1]   # one epoch of 6.1 s
    measured = [{"t": t, "samples": 4096} for t in ends]
    intervals, rate = job.epoch_readings(10.0, measured)
    assert intervals == pytest.approx([3.0, 3.0, 3.0, 6.1, 3.0, 3.0])
    assert rate == pytest.approx(4096 / 3.0)
    # ... and a slowdown of every epoch moves it one to one.
    slow = [{"t": 10.0 + 3.3 * (i + 1), "samples": 4096} for i in range(6)]
    assert job.epoch_readings(10.0, slow)[1] == pytest.approx(4096 / 3.3)


def test_same_seed_same_first_loss(bench_modules, tiny_tree):
    run = bench_modules["run"]
    a, b, c = (
        run.run_cell(tiny_tree, "dlrm_tiny.fit_staged", seed=s, seconds=0.3,
                     trace=0, platform="cpu")["notes"]["warmup_loss"]
        for s in (9, 9, 10)
    )
    assert a == b and a != c


def test_correct_is_false_when_the_reference_disagrees(bench_modules, tiny_tree):
    out = bench_modules["run"].run_cell(
        tiny_tree, "dlrm_tiny.fit_staged", seed=3, seconds=0.3, trace=0,
        platform="cpu", flip_reference=True,
    )
    assert out["line"]["correct"] is False
    assert out["notes"]["checks"]["logits_match_reference"] is False


NEW_GENERATOR = '''
import numpy as np


def generate(seed, sizes, *, rows, high):
    """Uniform ids below ``high``: a generator no other cell uses."""
    rng = np.random.default_rng(seed)
    cols = {f"I{i}": rng.random(rows, dtype=np.float32)
            for i in range(sizes["dense_features"])}
    for t, size in enumerate(sizes["vocab_sizes"]):
        cols[f"C{t}"] = rng.integers(0, min(size, high), rows).astype(
            np.float32)
    cols["label"] = (rng.random(rows) < 0.5).astype(np.float32)
    return cols
'''
NEW_READER = '''
def read(facts):
    return facts["samples"] / facts["batch"] / facts["base_s"]
'''


def test_a_new_cell_is_files_and_entries_only(bench_modules, tiny_tree):
    """A later PR adds a configuration with a generator of its own, a
    per-layer metric and a cell by adding files and ``BENCHMARK.json``
    entries; no file that is there changes."""
    bench_dir = os.path.join(tiny_tree, "benchmark")
    before = {}
    for base, _, files in os.walk(bench_dir):
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                before[path] = fh.read()

    def add_file(rel, text):
        path = os.path.join(bench_dir, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(text)

    with open(os.path.join(bench_dir, "configs", "dlrm_tiny.json")) as f:
        sizes = json.load(f)
    sizes["vocab_sizes"] = [7, 300]
    add_file("configs/dlrm_mini.json", json.dumps(sizes))
    add_file("generators/uniform_ids.py", NEW_GENERATOR)
    add_file("layers/step.rate.py", NEW_READER)
    add_cell(tiny_tree, "dlrm_mini.fit_b32", "dlrm_tiny.fit_staged",
             "dlrm_mini", {"per_chip_batch": 32, "steps_per_epoch": 3,
                           "data": {"generator": "uniform_ids", "high": 5}})
    with open(os.path.join(tiny_tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "dlrm_mini", "source": "test", "reduced": [], "why": "test",
        "file": "benchmark/configs/dlrm_mini.json",
    })
    bench["per_layer"].append({
        "name": "step.rate", "unit": "steps/s", "better": "higher",
        "source": "host_clock", "layer": "step",
        "moves": "train_samples_per_s", "workloads": ["dlrm_mini.fit_b32"],
    })
    with open(os.path.join(tiny_tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    for path, content in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == content, path

    out = bench_modules["run"].run_cell(
        tiny_tree, "dlrm_mini.fit_b32", seed=1, seconds=0.3, trace=1,
        platform="cpu",
    )
    assert out["line"]["correct"] is True
    assert out["notes"]["epoch_s"]
    assert out["line"]["metrics"]["step.rate"]["value"] > 0


def test_the_command_fails_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "dlrm_kaggle.fit_staged", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert proc.stdout == ""


def test_the_command_fails_in_a_bare_directory(tmp_path):
    """Only ``BENCHMARK.json`` and the files under ``paths``: no program,
    no result."""
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "dlrm_kaggle.fit_staged", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
