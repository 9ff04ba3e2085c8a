"""``BENCHMARK.json`` and the benchmark's files against the contract: names,
units, limits, and every name in one file resolving to a file of its own."""
import json
import os
import re

import pytest

from bench_tree import BENCH_DIR, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def _workload_file(name):
    with open(os.path.join(BENCH_DIR, "workloads", name + ".json")) as f:
        return json.load(f)


def test_top_level_keys_and_limits(real_bench):
    assert set(real_bench) == TOP_KEYS
    assert real_bench["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= real_bench["run_seconds"] <= 51
    assert isinstance(real_bench["run_seconds"], int)
    for path in real_bench["paths"]:
        assert os.path.isdir(os.path.join(REPO, path)), path
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    four = [w for w in real_bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(real_bench["workloads"]) // 4)


def test_names_units_and_keys(real_bench):
    seen = set()
    for group, keys, optional in (
        ("configs", {"name", "source", "file", "reduced", "why"}, set()),
        ("workloads", {"name", "config", "traffic", "chips", "why"}, set()),
        ("end_to_end", {"name", "unit", "better", "bound", "source"},
         {"workloads"}),
        ("per_layer", {"name", "unit", "better", "source", "layer", "moves"},
         {"workloads"}),
    ):
        for entry in real_bench[group]:
            assert keys <= set(entry) <= keys | optional, entry
            assert NAME.match(entry["name"]), entry["name"]
            key = (group, entry["name"])
            assert key not in seen
            seen.add(key)
            texts = [entry[k] for k in ("why", "layer") if k in entry]
            if group == "configs":
                texts.append(entry["source"])
            for text in texts:
                assert 1 <= len(text) <= 200
                assert "\n" not in text and "\t" not in text
    metric_names = [m["name"] for m in
                    real_bench["end_to_end"] + real_bench["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for m in real_bench["end_to_end"] + real_bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in real_bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in real_bench["end_to_end"])


def test_files_under_paths_are_named_from_name_characters(real_bench):
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in real_bench["paths"]:
        for base, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), REPO)
                assert allowed.match(rel), rel


def test_configs_resolve(real_bench):
    files = set()
    used = {w["config"] for w in real_bench["workloads"]}
    for config in real_bench["configs"]:
        assert config["name"] in used
        assert config["file"].startswith(tuple(real_bench["paths"]))
        assert config["file"] not in files
        files.add(config["file"])
        with open(os.path.join(REPO, config["file"])) as f:
            sizes = json.load(f)
        assert sorted(sizes["reduced"]) == sorted(config["reduced"])
        assert sizes["assumed"], "assumed sizes are listed with reasons"
        assert sizes["source"]
        builder = os.path.join(BENCH_DIR, "configs", sizes["builder"] + ".py")
        assert os.path.isfile(builder)
        for key in config["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank|hidden|intermediate)", key)


def test_published_widths_are_not_cut(real_bench):
    by_name = {c["name"]: c["file"] for c in real_bench["configs"]}
    with open(os.path.join(REPO, by_name["bert_base"])) as f:
        bert = json.load(f)
    assert (bert["hidden_size"], bert["num_hidden_layers"],
            bert["num_attention_heads"], bert["intermediate_size"],
            bert["vocab_size"]) == (768, 12, 12, 3072, 30522)
    with open(os.path.join(REPO, by_name["dlrm_kaggle"])) as f:
        dlrm = json.load(f)
    assert dlrm["embed_dim"] == 16 and dlrm["dense_features"] == 13
    assert dlrm["bottom_mlp"] == [512, 256, 64, 16]
    assert dlrm["top_mlp"] == [512, 256]
    assert len(dlrm["vocab_sizes"]) == 26
    assert sum(dlrm["vocab_sizes"]) == 33_762_577
    assert max(dlrm["vocab_sizes"]) < 2 ** 24, "ids ride a float32 pack"
    assert dlrm["embedding_impl"] == "take"


def test_workloads_resolve_and_agree_with_their_files(real_bench):
    configs = {c["name"] for c in real_bench["configs"]}
    pairs = set()
    for w in real_bench["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert NAME.match(w["traffic"])
        data = _workload_file(w["name"])
        assert data["config"] == w["config"]
        assert data["chips"] == w["chips"]
        assert data["why"] == w["why"]
        assert os.path.isfile(
            os.path.join(BENCH_DIR, "jobs", data["job"] + ".py")
        )
        traffic = data["traffic"]
        for kind, name in (
            ("generators", traffic["data"]["generator"]),
            ("stagings", traffic["staging"].get("kind")),
        ):
            assert name is None or os.path.isfile(
                os.path.join(BENCH_DIR, kind, name + ".py")
            ), (w["name"], kind, name)
        assert data["traffic"].get("mesh", {}).get("dp", 1) == w["chips"]
    on_disk = {f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, "workloads"))}
    assert on_disk == {w["name"] for w in real_bench["workloads"]}


def test_metrics_resolve(real_bench):
    cells = {w["name"] for w in real_bench["workloads"]}
    end_to_end = {m["name"]: m for m in real_bench["end_to_end"]}
    layers = set()
    layer_dir = os.path.join(BENCH_DIR, "layers")
    for m in real_bench["per_layer"]:
        reader = m["name"]
        alias = os.path.join(layer_dir, reader + ".txt")
        if os.path.exists(alias):
            # The same reading under a second name: it moves another metric.
            assert not os.path.exists(os.path.join(layer_dir, reader + ".py"))
            with open(alias) as f:
                reader = f.read().strip()
        assert os.path.isfile(os.path.join(layer_dir, reader + ".py")), m["name"]
        assert m["moves"] in end_to_end
        layers.add(m["layer"])
        moved = end_to_end[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            # Reported only where the metric it moves is.
            assert cell in moved.get("workloads", cells), (m["name"], cell)
    readers = {f.rsplit(".", 1)[0] for f in os.listdir(layer_dir)
               if f.endswith((".py", ".txt"))}
    assert readers == {m["name"] for m in real_bench["per_layer"]}
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"
    for cell in cells:
        mine = lambda m: cell in m.get("workloads", cells)  # noqa: E731
        names = [m["name"] for m in real_bench["end_to_end"] if mine(m)]
        assert "setup_s" in names and len(names) >= 2, cell
        assert any(mine(m) for m in real_bench["per_layer"]), cell


def test_peaks_table_names_its_source():
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["source"]


def test_unknown_device_kind_is_an_error(bench_modules, real_bench):
    harness = bench_modules["harness"]
    cell = harness.load_cell(REPO, real_bench["workloads"][0]["name"])
    with pytest.raises(SystemExit):
        harness.peaks_for(cell, "TPU v9 imaginary")
