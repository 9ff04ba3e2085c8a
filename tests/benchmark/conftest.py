"""Fixtures of the benchmark's own tests: a temporary checkout that holds
``BENCHMARK.json`` and a copy of ``benchmark/`` with tiny configurations
and cells added as files (the real files are not edited), and the
benchmark's modules imported from the repo."""
import importlib
import json
import os
import shutil
import sys

import pytest

from bench_tree import BENCH_DIR, REPO, TINY_CELLS, TINY_CONFIGS, add_cell


@pytest.fixture(scope="session")
def bench_modules():
    """``run``, ``harness``, ``trace_reduce`` of the repo's benchmark,
    imported once."""
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    names = ("run", "harness", "trace_reduce")
    return {n: importlib.import_module(n) for n in names}


@pytest.fixture(scope="session")
def real_bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def tiny_tree(tmp_path_factory):
    """A checkout-shaped temporary tree with the tiny cells added."""
    root = str(tmp_path_factory.mktemp("bench_tree"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(
        BENCH_DIR, os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, sizes in TINY_CONFIGS.items():
        path = os.path.join("benchmark", "configs", name + ".json")
        with open(os.path.join(root, path), "w") as f:
            json.dump(sizes, f)
        bench["configs"].append({
            "name": name, "source": "test", "file": path, "reduced": [],
            "why": "tiny preset for the CPU tests",
        })
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    for name, (source, config, changes) in TINY_CELLS.items():
        add_cell(root, name, source, config, changes)
    return root
