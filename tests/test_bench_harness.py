"""Unit tests for the bench.py harness plumbing.

The bench's *numbers* come from real runs; what must never regress is
the machinery that guarantees a run cannot be lost or misread:
partial-result streaming, the device stamp on every result, config
filtering, and the budget-capped baseline loops.
"""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
import bench  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_bench_state(monkeypatch):
    """bench module state is process-global; isolate each test."""
    monkeypatch.setattr(bench, "_DEADLINE", None)
    monkeypatch.setattr(bench, "_EMITTED", False, raising=False)
    monkeypatch.delenv("RAYDP_TPU_ONLY", raising=False)
    yield


# ----------------------------------------------------- _only_filter

def test_only_filter_default_is_identity():
    assert bench._only_filter(["a", "b"]) == ["a", "b"]


def test_only_filter_restricts_and_preserves_matrix_order(monkeypatch):
    monkeypatch.setenv("RAYDP_TPU_ONLY", "c, a")
    # Order comes from the matrix (cheap-first), not the env var.
    assert bench._only_filter(["a", "b", "c"]) == ["a", "c"]


def test_only_filter_unknown_names_drop_silently(monkeypatch):
    monkeypatch.setenv("RAYDP_TPU_ONLY", "nope")
    assert bench._only_filter(["a"]) == []


def test_matrix_names_are_unique():
    names = [n for n, _ in bench.MATRIX]
    assert len(names) == len(set(names))


# ----------------------------------------------------- _torch_rate

class _SlowLinear:
    """Wraps a tiny torch model whose forward sleeps, to make batch
    wall time controllable without burning real FLOPs."""

    def __new__(cls, delay_s):
        import torch

        class M(torch.nn.Module):
            def __init__(self):
                super().__init__()
                self.lin = torch.nn.Linear(4, 1)

            def forward(self, x):
                import time as _t

                _t.sleep(delay_s)
                return self.lin(x)

        return M()


def _mse_batch(i):
    import torch

    x = torch.from_numpy(np.ones((2, 4), np.float32))
    y = torch.from_numpy(np.zeros((2, 1), np.float32))
    return x, y


def test_torch_rate_runs_full_count_without_budget():
    calls = []

    def make_batch(i):
        calls.append(i)
        return _mse_batch(i)

    rate = bench._torch_rate(_SlowLinear(0.0), make_batch, n_batches=4)
    assert len(calls) == 4
    assert rate > 0


def test_torch_rate_budget_stops_after_first_timed_batch():
    calls = []

    def make_batch(i):
        calls.append(i)
        return _mse_batch(i)

    # Each batch takes ~50 ms; budget expires immediately after the
    # first timed batch (warmup + 1), well before all 8.
    rate = bench._torch_rate(
        _SlowLinear(0.05), make_batch, n_batches=8, budget_s=0.01
    )
    assert len(calls) == 2  # warmup + one timed — never zero timed
    assert rate > 0


def test_torch_rate_deadline_guard_still_yields_a_rate(monkeypatch):
    import time as _t

    # Global deadline already blown: must still time ONE batch (a
    # rate of n/0 batches would crash the config and lose the round's
    # other results).
    monkeypatch.setattr(bench, "_DEADLINE", _t.monotonic() - 1000)
    rate = bench._torch_rate(_SlowLinear(0.0), _mse_batch, n_batches=8)
    assert rate > 0


# ----------------------------------------------------- emission

def test_results_are_stamped_with_the_device(tmp_path, monkeypatch):
    """Every result names the platform, device kind and device count it
    ran on, and a section that raises is recorded without stopping the
    run (main() turns it into a non-zero exit)."""
    stamp = {"platform": "tpu", "device_kind": "TPU vTest",
             "device_count": 4}
    state = {"configs": {}, "device": stamp, "profile": None,
             "analysis": None, "notes": [], "emitted": False}
    monkeypatch.setattr(bench, "_STATE", state)
    monkeypatch.setenv("RAYDP_TPU_BENCH_PARTIAL", str(tmp_path / "p.json"))

    def boom():
        raise RuntimeError("section broke")

    bench._record("good", lambda: {"samples_per_sec": 5})
    bench._record("bad", boom)
    for name in ("good", "bad"):
        assert {k: state["configs"][name][k] for k in stamp} == stamp
    assert state["configs"]["bad"]["error"] == "RuntimeError: section broke"
    out = bench._assemble()
    assert {k: out[k] for k in stamp} == stamp
    with open(tmp_path / "p.json") as f:
        assert json.load(f)["configs"]["good"]["samples_per_sec"] == 5


def test_timed_train_steps_returns_wall_time():
    import jax.numpy as jnp
    import optax

    def loss_of(p, x, y):
        return jnp.mean((x @ p["w"] - y) ** 2)

    dt = bench._timed_train_steps(
        loss_of,
        {"w": jnp.ones((4, 1))},
        optax.sgd(0.1),
        (jnp.ones((8, 4)), jnp.zeros((8, 1))),
        n_steps=2,
    )
    assert dt > 0
