"""What a CPU can check of the chip bring-up (PR 21): nothing on the
path to the device falls back quietly, and ``chip_smoke.py`` refuses to
run anywhere but on a TPU."""
import os
import shutil
import subprocess
import sys
import types

import jax.numpy as jnp
import pytest

from raydp_tpu.utils import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------ compile cache

def test_compile_cache_left_to_the_environment(monkeypatch):
    import jax

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.compile_cache_dir() is None
    assert compile_cache.ensure_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_not_configured_on_cpu(monkeypatch):
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert compile_cache.compile_cache_dir() is None


def test_compile_cache_fixed_path_same_in_child(monkeypatch, tmp_path):
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    here = compile_cache.compile_cache_dir()
    assert here == os.path.join(ROOT, ".jax_cache")
    # A child started somewhere else, under another pid, at another
    # time, lands on the same directory.
    child = subprocess.run(
        [sys.executable, "-c",
         "import importlib.util as u, sys\n"
         "spec = u.spec_from_file_location('cc', sys.argv[1])\n"
         "m = u.module_from_spec(spec); spec.loader.exec_module(m)\n"
         "print(m.compile_cache_dir())",
         compile_cache.__file__],
        cwd=tmp_path, capture_output=True, text=True, timeout=60,
        env=dict(os.environ),
    )
    assert child.returncode == 0, child.stderr
    assert child.stdout.strip() == here


def test_compile_seconds_ignore_what_the_cache_saved():
    """A cache hit makes jax report the compile time it SAVED; that is
    not compile time spent and must not land in compile/seconds."""
    from jax import monitoring

    from raydp_tpu.utils.profiling import install_compile_listener, metrics

    assert install_compile_listener()

    def spent():
        return metrics.snapshot().get("counters", {}).get(
            "compile/seconds", 0.0
        )

    before = spent()
    monitoring.record_event_duration_secs(
        "/jax/compilation_cache/compile_time_saved_sec", 50.0
    )
    assert spent() == before
    monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 2.0
    )
    assert spent() == before + 2.0


# ------------------------------------------------- no quiet fallbacks

def test_flash_attention_off_the_chip_raises():
    """attention_impl='flash' is the Mosaic kernel or an error — never
    the Pallas interpreter picked behind the caller's back."""
    import jax

    from raydp_tpu.models.transformer import (
        MultiHeadAttention,
        tiny_transformer,
    )

    model = MultiHeadAttention(
        tiny_transformer(max_len=32, attention_impl="flash")
    )
    x = jnp.zeros((1, 32, 128), jnp.float32)
    # Abstract init, then lowering only: the refusal comes from
    # lowering the kernel for the CPU, before anything is compiled.
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="interpret mode"):
        jax.jit(model.apply).lower(params, x)


def test_fit_ends_on_an_accelerator_no_table_lists(monkeypatch):
    """No gauge may end a fit: telemetry that looks at the local
    devices sees a kind no peak table knows, and a two-epoch stream fit
    still ends and returns its history."""
    import jax
    import numpy as np
    import pandas as pd

    from raydp_tpu.models.mlp import MLP
    from raydp_tpu.train.estimator import JAXEstimator
    from raydp_tpu.utils.profiling import sample_resource_gauges

    fake = types.SimpleNamespace(
        platform="tpu", device_kind="TPU v99 mega",
        memory_stats=lambda: None,
    )
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: [fake])
    rs = np.random.RandomState(0)
    cols = [f"f{i}" for i in range(4)]
    df = pd.DataFrame(rs.rand(512, 4).astype(np.float32), columns=cols)
    df["label"] = df[cols].sum(axis=1)
    est = JAXEstimator(
        model=MLP(hidden=(8,), out_dim=1), loss="mse", num_epochs=2,
        batch_size=128, feature_columns=cols, label_column="label",
        epoch_mode="stream",
    )
    history = est.fit_on_df(df)
    sample_resource_gauges()
    assert [h["epoch"] for h in history] == [0, 1]
    assert all(np.isfinite(h["train_loss"]) for h in history)


def test_second_replica_on_a_tpu_host_fails_at_once(monkeypatch):
    from raydp_tpu.serve import group as serve_group

    monkeypatch.setattr(
        serve_group, "_tpu_chip_nodes", lambda: ["/dev/vfio/0"]
    )
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    g = serve_group.ReplicaGroup(replicas=2, mode="decode")
    with pytest.raises(serve_group.ServeError, match="replicas=1"):
        g.start()
    assert not g._started and not g._slots  # nothing was spawned


def test_native_library_is_keyed_on_source_and_flags(tmp_path):
    from raydp_tpu.native import build

    src = tmp_path / "a.cpp"
    src.write_text("int f() { return 1; }\n")
    p1 = build._lib_path([str(src)], ("-O3",))
    assert p1 == build._lib_path([str(src)], ("-O3",))
    assert p1 != build._lib_path([str(src)], ("-O3", "-fopenmp"))
    src.write_text("int f() { return 2; }\n")
    assert p1 != build._lib_path([str(src)], ("-O3",))
    assert not any("-march" in f for flags in build._FLAG_SETS for f in flags)


# --------------------------------------------------------- chip_smoke

def _run_smoke(cwd, script):
    return subprocess.run(
        [sys.executable, script], cwd=cwd, capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def test_chip_smoke_refuses_to_run_without_a_tpu():
    res = _run_smoke(ROOT, os.path.join(ROOT, "chip_smoke.py"))
    assert res.returncode != 0
    no_tpu = [ln for ln in res.stderr.splitlines() if "no TPU" in ln]
    assert len(no_tpu) == 1, res.stderr
    assert res.stdout.strip() == ""  # no result line, no numbers


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    res = _run_smoke(tmp_path, str(tmp_path / "chip_smoke.py"))
    assert res.returncode != 0
    assert "raydp_tpu" in res.stderr
    assert res.stdout.strip() == ""


def test_chip_smoke_last_line_is_exactly_the_verdict():
    """The driver reads the last stdout line and accepts only the keys
    ok and device{platform, kind, count}; the summary goes before it."""
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    results = {
        "train": {"device": dict(device), "jax": "0.9.0", "fits": []},
        "serve": {"answers": []},
        "gang": {"losses": [1.0]},
    }
    lines = chip_smoke.result_lines(results, 12.34)
    assert all("\n" not in ln for ln in lines)
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert isinstance(json.loads(lines[-1])["device"]["count"], int)
    summary = json.loads(lines[0])
    assert lines[0].endswith('"claim": null}')
    assert set(summary["phases"]) == set(chip_smoke.PHASES)
    assert "device" in results["train"]  # the input is not consumed
