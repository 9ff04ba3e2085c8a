"""Client mode: a second driver process attaching to a live AppMaster.

Reference parity: the reference parameterizes every test over direct and
``ray://`` client modes (python/raydp/tests/conftest.py:42-49) and tests
a driver living inside another process (test_spark_cluster.py:38-57).
Here the remote-driver pipeline runs in a genuine subprocess speaking
only gRPC to the cluster.
"""
import json
import subprocess
import sys

import pandas as pd
import pytest

import raydp_tpu
import raydp_tpu.dataframe as rdf

# The pipeline body run by BOTH modes (direct exec / remote subprocess).
PIPELINE = """
import numpy as np
import pandas as pd
import raydp_tpu.dataframe as rdf
from raydp_tpu.data import MLDataset

def run_pipeline():
    rng = np.random.default_rng(0)
    pdf = pd.DataFrame({
        "k": rng.integers(0, 5, 2000),
        "v": rng.standard_normal(2000),
    })
    df = rdf.from_pandas(pdf, num_partitions=4)
    agg = (
        df.withColumn("v2", rdf.col("v") * 2.0)
        .groupBy("k").agg({"v2": "sum"})
        .to_pandas().sort_values("k")
    )
    refs = df.to_object_refs()
    back = rdf.from_refs(refs).to_pandas()
    ds = MLDataset.from_df(df, num_shards=2)
    return {
        "agg_keys": [int(k) for k in agg["k"]],
        "agg_sum": float(agg["sum(v2)"].sum()),
        "roundtrip_rows": int(len(back)),
        "shard_rows": int(ds.rows_per_shard),
        "expected_sum": float((pdf.v * 2.0).sum()),
    }
"""


def _check(result):
    assert result["agg_keys"] == [0, 1, 2, 3, 4]
    assert abs(result["agg_sum"] - result["expected_sum"]) < 1e-6
    assert result["roundtrip_rows"] == 2000
    assert result["shard_rows"] == 1000


@pytest.fixture()
def session():
    s = raydp_tpu.init(app_name="client-mode-test", num_workers=2)
    yield s
    raydp_tpu.stop()


@pytest.mark.parametrize("mode", ["direct", "client"])
def test_pipeline_both_driver_modes(session, mode):
    if mode == "direct":
        ns = {}
        exec(PIPELINE, ns)
        _check(ns["run_pipeline"]())
        return

    addr = session.cluster.master.address
    script = (
        "import json, raydp_tpu\n"
        f"s = raydp_tpu.connect({addr!r})\n"
        + PIPELINE
        + "\nout = run_pipeline()\n"
        "raydp_tpu.stop()\n"
        "print('RESULT ' + json.dumps(out))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=180,
        env={
            **__import__("os").environ,
            "JAX_PLATFORMS": "cpu",
        },
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = next(
        l for l in proc.stdout.splitlines() if l.startswith("RESULT ")
    )
    _check(json.loads(line[len("RESULT "):]))
    # disconnecting the client must leave the cluster alive
    assert len(session.cluster.alive_workers()) == 2
    out = rdf.from_pandas(pd.DataFrame({"x": [1, 2]})).to_pandas()
    assert len(out) == 2


def test_client_refs_visible_to_owning_driver(session):
    """Objects a client transfers to the holder survive its disconnect
    and stay readable from the owning driver."""
    addr = session.cluster.master.address
    script = (
        "import json, pandas as pd, raydp_tpu\n"
        "import raydp_tpu.dataframe as rdf\n"
        f"s = raydp_tpu.connect({addr!r})\n"
        "df = rdf.from_pandas(pd.DataFrame({'x': list(range(50))}), num_partitions=2)\n"
        "refs = df.to_object_refs()\n"
        "ids = [(r.object_id, r.size, r.owner, r.num_rows, r.node_id) for r in refs]\n"
        "raydp_tpu.stop()\n"
        "print('REFS ' + json.dumps(ids))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=120,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = next(l for l in proc.stdout.splitlines() if l.startswith("REFS "))
    from raydp_tpu.store.object_store import ObjectRef

    refs = [ObjectRef(*vals) for vals in json.loads(line[len("REFS "):])]
    total = sum(
        session.cluster.resolver.get_arrow_table(r).num_rows for r in refs
    )
    assert total == 50


def test_connect_guard_in_process_with_live_session(session):
    with pytest.raises(RuntimeError, match="already active"):
        raydp_tpu.connect(session.cluster.master.address)


# Estimator + MLDataset parity over both driver modes (reference runs its
# whole suite under direct AND ray:// client modes, conftest.py:42-49).
FIT_PIPELINE = """
def run_fit():
    import numpy as np
    import pandas as pd
    import flax.linen as nn
    import raydp_tpu.dataframe as rdf
    from raydp_tpu.train import JAXEstimator

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(1)(nn.relu(nn.Dense(8)(x)))

    rng = np.random.default_rng(1)
    pdf = pd.DataFrame({
        "a": rng.standard_normal(512),
        "b": rng.standard_normal(512),
    })
    pdf["y"] = 2.0 * pdf.a - pdf.b
    est = JAXEstimator(
        MLP(), num_epochs=4, batch_size=64,
        feature_columns=["a", "b"], label_column="y", seed=7,
    )
    hist = est.fit_on_df(
        rdf.from_pandas(pdf, num_partitions=2), num_shards=2
    )
    return {
        "first": float(hist[0]["train_loss"]),
        "last": float(hist[-1]["train_loss"]),
        "epochs": len(hist),
    }
"""

ROUNDTRIP_PIPELINE = """
def run_roundtrip():
    import numpy as np
    import pandas as pd
    import raydp_tpu.dataframe as rdf
    from raydp_tpu.data import MLDataset

    pdf = pd.DataFrame({
        "x": np.arange(300, dtype=np.int64),
        "y": np.arange(300, dtype=np.float64) * 0.5,
    })
    df = rdf.from_pandas(pdf, num_partitions=3)
    ds = MLDataset.from_df(df, num_shards=2)
    back = ds.to_df().to_pandas().sort_values("x").reset_index(drop=True)
    return {
        "rows": int(len(back)),
        "x_sum": int(back["x"].sum()),
        "y_sum": float(back["y"].sum()),
        "shards": int(ds.num_shards),
    }
"""


def _run_in_mode(session, mode, pipeline, fn_name):
    if mode == "direct":
        ns = {}
        exec(pipeline, ns)
        return ns[fn_name]()
    addr = session.cluster.master.address
    script = (
        "import json, raydp_tpu\n"
        f"s = raydp_tpu.connect({addr!r})\n"
        + pipeline
        + f"\nout = {fn_name}()\n"
        "raydp_tpu.stop()\n"
        "print('RESULT ' + json.dumps(out))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(
        l for l in proc.stdout.splitlines() if l.startswith("RESULT ")
    )
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("mode", ["direct", "client"])
def test_estimator_fit_both_driver_modes(session, mode):
    out = _run_in_mode(session, mode, FIT_PIPELINE, "run_fit")
    assert out["epochs"] == 4
    assert out["last"] < out["first"], out  # loss must decrease


@pytest.mark.parametrize("mode", ["direct", "client"])
def test_ml_dataset_roundtrip_both_driver_modes(session, mode):
    out = _run_in_mode(session, mode, ROUNDTRIP_PIPELINE, "run_roundtrip")
    assert out["rows"] == 300
    assert out["x_sum"] == sum(range(300))
    assert abs(out["y_sum"] - sum(range(300)) * 0.5) < 1e-9
    assert out["shards"] == 2
