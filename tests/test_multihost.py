"""Multi-host data/control plane tests.

Two virtual hosts simulated on one machine: node-scoped shm namespaces
keep the "hosts" physically apart (a node-0 process never opens node-1
segments), per-node store agents serve cross-node fetches over gRPC, and
the master's directory routes lifecycle ops to the owning node. The
reference's counterpart story is Ray's cluster-wide object store
(reference: ObjectStoreWriter.scala:58-79 cluster-visible Ray.put,
test shape: python/raydp/tests/test_spark_cluster.py + the CI head node).
"""
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import raydp_tpu
import raydp_tpu.dataframe as rdf
from raydp_tpu.data import MLDataset
from raydp_tpu.store.object_store import OWNER_HOLDER


@pytest.fixture()
def twohost():
    session = raydp_tpu.init(
        app_name="multihost-test", num_workers=2, num_virtual_nodes=2
    )
    yield session
    raydp_tpu.stop()


def _worker_on(session, node_id):
    w = next(
        (w for w in session.cluster.alive_workers() if w.node_id == node_id),
        None,
    )
    assert w is not None, f"no alive worker on {node_id}"
    return w.worker_id


def _make_write_task():
    # Defined as a closure so cloudpickle serializes it by value (a
    # module-level fn would be pickled by reference to this test module,
    # which workers can't import).
    def write_table(ctx):
        table = pa.table({"x": [1, 2, 3], "y": [10.0, 20.0, 30.0]})
        return ctx.put_table(table)

    return write_table


_write_table = _make_write_task()


def test_workers_spread_across_virtual_nodes(twohost):
    nodes = {w.node_id for w in twohost.cluster.alive_workers()}
    assert nodes == {"node-0", "node-1"}
    # the remote node has a store agent; the driver node's is the master
    agents = twohost.cluster.master.store.agents()
    assert "node-1" in agents and "node-0" in agents


def test_remote_ref_readable_on_driver(twohost):
    ref = twohost.cluster.submit(
        _write_table, worker_id=_worker_on(twohost, "node-1")
    )
    assert ref.node_id == "node-1"
    # driver-local store must NOT see it (separate "hosts")...
    assert not twohost.cluster.master.store.contains(ref)
    # ...but the resolver fetches it through node-1's store agent.
    table = twohost.cluster.resolver.get_arrow_table(ref)
    assert table.column("x").to_pylist() == [1, 2, 3]


def test_cross_node_worker_to_worker_read(twohost):
    ref = twohost.cluster.submit(
        _write_table, worker_id=_worker_on(twohost, "node-1")
    )

    def read_back(ctx, r):
        assert ctx.node_id != r.node_id  # forced remote path
        return ctx.get_table(r).column("y").to_pylist()

    got = twohost.cluster.submit(
        read_back, ref, worker_id=_worker_on(twohost, "node-0")
    )
    assert got == [10.0, 20.0, 30.0]


def test_dataframe_pipeline_across_hosts(twohost):
    n = 4000
    rng = np.random.default_rng(0)
    pdf = pd.DataFrame(
        {
            "k": rng.integers(0, 7, n),
            "v": rng.standard_normal(n),
        }
    )
    df = rdf.from_pandas(pdf, num_partitions=4)
    refs = df.to_object_refs()
    assert {r.node_id for r in refs} == {"node-0", "node-1"}

    out = (
        rdf.from_pandas(pdf, num_partitions=4)
        .withColumn("v2", rdf.col("v") * 2.0)
        .filter(rdf.col("k") < 5)
        .groupBy("k")
        .agg({"v2": "sum"})
        .to_pandas()
        .sort_values("k")
        .reset_index(drop=True)
    )
    expected = (
        pdf[pdf.k < 5]
        .assign(v2=lambda d: d.v * 2.0)
        .groupby("k", as_index=False)["v2"]
        .sum()
        .sort_values("k")
        .reset_index(drop=True)
    )
    assert np.allclose(out["sum(v2)"].to_numpy(), expected["v2"].to_numpy())


def test_ingest_ships_only_to_the_other_node(twohost):
    """Initial placement is across NODES: a partition whose round-robin
    target sits on the driver's node is the driver's own put (no task),
    one whose target sits on the other node is still shipped over the
    data plane and lands THERE, holder-owned and in the directory. A
    stage over the mixed frame returns the right rows, in order."""
    from tests.test_dataframe_cluster import _ingest_counters as counters

    cluster = twohost.cluster
    store = cluster.master.store
    n = 4001
    pdf = pd.DataFrame({
        "i": np.arange(n, dtype=np.int64),
        "v": np.random.default_rng(2).standard_normal(n),
    })
    targets = sorted(cluster.alive_workers(), key=lambda w: w.worker_id)
    tasks = []
    submit_async = cluster.submit_async

    def counting_submit(fn, *args, **kwargs):
        tasks.append(kwargs.get("worker_id"))
        return submit_async(fn, *args, **kwargs)

    before = counters()
    cluster.submit_async = counting_submit
    try:
        df = rdf.from_pandas(pdf, num_partitions=4)
    finally:
        del cluster.submit_async
    after = counters()
    refs = df._parts
    want_nodes = [targets[j % 2].node_id for j in range(4)]
    assert sorted(want_nodes) == ["node-0"] * 2 + ["node-1"] * 2
    assert [r.node_id for r in refs] == want_nodes
    assert (after[0] - before[0], after[1] - before[1]) == (2, 2)
    assert tasks == [t.worker_id for t in targets if t.node_id == "node-1"] * 2
    assert all(r.owner == OWNER_HOLDER for r in refs)
    assert [store.get_ref(r.object_id) for r in refs] == refs
    assert [r.num_rows for r in refs] == [1001, 1000, 1000, 1000]
    # the staged scratch copies of the shipped partitions are gone
    assert {r.object_id for r in store.refs()} >= {r.object_id for r in refs}
    assert sum(r.num_rows for r in store.refs()) == n

    out = (
        df.withColumn("w", rdf.col("v") * 2.0)
        .filter(rdf.col("i") % 3 == 0)
        .to_pandas()
    )
    expected = pdf[pdf.i % 3 == 0].assign(w=lambda d: d.v * 2.0)
    pd.testing.assert_frame_equal(out, expected.reset_index(drop=True))


def test_broadcast_join_across_hosts(twohost):
    left = rdf.from_pandas(
        pd.DataFrame({"k": [0, 1, 2, 3] * 50, "a": range(200)}),
        num_partitions=4,
    )
    right = rdf.from_pandas(
        pd.DataFrame({"k": [0, 1, 2, 3], "name": ["w", "x", "y", "z"]}),
        num_partitions=1,
    )
    out = left.join(right, on="k").to_pandas()
    assert len(out) == 200
    assert set(out["name"]) == {"w", "x", "y", "z"}


def test_holder_object_survives_remote_worker_death(twohost):
    wid = _worker_on(twohost, "node-1")
    ref = twohost.cluster.submit(_write_table, worker_id=wid)
    kept = twohost.cluster.master.store.transfer_to_holder(ref)
    assert kept.owner == OWNER_HOLDER and kept.node_id == "node-1"
    lost = twohost.cluster.submit(_write_table, worker_id=wid)

    twohost.cluster.kill_worker(wid)

    # non-transferred object was unlinked ON ITS NODE via the agent
    with pytest.raises(Exception):
        twohost.cluster.resolver.get_bytes(lost)
    # holder-owned object still fetchable through the node-1 agent
    table = twohost.cluster.resolver.get_arrow_table(kept)
    assert table.num_rows == 3


def test_mldataset_and_estimator_across_hosts(twohost):
    import optax

    from raydp_tpu.models import MLP
    from raydp_tpu.train import JAXEstimator

    rng = np.random.default_rng(1)
    a = rng.standard_normal(1024)
    b = rng.standard_normal(1024)
    y = 2 * a - 3 * b + 1
    df = rdf.from_pandas(
        pd.DataFrame({"a": a, "b": b, "y": y}), num_partitions=4
    )
    ds = MLDataset.from_df(df, num_shards=2)
    # blocks live on both hosts, and every shard materializes on the driver
    assert {r.node_id for r in ds.blocks} == {"node-0", "node-1"}
    cols = ds.shard_columns(0, ["a", "b", "y"])
    assert len(cols["a"]) == ds.rows_per_shard

    est = JAXEstimator(
        model=MLP(hidden=(16,), out_dim=1),
        optimizer=optax.adam(1e-2),
        loss="mse",
        num_epochs=4,
        batch_size=256,
        feature_columns=["a", "b"],
        label_column="y",
        seed=0,
    )
    history = est.fit_on_df(df)
    assert history[-1]["train_loss"] < history[0]["train_loss"]


def test_mldataset_holder_survives_stop():
    """Holder-owned MLDataset blocks outlive worker teardown
    (stop(del_obj_holder=False)) and stay readable — moved here from
    test_ml_dataset.py, which now runs under shared dual-mode sessions
    and must not manage cluster lifecycle itself."""
    rng = np.random.default_rng(0)
    pdf = pd.DataFrame(
        {
            "a": rng.standard_normal(400),
            "b": rng.standard_normal(400),
            "label": rng.standard_normal(400),
        }
    )
    raydp_tpu.init(app_name="mlds-holder", num_workers=2,
                   memory_per_worker="256MB")
    try:
        ds = MLDataset.from_df(
            rdf.from_pandas(pdf, num_partitions=4), num_shards=2
        )
        loader = ds.to_jax(["a", "b"], "label", batch_size=100, rank=1,
                           shuffle=False)
        assert sum(x.shape[0] for x, _ in loader) == ds.rows_per_shard
        # Shards survive worker teardown (holder ownership).
        raydp_tpu.stop(del_obj_holder=False)
        loader2 = ds.to_jax(["a"], "label", batch_size=100, rank=0,
                            shuffle=False)
        assert sum(x.shape[0] for x, _ in loader2) == ds.rows_per_shard
    finally:
        raydp_tpu.stop()


def test_refs_survive_worker_churn():
    """Refs handed across the boundary stay readable after the pool
    shrinks (holder ownership) — the from_refs frame keeps working.
    Moved from test_reverse_path.py: killing a worker must not mutate
    the shared dual-mode session that suite runs on."""
    session = raydp_tpu.init(app_name="revpath-churn", num_workers=2)
    try:
        rng = np.random.default_rng(3)
        pdf = pd.DataFrame(
            {"i": np.arange(100, dtype=np.int64),
             "v": rng.standard_normal(100)}
        )
        refs = rdf.from_pandas(pdf, num_partitions=2).to_object_refs()
        victim = session.cluster.alive_workers()[0].worker_id
        session.cluster.kill_worker(victim)
        out = (
            rdf.from_refs(refs).to_pandas()
            .sort_values("i").reset_index(drop=True)
        )
        pd.testing.assert_frame_equal(out, pdf)
    finally:
        raydp_tpu.stop()
