"""DataFrame engine on the cluster backend: stages ship to real worker
processes, partitions live in the shm object store (parity with reference
Spark-executor execution, test_spark_cluster.py:70-98 round-trip)."""
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import raydp_tpu
import raydp_tpu.dataframe as rdf
from raydp_tpu.dataframe import col
from raydp_tpu.dataframe.executor import ClusterExecutor

from tests.test_dataframe import _fake_taxi, nyc_taxi_preprocess


@pytest.fixture(scope="module")
def session():
    s = raydp_tpu.init(app_name="dftest", num_workers=2,
                       memory_per_worker="256MB")
    yield s
    raydp_tpu.stop()


def test_cluster_executor_selected(session):
    df = rdf.from_pandas(pd.DataFrame({"a": np.arange(10)}), num_partitions=2)
    assert isinstance(df._executor, ClusterExecutor)
    assert df.count() == 10


def test_taxi_pipeline_on_cluster(session):
    raw = rdf.from_pandas(_fake_taxi(1500, seed=3), num_partitions=4)
    assert isinstance(raw._executor, ClusterExecutor)
    result = nyc_taxi_preprocess(raw).to_pandas()
    assert len(result) > 0
    assert "manhattan" in result.columns

    # Cluster execution must equal local execution row-for-row.
    from raydp_tpu.dataframe.executor import LocalExecutor
    from raydp_tpu.dataframe.io import _distribute

    local_raw = _distribute(
        rdf.from_pandas(_fake_taxi(1500, seed=3)).collect_partitions(),
        executor=LocalExecutor(),
    )
    local = nyc_taxi_preprocess(local_raw).to_pandas()
    assert len(result) == len(local)
    assert sorted(result.columns) == sorted(local.columns)


def test_groupby_on_cluster(session):
    df = rdf.from_pandas(
        pd.DataFrame(
            {"k": ["a", "b", "a", "c", "b", "a"], "v": [1, 2, 3, 4, 5, 6]}
        ),
        num_partitions=3,
    )
    out = df.groupBy("k").agg(("v", "sum")).to_pandas().set_index("k")
    assert out.loc["a", "sum(v)"] == 10
    assert out.loc["b", "sum(v)"] == 7
    assert out.loc["c", "sum(v)"] == 4


def test_random_split_disjoint_on_cluster(session):
    big = rdf.range(2000, num_partitions=4)
    a, b = big.random_split([0.7, 0.3], seed=11)
    ids_a = set(a.to_pandas()["id"])
    ids_b = set(b.to_pandas()["id"])
    assert len(ids_a) + len(ids_b) == 2000
    assert not (ids_a & ids_b)


def test_to_object_refs_with_ownership(session):
    df = rdf.range(100, num_partitions=2)
    refs = df.to_object_refs(owner_transfer=True)
    store = session.cluster.master.store
    assert all(r.owner == "__holder__" for r in (store.get_ref(x.object_id) for x in refs))
    total = sum(store.get_arrow_table(r).num_rows for r in refs)
    assert total == 100


def test_distributed_file_scan(tmp_path, session):
    """Under cluster execution, read_parquet/read_csv ship split specs to
    WORKERS (executor-side scan, Spark's input-split model) — partitions
    come back as ObjectRefs, one per row group / file."""
    import pyarrow.parquet as pq

    from raydp_tpu.store.object_store import ObjectRef

    pdf = pd.DataFrame(
        {"a": np.arange(8_000), "b": np.random.randn(8_000)}
    )
    for i in range(2):
        pq.write_table(
            pa.Table.from_pandas(
                pdf.iloc[i * 4000:(i + 1) * 4000], preserve_index=False
            ),
            str(tmp_path / f"p{i}.parquet"),
            row_group_size=2000,
        )
    pdf.to_csv(str(tmp_path / "all.csv"), index=False)

    df = rdf.read_parquet(str(tmp_path / "*.parquet"), num_partitions=4)
    assert all(isinstance(p, ObjectRef) for p in df._parts)
    assert df.num_partitions == 4
    out = df.to_pandas().sort_values("a").reset_index(drop=True)
    assert out["a"].tolist() == pdf["a"].tolist()

    dfc = rdf.read_csv(str(tmp_path / "all.csv"))
    assert all(isinstance(p, ObjectRef) for p in dfc._parts)
    assert dfc.count() == 8_000


def test_union_mixed_executors(session):
    """Union (and binary ops generally) must coerce a local frame's
    partitions into the cluster executor instead of mixing raw tables
    with ObjectRefs."""
    from raydp_tpu.dataframe.executor import LocalExecutor
    from raydp_tpu.store.object_store import ObjectRef

    cluster_df = rdf.from_pandas(
        pd.DataFrame({"x": [1, 2, 3]}), num_partitions=2
    )
    assert all(isinstance(p, ObjectRef) for p in cluster_df._flush()._parts)
    local_df = rdf.DataFrame(
        [pa.table({"x": [4, 5]})], LocalExecutor()
    )
    out = cluster_df.union(local_df)
    assert sorted(out.to_pandas()["x"].tolist()) == [1, 2, 3, 4, 5]
    # and the reverse direction: cluster parts materialize into local
    out2 = local_df.union(cluster_df)
    assert sorted(out2.to_pandas()["x"].tolist()) == [1, 2, 3, 4, 5]


def _ingest_counters():
    from raydp_tpu.utils.profiling import metrics

    counters = metrics.snapshot()["counters"]
    return (counters.get("df/ingest_partitions_local", 0.0),
            counters.get("df/ingest_partitions_shipped", 0.0))


def test_ingest_on_the_drivers_node_is_the_drivers_put(session, monkeypatch):
    """Every worker sits on the driver's node, so there is nothing to
    place: ``from_pandas`` submits NO task, each partition is the
    driver's own holder-owned put of a slice (the slice's bytes, not the
    parent's), in order — and, since no worker wrote them, the frame
    computes after every worker alive at ingest is gone. LAST in this
    module: it replaces the session's workers."""
    from raydp_tpu.store.object_store import OWNER_HOLDER, ObjectRef
    from raydp_tpu.telemetry import recorder

    cluster = session.cluster
    n = 20_003
    rng = np.random.default_rng(5)
    pdf = pd.DataFrame({
        "i": np.arange(n, dtype=np.int64),
        "k": rng.integers(0, 11, n),
        "v": rng.standard_normal(n),
    })

    def no_task(*args, **kwargs):
        raise AssertionError("ingest on the driver's node submitted a task")

    local0, shipped0 = _ingest_counters()
    with monkeypatch.context() as m:
        m.setattr(cluster, "submit_async", no_task)
        m.setattr(cluster, "submit_batch", no_task)
        df = rdf.from_pandas(pdf, num_partitions=8)
    local1, shipped1 = _ingest_counters()
    assert (local1 - local0, shipped1 - shipped0) == (8, 0)
    sp = [s for s in recorder.spans() if s.name == "df/from_pandas"][-1]
    assert sp.attrs == {"rows": n, "partitions": 8, "local": 8}

    store = cluster.master.store
    refs = df._parts
    assert all(isinstance(r, ObjectRef) for r in refs)
    assert {(r.owner, r.node_id) for r in refs} == {
        (OWNER_HOLDER, store.node_id)
    }
    assert [store.get_ref(r.object_id) for r in refs] == refs
    # rows and order: partition j is the parent's j-th row range
    assert [r.num_rows for r in refs] == [2501] * 3 + [2500] * 5
    got = pd.concat(
        [store.get_arrow_table(r).to_pandas() for r in refs],
        ignore_index=True,
    )
    pd.testing.assert_frame_equal(got, pdf)
    # a partition costs its slice, not the 8x larger parent
    whole = store.put_arrow_table(pa.Table.from_pandas(pdf))
    assert max(r.size for r in refs) < whole.size / 7
    store.delete(whole)

    expected = pdf.groupby("k", as_index=False)["v"].sum()
    at_ingest = [w.worker_id for w in cluster.alive_workers()]
    for wid in at_ingest:
        cluster.kill_worker(wid)
    cluster.request_workers(len(at_ingest))
    assert not {w.worker_id for w in cluster.alive_workers()} & set(at_ingest)
    assert all(store.contains(r) for r in refs)
    out = (
        df.groupBy("k").agg({"v": "sum"}).to_pandas()
        .sort_values("k").reset_index(drop=True)
    )
    assert out["k"].tolist() == expected["k"].tolist()
    assert np.allclose(out["sum(v)"].to_numpy(), expected["v"].to_numpy())
