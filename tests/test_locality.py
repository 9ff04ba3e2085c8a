"""Locality-aware shard assignment (VERDICT r1 item 5).

Shard plans keep bytes node-local on a 2-virtual-host layout while
preserving every divide_blocks invariant (equal samples per rank, full
coverage, in-bounds slices). Reference behavior being matched:
locality-preferring shard selection in to_torch
(python/raydp/spark/dataset.py:411-443) and RDD preferred locations
(rdd/RayDatasetRDD.scala:53-55).
"""
import numpy as np
import pandas as pd
import pytest

import raydp_tpu
import raydp_tpu.dataframe as rdf
from raydp_tpu.data import MLDataset
from raydp_tpu.dataframe.scheduler import resolve
from raydp_tpu.utils.sharding import (
    assignment_sample_counts,
    divide_blocks_local,
    locality_fraction,
)


def _coverage(assignment, blocks):
    seen = [np.zeros(b, dtype=bool) for b in blocks]
    for plan in assignment.values():
        for s in plan:
            assert s.offset >= 0
            assert s.offset + s.num_samples <= blocks[s.block_index]
            seen[s.block_index][s.offset:s.offset + s.num_samples] = True
    return all(arr.all() for arr in seen)


def test_balanced_layout_is_fully_local():
    blocks = [100, 100, 100, 100]
    nodes = ["node-0", "node-0", "node-1", "node-1"]
    ranks = ["node-0", "node-1"]
    plan = divide_blocks_local(blocks, 2, nodes, ranks)
    counts = assignment_sample_counts(plan)
    assert set(counts.values()) == {200}
    assert _coverage(plan, blocks)
    assert locality_fraction(plan, nodes, ranks) == 1.0


def test_imbalanced_layout_spills_minimum():
    # node-0 holds 75% of rows but only half the ranks: one node-1 rank
    # must read remotely, everything else stays local.
    blocks = [300, 300, 100, 100]
    nodes = ["node-0", "node-0", "node-1", "node-1"]
    ranks = ["node-0", "node-0", "node-1", "node-1"]
    plan = divide_blocks_local(blocks, 4, nodes, ranks)
    counts = assignment_sample_counts(plan)
    assert set(counts.values()) == {200}
    assert _coverage(plan, blocks)
    frac = locality_fraction(plan, nodes, ranks)
    # 600 local to node-0 ranks (400 capacity... they take 400 local),
    # node-1 ranks have 200 local + 200 remote: optimum = 750/800
    assert frac >= 0.74, frac


def test_uneven_blocks_invariants_hold():
    rng = np.random.default_rng(0)
    blocks = [int(b) for b in rng.integers(1, 500, size=13)]
    nodes = [f"node-{i % 3}" for i in range(13)]
    ranks = ["node-0", "node-1", "node-2", "node-0", "node-1"]
    plan = divide_blocks_local(blocks, 5, nodes, ranks, shuffle=True,
                               shuffle_seed=7)
    counts = assignment_sample_counts(plan)
    expected = -(-sum(blocks) // 5)
    assert set(counts.values()) == {expected}
    assert _coverage(plan, blocks)


def test_determinism():
    blocks = [50, 60, 70, 80]
    nodes = ["node-0", "node-1", "node-0", "node-1"]
    ranks = ["node-0", "node-1"]
    a = divide_blocks_local(blocks, 2, nodes, ranks, shuffle=True, shuffle_seed=3)
    b = divide_blocks_local(blocks, 2, nodes, ranks, shuffle=True, shuffle_seed=3)
    assert a == b


def test_mldataset_locality_on_two_hosts():
    session = raydp_tpu.init(
        app_name="locality-test", num_workers=2, num_virtual_nodes=2
    )
    try:
        rng = np.random.default_rng(1)
        pdf = pd.DataFrame(
            {"a": rng.standard_normal(4000), "y": rng.standard_normal(4000)}
        )
        df = rdf.from_pandas(pdf, num_partitions=4)
        ds = MLDataset.from_df(
            df, num_shards=2, rank_nodes=["node-0", "node-1"]
        )
        assert set(ds.block_nodes) == {"node-0", "node-1"}
        assert ds.locality() == 1.0  # balanced ingest → fully local plan
        # shards still materialize correctly through the resolver
        total = sum(
            len(ds.shard_columns(r, ["a"])["a"]) for r in range(2)
        )
        assert total == 2 * ds.rows_per_shard
    finally:
        raydp_tpu.stop()


def test_stage_stays_on_each_partitions_node_after_mixed_ingest():
    """Two of four partitions are the driver's own put (node-0), two are
    shipped to node-1; a narrow stage runs each partition where its
    bytes are, so every output block is on its input's node."""
    raydp_tpu.init(
        app_name="locality-ingest", num_workers=2, num_virtual_nodes=2
    )
    try:
        pdf = pd.DataFrame({"a": np.arange(4000, dtype=np.float64)})
        df = rdf.from_pandas(pdf, num_partitions=4)
        src_nodes = [r.node_id for r in df._parts]
        assert sorted(src_nodes) == ["node-0", "node-0", "node-1", "node-1"]
        out = df.withColumn("b", rdf.col("a") + 1.0).persist()
        out_refs = resolve(out._parts)
        assert [r.node_id for r in out_refs] == src_nodes
        got = out.to_pandas()
        assert got["a"].tolist() == pdf["a"].tolist()
        assert got["b"].tolist() == (pdf["a"] + 1.0).tolist()
    finally:
        raydp_tpu.stop()


def test_mldataset_without_topology_unchanged():
    import pyarrow as pa

    tables = [pa.table({"x": list(range(10))}) for _ in range(4)]
    ds = MLDataset(tables, num_shards=2)
    assert ds.locality() is None
    assert sum(s.num_samples for s in ds.shard_plan[0]) == 20
