"""Window function / explode / monotonic-id tests — the DLRM
preprocessing op surface (SURVEY §7.3), checked against Spark semantics,
on both the local and cluster executors."""
import numpy as np
import pandas as pd
import pytest

from raydp_tpu import dataframe as rdf
from raydp_tpu.dataframe import (
    Window,
    col,
    desc,
    lag,
    lead,
    monotonically_increasing_id,
    rank,
    row_number,
    window_sum,
)


def _freq_df(parts=3):
    # (column_id, data) pairs with known counts per group.
    rows = []
    for cid, counts in [(0, {"a": 5, "b": 3, "c": 1}), (1, {"x": 4, "y": 2})]:
        for val, cnt in counts.items():
            for _ in range(cnt):
                rows.append((cid, val))
    rng = np.random.default_rng(0)
    rng.shuffle(rows)
    pdf = pd.DataFrame(rows, columns=["column_id", "data"])
    return rdf.from_pandas(pdf, num_partitions=parts)


def test_row_number_frequency_ids():
    """The DLRM id-assignment pattern: most frequent value gets id 0."""
    df = _freq_df()
    counts = df.groupBy("column_id", "data").count()
    w = Window.partitionBy("column_id").orderBy(desc("count"))
    ids = counts.withColumn("id", row_number().over(w) - 1)
    out = ids.to_pandas().sort_values(["column_id", "id"])
    got = {
        (r.column_id, r.data): r.id for r in out.itertuples()
    }
    assert got[(0, "a")] == 0 and got[(0, "b")] == 1 and got[(0, "c")] == 2
    assert got[(1, "x")] == 0 and got[(1, "y")] == 1


def test_rank_and_ties():
    pdf = pd.DataFrame(
        {"g": ["a"] * 4 + ["b"] * 2, "v": [10, 10, 5, 1, 7, 7]}
    )
    df = rdf.from_pandas(pdf, num_partitions=2)
    w = Window.partitionBy("g").orderBy(desc("v"))
    out = (
        df.withColumn("r", rank().over(w))
        .to_pandas()
        .sort_values(["g", "v"], ascending=[True, False])
    )
    assert out[out.g == "a"].r.tolist() == [1, 1, 3, 4]
    assert out[out.g == "b"].r.tolist() == [1, 1]


def test_lag_lead():
    pdf = pd.DataFrame({"g": ["a"] * 3 + ["b"] * 2, "t": [1, 2, 3, 1, 2],
                        "v": [10.0, 20.0, 30.0, 1.0, 2.0]})
    df = rdf.from_pandas(pdf, num_partitions=2)
    w = Window.partitionBy("g").orderBy("t")
    out = (
        df.withColumn("prev", lag("v", 1).over(w))
        .withColumn("next", lead("v", 1).over(w))
        .to_pandas()
        .sort_values(["g", "t"])
    )
    a = out[out.g == "a"]
    assert np.isnan(a.prev.iloc[0]) and a.prev.iloc[1:].tolist() == [10.0, 20.0]
    assert a.next.iloc[:2].tolist() == [20.0, 30.0] and np.isnan(a.next.iloc[2])


def test_window_sum():
    pdf = pd.DataFrame({"g": ["a", "a", "b"], "v": [1.0, 2.0, 5.0]})
    df = rdf.from_pandas(pdf, num_partitions=2)
    w = Window.partitionBy("g")
    out = df.withColumn("total", window_sum("v").over(w)).to_pandas()
    assert dict(zip(out.g, out.total))["b"] == 5.0
    assert out[out.g == "a"].total.tolist() == [3.0, 3.0]


def test_posexplode_groupby_count():
    """The full DLRM frequency pipeline on our engine."""
    pdf = pd.DataFrame(
        {"c0": ["u", "u", "v"], "c1": ["u", "w", "w"]}
    )
    df = rdf.from_pandas(pdf, num_partitions=2)
    melted = df.posexplode(["c0", "c1"], pos_name="column_id",
                           value_name="data")
    counts = melted.groupBy("column_id", "data").count().to_pandas()
    got = {(r.column_id, r.data): r for r in counts.itertuples()}
    assert got[(0, "u")].count == 2 and got[(0, "v")].count == 1
    assert got[(1, "w")].count == 2 and got[(1, "u")].count == 1


def test_explode_list_column():
    pdf = pd.DataFrame({"id": [1, 2], "vals": [[10, 20], [30]]})
    df = rdf.from_pandas(pdf, num_partitions=1)
    out = df.explode("vals", pos="p").to_pandas()
    assert out.vals.tolist() == [10, 20, 30]
    assert out.p.tolist() == [0, 1, 0]
    assert out.id.tolist() == [1, 1, 2]


def test_monotonically_increasing_id():
    pdf = pd.DataFrame({"v": list(range(100))})
    df = rdf.from_pandas(pdf, num_partitions=4)
    out = df.withColumn("mid", monotonically_increasing_id()).to_pandas()
    ids = out.mid.to_numpy()
    assert len(np.unique(ids)) == 100
    # ids are increasing within each partition block of 2^33
    parts = ids >> 33
    for p in np.unique(parts):
        block = ids[parts == p]
        assert (np.diff(block) > 0).all()


def test_distinct():
    pdf = pd.DataFrame({"a": [1, 1, 2, 2, 3], "b": ["x", "x", "y", "z", "z"]})
    df = rdf.from_pandas(pdf, num_partitions=3)
    out = df.distinct().to_pandas().sort_values(["a", "b"])
    assert len(out) == 4
    only_a = df.distinct(subset=["a"]).to_pandas()
    assert sorted(only_a.a.tolist()) == [1, 2, 3]


def test_window_in_select():
    """Window functions inside select() must exchange too (regression:
    silently computed per physical partition)."""
    pdf = pd.DataFrame({"g": ["a"] * 4, "v": [4, 3, 2, 1]})
    df = rdf.from_pandas(pdf, num_partitions=4)  # group split across parts
    w = Window.partitionBy("g").orderBy(desc("v"))
    out = df.select(
        col("g"), col("v"), (row_number().over(w)).alias("r")
    ).to_pandas().sort_values("v", ascending=False)
    assert out.r.tolist() == [1, 2, 3, 4]


def test_monotonic_id_in_select():
    pdf = pd.DataFrame({"v": list(range(20))})
    df = rdf.from_pandas(pdf, num_partitions=3)
    out = df.select(
        col("v"), monotonically_increasing_id().alias("id")
    ).to_pandas()
    assert out.id.nunique() == 20


def test_lag_default_keeps_genuine_nulls():
    """lag(col, n, default) fills only out-of-window holes; a real null
    value in the previous row stays null (Spark semantics)."""
    pdf = pd.DataFrame(
        {"g": ["a"] * 3, "t": [1, 2, 3], "v": [10.0, None, 30.0]}
    )
    df = rdf.from_pandas(pdf, num_partitions=1)
    w = Window.partitionBy("g").orderBy("t")
    out = (
        df.withColumn("prev", lag("v", 1, default=-1.0).over(w))
        .to_pandas().sort_values("t")
    )
    assert out.prev.iloc[0] == -1.0          # out-of-window → default
    assert out.prev.iloc[1] == 10.0
    assert np.isnan(out.prev.iloc[2])        # genuine null stays null


def test_explode_drops_null_and_empty():
    pdf = pd.DataFrame({"id": [1, 2, 3], "vals": [[10, 20], None, []]})
    df = rdf.from_pandas(pdf, num_partitions=1)
    out = df.explode("vals", pos="p").to_pandas()
    assert out.id.tolist() == [1, 1]
    assert out.vals.tolist() == [10, 20]
    out2 = df.explode("vals").to_pandas()
    assert out2.id.tolist() == [1, 1]


def test_chained_windows_exchange_once():
    """Two window columns on the same spec shuffle once (elision)."""
    pdf = pd.DataFrame({"g": ["a", "b"] * 8, "v": list(range(16))})
    df = rdf.from_pandas(pdf, num_partitions=4)
    calls = []
    orig_exchange = type(df._executor).exchange
    orig_coalesced = type(df._executor).run_coalesced

    def counting_exchange(self, *a, **k):
        calls.append("exchange")
        return orig_exchange(self, *a, **k)

    def counting_coalesced(self, *a, **k):
        calls.append("coalesced")
        return orig_coalesced(self, *a, **k)

    w = Window.partitionBy("g").orderBy("v")
    import unittest.mock as mock

    # Small data takes the adaptive coalesce instead of a hash exchange;
    # either way the co-location step must run exactly ONCE for both
    # window columns.
    with mock.patch.object(
        type(df._executor), "exchange", counting_exchange
    ), mock.patch.object(
        type(df._executor), "run_coalesced", counting_coalesced
    ):
        out = (
            df.withColumn("r", row_number().over(w))
            .withColumn("prev", lag("v").over(w))
            .to_pandas()
        )
    assert len(calls) == 1, f"expected 1 co-location op, saw {calls}"
    a = out[out.g == "a"].sort_values("v")
    assert a.r.tolist() == list(range(1, 9))


def test_window_in_filter_dedup_idiom():
    """The Spark dedup pattern filter(row_number().over(w) == 1) must
    exchange groups first (regression: silently kept one row per
    physical partition per group)."""
    pdf = pd.DataFrame({"g": ["a"] * 6, "v": [6, 5, 4, 3, 2, 1]})
    df = rdf.from_pandas(pdf, num_partitions=3)
    w = Window.partitionBy("g").orderBy(desc("v"))
    out = df.filter(row_number().over(w) == 1).to_pandas()
    assert len(out) == 1 and out.v.iloc[0] == 6


def test_key_overwrite_clears_colocation():
    """Overwriting or renaming a window key must clear the cached
    exchange keys so the next window op re-shuffles."""
    pdf = pd.DataFrame({"g": ["a", "b"] * 8, "v": list(range(16))})
    df = rdf.from_pandas(pdf, num_partitions=4)
    step1 = df.withColumn(
        "r", row_number().over(Window.partitionBy("g").orderBy("v"))
    )
    assert step1._exchange_keys == ("g",)
    assert step1.withColumn("g", col("v") % 2)._exchange_keys is None
    assert step1.withColumnRenamed("g", "h")._exchange_keys is None
    # filter keeps co-location (row subset)
    assert step1.filter(col("v") > 3)._exchange_keys == ("g",)
    out = (
        step1.withColumn("g", col("v") % 2)
        .withColumn("tot", window_sum("v").over(Window.partitionBy("g")))
        .to_pandas()
    )
    want = out.groupby("g").v.transform("sum")
    assert (out.tot == want).all()


def test_rank_with_nulls():
    pdf = pd.DataFrame({"g": ["a"] * 4, "v": [3.0, None, 1.0, 2.0]})
    df = rdf.from_pandas(pdf, num_partitions=2)
    w = Window.partitionBy("g").orderBy("v")
    out = df.withColumn("r", rank().over(w)).to_pandas()
    got = dict(zip(out.v.fillna(-1), out.r))
    # Spark: nulls first ascending → null ranks 1, then 1.0→2, 2.0→3, 3.0→4
    assert got[-1] == 1 and got[1.0] == 2 and got[2.0] == 3 and got[3.0] == 4


def test_row_number_null_ordering():
    """Spark orders nulls first on ascending keys — row_number and rank
    must agree on which row is first."""
    pdf = pd.DataFrame({"g": ["a"] * 4, "v": [3.0, None, 1.0, 2.0]})
    df = rdf.from_pandas(pdf, num_partitions=2)
    w = Window.partitionBy("g").orderBy("v")
    out = (
        df.withColumn("rn", row_number().over(w))
        .withColumn("rk", rank().over(w))
        .to_pandas()
    )
    null_row = out[out.v.isna()].iloc[0]
    assert null_row.rn == 1 and null_row.rk == 1
    # Descending: nulls last.
    w2 = Window.partitionBy("g").orderBy(desc("v"))
    out2 = df.withColumn("rn", row_number().over(w2)).to_pandas()
    assert out2[out2.v.isna()].rn.iloc[0] == 4


def test_window_sum_range_frame_ties():
    """Spark's default frame is RANGE: peer rows (tied order keys) all
    receive the full peer-inclusive running total."""
    pdf = pd.DataFrame({"g": ["a"] * 3, "t": [1, 1, 2],
                        "v": [1.0, 2.0, 3.0]})
    df = rdf.from_pandas(pdf, num_partitions=1)
    w = Window.partitionBy("g").orderBy("t")
    out = df.withColumn("run", window_sum("v").over(w)).to_pandas()
    got = sorted(zip(out.t, out.run))
    assert got == [(1, 3.0), (1, 3.0), (2, 6.0)]


def test_window_sum_range_frame_negative_values():
    """Peer-group total is the LAST cumsum value, not the max — with
    negative values cumsum is not monotone (regression: transform("max")
    overstated the total)."""
    pdf = pd.DataFrame({"g": ["a"] * 3, "t": [1, 1, 2],
                        "v": [5.0, -2.0, 1.0]})
    df = rdf.from_pandas(pdf, num_partitions=1)
    w = Window.partitionBy("g").orderBy("t")
    out = df.withColumn("run", window_sum("v").over(w)).to_pandas()
    got = sorted(zip(out.t, out.run))
    assert got == [(1, 3.0), (1, 3.0), (2, 4.0)]


def test_window_sum_all_null_peer_group_carries_total_forward():
    """A peer group whose values are all null keeps the prior running
    total (Spark: sum over a frame ignores nulls); leading null frames
    stay null."""
    pdf = pd.DataFrame({
        "g": ["a"] * 3 + ["b"],
        "t": [1, 2, 3, 1],
        "v": [1.0, None, 2.0, None],
    })
    df = rdf.from_pandas(pdf, num_partitions=1)
    w = Window.partitionBy("g").orderBy("t")
    out = df.withColumn("run", window_sum("v").over(w)).to_pandas()
    a = out[out.g == "a"].sort_values("t")
    assert a.run.tolist() == [1.0, 1.0, 3.0]
    assert np.isnan(out[out.g == "b"].run.iloc[0])


def test_window_sum_running_with_orderby():
    pdf = pd.DataFrame({"g": ["a"] * 3 + ["b"], "t": [1, 2, 3, 1],
                        "v": [1.0, 2.0, 3.0, 5.0]})
    df = rdf.from_pandas(pdf, num_partitions=2)
    w = Window.partitionBy("g").orderBy("t")
    out = df.withColumn("run", window_sum("v").over(w)).to_pandas()
    a = out[out.g == "a"].sort_values("t")
    assert a.run.tolist() == [1.0, 3.0, 6.0]       # running sum
    assert out[out.g == "b"].run.tolist() == [5.0]


@pytest.fixture(scope="module")
def session():
    import raydp_tpu

    s = raydp_tpu.init(app_name="wintest", num_workers=2,
                       memory_per_worker="256MB")
    yield s
    raydp_tpu.stop()


def test_window_on_cluster_executor(session):
    """Window + posexplode runs through real ETL workers + shm store."""
    pdf = pd.DataFrame(
        # No two values of a column tie: row_number over equal counts
        # follows the order the workers' partitions arrive in.
        {"c0": ["u"] * 4 + ["v"] * 2, "c1": ["w"] * 4 + ["u"] * 2}
    )
    df = rdf.from_pandas(pdf, num_partitions=2)
    melted = df.posexplode(["c0", "c1"], pos_name="column_id",
                           value_name="data")
    counts = melted.groupBy("column_id", "data").count()
    w = Window.partitionBy("column_id").orderBy(desc("count"))
    out = counts.withColumn("id", row_number().over(w) - 1).to_pandas()
    got = {(r.column_id, r.data): r.id for r in out.itertuples()}
    assert got[(0, "u")] == 0 and got[(0, "v")] == 1
    assert got[(1, "w")] == 0 and got[(1, "u")] == 1


def test_window_min_max_mean_count_whole_partition():
    import numpy as np
    import pandas as pd

    from raydp_tpu.dataframe import (
        Window,
        window_count,
        window_max,
        window_mean,
        window_min,
    )

    rng = np.random.default_rng(2)
    pdf = pd.DataFrame(
        {"k": rng.integers(0, 5, 300), "v": rng.standard_normal(300)}
    )
    pdf.loc[::17, "v"] = np.nan
    w = Window.partitionBy("k")
    out = (
        rdf.from_pandas(pdf, num_partitions=3)
        .withColumn("mn", window_min("v").over(w))
        .withColumn("mx", window_max("v").over(w))
        .withColumn("avg", window_mean("v").over(w))
        .withColumn("cnt", window_count("v").over(w))
        .to_pandas()
    )
    g = pdf.groupby("k")["v"]
    for k, sub in out.groupby("k"):
        assert np.allclose(sub["mn"], g.min()[k])
        assert np.allclose(sub["mx"], g.max()[k])
        assert np.allclose(sub["avg"], g.mean()[k])
        assert (sub["cnt"] == g.count()[k]).all()


def test_window_running_aggregates_with_order():
    import numpy as np
    import pandas as pd

    from raydp_tpu.dataframe import Window, window_max, window_mean

    pdf = pd.DataFrame(
        {
            "k": [0, 0, 0, 0, 1, 1],
            "t": [1, 2, 3, 4, 1, 2],
            "v": [5.0, 1.0, 7.0, 3.0, 2.0, 8.0],
        }
    )
    w = Window.partitionBy("k").orderBy("t")
    out = (
        rdf.from_pandas(pdf, num_partitions=2)
        .withColumn("runmax", window_max("v").over(w))
        .withColumn("runavg", window_mean("v").over(w))
        .to_pandas()
        .sort_values(["k", "t"])
        .reset_index(drop=True)
    )
    assert out["runmax"].tolist() == [5.0, 5.0, 7.0, 7.0, 2.0, 8.0]
    assert np.allclose(
        out["runavg"], [5.0, 3.0, 13 / 3, 4.0, 2.0, 5.0]
    )


def test_chained_window_reads_prior_window_column():
    """A second window expr may reference the column the first stage
    created (frame cache must not serve a table lacking it)."""
    pdf = pd.DataFrame({"g": ["a", "a", "a", "b", "b"], "v": [3, 1, 2, 5, 4]})
    df = rdf.from_pandas(pdf, num_partitions=2)
    w = Window.partitionBy("g").orderBy("v")
    out = (
        df.withColumn("r", row_number().over(w))
        .withColumn("prev_r", lag("r").over(w))
        .to_pandas()
        .sort_values(["g", "v"])
        .reset_index(drop=True)
    )
    assert out["r"].tolist() == [1, 2, 3, 1, 2]
    assert out["prev_r"].fillna(-1).tolist() == [-1, 1, 2, -1, 1]


def test_window_sum_big_int64_exact_and_dtype():
    """Null-free int64 aggregates exactly (no float64 2^53 cliff) and
    keeps an integer dtype (review r3 findings 1/4)."""
    big = 2**53 + 1
    pdf = pd.DataFrame(
        {"g": ["a", "a", "b"], "t": [1, 2, 1], "v": [big, 1, 7]}
    )
    df = rdf.from_pandas(pdf, num_partitions=1)
    w = Window.partitionBy("g").orderBy("t")
    out = (
        df.withColumn("rs", window_sum("v").over(w))
        .to_pandas()
        .sort_values(["g", "t"])
    )
    assert out.rs.dtype.kind in "iu"
    assert out.rs.tolist() == [big, big + 1, 7]
    # whole-partition frame too
    w2 = Window.partitionBy("g")
    out2 = df.withColumn("tot", window_sum("v").over(w2)).to_pandas()
    assert out2.tot.dtype.kind in "iu"
    assert dict(zip(out2.g, out2.tot))["a"] == big + 1


def test_window_sum_valid_nan_does_not_poison_running_sum():
    """A NaN VALUE (valid, not null) is skipped like pandas' skipna
    cumsum — it must not poison the rest of the group (review r3 #2)."""
    pdf = pd.DataFrame(
        {
            "g": ["a"] * 4,
            "t": [1, 2, 3, 4],
            "v": [1.0, np.nan, 2.0, 3.0],
        }
    )
    df = rdf.from_pandas(pdf, num_partitions=1)
    w = Window.partitionBy("g").orderBy("t")
    out = (
        df.withColumn("rs", window_sum("v").over(w))
        .to_pandas()
        .sort_values("t")
    )
    assert out.rs.tolist() == [1.0, 1.0, 3.0, 6.0]
