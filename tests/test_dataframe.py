"""DataFrame engine tests: expression ops, wide ops, IO, and the NYC-taxi
preprocessing pipeline (op-surface parity with reference
examples/data_process.py:9-94)."""
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import raydp_tpu.dataframe as rdf
from raydp_tpu.dataframe import col, lit, udf, when
from raydp_tpu.dataframe import hour, dayofweek, dayofmonth, month, year


@pytest.fixture()
def people():
    return rdf.from_pandas(
        pd.DataFrame(
            {
                "name": ["ann", "bob", "cat", "dan", "eve", "fay"],
                "age": [34, 21, 45, 21, 60, 17],
                "city": ["nyc", "sf", "nyc", "la", "sf", "nyc"],
                "income": [90.0, 70.0, None, 50.0, 120.0, 10.0],
            }
        ),
        num_partitions=3,
    )


def test_select_filter_withcolumn(people):
    out = (
        people.filter(col("age") >= 21)
        .withColumn("age2", col("age") * 2)
        .select("name", "age2")
        .to_pandas()
    )
    assert list(out.columns) == ["name", "age2"]
    assert out["age2"].tolist() == [68, 42, 90, 42, 120]


def test_filter_col_vs_col(people):
    out = people.filter(col("age") > col("income")).to_pandas()
    assert set(out["name"]) == {"eve" if False else "fay"}  # 17 > 10


def test_drop_fillna_dropna(people):
    assert "income" not in people.drop("income").columns
    filled = people.fillna({"income": 0.0}).to_pandas()
    assert filled["income"].isna().sum() == 0
    dropped = people.dropna(subset=["income"])
    assert dropped.count() == 5


def test_when_case(people):
    out = people.withColumn(
        "bracket",
        when(col("age") >= 60, "senior").when(col("age") >= 21, "adult")
        .otherwise("minor"),
    ).to_pandas()
    assert out.set_index("name")["bracket"].to_dict() == {
        "ann": "adult", "bob": "adult", "cat": "adult",
        "dan": "adult", "eve": "senior", "fay": "minor",
    }


def test_udf(people):
    @udf("int")
    def square(x):
        return int(x * x)

    out = people.withColumn("sq", square("age")).to_pandas()
    assert out["sq"].tolist() == [x * x for x in out["age"].tolist()]


def test_groupby_count_sum_mean(people):
    out = (
        people.groupBy("city")
        .agg(("age", "sum"), ("age", "mean"), ("*", "count"))
        .to_pandas()
        .set_index("city")
        .sort_index()
    )
    assert out.loc["nyc", "sum(age)"] == 34 + 45 + 17
    assert out.loc["sf", "mean(age)"] == pytest.approx((21 + 60) / 2)
    assert out.loc["la", "count"] == 1


def test_groupby_min_max(people):
    out = (
        people.groupBy("city").agg(("age", "min"), ("age", "max"))
        .to_pandas().set_index("city")
    )
    assert out.loc["nyc", "min(age)"] == 17
    assert out.loc["nyc", "max(age)"] == 45


def test_join(people):
    lookup = rdf.from_items(
        [
            {"city": "nyc", "state": "NY"},
            {"city": "sf", "state": "CA"},
        ]
    )
    inner = people.join(lookup, on="city").to_pandas()
    assert len(inner) == 5  # la dropped
    left = people.join(lookup, on="city", how="left").to_pandas()
    assert len(left) == 6
    assert left.loc[left["city"] == "la", "state"].isna().all()


def test_orderby_multi_partition():
    rng = np.random.default_rng(0)
    df = rdf.from_pandas(
        pd.DataFrame({"x": rng.permutation(1000), "y": rng.standard_normal(1000)}),
        num_partitions=5,
    )
    out = df.orderBy("x").to_pandas()
    assert out["x"].tolist() == sorted(out["x"].tolist())
    desc = df.orderBy("x", ascending=False).to_pandas()
    assert desc["x"].tolist() == sorted(desc["x"].tolist(), reverse=True)


def test_repartition_union_limit(people):
    rep = people.repartition(2)
    assert rep.num_partitions == 2
    assert rep.count() == 6
    both = people.union(people)
    assert both.count() == 12
    assert both.limit(7).count() == 7


def test_random_split(people):
    big = rdf.range(5000, num_partitions=4)
    a, b = big.random_split([0.8, 0.2], seed=7)
    na, nb = a.count(), b.count()
    assert na + nb == 5000
    assert 0.75 * 5000 < na < 0.85 * 5000
    # deterministic given same seed
    a2, _ = big.random_split([0.8, 0.2], seed=7)
    assert a2.count() == na
    # splits are disjoint: ids don't overlap
    ids_a = set(a.to_pandas()["id"])
    ids_b = set(b.to_pandas()["id"])
    assert not (ids_a & ids_b)


def test_csv_parquet_roundtrip(tmp_path):
    df = pd.DataFrame(
        {"a": np.arange(100), "b": np.random.default_rng(1).standard_normal(100)}
    )
    csv_path = tmp_path / "data.csv"
    df.to_csv(csv_path, index=False)
    loaded = rdf.read_csv(str(csv_path), num_partitions=3)
    assert loaded.count() == 100
    assert loaded.num_partitions == 3

    pq_dir = tmp_path / "pq"
    loaded.write_parquet(str(pq_dir))
    back = rdf.read_parquet(str(pq_dir))
    assert back.count() == 100
    assert set(back.columns) == {"a", "b"}


def test_schema_and_peek(people):
    s = people.withColumn("x", col("age") + 1).schema
    assert "x" in s.names


def test_datetime_functions():
    df = rdf.from_pandas(
        pd.DataFrame(
            {
                "ts": pd.to_datetime(
                    ["2015-02-18 14:30:00", "2020-12-31 23:59:59"]
                )
            }
        )
    )
    out = (
        df.withColumn("y", year(col("ts")))
        .withColumn("m", month(col("ts")))
        .withColumn("d", dayofmonth(col("ts")))
        .withColumn("h", hour(col("ts")))
        .withColumn("dow", dayofweek(col("ts")))
        .to_pandas()
    )
    assert out["y"].tolist() == [2015, 2020]
    assert out["m"].tolist() == [2, 12]
    assert out["d"].tolist() == [18, 31]
    assert out["h"].tolist() == [14, 23]
    # 2015-02-18 is a Wednesday → Spark dayofweek = 4
    assert out["dow"].tolist()[0] == 4


def test_string_timestamps_parse():
    df = rdf.from_items([{"ts": "2015-02-18 14:30:00"}])
    out = df.withColumn("h", hour(col("ts"))).to_pandas()
    assert out["h"].tolist() == [14]


def _fake_taxi(n=2000, seed=0):
    rng = np.random.default_rng(seed)
    return pd.DataFrame(
        {
            "key": np.arange(n).astype(str),
            "fare_amount": rng.uniform(-5, 300, n),
            "pickup_datetime": pd.to_datetime(
                rng.integers(1420070400, 1483228800, n), unit="s"
            ),
            "pickup_longitude": rng.uniform(-77, -71, n),
            "pickup_latitude": rng.uniform(37, 43, n),
            "dropoff_longitude": rng.uniform(-77, -71, n),
            "dropoff_latitude": rng.uniform(37, 43, n),
            "passenger_count": rng.integers(0, 9, n),
        }
    )


def nyc_taxi_preprocess(data):
    """The reference pipeline, expressed in this engine
    (reference: examples/data_process.py:9-94)."""
    from raydp_tpu.dataframe import col, udf, lit

    data = (
        data.filter(col("pickup_longitude") <= -72)
        .filter(col("pickup_longitude") >= -76)
        .filter(col("dropoff_longitude") <= -72)
        .filter(col("dropoff_longitude") >= -76)
        .filter(col("pickup_latitude") <= 42)
        .filter(col("pickup_latitude") >= 38)
        .filter(col("dropoff_latitude") <= 42)
        .filter(col("dropoff_latitude") >= 38)
        .filter(col("passenger_count") <= 6)
        .filter(col("passenger_count") >= 1)
        .filter(col("fare_amount") > 0)
        .filter(col("fare_amount") < 250)
        .filter(col("dropoff_longitude") != col("pickup_longitude"))
        .filter(col("dropoff_latitude") != col("pickup_latitude"))
    )
    data = (
        data.withColumn("day", dayofmonth(col("pickup_datetime")))
        .withColumn("hour_of_day", hour(col("pickup_datetime")))
        .withColumn("day_of_week", dayofweek(col("pickup_datetime")) - 2)
        .withColumn("month_of_year", month(col("pickup_datetime")))
        .withColumn("year", year(col("pickup_datetime")))
    )

    @udf("int")
    def night(h, weekday):
        return int(16 <= h <= 20 and weekday < 5)

    data = data.withColumn("night", night("hour_of_day", "day_of_week"))
    data = (
        data.withColumn(
            "abs_diff_longitude",
            abs(col("dropoff_longitude") - col("pickup_longitude")),
        )
        .withColumn(
            "abs_diff_latitude",
            abs(col("dropoff_latitude") - col("pickup_latitude")),
        )
        .withColumn(
            "manhattan", col("abs_diff_latitude") + col("abs_diff_longitude")
        )
    )
    return data.drop(
        "pickup_datetime",
        "pickup_longitude",
        "pickup_latitude",
        "dropoff_longitude",
        "dropoff_latitude",
        "passenger_count",
        "key",
    )


def test_nyc_taxi_pipeline_local():
    raw = rdf.from_pandas(_fake_taxi(), num_partitions=4)
    out = nyc_taxi_preprocess(raw)
    result = out.to_pandas()
    assert len(result) > 0
    assert "manhattan" in result.columns
    assert "pickup_datetime" not in result.columns
    assert (result["fare_amount"] > 0).all()
    assert result["night"].isin([0, 1]).all()
    # equivalence against pandas reference computation
    pdf = _fake_taxi()
    mask = (
        (pdf.pickup_longitude <= -72) & (pdf.pickup_longitude >= -76)
        & (pdf.dropoff_longitude <= -72) & (pdf.dropoff_longitude >= -76)
        & (pdf.pickup_latitude <= 42) & (pdf.pickup_latitude >= 38)
        & (pdf.dropoff_latitude <= 42) & (pdf.dropoff_latitude >= 38)
        & (pdf.passenger_count <= 6) & (pdf.passenger_count >= 1)
        & (pdf.fare_amount > 0) & (pdf.fare_amount < 250)
        & (pdf.dropoff_longitude != pdf.pickup_longitude)
        & (pdf.dropoff_latitude != pdf.pickup_latitude)
    )
    assert len(result) == int(mask.sum())


def test_error_messages():
    df = rdf.from_items([{"a": 1}])
    with pytest.raises(KeyError, match="'b'"):
        df.select(col("b")).to_pandas()
    with pytest.raises(ValueError):
        df.join(df, on="a", how="sideways")
    with pytest.raises(ValueError):
        df.random_split([])
    with pytest.raises(FileNotFoundError):
        rdf.read_csv("/nonexistent/*.csv")


def test_groupby_count_null_keys():
    t = pa.table({"k": ["a", None, None], "v": [1, 2, 3]})
    out = rdf.from_arrow(t).groupBy("k").count().to_pandas()
    keys = [None if pd.isna(x) else x for x in out["k"].tolist()]
    counts = dict(zip(keys, out["count"]))
    assert counts[None] == 2  # null group counts ROWS, Spark semantics
    assert counts["a"] == 1


def test_select_duplicate_names_rejected():
    df = rdf.from_items([{"x": 1}])
    with pytest.raises(ValueError, match="duplicate"):
        df.select("x", (col("x") + 1).alias("x"))


def test_agg_stddev_variance_matches_pandas():
    import numpy as np
    import pandas as pd

    rng = np.random.default_rng(5)
    pdf = pd.DataFrame(
        {"k": rng.integers(0, 4, 500), "v": rng.standard_normal(500) * 3}
    )
    out = (
        rdf.from_pandas(pdf, num_partitions=4)
        .groupBy("k")
        .agg({"v": "stddev"}, ("v", "variance"))
        .to_pandas()
        .sort_values("k")
        .reset_index(drop=True)
    )
    exp = pdf.groupby("k")["v"].agg(["std", "var"]).reset_index()
    assert np.allclose(out["stddev(v)"], exp["std"])
    assert np.allclose(out["variance(v)"], exp["var"])


def test_agg_first_last_and_count_distinct():
    import numpy as np
    import pandas as pd

    pdf = pd.DataFrame(
        {
            "k": [0, 0, 0, 1, 1, 2],
            "v": [10, 10, 20, 30, 30, 40],
        }
    )
    out = (
        rdf.from_pandas(pdf, num_partitions=3)
        .groupBy("k")
        .agg({"v": "count_distinct"})
        .to_pandas()
        .sort_values("k")
        .reset_index(drop=True)
    )
    exp = pdf.groupby("k")["v"].nunique().reset_index()
    assert out["count_distinct(v)"].tolist() == exp["v"].tolist()

    first = (
        rdf.from_pandas(pdf, num_partitions=1)
        .groupBy("k")
        .agg({"v": "first"}, ("v", "last"))
        .to_pandas()
        .sort_values("k")
        .reset_index(drop=True)
    )
    assert first["first(v)"].tolist() == [10, 30, 40]
    assert first["last(v)"].tolist() == [20, 30, 40]


class _SpyTable:
    """A pyarrow table that records how ``group_by`` is asked for
    threads (``pa.Table`` itself is immutable: no attribute can be
    patched on it)."""

    def __init__(self, table):
        self._table = table
        self.use_threads = []

    def group_by(self, keys, use_threads=True):
        self.use_threads.append(use_threads)
        return self._table.group_by(keys, use_threads=use_threads)

    def __getattr__(self, name):
        return getattr(self._table, name)


@pytest.mark.parametrize("agg_fn", ["_direct_agg", "_local_agg"])
@pytest.mark.parametrize("specs, threads, expected", [
    # Ordered aggregators: pyarrow refuses them under threads.
    ([("v", "first"), ("v", "last"), ("v", "sum")], False,
     [[10, 30, 40], [20, 35, 40], [40, 65, 40]]),
    # Counting stages keep arrow's threads.
    ([("v", "count"), ("v", "sum")], True,
     [[3, 2, 1], [40, 65, 40]]),
])
def test_group_by_threads_unless_an_aggregator_is_ordered(
    agg_fn, specs, threads, expected
):
    from raydp_tpu.dataframe import dataframe as dfmod

    table = _SpyTable(pa.table({
        "k": [0, 0, 1, 0, 1, 2],
        "v": [10, 10, 30, 20, 35, 40],
    }))
    out = getattr(dfmod, agg_fn)(table, ["k"], specs)
    assert table.use_threads == [threads]
    out = out.sort_by("k")
    assert out.column("k").to_pylist() == [0, 1, 2]
    # Key columns first, then one output per aggregation in order.
    got = [out.column(1 + i).to_pylist() for i in range(len(specs))]
    assert got == expected


def test_agg_fanout_scales_beyond_old_cap():
    import numpy as np
    import pandas as pd

    rng = np.random.default_rng(6)
    pdf = pd.DataFrame(
        {"k": rng.integers(0, 100, 5000), "v": rng.standard_normal(5000)}
    )
    df = rdf.from_pandas(pdf, num_partitions=16)
    agg = df.groupBy("k").agg({"v": "sum"})
    out = agg.to_pandas().sort_values("k").reset_index(drop=True)
    exp = pdf.groupby("k", as_index=False)["v"].sum()
    assert np.allclose(out["sum(v)"].to_numpy(), exp["v"].to_numpy())
    # fan-out followed the executor's default, not the old hard cap of 8
    assert agg.num_partitions > 8 or df._executor.default_fanout() <= 8


def test_groupby_apply_in_pandas():
    import numpy as np
    import pandas as pd

    rng = np.random.default_rng(8)
    pdf = pd.DataFrame(
        {"k": rng.integers(0, 5, 400), "v": rng.standard_normal(400)}
    )

    def center(g):
        g = g.copy()
        g["v"] = g["v"] - g["v"].mean()
        return g

    out = (
        rdf.from_pandas(pdf, num_partitions=4)
        .groupBy("k")
        .applyInPandas(center)
        .to_pandas()
    )
    assert len(out) == 400
    means = out.groupby("k")["v"].mean()
    assert np.allclose(means, 0.0, atol=1e-12)

    # fn may aggregate (return fewer rows) or drop groups (None/empty)
    def summarize(g):
        if g["k"].iloc[0] == 0:
            return None
        return pd.DataFrame({"k": [g["k"].iloc[0]], "n": [len(g)]})

    import pyarrow as pa

    out2 = (
        rdf.from_pandas(pdf, num_partitions=4)
        .groupBy("k")
        .applyInPandas(
            summarize,
            schema=pa.schema([("k", pa.int64()), ("n", pa.int64())]),
        )
        .to_pandas()
        .sort_values("k")
    )
    exp = pdf[pdf.k != 0].groupby("k").size()
    assert out2["n"].tolist() == exp.tolist()


def test_agg_collect_list_and_set():
    import pandas as pd

    pdf = pd.DataFrame(
        {"k": [0, 0, 0, 1, 1, 2], "v": [3, 3, 1, 5, 5, 9]}
    )
    out = (
        rdf.from_pandas(pdf, num_partitions=3)
        .groupBy("k")
        .agg({"v": "collect_list"}, ("v", "collect_set"), ("v", "count_distinct"))
        .to_pandas()
        .sort_values("k")
        .reset_index(drop=True)
    )
    lists = [sorted(x) for x in out["collect_list(v)"]]
    assert lists == [[1, 3, 3], [5, 5], [9]]
    sets = [sorted(x) for x in out["collect_set(v)"]]
    assert sets == [[1, 3], [5], [9]]
    assert out["count_distinct(v)"].tolist() == [2, 1, 1]


def test_apply_in_pandas_schema_survives_empty_partitions():
    import pandas as pd
    import pyarrow as pa

    # 1 group, many shuffle partitions -> most partitions hold no groups;
    # downstream ops on fn-output columns must still resolve.
    pdf = pd.DataFrame({"k": [1] * 50, "v": range(50)})
    schema = pa.schema([("k", pa.int64()), ("n", pa.int64())])

    def agg(g):
        return pd.DataFrame({"k": [g["k"].iloc[0]], "n": [len(g)]})

    out = (
        rdf.from_pandas(pdf, num_partitions=4)
        .groupBy("k")
        .applyInPandas(agg, schema=schema)
        .withColumn("n2", rdf.col("n") * 2)
        .to_pandas()
    )
    assert out["n2"].tolist() == [100]


def test_sample_fraction():
    import pandas as pd

    pdf = pd.DataFrame({"x": range(10_000)})
    df = rdf.from_pandas(pdf, num_partitions=4)
    s = df.sample(0.3, seed=5)
    n = s.count()
    assert 2500 < n < 3500
    # deterministic: same seed, same rows
    assert s.count() == df.sample(0.3, seed=5).count()
    assert df.sample(0.0, seed=1).count() == 0
    assert df.sample(1.0, seed=1).count() == 10_000


def test_agg_distinct_all_null_group():
    """A group whose values are ALL null must not KeyError (ADVICE r2):
    Spark returns count_distinct=0 and collect_set=[] for such groups."""
    import pandas as pd

    pdf = pd.DataFrame(
        {
            "k": ["a", "a", "b", "b", "c"],
            "v": [1.0, 2.0, None, None, 3.0],
        }
    )
    df = rdf.from_pandas(pdf, num_partitions=2)
    out = (
        df.groupBy("k")
        .agg({"v": "count_distinct"})
        .to_pandas()
        .sort_values("k")
        .reset_index(drop=True)
    )
    assert out["count_distinct(v)"].tolist() == [2, 0, 1]

    sets = (
        df.groupBy("k")
        .agg({"v": "collect_set"}, ("v", "collect_list"))
        .to_pandas()
        .sort_values("k")
        .reset_index(drop=True)
    )
    assert sorted(sets["collect_set(v)"][0]) == [1.0, 2.0]
    assert list(sets["collect_set(v)"][1]) == []
    assert list(sets["collect_list(v)"][1]) == []
    assert list(sets["collect_list(v)"][2]) == [3.0]


@pytest.mark.parametrize("tier", ["direct", "coalesced_combine", "exchange"])
def test_agg_adaptive_tiers_parity(monkeypatch, tier):
    """The three adaptive agg plans (single-pass arrow, partial+single
    combine, partial+hash exchange) must produce identical results."""
    import numpy as np
    import pandas as pd

    import raydp_tpu.dataframe.dataframe as dfmod

    if tier == "direct":
        monkeypatch.setattr(dfmod, "_AGG_COALESCE_BYTES", 1 << 40)
    elif tier == "coalesced_combine":
        monkeypatch.setattr(dfmod, "_AGG_COALESCE_BYTES", 0)
        monkeypatch.setattr(dfmod, "_COMBINE_COALESCE_BYTES", 1 << 40)
    else:
        monkeypatch.setattr(dfmod, "_AGG_COALESCE_BYTES", 0)
        monkeypatch.setattr(dfmod, "_COMBINE_COALESCE_BYTES", 0)

    rng = np.random.RandomState(3)
    pdf = pd.DataFrame(
        {
            "k": rng.randint(0, 50, 5000),
            "v": np.where(rng.rand(5000) < 0.1, np.nan, rng.randn(5000)),
            "w": rng.randint(0, 7, 5000).astype(float),
        }
    )
    out = (
        rdf.from_pandas(pdf, num_partitions=4)
        .groupBy("k")
        .agg(
            {"v": "sum"},
            ("v", "mean"),
            ("v", "stddev"),
            ("w", "count_distinct"),
            ("v", "count"),
            ("*", "count"),
            ("w", "max"),
        )
        .to_pandas()
        .sort_values("k")
        .reset_index(drop=True)
    )
    g = pdf.groupby("k")
    assert np.allclose(out["sum(v)"], g["v"].sum())
    assert np.allclose(out["mean(v)"], g["v"].mean())
    assert np.allclose(out["stddev(v)"], g["v"].std())
    assert out["count_distinct(w)"].tolist() == g["w"].nunique().tolist()
    assert out["count(v)"].tolist() == g["v"].count().tolist()
    assert out["count"].tolist() == g.size().tolist()
    assert np.allclose(out["max(w)"], g["w"].max())


@pytest.mark.parametrize("how,pd_how", [
    ("inner", "inner"), ("left", "left"), ("outer", "outer"),
])
def test_shuffle_join_parity(monkeypatch, how, pd_how):
    """Large-right joins take the shuffle hash join; results must match
    pandas merge exactly (broadcast path covered by test_join)."""
    import raydp_tpu.dataframe.dataframe as dfmod

    monkeypatch.setattr(dfmod, "_BROADCAST_JOIN_BYTES", 0)  # force shuffle
    rng = np.random.RandomState(4)
    lpdf = pd.DataFrame(
        {"k": rng.randint(0, 200, 3000), "lv": rng.randn(3000)}
    )
    rpdf = pd.DataFrame(
        {
            # int32 keys on the right: bucketing must still agree.
            "k": rng.randint(0, 250, 2500).astype(np.int32),
            "rv": rng.randn(2500),
        }
    )
    out = (
        rdf.from_pandas(lpdf, num_partitions=4)
        .join(rdf.from_pandas(rpdf, num_partitions=3), on="k", how=how)
        .to_pandas()
        .sort_values(["k", "lv", "rv"], na_position="last")
        .reset_index(drop=True)
    )
    exp = (
        lpdf.merge(rpdf.assign(k=rpdf.k.astype(np.int64)), on="k", how=pd_how)
        .sort_values(["k", "lv", "rv"], na_position="last")
        .reset_index(drop=True)
    )
    assert len(out) == len(exp)
    assert out["k"].tolist() == exp["k"].tolist()
    assert np.allclose(
        out["lv"].fillna(-9e9), exp["lv"].fillna(-9e9)
    )
    assert np.allclose(
        out["rv"].fillna(-9e9), exp["rv"].fillna(-9e9)
    )


def test_broadcast_outer_join_routes_to_shuffle():
    """Regression (review r3c): a per-partition broadcast right/full
    outer join duplicated unmatched right rows once per left partition.
    These join types must shuffle regardless of right-side size."""
    lpdf = pd.DataFrame({"k": [1, 2, 3, 4], "lv": [10, 20, 30, 40]})
    rpdf = pd.DataFrame({"k": [2, 99], "rv": [200, 990]})
    left = rdf.from_pandas(lpdf, num_partitions=2)
    right = rdf.from_pandas(rpdf, num_partitions=1)
    out = (
        left.join(right, on="k", how="outer")
        .to_pandas()
        .sort_values("k")
        .reset_index(drop=True)
    )
    exp = lpdf.merge(rpdf, on="k", how="outer")
    assert len(out) == len(exp) == 5
    assert out[out.k == 99].rv.tolist() == [990]

    routed = left.join(right, on="k", how="right").to_pandas()
    assert len(routed) == 2
    assert sorted(routed.k.tolist()) == [2, 99]
