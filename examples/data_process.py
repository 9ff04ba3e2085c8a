"""Distributed ETL: the NYC-taxi preprocessing pipeline.

Counterpart of the reference's examples/data_process.py (its
filter/withColumn/UDF/drop/random_split sequence is the op checklist,
reference: examples/data_process.py:9-94) on the raydp_tpu DataFrame
engine: a real multi-process session executes every stage on ETL workers
with partitions in the shm object store.

Run: python examples/data_process.py [--smoke] [--rows N]
"""
import argparse
import os
import sys
import tempfile

import numpy as np
import pandas as pd

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import raydp_tpu
import raydp_tpu.dataframe as rdf
from raydp_tpu.dataframe import col, hour, dayofweek, udf


def synthetic_taxi(n_rows: int) -> pd.DataFrame:
    rng = np.random.default_rng(0)
    t0 = pd.Timestamp("2020-01-01")
    pickup = t0 + pd.to_timedelta(
        rng.integers(0, 365 * 24 * 3600, n_rows), unit="s"
    )
    trip_min = rng.gamma(2.0, 7.0, n_rows)
    pickup_lon = -73.98 + 0.1 * rng.standard_normal(n_rows)
    pickup_lat = 40.75 + 0.1 * rng.standard_normal(n_rows)
    dropoff_lon = -73.97 + 0.1 * rng.standard_normal(n_rows)
    dropoff_lat = 40.76 + 0.1 * rng.standard_normal(n_rows)
    # Fare follows the trip DISTANCE the features can reconstruct (plus a
    # duration term and noise) — so the estimator examples actually have
    # signal to learn, like the real NYC dataset.
    dist_km = np.hypot(
        (dropoff_lon - pickup_lon) * 84.3,  # km/deg at 40.75N
        (dropoff_lat - pickup_lat) * 111.1,
    )
    return pd.DataFrame(
        {
            "pickup_datetime": pickup,
            "dropoff_datetime": pickup + pd.to_timedelta(trip_min, unit="m"),
            "passenger_count": rng.integers(0, 7, n_rows),
            "pickup_longitude": pickup_lon,
            "pickup_latitude": pickup_lat,
            "dropoff_longitude": dropoff_lon,
            "dropoff_latitude": dropoff_lat,
            "fare_amount": np.maximum(
                2.5,
                2.5
                + 1.6 * dist_km
                + 0.3 * trip_min
                + rng.standard_normal(n_rows),
            ),
        }
    )


def nyc_taxi_preprocess(df: "rdf.DataFrame") -> "rdf.DataFrame":
    """The reference pipeline: drop bad rows, derive time + distance
    features, drop raw columns."""
    df = df.filter(
        (col("fare_amount") > 0) & (col("passenger_count") > 0)
    )
    df = df.withColumn("hour", hour(col("pickup_datetime")))
    df = df.withColumn("day_of_week", dayofweek(col("pickup_datetime")))

    @udf("double")
    def haversine(lon1, lat1, lon2, lat2):
        rad = np.pi / 180.0
        dlon = (lon2 - lon1) * rad
        dlat = (lat2 - lat1) * rad
        a = (
            np.sin(dlat / 2) ** 2
            + np.cos(lat1 * rad) * np.cos(lat2 * rad) * np.sin(dlon / 2) ** 2
        )
        return 6371.0 * 2 * np.arcsin(np.sqrt(a))

    df = df.withColumn(
        "distance_km",
        haversine(
            col("pickup_longitude"), col("pickup_latitude"),
            col("dropoff_longitude"), col("dropoff_latitude"),
        ),
    )
    return df.select(
        "hour", "day_of_week", "distance_km", "passenger_count",
        "fare_amount",
    )


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true")
    parser.add_argument("--rows", type=int, default=200_000)
    args = parser.parse_args()
    n_rows = 5_000 if args.smoke else args.rows

    session = raydp_tpu.init(app_name="data-process", num_workers=2)
    try:
        with tempfile.TemporaryDirectory() as tmp:
            path = f"{tmp}/taxi.parquet"
            synthetic_taxi(n_rows).to_parquet(path)
            df = rdf.read_parquet(path, num_partitions=4)
            out = nyc_taxi_preprocess(df)
            train, test = out.random_split([0.9, 0.1], seed=42)
            n_train, n_test = train.count(), test.count()
            stats = (
                out.groupBy("day_of_week")
                .agg({"fare_amount": "mean"})
                .to_pandas()
                .sort_values("day_of_week")
            )
        print(f"rows in: {n_rows}  train: {n_train}  test: {n_test}")
        print(stats.to_string(index=False))
        assert n_train + n_test <= n_rows
        print("data_process OK")
    finally:
        raydp_tpu.stop()


if __name__ == "__main__":
    main()
