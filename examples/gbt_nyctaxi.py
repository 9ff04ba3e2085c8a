"""Gradient-boosted trees on the NYC-taxi ETL output.

Counterpart of the reference's examples/xgboost_ray_nyctaxi.py (Spark
preprocessing → xgboost_ray train/predict on the same cluster); here the
same pipeline runs DataFrame → MLDataset → GBTEstimator with the
histogram method jitted onto the visible accelerator.

Run: python examples/gbt_nyctaxi.py [--smoke]
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import raydp_tpu  # noqa: E402
import raydp_tpu.dataframe as rdf  # noqa: E402
from data_process import nyc_taxi_preprocess, synthetic_taxi  # noqa: E402


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true")
    parser.add_argument("--rows", type=int, default=200_000)
    parser.add_argument("--trees", type=int, default=60)
    args = parser.parse_args()
    n_rows = 8_000 if args.smoke else args.rows
    n_trees = 10 if args.smoke else args.trees

    from raydp_tpu.data import MLDataset
    from raydp_tpu.train import GBTEstimator

    session = raydp_tpu.init(app_name="gbt-nyctaxi")
    try:
        df = nyc_taxi_preprocess(
            rdf.from_pandas(synthetic_taxi(n_rows), num_partitions=4)
        )
        train_df, test_df = df.random_split([0.9, 0.1], seed=42)
        features = ["hour", "day_of_week", "distance_km", "passenger_count"]
        est = GBTEstimator(
            n_trees=n_trees,
            max_depth=5,
            feature_columns=features,
            label_column="fare_amount",
        )
        hist = est.fit_on_df(train_df, num_shards=2)
        test_ds = MLDataset.from_df(test_df, num_shards=2)
        metrics = est.evaluate(test_ds)
        print(
            f"rounds={len(hist)} "
            f"first_loss={hist[0]['train_loss']:.3f} "
            f"last_loss={hist[-1]['train_loss']:.3f} "
            f"test_rmse={metrics['rmse']:.3f}"
        )
        assert hist[-1]["train_loss"] < hist[0]["train_loss"]
        print("OK")
    finally:
        raydp_tpu.stop()


if __name__ == "__main__":
    main()
