"""NYC-taxi fare regression with JAXEstimator — ETL to training in one
program on one cluster.

Counterpart of the reference's examples/pytorch_nyctaxi.py (Spark
preprocessing → TorchEstimator fit_on_spark); here the same pipeline runs
DataFrame → MLDataset → JAXEstimator with the train step jitted onto the
visible accelerator.

Run: python examples/jax_nyctaxi.py [--smoke]
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import raydp_tpu
import raydp_tpu.dataframe as rdf
from data_process import nyc_taxi_preprocess, synthetic_taxi


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true")
    parser.add_argument("--rows", type=int, default=200_000)
    parser.add_argument("--epochs", type=int, default=10)
    args = parser.parse_args()
    n_rows = 8_000 if args.smoke else args.rows
    epochs = 3 if args.smoke else args.epochs

    import optax

    from raydp_tpu.models.mlp import taxi_fare_regressor
    from raydp_tpu.train import JAXEstimator

    # num_workers intentionally NOT hardcoded: raydp-tpu-submit's
    # --num-workers (RAYDP_TPU_NUM_WORKERS) controls it, default 2.
    session = raydp_tpu.init(app_name="jax-nyctaxi")
    try:
        df = nyc_taxi_preprocess(
            rdf.from_pandas(synthetic_taxi(n_rows), num_partitions=4)
        )
        train_df, test_df = df.random_split([0.9, 0.1], seed=42)
        features = ["hour", "day_of_week", "distance_km", "passenger_count"]
        est = JAXEstimator(
            model=taxi_fare_regressor(),
            optimizer=optax.adam(1e-3),
            loss="smooth_l1",
            metrics=["mae"],
            num_epochs=epochs,
            batch_size=512,
            feature_columns=features,
            label_column="fare_amount",
            seed=0,
        )
        history = est.fit_on_df(train_df, test_df, num_shards=2)
        first, last = history[0], history[-1]
        print(
            f"train_loss {first['train_loss']:.4f} -> {last['train_loss']:.4f}"
            f"  eval_mae {last.get('eval_mae', float('nan')):.3f}"
            f"  ({last['samples_per_sec']:.0f} samples/s)"
        )
        assert last["train_loss"] < first["train_loss"]
        print("jax_nyctaxi OK")
    finally:
        raydp_tpu.stop()


if __name__ == "__main__":
    main()
