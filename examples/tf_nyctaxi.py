"""NYC-taxi fare regression through the keras-compat TFEstimator.

Counterpart of the reference's examples/tensorflow_nyctaxi.py (keras
functional model + TFEstimator.fit_on_spark): the same Dense/BatchNorm
stack is declared in the keras WIRE format (what ``model.to_json()``
emits — no TensorFlow import needed), TFEstimator lowers it onto JAX,
and the ETL half runs on this framework's DataFrame engine instead of
Spark.

Run: python examples/tf_nyctaxi.py [--smoke]
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import raydp_tpu
import raydp_tpu.dataframe as rdf
from data_process import nyc_taxi_preprocess, synthetic_taxi


def _dense(units, activation="linear"):
    return {
        "class_name": "Dense",
        "config": {"units": units, "activation": activation},
    }


def _batchnorm():
    return {"class_name": "BatchNormalization", "config": {}}


def keras_taxi_model() -> str:
    """The reference example's Dense(256..16)+BatchNorm tower, as the
    keras to_json() wire format (reference:
    examples/tensorflow_nyctaxi.py:38-53)."""
    layers = []
    for units in (256, 128, 64, 32, 16):
        layers.append(_dense(units, "relu"))
        layers.append(_batchnorm())
    layers.append(_dense(1))
    return json.dumps(
        {
            "class_name": "Sequential",
            "config": {"name": "taxi_fare", "layers": layers},
        }
    )


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true")
    parser.add_argument("--rows", type=int, default=200_000)
    parser.add_argument("--epochs", type=int, default=12)
    args = parser.parse_args()
    n_rows = 8_000 if args.smoke else args.rows
    epochs = 3 if args.smoke else args.epochs

    from raydp_tpu.train import TFEstimator

    session = raydp_tpu.init(app_name="tf-nyctaxi")
    try:
        df = nyc_taxi_preprocess(
            rdf.from_pandas(synthetic_taxi(n_rows), num_partitions=4)
        )
        train_df, test_df = df.random_split([0.9, 0.1], seed=42)
        features = ["hour", "day_of_week", "distance_km", "passenger_count"]
        est = TFEstimator(
            num_workers=1,
            model=keras_taxi_model(),
            optimizer={
                "class_name": "Adam",
                "config": {"learning_rate": 1e-3},
            },
            loss="mean_squared_error",
            metrics=["mae"],
            feature_columns=features,
            label_column="fare_amount",
            batch_size=256,
            num_epochs=epochs,
            seed=0,
        )
        history = est.fit_on_df(train_df, test_df)
        first, last = history[0], history[-1]
        print(
            f"train_loss {first['train_loss']:.4f} -> {last['train_loss']:.4f}"
            f"  eval_mae {last.get('eval_mae', float('nan')):.3f}"
            f"  ({last['samples_per_sec']:.0f} samples/s)"
        )
        assert last["train_loss"] < first["train_loss"]
        est.shutdown()
        print("tf_nyctaxi OK")
    finally:
        raydp_tpu.stop()


if __name__ == "__main__":
    main()
