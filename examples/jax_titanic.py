"""Titanic survival classifier — the reference's TF example on JAX.

Counterpart of examples/tensorflow_titanic.ipynb: fillna + categorical
encoding on the DataFrame engine, then a binary classifier via
JAXEstimator (the TFEstimator capability maps to JAXEstimator per
SURVEY §7.1).

Run: python examples/jax_titanic.py [--smoke]
"""
import argparse
import os
import sys

import numpy as np
import pandas as pd

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import raydp_tpu
import raydp_tpu.dataframe as rdf
from raydp_tpu.dataframe import col, when


def synthetic_titanic(n: int) -> pd.DataFrame:
    """Titanic-shaped data (the real CSV is 891 rows; synthesize more
    with the same columns/missingness so the pipeline is identical)."""
    rng = np.random.default_rng(7)
    sex = rng.choice(["male", "female"], n)
    pclass = rng.choice([1, 2, 3], n, p=[0.24, 0.21, 0.55])
    age = rng.normal(30, 14, n).clip(0.5, 80)
    age[rng.random(n) < 0.2] = np.nan  # the famous missing ages
    fare = rng.gamma(2.0, 16.0, n)
    logit = (
        1.2 * (sex == "female")
        - 0.45 * (pclass - 2)
        - 0.012 * np.nan_to_num(age, nan=30.0)
        + 0.004 * fare
    )
    survived = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float64)
    return pd.DataFrame(
        {
            "Pclass": pclass, "Sex": sex, "Age": age,
            "SibSp": rng.integers(0, 5, n), "Parch": rng.integers(0, 4, n),
            "Fare": fare, "Survived": survived,
        }
    )


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true")
    args = parser.parse_args()
    n_rows = 4_000 if args.smoke else 50_000
    epochs = 3 if args.smoke else 12

    import optax

    from raydp_tpu.models import binary_classifier
    from raydp_tpu.train import JAXEstimator

    session = raydp_tpu.init(app_name="jax-titanic", num_workers=2)
    try:
        df = rdf.from_pandas(synthetic_titanic(n_rows), num_partitions=4)
        # fillna + encode (the notebook's preprocessing cells)
        df = df.fillna({"Age": 30.0})
        df = df.withColumn(
            "is_female", when(col("Sex") == "female", 1.0).otherwise(0.0)
        )
        # Feature scaling (the notebook normalizes likewise) — unscaled
        # Fare/Age dominate the gradient otherwise.
        df = df.withColumn("age_n", col("Age") / 40.0 - 0.75)
        df = df.withColumn("fare_n", col("Fare") / 50.0 - 0.6)
        df = df.withColumn("class_n", col("Pclass") - 2.0)
        df = df.select(
            "class_n", "is_female", "age_n", "SibSp", "Parch", "fare_n",
            "Survived",
        )
        train_df, eval_df = df.random_split([0.85, 0.15], seed=1)
        est = JAXEstimator(
            model=binary_classifier(),
            optimizer=optax.adam(3e-3),
            loss="bce",
            metrics=["accuracy"],
            num_epochs=epochs,
            batch_size=256,
            feature_columns=[
                "class_n", "is_female", "age_n", "SibSp", "Parch", "fare_n"
            ],
            label_column="Survived",
            seed=0,
        )
        history = est.fit_on_df(train_df, eval_df)
        last = history[-1]
        print(
            f"train_loss {history[0]['train_loss']:.4f} -> "
            f"{last['train_loss']:.4f}  eval_acc {last['eval_accuracy']:.3f}"
        )
        assert last["eval_accuracy"] > 0.6
        print("jax_titanic OK")
    finally:
        raydp_tpu.stop()


if __name__ == "__main__":
    main()
