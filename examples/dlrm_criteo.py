"""DLRM CTR training on synthetic Criteo-shaped data.

Counterpart of the reference's examples/pytorch_dlrm.ipynb: the Spark
preprocessing (groupBy counts → frequency-thresholded id remapping) runs
on the DataFrame engine, then DLRM trains with tp-row-sharded embedding
tables when the mesh has a tp axis (the notebook trains replicated —
sharded tables are this framework's new capability, SURVEY §2.4).

Run: python examples/dlrm_criteo.py [--smoke]
"""
import argparse
import os
import sys

import numpy as np
import pandas as pd

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import raydp_tpu
import raydp_tpu.dataframe as rdf
from raydp_tpu.dataframe import col


def synthetic_criteo(n: int, n_dense=4, n_cat=6, vocab=1000) -> pd.DataFrame:
    rng = np.random.default_rng(11)
    out = {}
    for i in range(n_dense):
        out[f"I{i}"] = rng.gamma(1.5, 2.0, n).astype(np.float32)
    for i in range(n_cat):
        # zipf-ish ids: frequent heads, long tails (what the frequency
        # threshold in the notebook is for)
        ids = (rng.pareto(1.2, n) * 17).astype(np.int64) % vocab
        out[f"C{i}"] = ids
    logit = -1.2 + 0.35 * out["I0"] - 0.2 * out["I1"] + 0.3 * (out["C0"] % 2)
    out["label"] = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(
        np.float32
    )
    return pd.DataFrame(out)


def remap_rare_ids(df, cat_cols, min_count: int):
    """The notebook's frequency-threshold preprocessing: categorical ids
    seen fewer than ``min_count`` times collapse to id 0; survivors are
    renumbered densely. Returns (df, vocab_sizes)."""
    from raydp_tpu.dataframe import udf

    vocab_sizes = []
    for c in cat_cols:
        counts = df.groupBy(c).count().to_pandas()
        keep_list = sorted(counts[counts["count"] >= min_count][c])
        mapping = {v: i + 1 for i, v in enumerate(keep_list)}
        vocab_sizes.append(len(keep_list) + 1)

        @udf("int64")
        def remap(ids, _m=mapping):
            return pd.Series(ids).map(_m).fillna(0).astype(np.int64).values

        df = df.withColumn(c, remap(col(c)))
    return df, tuple(vocab_sizes)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true")
    args = parser.parse_args()
    n_rows = 8_192 if args.smoke else 500_000
    epochs = 2 if args.smoke else 5

    import optax

    from raydp_tpu.models.dlrm import DLRMConfig, PackedDLRM
    from raydp_tpu.parallel import MeshSpec
    from raydp_tpu.train import JAXEstimator

    session = raydp_tpu.init(app_name="dlrm-criteo", num_workers=2)
    try:
        n_dense, n_cat = 4, 6
        df = rdf.from_pandas(synthetic_criteo(n_rows), num_partitions=4)
        df, vocab_sizes = remap_rare_ids(
            df, [f"C{i}" for i in range(n_cat)], min_count=3
        )
        cfg = DLRMConfig(
            dense_features=n_dense,
            vocab_sizes=vocab_sizes,
            embed_dim=32,
            bottom_mlp=(64, 32),
            top_mlp=(64, 32),
        )
        import jax

        # Every device found is used: two-way tensor parallelism where
        # the count allows it, data parallelism over the rest.
        n_dev = len(jax.devices())
        mesh = (
            MeshSpec(dp=n_dev // 2, tp=2)
            if n_dev % 4 == 0
            else MeshSpec(dp=n_dev)
        )
        est = JAXEstimator(
            model=PackedDLRM(cfg=cfg),
            optimizer=optax.adagrad(5e-2),
            loss="bce",
            metrics=["accuracy"],
            num_epochs=epochs,
            batch_size=1024,
            feature_columns=[f"I{i}" for i in range(n_dense)]
            + [f"C{i}" for i in range(n_cat)],
            label_column="label",
            mesh=mesh,
            seed=0,
            epoch_mode="stream",
        )
        history = est.fit_on_df(df, num_shards=2)
        first, last = history[0], history[-1]
        print(
            f"vocabs={vocab_sizes}  train_loss {first['train_loss']:.4f}"
            f" -> {last['train_loss']:.4f}"
        )
        assert last["train_loss"] < first["train_loss"]
        print("dlrm_criteo OK")
    finally:
        raydp_tpu.stop()


if __name__ == "__main__":
    main()
