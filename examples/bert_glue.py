"""BERT-style sequence-classifier fine-tune (the GLUE config).

The new-capability benchmark config (BASELINE.md row 5, no reference
artifact): token sequences flow DataFrame → MLDataset → JAXEstimator with
tensor/sequence-parallel parameter shardings derived from the model's
logical axes when the mesh has tp/sp axes.

Run: python examples/bert_glue.py [--smoke]
"""
import argparse
import os
import sys

import numpy as np
import pandas as pd

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import raydp_tpu
import raydp_tpu.dataframe as rdf


def synthetic_glue(n: int, seq: int, vocab: int) -> pd.DataFrame:
    """Learnable stand-in for a tokenized GLUE task: the label depends on
    whether marker token 7 appears in the sequence."""
    rng = np.random.default_rng(0)
    ids = rng.integers(10, vocab, size=(n, seq))
    pos = rng.random(n) < 0.5
    ids[pos, rng.integers(0, seq, pos.sum())] = 7
    cols = {f"t{i}": ids[:, i] for i in range(seq)}
    cols["label"] = pos.astype(np.int64)
    return pd.DataFrame(cols)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true")
    args = parser.parse_args()
    seq = 16 if args.smoke else 128
    n_rows = 1_024 if args.smoke else 8_192
    epochs = 3 if args.smoke else 3

    import jax
    import optax

    from raydp_tpu.models.transformer import (
        SequenceClassifier,
        bert_base,
        tiny_transformer,
    )
    from raydp_tpu.parallel import MeshSpec
    from raydp_tpu.train import JAXEstimator

    cfg = (
        tiny_transformer(max_len=seq, vocab_size=256, dropout_rate=0.0)
        if args.smoke
        else bert_base(max_len=seq)
    )
    # Every device found is used: tensor and sequence parallelism where
    # the count allows the full 3-axis mesh, data parallelism otherwise.
    n_dev = len(jax.devices())
    mesh = (
        MeshSpec(dp=n_dev // 4, tp=2, sp=2)
        if n_dev % 8 == 0
        else MeshSpec(dp=n_dev)
    )

    session = raydp_tpu.init(app_name="bert-glue", num_workers=2)
    try:
        df = rdf.from_pandas(
            synthetic_glue(n_rows, seq, cfg.vocab_size), num_partitions=4
        )
        est = JAXEstimator(
            model=SequenceClassifier(cfg=cfg, num_classes=2),
            optimizer=optax.adamw(3e-4 if args.smoke else 2e-5),
            loss="softmax_ce",
            metrics=["categorical_accuracy"],
            num_epochs=epochs,
            batch_size=64,
            feature_columns=[f"t{i}" for i in range(seq)],
            label_column="label",
            feature_dtype=np.int32,
            label_dtype=np.int32,
            mesh=mesh,
            seed=0,
        )
        history = est.fit_on_df(df, num_shards=2)
        first, last = history[0], history[-1]
        sharded = any(
            any(s is not None for s in x.sharding.spec)
            for x in jax.tree_util.tree_leaves(est._state.params)
        )
        print(
            f"mesh={mesh.axis_sizes}  params_sharded={sharded}  "
            f"train_loss {first['train_loss']:.4f} -> {last['train_loss']:.4f}"
        )
        assert last["train_loss"] < first["train_loss"]
        print("bert_glue OK")
    finally:
        raydp_tpu.stop()


if __name__ == "__main__":
    main()
