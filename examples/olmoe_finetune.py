"""Continue training an OLMoE-style routed decoder LM on token rows.

The causal-LM counterpart of bert_glue.py: a DataFrame of tokenized rows
(documents concatenated with an end-of-text id, cut into rows of the
context length) flows DataFrame → MLDataset → JAXEstimator, which trains
``CausalLM(olmoe(...))`` on next-token prediction (``loss="lm_ce"``,
``self_supervised=True``) with the router's load-balancing and z-loss in
the objective (``aux_losses=True``). No token is dropped by the routing;
the tokens each expert received come back with each epoch's loss as the
gauges ``moe/load_max_over_mean`` and ``moe/aux_loss``.

Tiny widths by default (the published ones are the benchmark's
``olmoe_1b_7b`` configuration: 10 GB of AdamW state for ONE layer).

Run: python examples/olmoe_finetune.py [--smoke]
"""
import argparse
import os
import sys

import numpy as np
import pandas as pd

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import raydp_tpu
import raydp_tpu.dataframe as rdf

EOS = 1


def token_rows(n: int, seq: int, vocab: int) -> pd.DataFrame:
    """A learnable stand-in for a tokenized corpus: documents count
    upwards from a random start (token t+1 follows token t), end in EOS
    and are concatenated with no mask across them."""
    rng = np.random.default_rng(0)
    stream = np.empty(n * seq, np.int64)
    at = 0
    while at < len(stream):
        length = min(int(rng.integers(8, 48)), len(stream) - at)
        start = int(rng.integers(2, vocab))
        stream[at:at + length] = 2 + (start + np.arange(length)) % (vocab - 2)
        stream[at + length - 1] = EOS
        at += length
    ids = stream.reshape(n, seq)
    return pd.DataFrame({f"t{i}": ids[:, i] for i in range(seq)})


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true")
    args = parser.parse_args()
    seq = 32 if args.smoke else 128
    n_rows = 128 if args.smoke else 2_048

    import jax.numpy as jnp
    import optax

    from raydp_tpu.models import CausalLM, olmoe
    from raydp_tpu.train import JAXEstimator
    from raydp_tpu.utils.profiling import metrics

    cfg = olmoe(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, max_len=seq,
        n_experts=8, top_k=2, d_expert=32, dtype=jnp.float32,
    )
    session = raydp_tpu.init(app_name="olmoe-finetune", num_workers=2)
    try:
        df = rdf.from_pandas(
            token_rows(n_rows, seq, cfg.vocab_size), num_partitions=4
        )
        est = JAXEstimator(
            model=CausalLM(cfg),
            optimizer=optax.adamw(3e-3),
            loss="lm_ce",
            self_supervised=True,
            aux_losses=True,
            num_epochs=3,
            batch_size=16,
            feature_columns=[f"t{i}" for i in range(seq)],
            feature_dtype=np.int32,
            epoch_mode="stream",
            seed=0,
        )
        history = est.fit_on_df(df, num_shards=2)
        first, last = history[0], history[-1]
        load = metrics.gauge_value("moe/load_max_over_mean")
        print(
            f"train_loss {first['train_loss']:.4f} -> "
            f"{last['train_loss']:.4f}  fullest expert over mean {load:.2f}"
        )
        assert last["train_loss"] < first["train_loss"]
        # Every (token, expert) pair of a step reached an expert.
        pairs = metrics.gauge_value("moe/expert_tokens_per_step")
        assert pairs == cfg.n_layers * 16 * seq * cfg.top_k, pairs
        print("olmoe_finetune OK")
    finally:
        raydp_tpu.stop()


if __name__ == "__main__":
    main()
