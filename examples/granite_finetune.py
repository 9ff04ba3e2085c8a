"""Continue training a Granite-4.0-H style hybrid state-space LM on token
rows.

The same path as olmoe_finetune.py (DataFrame → MLDataset → JAXEstimator,
``loss="lm_ce"``, ``self_supervised=True``) with a stack whose layers are
not all alike: ``granite_h_micro(...)`` builds Mamba-2 layers (a chunked
state-space scan, ``ops/ssd.py``) beside grouped-query attention without
positions from a per-layer pattern, a dense SwiGLU MLP after either, the
published multipliers and a head tied to the embedding. The stack reports
itself once where the step is built: gauges ``ssm/layers``,
``ssm/chunks_per_step``, ``ssm/state_bytes_per_sequence``.

Tiny widths by default (the published ones are the benchmark's
``granite_4_0_h_micro`` configuration: 10.4 GB of AdamW state for the
model's first six layers).

Run: python examples/granite_finetune.py [--smoke]
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import raydp_tpu
import raydp_tpu.dataframe as rdf

from olmoe_finetune import token_rows


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true")
    args = parser.parse_args()
    seq = 32 if args.smoke else 128
    n_rows = 128 if args.smoke else 2_048

    import jax.numpy as jnp
    import optax

    from raydp_tpu.models import CausalLM, granite_h_micro
    from raydp_tpu.train import JAXEstimator
    from raydp_tpu.utils.profiling import metrics

    cfg = granite_h_micro(
        vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, n_layers=3,
        layer_types=("mamba", "mamba", "attention"), d_ff=128, max_len=seq,
        attention_scale=1 / 16, ssm_heads=4, ssm_head_dim=32, ssm_state=16,
        ssm_chunk=8, dtype=jnp.float32,
    )
    session = raydp_tpu.init(app_name="granite-finetune", num_workers=2)
    try:
        df = rdf.from_pandas(
            token_rows(n_rows, seq, cfg.vocab_size), num_partitions=4
        )
        est = JAXEstimator(
            model=CausalLM(cfg),
            optimizer=optax.adamw(3e-3),
            loss="lm_ce",
            self_supervised=True,
            num_epochs=3,
            batch_size=16,
            feature_columns=[f"t{i}" for i in range(seq)],
            feature_dtype=np.int32,
            epoch_mode="stream",
            seed=0,
        )
        history = est.fit_on_df(df, num_shards=2)
        first, last = history[0], history[-1]
        chunks = metrics.gauge_value("ssm/chunks_per_step")
        print(
            f"train_loss {first['train_loss']:.4f} -> "
            f"{last['train_loss']:.4f}  scan chunks a step {chunks:.0f}"
        )
        assert last["train_loss"] < first["train_loss"]
        # Two state-space layers, 16 sequences in chunks of 8 tokens.
        assert metrics.gauge_value("ssm/layers") == 2
        assert chunks == 2 * 16 * seq // cfg.ssm_chunk, chunks
        print("granite_finetune OK")
    finally:
        raydp_tpu.stop()


if __name__ == "__main__":
    main()
