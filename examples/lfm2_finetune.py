"""Continue training an LFM2-8B-A1B style sparse hybrid LM on token rows,
as ONE SHARE of an expert-parallel deployment.

The same path as olmoe_finetune.py and granite_finetune.py (DataFrame →
MLDataset → JAXEstimator, ``loss="lm_ce"``, ``self_supervised=True``).
``lfm2_8b_a1b(...)`` builds gated short convolutions beside grouped-query
attention with an RMSNorm over each head's q and k, and an FFN kind per
layer: dense SwiGLU in the leading layers, then experts chosen by sigmoid
score + ``expert_bias`` and weighted by their normalised scores.
``experts_held``/``first_expert`` make every routed layer the share a chip
of an expert-parallel group holds: the router scores all experts, the
layer computes the part of the sum its own experts give, and nothing
stands in for the absent chips. ``aux_losses=True`` is what makes the step
sow its routing counts (this model has no auxiliary loss); they arrive as
the gauges ``moe/held_pair_share`` and ``moe/load_max_over_mean``.

Tiny widths by default (the published ones are the benchmark's
``lfm2_8b_a1b`` configuration: 10.7 GB of AdamW state for the model's
first seven layers with 8 of 32 experts).

Run: python examples/lfm2_finetune.py [--smoke]
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

    from raydp_tpu.models import CausalLM, lfm2_8b_a1b
    from raydp_tpu.train import JAXEstimator
    from raydp_tpu.utils.profiling import metrics

    cfg = lfm2_8b_a1b(
        vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, n_layers=4,
        layer_types=("conv:swiglu", "conv:moe", "attention:moe", "conv:moe"),
        d_ff=128, max_len=seq, n_experts=8, top_k=2, d_expert=32,
        experts_held=4, first_expert=4, dtype=jnp.float32,
    )
    session = raydp_tpu.init(app_name="lfm2-finetune", num_workers=2)
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

        def bias(e):
            return np.asarray(e._state.params["buffers"]["encoder"][
                "block_1"]["moe"]["expert_bias"])

        est._init_state(token_rows(16, seq, cfg.vocab_size).to_numpy(
            np.int32))
        drawn = bias(est)
        history = est.fit_on_df(df, num_shards=2)
        first, last = history[0], history[-1]
        share = metrics.gauge_value("moe/held_pair_share")
        print(
            f"train_loss {first['train_loss']:.4f} -> "
            f"{last['train_loss']:.4f}  pairs on held experts {share:.1%}"
        )
        assert last["train_loss"] < first["train_loss"]
        assert metrics.gauge_value("conv/layers") == 3
        assert metrics.gauge_value("moe/experts_routed") == 8
        assert metrics.gauge_value("moe/experts_held") == 4
        assert 0.0 < share < 1.0
        # The selection bias is a buffer: training leaves it as drawn.
        np.testing.assert_array_equal(bias(est), drawn)
        print("lfm2_finetune OK")
    finally:
        raydp_tpu.stop()


if __name__ == "__main__":
    main()
