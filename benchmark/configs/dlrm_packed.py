"""DLRM (Naumov et al. 2019) over one packed feature matrix: how the
benchmark builds it through the program, its plain reference, and its
operation and byte counts. Sizes come from the configuration's JSON."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Program logit (bf16 trunk, f32 logit layer) against the f32 "highest"
# reference, as the largest absolute difference over the largest reference
# magnitude. bf16 table rows, a 367-wide interaction and seven bf16 layers:
# the chip measured 0.24-1.04% in 39 runs (PR 22). 3% is three times the
# worst of them; a wrong table row or a missing interaction term moves the
# logit by tens of percent.
TOLERANCE = 0.03
CHECK_ROWS = 256


def columns(sizes: dict):
    return (
        [f"I{i}" for i in range(sizes["dense_features"])]
        + [f"C{t}" for t in range(len(sizes["vocab_sizes"]))]
    )


def estimator_kwargs(sizes: dict, traffic: dict, mesh_spec) -> dict:
    import optax

    from raydp_tpu.models.dlrm import DLRMConfig, PackedDLRM

    cfg = DLRMConfig(
        dense_features=sizes["dense_features"],
        vocab_sizes=tuple(sizes["vocab_sizes"]),
        embed_dim=sizes["embed_dim"],
        bottom_mlp=tuple(sizes["bottom_mlp"]),
        top_mlp=tuple(sizes["top_mlp"]),
        interaction=sizes["interaction"],
        embedding_impl=sizes["embedding_impl"],
        dtype=jnp.dtype(sizes["compute_dtype"]),
        param_dtype=jnp.dtype(sizes["param_dtype"]),
    )
    opt = sizes["optimizer"]
    return dict(
        model=PackedDLRM(cfg=cfg),
        optimizer=getattr(optax, opt["name"])(opt["learning_rate"]),
        loss="bce",
        feature_columns=columns(sizes),
        label_column="label",
        feature_dtype=np.float32,
        label_dtype=np.float32,
    )


def check_batch(sizes: dict, traffic: dict, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    dense = np.log1p(rng.gamma(1.5, 2.0, (CHECK_ROWS, sizes["dense_features"])))
    ids = np.stack(
        [rng.integers(0, v, CHECK_ROWS) for v in sizes["vocab_sizes"]], axis=1
    )
    return np.concatenate([dense, ids], axis=1).astype(np.float32)


def reference_logits(params, x, sizes: dict):
    """Plain float32 DLRM forward pass: bottom MLP on the dense features,
    one table row per categorical feature, pairwise dot products of the 27
    vectors (strict lower triangle, row-major, as the reference's
    ``interact_features``), concatenated behind the bottom output, top MLP,
    one logit."""
    p = params["params"]["dlrm"]
    d = sizes["dense_features"]
    with jax.default_matmul_precision("highest"):
        h = x[:, :d].astype(jnp.float32)
        for i in range(len(sizes["bottom_mlp"])):
            layer = p[f"bottom_{i}"]
            h = jax.nn.relu(h @ layer["kernel"] + layer["bias"])
        ids = x[:, d:].astype(jnp.int32)
        vecs = [h] + [
            p[f"emb_{t}"]["table"][ids[:, t]]
            for t in range(len(sizes["vocab_sizes"]))
        ]
        feats = jnp.stack(vecs, axis=1)
        z = jnp.einsum("bfd,bgd->bfg", feats, feats)
        pairs = [
            z[:, i, j] for i in range(feats.shape[1]) for j in range(i)
        ]
        top = jnp.concatenate([h, jnp.stack(pairs, axis=1)], axis=1)
        for i in range(len(sizes["top_mlp"])):
            layer = p[f"top_{i}"]
            top = jax.nn.relu(top @ layer["kernel"] + layer["bias"])
        return (top @ p["logit"]["kernel"] + p["logit"]["bias"])[:, 0]


def _mlp_shapes(sizes: dict):
    n_vec = 1 + len(sizes["vocab_sizes"])
    pairs = n_vec * (n_vec - 1) // 2
    bottom = [sizes["dense_features"], *sizes["bottom_mlp"]]
    top = [sizes["embed_dim"] + pairs, *sizes["top_mlp"], 1]
    return bottom, top, pairs


def flops_per_sample(sizes: dict, traffic: dict) -> float:
    """3 x 2 x (MLP matrix parameters + the 351 pairwise dot products of
    width 16). The table lookups are gathers and count as bytes."""
    bottom, top, pairs = _mlp_shapes(sizes)
    macs = sum(a * b for a, b in zip(bottom, bottom[1:]))
    macs += sum(a * b for a, b in zip(top, top[1:]))
    macs += pairs * sizes["embed_dim"]
    return 3.0 * 2 * macs


def bytes_per_step(sizes: dict, traffic: dict, batch: int) -> float:
    """Bytes a step needs: for every table row the batch touches (at most
    one per sample and table), the float32 row and its Adagrad accumulator
    read and written once; the MLP parameters and accumulators read and
    written once; the batch read. NOT the tables: a step that streams all
    2.16 GB of them does work the batch does not ask for."""
    bottom, top, _ = _mlp_shapes(sizes)
    mlp = sum(a * b + b for a, b in zip(bottom, bottom[1:]))
    mlp += sum(a * b + b for a, b in zip(top, top[1:]))
    rows = batch * len(sizes["vocab_sizes"])
    row_bytes = 4 * sizes["embed_dim"]
    features = sizes["dense_features"] + len(sizes["vocab_sizes"]) + 1
    return 4.0 * rows * row_bytes + 4.0 * 4 * mlp + 4.0 * batch * features
