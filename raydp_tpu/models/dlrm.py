"""DLRM: deep learning recommendation model with mesh-sharded embeddings.

The DLRM/Criteo benchmark config of BASELINE.md. The reference runs DLRM
replicated on 2 Ray Train workers (reference: examples/pytorch_dlrm.ipynb,
final cells — plain TorchEstimator, embeddings fully replicated per GPU);
sharded embedding tables are a new capability (SURVEY §2.4
"Embedding-table sharding" row: absent in reference).

TPU-first design:

* **Row-sharded tables over ``tp``** — each table carries logical axes
  ``('vocab', 'embed')``; the default rules map ``vocab → tp`` so a
  table's rows are split across the tensor-parallel axis and stay in HBM.
* **Lookup as one-hot matmul** (``embedding_impl='onehot'``): a
  ``[B, V] @ [V, D]`` contraction whose contracting dim is sharded, so
  GSPMD partitions it locally and inserts one ``psum`` over ``tp`` — the
  canonical sharded-embedding-lookup collective, and it runs on the MXU
  instead of the scatter/gather units. ``'take'`` keeps small tables
  replicated with a plain gather; ``'auto'`` switches on vocab size AND
  backend (accelerators only — on CPU the one-hot is pure flop
  inflation, so auto always gathers there).
* **The row path of ``take``** — a ``take`` table sows the ids it looks
  up (collection ``ROW_IDS``) and accepts the gathered rows from outside
  (collection ``ROWS``). ``JAXEstimator``'s train step uses both
  (``train/rowsparse.py``): it gathers the float32 rows of the batch's
  distinct ids once, the forward casts that ``[n, D]`` block to
  ``dtype``, the gradient is taken with respect to the block, and the
  optimizer runs on the block and on the same rows of its own state
  before one in-place row scatter — no pass over the table. It engages,
  per table and with no switch, when the table is large enough for it
  to beat a dense pass (128 rows for each id the step looks up in it,
  and 32 MiB: measured, ``rowsparse.MIN_ROWS_PER_ID``), its rows are
  not sharded over the mesh, and
  the optimizer is row-exact (a zero-gradient row keeps value and state:
  Adagrad, plain SGD; not Adam, momentum or weight decay — probed, see
  ``rowsparse.row_exact``). Otherwise, and in ``predict``/``evaluate``
  or any plain ``apply``, the lookup is ``table.astype(dtype)[ids]`` as
  before. Out-of-range ids are invalid input either way; the row path
  reads the last row for them where the dense lookup reads NaN.
* **Dot-product feature interaction** with static lower-triangle
  indices (no dynamic shapes), bf16 through the trunk, f32 logits.
* Multi-hot bags: pass ids ``[B, n_tables, L]`` with sum/mean pooling —
  pooling happens *before* the psum so bytes over ICI stay ``B×D``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn

from raydp_tpu.models.transformer import param_shardings  # generic helper

# Logical axis → mesh axis for DLRM. Embedding rows shard over tp; the
# batch shards over dp (and pp when present, handled by estimator).
LOGICAL_RULES: Tuple[Tuple[str, Optional[str]], ...] = (
    ("batch", "dp"),
    ("vocab", "tp"),
    ("embed", None),
    ("mlp", "tp"),
    ("hidden", None),
)

# Above this vocab size 'auto' switches from replicated-take to the
# sharded one-hot contraction (one-hot flops beat replicating big tables).
AUTO_ONEHOT_THRESHOLD = 8192


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    """Criteo-shaped defaults: 13 dense features, 26 categorical tables."""

    dense_features: int = 13
    vocab_sizes: Tuple[int, ...] = tuple([100_000] * 26)
    embed_dim: int = 128                     # MXU-aligned
    bottom_mlp: Tuple[int, ...] = (512, 256, 128)
    top_mlp: Tuple[int, ...] = (1024, 1024, 512, 256)
    interaction: str = "dot"                 # dot | cat
    embedding_impl: str = "auto"             # auto | take | onehot
    pooling: str = "sum"                     # sum | mean (multi-hot bags)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @property
    def n_tables(self) -> int:
        return len(self.vocab_sizes)

    def impl_for(self, vocab: int) -> str:
        if self.embedding_impl != "auto":
            return self.embedding_impl
        if vocab < AUTO_ONEHOT_THRESHOLD:
            return "take"
        # The one-hot contraction is an ACCELERATOR trade: it moves the
        # lookup onto the MXU and gives GSPMD a contracting dim to
        # partition (one psum over tp). On CPU the [B, V] one-hot is
        # pure flop inflation — a 10k-vocab table turns a gather into a
        # ~2.6 GMAC matmul per step (measured 5x whole-model slowdown in
        # the CPU-fallback DLRM bench). Auto therefore consults the
        # backend; the CPU-mesh sharding test pins impl='onehot'
        # explicitly (tests/test_dlrm.py::test_sharded_tables_on_tp_mesh)
        # so that path keeps end-to-end coverage without a TPU.
        import jax

        return "onehot" if jax.default_backend() != "cpu" else "take"


#: The row path's two variable collections (``raydp_tpu/train/rowsparse.py``
#: is the other side). A module whose parameter is looked up by row sows
#: the flat ids into ``ROW_IDS`` under the parameter's own name, whenever
#: the caller makes that collection mutable; an empty id vector says the
#: lookup is not a gather. Where the caller passes ``ROWS`` with an entry
#: of that name, it holds ``param[ids]``, ``[len(ids), D]``, and the module
#: uses it instead of reading the parameter.
ROW_IDS = "row_ids"
ROWS = "rows"


def _mlp_init(*logical_axes):
    return nn.with_logical_partitioning(
        nn.initializers.xavier_uniform(), logical_axes
    )


class ShardedEmbedding(nn.Module):
    """One embedding table with vocab-dim sharding metadata.

    ``ids`` is ``[B]`` (one-hot) or ``[B, L]`` (multi-hot bag, pooled).
    """

    vocab_size: int
    embed_dim: int
    impl: str = "take"
    pooling: str = "sum"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, ids):
        table = self.param(
            "table",
            nn.with_logical_partitioning(
                nn.initializers.normal(
                    stddev=1.0 / np.sqrt(self.embed_dim)
                ),
                ("vocab", "embed"),
            ),
            (self.vocab_size, self.embed_dim),
            self.param_dtype,
        )

        squeeze = ids.ndim == 1
        if squeeze:
            ids = ids[:, None]              # [B, 1] — unify with bags

        if self.impl == "onehot":
            # No row of a contraction can be handed in from outside.
            self._sow_row_ids(jnp.zeros((0,), jnp.int32))
            table = table.astype(self.dtype)
            # Sum over the bag inside the contraction: multiply the
            # one-hot along L before the matmul so the [B, V] operand is
            # the pooled bag indicator and the psum moves B×D, not B×L×D.
            oh = jax.nn.one_hot(ids, self.vocab_size, dtype=self.dtype)
            bag = oh.sum(axis=1)            # [B, V]
            out = bag @ table               # GSPMD: local matmul + psum(tp)
        elif self.impl == "take":
            self._sow_row_ids(ids.reshape(-1))
            if self.has_variable(ROWS, "table"):
                # Row path: the caller gathered table[ids] already, in
                # the table's own dtype; the table is not read here.
                rows = self.get_variable(ROWS, "table").astype(self.dtype)
                out = rows.reshape(ids.shape + (self.embed_dim,)).sum(axis=1)
            else:
                out = jnp.take(
                    table.astype(self.dtype), ids, axis=0
                ).sum(axis=1)
        else:
            raise ValueError(f"unknown embedding impl {self.impl!r}")

        if self.pooling == "mean" and not squeeze:
            out = out / ids.shape[1]
        return out                           # [B, D]

    def _sow_row_ids(self, ids):
        # Not while initializing: ``init`` makes every collection mutable,
        # and the ids are no variable of the model.
        if not self.is_initializing():
            self.sow(ROW_IDS, "table", ids,
                     reduce_fn=lambda _, new: new, init_fn=lambda: None)


class DotInteraction(nn.Module):
    """Pairwise dot products of feature vectors (lower triangle, no
    self-interactions) — static indices, one batched matmul."""

    @nn.compact
    def __call__(self, feats):               # [B, F, D]
        z = jnp.einsum("bfd,bgd->bfg", feats, feats)
        li, lj = np.tril_indices(feats.shape[1], k=-1)
        return z[:, li, lj]                  # [B, F*(F-1)/2]


class DLRM(nn.Module):
    """Bottom MLP over dense features + sharded embedding bag per
    categorical feature + feature interaction + top MLP → CTR logit."""

    cfg: DLRMConfig

    @nn.compact
    def __call__(self, dense, sparse):
        """dense: ``[B, dense_features]`` float; sparse: int ids
        ``[B, n_tables]`` or ``[B, n_tables, L]`` (bags)."""
        cfg = self.cfg
        x = dense.astype(cfg.dtype)
        for i, width in enumerate(cfg.bottom_mlp):
            x = nn.Dense(
                width,
                kernel_init=_mlp_init("hidden", "mlp"),
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name=f"bottom_{i}",
            )(x)
            x = nn.relu(x)
        if cfg.bottom_mlp[-1] != cfg.embed_dim:
            raise ValueError(
                "bottom MLP output width must equal embed_dim "
                f"({cfg.bottom_mlp[-1]} != {cfg.embed_dim})"
            )

        embs = []
        for t, vocab in enumerate(cfg.vocab_sizes):
            ids = sparse[:, t]
            embs.append(
                ShardedEmbedding(
                    vocab, cfg.embed_dim,
                    impl=cfg.impl_for(vocab), pooling=cfg.pooling,
                    dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                    name=f"emb_{t}",
                )(ids)
            )

        feats = jnp.stack([x] + embs, axis=1)   # [B, 1+T, D]
        feats = nn.with_logical_constraint(feats, ("batch", None, "embed"))
        if cfg.interaction == "dot":
            inter = DotInteraction(name="interaction")(feats)
            top = jnp.concatenate([x, inter], axis=-1)
        elif cfg.interaction == "cat":
            top = feats.reshape(feats.shape[0], -1)
        else:
            raise ValueError(f"unknown interaction {cfg.interaction!r}")

        for i, width in enumerate(cfg.top_mlp):
            top = nn.Dense(
                width,
                kernel_init=_mlp_init("hidden", "mlp"),
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name=f"top_{i}",
            )(top)
            top = nn.relu(top)
        # f32 logit for a stable sigmoid/BCE.
        return nn.Dense(
            1, kernel_init=_mlp_init("hidden", None),
            dtype=jnp.float32, param_dtype=cfg.param_dtype, name="logit",
        )(top)[:, 0]


def dlrm_shardings(model: DLRM, mesh, dense, sparse):
    """(abstract_variables, NamedShardings) for DLRM params under the
    DLRM logical rules — big tables land row-sharded over ``tp``."""
    return param_shardings(model, mesh, dense, sparse, rules=LOGICAL_RULES)


class PackedDLRM(nn.Module):
    """DLRM over a single packed feature matrix — the ``fit_on_df`` form.

    ``x`` is ``[B, dense_features + n_tables]``: the leading columns are
    dense floats, the trailing ones categorical ids (float-encoded by the
    DataFrame→tensor path; cast back to int here). Lets a CTR table flow
    DataFrame → MLDataset → JAXEstimator without a custom batch adapter.
    """

    cfg: DLRMConfig

    @nn.compact
    def __call__(self, x):
        d = self.cfg.dense_features
        dense = x[:, :d]
        sparse = x[:, d:].astype(jnp.int32)
        return DLRM(self.cfg, name="dlrm")(dense, sparse)


# ---------------------------------------------------------------- factories

def criteo_dlrm(**overrides) -> DLRMConfig:
    """The Criteo Terabyte-shaped config (BASELINE.md DLRM row)."""
    return DLRMConfig(**overrides)


def tiny_dlrm(**overrides) -> DLRMConfig:
    """Small config for tests/dry runs. With the default
    ``embedding_impl='auto'`` every table resolves to ``take`` on CPU
    hosts (backend-aware auto); pass ``embedding_impl='onehot'`` to
    exercise the sharded-contraction path on a CPU mesh."""
    defaults = dict(
        dense_features=4,
        vocab_sizes=(64, 10_000, 128, 32),   # mixes take + onehot paths
        embed_dim=16,
        bottom_mlp=(32, 16),
        top_mlp=(32, 16),
    )
    defaults.update(overrides)
    return DLRMConfig(**defaults)
