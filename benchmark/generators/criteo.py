"""Criteo-shaped rows: dense features (gamma, ``null_share`` of them
missing), one Zipf-distributed id per row and table, and a label that
depends on both. ``form`` says how the columns come out:

``raw``    nullable float dense columns ``I*``, the categorical columns
           ``C*`` as 64-bit hashed ids, and ``label``: what the ETL reads;
``final``  the same rows in the form the model reads: nulls filled with 0,
           ``log(x + 1)`` of the dense columns, ids in [0, table size) as
           float32 (every Criteo-Kaggle id is below 2**24, so the float is
           exact).

Every column has a generator of its own spawned from the seed, so the
columns are drawn on a few threads (numpy releases the GIL) and still
repeat exactly.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

GENERATOR_THREADS = 8

# Prime above every table size: ``rank * _SPREAD % size`` is a bijection on
# [0, size), so the popular ranks do not sit in adjacent table rows.
_SPREAD = 2_147_483_647
# Odd multiplier of the 64-bit hash the raw categorical ids are stored as.
_HASH_MULT = 0x9E3779B97F4A7C15


def zipf_ranks(rng, n: int, size: int, s: float) -> np.ndarray:
    """``n`` ranks in [0, size) with P(rank) ~ (rank + 1) ** -s, by the
    inverse of the continuous CDF (exact enough for a load shape; ``s``
    must not be 1)."""
    u = rng.random(n)
    top = float(size + 1) ** (1.0 - s)
    r = np.floor((u * (top - 1.0) + 1.0) ** (1.0 / (1.0 - s))) - 1.0
    return np.clip(r, 0, size - 1).astype(np.int64)


def _raw_dense(x, missing):
    return np.where(missing, np.float32(np.nan), x)


def _raw_id(t, ranks, size):
    h = (ranks.astype(np.uint64) + np.uint64(t + 1)) * np.uint64(_HASH_MULT)
    return (h >> np.uint64(1)).astype(np.int64)


def _final_dense(x, missing):
    return np.log1p(np.where(missing, np.float32(0), x))


def _final_id(t, ranks, size):
    if size < 2:
        return np.zeros(len(ranks), np.float32)
    return (1 + (ranks * _SPREAD) % (size - 1)).astype(np.float32)


FORMS = {"raw": (_raw_dense, _raw_id), "final": (_final_dense, _final_id)}


def generate(seed: int, sizes: dict, *, rows: int, form: str,
             zipf_s: float = 1.1, null_share: float = 0.1) -> dict:
    dense_form, id_form = FORMS[form]
    n_dense, vocab = sizes["dense_features"], sizes["vocab_sizes"]
    seeds = np.random.SeedSequence(seed).spawn(n_dense + len(vocab) + 1)

    def dense_column(i):
        rng = np.random.default_rng(seeds[i])
        x = 2.0 * rng.standard_gamma(1.5, rows, dtype=np.float32)
        missing = rng.random(rows, dtype=np.float32) < null_share
        return x, dense_form(x, missing)

    def id_column(t):
        # Table t holds ids 1..size-1 (0 is the "rare" id), so ranks come
        # from size - 1 values and a renumbering can never reach ``size``.
        rng = np.random.default_rng(seeds[n_dense + t])
        ranks = zipf_ranks(rng, rows, max(1, vocab[t] - 1), zipf_s)
        return ranks[:1] if t else ranks, id_form(t, ranks, vocab[t])

    with ThreadPoolExecutor(max_workers=GENERATOR_THREADS) as pool:
        dense = list(pool.map(dense_column, range(n_dense)))
        ids = list(pool.map(id_column, range(len(vocab))))
    logit = -1.2 + 0.35 * dense[0][0] - 0.2 * dense[1][0] + 0.3 * (
        ids[0][0] % 2
    )
    draw = np.random.default_rng(seeds[-1]).random(rows)
    cols = {f"I{i}": column for i, (_, column) in enumerate(dense)}
    cols.update({f"C{t}": column for t, (_, column) in enumerate(ids)})
    cols["label"] = (draw < 1.0 / (1.0 + np.exp(-logit))).astype(np.float32)
    return cols
