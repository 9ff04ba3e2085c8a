"""Tokenized-GLUE stand-in (the task of ``chip_smoke._glue_frame``), drawn
vectorised from ``numpy.random.default_rng(seed)``: one seed, one data set.

``marker`` says whether token 7 occurs in the row; every ``invalid_every``-th
row is flagged invalid for the ETL filter to drop, so ``rows`` valid rows
come out of ``rows * k / (k - 1)`` raw ones.
"""
import numpy as np


def generate(seed: int, sizes: dict, *, rows: int, seq_len: int,
             invalid_every: int = 5) -> dict:
    seq, vocab = seq_len, sizes["vocab_size"]
    n_raw = rows * invalid_every // (invalid_every - 1)
    rng = np.random.default_rng(seed)
    ids = rng.integers(10, vocab, size=(n_raw, seq))
    pos = rng.random(n_raw) < 0.5
    ids[pos, rng.integers(0, seq, int(pos.sum()))] = 7
    cols = {f"t{i}": ids[:, i] for i in range(seq)}
    cols["marker"] = pos.astype(np.int64)
    cols["valid"] = (
        np.arange(n_raw) % invalid_every != invalid_every - 1
    ).astype(np.int64)
    return cols
