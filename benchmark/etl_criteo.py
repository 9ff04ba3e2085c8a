"""The Criteo preprocessing of the reference's DLRM notebook, twice: on the
DataFrame engine (what the ``etl_fit`` cell times) and in plain
pandas/numpy (what decides ``correct``).

Per chunk: nulls of the dense columns become 0, then ``log(x + 1)``; each
categorical column is counted (``groupBy().count()``), ids seen fewer than
``min_count`` times collapse to id 0 and the survivors are renumbered
densely from 1 in ascending order of the raw id.

This is the benchmark's own copy of ``examples/dlrm_criteo.py:
remap_rare_ids`` with two differences. The example's ``len(keep) + 1`` ids
overrun a table whose every value survives, so ids here are held inside
``[0, table size)`` (``minimum(id, size - 1)``; the generator draws from
``size - 1`` values, so the clamp never bites on the benchmark's data).
And the example maps ids through a row-at-a-time ``@udf``; here the
mapping is one vectorised ``map_batches`` over all columns, the form a
user who pays for the cluster writes.
"""
from __future__ import annotations

import numpy as np
import pandas as pd


def columns(sizes: dict):
    dense = [f"I{i}" for i in range(sizes["dense_features"])]
    cats = [f"C{t}" for t in range(len(sizes["vocab_sizes"]))]
    return dense, cats


def _remap(values: np.ndarray, keep: np.ndarray, size: int) -> np.ndarray:
    """Raw ids -> 1 + rank among the sorted survivors ``keep``, 0 if rare."""
    if len(keep) == 0:
        return np.zeros(len(values), np.int64)
    pos = np.minimum(np.searchsorted(keep, values), len(keep) - 1)
    ids = np.where(keep[pos] == values, pos + 1, 0)
    return np.minimum(ids, size - 1).astype(np.int64)


def engine_transform(df, sizes: dict, min_count: int):
    """``df`` is a raydp_tpu DataFrame of raw rows; returns the DataFrame in
    model form (float dense columns, int64 ids, label)."""
    import pyarrow as pa

    from raydp_tpu.dataframe import col, log

    dense, cats = columns(sizes)
    keeps = {}
    for c in cats:
        counts = df.groupBy(c).count().to_pandas()
        keeps[c] = np.sort(
            counts.loc[counts["count"] >= min_count, c].to_numpy(np.int64)
        )
    table_size = dict(zip(cats, sizes["vocab_sizes"]))

    def remap_all(t: pa.Table) -> pa.Table:
        for c, keep in keeps.items():
            ids = _remap(
                t.column(c).to_numpy().astype(np.int64), keep, table_size[c]
            )
            t = t.set_column(t.column_names.index(c), c, pa.array(ids))
        return t

    out = df.fillna(0.0, subset=dense)
    for c in dense:
        out = out.withColumn(c, log(col(c) + 1.0))
    return out.map_batches(remap_all).select(*dense, *cats, "label")


def reference_transform(raw: pd.DataFrame, sizes: dict, min_count: int):
    """The same transform on a pandas frame, sharing no code with the
    engine (``value_counts`` and a dict lookup in place of the exchange
    and ``searchsorted``)."""
    dense, cats = columns(sizes)
    out = {}
    for c in dense:
        x = raw[c].to_numpy(np.float64)
        out[c] = np.log(np.where(np.isnan(x), 0.0, x) + 1.0)
    for c, size in zip(cats, sizes["vocab_sizes"]):
        vc = raw[c].value_counts()
        keep = sorted(int(v) for v in vc.index[vc.to_numpy() >= min_count])
        lookup = {v: min(i + 1, size - 1) for i, v in enumerate(keep)}
        out[c] = raw[c].map(lookup).fillna(0).to_numpy(np.int64)
    out["label"] = raw["label"].to_numpy()
    return pd.DataFrame(out)


def compare(engine_out: pd.DataFrame, reference: pd.DataFrame, sizes: dict):
    """(ok, detail): ids exactly, floats to 1e-6, after putting both in one
    row order (the engine's partitions may come back in any order)."""
    dense, cats = columns(sizes)
    order = dense + cats + ["label"]
    if len(engine_out) != len(reference):
        return False, f"rows {len(engine_out)} != {len(reference)}"
    a = engine_out[order].sort_values(order).reset_index(drop=True)
    b = reference[order].sort_values(order).reset_index(drop=True)
    for c, size in zip(cats, sizes["vocab_sizes"]):
        if not np.array_equal(a[c].to_numpy(), b[c].to_numpy()):
            return False, f"ids of {c} differ"
        if a[c].min() < 0 or a[c].max() >= size:
            return False, f"{c} leaves [0, {size})"
    err = float(np.max(np.abs(
        a[dense].to_numpy(np.float64) - b[dense].to_numpy(np.float64)
    )))
    if not err <= 1e-6:
        return False, f"dense columns differ by {err}"
    return True, f"{len(a)} rows, max dense error {err:.2e}"
