"""The row path of the train step: the optimizer applied to the table
rows a batch names, not to the tables.

An embedding table of millions of rows has a gradient in at most as many
rows as the batch holds ids. With an optimizer for which a row of zero
gradient keeps its value and its state (Adagrad, plain SGD), gathering
the touched rows and their state rows, running the optimizer on that
compact block and scattering the rows back IS the dense step: the same
numbers, without a pass over the table. The dense step is the special
case "all rows".

The model's side of it is two variable collections
(``raydp_tpu.models.dlrm.ROW_IDS`` / ``ROWS``): a module whose parameter
is looked up by row sows the ids it looks up, and takes the gathered rows
in place of its parameter. The step's side is here:

* :func:`plan` — which tables take the row path, from what the step can
  observe: (a) the lookup is a gather (the module sowed ids), (b) the
  table is large enough, in rows for each id the step looks up in it
  and in bytes, for the row path to beat a dense pass, (c) its rows are
  not sharded over the mesh, (d) the optimizer is :func:`row_exact`.
* :func:`make_step` — dedup the ids, gather each table's rows once,
  differentiate with respect to the gathered rows, one ``tx.update`` on
  the tree in which every such table and every optimizer-state leaf that
  mirrors it is replaced by its ``[n_ids, D]`` block, scatter back.

No op of the step has a whole row-path table as an operand, except the
row gather and the in-place row scatter.
"""
from __future__ import annotations

import contextlib
import functools
import logging
import operator
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import optax
from flax.traverse_util import flatten_dict, unflatten_dict
from jax.tree_util import DictKey, tree_map_with_path

from raydp_tpu.models.dlrm import ROW_IDS, ROWS

logger = logging.getLogger(__name__)

Path = Tuple[str, ...]

#: Rows per scatter op, and what a table must have to take the row path:
#: rows for each id the step looks up in it, and bytes. All three are
#: TPU v5e measurements, PERF.md §6 (PR 25): a dense pass costs about
#: 0.7 us per 1,000 rows and the row path 0.1 us per id, equal near 128
#: rows an id; and a table of 18 MB is copied into fast memory, in
#: another layout, around every gather and scatter (2.5 times the dense
#: pass), one of 26 MB is not.
SCATTER_CHUNK = 256
MIN_ROWS_PER_ID = 128
MIN_TABLE_BYTES = 32 << 20

ROW = "row"
REASONS = {
    "a": "its lookup is not a gather",
    "b": "a dense pass over it is cheaper (rows per id, bytes)",
    "c": "its rows are sharded over the mesh",
    "d": "the optimizer is not row-exact",
}


class Block(NamedTuple):
    """The rows of one table that a step touches."""

    path: Path        # of the table, through the variables
    uids: jax.Array   # [n] distinct row ids, ascending; padding >= n_rows
    inverse: jax.Array  # [n] position in ``uids`` of each looked-up id
    n_real: jax.Array   # how many of ``uids`` are rows of the table
    shape: Tuple[int, ...]  # of the table

    def scope(self):
        """The owning module's path, one scope a name as flax enters
        them: a transform wraps only the first name it meets inside
        (``jvp(rows)/dlrm/emb_3/...``), so the module's names come
        after one of the step's own."""
        stack = contextlib.ExitStack()
        for name in ("rows",) + self.path[1:-1]:
            stack.enter_context(jax.named_scope(name))
        return stack


def plan(
    ids: Dict[Path, Any],
    tables: Dict[Path, Any],
    row_sharded: Callable[[Path], bool],
    tx_row_exact: bool,
) -> Dict[Path, str]:
    """``ROW`` or the letter of what keeps it dense, for each table
    that ``ids`` (arrays or their shapes) are looked up in."""

    def verdict(path: Path) -> str:
        n_ids, table = ids[path].size, tables[path]
        if n_ids == 0:
            return "a"
        if (table.shape[0] <= MIN_ROWS_PER_ID * n_ids
                or table.size * table.dtype.itemsize < MIN_TABLE_BYTES):
            return "b"
        if row_sharded(path):
            return "c"
        return ROW if tx_row_exact else "d"

    return {path: verdict(path) for path in ids}


def report(verdicts: Dict[Path, str], tables: Dict[Path, Any]):
    """The choice is static for a compiled step: two gauges and one log
    line where the step is built, nothing per step."""
    from raydp_tpu.utils.profiling import metrics

    rows = {path: tables[path].shape[0] for path in verdicts}
    on = [path for path, v in verdicts.items() if v == ROW]
    share = sum(rows[p] for p in on) / max(1, sum(rows.values()))
    metrics.gauge_set("train/rowsparse_tables", len(on))
    metrics.gauge_set("train/rowsparse_row_share", share)
    if not verdicts:
        return
    dense = "; ".join(
        f"({v}) {REASONS[v]}: "
        + ", ".join("/".join(p[1:-1]) for p in verdicts if verdicts[p] == v)
        for v in sorted(set(verdicts.values()) - {ROW})
    )
    logger.info(
        "train step row path: %d of %d tables, %.4f%% of their rows; "
        "dense: %s",
        len(on), len(verdicts), 100.0 * share, dense or "none",
    )


def table_paths(row_ids) -> Dict[Path, Any]:
    """What the modules sowed into ``ROW_IDS``, keyed by the path of the
    parameter the ids index (its collection first)."""
    return {
        ("params",) + path: ids
        for path, ids in flatten_dict(dict(row_ids)).items()
    }


def leaf_at(tree, path: Path):
    return functools.reduce(operator.getitem, path, tree)


def _dict_path(path) -> Path:
    """The trailing run of dict keys of a tree path: where a leaf of an
    optimizer state sits in the parameter tree that the state mirrors."""
    names = []
    for key in reversed(path):
        if not isinstance(key, DictKey):
            break
        names.append(key.key)
    return tuple(reversed(names))


def _on_tables(fn, blocks: Dict[Path, Block], tree, *rest):
    """``fn(block, leaf, *rest_leaves)`` on every leaf of ``tree`` that
    is a row-path table or mirrors one (same dict path, same shape); any
    other leaf comes from the last tree given."""

    def one(path, leaf, *others):
        names = _dict_path(path)
        for table, block in blocks.items():
            if names[-len(table):] == table and leaf.shape == block.shape:
                return fn(block, leaf, *others)
        return others[-1] if others else leaf

    return tree_map_with_path(one, tree, *rest)


def dedup(ids, n_rows: int):
    """``(uids, inverse, n_real)``: the distinct ids, ascending, at the
    front of a vector padded to the static ``len(ids)``; each id's
    position among them; how many are real. Padding is ``n_rows + i``:
    out of range (the gather clips it, the scatter drops it), ascending
    and distinct, so the whole vector is sorted and unique. Three sorts
    and no scatter: on the chip a 4,096-element sort costs a few
    microseconds, a 4,096-element scatter a hundred."""
    n = ids.shape[0]
    iota = jnp.arange(n, dtype=ids.dtype)
    # As the dense lookup: a negative id counts from the end. An id
    # beyond the table is invalid input; it reads and updates the last row.
    ids = jnp.clip(jnp.where(ids < 0, ids + n_rows, ids), 0, n_rows - 1)
    sorted_ids, order = jax.lax.sort((ids, iota), num_keys=1)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]]
    )
    slot = jnp.cumsum(first, dtype=ids.dtype) - 1   # of a sorted position
    _, rank = jax.lax.sort((order, iota), num_keys=1)  # order's inverse
    uids = jax.lax.sort(jnp.where(first, sorted_ids, n_rows + iota))
    return uids, jnp.take(slot, rank), slot[-1] + 1


def gather_rows(block: "Block", leaf):
    """``leaf[uids]``; a padding slot reads the last row."""
    return jnp.take(leaf, block.uids, axis=0, mode="clip")


def scatter_rows(block: "Block", leaf, rows):
    """``leaf[uids] = rows`` in place, ``SCATTER_CHUNK`` rows a time and
    only as many chunks as hold a real id: XLA's TPU scatter is a serial
    loop over its updates, dropped ones included, and a Zipf batch names
    half as many distinct rows as it has ids."""
    n = block.uids.shape[0]
    chunk = min(n, SCATTER_CHUNK)

    def body(k, leaf):
        # dynamic_slice clamps the last start: rows written twice are
        # written with the same values.
        uids = jax.lax.dynamic_slice(block.uids, (k * chunk,), (chunk,))
        part = jax.lax.dynamic_slice(
            rows, (k * chunk, 0), (chunk,) + rows.shape[1:]
        )
        return leaf.at[uids].set(
            part, mode="drop", indices_are_sorted=True, unique_indices=True
        )

    return jax.lax.fori_loop(0, -(-block.n_real // chunk), body, leaf)


def apply_rows(tx, params, opt_state, compact, grads, blocks):
    """One ``tx.update`` on the compact tree (``compact`` and ``grads``
    hold ``[n, D]`` row blocks where ``params`` holds a row-path table),
    then rows and state rows scattered back: ``(params, opt_state)``."""
    state = _on_tables(gather_rows, blocks, opt_state)
    updates, state = tx.update(grads, state, compact)
    compact = optax.apply_updates(compact, updates)

    return (
        _on_tables(scatter_rows, blocks, params, compact),
        _on_tables(scatter_rows, blocks, opt_state, state),
    )


def row_exact(tx: optax.GradientTransformation) -> bool:
    """Whether ``tx`` on the touched rows alone is ``tx`` on everything.

    Probed, not looked up: three steps on an 8-row parameter beside a
    dense one, each step touching other rows (one id twice), once densely
    and once through :func:`apply_rows`. Every parameter and every state
    leaf must come out bit for bit the same. Adagrad and plain SGD do;
    whatever decays a moment, a weight or an accumulator on a zero
    gradient, or takes a norm over a parameter, does not."""
    table = (jnp.arange(24, dtype=jnp.float32).reshape(8, 3) + 1.0) / 7.0
    params = {"params": {"t": {"table": table},
                         "o": jnp.asarray([0.5, -1.5], jnp.float32)}}
    path = ("params", "t", "table")

    def run():
        dense = rows = (params, tx.init(params))
        for step, ids in enumerate(([1, 5, 5], [2, 5, 7], [1, 2, 2])):
            block = Block(
                path, *dedup(jnp.asarray(ids, jnp.int32), 8), table.shape
            )
            g_ids = jnp.cos(jnp.arange(9, dtype=jnp.float32) + step)
            g_rows = jax.ops.segment_sum(
                g_ids.reshape(3, 3), block.inverse, num_segments=3
            )
            g_other = jnp.asarray([0.25, -0.75], jnp.float32) * (step + 1)

            def grads(g_table):
                return {"params": {"t": {"table": g_table}, "o": g_other}}

            g_dense = scatter_rows(block, jnp.zeros_like(table), g_rows)
            updates, state = tx.update(grads(g_dense), dense[1], dense[0])
            dense = (optax.apply_updates(dense[0], updates), state)

            blocks = {path: block}
            compact = _on_tables(gather_rows, blocks, rows[0])
            rows = apply_rows(tx, *rows, compact, grads(g_rows), blocks)
        same = jax.tree_util.tree_map(
            lambda a, b: jnp.array_equal(a, b, equal_nan=True), dense, rows
        )
        return jnp.all(jnp.stack(jax.tree_util.tree_leaves(same)))

    try:
        return bool(jax.jit(run)())
    except Exception:  # a transform that cannot take the compact tree
        logger.debug("row-exact probe raised; dense step", exc_info=True)
        return False


def make_step(loss_of, ids_of, choose, dense_step):
    """The ``(state, x, y, rng) → (state, loss, gnorm, stats)`` step on the
    row path. ``loss_of(state, variables, x, y, rng)`` is the objective
    and what the model sowed about the step,
    ``ids_of(state, x, rng)`` the ``ROW_IDS`` collection of one apply,
    ``choose(ids, tables)`` the plan; a step whose plan is empty at its
    shapes is ``dense_step``."""

    def train_step(state, x, y, rng):
        variables = state.params
        ids = table_paths(ids_of(state, x, rng))
        tables = {p: leaf_at(variables, p) for p in ids}
        verdicts = choose(ids, tables)
        blocks = {}
        for path, verdict in verdicts.items():
            if verdict == ROW:
                shape = tables[path].shape
                with jax.named_scope("part:update"):
                    block = dedup(ids[path], shape[0])
                blocks[path] = Block(path, *block, shape)
        if not blocks:
            return dense_step(state, x, y, rng)

        def gather(block, table):
            with block.scope():
                return gather_rows(block, table)

        compact = _on_tables(gather, blocks, variables)

        def compute(compact):
            # The tables ride along as constants: the modules declare
            # them and do not read them. Gradients are the blocks'.
            full = _on_tables(lambda _, table, rows: table,
                              blocks, variables, compact)
            rows = {}
            for block in blocks.values():
                with block.scope():
                    rows[block.path[1:]] = jnp.take(
                        leaf_at(compact, block.path), block.inverse, axis=0
                    )
            return loss_of(
                state, {**full, ROWS: unflatten_dict(rows)}, x, y, rng
            )

        (loss_val, stats), grads = jax.value_and_grad(
            compute, has_aux=True
        )(compact)
        # The blocks hold every nonzero entry of the tables' gradients,
        # duplicates summed: the norm is the dense one.
        with jax.named_scope("part:grad_norm"):
            gnorm = optax.global_norm(grads)
        with jax.named_scope("part:update"):
            params, opt_state = apply_rows(
                state.tx, variables, state.opt_state, compact, grads, blocks
            )
        state = state.replace(
            step=state.step + 1, params=params, opt_state=opt_state
        )
        return state, loss_val, gnorm, stats

    return train_step
