"""Sharded ML dataset: the DataFrame → trainer handoff.

Capability parity with the reference's RayMLDataset layer
(reference: python/raydp/spark/dataset.py:43-457 — RecordPiece shards,
``from_spark``/``from_parquet``/``to_torch``, equal-sample division via
``divide_blocks``, locality-aware shard selection). TPU-first differences:
shards map to the **data axis of the device mesh** (one shard per dp rank),
and consumption is a double-buffered ``jax.device_put`` infeed instead of a
torch DataLoader (though ``to_torch`` exists for interop).
"""
from __future__ import annotations

import contextlib
import math
import threading
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence, Tuple, Union

import numpy as np
import pyarrow as pa

from raydp_tpu.dataframe.scheduler import (
    PendingPartition,
    is_pending,
    resolve_one,
)
from raydp_tpu.store.object_store import ObjectRef, ObjectStore
from raydp_tpu.telemetry import span
from raydp_tpu.utils.sharding import (
    BlockSlice,
    divide_blocks,
    divide_blocks_local,
    locality_fraction,
)

Block = Union[pa.Table, ObjectRef]


class MLDataset:
    """An immutable list of Arrow blocks + a shard plan over them.

    Every shard yields exactly ``ceil(total_rows / num_shards)`` samples per
    epoch (block reuse pads short shards) so SPMD data-parallel steps stay
    in lockstep.
    """

    def __init__(
        self,
        blocks: List[Block],
        num_shards: int,
        shuffle: bool = False,
        shuffle_seed: Optional[int] = None,
        store: Optional[ObjectStore] = None,
        rank_nodes: Optional[List[str]] = None,
    ):
        if not blocks:
            raise ValueError("MLDataset needs at least one block")
        self._blocks = list(blocks)
        self.num_shards = num_shards
        self.shuffle = shuffle
        self.shuffle_seed = shuffle_seed
        self._store = store
        self.rank_nodes = list(rank_nodes) if rank_nodes is not None else None
        if len(blocks) < num_shards:
            raise ValueError(
                f"{len(blocks)} blocks cannot feed {num_shards} shards; "
                "repartition the DataFrame first"
            )
        # Streaming handoff: blocks may still be in-flight ETL tasks
        # (PendingPartition). The shard plan needs every block's size, so
        # it is DEFERRED until a consumer actually needs it
        # (_ensure_plan) — the epoch-0 prefix streamer reads only the
        # monotone lower bound in ``_known`` and never barriers.
        self._plan_mu = threading.Lock()
        self._known: List[Optional[int]] = []
        for b in self._blocks:
            if is_pending(b):
                self._known.append(None)
            elif isinstance(b, ObjectRef):
                self._known.append(
                    b.num_rows if b.num_rows >= 0 else None
                )
            else:
                self._known.append(b.num_rows)
        self._block_sizes: Optional[List[int]] = None
        self.block_nodes: Optional[List[Optional[str]]] = None
        self._shard_plan: Optional[Dict[int, List[BlockSlice]]] = None
        for i, b in enumerate(self._blocks):
            if is_pending(b):
                b.future.add_done_callback(
                    lambda f, i=i: self._note_block(i, f)
                )
        if not any(is_pending(b) for b in self._blocks):
            self._ensure_plan()

    @property
    def blocks(self) -> List[Block]:
        """Concrete blocks (ObjectRefs / tables) — the materialized view
        every non-streaming consumer (store feed, SPMD fit, shard
        readers) sees, so it BARRIERS on blocks still in flight.
        Streaming consumers read ``known_rows()`` /
        ``iter_prefix_tables()`` instead and never touch this."""
        if any(is_pending(b) for b in self._blocks):
            resolved = [resolve_one(b) for b in self._blocks]
            with self._plan_mu:
                self._blocks = resolved
        return self._blocks

    def has_pending_blocks(self) -> bool:
        """True while any block is still an in-flight ETL partition."""
        return any(
            is_pending(b) and not b.future.done() for b in self._blocks
        )

    def known_rows(self) -> Tuple[int, bool]:
        """(sum of block sizes known SO FAR, whether all are known).
        The sum only grows as pending blocks land, so it is a safe lower
        bound of ``total_rows`` — what the epoch-0 prefix streamer sizes
        its emit limit with."""
        with self._plan_mu:
            vals = list(self._known)
        return (
            sum(v for v in vals if v is not None),
            all(v is not None for v in vals),
        )

    def iter_prefix_tables(self) -> Iterator[Tuple[int, pa.Table]]:
        """Yield ``(block_index, table)`` in block order, waiting on each
        pending block IN ORDER — the dataset prefix streams out while
        later blocks are still being produced."""
        for i, b in enumerate(list(self._blocks)):
            table = self._resolve(resolve_one(b))
            with self._plan_mu:
                if self._known[i] is None:
                    self._known[i] = table.num_rows
            yield i, table

    def _note_block(self, i: int, fut) -> None:
        """Done-callback of pending block ``i``: record its row count the
        moment it lands (feeds ``known_rows``)."""
        if fut.exception() is not None:
            return
        ref = fut.result()
        rows = getattr(ref, "num_rows", -1)
        if rows is None or rows < 0:
            return  # unknowable without a fetch; prefix iteration fills it
        with self._plan_mu:
            if self._known[i] is None:
                self._known[i] = int(rows)

    def _ensure_plan(self) -> None:
        """Barrier: resolve every block and build the shard plan. All
        shard accessors funnel through here; until one does, a dataset
        over pending blocks never blocks its creator."""
        if self._shard_plan is not None:
            return
        # Resolve OUTSIDE the lock (arbitrarily long); idempotent, so a
        # racing second consumer just re-resolves the same futures. Over
        # pending blocks this is the wait for the lazily run last ETL
        # stage, seen from the consumer: ``handoff/await_blocks``.
        pending = sum(1 for b in self._blocks if is_pending(b))
        with span(
            "handoff/await_blocks", blocks=len(self._blocks), pending=pending
        ) if pending else contextlib.nullcontext():
            blocks = [resolve_one(b) for b in self._blocks]
        sizes = [self._block_rows(b) for b in blocks]
        with self._plan_mu:
            if self._shard_plan is not None:
                return
            self._blocks = blocks
            self._block_sizes = sizes
            self._known = [int(s) for s in sizes]
            # Locality-aware division when the consumer topology is
            # known: rank_nodes[r] names the node rank r runs on; ref
            # blocks carry their node, so shard plans keep bytes
            # node-local (reference: locality-preferring shard
            # selection, dataset.py:411-443).
            self.block_nodes = [
                b.node_id if isinstance(b, ObjectRef) else None
                for b in blocks
            ]
            if self.rank_nodes is not None and any(
                n is not None for n in self.block_nodes
            ):
                nodes = [n or "node-0" for n in self.block_nodes]
                self._shard_plan = divide_blocks_local(
                    sizes, self.num_shards, nodes, self.rank_nodes,
                    self.shuffle, self.shuffle_seed,
                )
            else:
                self._shard_plan = divide_blocks(
                    sizes, self.num_shards, self.shuffle, self.shuffle_seed
                )

    def locality(self) -> Optional[float]:
        """Fraction of planned samples that are node-local (None when no
        topology was supplied)."""
        if self.rank_nodes is None:
            return None
        self._ensure_plan()
        nodes = [n or "node-0" for n in self.block_nodes]
        return locality_fraction(self._shard_plan, nodes, self.rank_nodes)

    # -- constructors ---------------------------------------------------
    @staticmethod
    def from_df(
        df,
        num_shards: int,
        shuffle: bool = False,
        shuffle_seed: Optional[int] = None,
        owner_transfer: bool = True,
        rank_nodes: Optional[List[str]] = None,
    ) -> "MLDataset":
        """From a raydp_tpu DataFrame (reference: RayMLDataset.from_spark,
        dataset.py:283-310). Repartitions up to ``num_shards`` if short.

        ``rank_nodes`` (one node id per shard rank) turns on
        locality-preferring shard assignment."""
        if df.num_partitions < num_shards:
            df = df.repartition(num_shards)
        from raydp_tpu.context import current_session

        session = current_session()
        if session is not None:
            # Streaming handoff: partitions still being produced arrive
            # as pending futures (owner transfer chained onto each), so
            # to_jax() can ingest early blocks while late ETL partitions
            # are in flight.
            refs = df._to_block_parts(owner_transfer=owner_transfer)
            if refs is None:
                refs = df.to_object_refs(owner_transfer=owner_transfer)
            # The resolver (not the raw store) so blocks written on any
            # node of a multi-host cluster resolve from the driver.
            store = session.cluster.resolver
            return MLDataset(
                refs, num_shards, shuffle, shuffle_seed, store,
                rank_nodes=rank_nodes,
            )
        return MLDataset(
            df.collect_partitions(), num_shards, shuffle, shuffle_seed,
            rank_nodes=rank_nodes,
        )

    @staticmethod
    def from_refs(
        refs: Sequence[ObjectRef],
        num_shards: int,
        shuffle: bool = False,
        shuffle_seed: Optional[int] = None,
        rank_nodes: Optional[List[str]] = None,
    ) -> "MLDataset":
        """Directly from ObjectRefs (parity with the reference's
        ``ray.data.from_arrow_refs`` entry, dataset.py:470-480). Resolves
        through the live session's node-aware resolver."""
        from raydp_tpu.context import require_session

        session = require_session()
        return MLDataset(
            list(refs), num_shards, shuffle, shuffle_seed,
            store=session.cluster.resolver, rank_nodes=rank_nodes,
        )

    @staticmethod
    def from_parquet(
        paths: Union[str, Sequence[str]],
        num_shards: int,
        shuffle: bool = False,
        shuffle_seed: Optional[int] = None,
        columns: Optional[List[str]] = None,
    ) -> "MLDataset":
        """Directly from parquet row groups (reference:
        RayMLDataset.from_parquet, dataset.py:313-349)."""
        import pyarrow.parquet as pq

        from raydp_tpu.dataframe.io import _expand

        if isinstance(paths, str):
            files = _expand(paths, (".parquet", ".pq"))
        else:
            files = list(paths)
        tables: List[pa.Table] = []
        for f in files:
            pf = pq.ParquetFile(f)
            for rg in range(pf.num_row_groups):
                tables.append(pf.read_row_group(rg, columns=columns))
        return MLDataset(tables, num_shards, shuffle, shuffle_seed)

    def to_df(self):
        """Back to a DataFrame — the reverse data path (C8 parity with
        ``ray_dataset_to_spark_dataframe``, reference:
        python/raydp/spark/dataset.py:506-577). Ref blocks become the
        frame's partitions with zero copies; in-memory blocks re-enter via
        the executor's scatter path."""
        import raydp_tpu.dataframe as rdf
        from raydp_tpu.context import current_session

        self._ensure_plan()
        if all(isinstance(b, ObjectRef) for b in self.blocks):
            session = current_session()
            if session is not None:
                return rdf.from_refs(self.blocks)
        tables = [self._resolve(b) for b in self.blocks]
        from raydp_tpu.dataframe.io import _distribute

        return _distribute(tables)

    # -- introspection --------------------------------------------------
    @property
    def shard_plan(self) -> Dict[int, List[BlockSlice]]:
        """rank → block slices. Building it needs every block's size, so
        the first read barriers on in-flight blocks."""
        self._ensure_plan()
        return self._shard_plan

    @property
    def block_sizes(self) -> List[int]:
        """Per-block row counts (barriers on in-flight blocks)."""
        self._ensure_plan()
        return list(self._block_sizes)

    @property
    def total_rows(self) -> int:
        self._ensure_plan()
        return sum(self._block_sizes)

    @property
    def rows_per_shard(self) -> int:
        return math.ceil(self.total_rows / self.num_shards)

    def schema(self) -> pa.Schema:
        # Only block 0 need exist — never barriers on the whole plan.
        return self._resolve(resolve_one(self._blocks[0])).schema

    # -- shard access ---------------------------------------------------
    def shard_tables(self, rank: int) -> List[pa.Table]:
        """The (sliced) blocks assigned to ``rank``."""
        self._ensure_plan()
        if rank not in self._shard_plan:
            raise IndexError(f"rank {rank} out of {self.num_shards}")
        out = []
        plan = self._shard_plan[rank]
        with span("handoff/fetch", rank=rank, blocks=len(plan)):
            for s in plan:
                table = self._resolve(self._blocks[s.block_index])
                if s.offset == 0 and s.num_samples == table.num_rows:
                    out.append(table)
                else:
                    out.append(table.slice(s.offset, s.num_samples))
        return out

    def shard_global_indices(self, rank: int) -> np.ndarray:
        """Global dataset row index (block order, then row order within
        block) of every sample in ``rank``'s plan, in plan order — the
        inverse of the shard plan. Inference uses this to scatter
        per-shard outputs back to dataset order: padding rows map to the
        same global index as the row they duplicate, so a scatter
        overwrites them with identical values and the padded sample count
        collapses back to ``total_rows``. (Training never needs this —
        the equal-samples padding is a lockstep invariant of the
        reference's divide_blocks, python/raydp/utils.py:149-222, that
        must NOT leak into inference results.)"""
        self._ensure_plan()
        if rank not in self._shard_plan:
            raise IndexError(f"rank {rank} out of {self.num_shards}")
        starts = np.zeros(len(self._block_sizes), dtype=np.int64)
        if len(self._block_sizes) > 1:
            starts[1:] = np.cumsum(self._block_sizes[:-1])
        parts = [
            starts[s.block_index] + s.offset
            + np.arange(s.num_samples, dtype=np.int64)
            for s in self._shard_plan[rank]
        ]
        if not parts:
            return np.empty((0,), dtype=np.int64)
        return np.concatenate(parts)

    def shard_columns(
        self, rank: int, columns: Optional[List[str]] = None
    ) -> Dict[str, np.ndarray]:
        """Shard materialized as contiguous numpy columns (loader input)."""
        tables = self.shard_tables(rank)
        with span("handoff/convert", rank=rank, tables=len(tables)):
            merged = (
                pa.concat_tables(tables, promote_options="default")
                if len(tables) > 1
                else tables[0]
            )
            names = columns or merged.column_names
            out: Dict[str, np.ndarray] = {}
            for name in names:
                # Direct Arrow→numpy (zero-copy when no nulls + numeric);
                # no pandas Series intermediary on the ingest path.
                out[name] = merged.column(name).to_numpy(
                    zero_copy_only=False
                )
        return out

    def to_jax(
        self,
        feature_columns: List[str],
        label_column: Optional[str] = None,
        batch_size: int = 256,
        rank: int = 0,
        shuffle: bool = True,
        seed: int = 0,
        feature_dtype=np.float32,
        label_dtype=np.float32,
        prefetch: int = 2,
        device=None,
        drop_last: bool = False,
        transfer_coalesce: Optional[int] = None,
        transfer_window: int = 2,
    ):
        """Device-feeding batch iterator for this shard (the TPU-native
        counterpart of ``to_torch``, reference dataset.py:411-443).

        ``transfer_coalesce`` batches ship per ``device_put``; features
        and labels pack into ONE staged buffer per chunk, so a chunk is
        exactly one transfer. ``None`` = auto-size: on the device path,
        chunks grow toward ~128MB (``RAYDP_TRANSFER_CHUNK_MB``, capped at
        32 batches); on the host path (``device=None``) auto stays at one
        batch per chunk — there is no transfer to amortize and per-batch
        granularity keeps prefetch memory small. An EXPLICIT value is
        honored on both paths (host callers may want bigger gather chunks
        for cache efficiency); ``1`` = per-batch transfers. Up to
        ``transfer_window`` chunk transfers stay in flight — see
        loader.py's module docstring for why this matters on
        high-latency device links."""
        from raydp_tpu.data.loader import JaxShardLoader

        return JaxShardLoader(
            self,
            rank=rank,
            feature_columns=feature_columns,
            label_column=label_column,
            batch_size=batch_size,
            shuffle=shuffle,
            seed=seed,
            feature_dtype=feature_dtype,
            label_dtype=label_dtype,
            prefetch=prefetch,
            device=device,
            drop_last=drop_last,
            transfer_coalesce=transfer_coalesce,
            transfer_window=transfer_window,
        )

    def to_torch(
        self,
        feature_columns: List[str],
        label_column: str,
        batch_size: int = 256,
        rank: int = 0,
        shuffle: bool = True,
        seed: int = 0,
    ):
        """Torch IterableDataset over this shard (API parity with the
        reference's TorchMLDataset, torch/torch_ml_dataset.py:25-111)."""
        from raydp_tpu.data.torch_adapter import TorchShardDataset

        return TorchShardDataset(
            self, rank, feature_columns, label_column, batch_size, shuffle,
            seed,
        )

    # -- internals ------------------------------------------------------
    def _resolve(self, block: Block) -> pa.Table:
        block = resolve_one(block)
        if isinstance(block, ObjectRef):
            store = self._store
            if store is not None:
                return store.get_arrow_table(block)
            from raydp_tpu.store.object_store import resolve_ambient_table

            return resolve_ambient_table(block)
        return block

    def _block_rows(self, block: Block) -> int:
        block = resolve_one(block)
        if isinstance(block, ObjectRef):
            if block.num_rows < 0:
                return self._resolve(block).num_rows
            return block.num_rows
        return block.num_rows
