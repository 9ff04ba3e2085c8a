"""Host→device batch pipeline: gather, stage, prefetch, device_put.

The hot path of training ingest. Per epoch:

  1. shard columns live as contiguous numpy arrays (zero-copy from Arrow
     where dtypes allow);
  2. a permutation is drawn (epoch-seeded — reshuffle every epoch like the
     reference's per-epoch shard shuffle, dataset.py:355-376);
  3. transfer CHUNKS (``transfer_coalesce`` batches each) are assembled
     by the native row-gather kernel (raydp_tpu/native/src/gather.cpp);
  4. a background thread keeps ``prefetch`` staged chunks ahead;
  5. chunks ship with ONE ``jax.device_put`` each and up to
     ``transfer_window`` chunks stay in flight while the caller computes;
     batches are on-device slices of landed chunks.

Why chunks: every ``device_put`` has a fixed cost, so small transfers
waste the link. On a local TPU v5e (PR 21 chip run) a call costs about
0.7 ms before any byte moves — 64 KB goes at 0.09 GB/s, 4 MB at 3.0,
32 MB at 5.1 and 128 MB at 5.7 GB/s. Coalescing N batches into one transfer
divides the fixed cost by N, and the multi-chunk window overlaps the
remaining transfers with compute; on-device slicing is free by
comparison (slices are async XLA ops that pipeline).
"""
from __future__ import annotations

import os
import queue
import threading
import time
from collections import deque
from typing import Dict, Iterator, List, NamedTuple, Optional, Tuple

import numpy as np

from raydp_tpu.native import lib as native
from raydp_tpu.telemetry import accounting as _acct
from raydp_tpu.telemetry import current_context, propagated, span
from raydp_tpu.telemetry import flight_recorder as _flight
from raydp_tpu.telemetry import overlap as _overlap
from raydp_tpu.telemetry import progress as _progress
from raydp_tpu.telemetry import watchdog as _watchdog
from raydp_tpu.utils.profiling import metrics

# Auto transfer-chunk sizing: coalesce batches until a chunk reaches this
# many bytes (or 32 batches, whichever is smaller). The figures in the
# module docstring put the knee of the local link near 32 MB; the 128 MB
# default bounds staging memory at window×128MB and was not chosen from
# them. The env var RAYDP_TRANSFER_CHUNK_MB overrides for tuning.
_TARGET_CHUNK_BYTES = int(
    __import__("os").environ.get("RAYDP_TRANSFER_CHUNK_MB", 128)
) * 1024 * 1024
_MAX_COALESCE = 32


class _PackedChunk(NamedTuple):
    """Features + labels packed into ONE contiguous staging buffer.

    A labeled chunk would otherwise pay the fixed device_put cost
    twice (features, then labels). Packing both into a single uint8
    buffer makes every chunk exactly one transfer;
    the typed views are recovered on device with zero-cost bitcasts.
    The packing memcpy happens producer-side (the staging generator /
    prefetch thread), so it overlaps the in-flight transfer window.
    """

    buf: np.ndarray  # uint8, [x.nbytes + y.nbytes]
    rows: int


def _pack_chunk(x: np.ndarray, y: np.ndarray) -> _PackedChunk:
    xb = np.ascontiguousarray(x).view(np.uint8).reshape(-1)
    yb = np.ascontiguousarray(y).view(np.uint8).reshape(-1)
    return _PackedChunk(np.concatenate([xb, yb]), x.shape[0])


def _cut_rows(bufs: List[np.ndarray], lo: int, hi: int) -> np.ndarray:
    """Rows [lo, hi) of the logical concatenation of ``bufs`` — a view
    when the cut stays inside one buffer, a copy when it spans two."""
    out = []
    pos = 0
    for b in bufs:
        n = len(b)
        if pos + n <= lo:
            pos += n
            continue
        if pos >= hi:
            break
        out.append(b[max(0, lo - pos):min(n, hi - pos)])
        pos += n
    return out[0] if len(out) == 1 else np.concatenate(out)


class JaxShardLoader:
    """Iterable over (features, labels) device arrays for one shard.

    Re-iterable: each ``iter()`` is a new epoch with a fresh permutation.
    """

    def __init__(
        self,
        dataset,
        rank: int,
        feature_columns: List[str],
        label_column: Optional[str],
        batch_size: int,
        shuffle: bool,
        seed: int,
        feature_dtype,
        label_dtype,
        prefetch: int,
        device,
        drop_last: bool,
        transfer_coalesce: Optional[int] = None,
        transfer_window: int = 2,
    ):
        self._dataset = dataset
        self._rank = rank
        self.feature_columns = feature_columns
        self.label_column = label_column
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.feature_dtype = np.dtype(feature_dtype)
        self.label_dtype = np.dtype(label_dtype)
        self.prefetch = max(0, prefetch)
        self.device = device
        self.drop_last = drop_last
        # None = auto-size chunks to ~_TARGET_CHUNK_BYTES; 1 = one
        # device_put per batch (the pre-r5 behavior, kept measurable for
        # the bench's micro-batch row).
        self.transfer_coalesce = transfer_coalesce
        self.transfer_window = max(1, transfer_window)
        self._epoch = 0
        self._columns: Optional[Dict[str, np.ndarray]] = None
        self._feat_matrix: Optional[np.ndarray] = None
        self._labels: Optional[np.ndarray] = None

    # -- sizing ---------------------------------------------------------
    def __len__(self) -> int:
        n = self._dataset.rows_per_shard
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size

    @property
    def num_features(self) -> int:
        return len(self.feature_columns)

    # -- epoch iteration ------------------------------------------------
    def __iter__(self):
        epoch = self._epoch
        self._epoch += 1
        # Workload-root attribution: an epoch driven with no ambient
        # JobContext (bare loader benchmarks) installs one process
        # default so its ingest usage still bills somewhere findable.
        if _acct.current_job() is None:
            _acct.set_process_job(_acct.mint_job("loader"))
        return self._epoch_iter(epoch)

    def set_epoch(self, epoch: int) -> None:
        self._epoch = epoch

    def _materialize(self) -> Dict[str, np.ndarray]:
        if self._columns is None:
            wanted = list(self.feature_columns)
            if self.label_column:
                wanted.append(self.label_column)
            # The hand-off's own work, on this (the producer's) thread:
            # the dataset opens ``handoff/await_blocks``, ``handoff/fetch``
            # and ``handoff/convert`` under it.
            with span("handoff/materialize", rank=self._rank) as sp:
                cols = self._dataset.shard_columns(self._rank, wanted)
                # On the recorder's copy (a profiler annotation took its
                # attrs at entry).
                plan = getattr(self._dataset, "shard_plan", None) or {}
                sp.attrs.update(
                    blocks=len(plan.get(self._rank, ())),
                    rows=len(next(iter(cols.values()), ())),
                    bytes=sum(c.nbytes for c in cols.values()),
                )
            self._columns = cols
        return self._columns

    def _stage_matrix(self):
        """Columns → ONE row-major ``[n, F]`` matrix, built once and reused
        every epoch. Batch assembly then gathers whole rows (a feature row
        is contiguous — often a single cache line) instead of hopping
        between F column arrays per row, which costs a cache miss per
        (row, column) under a shuffled permutation. Measured ~6× ingest
        bandwidth on 16-feature shuffled epochs.
        """
        if self._feat_matrix is not None:
            return self._feat_matrix, self._labels
        cols = self._materialize()
        feats = [cols[c] for c in self.feature_columns]
        n = len(feats[0])
        with span("ingest/stage_matrix", rank=self._rank, rows=n,
                  features=len(feats)):
            if self.feature_dtype in (np.dtype(np.float32),
                                      np.dtype(np.int32)):
                # Sequential pass through the native kernel.
                matrix = native.gather_matrix(
                    feats, np.arange(n, dtype=np.int64),
                    out_dtype=self.feature_dtype,
                )
            else:
                matrix = np.stack(
                    [f.astype(self.feature_dtype, copy=False) for f in feats],
                    axis=1,
                )
        labels = None
        if self.label_column:
            labels = cols[self.label_column].astype(
                self.label_dtype, copy=False
            )
        # Drop the per-column feature buffers: the matrix replaces them
        # (keeps peak memory at ~2× dataset, steady-state at ~1×).
        for c in self.feature_columns:
            cols.pop(c, None)
        self._feat_matrix, self._labels = matrix, labels
        _acct.add_usage(
            _acct.STAGED_BYTES,
            matrix.nbytes + (labels.nbytes if labels is not None else 0),
        )
        return matrix, labels

    def _coalesce_batches(self) -> int:
        """Batches per transfer chunk. Explicit setting ALWAYS wins —
        including on the host path (device None), where a caller may want
        bigger gather chunks for cache efficiency. Auto (None) sizes
        chunks toward ``_TARGET_CHUNK_BYTES`` capped at ``_MAX_COALESCE``;
        host-path auto stays at 1: there is no transfer to amortize and
        per-batch granularity keeps prefetch memory small."""
        if self.transfer_coalesce is not None:
            return max(1, self.transfer_coalesce)
        if self.device is None:
            return 1
        row_bytes = (
            self.num_features * self.feature_dtype.itemsize
            + (self.label_dtype.itemsize if self.label_column else 0)
        )
        batch_bytes = max(1, self.batch_size * row_bytes)
        return int(
            min(_MAX_COALESCE, max(1, _TARGET_CHUNK_BYTES // batch_bytes))
        )

    def _staged_chunks(
        self, epoch: int, rows_per_chunk: int, pack: bool = False,
        start_row: int = 0,
    ) -> Iterator:
        """Gather the epoch's rows in ``rows_per_chunk`` pieces (a chunk
        is ``transfer_coalesce`` batches; 1 batch on the host path).

        ``pack=True`` (device path with labels): each chunk is emitted as
        a :class:`_PackedChunk` — features and labels in one staging
        buffer — so the consumer ships it with a single device_put. The
        pack memcpy runs HERE, on the producer side, overlapping the
        consumer's in-flight transfers.

        ``start_row`` (chunk-aligned) skips rows the epoch-0 prefix
        streamer already served — this generator finishes the epoch from
        there."""
        matrix, labels = self._stage_matrix()
        n = matrix.shape[0]
        order = None
        if self.shuffle:
            rng = np.random.default_rng(self.seed + epoch * 1009 + self._rank)
            order = rng.permutation(n)
        # Rows the epoch actually serves (drop_last trims the ragged
        # batch tail).
        n_used = min(n, len(self) * self.batch_size)
        # Hoisted out of the hot loop: meter() takes the registry lock.
        rows_meter = metrics.meter("ingest/rows")
        bytes_meter = metrics.meter("ingest/bytes")
        # Ingest shows up in /debug/progress like any plan stage: one
        # stage per epoch, one task per transfer chunk.
        remaining = max(0, n_used - start_row)
        n_chunks = max(1, -(-remaining // rows_per_chunk)) if remaining else 0
        prog_id = _progress.stage_store.next_id()
        _progress.progress.stage_begin(
            prog_id, f"ingest[epoch {epoch}]", n_chunks
        )
        try:
            yield from self._chunk_iter(
                epoch, rows_per_chunk, pack, matrix, labels, order, n_used,
                rows_meter, bytes_meter, prog_id, start_row,
            )
        finally:
            # finally (not loop-end): a consumer that stops early —
            # drop_last, a broken epoch, estimator teardown — closes
            # the generator, and the stage must not stay "active" in
            # /debug/progress forever.
            _progress.progress.stage_end(prog_id)

    def _chunk_iter(self, epoch, rows_per_chunk, pack, matrix, labels,
                    order, n_used, rows_meter, bytes_meter, prog_id,
                    start_row=0):
        for lo in range(start_row, n_used, rows_per_chunk):
            hi = min(lo + rows_per_chunk, n_used)
            # The span closes before the yield: a suspended generator must
            # not hold an open span on this thread's stack while consumer
            # code (steps, other chunks) runs and parents under it.
            # Same close-before-yield rule for the watchdog bracket: an
            # in-flight op must cover only the gather, not the
            # generator's suspension (which can legitimately last a full
            # step and would read as an ingest stall).
            with _watchdog.inflight("ingest/chunk", epoch=epoch,
                                    rank=self._rank), \
                 span("ingest/chunk", epoch=epoch, rank=self._rank,
                      rows=hi - lo):
                if order is None:
                    # Sequential epoch: zero-copy row-slice views.
                    x = matrix[lo:hi]
                    y = labels[lo:hi] if labels is not None else None
                else:
                    idx = order[lo:hi]
                    x = native.gather_rows(matrix, idx)
                    y = labels[idx] if labels is not None else None
                rows_meter.add(hi - lo)
                bytes_meter.add(
                    x.nbytes + (y.nbytes if y is not None else 0)
                )
                chunk = (
                    _pack_chunk(x, y) if pack and y is not None else (x, y)
                )
            _flight.record("loader", "chunk", epoch=epoch, rank=self._rank,
                           rows=hi - lo)
            _progress.progress.task_done(prog_id)
            yield chunk
        _progress.progress.stage_end(prog_id)

    def _stage_block(self, table) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """One landed block → (feature matrix piece, labels piece), with
        the same dtype pipeline as :meth:`_stage_matrix` so the streamed
        prefix is bit-identical to the barriered epoch."""
        feats = [
            table.column(c).to_numpy(zero_copy_only=False)
            for c in self.feature_columns
        ]
        n = table.num_rows
        if self.feature_dtype in (np.dtype(np.float32), np.dtype(np.int32)):
            m = native.gather_matrix(
                feats, np.arange(n, dtype=np.int64),
                out_dtype=self.feature_dtype,
            )
        else:
            m = np.stack(
                [f.astype(self.feature_dtype, copy=False) for f in feats],
                axis=1,
            )
        y = None
        if self.label_column:
            y = table.column(self.label_column).to_numpy(
                zero_copy_only=False
            ).astype(self.label_dtype, copy=False)
        return m, y

    def _streaming_chunks(
        self, epoch: int, rows_per_chunk: int, pack: bool
    ) -> Iterator:
        """Epoch-0 prefix streamer: start serving batches while LATE ETL
        partitions are still being produced.

        Only valid for rank 0 of an unshuffled epoch over an unshuffled
        dataset: ``divide_blocks`` hands rank 0 the dataset prefix
        ``[0, ceil(total/num_shards))``, so rows staged from the first
        landed blocks ARE the head of this shard. ``known_rows()`` is a
        monotone lower bound of ``total_rows``, hence
        ``ceil(known/num_shards)`` never overshoots the shard end — whole
        chunks below that bound are safe to emit before the plan exists.
        Once every block has landed, the remainder of the epoch (and the
        reusable epoch-1+ matrix) is delegated to :meth:`_staged_chunks`
        with ``start_row`` pointing past what was already served."""
        ds = self._dataset
        shards = ds.num_shards
        bs = self.batch_size
        rows_meter = metrics.meter("ingest/rows")
        bytes_meter = metrics.meter("ingest/bytes")
        prog_id = _progress.stage_store.next_id()
        _progress.progress.stage_begin(
            prog_id, f"ingest[epoch {epoch} prefix]", 0
        )
        feat_bufs: List[np.ndarray] = []
        label_bufs: List[np.ndarray] = []
        staged = 0  # dataset-prefix rows staged into the buffers
        emitted = 0  # rows already yielded
        try:
            for _idx, table in ds.iter_prefix_tables():
                # Staging a landed block is ingest work that overlaps the
                # still-running ETL tail — the overlap counter's bread
                # and butter.
                with _overlap.tracker.ingest(), \
                     span("ingest/stream_block", rank=self._rank,
                          rows=table.num_rows):
                    m, y = self._stage_block(table)
                feat_bufs.append(m)
                if y is not None:
                    label_bufs.append(y)
                staged += table.num_rows
                known, complete = ds.known_rows()
                if complete:
                    break
                bound = min(staged, -(-known // shards))
                bound -= bound % bs  # batch-aligned (drop_last-safe)
                while emitted + rows_per_chunk <= bound:
                    hi = emitted + rows_per_chunk
                    with _watchdog.inflight("ingest/chunk", epoch=epoch,
                                            rank=self._rank), \
                         span("ingest/chunk", epoch=epoch, rank=self._rank,
                              rows=rows_per_chunk, streamed=True):
                        x = _cut_rows(feat_bufs, emitted, hi)
                        yc = (
                            _cut_rows(label_bufs, emitted, hi)
                            if label_bufs else None
                        )
                        rows_meter.add(rows_per_chunk)
                        bytes_meter.add(
                            x.nbytes + (yc.nbytes if yc is not None else 0)
                        )
                        chunk = (
                            _pack_chunk(x, yc)
                            if pack and yc is not None else (x, yc)
                        )
                    _flight.record("loader", "chunk", epoch=epoch,
                                   rank=self._rank, rows=rows_per_chunk,
                                   streamed=True)
                    _progress.progress.task_done(prog_id)
                    emitted = hi
                    yield chunk
            metrics.counter_add("ingest/stream_prefix_rows", emitted)
        finally:
            feat_bufs.clear()
            label_bufs.clear()
            _progress.progress.stage_end(prog_id)
        # Every block has landed: finish the epoch through the normal
        # staged path (which also builds the epoch-1+ matrix).
        yield from self._staged_chunks(
            epoch, rows_per_chunk, pack, start_row=emitted
        )

    def _unpack_device(self, buf, rows: int):
        """On-device recovery of (features, labels) from one packed
        buffer: slices + reshapes + bitcasts are async XLA ops on bytes
        already resident — no further host↔device traffic."""
        from jax import lax

        nf = self.num_features
        fsz = self.feature_dtype.itemsize
        lsz = self.label_dtype.itemsize
        nb_x = rows * nf * fsz
        xb = buf[:nb_x].reshape((rows, nf, fsz) if fsz > 1 else (rows, nf))
        x = lax.bitcast_convert_type(xb, self.feature_dtype)
        yb = buf[nb_x:nb_x + rows * lsz]
        if lsz > 1:
            yb = yb.reshape((rows, lsz))
        y = lax.bitcast_convert_type(yb, self.label_dtype)
        return x, y

    def _epoch_iter(self, epoch: int):
        import jax

        bs = self.batch_size
        chunk_batches = self._coalesce_batches()
        device = self.device
        # Labeled device chunks are packed producer-side so each chunk is
        # exactly ONE device_put (unlabeled chunks already are).
        pack = device is not None and self.label_column is not None
        source = None
        if (
            epoch == 0
            and self._rank == 0
            and not self.shuffle
            and self._feat_matrix is None
        ):
            ds = self._dataset
            if (
                hasattr(ds, "has_pending_blocks")
                and not getattr(ds, "shuffle", False)
                and getattr(ds, "rank_nodes", None) is None
                and ds.has_pending_blocks()
            ):
                from raydp_tpu.dataframe.scheduler import streaming_enabled

                if streaming_enabled():
                    source = self._streaming_chunks(
                        epoch, chunk_batches * bs, pack
                    )
        if source is None:
            source = self._staged_chunks(epoch, chunk_batches * bs, pack=pack)
        stop_event = None
        if self.prefetch > 0:
            # prefetch counts CHUNKS: with coalescing the host-side
            # staging holds at most prefetch × chunk bytes.
            source, stop_event = _background(source, self.prefetch)

        def put_chunk(chunk):
            if isinstance(chunk, _PackedChunk):
                # Bracketed: a host→device transfer that never completes
                # (device link wedge) is a classic silent hang.
                with _overlap.tracker.ingest(), \
                     _watchdog.inflight("ingest/device_put",
                                        rank=self._rank), \
                     span("ingest/device_put", rank=self._rank):
                    buf = jax.device_put(chunk.buf, device)
                return self._unpack_device(buf, chunk.rows)
            x, y = chunk
            if device is not None:
                with _overlap.tracker.ingest(), \
                     _watchdog.inflight("ingest/device_put",
                                        rank=self._rank), \
                     span("ingest/device_put", rank=self._rank):
                    x = jax.device_put(x, device)
                    y = jax.device_put(y, device) if y is not None else None
            return x, y

        def batches_of(chunk):
            x, y = chunk
            n = x.shape[0] if hasattr(x, "shape") else len(x)
            for lo in range(0, n, bs):
                hi = min(lo + bs, n)
                # On-device slicing: an async XLA slice per batch, which
                # pipelines behind the chunk transfer instead of paying a
                # host→device trip per batch.
                xb = x[lo:hi]
                yb = y[lo:hi] if y is not None else None
                yield (xb, yb) if self.label_column else xb

        # Transfer window: keep up to ``transfer_window`` chunk transfers
        # in flight ahead of the consumer (double buffering generalized —
        # the consumer drains batches of chunk i while chunks i+1..i+W
        # are still shipping).
        window: deque = deque()
        try:
            for chunk in source:
                window.append(put_chunk(chunk))
                if len(window) > self.transfer_window:
                    yield from batches_of(window.popleft())
            while window:
                yield from batches_of(window.popleft())
        finally:
            # Abandoned epoch (early break / single next()): unblock the
            # producer thread so it exits instead of leaking.
            if stop_event is not None:
                stop_event.set()


def _background(it: Iterator, depth: int):
    """Run ``it`` in a daemon thread, buffering ``depth`` items.

    Returns ``(iterator, stop_event)``; setting the event makes the
    producer drain out promptly (a full queue never blocks it forever).

    The consumer's trace context is captured HERE (typically inside the
    epoch span) and installed on the producer thread, so the
    ``ingest/*`` spans it records nest in the training trace instead of
    starting a fresh one per epoch."""
    q: "queue.Queue" = queue.Queue(maxsize=depth)
    _DONE = object()
    stop = threading.Event()
    # Producer errors surface PROMPTLY through this side channel: queueing
    # the exception behind ``depth`` buffered items would make the
    # consumer drain stale chunks first and report the failure a full
    # prefetch window late.
    err: List[BaseException] = []
    trace_ctx = current_context()

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def producer():
        with propagated(trace_ctx):
            try:
                for item in it:
                    if not _put(item):
                        return
                _put(_DONE)
            except BaseException as exc:  # surface errors on consumer side
                err.append(exc)
                # Wake a consumer blocked on an empty queue; a full one
                # means it will hit the err check on its next pull.
                _put(_DONE)

    thread = threading.Thread(target=producer, daemon=True)
    thread.start()

    def consume():
        # Consumer-side starvation: every second spent blocked here is
        # a second the training loop sat idle waiting for data. The
        # producer already accounts its own pack/put time; this counter
        # closes the gap.
        while True:
            if err:
                raise err[0]
            # One span per chunk, closed before the yield below.
            with span("ingest/wait") as sp:
                item = q.get()
            metrics.counter_add("ingest/wait_seconds", sp.duration_s)
            if err:
                # Raced with the failure while pulling: prefer the error
                # over any still-buffered item.
                raise err[0]
            if item is _DONE:
                return
            yield item

    return consume(), stop
