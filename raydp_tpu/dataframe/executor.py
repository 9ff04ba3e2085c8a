"""Partition executors: where DataFrame stages actually run.

Two backends with one interface:

  * ``LocalExecutor`` — partitions are in-memory ``pa.Table``s, stages run
    on a thread pool (pyarrow kernels release the GIL). Like Spark
    ``local[n]``; the default when no session is live.
  * ``ClusterExecutor`` — partitions are ``ObjectRef``s in the shm store,
    stages ship to ETL worker processes via the control plane (the
    reference's executor-side ``mapPartitions`` over Ray actors,
    ObjectStoreWriter.scala:93-164). Locality: a partition is routed to a
    stable worker per index so repeated stages reuse page-cache-warm
    segments (reference threads locality through getPreferredLocations,
    RayDatasetRDD.scala:53-55).
"""
from __future__ import annotations

import contextlib
import functools
import itertools
import os
import threading
import time
from collections import OrderedDict
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, List, Optional, Sequence

import pyarrow as pa

from raydp_tpu.cluster.cluster import TaskSpec
from raydp_tpu.dataframe.scheduler import (
    PendingPartition,
    StreamingStage,
    resolve,
    resolve_one,
    streaming_enabled,
)
from raydp_tpu.store.object_store import ObjectRef, ObjectStore
from raydp_tpu.telemetry import accounting as _acct
from raydp_tpu.telemetry import span
from raydp_tpu.telemetry.progress import (
    StageStats,
    progress,
    stage_stats_enabled,
    stage_store,
)
from raydp_tpu.utils.profiling import metrics

StageFn = Callable[[pa.Table], pa.Table]


def _ensure_etl_job() -> None:
    """Workload-root job attribution for bare pipelines: the first
    executed stage in a process with no ambient JobContext mints one
    process-default ``etl`` job. Explicit user ``job_scope``s (and SPMD
    jobs, which install their own) take precedence via current_job()."""
    if _acct.current_job() is None:
        _acct.set_process_job(_acct.mint_job("etl"))


@contextlib.contextmanager
def _stage_span(op: str, n_parts: int, executor: str, **attrs):
    """Span around one stage execution (driver side: covers
    submit AND result gather on the cluster backend, so the duration is
    the stage's wall time as the query planner experiences it). Under
    streaming dispatch the span covers scheduling only — completion
    happens on callback threads and the true wall lands in StageStats.

    Stages are also the control plane's fair-share interleaving points:
    each execution passes through the arbiter's ``stage_gate`` (a
    transient one-slot "turn" granted in deficit-weighted round-robin
    order across tenants; doc/scheduling.md) — a no-op unless
    ``RAYDP_TPU_SCHED_CAPACITY`` enables arbitration."""
    from raydp_tpu.control import stage_gate

    _ensure_etl_job()
    with stage_gate(label=op), span(
        "df/stage", op=op, parts=n_parts, executor=executor, **attrs
    ):
        yield


# -- per-stage runtime statistics ------------------------------------------
# The planner names the stage it is about to run (``stage_label``); the
# executor records a StageStats per stage into the driver-side
# ``stage_store`` and streams done/total task counts into ``progress``.
# The label context also collects the stage ids it covered, which is how
# DataFrame plan nodes re-associate runtime numbers with themselves for
# EXPLAIN ANALYZE / the future AQE.
_stage_ctx = threading.local()


@contextlib.contextmanager
def stage_label(label: str):
    """Name the executor stages run inside this context after the plan
    node driving them; yields the list of stage ids recorded."""
    ids: List[int] = []
    prev = getattr(_stage_ctx, "cur", None)
    _stage_ctx.cur = (label, ids)
    try:
        yield ids
    finally:
        _stage_ctx.cur = prev


def _part_meta(part: Any) -> "tuple[int, int]":
    """(rows, bytes) of one partition without materializing it; rows is
    -1 when unknowable (refs stored without a row count)."""
    if isinstance(part, PendingPartition):
        if not part.future.done() or part.future.exception() is not None:
            return -1, 0
        part = part.future.result()
    if isinstance(part, ObjectRef):
        return part.num_rows, part.size
    if isinstance(part, pa.Table):
        return part.num_rows, part.nbytes
    return -1, 0


#: Envelopes a ``stage/close`` span lists at most (its one string attr).
_CLOSE_ENVELOPES = 32
#: What a stage's task bodies add up to (``StageStats``): the three parts
#: a worker stamps (``fetch``, ``put``, ``register``) and the bodies whole.
_BODY_PARTS = ("fetch_s", "put_s", "register_s", "body_s")


def _union_s(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    covered, cursor = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, cursor), min(b, hi)
        if b > a:
            covered += b - a
            cursor = b
    return covered


def format_envelopes(envelopes) -> str:
    """The ``envelopes`` attr of a ``stage/close`` span: for each envelope
    ``<env>:<worker>:<ret - recv>:<start>-<end>+<start>-<end>...`` joined
    by ``;`` — whole microseconds, the body intervals relative to
    ``recv``. No ``,``, ``=`` or ``#``: a profiler annotation's attrs
    travel inside its name. ``benchmark/stage_trace.py:parse_envelopes``
    reads it back."""
    out = []
    for e in envelopes:
        worker = "".join(
            c if c.isalnum() or c in "_.-" else "_" for c in str(e["worker"])
        )
        bodies = "+".join(
            f"{round((a - e['recv']) * 1e6)}-{round((b - e['recv']) * 1e6)}"
            for a, b in sorted(e["bodies"])
        )
        out.append(
            f"{e['env']}:{worker}:{round((e['ret'] - e['recv']) * 1e6)}"
            f":{bodies}"
        )
    return ";".join(out)


class _StageRecorder:
    """Accumulates one :class:`StageStats` while a stage runs.

    Cheap when disabled (``RAYDP_TPU_STAGE_STATS=0``): every method
    no-ops after one boolean check. ``round()`` opens one round of
    envelopes (one ``Cluster.submit_batch``/``submit_async`` call; an
    exchange has two) and returns that call's ``meta_sink``, so worker
    attribution and the stamps of both sides ride the existing task
    replies.

    At the close the stage's wall is partitioned along its critical
    path: of each round, the envelope whose reply came last; of
    overlapping rounds (a streaming stage pumps one per upstream
    completion), the chain that ends last. Per round on the chain,
    ``submit_s`` is round start → that envelope's send, ``transit_s``
    ``(reply − send) − (ret − recv)`` (no common clock needed),
    ``exec_s`` the union of the envelope's body intervals, ``load_s``
    the rest of ``ret − recv``; ``driver_s`` is what is left of
    ``wall_s`` (before the first round — for a streaming stage the wait
    for upstream partitions, kept apart as ``upstream_s`` — between
    rounds, and after the last reply). The five sum to ``wall_s``.

    Beside the partition, ``fetch_s``, ``put_s``, ``register_s`` and
    ``body_s`` add up what ALL the stage's task bodies spent in
    ``WorkerContext.get_table``, in the store's write, in the
    ``RegisterObject`` round trip and in all (``tasks_stamped`` bodies):
    work, not wall, so no span attr carries them."""

    def __init__(self, op: str, parts_in: Sequence[Any], kind: str,
                 total_tasks: Optional[int] = None, streaming: bool = False):
        self.enabled = stage_stats_enabled()
        cur = getattr(_stage_ctx, "cur", None)
        self.op = cur[0] if cur else op
        self._ids_sink = cur[1] if cur else None
        self._ids_sunk = False
        self.kind = kind
        self.streaming = bool(streaming)
        self._t0 = time.perf_counter()
        self._rounds: List[dict] = []
        self._workers: dict = {}
        self._bodies = dict.fromkeys(_BODY_PARTS, 0.0)
        self._tasks_stamped = 0
        self._mu = threading.Lock()
        self._outs: Optional[List[Any]] = None
        self._rows_in = self._bytes_in = 0
        self._out_meta: dict = {}
        self.stage_id = 0
        if not self.enabled:
            return
        self.stage_id = stage_store.next_id()
        self._parts_in = len(parts_in)
        if self.streaming:
            # Inputs may still be pending futures: rows_in/bytes_in
            # accrue per task at dispatch time (task_input), keeping the
            # totals identical to the barriered path. The stage id must
            # land in the label sink NOW — the planner copies that list
            # into the lineage node before this stage completes.
            if self._ids_sink is not None:
                self._ids_sink.append(self.stage_id)
                self._ids_sunk = True
        else:
            rows = nbytes = 0
            for p in parts_in:
                r, b = _part_meta(p)
                if r > 0:
                    rows += r
                nbytes += b
            self._rows_in, self._bytes_in = rows, nbytes
        total = total_tasks if total_tasks is not None else len(parts_in)
        progress.stage_begin(self.stage_id, self.op, total)

    def round(self) -> Callable:
        """Open one round of envelopes — call it where the round's
        ``submit_batch``/``submit_async`` is called — and return that
        call's ``meta_sink``."""
        rnd = {"start": time.perf_counter(), "envelopes": {}}
        if self.enabled:
            with self._mu:
                self._rounds.append(rnd)
        return functools.partial(self._task_meta, rnd)

    def _task_meta(self, rnd: dict, index: int, worker_id: Optional[str],
                   exec_s: float, stamps: Optional[dict] = None) -> None:
        """Per-task completion (``meta_sink`` shape): worker attribution
        and, per envelope of the round, the stamps of both sides."""
        if not self.enabled:
            return
        wid = worker_id or "?"
        with self._mu:
            self._workers[wid] = self._workers.get(wid, 0) + 1
            if stamps is not None:
                env = rnd["envelopes"].get(stamps["env"])
                if env is None:
                    env = rnd["envelopes"][stamps["env"]] = {
                        "env": stamps["env"], "worker": wid,
                        "send": stamps["send"], "reply": stamps["reply"],
                        "recv": stamps["recv"], "ret": stamps["ret"],
                        "bodies": [],
                    }
                env["bodies"].append((stamps["start"], stamps["end"]))
                if stamps.get("parts") is not None:
                    self._tasks_stamped += 1
                    self._bodies["body_s"] += stamps["end"] - stamps["start"]
                    for kind, t0, t1 in stamps["parts"]:
                        if kind + "_s" in self._bodies:
                            self._bodies[kind + "_s"] += t1 - t0
        progress.task_done(self.stage_id)

    def task_done(self, n: int = 1) -> None:
        if self.enabled:
            progress.task_done(self.stage_id, n)

    def finish(self, parts_out: Sequence[Any]) -> None:
        if self.enabled:
            self._outs = list(parts_out)

    def task_input(self, dep_parts: Sequence[Any]) -> None:
        """Streaming mode: account one task's (resolved) inputs at
        dispatch time — by then the upstream partitions exist, so the
        stage totals match what the barriered path would have seen."""
        if not self.enabled:
            return
        rows = nbytes = 0
        for p in dep_parts:
            r, b = _part_meta(p)
            if r > 0:
                rows += r
            nbytes += b
        with self._mu:
            self._rows_in += rows
            self._bytes_in += nbytes

    def task_output(self, index: int, part: Any) -> None:
        """Streaming mode: record one completed task's output partition
        (keyed by index so skew stats stay order-stable regardless of
        completion order)."""
        if not self.enabled:
            return
        meta = _part_meta(part)
        with self._mu:
            self._out_meta[index] = meta

    def _partition(self) -> dict:
        """The four measured parts of the wall along the critical path,
        ``upstream_s`` and the envelopes in reply order (see the class
        docstring)."""
        with self._mu:
            rounds = [
                (r["start"], list(r["envelopes"].values()))
                for r in self._rounds if r["envelopes"]
            ]
        last = sorted(
            ((start, max(envs, key=lambda e: e["reply"]))
             for start, envs in rounds),
            key=lambda sc: -sc[1]["reply"],
        )
        parts = {"submit_s": 0.0, "transit_s": 0.0, "load_s": 0.0,
                 "exec_s": 0.0, "upstream_s": 0.0}
        cursor = float("inf")
        for start, crit in last:
            if crit["reply"] > cursor:
                continue  # overlaps the chain: off the critical path
            cursor = start
            worker = max(0.0, crit["ret"] - crit["recv"])
            body = _union_s(crit["bodies"], crit["recv"], crit["ret"])
            parts["submit_s"] += max(0.0, crit["send"] - start)
            parts["transit_s"] += max(
                0.0, crit["reply"] - crit["send"] - worker
            )
            parts["load_s"] += worker - body
            parts["exec_s"] += body
        if last:
            parts["upstream_s"] = max(0.0, cursor - self._t0)
        parts["envelopes"] = sorted(
            (e for _, envs in rounds for e in envs),
            key=lambda e: e["reply"],
        )
        return parts

    @contextlib.contextmanager
    def _closing(self):
        """The ``stage/close`` span of a cluster stage, around the
        close's own work (``_part_meta`` over the outputs, ``_emit``).
        Its attrs are known when it opens, because the replies are in:
        the parts in µs (``driver_us`` as of now: the close itself
        comes on top) and each envelope's worker-side stamps — a
        profiler annotation takes attrs at entry only, so they cannot
        go on ``stage/envelope`` itself."""
        if self.kind != "cluster":
            yield None
            return
        parts = self._partition()
        measured = sum(
            parts[k] for k in ("submit_s", "transit_s", "load_s", "exec_s")
        )
        envelopes = parts.pop("envelopes")
        with span(
            "stage/close", stage=self.stage_id, op=self.op,
            submit_us=round(parts["submit_s"] * 1e6),
            transit_us=round(parts["transit_s"] * 1e6),
            load_us=round(parts["load_s"] * 1e6),
            exec_us=round(parts["exec_s"] * 1e6),
            driver_us=round(
                (time.perf_counter() - self._t0 - measured) * 1e6
            ),
            envelopes=format_envelopes(envelopes[-_CLOSE_ENVELOPES:]),
        ):
            yield parts

    def close_streaming(self) -> None:
        """Finalize a streaming stage: called by the scheduler after the
        last task lands, BEFORE the final output future resolves."""
        if not self.enabled:
            return
        with self._closing() as parts:
            with self._mu:
                meta = dict(self._out_meta)
            part_rows = [meta[i][0] for i in sorted(meta)]
            part_bytes = [meta[i][1] for i in sorted(meta)]
            self._emit(part_rows, part_bytes, len(meta), parts)

    def close(self) -> None:
        if not self.enabled:
            return
        with self._closing() as parts:
            part_rows: List[int] = []
            part_bytes: List[int] = []
            for p in self._outs or ():
                r, b = _part_meta(p)
                part_rows.append(r)
                part_bytes.append(b)
            self._emit(part_rows, part_bytes, len(self._outs or ()), parts)

    def _emit(self, part_rows: List[int], part_bytes: List[int],
              parts_out: int, parts: Optional[dict]) -> None:
        wall = time.perf_counter() - self._t0
        rows_out = sum(r for r in part_rows if r > 0)
        parts = dict(parts or {})  # a local stage has no partition
        if parts:
            parts["driver_s"] = wall - sum(
                parts[k]
                for k in ("submit_s", "transit_s", "load_s", "exec_s")
            )
        stats = StageStats(
            stage_id=self.stage_id,
            op=self.op,
            executor=self.kind,
            rows_in=self._rows_in,
            rows_out=rows_out,
            bytes_in=self._bytes_in,
            bytes_out=sum(part_bytes),
            parts_in=self._parts_in,
            parts_out=parts_out,
            wall_s=wall,
            # The time the stage's critical tasks existed and were
            # neither being submitted nor running — measured, not
            # inferred from a sum of task seconds over all workers.
            queue_s=parts.get("transit_s", 0.0) + parts.get("load_s", 0.0),
            workers=dict(self._workers),
            part_rows=part_rows,
            part_bytes=part_bytes,
            tasks_stamped=self._tasks_stamped,
            **self._bodies,
            **parts,
        )
        stage_store.record(stats)
        progress.stage_end(self.stage_id)
        if self._ids_sink is not None and not self._ids_sunk:
            self._ids_sink.append(self.stage_id)
        # Read by name: ``export.py`` folds them into the families
        # ``raydp_stage_{rows,bytes,seconds}_total``, the dashboard reads
        # ``stage/rows_out/``.
        metrics.counter_add(f"stage/rows_in/{self.op}", self._rows_in)
        metrics.counter_add(f"stage/rows_out/{self.op}", rows_out)
        metrics.counter_add(f"stage/bytes_in/{self.op}", self._bytes_in)
        metrics.counter_add(f"stage/bytes_out/{self.op}", stats.bytes_out)
        metrics.counter_add(f"stage/seconds/{self.op}", wall)


@contextlib.contextmanager
def _stage(op: str, parts_in: Sequence[Any], executor: str,
           total_tasks: Optional[int] = None):
    """Span + counter + StageStats recording around one stage."""
    rec = _StageRecorder(op, parts_in, executor, total_tasks)
    with _stage_span(op, len(parts_in), executor):
        try:
            yield rec
        finally:
            rec.close()

# Memoized gather-concat for coalesced runs (Spark's analog: shuffle
# block reuse). Interactive ETL re-runs queries over the SAME stored
# partitions; re-fetching and re-concatenating them rebuilds fresh
# buffers each time, which also defeats every buffer-identity cache
# downstream (the window engine's one-sort-per-spec frame cache keys on
# buffer addresses). Keyed by partition identity (object ids / table
# ids), LRU-bounded by bytes. Lives per PROCESS: in cluster mode the
# memo sits in the ETL worker that coalesced runs route to (stable
# majority-resident placement), in local mode in the driver.
_CONCAT_MEMO_BYTES = int(
    os.environ.get("RAYDP_TPU_CONCAT_CACHE_BYTES", 256 << 20)
)
_concat_memo: OrderedDict = OrderedDict()
_concat_memo_lock = threading.Lock()


def _fetch_concat_cached(ctx, refs) -> pa.Table:
    """Worker-side gather for pre_concat coalesced runs: on a memo hit
    the shm fetches are skipped along with the concat. Only ObjectRefs
    are memoized — their object ids are globally unique, while id() of
    a per-task unpickled raw ref could be recycled after GC and alias a
    stale entry."""
    if all(isinstance(r, ObjectRef) for r in refs):
        key = tuple(r.object_id for r in refs)
        with _concat_memo_lock:
            ent = _concat_memo.get(key)
            if ent is not None:
                _concat_memo.move_to_end(key)
                return ent[1]
    else:
        key = None
    tables = [ctx.get_table(r) for r in refs]
    return _concat_cached(tables, key)


def _concat_cached(tables: List[pa.Table], key, keepalive=None) -> pa.Table:
    """``_concat`` with identity-keyed memoization. ``keepalive`` pins
    the objects whose ids form ``key`` (local mode: id() reuse after GC
    would otherwise alias a stale entry)."""
    if key is None:
        return _concat(tables)
    with _concat_memo_lock:
        hit = _concat_memo.pop(key, None)
        if hit is not None:
            _concat_memo[key] = hit  # refresh LRU position
            return hit[1]
    out = _concat(tables)
    # Entry cost: arrow's concat is zero-copy (the output references the
    # input chunks' buffers), so ``out.nbytes`` already measures the
    # retained memory and the keepalive pins only object headers on top.
    cost = out.nbytes
    with _concat_memo_lock:
        _concat_memo[key] = (keepalive, out, cost)
        total = sum(c for _, _, c in _concat_memo.values())
        while total > _CONCAT_MEMO_BYTES and len(_concat_memo) > 1:
            _, (_, _, evicted_cost) = _concat_memo.popitem(last=False)
            total -= evicted_cost
    return out


class Executor:
    def map_partitions(self, parts: List[Any], fn: StageFn) -> List[Any]:
        raise NotImplementedError

    def map_partitions_indexed(
        self, parts: List[Any], fn: Callable[[pa.Table, int], pa.Table]
    ) -> List[Any]:
        """Like map_partitions, but ``fn`` also receives the partition
        index (for partition-indexed ops like monotonically_increasing_id)."""
        raise NotImplementedError

    def map_pairs(
        self,
        parts_a: List[Any],
        parts_b: List[Any],
        fn: Callable[[pa.Table, pa.Table], pa.Table],
    ) -> List[Any]:
        """Zip two equally-partitioned lists through a binary stage
        (bucket i of a shuffle join meets bucket i)."""
        raise NotImplementedError

    def exchange(
        self,
        parts: List[Any],
        splitter: Callable[[pa.Table], List[pa.Table]],
        n_out: int,
        combine: Optional[StageFn] = None,
        replan: Optional[Callable[[List[int]], Any]] = None,
    ) -> List[Any]:
        """All-to-all: split every partition into n_out chunks, then
        concatenate chunk i across partitions into output partition i.

        ``replan`` is the AQE hook: called with the measured per-bucket
        byte sizes AFTER the split phase and BEFORE merge dispatch — the
        one point where the true shuffle layout is known but nothing has
        been merged yet. It returns an
        :class:`raydp_tpu.dataframe.aqe.ExchangePlan` (or ``None`` to
        keep the static layout); the executor then builds output
        partitions group-by-group instead of one-per-bucket. ``split``
        groups are only legal with ``combine=None`` (a per-bucket
        combine over a sub-bucket would see partial groups)."""
        raise NotImplementedError

    def part_nbytes(self, part: Any) -> int:
        """Approximate in-memory/wire size of one partition, WITHOUT
        materializing it — drives adaptive shuffle planning (Spark AQE's
        coalescing decisions read shuffle statistics the same way)."""
        raise NotImplementedError

    def discard(self, parts: List[Any]) -> None:
        """Free intermediate partitions (shuffle temps). No-op where
        partitions are plain in-memory tables."""

    def run_coalesced(
        self,
        parts: List[Any],
        fn: Callable[[Any], pa.Table],
        pre_concat: bool = False,
    ) -> Any:
        """Run ``fn`` over ALL partitions in one task and return a single
        output partition. The adaptive small-data plan: when inputs (or
        partial-agg outputs) are small, one arrow kernel pass — which
        parallelizes internally across cores — beats a process-level
        hash exchange whose per-task orchestration would dominate.

        ``pre_concat=True``: the executor concatenates the partitions
        itself — memoized by partition identity (``_concat_cached``) so
        repeated queries over the same stored partitions hand ``fn`` the
        SAME table object (same buffers → downstream buffer-identity
        caches hit) — and ``fn`` receives one ``pa.Table`` instead of a
        list."""
        raise NotImplementedError

    def materialize(self, part: Any) -> pa.Table:
        raise NotImplementedError

    def head(self, part: Any, k: int) -> pa.Table:
        """First ``k`` rows of one partition (schema/peek probes).
        Backends cut the head where the partition lives — the driver
        never pulls the whole table for a 32-row probe."""
        raise NotImplementedError

    def put(self, table: pa.Table) -> Any:
        raise NotImplementedError

    def put_many(self, tables: List[pa.Table]) -> List[Any]:
        """Bulk ingest; overridden where scatter can run concurrently."""
        return [self.put(t) for t in tables]

    def num_rows(self, part: Any) -> int:
        raise NotImplementedError

    def sample_column(self, parts: List[Any], column: str, k: int) -> list:
        """Up to ``k`` non-null sample values of ``column`` per partition,
        WITHOUT materializing partitions on the driver (range-sort pivots)."""
        raise NotImplementedError

    def default_fanout(self) -> int:
        """How many output partitions a shuffle should target."""
        return 8


def _split_groups(items: List[Any], k: int) -> List[List[Any]]:
    """Distribute one bucket's per-input chunk list over ``k``
    contiguous, non-empty groups (AQE skew splitting). Contiguous in
    input order so sub-bucket contents stay deterministic run-to-run;
    ``plan_exchange`` clamps ``k`` to the input-partition count, the
    ``min`` here is belt-and-braces."""
    k = max(1, min(k, len(items)))
    base, extra = divmod(len(items), k)
    groups, offset = [], 0
    for i in range(k):
        size = base + (1 if i < extra else 0)
        groups.append(items[offset:offset + size])
        offset += size
    return groups


def _concat(tables: List[pa.Table]) -> pa.Table:
    tables = [t for t in tables if t is not None]
    if not tables:
        return pa.table({})
    # Drop empty tables: stages like distributed agg can emit empties with
    # an intermediate schema (partial-agg columns); schema-promoting concat
    # would leak those as all-null columns.
    non_empty = [t for t in tables if t.num_rows > 0]
    if not non_empty:
        return tables[0]
    if len(non_empty) == 1:
        return non_empty[0]
    return pa.concat_tables(non_empty, promote_options="default")


class LocalExecutor(Executor):
    def __init__(self, max_threads: Optional[int] = None):
        self._pool = ThreadPoolExecutor(
            max_workers=max_threads or min(8, (os.cpu_count() or 2) * 2)
        )

    def _stream_narrow(self, op, deps, call_of):
        """Event-driven narrow stage on the thread pool: each output's
        task runs the moment its upstream partitions exist; callers get
        pending partitions immediately."""
        rec = _StageRecorder(op, [d[0] for d in deps], "local",
                             total_tasks=len(deps), streaming=True)

        def run_one(i, vals):
            rec.task_input(vals[:1])
            out = call_of(i, vals)
            rec.task_done()
            return out

        def submit(items):
            return [self._pool.submit(run_one, i, vals)
                    for i, vals in items]

        stage = StreamingStage(deps, submit, on_output=rec.task_output,
                               on_close=rec.close_streaming, op=op)
        with _stage_span(op, len(deps), "local", streaming=True):
            outs = stage.start()
        return outs

    def map_partitions(self, parts, fn):
        if streaming_enabled() and parts:
            return self._stream_narrow(
                "map_partitions", [[p] for p in parts],
                lambda i, vals: fn(vals[0]),
            )
        parts = resolve(parts)
        with _stage("map_partitions", parts, "local") as rec:
            def run(t):
                out = fn(t)
                rec.task_done()
                return out

            outs = list(self._pool.map(run, parts))
            rec.finish(outs)
            return outs

    def map_partitions_indexed(self, parts, fn):
        if streaming_enabled() and parts:
            return self._stream_narrow(
                "map_partitions_indexed", [[p] for p in parts],
                lambda i, vals: fn(vals[0], i),
            )
        parts = resolve(parts)
        with _stage("map_partitions_indexed", parts, "local") as rec:
            def run(t, i):
                out = fn(t, i)
                rec.task_done()
                return out

            outs = list(self._pool.map(run, parts, range(len(parts))))
            rec.finish(outs)
            return outs

    def map_pairs(self, parts_a, parts_b, fn):
        if streaming_enabled() and parts_a:
            return self._stream_narrow(
                "map_pairs",
                [[a, b] for a, b in zip(parts_a, parts_b)],
                lambda i, vals: fn(vals[0], vals[1]),
            )
        parts_a = resolve(parts_a)
        parts_b = resolve(parts_b)
        with _stage("map_pairs", parts_a, "local") as rec:
            def run(ta, tb):
                out = fn(ta, tb)
                rec.task_done()
                return out

            outs = list(self._pool.map(run, parts_a, parts_b))
            rec.finish(outs)
            return outs

    def exchange(self, parts, splitter, n_out, combine=None, replan=None):
        # Wide stage: every input partition feeds every output bucket,
        # so this is a true barrier — resolve pendings up front.
        parts = resolve(parts)
        with _stage("exchange", parts, "local",
                    total_tasks=len(parts) + n_out) as rec:
            metrics.counter_add("shuffle/exchanges")
            chunked = list(self._pool.map(splitter, parts))
            rec.task_done(len(parts))
            moved = sum(
                c.nbytes for chunks in chunked for c in chunks
            )
            metrics.counter_add("shuffle/bytes", moved)
            # Single host: every chunk is already local to its merge.
            metrics.counter_add("shuffle/local_bytes", moved)
            _acct.add_usage(_acct.SHUFFLE_BYTES, moved)
            plan = None
            if replan is not None:
                plan = replan([
                    sum(chunks[i].nbytes for chunks in chunked)
                    for i in range(n_out)
                ])
            outs = []
            if plan is None:
                for i in range(n_out):
                    merged = _concat([chunks[i] for chunks in chunked])
                    outs.append(combine(merged) if combine else merged)
                    rec.task_done()
                rec.finish(outs)
                return outs
            for g in plan.groups:
                if g[0] == "merge":
                    # Bucket-major order: a group of one bucket is
                    # byte-identical to the static merge of that bucket.
                    merged = _concat(
                        [chunks[i] for i in g[1] for chunks in chunked]
                    )
                    outs.append(combine(merged) if combine else merged)
                elif g[0] == "replicate":
                    merged = _concat([chunks[g[1]] for chunks in chunked])
                    merged = combine(merged) if combine else merged
                    outs.extend([merged] * g[2])
                else:  # ("split", id, k): combine is None by contract
                    for grp in _split_groups(
                        [chunks[g[1]] for chunks in chunked], g[2]
                    ):
                        outs.append(_concat(grp))
                rec.task_done()
            rec.finish(outs)
            return outs

    def part_nbytes(self, part):
        return resolve_one(part).nbytes

    def run_coalesced(self, parts, fn, pre_concat=False):
        parts = resolve(list(parts))
        with _stage("run_coalesced", parts, "local", total_tasks=1) as rec:
            if not pre_concat:
                out = fn(parts)
            else:
                key = ("local",) + tuple(id(t) for t in parts)
                out = fn(_concat_cached(parts, key, keepalive=parts))
            rec.finish([out] if isinstance(out, pa.Table) else [])
            return out

    def materialize(self, part):
        return resolve_one(part)

    def head(self, part, k):
        part = resolve_one(part)
        return part.slice(0, min(k, part.num_rows))

    def put(self, table):
        return table

    def num_rows(self, part):
        return resolve_one(part).num_rows

    def sample_column(self, parts, column, k):
        return [
            vals
            for t in resolve(parts)
            for vals in [_sample_table(t, column, k)]
        ]

    def default_fanout(self) -> int:
        return min(8, (os.cpu_count() or 2) * 2)


def _sample_table(t: pa.Table, column: str, k: int) -> list:
    if t.num_rows == 0:
        return []
    series = t.column(column).to_pandas().dropna()
    if not len(series):
        return []
    return series.sample(min(k, len(series)), random_state=0).tolist()


class ClusterExecutor(Executor):
    """Runs stages on the session's ETL workers; partitions live in shm."""

    def __init__(self, cluster):
        self.cluster = cluster
        self.store: ObjectStore = cluster.master.store
        self._put_rr = itertools.count()

    # Stable partition→worker routing, locality-first: a partition ref is
    # routed to a worker on the node where its bytes already live (zero-copy
    # shm read), falling back to index round-robin. The reference does the
    # same via getPreferredLocations (RayDatasetRDD.scala:53-55).
    def _worker_for(self, index: int, ref=None) -> Optional[str]:
        workers = self.cluster.alive_workers()
        if not workers:
            return None
        if isinstance(ref, ObjectRef):
            local = sorted(
                w.worker_id for w in workers if w.node_id == ref.node_id
            )
            if local:
                return local[index % len(local)]
        ordered = sorted(w.worker_id for w in workers)
        return ordered[index % len(ordered)]

    def _stream_narrow(self, op, deps, spec_of):
        """Event-driven narrow stage: every output's task ships the
        moment its upstream partitions exist. Each scheduler pump
        batches ALL simultaneously-ready outputs into ONE submit_batch
        call, so the one-RunTaskBatch-envelope-per-worker amortization
        is preserved (the all-concrete case is exactly one batch)."""
        rec = _StageRecorder(op, [d[0] for d in deps], "cluster",
                             total_tasks=len(deps), streaming=True)

        def submit(items):
            for _i, vals in items:
                rec.task_input(vals[:1])
            specs = [spec_of(i, vals) for i, vals in items]
            return self.cluster.submit_batch(specs, meta_sink=rec.round())

        stage = StreamingStage(deps, submit, on_output=rec.task_output,
                               on_close=rec.close_streaming, op=op)
        with _stage_span(op, len(deps), "cluster", streaming=True):
            outs = stage.start()
        return outs

    def map_partitions(self, parts, fn):
        def task(ctx, ref):
            table = ctx.get_table(ref)
            return ctx.put_table(fn(table), holder=True)

        if streaming_enabled() and parts:
            return self._stream_narrow(
                "map_partitions", [[p] for p in parts],
                lambda i, vals: TaskSpec(
                    task, (vals[0],),
                    worker_id=self._worker_for(i, vals[0]),
                ),
            )
        parts = resolve(parts)
        with _stage("map_partitions", parts, "cluster") as rec:
            # One RunTaskBatch envelope per worker (not per partition):
            # per-call gRPC+pickle overhead amortizes over all of that
            # worker's partitions, and fn serializes once per envelope.
            futures = self.cluster.submit_batch([
                TaskSpec(task, (ref,), worker_id=self._worker_for(i, ref))
                for i, ref in enumerate(parts)
            ], meta_sink=rec.round())
            outs = [f.result() for f in futures]
            rec.finish(outs)
            return outs

    def map_partitions_indexed(self, parts, fn):
        def task(ctx, ref, index):
            table = ctx.get_table(ref)
            return ctx.put_table(fn(table, index), holder=True)

        if streaming_enabled() and parts:
            return self._stream_narrow(
                "map_partitions_indexed", [[p] for p in parts],
                lambda i, vals: TaskSpec(
                    task, (vals[0], i),
                    worker_id=self._worker_for(i, vals[0]),
                ),
            )
        parts = resolve(parts)
        with _stage("map_partitions_indexed", parts, "cluster") as rec:
            futures = self.cluster.submit_batch([
                TaskSpec(task, (ref, i), worker_id=self._worker_for(i, ref))
                for i, ref in enumerate(parts)
            ], meta_sink=rec.round())
            outs = [f.result() for f in futures]
            rec.finish(outs)
            return outs

    def part_nbytes(self, part):
        part = resolve_one(part)
        return part.size if isinstance(part, ObjectRef) else part.nbytes

    def discard(self, parts):
        for ref in parts:
            if isinstance(ref, PendingPartition):
                # Free the partition whenever its producer lands; a
                # failed producer has nothing to free.
                ref.future.add_done_callback(self._discard_done)
            elif isinstance(ref, ObjectRef):
                self.store.delete(ref)

    def _discard_done(self, fut) -> None:
        if fut.exception() is not None:
            return
        ref = fut.result()
        if isinstance(ref, ObjectRef):
            try:
                self.store.delete(ref)
            except Exception:
                pass

    def run_coalesced(self, parts, fn, pre_concat=False):
        # Coalesced runs need every input in one task: barrier here.
        parts = resolve(list(parts))
        if pre_concat:
            def task(ctx, refs):
                # _fetch_concat_cached is resolved in the WORKER's own
                # executor module (pickled by reference), so the memo —
                # and its lock — live worker-side and never ship.
                return ctx.put_table(
                    fn(_fetch_concat_cached(ctx, refs)), holder=True
                )
        else:
            def task(ctx, refs):
                tables = [ctx.get_table(r) for r in refs]
                return ctx.put_table(fn(tables), holder=True)

        # Locality: run on the worker whose node holds the most input
        # bytes (one cross-node fetch per remote partition either way;
        # majority-resident placement minimizes them).
        by_node = {}
        for ref in parts:
            if isinstance(ref, ObjectRef):
                by_node[ref.node_id] = by_node.get(ref.node_id, 0) + ref.size
        worker_id = None
        if by_node:
            best = max(by_node, key=by_node.get)
            workers = sorted(
                w.worker_id
                for w in self.cluster.alive_workers()
                if w.node_id == best
            )
            if workers:
                worker_id = workers[0]
        parts = list(parts)
        with _stage("run_coalesced", parts, "cluster",
                    total_tasks=1) as rec:
            fut = self.cluster.submit_async(
                task, parts, worker_id=worker_id, meta_sink=rec.round()
            )
            out = fut.result()
            rec.finish([out])
            return out

    def map_pairs(self, parts_a, parts_b, fn):
        def task(ctx, ra, rb):
            ta = ctx.get_table(ra)
            tb = ctx.get_table(rb)
            return ctx.put_table(fn(ta, tb), holder=True)

        if streaming_enabled() and parts_a:
            return self._stream_narrow(
                "map_pairs",
                [[a, b] for a, b in zip(parts_a, parts_b)],
                lambda i, vals: TaskSpec(
                    task, (vals[0], vals[1]),
                    worker_id=self._worker_for(i, vals[0]),
                ),
            )
        parts_a = resolve(parts_a)
        parts_b = resolve(parts_b)
        with _stage("map_pairs", parts_a, "cluster") as rec:
            futures = self.cluster.submit_batch([
                TaskSpec(task, (ra, rb), worker_id=self._worker_for(i, ra))
                for i, (ra, rb) in enumerate(zip(parts_a, parts_b))
            ], meta_sink=rec.round())
            outs = [f.result() for f in futures]
            rec.finish(outs)
            return outs

    def _free_refs(self, refs) -> None:
        for ref in refs:
            if isinstance(ref, ObjectRef):
                try:
                    self.store.delete(ref)
                except Exception:
                    pass

    def _merge_worker(self, index: int, refs):
        """Locality-scheduled merge placement: (worker_id, node_id) of
        the node already holding the most input bytes for this bucket —
        those chunks are zero-copy shm reads there, only the minority
        streams over. Workers on the winning node are spread by bucket
        index; round-robin fallback when nothing is resident."""
        by_node: dict = {}
        for r in refs:
            if isinstance(r, ObjectRef):
                # max(size, 1): empty chunks still vote for their node.
                by_node[r.node_id] = by_node.get(r.node_id, 0) + max(r.size, 1)
        workers = self.cluster.alive_workers()
        if by_node and workers:
            # Sorted iteration breaks byte ties deterministically.
            best = max(sorted(by_node), key=lambda n: by_node[n])
            local = sorted(
                w.worker_id for w in workers if w.node_id == best
            )
            if local:
                return local[index % len(local)], best
        wid = self._worker_for(index)
        node = next(
            (w.node_id for w in workers if w.worker_id == wid), None
        )
        return wid, node

    def exchange(self, parts, splitter, n_out, combine=None, replan=None):
        def split_task(ctx, ref):
            table = ctx.get_table(ref)
            return [ctx.put_table(chunk, holder=True) for chunk in splitter(table)]

        def merge_task(ctx, refs):
            tables = [ctx.get_table(r) for r in refs]
            merged = _concat(tables)
            if combine is not None:
                merged = combine(merged)
            return ctx.put_table(merged, holder=True)

        def preconcat_task(ctx, refs):
            # Eager pre-merge: concat only — ``combine`` runs exactly
            # once per bucket, in the final merge.
            return ctx.put_table(
                _concat([ctx.get_table(r) for r in refs]), holder=True
            )

        # Eager pre-merge threshold: with >= N chunks of a bucket ready
        # while splits are still running, concat them now so the final
        # merge starts from partially-reduced inputs. Off by default —
        # it trades intra-bucket row order (arrival order, not input
        # order) for overlap, so it is an explicit opt-in.
        try:
            eager_min = int(
                os.environ.get("RAYDP_TPU_EXCHANGE_EAGER_MERGE", "0") or 0
            )
        except ValueError:
            eager_min = 0

        # Wide stage: every split must exist before buckets can close —
        # resolve pendings up front (the downstream merge dispatch is
        # already streamed below).
        parts = resolve(parts)
        with _stage("exchange", parts, "cluster",
                    total_tasks=len(parts) + n_out) as rec:
            metrics.counter_add("shuffle/exchanges")
            split_futures = self.cluster.submit_batch([
                TaskSpec(split_task, (ref,),
                         worker_id=self._worker_for(i, ref))
                for i, ref in enumerate(parts)
            ], meta_sink=rec.round())
            # Stream split completions (one envelope per worker resolves
            # independently) instead of gathering in submission order:
            # merge planning starts the moment the last chunk EXISTS,
            # and the eager path can pre-concat hot buckets while slow
            # splits are still running.
            from concurrent.futures import FIRST_COMPLETED, wait as _wait

            idx_of = {f: i for i, f in enumerate(split_futures)}
            chunks_by_part: List[Optional[list]] = [None] * len(parts)
            avail: List[list] = [[] for _ in range(n_out)]
            early: List[list] = [[] for _ in range(n_out)]
            pending = set(split_futures)
            while pending:
                done, pending = _wait(pending, return_when=FIRST_COMPLETED)
                for f in done:
                    row = f.result()  # raises like the old ordered gather
                    chunks_by_part[idx_of[f]] = row
                    if eager_min > 0:
                        for i, ref in enumerate(row):
                            avail[i].append(ref)
                if eager_min > 0 and pending:
                    for i in range(n_out):
                        if len(avail[i]) >= eager_min:
                            batch, avail[i] = avail[i], []
                            wid, _node = self._merge_worker(i, batch)
                            fut = self.cluster.submit_async(
                                preconcat_task, batch, worker_id=wid
                            )
                            fut.add_done_callback(
                                lambda _f, refs=batch: self._free_refs(refs)
                            )
                            early[i].append(fut)

            if eager_min > 0:
                # Arrival order within a bucket (pre-merged blocks first).
                inputs = [
                    [f.result() for f in early[i]] + avail[i]
                    for i in range(n_out)
                ]
            else:
                # Deterministic: chunk i of every input, in input order.
                inputs = [
                    [chunks[i] for chunks in chunks_by_part]
                    for i in range(n_out)
                ]

            # AQE replan: only over the deterministic layout — the eager
            # path already traded bucket order for overlap and its refs
            # are partially pre-merged, so the measured per-bucket sizes
            # would double-count. Each group of the returned plan becomes
            # one (or, for splits, k) merge task(s); every split ref is
            # still consumed by exactly one merge, so the byte counters
            # and per-merge freeing below are unchanged.
            plan = None
            if replan is not None and eager_min == 0:
                plan = replan([
                    sum(r.size for r in refs if isinstance(r, ObjectRef))
                    for refs in inputs
                ])
            if plan is None:
                groups = [("merge", [i]) for i in range(n_out)]
            else:
                groups = plan.groups

            specs, merge_inputs, repeats = [], [], []
            total_b = local_b = 0
            for g in groups:
                if g[0] == "merge":
                    # Bucket-major ref order: singleton groups reproduce
                    # the static merge exactly.
                    batches = [[r for i in g[1] for r in inputs[i]]]
                    rep = 1
                elif g[0] == "replicate":
                    batches = [inputs[g[1]]]
                    rep = g[2]
                else:  # ("split", id, k): combine is None by contract
                    batches = _split_groups(inputs[g[1]], g[2])
                    rep = 1
                for refs in batches:
                    wid, node = self._merge_worker(len(specs), refs)
                    for r in refs:
                        if isinstance(r, ObjectRef):
                            total_b += r.size
                            if node is not None and r.node_id == node:
                                local_b += r.size
                    specs.append(
                        TaskSpec(merge_task, (refs,), worker_id=wid,
                                 node_id=node)
                    )
                    merge_inputs.append(refs)
                    repeats.append(rep)
            metrics.counter_add("shuffle/bytes", total_b)
            metrics.counter_add("shuffle/local_bytes", local_b)
            _acct.add_usage(_acct.SHUFFLE_BYTES, total_b)
            merge_futures = self.cluster.submit_batch(
                specs, meta_sink=rec.round()
            )
            # Merge i consumes exactly its input refs, so they are dead
            # the moment that merge lands — free them then, instead of
            # holding the whole shuffle's intermediates until the full
            # barrier (peak shm across a shuffle drops to the still-
            # unmerged buckets).
            for f, refs in zip(merge_futures, merge_inputs):
                f.add_done_callback(
                    lambda fut, rr=refs: self._free_refs(rr)
                )
            outs = []
            for f, rep in zip(merge_futures, repeats):
                ref = f.result()
                outs.extend([ref] * rep)
            rec.finish(outs)
            return outs

    def materialize(self, part):
        return self.cluster.resolver.get_arrow_table(resolve_one(part))

    def head(self, part, k):
        part = resolve_one(part)
        if not isinstance(part, ObjectRef):
            return part.slice(0, min(k, part.num_rows))

        def probe(ctx, ref, n):
            table = ctx.get_table(ref)
            n = min(n, table.num_rows)
            # take(), not slice(): a slice pickles its PARENT buffers
            # (the whole partition would ride the reply); take copies
            # just the probe rows.
            return table.take(pa.array(range(n), type=pa.int64()))

        return self.cluster.submit_async(
            probe, part, k, worker_id=self._worker_for(0, part)
        ).result()

    def put(self, table):
        return self._put_async(table).result()

    def put_many(self, tables):
        # Scatter concurrently: ingest wall-clock is the slowest single
        # transfer, not the sum. Source frames stay concrete (refs, not
        # pendings): ingest is driver-local put work, and downstream
        # consumers — union coercion, to_object_refs, the store feed —
        # rely on source partitions being addressable refs. Streaming
        # starts at the first narrow STAGE over these refs.
        futures = [self._put_async(t) for t in tables]
        return [f.result() for f in futures]

    def _put_async(self, table):
        """Ingest a partition, holder-owned (base data must survive pool
        shrinks: the kill_worker contract), placed round-robin over the
        alive workers so initial placement is spread across NODES (Spark
        parallelize lands blocks on executors, not the driver) — without
        that every partition would start on the driver node and locality
        routing would keep all work there.

        Where the round-robin target sits on the node this process's
        store writes to, there is nothing to place: the driver's own put
        IS the partition — one IPC write into the segment, no task, no
        staged copy, no worker re-put, no RegisterObject (the master's
        store is the directory). A target on another node — or any
        target, for a ``RemoteCluster`` client, whose store proxy is on
        no data node — gets the table over the DATA plane
        (``data_args``): staged once in the driver-node store, the
        RunTask envelope carries only the ref, and the worker streams it
        from the driver node's agent and re-puts it on its own node. No
        table bytes ride the control plane either way."""
        workers = sorted(
            self.cluster.alive_workers(), key=lambda w: w.worker_id
        )
        target = (
            workers[next(self._put_rr) % len(workers)] if workers else None
        )
        if target is None or target.node_id == self.store.node_id:
            metrics.counter_add("df/ingest_partitions_local")
            f = Future()
            f.set_result(self.store.put_arrow_table(table))
            return f

        def ingest(ctx, t):
            return ctx.put_table(t, holder=True)

        metrics.counter_add("df/ingest_partitions_shipped")
        return self.cluster.submit_async(
            ingest, worker_id=target.worker_id, data_args=(table,)
        )

    def num_rows(self, part):
        part = resolve_one(part)
        return part.num_rows if isinstance(part, ObjectRef) else -1

    def default_fanout(self) -> int:
        # 2 shuffle partitions per alive worker keeps every worker busy in
        # the merge phase and scales with dynamic allocation (no hard cap).
        return max(8, 2 * len(self.cluster.alive_workers()))

    def sample_column(self, parts, column, k):
        def task(ctx, ref):
            return _sample_table(ctx.get_table(ref), column, k)

        parts = resolve(parts)
        futures = self.cluster.submit_batch([
            TaskSpec(task, (ref,), worker_id=self._worker_for(i, ref))
            for i, ref in enumerate(parts)
        ])
        return [f.result() for f in futures]
