"""Multi-process distributed fit: JAXEstimator across a supervised SPMD gang.

The multi-host training story (reference: Ray Train spawns worker
processes wired with torch DDP, torch/estimator.py:276-297). Here each
gang rank joins ``jax.distributed`` — its local chips become part of ONE
global mesh — builds the estimator from a user factory, and feeds its
own dataset shard; batches assemble into global arrays
(``make_array_from_process_local_data``) and XLA psums gradients over
the global dp axis. On a TPU pod: one rank per host. In tests: ranks are
local processes with CPU devices, and the collectives run over gloo.

Supervision (doc/fault_tolerance.md): on shared TPU pools ranks die and
hosts get preempted as a matter of course, so ``fit_spmd`` wraps the
gang in a supervisor loop — rank death or a registration timeout tears
the gang down and relaunches it with jittered exponential backoff under
a restart budget, auto-resuming from the newest orbax checkpoint in
``checkpoint_dir`` (``save_every_steps`` bounds the replay). A SIGTERM
preemption notice drains the in-flight step and writes an emergency
checkpoint first (estimator drain path), so the relaunch loses nothing.
With ``elastic=True`` the relaunch may land on a *smaller* world: the
sharded orbax restore lays params/opt state out on the new mesh and the
loader re-shards the remaining epoch — losing a host degrades
throughput instead of killing the job. Recovery events ride the
telemetry registry as ``restarts/total`` / ``preemptions/total`` /
``replay/steps`` (exported as ``raydp_restarts_total`` etc.).
"""
from __future__ import annotations

import logging
import os
import random
import re
import time
from typing import Any, Callable, Dict, List, Optional

logger = logging.getLogger(__name__)

__all__ = ["fit_spmd"]

# "job X: only 2/4 ranks registered within 30s ..." — the registration
# shortfall SPMDJob raises when a host never comes up; elastic mode
# shrinks the world to the ranks that did register.
_REGISTERED_RE = re.compile(r"only (\d+)/(\d+) ranks registered")

# mid-step / emergency checkpoints encode the optimizer step in their
# directory name; epoch and final checkpoints don't (replay accounting
# is skipped for those).
_CKPT_STEP_RE = re.compile(r"^step_(?:mid|emergency)_(\d+)$")


def _newest_checkpoint(checkpoint_dir: Optional[str]) -> Optional[str]:
    """Newest complete orbax checkpoint under ``checkpoint_dir``.

    A checkpoint directory is considered complete when its orbax
    ``_METADATA`` exists (StandardCheckpointer writes it at commit);
    half-written checkpoints from a process that died mid-save are
    skipped, so a crash during save can cost one checkpoint interval
    but never a failed restore.
    """
    if not checkpoint_dir or not os.path.isdir(checkpoint_dir):
        return None
    best, best_mtime = None, -1.0
    for name in os.listdir(checkpoint_dir):
        if not (name.startswith("step_") or name == "final"):
            continue
        path = os.path.join(checkpoint_dir, name)
        meta = os.path.join(path, "_METADATA")
        if not os.path.isfile(meta):
            continue
        mtime = os.path.getmtime(meta)
        if mtime > best_mtime:
            best, best_mtime = path, mtime
    return best


def _ckpt_step(path: Optional[str]) -> Optional[int]:
    """Optimizer step encoded in a checkpoint dir name, or None."""
    if not path:
        return None
    m = _CKPT_STEP_RE.match(os.path.basename(path))
    return int(m.group(1)) if m else None


def _rank0_steps(job) -> int:
    """Cumulative optimizer steps rank 0 has reported via heartbeat
    metric deltas (±1 beat of lag — advisory, used for replay
    accounting only)."""
    try:
        workers = job.metrics_snapshot().get("workers", {})
        timer = workers.get("rank-0", {}).get("timer/train/step", {})
        return int(timer.get("count", 0))
    except Exception:
        return 0


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        return float(raw)
    except ValueError:
        return default


def fit_spmd(
    make_estimator: Callable[[], Any],
    train_ds,
    world_size: int,
    num_procs_per_node: int = 1,
    hosts: Optional[List[str]] = None,
    env: Optional[Dict[str, str]] = None,
    timeout: float = 600.0,
    max_restarts: Optional[int] = None,
    restart_backoff_s: Optional[float] = None,
    checkpoint_dir: Optional[str] = None,
    elastic: bool = False,
    min_world_size: int = 1,
) -> Dict[str, Any]:
    """Train ``make_estimator()`` data-parallel over ``world_size``
    processes. ``train_ds`` (MLDataset) is divided into ``world_size``
    equal shards; rank r consumes shard r. Returns rank 0's history and
    host-numpy params (replicated state).

    The factory runs INSIDE each rank (cloudpickled), after
    ``jax.distributed`` is initialized — build the MeshSpec there from
    ``jax.devices()`` (e.g. ``MeshSpec(dp=len(jax.devices()))``).

    Supervision: when ``checkpoint_dir`` is given, the gang is
    supervised — on rank death, registration timeout, or preemption the
    job is torn down and relaunched (jittered exponential backoff,
    ``restart_backoff_s`` base, env ``RAYDP_TPU_RESTART_BACKOFF_S``)
    under a budget of ``max_restarts`` relaunches (env
    ``RAYDP_TPU_MAX_RESTARTS``, default 3), resuming from the newest
    checkpoint in ``checkpoint_dir``. Configure the estimator factory
    with the SAME ``checkpoint_dir`` (and ``save_every_steps`` to bound
    replay): the ranks write checkpoints there, the supervisor picks
    resume points from it. Without ``checkpoint_dir``, failures
    restart training from scratch under the same budget.

    Elastic resize: ``elastic=True`` allows a relaunch onto fewer hosts
    — a registration shortfall shrinks the world to the ranks that did
    register (never below ``min_world_size``), the dataset is re-sharded
    for the new world, and the sharded orbax restore lays the state out
    on the new mesh. Strict mode (default) keeps the historical
    contract: ``train_ds.num_shards`` must equal ``world_size``.
    """
    from raydp_tpu.context import current_session
    from raydp_tpu.data.ml_dataset import MLDataset
    from raydp_tpu.spmd import create_spmd_job
    from raydp_tpu.spmd.job import SPMDJobError
    from raydp_tpu.store.object_store import ObjectRef
    from raydp_tpu.telemetry import accounting as _acct
    from raydp_tpu.telemetry import events as _events
    from raydp_tpu.telemetry import flight_recorder as _flight
    from raydp_tpu.utils.profiling import metrics as _metrics

    if not elastic and train_ds.num_shards != world_size:
        raise ValueError(
            f"train_ds must have num_shards == world_size "
            f"({train_ds.num_shards} != {world_size})"
        )
    if min_world_size < 1:
        raise ValueError("min_world_size must be >= 1")

    if max_restarts is None:
        max_restarts = int(_env_float("RAYDP_TPU_MAX_RESTARTS", 3))
    if restart_backoff_s is None:
        restart_backoff_s = _env_float("RAYDP_TPU_RESTART_BACKOFF_S", 1.0)

    session = current_session()
    store_mode = session is not None and all(
        isinstance(b, ObjectRef) for b in train_ds.blocks
    )
    if store_mode:
        cluster = session.cluster
        master = getattr(cluster, "master_address", None) or (
            cluster.master.address
        )
        namespace = cluster.namespace
        blocks = list(train_ds.blocks)
    else:
        master = namespace = None
        blocks = None

    def _shard_payloads(ds, cur_world: int) -> List[tuple]:
        if store_mode:
            return [(ds.shard_plan[r],) for r in range(cur_world)]
        # In-memory blocks: the driver slices each rank's shard tables
        # and ships only those rows.
        return [(ds.shard_tables(r),) for r in range(cur_world)]

    def _resharded(cur_world: int):
        if train_ds.num_shards == cur_world:
            return train_ds
        # Same blocks, new shard plan: the remaining epochs are laid out
        # over the surviving world (rank_nodes topology no longer maps
        # once hosts left, so it is dropped).
        return MLDataset(
            list(train_ds.blocks),
            num_shards=cur_world,
            shuffle=train_ds.shuffle,
            shuffle_seed=train_ds.shuffle_seed,
            store=getattr(train_ds, "_store", None),
        )

    cur_world = world_size
    restarts = 0
    prev_obs_steps = 0
    job = None
    job_world = None
    results = None
    # Accounting root: the whole supervised fit — every relaunch, every
    # incarnation's chip-seconds — bills to ONE job. An ambient job
    # (user-scoped) wins; gangs launched below inherit the scope.
    # Entered manually so the supervisor loop keeps its indentation.
    fit_job = _acct.ensure_job("fit-spmd", world_size=world_size)
    _scope = _acct.job_scope(fit_job)
    _scope.__enter__()

    def _preempt_gang() -> None:
        # Scheduler victim hook: SIGTERM the CURRENT incarnation's
        # ranks (closure reads the live ``job`` binding) so they drain
        # to an emergency checkpoint and surface PreemptionError.
        j = job
        if j is not None:
            try:
                j.request_preemption()
            except Exception:
                pass

    from raydp_tpu.control import get_arbiter as _get_arbiter

    arb = _get_arbiter()
    lease = None
    try:
        # Control-plane admission: the whole supervised fit holds ONE
        # gang lease across restarts. Blocks in the admission queue
        # when the cluster is full; raises ClusterBusyError on shed or
        # admission timeout; inert no-op when the arbiter is disabled.
        lease = arb.acquire(
            fit_job, slots=world_size, kind="gang", label="fit-spmd",
            on_preempt=_preempt_gang,
        )
        while True:
            if not lease.active:
                # Preempted last attempt: the drain released the lease
                # (freeing the slots to the higher-priority arrival) —
                # re-enter admission behind it and resume from the
                # emergency checkpoint once capacity returns. The
                # arbiter emits sched/resume on this grant.
                lease = arb.acquire(
                    fit_job, slots=cur_world, kind="gang",
                    label="fit-spmd", on_preempt=_preempt_gang,
                )
            lease.renew()
            ds = _resharded(cur_world)
            resume = _newest_checkpoint(checkpoint_dir)
            if restarts and resume is not None:
                # Replay bound check (advisory, heartbeat-lag accuracy):
                # steps the dead incarnation ran past the checkpoint we
                # are resuming from will be re-executed.
                ck = _ckpt_step(resume)
                if ck is not None and prev_obs_steps > ck:
                    _metrics.counter_add(
                        "replay/steps", prev_obs_steps - ck
                    )
            if job is None or job_world != cur_world:
                # New world size needs a new gang definition; same-size
                # relaunches reuse the job object so its telemetry view
                # (and rank metric continuity) survives the restart.
                job = create_spmd_job(
                    job_name="jax-fit-spmd",
                    world_size=cur_world,
                    num_procs_per_node=num_procs_per_node,
                    hosts=hosts,
                    env=env,
                    timeout=60.0,
                )
                job_world = cur_world

            def work(ctx, payload, resume_from=resume,
                     _store_mode=store_mode, _master=master,
                     _namespace=namespace, _blocks=blocks):
                import jax

                ctx.init_jax_distributed()

                import numpy as np

                from raydp_tpu.data.ml_dataset import MLDataset

                if _store_mode:
                    from raydp_tpu.train.torch_estimator import (
                        resolve_plan_tables,
                    )

                    tables = resolve_plan_tables(
                        _master, _namespace, _blocks, payload
                    )
                else:
                    tables = payload
                shard_ds = MLDataset(list(tables), num_shards=1)
                est = make_estimator()
                history = est.fit(shard_ds, resume_from=resume_from)
                out = {"rank": ctx.rank, "history": history}
                if ctx.rank == 0:
                    _, params = est.get_model()
                    out["params"] = jax.tree_util.tree_map(
                        np.asarray, params
                    )
                return out

            try:
                if restarts:
                    _flight.record(
                        "supervisor", "relaunch", attempt=restarts,
                        world_size=cur_world,
                        **({"resume": os.path.basename(resume)}
                           if resume else {}),
                    )
                    _events.emit(
                        "train/resume", attempt=restarts,
                        world_size=cur_world,
                        resume_step=_ckpt_step(resume),
                        **({"resume": os.path.basename(resume)}
                           if resume else {}),
                    )
                    logger.warning(
                        "fit_spmd: relaunching gang (restart %d/%d, "
                        "world %d%s)", restarts, max_restarts, cur_world,
                        f", resume {os.path.basename(resume)}" if resume
                        else ", from scratch",
                    )
                job.start()
                results = job.run(
                    work, timeout=timeout,
                    per_rank_args=_shard_payloads(ds, cur_world),
                )
                break
            except SPMDJobError as exc:
                err_text = str(exc)
                prev_obs_steps = _rank0_steps(job)
                preempted = "PreemptionError" in err_text
                if preempted:
                    _metrics.counter_add("preemptions/total")
                    _events.emit(
                        "preempt/request", attempt=restarts,
                        world_size=cur_world,
                    )
                    # Yield capacity NOW: the drain is durable (the
                    # emergency checkpoint committed before the rank
                    # raised), so the slots go to whoever the arbiter
                    # queued; this fit re-enters admission above.
                    lease.release(state="drained")
                _flight.record(
                    "supervisor", "gang_failed", attempt=restarts,
                    world_size=cur_world, preempted=preempted,
                    error=err_text[:200],
                )
                if restarts >= max_restarts:
                    raise SPMDJobError(
                        f"fit_spmd: restart budget exhausted "
                        f"({max_restarts} restarts); last failure: "
                        f"{err_text}"
                    ) from exc
                restarts += 1
                _metrics.counter_add("restarts/total")
                # Elastic shrink: a registration shortfall means hosts
                # are gone — continue on the ranks that showed up. The
                # job's last_registered is authoritative; the message
                # regex covers older/remote job objects.
                m = _REGISTERED_RE.search(err_text)
                if elastic and m:
                    got = (
                        job.last_registered
                        if getattr(job, "last_registered", None) is not None
                        else int(m.group(1))
                    )
                    if min_world_size <= got < cur_world:
                        logger.warning(
                            "fit_spmd: elastic resize %d -> %d ranks",
                            cur_world, got,
                        )
                        _events.emit(
                            "gang/resize", from_world=cur_world,
                            to_world=got, attempt=restarts,
                        )
                        cur_world = got
                        # Elastic shrink returns the departed hosts'
                        # slots to the queue.
                        lease.resize(cur_world)
                delay = restart_backoff_s * (2 ** (restarts - 1))
                delay *= 1.0 + random.uniform(0.0, 0.25)  # decorrelate
                logger.warning(
                    "fit_spmd: gang failed (%s); backing off %.1fs "
                    "before restart %d/%d",
                    err_text.splitlines()[0][:160], delay, restarts,
                    max_restarts,
                )
                time.sleep(delay)
            finally:
                # Tear down between attempts AND after success/budget
                # exhaustion; restartable job objects tolerate repeated
                # stop().
                try:
                    job.stop()
                except Exception:
                    pass
    finally:
        if job is not None:
            try:
                job.stop()
            except Exception:
                pass
        # Capacity must never leak: budget exhaustion, success, and
        # user exceptions all return the slots so queued tenants are
        # admitted instead of hanging (Lease.release is idempotent and
        # a no-op for a lease already drained by preemption).
        if lease is not None:
            try:
                lease.release()
            except Exception:
                pass
        _scope.__exit__(None, None, None)
    rank0 = next(r for r in results if r["rank"] == 0)
    return {
        "history": rank0["history"],
        "params": rank0.get("params"),
        "per_rank_history": [r["history"] for r in results],
        "restarts": restarts,
        "world_size": cur_world,
    }
