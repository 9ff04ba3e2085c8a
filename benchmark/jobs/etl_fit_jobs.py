"""Job kind ``etl_fit_jobs``: the source paper's whole pipeline, job after
job. A job takes a fresh raw chunk, runs the configuration's ETL on the
cluster's workers, hands the result over (``MLDataset.from_df``) and trains
one epoch on it with the one estimator all jobs share; its wall time runs
from the first DataFrame call to the end of the epoch (a host fetch of the
loss). Chunks are drawn from the seed between jobs, outside any job's time.

What runs where inside a job: the DataFrame calls run the counting actions
at once and only PLAN the final stage; ``from_df`` hands over pending
futures, so that stage runs under ``fit``, while the loader waits for its
blocks. The job therefore reports three readings: the host clock around the
DataFrame calls (``etl_calls_s``), the engine's own stage records of the
job, final stage included (``stage_s``), and the loader's wait counter.

Set-up: the cluster, the device, one warm-up job (it compiles, and its ETL
output is compared with the plain pandas transform). Then jobs run back to
back until ``--seconds`` is over; a job that has started is finished.
"""
from __future__ import annotations

import math
import os
import statistics
import time


def run(ctx) -> dict:
    cell, traffic = ctx.cell, ctx.cell.traffic
    staging = traffic["staging"]
    ctx.start_cluster(staging["workers"])
    devices = ctx.require_devices()

    import pandas as pd

    import raydp_tpu.dataframe as rdf
    from raydp_tpu.data.ml_dataset import MLDataset
    from raydp_tpu.parallel import MeshSpec
    from raydp_tpu.telemetry.progress import stage_store
    from raydp_tpu.train import JAXEstimator

    etl = ctx.harness.load_module(
        os.path.join(cell.bench_dir, traffic["etl"]["module"] + ".py")
    )
    mesh = MeshSpec(**traffic.get("mesh", {}))
    batch = traffic["per_chip_batch"] * mesh.dp
    rows = traffic["rows_per_job"]
    clock = ctx.harness.epoch_clock()
    est = JAXEstimator(
        **cell.model.estimator_kwargs(cell.sizes, traffic, mesh),
        batch_size=batch, mesh=mesh, seed=ctx.seed,
        epoch_mode=traffic["epoch_mode"], callbacks=[clock],
    )
    prof = ctx.profiler
    spanned = {"estimator": False}

    def chunk(i: int):
        return pd.DataFrame(cell.generate(
            traffic["data"], ctx.seed * 100_003 + i, rows=rows
        ))

    def stage_totals(after_id: int) -> dict:
        """The engine's own per-stage records (``stage_store``: the
        driver-side wall of a stage, from its start to its last task)
        since ``after_id``, summed by plan-node label."""
        totals: dict = {}
        for sid in range(after_id + 1, stage_store.last_id() + 1):
            s = stage_store.get(sid)
            if s is None:
                continue
            t = totals.setdefault(
                s.op, {"stages": 0, "wall_s": 0.0, "rows_out": 0,
                       "bytes_out": 0},
            )
            t["stages"] += 1
            t["wall_s"] += s.wall_s
            t["rows_out"] += s.rows_out
            t["bytes_out"] += s.bytes_out
        return totals

    def job(raw):
        """Runs one job; returns what it measured and the ETL's output
        frame (the caller drops it, and with it the job's blocks)."""
        first_stage = stage_store.last_id()
        t0 = time.perf_counter()
        with prof.span("etl"):
            df = rdf.from_pandas(raw, num_partitions=staging["partitions"])
            out = etl.engine_transform(
                df, cell.sizes, traffic["etl"]["min_count"]
            )
        t1 = time.perf_counter()
        with prof.span("handoff"):
            dataset = MLDataset.from_df(
                out, num_shards=staging["shards"], shuffle=True,
                shuffle_seed=ctx.seed,
            )
        if prof.enabled:
            dataset = ctx.harness.SpannedDataset(dataset, prof)
            if est._train_step is not None and not spanned["estimator"]:
                ctx.harness.span_estimator(est, prof)
                spanned["estimator"] = True
        est.fit(dataset, num_epochs=1)
        if prof.enabled:
            dataset.close_boundary()
        t2 = time.perf_counter()
        stages = stage_totals(first_stage)
        return {"wall_s": t2 - t0, "etl_calls_s": t1 - t0, "fit_s": t2 - t1,
                "stage_s": sum(t["wall_s"] for t in stages.values()),
                "loss": clock.records[-1]["loss"],
                "samples": clock.records[-1]["samples"],
                "stages": stages}, out

    def brief(j: dict) -> dict:
        return {k: v for k, v in j.items() if k != "stages"}

    raw0 = chunk(0)
    warm, out0 = job(raw0)
    ctx.note("warmup_job", brief(warm))
    ctx.note("warmup_loss", warm["loss"])
    etl_ok, etl_detail = etl.compare(
        out0.to_pandas(),
        etl.reference_transform(raw0, cell.sizes, traffic["etl"]["min_count"]),
        cell.sizes,
    )
    ctx.note("etl_check", etl_detail)
    del raw0, out0

    trace_jobs = traffic.get("trace_jobs", 2)
    next_raw = chunk(1)
    compiles0 = ctx.compiles.count
    wait0 = ctx.harness.counter("ingest/wait_seconds")
    ctx.window_opens()
    prof.start()
    t_open = time.perf_counter()
    jobs = []
    while time.perf_counter() - t_open < ctx.seconds:
        jobs.append(job(next_raw)[0])
        if prof.running and len(jobs) >= trace_jobs:
            prof.stop(ctx.trace_reduce)
        next_raw = chunk(len(jobs) + 1)
    prof.stop(ctx.trace_reduce)
    window_s = time.perf_counter() - t_open

    walls = [j["wall_s"] for j in jobs]
    facts = {
        "cell": cell, "chips": mesh.size, "per_chip_batch":
        traffic["per_chip_batch"], "batch": batch,
        # Shares are taken over the jobs' own wall time: the chunks drawn
        # between jobs are in the window and in no job.
        "base_s": sum(walls),
        "etl_calls_s": sum(j["etl_calls_s"] for j in jobs),
        "etl_stage_s": sum(j["stage_s"] for j in jobs),
        "infeed_wait_s": ctx.harness.counter("ingest/wait_seconds") - wait0,
        "trace": prof.reduced, "devices": devices,
    }
    return {
        "attempted": len(jobs),
        "failed": sum(1 for j in jobs if not math.isfinite(j["loss"])),
        "end_to_end": {
            "pipeline_rows_per_s": rows / statistics.median(walls),
        },
        "compiles_in_window": ctx.compiles.count - compiles0,
        "checks": {"etl_matches_pandas": etl_ok},
        "facts": facts,
        "estimator": est,
        "notes": {"jobs": len(jobs), "window_s": window_s,
                  "job_wall_s": walls, "rows_per_job": rows,
                  "median_job": brief(sorted(
                      jobs, key=lambda j: j["wall_s"])[len(jobs) // 2]),
                  "etl_stages_of_last_job": jobs[-1]["stages"],
                  "last_loss": jobs[-1]["loss"]},
    }
