"""Job kind ``fit_window``: one ``JAXEstimator`` trains on one staged data
set; the measured window is whole epochs of ONE ``fit`` call.

Set-up: the cluster (when the staging asks for one), the device, the data
from the seed (``generators/<name>.py``), the staging
(``stagings/<kind>.py``), a warm-up ``fit`` of one epoch (it compiles or
loads from the cache), then the measured ``fit`` of ``1 + n`` epochs whose
first epoch is warm-up too (it restarts the loaders). The benchmark's clock
is read at every epoch's end (a host fetch of the loss). An epoch's reading
is its samples over the seconds since the previous epoch's end, so the
waits between epochs and on the loader are inside it, and the run's
``train_samples_per_s`` is the median of the window's readings: 2 of 49
fit runs on the chip had ONE epoch stalled by 2.4-3.1 s on the host, which
moves the rate over the whole span by 6-9% and the median not at all. ``n``
is sized from the warm-up epoch (``steady_epoch_s``) to fill ``--seconds``.
"""
from __future__ import annotations

import math
import statistics
import time

# The warm-up epoch overstates a steady one: the first execution of a large
# program costs more than a later one (BERT-base on the chip: 3.38-3.40 s
# estimated against 3.03 s measured, DLRM 7.15 against 7.18). The epoch
# count allows for an estimate this much too long.
ESTIMATE_MAY_BE_OVER = 0.15


def steady_epoch_s(since: float, epoch_end: float, compiles):
    """What an epoch takes once its step program is ready, read off the one
    warm-up epoch: from the end of the program's own ``train/step`` span of
    the epoch's first step (that dispatch returns when the step is traced,
    compiled or loaded, and queued on the device) to the epoch's end (a
    host fetch of the loss), less the seconds the host spent on the small
    programs it met after that (the loss accumulation): in between the
    device runs the epoch's steps back to back. All ``perf_counter``
    readings. None when the program recorded no such span after ``since``."""
    from raydp_tpu.telemetry import recorder

    for span in recorder.spans():
        if (span.name == "train/step" and span.attrs.get("step") == 0
                and span.attrs.get("epoch") == 0
                and span.start_mono >= since and span.end_mono is not None
                and span.end_mono < epoch_end):
            ready = span.end_mono
            return epoch_end - ready - compiles.seconds_between(
                ready, epoch_end
            )
    return None


def epoch_readings(window_start: float, measured: list):
    """Seconds from the previous epoch's end (or the window's start) to
    each measured epoch's end, and the median over the epochs of samples
    per second of that interval."""
    ends = [window_start] + [r["t"] for r in measured]
    intervals = [b - a for a, b in zip(ends, ends[1:])]
    return intervals, statistics.median(
        r["samples"] / dt for r, dt in zip(measured, intervals)
    )


def run(ctx) -> dict:
    cell, traffic = ctx.cell, ctx.cell.traffic
    staging = traffic["staging"]
    if staging.get("workers"):
        ctx.start_cluster(staging["workers"])
    devices = ctx.require_devices()

    from raydp_tpu.parallel import MeshSpec
    from raydp_tpu.train import JAXEstimator
    from raydp_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    mesh = MeshSpec(**traffic.get("mesh", {}))
    batch = traffic["per_chip_batch"] * mesh.dp
    rows = batch * traffic["steps_per_epoch"]

    t0 = time.perf_counter()
    columns = cell.generate(traffic["data"], ctx.seed, rows=rows)
    dataset = cell.part("stagings", staging["kind"]).stage(
        columns, staging, ctx.seed
    )
    ctx.note("data_s", time.perf_counter() - t0)
    if dataset.total_rows != rows:
        raise RuntimeError(f"staged {dataset.total_rows} rows, not {rows}")

    clock = ctx.harness.epoch_clock()
    est = JAXEstimator(
        **cell.model.estimator_kwargs(cell.sizes, traffic, mesh),
        batch_size=batch, mesh=mesh, seed=ctx.seed,
        epoch_mode=traffic["epoch_mode"], callbacks=[clock],
    )
    t_fit = time.perf_counter()
    est.fit(dataset, num_epochs=1)
    warm = clock.records[0]
    epoch_s = steady_epoch_s(t_fit, warm["t"], ctx.compiles)
    if epoch_s is None:
        # No span of the first step to read (the tracing PR may rename
        # it): a second epoch gives the time.
        est.fit(dataset, num_epochs=1)
        epoch_s = clock.records[-1]["time_s"]
    ctx.note("warmup_epoch_s", warm["time_s"])
    ctx.note("steady_epoch_s", epoch_s)
    ctx.note("warmup_loss", warm["loss"])
    if est.effective_epoch_mode != traffic["epoch_mode"]:
        raise RuntimeError(f"fit ran {est.effective_epoch_mode!r} epochs")

    n = max(1, math.ceil(
        ctx.seconds / max((1.0 - ESTIMATE_MAY_BE_OVER) * epoch_s, 1e-3)
    ))
    prof = ctx.profiler
    if prof.enabled:
        dataset = ctx.harness.SpannedDataset(dataset, prof)
        ctx.harness.span_estimator(est, prof)
    trace_epochs = min(n, traffic.get("trace_epochs", 2))
    state = {}

    def on_epoch(record: dict) -> None:
        if "start" not in state:
            # End of the in-call warm-up epoch: the window opens.
            state["start"] = record
            state["compiles"] = ctx.compiles.count
            state["wait"] = ctx.harness.counter("ingest/wait_seconds")
            state["at"] = len(clock.records)
            ctx.window_opens()
            prof.start()
        elif prof.running and len(clock.records) - state["at"] >= trace_epochs:
            prof.stop(ctx.trace_reduce)

    clock.hooks.append(on_epoch)
    mark = len(clock.records)
    est.fit(dataset, num_epochs=1 + n)
    if prof.enabled:
        dataset.close_boundary()
    measured = clock.records[mark + 1:]
    start, end = state["start"], measured[-1]
    window_s = end["t"] - start["t"]
    samples = sum(r["samples"] for r in measured)
    intervals, rate = epoch_readings(start["t"], measured)

    facts = {
        "cell": cell, "chips": mesh.size, "per_chip_batch":
        traffic["per_chip_batch"], "batch": batch, "base_s": window_s,
        "samples": samples,
        "infeed_wait_s": ctx.harness.counter("ingest/wait_seconds")
        - state["wait"],
        "trace": prof.reduced, "devices": devices,
    }
    if prof.reduced.get("steps"):
        # From the trace's own clock: the profiler's start-up is not in it.
        facts["traced_samples_per_s"] = (
            prof.reduced["steps"] * batch / prof.reduced["window_s"]
        )
    losses = [r["loss"] for r in measured]
    return {
        "attempted": len(measured),
        "failed": sum(1 for v in losses if not math.isfinite(v)),
        "end_to_end": {"train_samples_per_s": rate},
        "compiles_in_window": ctx.compiles.count - state["compiles"],
        "facts": facts,
        "estimator": est,
        "notes": {"epochs": len(measured), "window_s": window_s,
                  "epoch_s": intervals,
                  "samples_per_s_over_the_span": samples / window_s,
                  "last_loss": losses[-1]},
    }
