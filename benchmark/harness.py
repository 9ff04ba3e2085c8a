"""What every job of the benchmark shares: finding a cell's files by the
names in ``BENCHMARK.json``, the device check, the compile counter, the
clock callback, the profiler window, the reference check and the readers
of the per-layer metrics.

Nothing here knows a cell, a configuration or a metric by name: a cell is
``workloads/<cell>.json``, its configuration ``configs/<config>.json`` plus
the builder module that file names, its job ``jobs/<kind>.py``, its input
generator ``generators/<name>.py``, its staging ``stagings/<kind>.py``, and
each per-layer metric ``layers/<metric>.py`` (or ``layers/<metric>.txt``
naming the metric whose reading it repeats).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time
from typing import Any, Callable, Dict, List

NO_DEVICE_EXIT = 3
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[benchmark] {msg}", file=sys.stderr, flush=True)


def load_module(path: str):
    """Import one of the benchmark's files by path. The module is not
    registered in ``sys.modules``, so cloudpickle ships what it defines to
    the cluster's workers by value (they cannot import the benchmark)."""
    name = "bench_" + os.path.basename(path).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything its files say."""

    bench_dir: str       # the benchmark's directory under it
    bench: dict          # BENCHMARK.json
    name: str
    chips: int
    workload: dict       # workloads/<cell>.json
    config_name: str
    sizes: dict          # configs/<config>.json
    model: Any           # the builder module that file names

    @property
    def traffic(self) -> dict:
        return self.workload["traffic"]

    def end_to_end(self) -> List[dict]:
        return [m for m in self.bench["end_to_end"] if self._mine(m)]

    def per_layer(self) -> List[dict]:
        return [m for m in self.bench["per_layer"] if self._mine(m)]

    def _mine(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def part(self, kind: str, name: str):
        """The module ``<kind>/<name>.py`` of the benchmark's directory: a
        job, a generator, a staging, a reader."""
        return load_module(os.path.join(self.bench_dir, kind, name + ".py"))

    def generate(self, spec: dict, seed: int, **extra) -> dict:
        """The columns (name -> numpy array) a workload file's ``data``
        group describes, drawn from ``seed`` by ``generators/<name>.py``."""
        params = {k: v for k, v in spec.items() if k != "generator"}
        params.update(extra)
        return self.part("generators", spec["generator"]).generate(
            seed, self.sizes, **params
        )


def load_cell(root: str, name: str) -> Cell:
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"BENCHMARK.json has no workload {name!r}")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    sizes = read_json(os.path.join(root, config["file"]))
    bench_dir = os.path.dirname(os.path.dirname(
        os.path.join(root, config["file"])
    ))
    workload = read_json(
        os.path.join(bench_dir, "workloads", name + ".json")
    )
    model = load_module(
        os.path.join(bench_dir, "configs", sizes["builder"] + ".py")
    )
    return Cell(bench_dir, bench, name, entry["chips"], workload,
                entry["config"], sizes, model)


def peaks_for(cell: Cell, device_kind: str) -> dict:
    table = read_json(os.path.join(cell.bench_dir, "peaks.json"))
    if device_kind not in table:
        raise SystemExit(
            f"peaks.json has no entry for device kind {device_kind!r}; "
            "add its published peaks with their source"
        )
    return table[device_kind]


# ------------------------------------------------------------ devices

def require_devices(chips: int, platform: str):
    """This process takes the accelerator. Anything but ``chips`` or more
    devices of ``platform`` ends the run with no result."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as exc:
        log(f"no device: {exc}")
        raise SystemExit(NO_DEVICE_EXIT)
    if devices[0].platform != platform or len(devices) < chips:
        log(
            f"this cell needs {chips} {platform} device(s); jax reports "
            f"{len(devices)} of platform {devices[0].platform!r}"
        )
        raise SystemExit(NO_DEVICE_EXIT)
    return devices


def memory_peak_bytes(devices) -> int:
    """Peak HBM of the fullest chip. On this runtime ``peak_bytes_in_use``
    counts live arrays only; the temporaries of loaded programs sit under
    ``bytes_reserved`` (in_use + reserved + largest free block = limit; PR
    22 chip run), so the peak is the sum of the two peaks."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(
            stats.get("peak_bytes_in_use", 0)
            + stats.get("peak_bytes_reserved", 0)
        ))
    return peak


class CompileCounter:
    """Counts backend compiles through the benchmark's own
    ``jax.monitoring`` listeners. A program found in the persistent cache
    counts too (``hits`` says how many were): inside a measured window
    there must be none of either."""

    HIT_EVENT = "/jax/compilation_cache/cache_hits"
    RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.hits = 0
        self.retrieval_s = 0.0
        # [name, seconds, perf_counter at its end] per backend compile
        self.programs: List[list] = []
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_) -> None:
        if event == self.HIT_EVENT:
            self.hits += 1

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.programs.append([
                str(kwargs.get("fun_name", "?")), float(duration),
                time.perf_counter(),
            ])
        elif event == self.RETRIEVAL_EVENT:
            self.retrieval_s += float(duration)

    def seconds_between(self, t0: float, t1: float) -> float:
        """Compile or cache-load seconds of the programs that became ready
        between two ``perf_counter`` readings."""
        return sum(s for _, s, t in self.programs if t0 < t <= t1)

    def summary(self) -> dict:
        return {
            "programs": self.count,
            "compile_or_cache_load_s": sum(p[1] for p in self.programs),
            "persistent_cache_hits": self.hits,
            "cache_retrieval_s": self.retrieval_s,
            "slowest": [p[:2] for p in
                        sorted(self.programs, key=lambda p: -p[1])[:3]],
        }


# ------------------------------------------------------------ profiler

class Profiler:
    """One traced window per run. ``span(label)`` marks what the host is
    doing (``bench/<label>`` in the trace); outside a traced run both are
    free."""

    def __init__(self, enabled: bool, out_dir: str):
        self.enabled = enabled
        self.dir = os.path.join(out_dir, "trace")
        self.running = False
        self.reduced: dict = {}
        self.wall_s = 0.0
        self.trace: dict = {}
        self.cost_s: dict = {}
        self._window = None
        self._t0 = 0.0

    def span(self, label: str):
        import contextlib

        import jax

        if not self.running:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation("bench/" + label)

    def start(self) -> None:
        import shutil

        import jax

        if not self.enabled or self.running or self.reduced:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        # The benchmark's spans are enough; Python's call tracer would add
        # an event per function call to the loop it is watching.
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.running = True
        self._t0 = time.perf_counter()
        self._window = self.span("window")
        self._window.__enter__()

    def stop(self, trace_reduce) -> None:
        import jax

        if not self.running:
            return
        self._window.__exit__(None, None, None)
        self.wall_s = time.perf_counter() - self._t0
        self.running = False
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        t1 = time.perf_counter()
        self.trace = trace_reduce.load_xplane(self.dir)
        self.reduced = trace_reduce.reduce_trace(self.trace)
        self.cost_s = {"stop_trace": t1 - t0,
                       "load_and_reduce": time.perf_counter() - t1}


class OpenSpan:
    """A ``Profiler.span`` opened in one call and closed in another (the
    epoch boundary starts in the loader and ends in the next epoch)."""

    def __init__(self, profiler: Profiler):
        self._profiler = profiler
        self._cm = None

    def open(self, label: str) -> None:
        self.close()
        self._cm = self._profiler.span(label)
        self._cm.__enter__()

    def close(self) -> None:
        if self._cm is not None:
            self._cm.__exit__(None, None, None)
            self._cm = None


# ------------------------------------------------- wrappers (traced runs)

class SpannedDataset:
    """Stands in for an ``MLDataset`` in a traced run: the loaders it hands
    out mark the time the step loop waits for a batch (``loader_wait``) and
    the time between the last batch of an epoch and the first request of
    the next (``epoch_boundary``). Everything else is the dataset's."""

    def __init__(self, dataset, profiler: Profiler):
        self._dataset = dataset
        self._profiler = profiler
        self._boundary = OpenSpan(profiler)

    def __getattr__(self, name):
        return getattr(self._dataset, name)

    def to_jax(self, **kwargs):
        return _SpannedLoader(
            self._dataset.to_jax(**kwargs), self._profiler, self._boundary
        )

    def close_boundary(self) -> None:
        self._boundary.close()


class _SpannedLoader:
    def __init__(self, loader, profiler, boundary):
        self._loader, self._profiler = loader, profiler
        self._boundary = boundary

    def __getattr__(self, name):
        return getattr(self._loader, name)

    def __iter__(self):
        it = iter(self._loader)
        while True:
            with self._profiler.span("loader_wait"):
                self._boundary.close()
                try:
                    item = next(it)
                except StopIteration:
                    self._boundary.open("epoch_boundary")
                    return
            yield item


def span_estimator(est, profiler: Profiler) -> None:
    """Mark the two calls of the step loop that the benchmark can reach
    from outside: the sharded ``device_put`` of a batch (``infeed_put``)
    and the dispatch of the jitted step (``dispatch``). The program gets
    its own annotations in the tracing PR; these wrappers go then."""
    shard_batch = est._shard_batch

    def spanned_shard_batch(x, y):
        with profiler.span("infeed_put"):
            return shard_batch(x, y)

    est._shard_batch = spanned_shard_batch
    if est._train_step is not None:
        train_step = est._train_step

        def spanned_train_step(*args):
            with profiler.span("dispatch"):
                return train_step(*args)

        est._train_step = spanned_train_step


# -------------------------------------------------------- the clock

def epoch_clock():
    """A ``TrainingCallback`` that reads the benchmark's own clock at every
    epoch end (the epoch has just ended on a host fetch of its loss) and
    calls ``on_epoch(index_in_fit, record)`` hooks."""
    from raydp_tpu.train.estimator import TrainingCallback

    class EpochClock(TrainingCallback):
        def __init__(self):
            self.records: List[dict] = []
            self.hooks: List[Callable[[dict], None]] = []

        def on_epoch_end(self, epoch: int, metrics: Dict[str, float]):
            record = {
                "t": time.perf_counter(),
                "epoch": epoch,
                "loss": float(metrics["train_loss"]),
                "samples": int(metrics["samples"]),
                "time_s": float(metrics["time_s"]),
            }
            self.records.append(record)
            for hook in list(self.hooks):
                hook(record)

    return EpochClock()


def counter(name: str) -> float:
    """A counter of the program's metrics registry, 0 if never touched."""
    from raydp_tpu.utils.profiling import metrics

    return float(metrics.snapshot().get("counters", {}).get(name, 0.0))


# ------------------------------------------------------ reference check

def check_reference(cell: Cell, est, seed: int, flip: bool = False):
    """Program logits against the configuration's plain float32 reference
    on one seeded batch. ``flip`` negates the reference (the self-test that
    ``correct`` can come out false)."""
    import jax
    import numpy as np

    x = cell.model.check_batch(cell.sizes, cell.traffic, seed)
    got = np.asarray(est.predict(x), np.float32)
    sizes = cell.sizes
    reference = jax.jit(
        lambda params, batch: cell.model.reference_logits(params, batch, sizes)
    )
    # The state's parameters stay on the device: 2 GB of tables are not
    # worth a trip to the host and back.
    want = np.asarray(reference(est._state.params, x), np.float32)
    if flip:
        want = -want
    scale = float(np.max(np.abs(want)))
    err = float(np.max(np.abs(got - want))) / max(scale, 1e-12)
    ok = bool(np.isfinite(err) and err <= cell.model.TOLERANCE)
    return ok, {
        "rows": int(len(x)), "max_abs_err_over_max_abs_ref": err,
        "tolerance": cell.model.TOLERANCE,
    }


# ---------------------------------------------------- per-layer readers

def read_layers(cell: Cell, facts: dict) -> Dict[str, dict]:
    """``facts`` is what the job measured (counters, spans, the reduced
    trace, sizes); each of the cell's per-layer metrics has a reader
    ``layers/<metric>.py`` whose ``read(facts)`` returns the value or None
    when there is nothing to read, and the metric is then left out. A
    metric that is another's reading under a second name (it moves another
    end-to-end metric) has ``layers/<metric>.txt`` with that metric's name
    in place of a reader."""
    out = {}
    for metric in cell.per_layer():
        reader = metric["name"]
        alias = os.path.join(cell.bench_dir, "layers", reader + ".txt")
        if os.path.exists(alias):
            with open(alias) as f:
                reader = f.read().strip()
        value = cell.part("layers", reader).read(facts)
        if value is not None:
            out[metric["name"]] = {
                "value": float(value), "unit": metric["unit"],
            }
    return out
