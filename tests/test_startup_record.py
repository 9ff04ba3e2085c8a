"""The process's start-up record (PR 49): jax's compile events split by
kind, by program and by the span that paid (``utils/profiling.py``), the
spans that outlive the ring (``telemetry/spans.py``), and where the
estimator opens them and says start-up is over."""
import threading

import numpy as np
import pandas as pd
import pytest
from jax import monitoring

import raydp_tpu.dataframe as rdf
from raydp_tpu.data.ml_dataset import MLDataset
from raydp_tpu.models import MLP
from raydp_tpu.telemetry import recorder
from raydp_tpu.telemetry.spans import RETAINED_MAX, SpanRecorder
from raydp_tpu.train import JAXEstimator
from raydp_tpu.utils import profiling
from raydp_tpu.utils.profiling import (
    BACKEND_EVENT,
    CACHE_HIT_EVENT,
    CACHE_MISS_EVENT,
    CACHE_RETRIEVAL_EVENT,
    LOWER_EVENT,
    MAX_COMPILE_RECORDS,
    TRACE_EVENT,
    compile_records,
    metrics,
)

SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
KINDS = ("trace", "lower", "backend", "cache_load")


def counters():
    return dict(metrics.snapshot().get("counters", {}))


def grew(before, name):
    return counters().get(name, 0.0) - before.get(name, 0.0)


def phase(event, seconds, inside=(), name="jit(f)"):
    """What jax reports around one phase: a scalar where it starts, a
    duration where it ends; ``inside`` runs in between."""
    monitoring.record_scalar(event, 0.0, fun_name=name)
    for step in inside:
        step()
    monitoring.record_event_duration_secs(event, seconds, fun_name=name)


@pytest.fixture
def listener():
    assert profiling.install_compile_listener()
    profiling._compile_log.clear()
    # A trace whose program an earlier file of this worker found compiled
    # leaves its seconds on the thread for the next record: not this test's.
    here = profiling._compile_log._thread()
    here.open = []
    here.trace_s = here.lower_s = here.retrieval_s = 0.0
    here.cache = "uncached"
    return counters()


# ------------------------------------------------ the listener, by hand

def a_hit():
    phase(BACKEND_EVENT, 0.5, inside=[
        lambda: monitoring.record_event(CACHE_HIT_EVENT),
        lambda: monitoring.record_event_duration_secs(SAVED_EVENT, 50.0),
        lambda: monitoring.record_event_duration_secs(
            CACHE_RETRIEVAL_EVENT, 0.25
        ),
    ])
    return ({"cache": "hit", "backend_s": 0.5, "retrieval_s": 0.25,
             "trace_s": 0.0, "lower_s": 0.0},
            {"cache_load": 0.5, "compile/cache_hits": 1, "compile/count": 1})


def a_miss():
    phase(TRACE_EVENT, 1.0, name="f")
    phase(LOWER_EVENT, 0.5)
    phase(BACKEND_EVENT, 2.0, inside=[
        lambda: monitoring.record_event(CACHE_MISS_EVENT),
    ])
    return ({"cache": "miss", "trace_s": 1.0, "lower_s": 0.5,
             "backend_s": 2.0, "fun_name": "jit(f)"},
            {"trace": 1.0, "lower": 0.5, "backend": 2.0,
             "compile/cache_misses": 1, "compile/count": 1})


def an_uncached_program():
    # No start reported either: an event fed by hand, as
    # test_chip_bringup.py feeds one.
    monitoring.record_event_duration_secs(
        BACKEND_EVENT, 2.0, fun_name="jit(g)"
    )
    return ({"cache": "uncached", "backend_s": 2.0, "fun_name": "jit(g)"},
            {"backend": 2.0, "compile/count": 1})


def a_trace_inside_a_trace():
    phase(TRACE_EVENT, 3.0, inside=[lambda: phase(TRACE_EVENT, 1.0)])
    phase(BACKEND_EVENT, 0.25)
    return ({"trace_s": 3.0, "backend_s": 0.25},
            {"trace": 3.0, "backend": 0.25, "compile/count": 1})


def a_compile_inside_a_trace():
    # An eager op on constants while a function is traced: its seconds
    # are its own kind's, and the trace around it gives them up.
    phase(TRACE_EVENT, 3.0, inside=[
        lambda: phase(BACKEND_EVENT, 1.0, name="jit(iota)"),
    ])
    phase(BACKEND_EVENT, 0.5)
    return ({"trace_s": 2.0, "backend_s": 0.5},
            {"trace": 2.0, "backend": 1.5, "compile/count": 2})


def what_the_cache_saved():
    monitoring.record_event_duration_secs(SAVED_EVENT, 50.0)
    return None, {}


@pytest.mark.parametrize("case", [
    a_hit, a_miss, an_uncached_program, a_trace_inside_a_trace,
    a_compile_inside_a_trace, what_the_cache_saved,
], ids=lambda f: f.__name__)
def test_listener_splits_by_kind_and_program(listener, case):
    record, moved = case()
    spent = {k: moved.pop(k, 0.0) for k in KINDS}
    for kind, seconds in spent.items():
        assert grew(listener, f"compile/{kind}_seconds") == pytest.approx(
            seconds
        ), kind
    # compile/seconds is the four kinds' sum, every second once.
    assert grew(listener, "compile/seconds") == pytest.approx(
        sum(spent.values())
    )
    for name in ("compile/cache_hits", "compile/cache_misses",
                 "compile/count"):
        assert grew(listener, name) == moved.get(name, 0), name
    records = compile_records()
    if record is None:
        assert records == []
        return
    last = records[-1]
    assert {k: last[k] for k in record} == record
    assert last["owner"] is None and last["t_end"] > 0


def test_owner_is_the_open_span_of_the_building_thread(listener):
    here, there = threading.Event(), threading.Event()

    def other_thread():
        with recorder.span("train/init_state", seed=0):
            here.wait(10)
            monitoring.record_event_duration_secs(
                BACKEND_EVENT, 1.0, fun_name="jit(init)"
            )
        there.set()

    t = threading.Thread(target=other_thread)
    with recorder.span("train/epoch_end", epoch=0):
        t.start()
        with recorder.span("train/first_dispatch", label="eval_step"):
            here.set()
            assert there.wait(10)
            monitoring.record_event_duration_secs(
                BACKEND_EVENT, 1.0, fun_name="jit(eval_step)"
            )
        monitoring.record_event_duration_secs(
            BACKEND_EVENT, 1.0, fun_name="jit(add)"
        )
    t.join()
    assert [(r["fun_name"], r["owner"]) for r in compile_records()] == [
        ("jit(init)", "train/init_state"),
        ("jit(eval_step)", "train/first_dispatch"),
        ("jit(add)", "train/epoch_end"),
    ]


def test_records_are_bounded_and_the_rest_counted(listener):
    built = profiling.programs_built()
    for i in range(MAX_COMPILE_RECORDS + 1):
        monitoring.record_event_duration_secs(
            BACKEND_EVENT, 0.001, fun_name=f"jit(f{i})"
        )
    records = compile_records()
    assert len(records) == MAX_COMPILE_RECORDS
    assert records[-1]["fun_name"] == f"jit(f{MAX_COMPILE_RECORDS - 1})"
    assert grew(listener, "compile/records_dropped") == 1
    assert profiling.programs_built() == built + MAX_COMPILE_RECORDS + 1
    assert grew(listener, "compile/count") == MAX_COMPILE_RECORDS + 1


def test_retained_spans_outlive_the_ring():
    rec = SpanRecorder(capacity=64)
    before = counters()
    for i in range(RETAINED_MAX + 1):
        with rec.span("train/first_dispatch", label=f"p{i}"):
            pass
    for i in range(5000):
        with rec.span("train/step", step=i):
            pass
    with rec.span("train/fit", epochs=1):
        pass
    kept = rec.retained()
    assert len(kept) == RETAINED_MAX
    assert [s.attrs["label"] for s in kept] == [
        f"p{i}" for i in range(RETAINED_MAX)
    ]
    assert all(s.end_mono is not None for s in kept)
    # The ring holds the newest only, and a flush does not take the kept.
    assert len(rec.spans()) == 64
    total = RETAINED_MAX + 1 + 5000 + 1
    assert rec.dropped == total - 64 + 2  # the 257th, and the late fit
    assert grew(before, "spans/dropped") == rec.dropped
    rec.drain()
    assert rec.spans() == [] and len(rec.retained()) == RETAINED_MAX
    rec.clear()
    assert rec.retained() == []


# ------------------------------------------- a tiny fit on the CPU

@pytest.fixture(scope="module")
def two_fits():
    """One estimator, a fit of two epochs and then a second fit: the
    spans, records, gauge and usage after each."""
    rng = np.random.default_rng(0)
    frame = pd.DataFrame({
        "a": rng.standard_normal(256), "b": rng.standard_normal(256),
    })
    frame["y"] = 2 * frame.a - 3 * frame.b
    ds = MLDataset.from_df(rdf.from_pandas(frame, num_partitions=2), 1)
    est = JAXEstimator(
        model=MLP(hidden=(8,), out_dim=1), loss="mse", batch_size=64,
        feature_columns=["a", "b"], label_column="y", seed=3,
        epoch_mode="stream",
    )
    recorder.clear()
    profiling._compile_log.clear()
    usage0 = counters().get("usage/compile_seconds", 0.0)
    ready = []

    class AtEpochEnd:
        def on_epoch_end(self, epoch, _):
            ready.append(metrics.gauge_value("train/ready_seconds"))

        def on_train_end(self, _):
            pass

    est.callbacks.append(AtEpochEnd())
    metrics.gauge_set("train/ready_seconds", -1.0)
    est.fit(ds, num_epochs=2)
    ready.append(metrics.gauge_value("train/ready_seconds"))
    first = {
        "spans": recorder.spans(), "records": compile_records(),
        "usage": counters().get("usage/compile_seconds", 0.0) - usage0,
        "stamp": profiling.ready_stamp(),
    }
    est.fit(ds, num_epochs=1)
    ready.append(metrics.gauge_value("train/ready_seconds"))
    return {"first": first, "ready": ready, "spans": recorder.spans(),
            "retained": recorder.retained(), "records": compile_records()}


def named(spans, name, **attrs):
    return [s for s in spans if s.name == name
            and all(s.attrs.get(k) == v for k, v in attrs.items())]


def test_the_init_program_is_heard_under_its_span(two_fits):
    """The listener is installed before ``model.init`` is traced: the
    parent installed it where the steps are built, after this program."""
    first = two_fits["first"]
    (init,) = named(first["spans"], "train/init_state")
    assert init.attrs == {"seed": 3, "sharded": True}
    mine = [r for r in first["records"] if r["owner"] == "train/init_state"]
    assert mine and all(
        init.start_mono < r["t_end"] <= init.end_mono for r in mine
    )
    # jit(<lambda>) is the init program; it paid for both traces of
    # create(), the abstract one for the shardings too.
    program = max(mine, key=lambda r: r["backend_s"])
    assert program["fun_name"] == "jit(<lambda>)"
    assert program["trace_s"] > 0 and program["lower_s"] > 0


def test_first_dispatch_once_a_label(two_fits):
    for spans in (two_fits["spans"], two_fits["retained"]):
        assert [s.attrs for s in named(spans, "train/first_dispatch")] == [
            {"label": "train_step"}
        ]
        assert len(named(spans, "train/fit")) == 2
        assert len(named(spans, "train/build_steps")) == 1
        assert len(named(spans, "mesh/build")) == 1
    step = [r for r in two_fits["records"]
            if r["owner"] == "train/first_dispatch"]
    assert [r["fun_name"] for r in step] == ["jit(train_step)"]


def test_ready_is_the_end_of_the_last_epoch_that_paid(two_fits):
    in_epoch_0, in_epoch_1, after_fit_1, in_fit_2, after_fit_2 = (
        two_fits["ready"]
    )
    # Seen from a callback, inside the epoch's end: not yet moved.
    assert in_epoch_0 == -1.0
    # The first epoch built programs; the second and the next fit none.
    assert in_epoch_1 > 0
    assert in_epoch_1 == after_fit_1 == in_fit_2 == after_fit_2
    first = two_fits["first"]
    (end_0,) = named(first["spans"], "train/epoch_end", epoch=0)
    assert first["stamp"] == end_0.end_mono
    import raydp_tpu

    assert after_fit_1 == pytest.approx(
        end_0.end_mono - raydp_tpu.IMPORTED_AT
    )
    assert len(two_fits["records"]) == len(first["records"])


def test_step_0_still_ends_with_its_dispatch(two_fits):
    """``benchmark/jobs/fit_window.steady_epoch_s`` reads the end of step
    0's span: the first dispatch lies inside it and nothing follows."""
    spans = two_fits["first"]["spans"]
    (step_0,) = named(spans, "train/step", epoch=0, step=0)
    (dispatch,) = named(spans, "train/first_dispatch")
    assert step_0.attrs == {"epoch": 0, "step": 0}
    assert step_0.start_mono <= dispatch.start_mono
    assert dispatch.end_mono <= step_0.end_mono
    assert step_0.end_mono - dispatch.end_mono < 0.05
    assert dispatch.parent_id == step_0.span_id


def test_compile_usage_is_the_guards_spans(two_fits):
    first = two_fits["first"]
    guarded = named(first["spans"], "train/init_state") + named(
        first["spans"], "train/first_dispatch"
    )
    assert first["usage"] == pytest.approx(
        sum(s.duration_s for s in guarded)
    )
