"""``benchmark/program_trace.py``: the step split by model part and the
program's spans, on a synthetic profile, on a hand-encoded ``.xplane.pb``,
through ``run_cell`` on the CPU, and on the slices recorded on the chip."""
import importlib
import json
import os
import struct

import pytest

from bench_tree import BENCH_DIR

MS = 1e6  # ns
RULES = [
    [r"(^|/)part:(update|grad_norm)(/|$)", "update"],
    [r"/block_\d+/attn(/|$)", "attention"],
    [r"/block_\d+(/|$)", "mlp"],
]


@pytest.fixture(scope="module")
def pt(bench_modules):
    return importlib.import_module("program_trace")


def _profile(ops, device_lines, host_lines):
    """A profile in ``load_profile``'s form; events name their op."""
    names = [o[0] for o in ops]

    def events(evs):
        return [[names.index(n), s, d] for n, s, d in evs]

    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": ln, "events": events(evs)} for ln, evs in device_lines.items()
    ]}]
    planes.append({"name": "/host:CPU", "lines": [
        {"name": "python", "events": events(evs)} for evs in host_lines
    ]})
    return {"ops": [list(o) for o in ops], "planes": planes}


def _synthetic():
    step = "jit(train_step)/"
    ops = [
        ("jit_train_step(1)", "", ""),
        ("%fusion.1", step + "jvp(M)/encoder/block_0/attn/qkv/dot_general:",
         "convolution fusion"),
        ("%fusion.2", step + "transpose(jvp(M))/encoder/block_1/mlp_up/"
         "dot_general:", "convolution fusion"),
        ("%fusion.3", step + "part:update/add:", "loop fusion"),
        ("%all-reduce.1", "", "all-reduce"),
        ("%while.1", step + "jvp(M)/encoder/block_0/while:", "while"),
        ("bench/window", "", ""),
        ("train/epoch", "", ""), ("train/step", "", ""),
        ("infeed/put", "", ""), ("ingest/wait", "", ""),
        ("ingest/chunk", "", ""), ("train/loss_fetch", "", ""),
        ("df/action", "", ""), ("df/stage", "", ""),
    ]
    device = {
        "XLA Modules": [
            ["jit_train_step(1)", 10 * MS, 40 * MS],
            ["jit_train_step(1)", 60 * MS, 40 * MS],
        ],
        "XLA Ops": [
            # run 1: attention 10, mlp 10 (backward), update 6, and 4 ms
            # of all-reduce nothing names; 10 ms of the run are idle.
            ["%fusion.1", 10 * MS, 10 * MS],
            ["%fusion.2", 20 * MS, 10 * MS],
            ["%fusion.3", 30 * MS, 6 * MS],
            ["%all-reduce.1", 36 * MS, 4 * MS],
            # run 2: a while op (mlp by its scope) whose body's two ops
            # are events nested inside it: they take their own time out
            # of it. 20 ms while = 8 attention + 6 update + 6 its own.
            ["%while.1", 60 * MS, 20 * MS],
            ["%fusion.1", 62 * MS, 8 * MS],
            ["%fusion.3", 72 * MS, 6 * MS],
            ["%fusion.2", 80 * MS, 10 * MS],
        ],
    }
    main = [
        ["bench/window", 0, 120 * MS],
        ["df/action", 0, 8 * MS],
        ["df/stage", 2 * MS, 4 * MS],
        ["train/epoch", 8 * MS, 92 * MS],
        ["infeed/put", 8 * MS, 1 * MS],
        ["train/step", 9 * MS, 2 * MS],
        ["ingest/wait", 50 * MS, 10 * MS],
        ["train/step", 60 * MS, 1 * MS],
        ["train/loss_fetch", 100 * MS, 12 * MS],
    ]
    producer = [["ingest/chunk", 52 * MS, 6 * MS]]
    return _profile(ops, device, [main, producer])


def test_synthetic_profile_splits_the_step_and_the_idle_time(pt):
    summary, report = pt.reduce_profile(_synthetic(), RULES)
    assert summary["steps"] == 2
    parts = summary["parts_ms"]
    assert parts == pytest.approx({
        "attention": (10 + 8) / 2, "mlp": (10 + 6 + 10) / 2,
        "update": (6 + 6) / 2, "rest": 4 / 2,
    })
    # The parts partition the step's busy time: 30 + 30 ms in two runs.
    assert sum(parts.values()) == pytest.approx(30.0)
    assert summary["step_device_ms"] == pytest.approx(30.0)
    assert report["hlo_category_ms"]["all-reduce"] == pytest.approx(2.0)
    assert report["rest_scopes_ms"] == [["", pytest.approx(2.0)]]

    # Host shares over the 120 ms window, on the thread of the steps.
    assert summary["put_share"] == pytest.approx(100 * 1 / 120)
    assert summary["dispatch_share"] == pytest.approx(100 * 3 / 120)
    assert summary["driver_share"] == pytest.approx(100 * (8 - 4) / 120)

    # Idle: 0-10, 40-60, 90-120 = 60 ms. Innermost (shortest-lived) span
    # first: df/stage 2-6, df/action the rest of 0-8, infeed/put 8-9,
    # train/step 9-10; the producer's chunk 52-58 beats the wait it
    # overlaps (50-60), the epoch takes 40-50 and 90-100, the fetch
    # 100-112, and nothing covers 112-120.
    idle = dict(report["idle_gaps_s"])
    assert report["idle_s"] == pytest.approx(0.060)
    assert idle == pytest.approx({
        "df/stage": 0.004, "df/action": 0.004, "infeed/put": 0.001,
        "train/step": 0.001, "ingest/chunk": 0.006, "ingest/wait": 0.004,
        "train/epoch": 0.020, "train/loss_fetch": 0.012,
        "unattributed": 0.008,
    })
    assert summary["idle_unattributed_share"] == pytest.approx(100 * 8 / 120)
    assert report["gaps_over_50_ms"] == []


def test_long_gaps_name_the_spans_open_in_them(pt):
    profile = _synthetic()
    names = [o[0] for o in profile["ops"]]
    ops_line = profile["planes"][0]["lines"][1]["events"]
    # Stall the second run's last operation by 70 ms.
    ops_line[-1][1] += 70 * MS
    profile["planes"][1]["lines"][0]["events"][0][2] = 200 * MS
    _, report = pt.reduce_profile(profile, RULES)
    (gap,) = report["gaps_over_50_ms"]
    assert gap["seconds"] == pytest.approx(0.070)
    assert gap["open_spans"] == ["train/epoch", "train/loss_fetch"]
    assert names.index("train/epoch") >= 0


def test_no_program_span_and_no_device_plane_give_nothing(pt):
    # The parent's program: device operations, none of the program's spans.
    profile = _synthetic()
    host = profile["planes"][1]["lines"]
    host[:] = [{"name": "python", "events": host[0]["events"][:1]}]
    summary, _ = pt.reduce_profile(profile, RULES)
    assert summary["parts_ms"]["attention"] == pytest.approx(9.0)
    for key in ("put_share", "dispatch_share", "driver_share",
                "idle_unattributed_share"):
        assert key not in summary
    # No device plane: the spans' shares only.
    profile = _synthetic()
    del profile["planes"][0]
    summary, _ = pt.reduce_profile(profile, RULES)
    assert "parts_ms" not in summary
    assert "idle_unattributed_share" not in summary
    assert summary["dispatch_share"] == pytest.approx(2.5)
    assert pt.reduce_profile({"ops": [], "planes": []}, RULES)[0] == {
        "window_s": 0.0
    }


# ----------------------------------------------- the wire-format reader

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(number << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _xplane(name, lines, metadata, stat_names):
    """``metadata``: {id: (name, [(stat id, str | ("ref", id))])};
    ``lines``: [(name, timestamp_ns, [(metadata id, offset_ps, duration_ps)])]"""
    out = _field(2, name)
    for lname, t0, events in lines:
        line = _field(2, lname) + _field(3, t0)
        for mid, off, dur in events:
            # An event stat (field 4) the reader must step over.
            stat = _field(1, 1) + _field(3, 7)
            line += _field(4, _field(1, mid) + _field(2, off)
                           + _field(3, dur) + _field(4, stat))
        out += _field(3, line)
    for mid, (mname, stats) in metadata.items():
        meta = _field(1, mid) + _field(2, mname)
        for sid, value in stats:
            if isinstance(value, tuple):
                meta += _field(5, _field(1, sid) + _field(7, value[1]))
            else:
                meta += _field(5, _field(1, sid) + _field(5, value))
        out += _field(4, _field(1, mid) + _field(2, meta))
    for sid, sname in stat_names.items():
        out += _field(5, _field(1, sid)
                      + _field(2, _field(1, sid) + _field(2, sname)))
    return _field(1, out)


def test_load_profile_reads_the_scope_from_the_metadata_record(pt, tmp_path):
    stat_names = {1: "device_offset_ps", 2: "tf_op", 3: "hlo_category",
                  4: "loop fusion"}
    scope = "jit(train_step)/jvp(M)/encoder/block_0/attn/out/dot_general:"
    device = _xplane("/device:TPU:0", [
        ("XLA Modules", 1000, [(1, 0, 5_000_000)]),
        ("XLA Ops", 1000, [(2, 0, 2_000_000), (3, 2_000_000, 1_000_000),
                           (2, 3_000_000, 2_000_000)]),
        ("Steps", 1000, [(1, 0, 5_000_000)]),
    ], {
        1: ("jit_train_step(1)", []),
        2: ("%fusion.7 = " + "f32[8,8] " * 40, [(2, scope), (3, ("ref", 4))]),
        3: ("%copy-done.1 = f32[8]", []),
    }, stat_names)
    other_chip = _xplane("/device:TPU:1", [
        ("XLA Ops", 1000, [(2, 0, 1)]),
    ], {2: ("%fusion.7", [])}, {})
    host = _xplane("/host:CPU", [
        ("python", 0, [(1, 500_000, 6_000_000), (2, 900_000, 1_000_000),
                       (3, 1_000_000, 100_000), (4, 0, 1)]),
        ("pjrt-tpu-tasks/1", 0, [(4, 0, 1)]),
    ], {1: ("bench/window", []), 2: ("train/step", []),
        3: ("bench/dispatch", []), 4: ("PjitFunction(train_step)", [])}, {})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(device + other_chip + host)
    profile = pt.load_profile(str(path))
    assert [p["name"] for p in profile["planes"]] == ["/device:TPU:0",
                                                      "/host:CPU"]
    dev = {ln["name"]: ln["events"] for ln in profile["planes"][0]["lines"]}
    assert set(dev) == {"XLA Modules", "XLA Ops"}
    op, start, dur = dev["XLA Ops"][0]
    name, got_scope, category = profile["ops"][op]
    assert (start, dur) == (1000.0, 2000.0)
    assert name.startswith("%fusion.7") and len(name) == 96
    assert (got_scope, category) == (scope, "loop fusion")
    # Two runs of one operation share one entry; the other has no scope.
    assert dev["XLA Ops"][2][0] == op
    assert profile["ops"][dev["XLA Ops"][1][0]][1:] == ["", ""]
    # Of the host: the program's spans and the window, no bench/ span, no
    # event of the runtime; a line with none of them is dropped.
    (line,) = profile["planes"][1]["lines"]
    assert [profile["ops"][e[0]][0] for e in line["events"]] == [
        "bench/window", "train/step"
    ]
    summary, _ = pt.reduce_profile(profile, RULES)
    assert summary["steps"] == 1
    assert summary["parts_ms"]["attention"] == pytest.approx(0.004)
    assert summary["parts_ms"]["rest"] == pytest.approx(0.001)


def test_recorded_form_round_trip_keeps_the_first_steps(pt, tmp_path):
    profile = _synthetic()
    path = str(tmp_path / "x_parts.trace.json.gz")
    pt.save_recorded(profile, path, steps=1)
    again = pt.load_recorded(path)
    summary, report = pt.reduce_profile(again, RULES)
    # The first run alone, its span the window: 10-50 ms.
    assert summary["steps"] == 1
    assert summary["window_s"] == pytest.approx(0.040)
    assert summary["parts_ms"] == pytest.approx(
        {"attention": 10.0, "mlp": 10.0, "update": 6.0, "rest": 4.0}
    )
    assert dict(report["idle_gaps_s"]) == pytest.approx({"train/epoch": 0.010})


# -------------------------------------------------- through run_cell (CPU)

@pytest.mark.parametrize("cell", ["bert_tiny.fit", "dlrm_tiny.etl_fit"])
def test_cpu_traced_run_reports_the_span_metrics_only(bench_modules, tiny_tree,
                                                      cell):
    """No TPU plane on the CPU: the ``program_span`` metrics are read from
    the host plane of the run's own profile, the ``device_trace`` ones are
    left out."""
    run, harness = bench_modules["run"], bench_modules["harness"]
    loaded = harness.load_cell(tiny_tree, cell)
    out = run.run_cell(tiny_tree, cell, seed=5, seconds=1.0, trace=1,
                       platform="cpu")
    metrics = out["line"]["metrics"]
    mine = {m["name"]: m["source"] for m in loaded.per_layer()}
    spans = {"bert_tiny.fit": {"infeed.put_share", "step.dispatch_share"},
             "dlrm_tiny.etl_fit": {"etl.job_driver_share"}}[cell]
    assert spans <= set(mine)
    for name in spans:
        assert mine[name] == "program_span"
        assert 0.0 < metrics[name]["value"] < 100.0, name
    new_device = {n for n in mine if n.startswith("step.") and n.endswith("_ms")
                  and n not in ("step.device_ms", "step.job_device_ms")}
    new_device |= {"device.idle_unattributed_share",
                   "device.job_idle_unattributed_share"} & set(mine)
    assert new_device and not new_device & set(metrics)
    with open(os.path.join(tiny_tree, "benchmark_out",
                           cell + ".program_trace.json")) as f:
        report = json.load(f)
    assert "train/step" in report["step_loop_thread_s"]
    assert report["read_s"] >= 0.0
    assert out["line"]["correct"] is True, out["notes"]["checks"]


# ------------------------------------------------- recorded on the chip

# What PERF.md quotes for the recorded slices (first four steps of the
# --trace 1 runs of PR 24, seed 101): per step, in ms.
RECORDED = {
    "bert_base_fit_s128": ("bert_encoder_classifier", {
        "parts_ms": {"attention": 35.938, "embed": 0.872, "mlp": 51.208,
                     "rest": 5.577, "update": 0.684},
        "step_device_ms": 94.279,
    }),
    "dlrm_kaggle_fit_staged": ("dlrm_packed", {
        "parts_ms": {"embed": 9.814, "interaction": 0.366, "mlp": 0.083,
                     "rest": 1.768, "update": 15.723},
        "step_device_ms": 27.754,
    }),
    "dlrm_kaggle_etl_fit": ("dlrm_packed", {
        "parts_ms": {"embed": 9.849, "interaction": 0.366, "mlp": 0.083,
                     "rest": 1.769, "update": 15.723},
        "step_device_ms": 27.790,
    }),
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_chip_slice_reproduces_perf_md(pt, name):
    builder, want = RECORDED[name]
    profile = pt.load_recorded(
        os.path.join(BENCH_DIR, "testdata", name + "_parts.trace.json.gz")
    )
    with open(os.path.join(BENCH_DIR, "parts", builder + ".json")) as f:
        rules = json.load(f)
    summary, report = pt.reduce_profile(profile, rules)
    assert summary["steps"] == pt.RECORDED_STEPS
    parts = summary["parts_ms"]
    assert parts == pytest.approx(want["parts_ms"], abs=5e-3)
    assert sum(parts.values()) == pytest.approx(
        summary["step_device_ms"], rel=1e-9
    )
    assert summary["step_device_ms"] == pytest.approx(
        want["step_device_ms"], abs=5e-3
    )
    assert parts["rest"] < 0.10 * summary["step_device_ms"]
    # The scope survived the cut of the names.
    scopes = [o[1] for o in profile["ops"] if o[1]]
    assert any("part:update" in s for s in scopes)
