"""The trace -> metrics reduction on interval arithmetic, on a synthetic
trace with a collective, and on the trace recorded on the chip."""
import os

import pytest

from bench_tree import BENCH_DIR

MS = 1e6  # ns


def _trace(device_lines, host_events=()):
    planes = [
        {"name": f"/device:TPU:{n}", "lines": [
            {"name": ln, "events": ev} for ln, ev in lines.items()
        ]} for n, lines in enumerate(device_lines)
    ]
    planes.append({"name": "/host:CPU", "lines": [
        {"name": "python3", "events": list(host_events)}
    ]})
    return {"planes": planes}


def test_interval_arithmetic(bench_modules):
    tr = bench_modules["trace_reduce"]
    merged = tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert merged == [(0, 3), (5, 8)]
    assert tr.total(merged) == 6
    assert tr.clip(merged, 2, 6) == [(2, 3), (5, 6)]
    assert tr.subtract([(0, 10)], merged) == [(3, 5), (8, 10)]
    assert tr.subtract(merged, [(0, 10)]) == []
    assert tr.subtract([(0, 4), (6, 9)], [(1, 2), (3, 7)]) == [
        (0, 1), (2, 3), (7, 9)
    ]
    assert tr.overlap((2, 6), merged) == 2


def test_operation_names(bench_modules):
    tr = bench_modules["trace_reduce"]
    text = ("%fusion.1351 = (f32[3072,768]{1,0:T(8,128)S(1)}, f32[3072,768]"
            "{1,0}) fusion(%p0, %p1), kind=kLoop")
    assert tr.op_name(text) == "fusion.1351"
    assert tr.op_group(text) == "fusion f32[3072,768]"
    assert tr.op_group("%copy-done.13 = f32[30522,768]{1,0} copy-done(%x)") == (
        "copy-done f32[30522,768]"
    )
    assert tr.is_collective("%all-reduce-start.3 = f32[8]{0} all-reduce-start(%g)")
    assert tr.is_collective("%reduce-scatter.1 = f32[8]{0} reduce-scatter(%g)")
    assert not tr.is_collective("%reduce.7 = f32[] reduce(%g)")


def test_synthetic_trace_with_a_collective(bench_modules):
    tr = bench_modules["trace_reduce"]
    step = "jit_train_step(1)"
    ops0 = [
        ["%fusion.1 = f32[8,8]{1,0} fusion(%a)", 10 * MS, 20 * MS],
        ["%fusion.2 = f32[8,8]{1,0} fusion(%b)", 30 * MS, 10 * MS],
        # 6 ms of all-reduce, nothing else running: exposed.
        ["%all-reduce.1 = f32[8]{0} all-reduce(%g)", 40 * MS, 6 * MS],
        ["%fusion.1 = f32[8,8]{1,0} fusion(%a)", 60 * MS, 20 * MS],
        ["%convolution.3 = bf16[4,4]{1,0} convolution(%c)", 80 * MS, 10 * MS],
    ]
    async0 = [
        # In flight for 12 ms, 8 of them under convolution.3: 4 exposed.
        ["%all-reduce-start.2 = f32[8]{0} all-reduce-start(%g)", 82 * MS, 12 * MS],
        ["%copy-start.1 = f32[8]{0} copy-start(%x)", 0, 100 * MS],
    ]
    mods0 = [[step, 10 * MS, 36 * MS], [step, 60 * MS, 30 * MS],
             ["jit_add(2)", 96 * MS, 1 * MS]]
    ops1 = [["%fusion.1 = f32[8,8]{1,0} fusion(%a)", 10 * MS, 40 * MS]]
    host = [["bench/window", 0, 100 * MS],
            ["bench/epoch_boundary", 44 * MS, 14 * MS],
            ["bench/loader_wait", 50 * MS, 6 * MS],
            ["not/ours", 0, 100 * MS]]
    r = tr.reduce_trace(_trace(
        [{"XLA Ops": ops0, "Async XLA Ops": async0, "XLA Modules": mods0},
         {"XLA Ops": ops1}], host,
    ))
    assert r["chips"] == 2
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s_per_chip"] == pytest.approx([0.066, 0.040])
    assert r["busy_s"] == pytest.approx(0.053)
    assert r["steps"] == 2
    assert r["step_device_ms"] == pytest.approx(33.0)
    assert r["collective_s"] == pytest.approx(0.018)
    # 6 ms alone + the 4 ms of the async one after convolution.3 ends.
    assert r["exposed_collective_s"] == pytest.approx(0.010)
    assert r["longest_gap_s"] == pytest.approx(0.014)
    ops = dict(r["device_ops"])
    assert ops["fusion f32[8,8]"] == pytest.approx(0.050)
    assert ops["all-reduce f32[8]"] == pytest.approx(0.006)
    assert r["device_ops"][0][0] == "fusion f32[8,8]"
    gaps = dict(r["idle_gaps"])
    # The gap 46..60: 6 ms under loader_wait (the inner span), 6 more under
    # the boundary, 2 under neither; 0..10 and 90..100 have no span of ours.
    assert gaps["loader_wait"] == pytest.approx(0.006)
    assert gaps["epoch_boundary"] == pytest.approx(0.006)
    assert gaps["unattributed"] == pytest.approx(0.022)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - 0.066)


def test_no_device_plane_gives_nothing(bench_modules):
    tr = bench_modules["trace_reduce"]
    assert tr.reduce_trace({"planes": []}) == {}
    assert tr.reduce_trace(_trace([], [["bench/window", 0, 5]])) == {}
    assert tr.reduce_trace(_trace([{"XLA Modules": []}])) == {}


def test_recorded_trace_round_trip(bench_modules, tmp_path):
    tr = bench_modules["trace_reduce"]
    trace = _trace([{"XLA Ops": [["%fusion.9 = f32[2]{0} fusion(%a)" + "x" * 500,
                                  1.0, 2.0]]}])
    path = str(tmp_path / "t.json.gz")
    tr.save_recorded(trace, path, name_chars=40)
    back = tr.load_recorded(path)
    assert len(back["planes"][0]["lines"][0]["events"][0][0]) == 40
    assert tr.reduce_trace(back)["busy_s"] == pytest.approx(2e-9)


# Recorded on the TPU v5 lite in PR 22 (operation names cut to 56
# characters): two BERT-base steps of batch 128 across an epoch boundary on
# one chip, and the first two steps of an epoch at dp=4. The expected values
# are what the reduction gave when the traces were read by hand.
RECORDED = {
    "bert_base_one_chip": dict(
        chips=1, steps=2, window_s=0.200189767, busy_s=0.188571842,
        step_device_ms=94.28435, collective_s=0.0, exposed_collective_s=0.0,
        longest_gap_s=0.008958924, top_op="fusion f32[3072,768]",
        top_op_s=0.028225599, top_gap="epoch_boundary",
    ),
    "bert_base_dp4": dict(
        chips=4, steps=2, window_s=0.204133922, busy_s=0.18894236875,
        step_device_ms=94.472213, collective_s=0.007587619,
        exposed_collective_s=0.007587619, longest_gap_s=0.009878218,
        top_op="convert_reduce_fusion f32[128,128]", top_op_s=0.02649989,
        top_gap="infeed_put",
    ),
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_chip_trace(bench_modules, name):
    tr = bench_modules["trace_reduce"]
    want = RECORDED[name]
    r = tr.reduce_trace(tr.load_recorded(
        os.path.join(BENCH_DIR, "testdata", name + ".trace.json.gz")
    ))
    for key in ("chips", "steps"):
        assert r[key] == want[key]
    for key in ("window_s", "busy_s", "step_device_ms", "collective_s",
                "exposed_collective_s", "longest_gap_s"):
        assert r[key] == pytest.approx(want[key], rel=1e-6), key
    assert r["device_ops"][0][0] == want["top_op"]
    assert r["device_ops"][0][1] == pytest.approx(want["top_op_s"], rel=1e-6)
    assert r["idle_gaps"][0][0] == want["top_gap"]
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) <= 10
    # Idle is what is left of the window; every gap second has a label.
    idle = r["window_s"] - r["busy_s_per_chip"][0]
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(idle, rel=1e-6)
    assert 0 < r["busy_s"] < r["window_s"]
    assert len(r["busy_s_per_chip"]) == want["chips"]
    if want["chips"] > 1:
        # The gradient all-reduce runs as a blocking operation of the
        # ``XLA Ops`` line on this chip: all of it is exposed.
        assert r["exposed_collective_s"] == r["collective_s"] > 0
