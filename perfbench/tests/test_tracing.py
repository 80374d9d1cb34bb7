"""The trace reduction on a synthetic trace, and the device metrics it
feeds."""
import pytest

import harness
import tracing
import tiny
from harness import Run, TraceView


def test_busy_union_ops_and_gap_attribution():
    ms = 1e6
    programs = {"/device:TPU:0": [(0, 2 * ms), (1 * ms, 3 * ms),
                                  (6 * ms, 7 * ms), (9 * ms, 12 * ms)]}
    ops = {"/device:TPU:0": [(0, 2 * ms, "conv"), (2 * ms, 3 * ms, "add"),
                             (6 * ms, 7 * ms, "conv")]}
    spans = [(3 * ms, 6 * ms, "backend.advance"),
             (4 * ms, 5 * ms, "backend.launch"),
             (7 * ms, 9 * ms, "backend.advance")]
    r = tracing.reduce((0, 10 * ms), programs, ops, spans)
    assert r["window_s"] == pytest.approx(0.010)
    # [0,3) + [6,7) + [9,10): the last program is clipped to the window
    assert r["busy_s"] == pytest.approx(0.005)
    assert r["device_ops"][0] == ["conv", pytest.approx(0.003)]
    gaps = dict(r["idle_gaps"])
    # [3,6) is put down to the launch inside it, not to the waiting engine
    assert gaps["backend.launch"] == pytest.approx(0.003)
    assert gaps["backend.advance"] == pytest.approx(0.002)


def test_no_device_program_is_an_error():
    with pytest.raises(ValueError):
        tracing.reduce((0, 1), {}, {}, [])


def test_roofline_and_mfu_from_counts():
    costs = [{"flops": 2e9, "bytes": 1e6}, {"flops": 1e6, "bytes": 8.19e7}]
    peaks = {"flops_per_s": 2e12, "hbm_bytes_per_s": 8.19e11}
    stages = [(1, 0, 0, 0.0, 1.0), (1, 0, 1, 1.0, 2.0)]
    device = {"busy_s": 0.004, "window_s": 0.010}
    run = Run([], stages, costs, peaks, 0.010, 1.0, 1.0, 1,
              TraceView(device, stages))
    read = lambda n: harness.load_module("metrics", n).read(run)  # noqa: E731
    # least time 1 ms (FLOPs) + 0.1 ms (bytes) over 4 ms busy
    assert read("stage_roofline") == pytest.approx(27.5)
    assert read("mfu") == pytest.approx(100 * 2.001e9 / (0.010 * 2e12))
    assert read("device_idle_share") == pytest.approx(60.0)


def test_traced_run_records_stage_executions():
    res = tiny.run(trace=True)
    run = res["run"]
    assert res["correct"], res["checks"]
    # every stage of the window's jobs, from its launch to launch plus the
    # worker's execution time, inside the window
    assert {st for _, _, st, _, _ in run.stages} == {0, 1, 2, 3}
    assert all(0.0 <= t0 <= t1 for *_, t0, t1 in run.stages)
    gap = harness.load_module("metrics", "stage_gap_ms").read(run)
    assert gap is not None and gap >= 0.0
