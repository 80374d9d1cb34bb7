"""The program's trace as the benchmark reduces it: the proportional split
of device-idle gaps on a hand-made trace, ``et_over_device`` on a
synthetic one, and a whole tiny run with the tracer on."""
import math

import pytest

import harness
import program_trace as pt
import tiny

MS = 1e6   # ns


def test_idle_gaps_are_split_by_the_share_each_span_covers():
    profile = {
        "window": (0, 10 * MS), "anchor": None, "stages": [],
        "programs": {"/device:TPU:0": [(0, 2 * MS), (6 * MS, 7 * MS),
                                       (9 * MS, 12 * MS)]},
        "spans": {
            # engine thread: waits 3 of the gap [2,6), admits 0.5 of it
            "daris.wait": [(2 * MS, 5 * MS)],
            "daris.release": [(5 * MS, 5.5 * MS)],
            # two workers side by side over [2,6): covered whole, split
            # 2 : 3 by each name's cover
            "daris.issue": [(2 * MS, 4 * MS)],
            "daris.sync": [(3 * MS, 6 * MS)],
        }}
    split = pt.split_idle(profile)
    eng, wrk = split["engine"], split["worker"]
    assert eng["daris.wait"] == pytest.approx(0.003)
    assert eng["daris.release"] == pytest.approx(0.0005)
    # [5.5,6) and the whole gap [7,9) hold no engine span
    assert eng[pt.NO_SPAN] == pytest.approx(0.0025)
    assert wrk["daris.issue"] == pytest.approx(0.004 * 2 / 5)
    assert wrk["daris.sync"] == pytest.approx(0.004 * 3 / 5)
    assert wrk[pt.NO_SPAN] == pytest.approx(0.002)
    # each kind accounts for the whole idle time, 6 ms
    for acc in (eng, wrk):
        assert sum(acc.values()) == pytest.approx(0.006)


class _Records:
    def __init__(self, stages, steps):
        self._stages, self._steps = stages, steps

    def stage_records(self):
        return self._stages

    def step_records(self):
        return self._steps


def _rec(job, stage, launch, harvest, et_ms):
    # job, task, priority, stage, ready, launch, pickup, input_ready,
    # issued, synced, put, harvest, et_ms
    return (job, 0, 0, stage, launch - 0.1, launch, launch + 0.05,
            launch + 0.1, launch + 0.2, harvest - 0.1, harvest - 0.05,
            harvest, et_ms)


def test_et_over_device_on_a_synthetic_trace():
    # the anchor puts the segment [1, 11) ms of the trace at [100, 110)
    # ms of the server's clock
    profile = {
        "window": (1 * MS, 11 * MS), "anchor": (1 * MS, 100.0),
        "spans": {}, "programs": {},
        "stages": [(2 * MS, 2.5 * MS, "resnet18_s0"),
                   (3 * MS, 3.5 * MS, "resnet18_s1"),
                   (12 * MS, 13 * MS, "resnet18_s0")]}   # after the segment
    recs = [_rec(1, 0, 100.5, 102.0, 1.2), _rec(1, 1, 102.2, 103.5, 1.0),
            _rec(2, 0, 110.5, 113.0, 2.0)]               # after it too
    steps = [(99.0, 100.0, math.inf, False, 100.1, 100.2),
             (100.2, 101.8, 105.0, True, 101.9, 102.0)]
    view = pt.view(_Records(recs, steps), (100.0, 120.0), profile)
    assert view.segment["executions"] == 2
    assert view.segment["et_ms"] == pytest.approx(2.2)
    assert view.segment["ms"] == pytest.approx((100.0, 110.0))
    assert pt.stage_device_s(profile) == {
        "resnet18_s0": [1, pytest.approx(0.0005)],
        "resnet18_s1": [1, pytest.approx(0.0005)]}
    assert view.segment["device_runs"] == 2

    class Run:
        program = view
    # the window's three executions average 1.4 ms; a stage program's
    # run in the segment takes 0.5 ms on the device
    assert pt.et_over_device(Run) == pytest.approx(1.4 / 0.5)
    # waits [99,100) and [100.2,101.8): 1.6 ms of the 20 ms window
    assert pt.engine_busy_share(Run) == pytest.approx(100 * (1 - 1.6 / 20))
    assert pt.handoff_ms(Run) == pytest.approx(0.1)
    # a wait capped at 102.5 ms that woke at 103.0 while job 1's stage 1
    # sat in its device wait (issued 102.4, synced 103.4)
    late = (102.0, 103.0, 102.5, False, 103.05, 103.1)
    assert pt.stalls(_Records(recs, steps + [late]), (100.0, 110.0),
                     min_ms=0.4) == [
        {"at_ms": pytest.approx(102.5), "kind": "late wake",
         "ms": pytest.approx(0.5),
         "in_flight": {"sync": [1, pytest.approx(1.0)]}}]
    # the profiler's start and stop after the window are not stalls
    assert pt.stalls(_Records(recs, steps + [late]), (100.0, 102.0),
                     min_ms=0.4) == []
    assert pt.worker_host_ms(Run) == pytest.approx(0.15)
    assert pt.queue_wait_p95_ms(Run) == pytest.approx(0.1)


def test_untraced_run_reads_nothing():
    class Run:
        pass
    for _, read in pt.READERS.values():
        assert read(Run) is None


def test_tiny_run_with_the_tracer_on():
    with pt.switched_on() as held:
        res = tiny.run(trace=True)
    assert res["correct"], res["checks"]
    run = res["run"]
    tr = tiny.load()
    w0 = tr["warmup_s"] * 1000.0
    run.program = pt.view(held["tracer"], (w0, w0 + 1000.0),
                          held["profile"])
    recs = run.program.stages
    # one record per stage execution of the window: the harness's hooks
    # time the same executions from the engine thread
    hooks = {(j, st) for j, _, st, _, _ in run.stages}
    mine = {(r.job, r.stage) for r in recs}
    assert len(hooks ^ mine) <= 2 and len(recs) > 20
    for r in recs:
        t = (r.ready, r.launch, r.pickup, r.input_ready, r.issued, r.synced,
             r.put, r.harvest)
        assert all(a <= b for a, b in zip(t, t[1:])), r
    # the program's spans reached the profile, from both thread kinds
    names = set(held["profile"]["spans"])
    assert {"daris.wait", "daris.dispatch", "daris.harvest"} <= names
    assert {"daris.upload", "daris.issue", "daris.sync"} <= names
    for name in ("engine_busy_share", "queue_wait_p95_ms", "handoff_ms",
                 "worker_host_ms"):
        v = pt.READERS[name][1](run)
        assert v is not None and math.isfinite(v) and v >= 0.0, name
    # no device programs in a CPU profile: nothing to divide by
    assert pt.et_over_device(run) is None
    # a stage's gap to its job's next stage is the two hand-offs plus the
    # next stage's wait in the queue, plus what the stamps around them
    # take (the pickup and put stamps bracket the worker's et_ms, and the
    # next stage is enqueued just after the harvest stamp)
    by = {(r.job, r.stage): r for r in recs}
    pairs = [(r, by[(r.job, r.stage + 1)]) for r in recs
             if (r.job, r.stage + 1) in by]
    assert pairs
    for a, b in pairs:
        gap = b.launch - (a.launch + a.et_ms)
        parts = (a.pickup - a.launch) + (a.harvest - a.put) \
            + (b.launch - b.ready)
        assert gap >= parts - 1e-9
    mean = sum(b.launch - (a.launch + a.et_ms) for a, b in pairs) / len(pairs)
    sp = pt.splits(run.program)
    assert sp["stage_gap"]["total"] == pytest.approx(mean)
    assert mean == pytest.approx(
        sp["stage_gap"]["pickup"] + sp["stage_gap"]["completion_queue"]
        + sp["stage_gap"]["queue_wait"], rel=0.1, abs=0.05)
    assert sp["et"]["et_ms"] == pytest.approx(
        sum(sp["et"][k] for k in ("input", "issue", "sync", "put")),
        abs=0.05)
    gap = harness.load_module("metrics", "stage_gap_ms").read(run)
    assert gap == pytest.approx(mean, rel=0.1, abs=0.05)
