"""repro.api facade: builder validation, sim-vs-real backend parity,
Poisson arrival determinism, submit()-path admission, release clamping."""
import pytest

from repro.api import (HP, LP, DeviceModel, FaultPlan, PeriodicArrival,
                       PoissonArrival, ServerConfig, StageProfile,
                       SubmitHandle, TaskSpec, TraceArrival)


def make_spec(name, prio, stage_times, period_ms, n_sat=1.0):
    return TaskSpec(
        name=name, period_ms=period_ms, priority=prio,
        stages=[StageProfile(f"{name}/s{j}", t, n_sat=n_sat, mem_frac=0.0,
                             overhead_ms=0.0)
                for j, t in enumerate(stage_times)])


def ideal_device():
    """Device on which one stage per lane runs at exactly t_alone speed."""
    return DeviceModel(n_units=4.0, bubble=0.0, l2_pressure=0.0)


# ---------------------------------------------------------------- builder
def test_builder_validates_horizon_and_geometry():
    spec = make_spec("t", HP, [1.0], 10.0)
    with pytest.raises(ValueError, match="horizon"):
        ServerConfig.sim().task(spec).horizon_ms(0.0).build()
    with pytest.raises(ValueError, match="context"):
        ServerConfig.sim().task(spec).contexts(0).build()
    with pytest.raises(ValueError, match="oversubscription"):
        ServerConfig.sim().task(spec).oversubscribe(0.5).build()


def test_builder_rejects_noise_on_realtime_backend():
    spec = make_spec("t", HP, [1.0], 10.0)
    with pytest.raises(ValueError, match="sim backend"):
        ServerConfig.realtime().task(spec).noise(0.1).build()


def test_builder_rejects_arrival_for_unknown_task():
    spec = make_spec("t", HP, [1.0], 10.0)
    with pytest.raises(ValueError, match="unknown task"):
        (ServerConfig.sim().task(spec)
         .arrival("nope", PeriodicArrival()).build())


def test_server_runs_once():
    srv = (ServerConfig.sim().task(make_spec("t", HP, [1.0], 10.0))
           .contexts(1).streams(1).oversubscribe(1.0)
           .horizon_ms(50.0).build())
    srv.run()
    with pytest.raises(RuntimeError, match="already"):
        srv.run()


# ----------------------------------------------------- sim vs real parity
def _parity_config(kind):
    # stage times chosen so every completion is >= 10ms away from any other
    # event: wall-clock jitter cannot reorder the decision sequence
    specs = [make_spec("hp-a", HP, [40.0, 25.0], 250.0),
             make_spec("lp-b", LP, [55.0, 35.0], 300.0)]
    cfg = ServerConfig.sim() if kind == "sim" else ServerConfig.realtime()
    cfg = (cfg.tasks(specs)
           .contexts(2).streams(1).oversubscribe(1.0)
           .device(ideal_device())
           .horizon_ms(580.0).phase_offsets(False).seed(0)
           .record_decisions())
    if kind == "sim":
        cfg = cfg.noise(0.0)
    return cfg.build()


def test_sim_and_realtime_backends_make_identical_decisions():
    """The acceptance contract of the facade redesign: on a fixed-time task
    set both backends must produce the same admit/dispatch/finish sequence
    (payload-less stages run as sleeps on the real backend)."""
    sim = _parity_config("sim")
    m_sim = sim.run()
    real = _parity_config("realtime")
    m_real = real.run()
    assert sim.decisions == real.decisions
    assert len(sim.decisions) > 20          # releases actually happened
    assert m_sim.completed == m_real.completed
    assert m_sim.rejected == m_real.rejected


def test_realtime_payload_exception_raises_from_run():
    """A stage payload that raises (a program that fails to compile or
    runs out of device memory) surfaces from run() at its harvest; the
    run neither hangs nor returns as if the stage had merely vanished."""
    import time

    def broken(x):
        raise ValueError("payload failed")

    spec = TaskSpec(name="broken", period_ms=50.0, priority=HP,
                    stages=[StageProfile("broken/s0", 1.0, n_sat=1.0,
                                         mem_frac=0.0, payload=broken)])
    srv = (ServerConfig.realtime().tasks([spec]).contexts(1)
           .horizon_ms(60_000.0).realtime_io(input_hw=4).build())
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="broken/s0") as err:
        srv.run()
    assert isinstance(err.value.__cause__, ValueError)
    assert time.perf_counter() - t0 < 30.0     # well before the horizon
    assert srv.backend._pool._threads == []     # workers were stopped


# ------------------------------------------------------- poisson arrivals
def _poisson_run(seed):
    srv = (ServerConfig.sim()
           .task(make_spec("p0", HP, [5.0], 50.0))
           .task(make_spec("p1", LP, [5.0], 50.0))
           .contexts(2).streams(1).oversubscribe(1.0)
           .device(ideal_device())
           .open_loop(rate_jps=40.0, seed=seed)
           .horizon_ms(1000.0).seed(3).record_decisions()
           .build())
    m = srv.run()
    return tuple(srv.decisions), m.completed[HP], m.completed[LP]


def test_poisson_arrivals_deterministic_under_fixed_seed():
    a = _poisson_run(seed=7)
    b = _poisson_run(seed=7)
    assert a == b
    assert a[1] + a[2] > 0
    c = _poisson_run(seed=8)
    assert c != a                      # the seed actually drives the trace


# ----------------------------------------------------------- submit path
def test_submit_admission_and_rejection():
    """Eq. 12 through the facade: U_r = 1 - 0.7; a 0.5-utilization LP job
    must be rejected, a 0.1-utilization one admitted and completed."""
    srv = (ServerConfig.sim()
           .task(make_spec("hog", HP, [70.0], 100.0))
           .contexts(1).streams(1).oversubscribe(1.0)
           .device(DeviceModel(n_units=1.0, bubble=0.0, l2_pressure=0.0))
           .horizon_ms(500.0).phase_offsets(False).noise(0.0)
           .build())
    big = srv.submit(make_spec("big-lp", LP, [50.0], 100.0), at_ms=10.0)
    small = srv.submit(make_spec("small-lp", LP, [10.0], 100.0), at_ms=20.0)
    m = srv.run()
    assert big.status == SubmitHandle.REJECTED
    assert small.status == SubmitHandle.COMPLETED
    assert small.response_ms > 0
    assert m.rejected[LP] == 1


def test_drain_completes_trace_workload():
    """drain() runs until submitted work finishes instead of spinning to
    the horizon."""
    srv = (ServerConfig.sim()
           .contexts(1).streams(1).oversubscribe(1.0)
           .device(ideal_device())
           .horizon_ms(10_000.0).noise(0.0)
           .build())
    handles = [srv.submit(make_spec(f"j{i}", LP, [5.0], 100.0), at_ms=i * 2.0)
               for i in range(5)]
    srv.drain()
    assert all(h.status == SubmitHandle.COMPLETED for h in handles)
    assert srv.core.now_ms() < 10_000.0      # stopped at idle, not horizon


def test_snapshot_shape():
    srv = (ServerConfig.sim().task(make_spec("t", HP, [1.0], 10.0))
           .contexts(2).streams(1).oversubscribe(1.0)
           .horizon_ms(100.0).build())
    srv.run()
    snap = srv.snapshot()
    assert {"now_ms", "contexts", "queue_depth", "lanes_busy",
            "active_jobs", "completed", "migrations"} <= set(snap)
    assert len(snap["contexts"]) == 2


# ------------------------------------------------------- release clamping
def test_periodic_arrival_clamps_release_storms():
    """After a stall past whole periods the next release is clamped to now
    and the fully-passed periods are reported as skipped (the
    release-storm fix)."""
    proc = PeriodicArrival(period_ms=10.0)
    proc.start(make_spec("t", HP, [1.0], 10.0), None)
    # no stall: strict periodicity, nothing skipped
    assert proc.next_after(20.0, 20.0) == (30.0, 0)
    # loop stalled from t=20 to t=55: releases at 30, 40, 50 would have
    # burst; instead we fire at 55 and report 2 fully-passed periods
    nxt, skipped = proc.next_after(20.0, 55.0)
    assert nxt == 55.0
    assert skipped == 2


def test_trace_arrival_replays_recorded_times():
    srv = (ServerConfig.sim()
           .task(make_spec("t", HP, [2.0], 100.0),
                 arrival=TraceArrival([5.0, 17.0, 42.0]))
           .contexts(1).streams(1).oversubscribe(1.0)
           .device(ideal_device())
           .horizon_ms(200.0).noise(0.0)
           .build())
    m = srv.run()
    assert m.completed[HP] == 3
    assert m.response_ms[HP] == pytest.approx([2.0, 2.0, 2.0])
