"""The in-program tracer (repro.runtime.trace): nothing built or kept
when it is off, one ordered record per stage execution when it is on,
admission and engine-loop counters, profiler spans on the trace's clock,
stable names for the stage programs, and a decision log that costs
nothing when it is not kept."""
from __future__ import annotations

import glob
import math
import re

import jax
import jax.numpy as jnp
import pytest

from repro.api import (HP, LP, DeviceModel, ManualArrival, ServerConfig,
                       StageProfile, TaskSpec)
from repro.runtime.engine_core import EngineCore
from repro.runtime.trace import STAGE_FIELDS, STEP_FIELDS


@jax.jit
def _stage(x):
    return jnp.tanh(x) * 1.5


def _spec(name, prio, n_stages=3, period_ms=20.0, payload=_stage,
          t_ms=1.0):
    return TaskSpec(name=name, period_ms=period_ms, priority=prio,
                    stages=[StageProfile(f"{name}/s{j}", t_ms, n_sat=1.0,
                                         mem_frac=0.0, payload=payload)
                            for j in range(n_stages)])


def _realtime(trace, horizon=400.0, manual=False, decisions=False):
    cfg = (ServerConfig.realtime()
           .contexts(2).streams(1).oversubscribe(2.0)
           .device(DeviceModel(n_units=2.0))
           .horizon_ms(horizon).phase_offsets(False)
           .realtime_io(input_hw=4))
    for i, prio in enumerate((HP, LP, LP)):
        cfg.task(_spec(f"t{i}", prio),
                 arrival=ManualArrival() if manual else None)
    if decisions:
        cfg.record_decisions()
    return (cfg.trace() if trace else cfg).build()


class _Refused:
    def __init__(self, *a, **kw):
        raise AssertionError("a TraceAnnotation was built")


def test_tracing_off_builds_no_annotation_and_keeps_no_record(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Refused)
    srv = _realtime(trace=False)
    assert srv.tracer is None and srv.backend._tracer is None
    m = srv.run()
    assert sum(m.completed.values()) > 0
    assert srv.tracer is None and "trace" not in srv.snapshot()
    # records on, spans off: still no annotation is built
    srv = _realtime(trace=True)
    srv.run()
    assert srv.tracer.n_stages > 0 and not srv.tracer.annotating


def test_traced_run_records_every_stage_execution_in_order():
    srv = _realtime(trace=True, decisions=True)
    srv.run()
    tr = srv.tracer
    recs = tr.stage_records()
    finishes = sum(d.startswith("finish ") for d in srv.decisions)
    assert len(recs) == finishes > 10
    first, last = STAGE_FIELDS.index("ready"), STAGE_FIELDS.index("harvest")
    for r in recs:
        assert len(r) == len(STAGE_FIELDS)
        instants = r[first:last + 1]
        assert all(a <= b for a, b in zip(instants, instants[1:])), r
        # the worker's own clock: pickup to put brackets its et_ms
        pickup, put = r[STAGE_FIELDS.index("pickup")], \
            r[STAGE_FIELDS.index("put")]
        assert 0.0 < r[-1] <= put - pickup + 1e-6
    assert {r[3] for r in recs} == {0, 1, 2}
    steps = tr.step_records()
    assert steps and all(len(s) == len(STEP_FIELDS) for s in steps)
    eng = tr.counters()["engine"]
    assert eng["wait_ms"] > 0.0 and eng["step_ms"] > 0.0
    assert eng["wait_ms"] + eng["step_ms"] <= srv.core.now_ms()
    assert 0 < len(eng["longest_steps"]) <= 8
    assert len(eng["longest_late_wakes"]) <= 8
    worst = eng["longest_steps"][0]
    assert worst["step_ms"] >= eng["longest_steps"][-1]["step_ms"]
    assert worst["step_ms"] == pytest.approx(
        worst["release_ms"] + worst["harvest_ms"] + worst["dispatch_ms"])


def test_admission_counters_show_refusal_runs():
    sc = (ServerConfig.sim().horizon_ms(2000.0).contexts(1).streams(1)
          .oversubscribe(1.0).device(DeviceModel(n_units=1.0))
          .phase_offsets(False).noise(0.0).seed(0).record_decisions()
          .trace())
    sc.task(_spec("hp", HP, 1, 10.0, None, t_ms=4.0))
    sc.task(_spec("lp", LP, 2, 10.0, None, t_ms=4.0))
    srv = sc.build()
    srv.run()
    c = srv.snapshot()["trace"]
    d = srv.decisions
    assert c["refused"]["lp"] == sum(x.startswith("reject lp") for x in d)
    assert c["refused"]["lp"] > 0
    assert c["admitted"]["hp"] + c["admitted"]["lp"] == sum(
        x.startswith(("admit ", "batch ")) for x in d)
    lp = c["refusals"]["lp"]
    assert 1 <= lp["run"] <= lp["longest_run"] <= c["refused"]["lp"]
    assert lp["mret_ms"] == pytest.approx(
        srv.task_named("lp").mret.task_mret())


def test_profile_holds_program_spans_on_the_records_clock(tmp_path):
    # compiled before serving: a first call's compile time in et_ms would
    # raise an LP task's MRET until Eq. 12 refused its next release
    _stage(jnp.zeros((1, 4, 4, 3), jnp.float32)).block_until_ready()
    srv = _realtime(trace=True, horizon=1e6, manual=True)
    names = ("t0", "t1", "t2")
    for k in range(6):
        srv.request(names[k % 3], 5.0 * k)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    srv.begin_serving()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    srv.tracer.annotate(True)
    while not srv.serving_idle():
        srv.pump(srv.core.now_ms() + 2.0)
    srv.tracer.annotate(False)
    jax.profiler.stop_trace()
    srv.end_serving()

    from jax.profiler import ProfileData
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    events = [e for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith("daris.")]
    by_name = {}
    for e in events:
        by_name.setdefault(e.name, []).append((e.start_ns, dict(e.stats)))
    assert {"daris.clock", "daris.release", "daris.dispatch",
            "daris.harvest", "daris.wait", "daris.upload", "daris.issue",
            "daris.sync"} <= set(by_name)
    for name in ("daris.dispatch", "daris.harvest", "daris.upload",
                 "daris.issue", "daris.sync"):
        assert all({"job", "stage"} <= set(st) for _, st in by_name[name])
    assert all({"task", "job", "admitted"} <= set(st)
               for _, st in by_name["daris.release"])
    (anchor_ns, clock), = by_name["daris.clock"]
    # a record's launch, placed on the trace's clock by the anchor, lands
    # at its own dispatch span
    dispatch = {(st["job"], st["stage"]): ns
                for ns, st in by_name["daris.dispatch"]}
    recs = srv.tracer.stage_records()
    assert len(recs) == len(dispatch) == 18
    for r in recs:
        at = anchor_ns + (r[5] - clock["now_ms"]) * 1e6
        assert abs(dispatch[(r[0], r[3])] - at) < 2e6


@pytest.mark.parametrize("model", ["resnet18", "resnet50", "unet",
                                   "inceptionv3"])
def test_stage_programs_carry_stable_names(model):
    from repro.models.cnn import BUILDERS
    kw = {"width": 8}
    if model != "unet":
        kw["n_classes"] = 10
    built = []      # built abstractly: shapes only, nothing computed
    params = jax.eval_shape(
        lambda: built.append(BUILDERS[model](**kw)) or built[0].params)
    x = jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32)
    names = []
    for st in built[0].stages:
        text = jax.jit(st).lower(params, x).as_text()
        names.append(re.search(r"module @(\w+)", text).group(1))
        x = jax.eval_shape(st, params, x)
    assert names == [f"jit_{model}_s{j}" for j in range(4)]


def test_decision_log_strings_are_not_built_when_off(monkeypatch):
    def refuse(self, msg):
        raise AssertionError(f"decision logged while off: {msg}")
    monkeypatch.setattr(EngineCore, "_log", refuse)
    sc = (ServerConfig.sim().horizon_ms(500.0).contexts(1).streams(1)
          .oversubscribe(1.0).device(DeviceModel(n_units=1.0))
          .phase_offsets(False).noise(0.0))
    sc.task(_spec("hp", HP, 1, 10.0, None, t_ms=4.0))
    sc.task(_spec("lp", LP, 2, 10.0, None, t_ms=4.0))
    srv = sc.build()
    m = srv.run()
    assert srv.decisions is None
    assert m.completed[HP] > 0 and m.rejected[LP] > 0
    assert not math.isnan(m.summary()["jps_hp"])
