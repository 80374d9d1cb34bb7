"""Serving front-end: SubmitHandle lifecycle, durable journal semantics,
daemon socket round-trips, crash-restart zero-lost durability, and the
journal -> TraceArrival bit-identical replay contract."""
from __future__ import annotations

import hashlib
import json
import threading
import time

import pytest

from repro.api import (HP, LP, DeviceModel, ManualArrival, ServerConfig,
                       StageProfile, SubmitHandle, TaskSpec)
from repro.serve import (DarisClient, Journal, ServeDaemon, audit_zero_lost,
                         build_server, read_journal, to_trace_arrivals,
                         unfinished_submits)
from repro.serve.journal import replay_plan, submit_records


def make_spec(name, prio, stage_times, period_ms, n_sat=1.0):
    return TaskSpec(
        name=name, period_ms=period_ms, priority=prio,
        stages=[StageProfile(f"{name}/s{j}", t, n_sat=n_sat, mem_frac=0.0,
                             overhead_ms=0.0)
                for j, t in enumerate(stage_times)])


def ideal_device():
    return DeviceModel(n_units=4.0, bubble=0.0, l2_pressure=0.0)


def serving_server(specs, *, contexts=1):
    cfg = ServerConfig.sim()
    for s in specs:
        cfg.task(s, arrival=ManualArrival())
    srv = (cfg.contexts(contexts).streams(1)
           .oversubscribe(float(contexts)).device(ideal_device())
           .horizon_ms(1e6).phase_offsets(False).noise(0.0).seed(0)
           .build())
    srv.begin_serving()
    return srv


# --------------------------------------------------- SubmitHandle surface
def test_handle_lifecycle_queued_running_completed():
    srv = serving_server([make_spec("hog", HP, [30.0], 1000.0),
                          make_spec("lp", LP, [10.0], 1000.0)])
    srv.request("hog", at_ms=0.0)
    h = srv.request("lp", at_ms=5.0)
    assert h.status == SubmitHandle.PENDING      # release not pumped yet
    assert not h.done
    srv.pump(5.0)
    assert h.status == SubmitHandle.QUEUED       # lane pinned by the hog
    assert h.status == SubmitHandle.ADMITTED     # back-compat alias
    srv.pump(30.0)
    assert h.status == SubmitHandle.RUNNING
    srv.pump(45.0)
    assert h.status == SubmitHandle.COMPLETED and h.done
    assert h.response_ms == pytest.approx(35.0)  # 5 -> 40
    r = h.result()
    assert r["status"] == "completed"
    assert r["task"] == "lp" and r["release_ms"] == 5.0
    assert srv.serving_idle()
    srv.end_serving()


def test_handle_rejected_on_admission_failure():
    srv = serving_server([make_spec("lp", LP, [900.0], 1000.0)])
    h1 = srv.request("lp", at_ms=0.0)
    h2 = srv.request("lp", at_ms=1.0)
    srv.pump(1.0)
    assert h1.status in (SubmitHandle.QUEUED, SubmitHandle.RUNNING)
    assert h2.status == SubmitHandle.REJECTED and h2.done
    m = srv.end_serving()
    assert m.rejected[LP] == 1


def test_handle_missed_when_deadline_blown():
    srv = serving_server([make_spec("hp", HP, [30.0], 20.0)])
    h = srv.request("hp", at_ms=0.0)
    srv.pump(0.0)
    m = srv.end_serving()
    assert h.status == SubmitHandle.MISSED and h.done
    assert h.response_ms == pytest.approx(30.0)
    assert m.missed[HP] == 1 and m.completed[HP] == 1


def test_per_tenant_accounting():
    srv = serving_server([make_spec("lp", LP, [10.0], 1000.0)])
    srv.request("lp", at_ms=0.0, tenant="teamA")
    srv.request("lp", at_ms=40.0, tenant="teamA")
    srv.request("lp", at_ms=80.0, tenant="teamB")
    m = srv.end_serving()
    assert set(m.per_tenant) == {"teamA", "teamB"}
    assert m.per_tenant["teamA"]["submitted"] == 2
    assert m.per_tenant["teamA"]["completed"] == 2
    assert m.per_tenant["teamB"]["submitted"] == 1
    assert m.per_tenant["teamB"]["resp"]["mean"] == pytest.approx(10.0)
    assert "per_tenant" in m.summary()


def test_serving_metrics_horizon_is_elapsed_time():
    srv = serving_server([make_spec("lp", LP, [10.0], 1000.0)])
    srv.request("lp", at_ms=5.0)
    m = srv.end_serving()
    assert m.horizon_ms == pytest.approx(15.0)   # not the 1e6 guard


# -------------------------------------------------------- journal basics
def test_journal_append_and_read(tmp_path):
    p = tmp_path / "j.jsonl"
    j = Journal(p)
    j.append({"rec": "submit", "seq": 0, "task": "t", "at_ms": 1.0})
    j.append({"rec": "done", "seq": 0, "status": "completed",
              "response_ms": 9.5})
    j.close()
    recs = read_journal(p)
    assert recs[0]["rec"] == "meta" and recs[0]["version"] == 1
    assert [r["rec"] for r in recs[1:]] == ["submit", "done"]
    # reopening an existing journal must NOT write a second meta record
    Journal(p).close()
    assert [r["rec"] for r in read_journal(p)].count("meta") == 1


def test_journal_drops_torn_tail(tmp_path):
    p = tmp_path / "j.jsonl"
    j = Journal(p)
    j.append({"rec": "submit", "seq": 0, "task": "t", "at_ms": 1.0})
    j.close()
    with open(p, "a", encoding="utf-8") as f:
        f.write('{"rec": "submit", "seq": 1, "ta')    # crash mid-write
    recs = read_journal(p)
    assert [r.get("seq") for r in submit_records(recs)] == [0]


def test_unfinished_and_audit():
    recs = [
        {"rec": "meta", "version": 1},
        {"rec": "submit", "seq": 0, "task": "a", "at_ms": 1.0},
        {"rec": "submit", "seq": 1, "task": "a", "at_ms": 2.0},
        {"rec": "submit", "seq": 2, "task": "b", "at_ms": 3.0},
        {"rec": "done", "seq": 1, "status": "completed",
         "response_ms": 5.0},
        {"rec": "resubmitted", "seq": 0, "at_ms": 9.0},
    ]
    # resubmitted does not finish a seq; 0 and 2 are still owed
    assert [r["seq"] for r in unfinished_submits(recs)] == [0, 2]
    assert audit_zero_lost(recs) == [0, 2]
    recs.append({"rec": "done", "seq": 0, "status": "cancelled",
                 "response_ms": None})
    recs.append({"rec": "done", "seq": 2, "status": "missed",
                 "response_ms": 30.0})
    assert audit_zero_lost(recs) == []


def test_to_trace_arrivals_and_replay_plan():
    recs = [
        {"rec": "submit", "seq": 0, "task": "a", "at_ms": 1.0},
        {"rec": "submit", "seq": 1, "task": "b", "at_ms": 2.0},
        {"rec": "submit", "seq": 2, "task": "a", "at_ms": 7.0},
        {"rec": "cancel", "seq": 1, "at_ms": 3.0},
    ]
    arr = to_trace_arrivals(recs)
    assert set(arr) == {"a", "b"}
    assert list(arr["a"].times) == [1.0, 7.0]
    arr2 = to_trace_arrivals(recs, until_ms=2.0)
    assert list(arr2["a"].times) == [1.0]
    subs, cancels = replay_plan(recs)
    assert len(subs) == 3 and cancels == [(1, 3.0)]


# ------------------------------------------------------- daemon fixtures
def daemon_cfg(**over):
    cfg = {
        "tasks": [
            {"dnn": "resnet18", "priority": "HP", "jps": 30.0},
            {"dnn": "unet", "priority": "LP", "jps": 10.0},
        ],
        "contexts": 2, "streams": 1, "oversubscribe": 2.0,
        "seed": 0, "noise": 0.0,
        "batching": {"max_batch": 4, "scope": "model"},
    }
    cfg.update(over)
    return cfg


def start_daemon(tmp_path, name="d", cfg=None, **kw):
    d = ServeDaemon(cfg or daemon_cfg(),
                    socket_path=str(tmp_path / f"{name}.sock"),
                    journal_path=str(tmp_path / "journal.jsonl"),
                    checkpoint_path=str(tmp_path / "ckpt.msgpack"), **kw)
    th = threading.Thread(target=d.run, daemon=True)
    th.start()
    c = DarisClient(d.socket_path)
    c.wait_up()
    return d, th, c


def test_daemon_round_trip(tmp_path):
    d, th, c = start_daemon(tmp_path, time_scale=200.0, tick_ms=1.0)
    assert c.ping()["ok"]
    s0 = c.submit("resnet18", tenant="teamA")
    assert s0["status"] in ("queued", "running", "completed")
    s1 = c.submit("unet", tenant="teamB")
    r0 = c.result(s0["seq"], timeout_s=30.0)
    assert r0["status"] in ("completed", "missed")
    assert r0["tenant"] == "teamA" and r0["response_ms"] is not None
    st = c.status(s1["seq"])
    assert st["ok"] and st["task"] == "unet"
    stats = c.stats()
    assert stats["submitted"] == 2
    assert "completed" in stats["snapshot"]
    assert "cancelled" in stats["snapshot"]
    # unknown task / unknown seq are clean errors, not daemon deaths
    from repro.serve.client import DaemonError
    with pytest.raises(DaemonError, match="KeyError"):
        c.submit("nonexistent-model")
    with pytest.raises(DaemonError, match="unknown seq"):
        c.cancel(999)
    out = c.drain()
    th.join(timeout=10.0)
    assert not th.is_alive()
    assert out["lost"] == []
    assert out["summary"]["jps_hp"] > 0.0       # the HP job completed
    assert audit_zero_lost(read_journal(tmp_path / "journal.jsonl")) == []


def test_stats_verb_shows_trace_counters_when_config_asks(tmp_path,
                                                           capsys):
    """``"trace": true`` in the serving config keeps the engine's tracer
    on, and ``python -m repro.serve stats`` shows its counters."""
    from repro.serve.__main__ import main
    assert "trace" not in build_server(daemon_cfg()).snapshot()
    d, th, c = start_daemon(tmp_path, cfg=daemon_cfg(trace=True),
                            time_scale=200.0, tick_ms=1.0)
    s = c.submit("resnet18", tenant="teamA")
    assert c.result(s["seq"], timeout_s=30.0)["status"] in ("completed",
                                                            "missed")
    capsys.readouterr()
    assert main(["stats", "--socket", d.socket_path]) == 0
    trace = json.loads(capsys.readouterr().out)["snapshot"]["trace"]
    assert trace["admitted"]["hp"] == 1 and trace["refused"]["lp"] == 0
    assert trace["records"]["stages"] >= 1
    assert {"wait_ms", "step_ms", "timeouts", "late_ms", "longest_steps",
            "longest_late_wakes"} <= set(trace["engine"])
    c.drain()
    th.join(timeout=10.0)
    assert not th.is_alive()


def test_daemon_cancel_round_trip(tmp_path):
    # virtual time frozen at ticks: submissions stay queued long enough
    # to be cancelled deterministically
    d, th, c = start_daemon(tmp_path, time_scale=0.0, tick_ms=1.0)
    s = c.submit("unet", tenant="teamA")
    assert s["status"] == "running"      # empty engine: dispatches at once
    out = c.cancel(s["seq"])
    assert out["status"] == "cancelled"
    r = c.result(s["seq"], timeout_s=5.0)
    assert r["status"] == "cancelled"
    fin = c.drain()
    th.join(timeout=10.0)
    assert fin["summary"]["cancelled_lp"] == 1
    assert fin["lost"] == []
    recs = read_journal(tmp_path / "journal.jsonl")
    assert [r["rec"] for r in recs if r.get("seq") == s["seq"]] \
        == ["submit", "cancel", "done"]


def test_daemon_sigterm_restart_zero_lost(tmp_path):
    """The durability contract end-to-end: acknowledge work, die by
    SIGTERM with it unfinished, restart on the same journal+checkpoint,
    finish every acknowledged seq under its original identity."""
    # time barely moves: nothing can finish before the TERM
    d1, th1, c1 = start_daemon(tmp_path, name="d1", time_scale=1e-7)
    seqs = [c1.submit("resnet18", tenant="teamA")["seq"] for _ in range(3)]
    seqs.append(c1.submit("unet", tenant="teamB")["seq"])
    d1._on_signal(None, None)            # what SIGTERM delivers
    th1.join(timeout=10.0)
    assert not th1.is_alive()

    recs = read_journal(tmp_path / "journal.jsonl")
    assert audit_zero_lost(recs) == seqs                # owed, not lost
    assert any(r["rec"] == "checkpoint" for r in recs)

    d2, th2, c2 = start_daemon(tmp_path, name="d2", time_scale=500.0)
    for seq in seqs:
        r = c2.result(seq, timeout_s=30.0)
        assert r["status"] in ("completed", "missed")
    fin = c2.drain()
    th2.join(timeout=10.0)
    assert fin["lost"] == []
    recs = read_journal(tmp_path / "journal.jsonl")
    assert audit_zero_lost(recs) == []
    assert sum(r["rec"] == "resubmitted" for r in recs) == len(seqs)


# ---------------------------------------------- bit-identical replay
def _digest(m):
    payload = repr((m.completed, m.missed, m.completed_inputs,
                    sorted(m.batch_hist.items()),
                    {p: [x.hex() for x in xs]
                     for p, xs in m.response_ms.items()}))
    return hashlib.sha256(payload.encode()).hexdigest()


def test_journal_replay_is_bit_identical(tmp_path):
    """Golden serving contract (sibling of test_engine_golden): traffic
    recorded by a live daemon, replayed from the journal as TraceArrival
    into a freshly built engine, reproduces the run bit-exactly —
    same counts and SHA-256 over IEEE-754 response times.

    Batching is off: the lazy-dispatch hold keys off future-arrival
    knowledge a live daemon cannot have (see ``to_trace_arrivals``), so
    the bit-exact contract covers hold-free traffic."""
    cfg = daemon_cfg()
    del cfg["batching"]
    # time_scale=0: stamps come purely from the deterministic tick
    d, th, c = start_daemon(tmp_path, cfg=cfg, time_scale=0.0, tick_ms=5.0)
    for i in range(12):
        c.submit("resnet18" if i % 3 else "unet",
                 tenant="teamA" if i % 2 else "teamB")
    c.drain()
    th.join(timeout=10.0)
    live = d.final_metrics
    assert sum(live.completed.values()) > 0

    recs = read_journal(tmp_path / "journal.jsonl")
    arrivals = to_trace_arrivals(recs)
    replay = build_server(cfg, arrivals=arrivals)
    m = replay.drain()
    assert _digest(m) == _digest(live)


def test_replay_cli_and_audit_cli(tmp_path):
    from repro.serve.__main__ import main
    cfg_path = tmp_path / "serve.json"
    cfg_path.write_text(json.dumps(daemon_cfg()))
    d, th, c = start_daemon(tmp_path, time_scale=0.0, tick_ms=5.0)
    c.submit("unet")
    c.drain()
    th.join(timeout=10.0)
    jrn = str(tmp_path / "journal.jsonl")
    assert main(["audit", "--journal", jrn]) == 0
    assert main(["replay", "--config", str(cfg_path),
                 "--journal", jrn]) == 0
    # an owed seq flips the audit to failing
    Journal(jrn).append({"rec": "submit", "seq": 99, "task": "unet",
                         "at_ms": 1e6})
    assert main(["audit", "--journal", jrn]) == 1


def test_build_server_requires_tasks():
    with pytest.raises(ValueError, match="at least one task"):
        build_server({"tasks": []})
