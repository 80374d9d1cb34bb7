"""Due-time arithmetic of the end-to-end and engine-loop metrics on
hand-made releases."""
import pytest

import harness
from harness import Req, Run


def load(name):
    return harness.load_module("metrics", name).read


def req(prio, due, release, finish, status="completed"):
    return Req(prio, due, release, finish, status, 33.0, 1)


def run_of(reqs, stages=(), window_s=1.0, end_ms=2000.0):
    return Run(list(reqs), list(stages), [], {}, window_s, end_ms, 1.0, 1)


def test_late_loop_shows_in_release_lag_and_tail():
    on_time = [req("hp", t, t, t + 5.0) for t in range(0, 1000, 10)]
    late = [req("hp", t, t + 20.0, t + 25.0) for t in range(0, 1000, 10)]
    for reqs, lag, p95 in ((on_time, 0.0, 5.0), (late, 20.0, 25.0)):
        run = run_of(reqs)
        assert load("release_lag_p95_ms")(run) == pytest.approx(lag)
        # timed from the due time, the loop's lateness is in the tail
        assert load("hp_p95_ms")(run) == pytest.approx(p95)


def test_unfinished_job_counts_as_missed():
    reqs = [req("hp", 0.0, 0.0, 10.0),
            req("hp", 10.0, 10.0, None, "running"),
            req("lp", 20.0, None, None, "rejected"),
            req("lp", 30.0, 30.0, 70.0, "missed")]
    run = run_of(reqs, window_s=2.0, end_ms=500.0)
    assert load("goodput_jps")(run) == pytest.approx(0.5)
    assert load("hp_met_share")(run) == pytest.approx(50.0)
    # the unfinished HP job's latency is at least the run's end - due
    assert load("hp_p95_ms")(run) == pytest.approx(10 + 0.95 * (490 - 10))
    # the refused LP release counts in the LP tail like an unfinished one
    assert load("lp_p95_ms")(run) == pytest.approx(40 + 0.95 * (480 - 40))


def test_lp_tail_reads_when_every_lp_release_is_refused():
    reqs = [req("hp", 0.0, 0.0, 5.0),
            req("lp", 10.0, None, None, "rejected"),
            req("lp", 20.0, None, None, "rejected")]
    run = run_of(reqs, end_ms=500.0)
    assert load("lp_admit_share")(run) == pytest.approx(0.0)
    assert load("lp_p95_ms")(run) == pytest.approx(480 + 0.95 * 10)


@pytest.mark.parametrize("case, value", [
    (req("lp", 40.0, 40.0, 73.0), 100.0),                 # met in time
    (req("lp", 40.0, 40.0, 73.5, "missed"), 50.0),        # finished late
    (req("lp", 40.0, None, None, "rejected"), 50.0),      # refused
    (req("lp", 40.0, 40.0, None, "running"), 50.0),       # never finished
    (req("hp", 40.0, 40.0, 45.0), None),                  # no LP release
], ids=["met", "late", "refused", "unfinished", "no_lp"])
def test_lp_met_share(case, value):
    # an HP job that missed does not count among LP releases
    reqs = [req("hp", 0.0, 0.0, 90.0, "missed"), case]
    if value is not None:
        reqs.append(req("lp", 10.0, 10.0, 20.0))
    got = load("lp_met_share")(run_of(reqs, end_ms=500.0))
    assert got == (None if value is None else pytest.approx(value))


@pytest.mark.parametrize("case, value", [
    (req("hp", 40.0, 40.0, 45.0), 5.0),                   # on time
    (req("hp", 40.0, 42.0, 80.0, "missed"), 40.0),        # late
    (req("hp", 40.0, 40.0, None, "running"), 460.0),      # never finished
    (req("lp", 40.0, 40.0, 45.0), 500.0),                 # LP ignored
    (req("lp", 40.0, None, None, "rejected"), None),      # no HP release
], ids=["on_time", "late", "unfinished", "lp_ignored", "no_hp"])
def test_hp_p50_ms(case, value):
    # beside the case, HP jobs 1 ms and 999 ms from their due times: an HP
    # case's latency is the median of the three, an LP one leaves the
    # median of the two; the late LP job never counts
    reqs = [req("lp", 0.0, 0.0, 300.0, "missed"), case]
    if value is not None:
        reqs += [req("hp", 10.0, 10.0, 11.0),
                 req("hp", 20.0, 20.0, 1019.0, "missed")]
    got = load("hp_p50_ms")(run_of(reqs, end_ms=500.0))
    assert got == (None if value is None else pytest.approx(value))


def test_admit_share_and_stage_gap():
    reqs = [req("lp", 0.0, 0.0, 9.0), req("lp", 1.0, None, None, "rejected")]
    stages = [(7, 0, 0, 1.0, 2.0), (7, 0, 1, 2.5, 3.0), (7, 0, 2, 3.5, 4.0),
              (8, 0, 0, 5.0, 6.0)]
    run = run_of(reqs, stages)
    assert load("lp_admit_share")(run) == pytest.approx(50.0)
    assert load("stage_gap_ms")(run) == pytest.approx(0.5)


def test_untraced_run_reads_no_device_metric():
    run = run_of([req("hp", 0.0, 0.0, 1.0)])
    for name in ("stage_roofline", "device_idle_share"):
        assert load(name)(run) is None


def test_every_metric_of_the_benchmark_has_a_reader():
    spec = harness.bench()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file(), \
            m["name"]
