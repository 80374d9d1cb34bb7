#!/usr/bin/env python3
"""The program's own trace (``repro.runtime.trace``): its ``daris.*`` spans
and the stage programs' device events reduced from a profile, the view of
its records that the readers below read, and a runner that serves a cell
with the tracer on.

    python3 perfbench/program_trace.py --workload resnet18.overload \\
        --seeds 11,12 --seconds 40

The runner runs a cell as ``run.py --trace 1`` does, through the same
``harness.run_cell``, with the program's tracer switched on for the whole
run (``switched_on``): every server it builds keeps its records, spans are
on while the profiler runs, and the profile is reduced here before the
harness deletes it. It prints one JSON object per seed: ``correct``, the
per-layer metrics of ``BENCHMARK.json`` read from this traced window
(so ``hp_p95_ms`` and ``stage_gap_ms`` are taken with the tracer on), the
readers below, and the engine loop's counters; on standard error, the
device-idle split and each stage program's device time.

The readers take a harness ``Run`` with a ``program`` attribute
(``view``); without one they return None:

    engine_busy_share   1 - the engine thread's time in its wait on the
                        completion queue over the measured window, in %
    queue_wait_p95_ms   p95 of launch - ready: the scheduler's queue
    handoff_ms          mean of (pickup - launch) + (harvest - put): the
                        worker pool's and the completion queue's hand-offs
    worker_host_ms      mean of issued - pickup: upload, reshard and the
                        payload call, host work before the chip has a stage
    et_over_device      the mean execution time MRET observes per stage
                        execution in the window, over the mean device time
                        of one run of a stage program in the traced
                        segment, the programs found by their names
                        (``jit_<model>_s<j>``): how far MRET's input lies
                        above the device's own time. The window's et_ms is
                        taken with the profiler off; the segment's own,
                        taken with it on, is logged beside it

It also names, for each engine step in the window that took, or each wake
that came, ``STALL_MS`` or more late, what the stage executions in flight
were doing meanwhile (``stalls``). After the window the harness starts and
stops the profiler inside the engine's wait, which would read as a late
wake.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

HERE = Path(__file__).resolve().parent
ENGINE = ("daris.release", "daris.dispatch", "daris.harvest", "daris.wait")
WORKER = ("daris.upload", "daris.reshard", "daris.issue", "daris.sync")
KINDS = {**{n: "engine" for n in ENGINE}, **{n: "worker" for n in WORKER}}
CLOCK = "daris.clock"
WINDOW = "bench.window"
NO_SPAN = "no span"
STAGE_PROGRAM = re.compile(r"jit_(\w+_s\d+)")
STALL_MS = 50.0
# a stage execution's phases, each between two of its instants
PHASES = {"pickup": ("launch", "pickup"), "input": ("pickup", "input_ready"),
          "issue": ("input_ready", "issued"), "sync": ("issued", "synced"),
          "put": ("synced", "put"), "completion_queue": ("put", "harvest")}

Interval = Tuple[float, float]


# ------------------------------------------------------------ the profile
def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def per_gap(intervals: List[Interval], gaps: List[Interval]) -> List[float]:
    """How much of each gap the sorted disjoint ``intervals`` cover."""
    out = [0.0] * len(gaps)
    starts = [g[0] for g in gaps]
    for s, e in intervals:
        j = max(bisect.bisect_right(starts, s) - 1, 0)
        while j < len(gaps) and gaps[j][0] < e:
            d = min(e, gaps[j][1]) - max(s, gaps[j][0])
            if d > 0:
                out[j] += d
            j += 1
    return out


def read_profile(log_dir: str) -> Optional[Dict]:
    """The traced segment (``bench.window``), the ``daris.clock`` anchor,
    the program's spans by name, each chip's program intervals and the
    stage programs' device events, all in ns on the trace's clock, and
    how often each device program ran. None when the trace holds no
    window marker."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        return None
    pd = ProfileData.from_file(paths[0])
    window, anchor = None, None
    spans: Dict[str, List[Interval]] = {}
    programs: Dict[str, List[Interval]] = {}
    stages: List[Tuple[float, float, str]] = []
    modules: Dict[str, int] = {}
    for plane in pd.planes:
        device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if device and line.name != "XLA Modules":
                continue
            for e in line.events:
                if device:
                    programs.setdefault(plane.name, []).append(
                        (e.start_ns, e.end_ns))
                    modules[e.name] = modules.get(e.name, 0) + 1
                    m = STAGE_PROGRAM.match(e.name)
                    if m:
                        stages.append((e.start_ns, e.end_ns, m.group(1)))
                elif e.name == WINDOW:
                    window = (e.start_ns, e.end_ns)
                elif e.name == CLOCK:
                    anchor = (e.start_ns, dict(e.stats)["now_ms"])
                elif e.name in KINDS:
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns))
    if window is None:
        return None
    return {"window": window, "anchor": anchor, "spans": spans,
            "programs": programs, "stages": stages, "modules": modules}


def split_idle(profile: Dict) -> Dict[str, Dict[str, float]]:
    """Each device-idle gap of the traced segment apportioned, per thread
    kind, by the share of it each span name covers: a gap that spans
    cover only in part gives the rest to ``no span``, and where spans of
    one kind overlap (workers run side by side) the covered time is
    split in proportion to each name's cover. Seconds, mean over chips."""
    lo, hi = profile["window"]
    chips = sorted(profile["programs"])
    out: Dict[str, Dict[str, float]] = {"engine": {}, "worker": {}}
    names = {n: union(v) for n, v in profile["spans"].items()}
    for chip in chips:
        busy = [(max(s, lo), min(e, hi))
                for s, e in union(profile["programs"][chip])
                if e > lo and s < hi]
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        for kind, acc in out.items():
            mine = {n: iv for n, iv in names.items() if KINDS[n] == kind}
            cover = {n: per_gap(iv, gaps) for n, iv in mine.items()}
            covered = per_gap(union([x for iv in mine.values()
                                     for x in iv]), gaps)
            for g, (s, e) in enumerate(gaps):
                total = sum(c[g] for c in cover.values())
                for n, c in cover.items():
                    if c[g] > 0:
                        acc[n] = acc.get(n, 0.0) + covered[g] * c[g] / total
                acc[NO_SPAN] = acc.get(NO_SPAN, 0.0) + (e - s) - covered[g]
    n = max(len(chips), 1)
    return {k: {name: v / n / 1e9 for name, v in acc.items()}
            for k, acc in out.items()}


def stage_device_s(profile: Dict) -> Dict[str, List[float]]:
    """Per stage program: [executions, device seconds] inside the traced
    segment."""
    lo, hi = profile["window"]
    out: Dict[str, List[float]] = {}
    for s, e, name in profile["stages"]:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            acc = out.setdefault(name, [0, 0.0])
            acc[0] += 1
            acc[1] += d / 1e9
    return out


# --------------------------------------------------------- the records
@dataclasses.dataclass
class ProgramView:
    """The tracer's records as the readers take them: ``stages``, the
    stage executions that lay wholly in ``window`` (ms on the server's
    clock), as named tuples of ``repro.runtime.trace.STAGE_FIELDS``;
    ``waits``, the engine's waits on its completion queue; ``segment``,
    for a profile: the runs of the stage programs in the traced segment
    and their device seconds (``device_runs``, ``device_s``), and with
    the clock anchor the segment in ms and the stage executions that lay
    in it (``executions``, their summed ``et_ms``)."""
    stages: List[tuple]
    waits: List[Interval]
    window: Interval
    segment: Optional[Dict] = None


def view(tracer, window: Interval, profile: Optional[Dict]) -> ProgramView:
    from repro.runtime.trace import STAGE_FIELDS

    rec = collections.namedtuple("Stage", STAGE_FIELDS)
    recs = [rec(*r) for r in tracer.stage_records()]
    w0, w1 = window
    waits = [(r[0], r[1]) for r in tracer.step_records()]
    out = ProgramView([r for r in recs
                       if w0 <= r.launch and r.harvest <= w1],
                      waits, window)
    if profile is not None:
        runs = stage_device_s(profile).values()
        out.segment = {"device_runs": sum(v[0] for v in runs),
                       "device_s": sum(v[1] for v in runs)}
        if profile["anchor"] is not None:
            ns, ms = profile["anchor"]
            a, b = (ms + (t - ns) / 1e6 for t in profile["window"])
            inside = [r for r in recs if a <= r.launch and r.harvest <= b]
            out.segment.update(ms=(a, b), executions=len(inside),
                               et_ms=sum(r.et_ms for r in inside))
    return out


def stalls(tracer, window: Interval,
           min_ms: float = STALL_MS) -> List[Dict]:
    """Each engine step in ``window`` that took ``min_ms`` or more, and
    each wait there that woke that late: when, how long, and per phase
    how many of the stage executions in flight spent most of the stall
    in it, with the longest such phase."""
    from repro.runtime.trace import STAGE_FIELDS, step_phases

    at = {f: i for i, f in enumerate(STAGE_FIELDS)}
    recs = tracer.stage_records()
    out = []
    for step in tracer.step_records():
        ph = step_phases(step)
        for kind, ms, lo in (("step", ph["step_ms"], step[1]),
                             ("late wake", ph["late_ms"],
                              step[1] - ph["late_ms"])):
            if ms < min_ms or not window[0] <= lo < window[1]:
                continue
            hi = lo + ms
            doing: Dict[str, List[float]] = {}
            for r in recs:
                if r[at["launch"]] >= hi or r[at["harvest"]] <= lo:
                    continue
                cover = {name: min(hi, r[at[b]]) - max(lo, r[at[a]])
                         for name, (a, b) in PHASES.items()}
                name = max(cover, key=cover.get)
                a, b = PHASES[name]
                acc = doing.setdefault(name, [0, 0.0])
                acc[0] += 1
                acc[1] = max(acc[1], r[at[b]] - r[at[a]])
            out.append({"at_ms": lo, "kind": kind, "ms": ms,
                        "in_flight": doing})
    return out


def _mean(xs: List[float]) -> Optional[float]:
    xs = [x for x in xs if not math.isnan(x)]
    return sum(xs) / len(xs) if xs else None


def splits(p: ProgramView) -> Dict[str, Dict[str, Optional[float]]]:
    """Means, in ms, of the parts of ``stage_gap_ms`` (over each stage
    and its job's next stage in the window: next launch - (launch +
    et_ms) = the pool's pick-up + the completion queue + the next
    stage's queue wait) and of ``et_ms`` (upload or reshard, the payload
    call, the device wait, the hand-over to the queue)."""
    by = {(r.job, r.stage): r for r in p.stages}
    pairs = [(r, by[(r.job, r.stage + 1)]) for r in p.stages
             if (r.job, r.stage + 1) in by]
    return {
        "stage_gap": {
            "pickup": _mean([a.pickup - a.launch for a, _ in pairs]),
            "completion_queue": _mean([a.harvest - a.put for a, _ in pairs]),
            "queue_wait": _mean([b.launch - b.ready for _, b in pairs]),
            "total": _mean([b.launch - (a.launch + a.et_ms)
                            for a, b in pairs])},
        "et": {
            "input": _mean([r.input_ready - r.pickup for r in p.stages]),
            "issue": _mean([r.issued - r.input_ready for r in p.stages]),
            "sync": _mean([r.synced - r.issued for r in p.stages]),
            "put": _mean([r.put - r.synced for r in p.stages]),
            "et_ms": _mean([r.et_ms for r in p.stages])}}


def engine_busy_share(run) -> Optional[float]:
    p = getattr(run, "program", None)
    if p is None:
        return None
    w0, w1 = p.window
    wait = sum(max(0.0, min(b, w1) - max(a, w0)) for a, b in p.waits)
    return 100.0 * (1.0 - wait / (w1 - w0))


def queue_wait_p95_ms(run) -> Optional[float]:
    import numpy as np

    p = getattr(run, "program", None)
    if p is None or not p.stages:
        return None
    return float(np.percentile([r.launch - r.ready for r in p.stages], 95))


def handoff_ms(run) -> Optional[float]:
    p = getattr(run, "program", None)
    if p is None:
        return None
    return _mean([(r.pickup - r.launch) + (r.harvest - r.put)
                  for r in p.stages])


def worker_host_ms(run) -> Optional[float]:
    p = getattr(run, "program", None)
    if p is None:
        return None
    return _mean([r.issued - r.pickup for r in p.stages])


def et_over_device(run) -> Optional[float]:
    p = getattr(run, "program", None)
    seg = p.segment if p is not None else None
    if seg is None or seg["device_s"] <= 0 or not p.stages:
        return None
    return _mean([r.et_ms for r in p.stages]) / (
        1e3 * seg["device_s"] / seg["device_runs"])


READERS = {"engine_busy_share": ("%", engine_busy_share),
           "queue_wait_p95_ms": ("ms", queue_wait_p95_ms),
           "handoff_ms": ("ms", handoff_ms),
           "worker_host_ms": ("ms", worker_host_ms),
           "et_over_device": ("x", et_over_device)}


# ------------------------------------------------------------- the runner
@contextlib.contextmanager
def switched_on():
    """Within the block every server built has the tracer on, its spans
    run while the profiler does, and each profile the harness reduces is
    reduced here too, before the harness deletes it. Yields a dict that
    receives the last server's ``tracer`` and the last ``profile``."""
    import jax

    import tracing
    from repro.api import ServerConfig

    held: Dict = {}
    build = ServerConfig.build
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace
    reduce = tracing.read_profile

    def traced_build(cfg):
        server = build(cfg.trace())
        held["tracer"] = server.tracer
        return server

    def started(*a, **kw):
        start(*a, **kw)
        held["tracer"].annotate(True)

    def stopped(*a, **kw):
        held["tracer"].annotate(False)
        stop(*a, **kw)

    def reduced(log_dir):
        held["profile"] = read_profile(log_dir)
        return reduce(log_dir)

    ServerConfig.build = traced_build
    jax.profiler.start_trace, jax.profiler.stop_trace = started, stopped
    tracing.read_profile = reduced
    try:
        yield held
    finally:
        ServerConfig.build = build
        jax.profiler.start_trace, jax.profiler.stop_trace = start, stop
        tracing.read_profile = reduce


def log_profile(profile: Dict, log) -> None:
    split = split_idle(profile)
    idle = sum(split["engine"].values())
    lo, hi = profile["window"]
    log(f"device idle {idle:.4f} s of {(hi - lo) / 1e9:.4f} s, apportioned "
        f"by the program's spans (s, share of idle):")
    for kind, acc in split.items():
        for name, s in sorted(acc.items(), key=lambda kv: -kv[1]):
            log(f"  {kind:6s} {name:15s} {s:.4f} "
                f"{100.0 * s / idle if idle else 0.0:5.1f}%")
    for name, (n, s) in sorted(stage_device_s(profile).items()):
        log(f"  stage program {name}: {n} runs, {s:.6f} s, "
            f"{1e3 * s / n:.4f} ms each")
    log(f"  device programs by name: {sorted(profile['modules'].items())}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(HERE.parent / "src"))
    sys.path.insert(0, str(HERE))
    import harness

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    spec = harness.bench()
    tr = harness.load_json(
        "traffic", f"{harness.workload(spec, args.workload)['traffic']}.json")
    w0 = tr["warmup_s"] * 1000.0
    t_start = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        with switched_on() as held:
            res = harness.run_cell(args.workload, seed, args.seconds, True,
                                   t_start=t_start, log=log)
        t_start = time.perf_counter()
        run = res["run"]
        tracer, profile = held["tracer"], held.get("profile")
        run.program = view(tracer, (w0, w0 + args.seconds * 1000.0),
                           profile)
        metrics = {}
        for m in spec["per_layer"]:
            v = harness.load_module("metrics", m["name"]).read(run)
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = v
        for name, (_, read) in READERS.items():
            v = read(run)
            if v is not None and math.isfinite(v):
                metrics[name] = v
        if profile is not None:
            log_profile(profile, log)
        seg = run.program.segment or {}
        if seg.get("executions"):
            log(f"et_ms per stage execution: window "
                f"{_mean([r.et_ms for r in run.program.stages])}, traced "
                f"segment {seg['et_ms'] / seg['executions']}")
        found = stalls(tracer, run.program.window)
        for st in found:
            log(f"stall: {st['kind']} at {st['at_ms']:.1f} ms, "
                f"{st['ms']:.1f} ms; stage executions in flight by phase "
                f"(count, longest ms): {st['in_flight']}")
        counters = tracer.counters()
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": res["correct"], "metrics": metrics,
            "segment": run.program.segment,
            "stalls": found,
            "splits": splits(run.program),
            "idle_split": split_idle(profile) if profile else None,
            "stage_device_s": stage_device_s(profile) if profile else None,
            "engine": counters["engine"],
            "admission": {k: counters[k] for k in
                          ("admitted", "refused", "refusals")}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
