"""Spans around the program's layers, and the reduction of a profiler
trace to device busy time, the top device operations and what the host
was doing while the device sat idle.

The spans are the benchmark's own (``jax.profiler.TraceAnnotation``,
written into the profiler's trace on the same clock as the device
events), wrapped around the engine thread's calls into the program only
in a traced run:

    bench.window        the traced window, on the engine thread
    sched.on_release    admission (Eq. 11-12) of one release
    backend.launch      dispatch of one stage to a worker
    backend.advance     the engine waiting for and harvesting completions

The stage payloads run on the worker threads untouched, so device idle
time that no span covers is the workers' own: a frame's upload, a stage's
dispatch and the wait for its result.
"""
from __future__ import annotations

import glob
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
SPAN_PREFIXES = ("sched.", "backend.")
# the engine waiting on its completion queue: the idle state of the host's
# drive loop, so a gap is put down to it only when nothing else ran
WAITING = "backend.advance"
TOP = 10

Interval = Tuple[float, float]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def overlap(a: List[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in clip(a, lo, hi))


def reduce(window: Interval, programs: Dict[str, List[Interval]],
           ops: Dict[str, List[Tuple[float, float, str]]],
           spans: List[Tuple[float, float, str]]) -> Dict:
    """Reduce one traced window (nanoseconds on the trace's clock).

    ``programs``: per chip, the intervals in which a compiled program ran.
    ``ops``: per chip, (start, end, name) of each device operation.
    ``spans``: (start, end, name) of the host spans, any thread.

    Returns ``busy_s`` (mean over chips of the union of program time),
    ``window_s``, ``busy_s_per_chip``, ``device_ops`` (the operations that
    took most time, seconds per chip) and ``idle_gaps`` (seconds of device
    idle per chip, put down to the host span that covered most of each
    gap, ``backend.advance`` only where no other span did)."""
    lo, hi = window
    chips = sorted(programs)
    if not chips:
        raise ValueError("the trace holds no device programs")
    by_name: Dict[str, List[Interval]] = {}
    for s, e, name in spans:
        by_name.setdefault(name, []).append((s, e))
    by_name = {k: union(v) for k, v in by_name.items()}
    busy, op_time, gap_time = {}, {}, {}
    for chip in chips:
        u = clip(union(programs[chip]), lo, hi)
        busy[chip] = sum(e - s for s, e in u)
        for s, e, name in ops.get(chip, []):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_time[name] = op_time.get(name, 0.0) + d
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            cover = {k: overlap(v, s, e) for k, v in by_name.items()}
            busy_names = {k: c for k, c in cover.items()
                          if c > 0 and k != WAITING}
            if busy_names:
                who = max(busy_names, key=busy_names.get)
            elif cover.get(WAITING, 0.0) > 0:
                who = WAITING
            else:
                who = "no span"
            gap_time[who] = gap_time.get(who, 0.0) + (e - s)
    n = len(chips)

    def top(d: Dict[str, float]) -> List[list]:
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": sum(busy.values()) / n / 1e9,
            "window_s": (hi - lo) / 1e9,
            "busy_s_per_chip": {c: busy[c] / 1e9 for c in chips},
            "device_ops": top(op_time), "idle_gaps": top(gap_time)}


def read_profile(log_dir: str) -> Optional[Dict]:
    """Reduce the one trace written under ``log_dir``; None when the
    trace has no window marker or no device program in it."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(paths)}")
    pd = ProfileData.from_file(paths[0])
    window = None
    programs: Dict[str, List[Interval]] = {}
    ops: Dict[str, List[Tuple[float, float, str]]] = {}
    spans: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    programs.setdefault(plane.name, []).extend(
                        (e.start_ns, e.end_ns) for e in line.events)
                elif line.name == "XLA Ops":
                    ops.setdefault(plane.name, []).extend(
                        (e.start_ns, e.end_ns, e.name) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.end_ns)
                    elif e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.start_ns, e.end_ns, e.name))
    if window is None or not programs:
        return None
    return reduce(window, programs, ops, spans)
