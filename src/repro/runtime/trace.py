"""In-program tracing of the serving path: timestamps, counters and
profiler spans (``ServerConfig.trace()``).

Two levels:

* **records**, on for the whole run once tracing is on: one tuple per
  stage execution (``STAGE_FIELDS``) and one per engine-loop step
  (``STEP_FIELDS``), each kept in a preallocated list and read after the
  run, plus the counters ``counters()`` returns (the ``"trace"`` entry of
  ``EngineCore.snapshot()``);
* **annotations**, ``jax.profiler.TraceAnnotation`` spans, switched on and
  off at run time by ``annotate(True/False)`` while a profiler runs.

Every instant is milliseconds on the backend's clock (``backend.now_ms()``).
Turning annotations on emits one ``daris.clock`` span whose ``now_ms``
argument places any record on the trace's clock.

Spans, each with the ids of the job and stage it serves as arguments:

    engine thread   daris.release   admission of one release (``task``;
                                    ``job`` and ``admitted`` once decided)
                    daris.dispatch  one stage handed to the worker pool
                    daris.harvest   one completion: MRET observation, the
                                    next stage enqueued
                    daris.wait      the wait on the completion queue
    worker threads  daris.upload    a fresh job's input put on its device
                    daris.reshard   inter-stage state moved to another
                                    context's device
                    daris.issue     the payload call (device work enqueued)
                    daris.sync      ``block_until_ready``

With tracing off the engine and the backend hold ``None`` in place of a
tracer, and each site costs one ``is not None`` test.
"""
from __future__ import annotations

import contextlib
import heapq
import itertools
import math
from typing import Callable, Dict, List, Optional

from ..core.task import HP, LP

# one stage execution; the worker's five instants are NaN on a backend
# without worker threads (the simulator)
STAGE_FIELDS = ("job", "task", "priority", "stage", "ready", "launch",
                "pickup", "input_ready", "issued", "synced", "put",
                "harvest", "et_ms")
# one engine-loop step: the wait on the completion queue [wait, woke) with
# its cap, whether it returned completions, the step's handling of them or
# of one timeline event [woke, handled), then dispatch [handled, end)
STEP_FIELDS = ("wait", "woke", "cap", "harvested", "handled", "end")
TOP = 8
CAPACITY = 1 << 18
_NO_MARKS = (math.nan,) * 5
_NULL = contextlib.nullcontext()
_PRIO = {HP: "hp", LP: "lp"}


def _top(heap: List[tuple], key: float, seq: int, rec: tuple) -> None:
    """Keep the ``TOP`` largest ``key`` in a min-heap."""
    if len(heap) < TOP:
        heapq.heappush(heap, (key, seq, rec))
    elif key > heap[0][0]:
        heapq.heapreplace(heap, (key, seq, rec))


def step_phases(rec: tuple) -> Dict[str, float]:
    """One step record as the engine's time split: the wait, how late it
    woke past its cap (or past its start, had the cap already passed),
    handling a timeline event (a release's admission, mostly) or
    completions, and dispatch."""
    wait, woke, cap, harvested, handled, end = rec
    late = 0.0 if harvested or math.isinf(cap) else woke - max(cap, wait)
    return {"at_ms": woke, "wait_ms": woke - wait, "late_ms": late,
            "step_ms": end - woke,
            "release_ms": 0.0 if harvested else handled - woke,
            "harvest_ms": handled - woke if harvested else 0.0,
            "dispatch_ms": end - handled}


class Tracer:
    """Records, counters and spans of one engine (see module docstring).
    Engine-thread methods run on the engine thread only; a worker thread
    touches only its own ``WorkerMarks``."""

    def __init__(self, clock: Callable[[], float]):
        self.clock = clock
        self.stages: List[Optional[tuple]] = [None] * CAPACITY
        self.steps: List[Optional[tuple]] = [None] * CAPACITY
        self.n_stages = 0
        self.n_steps = 0
        self.dropped = 0
        self.annotating = False
        self._ann = None        # jax.profiler.TraceAnnotation, on first use
        # admission: counts per priority; per task index, [name, current
        # run of consecutive refusals, longest run, task MRET at the last
        # refusal]
        self.admitted = {HP: 0, LP: 0}
        self.refused = {HP: 0, LP: 0}
        self._refusals: Dict[int, list] = {}
        # engine loop
        self.wait_ms = 0.0
        self.step_ms = 0.0
        self.timeouts = 0
        self.late_ms = 0.0
        self._longest: List[tuple] = []
        self._late: List[tuple] = []
        self._seq = itertools.count()
        self._step = [0.0, 0.0, math.inf, False, 0.0]
        self._wait_span = None

    # ------------------------------------------------------------ spans
    def annotate(self, on: bool) -> None:
        """Spans on (while a profiler runs) or off. Turning them on emits
        the ``daris.clock`` anchor."""
        if on and not self.annotating:
            if self._ann is None:
                from jax.profiler import TraceAnnotation
                self._ann = TraceAnnotation
            with self._ann("daris.clock", now_ms=self.clock()):
                pass
        self.annotating = on

    def _span(self, name: str, **args):
        return self._ann(name, **args) if self.annotating else _NULL

    # ------------------------------------------------------- engine loop
    def wait_begin(self, cap: float) -> None:
        if self.annotating:
            self._wait_span = self._ann("daris.wait")
            self._wait_span.__enter__()
        st = self._step
        st[2] = cap
        st[0] = self.clock()

    def woke(self, now: float, harvested: bool) -> None:
        if self._wait_span is not None:
            self._wait_span.__exit__(None, None, None)
            self._wait_span = None
        st = self._step
        st[1] = now
        st[3] = harvested
        self.wait_ms += now - st[0]

    def handled(self) -> None:
        self._step[4] = self.clock()

    def step_end(self) -> None:
        rec = (*self._step, self.clock())
        if self.n_steps < CAPACITY:
            self.steps[self.n_steps] = rec
            self.n_steps += 1
        else:
            self.dropped += 1
        wait, woke, cap, harvested, _, end = rec
        self.step_ms += end - woke
        seq = next(self._seq)
        _top(self._longest, end - woke, seq, rec)
        if not harvested and not math.isinf(cap):
            self.timeouts += 1
            late = woke - max(cap, wait)
            if late > 0.0:
                self.late_ms += late
                _top(self._late, late, seq, rec)

    def release(self, task, now: float, on_release: Callable):
        """Admit or refuse one release through ``on_release`` and count
        the outcome. Returns the job, None when refused."""
        with self._span("daris.release", task=task.index) as a:
            job = on_release(task, now)
            if a is not None:
                a.set_metadata(job=-1 if job is None else job.job_id,
                               admitted=int(job is not None))
        run = self._refusals.get(task.index)
        if job is None:
            self.refused[task.priority] += 1
            if run is None:
                run = self._refusals[task.index] = [task.name, 0, 0, 0.0]
            run[1] += 1
            run[2] = max(run[2], run[1])
            run[3] = task.mret.task_mret()
        else:
            self.admitted[task.priority] += 1
            if run is not None:
                run[1] = 0
        return job

    def dispatch(self, lane: tuple, inst, launch: Callable) -> None:
        job = inst.job
        with self._span("daris.dispatch", job=job.job_id,
                        stage=job.stage_idx):
            launch(lane, inst)

    def harvest(self, c, now: float, on_completion: Callable) -> None:
        """Record one stage execution at its harvest, then hand the
        completion to ``on_completion``."""
        inst = c.inst
        job = inst.job
        rec = (job.job_id, job.task.index, job.task.priority, job.stage_idx,
               inst.enqueue_ms, inst.start_ms, *(c.marks or _NO_MARKS), now,
               c.et_ms)
        if self.n_stages < CAPACITY:
            self.stages[self.n_stages] = rec
            self.n_stages += 1
        else:
            self.dropped += 1
        with self._span("daris.harvest", job=job.job_id,
                        stage=job.stage_idx):
            on_completion(c)

    # ----------------------------------------------------------- workers
    def worker(self, inst) -> "WorkerMarks":
        return WorkerMarks(self, inst.job.job_id, inst.job.stage_idx)

    # ------------------------------------------------------------ output
    def stage_records(self) -> List[tuple]:
        return self.stages[:self.n_stages]

    def step_records(self) -> List[tuple]:
        return self.steps[:self.n_steps]

    def counters(self) -> Dict:
        """Admission and engine-loop counters, JSON-ready."""
        def worst(heap):
            return [step_phases(r) for _, _, r in sorted(heap, reverse=True)]
        return {
            "admitted": {_PRIO[p]: n for p, n in self.admitted.items()},
            "refused": {_PRIO[p]: n for p, n in self.refused.items()},
            "refusals": {r[0]: {"run": r[1], "longest_run": r[2],
                                "mret_ms": r[3]}
                         for r in self._refusals.values()},
            "engine": {"wait_ms": self.wait_ms, "step_ms": self.step_ms,
                       "timeouts": self.timeouts, "late_ms": self.late_ms,
                       "longest_steps": worst(self._longest),
                       "longest_late_wakes": worst(self._late)},
            "records": {"stages": self.n_stages, "steps": self.n_steps,
                        "dropped": self.dropped},
        }


class WorkerMarks:
    """One stage execution's instants on its worker thread: pickup,
    input_ready, issued, synced and put, in that order. ``finish`` returns
    them as a tuple that rides the completion to the engine thread, so no
    lock is needed. An instant the stage never reached (a payload-less
    stage, a payload that raised) takes the value of ``put``."""

    __slots__ = ("_tr", "_args", "_t", "_span")

    def __init__(self, tracer: Tracer, job_id: int, stage: int):
        self._tr = tracer
        self._args = {"job": job_id, "stage": stage}
        self._span = None
        self._t = [tracer.clock()]

    def _close(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def span(self, name: str) -> None:
        """Close the open span; open ``name`` when annotating."""
        self._close()
        if self._tr.annotating:
            self._span = self._tr._ann(name, **self._args)
            self._span.__enter__()

    def mark(self, span: Optional[str] = None) -> None:
        """Stamp the next instant, then open ``span`` (or none)."""
        self._close()
        self._t.append(self._tr.clock())
        if span is not None:
            self.span(span)

    def finish(self) -> tuple:
        """Stamp ``put``: the caller queues the completion right after."""
        self._close()
        t = self._tr.clock()
        return (*self._t, *(t,) * (5 - len(self._t)))
