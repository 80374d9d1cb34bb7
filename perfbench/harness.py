"""One benchmark run of one cell: build the DARIS realtime server from the
program's public calls, offer the cell's traffic open loop, time every
job from its due time, and check the served logits.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic, ``configs/<config>.json`` holds the
model's sizes, ``models/<family>.py`` its weights, plain reference and
work counts, ``traffic/<traffic>.json`` the load and partition,
``limits/<config>.json`` the limit of the comparison, and
``metrics/<metric>.py`` one reader per metric.

Timeline of a run, on the server's clock (ms from the engine's start):

    [0, warmup)                  load runs, MRET windows fill (set-up)
    [warmup, warmup + seconds)   the measured window: a job belongs to it
                                 when its due time does
    [.., + grace)                load goes on; in-window jobs may finish
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

import check
import traffic as traffic_mod
import tracing

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent
SETTLE_MS = 300.0   # after the profiler starts, before the traced segment
# JAX's events for a function traced and for a program compiled (or loaded
# from the persistent cache): neither may happen inside the window
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------- data files
def load_json(*parts: str) -> Dict:
    return json.loads(HERE.joinpath(*parts).read_text())


def bench() -> Dict:
    return json.loads((CHECKOUT / "BENCHMARK.json").read_text())


def workload(spec: Dict, name: str) -> Dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                     f"{[w['name'] for w in spec['workloads']]}")


def load_module(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device_check(chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"{chips} chips asked, {len(devs)} found")
    kinds = load_json("peaks.json")
    if devs[0].device_kind not in kinds:
        raise NoChip(f"device kind {devs[0].device_kind!r} is not in "
                     f"peaks.json")
    return devs


def use_compile_cache() -> None:
    """JAX's persistent cache at the fixed ``<checkout>/.jax_cache``, so
    that only a checkout's first run of a cell compiles."""
    jax.config.update("jax_compilation_cache_dir",
                      str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


# ----------------------------------------------------------- the timed path
class Recorder:
    """What the benchmark's hooks on the built server see. The served
    stage payloads are the program's own, untouched; the hooks sit on the
    engine thread's calls into the backend. Read after the run."""

    def __init__(self, keep: Callable[[int], bool], index: Dict[str, int]):
        self.keep = keep        # job id -> keep its final logits?
        self.index = index      # task name -> position in the task list
        self.trace = False      # spans on: only while the profiler runs
        self.launched: Dict[tuple, float] = {}   # (job, stage) -> s
        self.stages: List[tuple] = []    # (job, task, stage, t0, t1) in s
        self.outputs: Dict[int, tuple] = {}   # job -> (task, logits)

    def span(self, name: str):
        return jax.profiler.TraceAnnotation(name) if self.trace \
            else contextlib.nullcontext()


@dataclasses.dataclass
class Req:
    """One release, as its handle tells it (ms on the server's clock)."""
    prio: str
    due: float
    release: Optional[float]
    finish: Optional[float]
    status: str
    deadline: float
    job: Optional[int]

    @property
    def admitted(self) -> bool:
        return self.status != "rejected"

    @property
    def met(self) -> bool:
        return self.finish is not None and \
            self.finish - self.due <= self.deadline


@dataclasses.dataclass
class Run:
    """What the metric readers read, all from the measured window:
    ``reqs``, the releases due in it; ``stages``, in a traced run, the
    stage executions that lay wholly inside it, (job, task, stage, start,
    end) in ms on the host clock, from the engine's launch of the stage to
    that launch plus the execution time its worker reported; ``costs``,
    each stage's FLOPs and bytes; ``end_ms``, when
    the run stopped (a job that never finished has a latency of at least
    ``end_ms - due``). ``trace``: in a traced run, the reduction of the
    traced segment after the window (``tracing.reduce``) with the stage
    executions that lay wholly inside that segment."""
    reqs: List[Req]
    stages: List[tuple]
    costs: List[Dict[str, float]]
    peaks: Dict
    window_s: float
    end_ms: float
    setup_s: float
    chips: int
    trace: Optional["TraceView"] = None


@dataclasses.dataclass
class TraceView:
    device: Dict
    stages: List[tuple]


# ------------------------------------------------------------------ a run
def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             config: Optional[Dict] = None, load: Optional[Dict] = None,
             plant: Optional[Callable] = None,
             control: bool = False, log=print) -> Dict:
    """Run one cell. ``config``/``load`` replace the cell's files (tests
    run tiny sizes on the CPU); ``plant(stage, last, payload)`` may
    replace a served payload (tests break the timed path with it).
    ``control``: the reference in bfloat16 takes the served logits' place
    before ``correct`` is decided (the control, which has to fail); the
    served logits stay in each sample under ``served``."""
    spec = bench()
    wl = workload(spec, name)
    chips = wl["chips"]
    devs = device_check(chips) if require_chip else jax.devices()
    use_compile_cache()
    cfg = config or load_json("configs", f"{wl['config']}.json")
    tr = load or load_json("traffic", f"{wl['traffic']}.json")
    mod = load_module("models", cfg["family"])
    lim = check.limits(HERE, cfg["name"])

    rng = np.random.default_rng(seed)
    streams = traffic_mod.streams(tr, cfg["name"], rng)
    pool = mod.image_pool(cfg, tr["image_pool"], rng)
    key = jax.random.PRNGKey(
        int(np.random.SeedSequence(seed).generate_state(1)[0]))
    picks = rng.integers(0, len(pool), 4096)
    # keep about four times the sample's final logits, by a seeded hash of
    # the job's id, so the window holds enough to draw from
    keep = min(1.0, 4.0 * tr["sample_jobs"] / max(
        1.0, len(streams) * tr["jps"] * seconds))
    salt = int(rng.integers(1 << 30))
    rec = Recorder(lambda job: (job * 2654435761 + salt) % 1000
                   < keep * 1000, {s.name: s.index for s in streams})

    warm = tr["warmup_s"] * 1000.0
    w0, w1 = warm, warm + seconds * 1000.0
    # a traced run measures its window untraced, then traces a segment
    # after it: the profiler's start stalls the engine and the trace
    # slows the host, and neither may touch the window
    traced = (w1, tr["trace_s"] * 1000.0) if trace else None
    horizon = w1 + tr["grace_ms"] + (
        SETTLE_MS + tr["trace_s"] * 1000.0 if trace else 0.0)
    server, weights = _build(cfg, tr, mod, streams, key, pool, picks,
                             devs[:chips], horizon, plant, log)
    handles = [(s, server.request(s.name, float(due)))
               for s in streams for due in s.releases(horizon)]
    hooks = _Hooks(server, rec, traced)
    # the client's handles and the set-up's objects are long-lived: keep
    # the collector from walking them in every full collection
    gc.collect()
    gc.freeze()
    pauses = _GcPauses()
    compiles: List[float] = []   # host clock of each trace or compile

    def on_compile(event, secs, **kw):
        if event in COMPILE_EVENTS:
            compiles.append(time.perf_counter())
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        server.run()
        error = None
    except Exception as e:  # noqa: BLE001 — a raising payload: not correct
        error = f"{type(e).__name__}: {e}"
        log(f"serving raised: {error}")
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
        pauses.close()
        hooks.close()
        gc.unfreeze()
    log(f"garbage collector: {pauses.count} collections of the oldest "
        f"generation while serving, longest {pauses.longest * 1e3:.3f} ms")
    if hooks.t0 is not None:
        in_window = sum(hooks.t0 + w0 / 1e3 <= t < hooks.t0 + w1 / 1e3
                        for t in compiles)
        log(f"compilations while serving: {len(compiles)}, in the window: "
            f"{in_window}")
    setup_s = (hooks.t0 if hooks.t0 is not None else time.perf_counter()) \
        - t_start + warm / 1000.0
    used = devs[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)

    reqs_all = [_req(s, h) for s, h in handles]
    reqs = [r for r in reqs_all if w0 <= r.due < w1]
    t0 = hooks.t0 or 0.0
    execs = [(j, t, st, (s0 - t0) * 1e3, (s1 - t0) * 1e3)
             for j, t, st, s0, s1 in rec.stages]
    run = Run(reqs, [e for e in execs if w0 <= e[3] and e[4] <= w1],
              mod.stage_costs(cfg),
              load_json("peaks.json").get(devs[0].device_kind, {}),
              seconds, horizon, setup_s, chips)
    if hooks.window is not None:
        a, b = hooks.window
        seg = [r for r in reqs_all if a <= r.due < b]
        for prio in ("hp", "lp"):
            mine = [r for r in seg if r.prio == prio]
            log(f"traced segment {prio}: due {len(mine)}, admitted "
                f"{sum(r.admitted for r in mine)}, met "
                f"{sum(r.met for r in mine)}")
        if hooks.device:
            run.trace = TraceView(hooks.device,
                                  [e for e in execs
                                   if a <= e[3] and e[4] <= b])

    # correctness, after the window: sample, free the program, compare
    srng = np.random.default_rng([seed, 1])
    samples = _sample(reqs, rec, picks, tr["sample_jobs"], srng)
    del server, handles
    rec.stages.clear()
    tasks = {s["task"] for s in samples}
    weights = {i: w for i, w in weights.items() if i in tasks}
    gc.collect()
    ref = check.Reference(mod, cfg, used[0])
    for s in samples:
        params, image = weights[s["task"]], pool[s["img"]]
        s["reference"] = ref.logits("stated", params, image)
        if control:
            s["served"] = s["logits"]
            s["logits"] = ref.logits("control", params, image)
    gaps = [check.gap(s["logits"], s["reference"]) for s in samples]
    mean = sum(gaps) / len(gaps) if gaps else float("inf")
    log(f"logit gap of the worst job {max(gaps, default=float('inf'))} "
        f"(for information; the mean over the jobs is compared)")
    wrong = len(samples) if not mean <= lim["logit_gap_mean"] else 0
    odd = sum(r.status in ("cancelled", "aborted") for r in reqs)
    checks = {
        "logit_gap_mean": {"value": mean, "limit": lim["logit_gap_mean"]},
        "jobs_compared": {"value": len(samples), "limit": 1},
        "chips_compared": {"value": len({s["device"] for s in samples}),
                           "limit": min(chips, tr["contexts"])},
        "payload_errors": {"value": int(error is not None), "limit": 0},
    }
    correct = (error is None and not wrong
               and checks["jobs_compared"]["value"] >= 1
               and checks["chips_compared"]["value"]
               >= checks["chips_compared"]["limit"])
    return {"run": run, "correct": correct, "attempted": len(reqs),
            "failed": wrong + odd + int(error is not None),
            "checks": checks, "memory_peak_bytes": peak, "devices": devs,
            "samples": samples, "weights": weights, "pool": pool,
            "reference": ref}


def _build(cfg, tr, mod, streams, key, pool, picks, devs, horizon, plant,
           log):
    """Weights on the device, the served task specs, the built server."""
    from jax.sharding import SingleDeviceSharding
    from repro.api import (HP, LP, DeviceModel, ManualArrival,
                           ServerConfig)
    from repro.models.cnn import StagedCNN
    from repro.serving.engine import staged_cnn_taskspec

    model, shapes = mod.program_stages(cfg)
    init = jax.jit(lambda k: mod.init_params(cfg, k))
    mine = jax.eval_shape(init, key)
    if jax.tree.structure(mine) != jax.tree.structure(shapes) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(mine),
                                               jax.tree.leaves(shapes))):
        raise SystemExit("the benchmark's weights do not fit the "
                         "program's stage functions")
    with jax.default_device(devs[0]):
        weights = {s.index: init(jax.random.fold_in(key, s.index))
                   for s in streams}
    contexts = tr["contexts"]
    devices = devs[:contexts]
    specs, took = [], []
    for s in streams:
        m = StagedCNN(name=s.name, params=weights[s.index],
                      stages=model.stages, input_hw=cfg["image_size"],
                      n_classes=cfg["num_classes"])
        t = time.perf_counter()
        spec = staged_cnn_taskspec(
            m, priority=HP if s.priority == traffic_mod.HP else LP,
            jps=tr["jps"], input_hw=cfg["image_size"],
            n_sat=float(contexts), devices=devices)
        took.append(time.perf_counter() - t)
        if plant is not None:
            last = len(spec.stages) - 1
            for j, st in enumerate(spec.stages):
                st.payload = plant(j, j == last, st.payload)
        specs.append(spec)
    log(f"set-up: {len(specs)} task specs in {sum(took):.3f} s; first "
        f"{took[0]:.3f} s, slowest later one "
        f"{max(took[1:], default=0.0):.3f} s (milliseconds when the "
        f"later tasks reuse the first one's compiled stages)")

    def factory(job):
        return pool[picks[job.job_id % len(picks)]]

    shardings = ({k: SingleDeviceSharding(d) for k, d in enumerate(devices)}
                 if len(devices) > 1 else None)
    cfg_s = (ServerConfig.realtime()
             .tasks(specs)
             .contexts(contexts).streams(tr["streams"])
             .oversubscribe(tr["oversubscription"])
             .device(DeviceModel(n_units=float(contexts)))
             .horizon_ms(horizon)
             .phase_offsets(False)
             .realtime_io(input_hw=cfg["image_size"], input_factory=factory,
                          ctx_shardings=shardings))
    for spec in specs:
        cfg_s.arrival(spec.name, ManualArrival())
    return cfg_s.build(), weights


class _Hooks:
    """Hooks on the engine thread's calls into the built server's backend.

    Every run: the engine's start instant, and the final logits of the
    kept jobs as each job retires (``on_job_done``). The program hands no
    job's output back, so this reads the backend's per-job state, which
    holds the last stage's result until that call drops it.

    ``traced`` = (start, length) in ms: a traced run. The hooks then also
    record each stage's launch and, at harvest, the execution time its
    worker reported; at ``start`` the engine thread starts the profiler
    and turns the spans on; ``SETTLE_MS`` later it opens the
    ``bench.window`` marker, ``length`` after that it closes it and stops
    the profiler."""

    def __init__(self, server, rec: Recorder,
                 traced: Optional[tuple] = None):
        self.t0 = None
        self.window = None        # (start, end) ms of the traced segment
        self.device = None        # the trace's reduction
        self._dir = None
        self._marker = None
        self._started = None
        backend, sched = server.backend, server.scheduler
        start, job_done = backend.start, backend.on_job_done

        def started():
            start()
            self.t0 = time.perf_counter()

        def on_job_done(job):
            if rec.keep(job.job_id):
                out = backend._job_state.get(job.job_id)
                if out is not None:
                    rec.outputs[job.job_id] = (rec.index[job.task.name],
                                               out)
            job_done(job)
        backend.start = started
        backend.on_job_done = on_job_done
        if traced is None:
            return
        at, length = traced
        advance, launch = backend.advance, backend.launch
        on_release = sched.on_release

        def traced_advance(cap_ms):
            now = backend.now_ms()
            if self._started is None and now >= at:
                self._dir = tempfile.mkdtemp(prefix="perfbench-trace-")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(self._dir, profiler_options=opts)
                rec.trace = True
                self._started = backend.now_ms()
            elif (self._marker is None and self._started is not None
                  and self.window is None
                  and now >= self._started + SETTLE_MS):
                self._marker = jax.profiler.TraceAnnotation(tracing.WINDOW)
                self._marker.__enter__()
                self._a = now
            elif self._marker is not None and now >= self._a + length:
                self._marker.__exit__(None, None, None)
                self._marker = None
                self.window = (self._a, now)
                rec.trace = False
                jax.profiler.stop_trace()
            with rec.span("backend.advance"):
                done = advance(cap_ms)
            for c in done:
                job = c.inst.job
                t = rec.launched.pop((job.job_id, job.stage_idx), None)
                if t is not None:
                    rec.stages.append((job.job_id, rec.index[job.task.name],
                                       job.stage_idx, t, t + c.et_ms / 1e3))
            return done

        def traced_launch(lane, inst):
            job = inst.job
            rec.launched[(job.job_id, job.stage_idx)] = time.perf_counter()
            with rec.span("backend.launch"):
                return launch(lane, inst)

        def traced_release(task, now):
            with rec.span("sched.on_release"):
                return on_release(task, now)

        backend.advance = traced_advance
        backend.launch = traced_launch
        sched.on_release = traced_release

    def close(self) -> None:
        """Stop a trace the run left running, reduce a whole one, delete
        it."""
        if self._dir is None:
            return
        import shutil
        if self.window is None:
            if self._marker is not None:
                self._marker.__exit__(None, None, None)
            jax.profiler.stop_trace()
        else:
            self.device = tracing.read_profile(self._dir)
        shutil.rmtree(self._dir, ignore_errors=True)
        self._dir = None


class _GcPauses:
    """Counts and times full (oldest-generation) collections."""

    def __init__(self):
        self.count, self.longest, self._t = 0, 0.0, None
        gc.callbacks.append(self._hook)

    def _hook(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.count += 1
            self.longest = max(self.longest, time.perf_counter() - self._t)

    def close(self):
        gc.callbacks.remove(self._hook)


def _req(stream, h) -> Req:
    finish = (h.release_ms + h.response_ms
              if h.response_ms is not None else None)
    return Req(stream.priority, h.at_ms, h.release_ms, finish, h.status,
               h.task.spec.deadline_ms,
               h.job.job_id if h.job is not None else None)


def _sample(reqs: List[Req], rec: Recorder, picks: np.ndarray, k: int,
            rng: np.random.Generator) -> List[Dict]:
    """Up to ``k`` in-window jobs that finished their last stage and whose
    logits were kept, drawn from the seed round-robin over the chips that
    served them."""
    by_dev: Dict[int, List[int]] = {}
    for r in reqs:
        if r.status in ("completed", "missed") and r.job in rec.outputs:
            dev = rec.outputs[r.job][1].device.id
            by_dev.setdefault(dev, []).append(r.job)
    queues = [list(rng.permutation(v)) for _, v in sorted(by_dev.items())]
    picked: List[int] = []
    while len(picked) < k and any(queues):
        for q in queues:
            if q and len(picked) < k:
                picked.append(int(q.pop()))
    out = []
    for job in picked:
        task, logits = rec.outputs[job]
        out.append({"job": job, "task": task,
                    "img": int(picks[job % len(picks)]),
                    "device": logits.device.id,
                    "logits": np.asarray(logits)})
    return out
