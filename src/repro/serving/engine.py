"""Deprecated shim: ``RealtimeEngine`` now delegates to the unified runtime.

Real execution (worker threads running jitted stage functions on wall
clock, measured times feeding MRET) lives in ``RealtimeBackend``
(runtime/backend.py), driven by the same ``EngineCore`` loop as the
simulator. New code should construct servers through the ``repro.api``
facade:

    from repro.api import ServerConfig
    metrics = (ServerConfig.realtime().tasks(specs).contexts(2)
               .horizon_ms(4000).realtime_io(input_hw=32).build().run())

``staged_cnn_taskspec`` / ``staged_lm_taskspec`` (AFET-style calibration
of staged models into TaskSpecs with jitted payloads) still live here;
``RealtimeEngine`` remains importable for one release.
"""
from __future__ import annotations

import time
import warnings
from typing import Callable, List, Optional, Sequence

import jax
import numpy as np

from ..core.metrics import RunMetrics
from ..core.scheduler import DarisScheduler
from ..core.task import StageProfile, TaskSpec
from ..models.cnn import StagedCNN
from ..runtime.arrivals import PeriodicArrival
from ..runtime.backend import RealtimeBackend
from ..runtime.engine_core import EngineCore

__all__ = ["RealtimeEngine", "staged_cnn_taskspec", "staged_lm_taskspec"]


def _timed_call(fn: Callable, x) -> tuple:
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(x))
    return out, (time.perf_counter() - t0) * 1000.0


def staged_cnn_taskspec(model: StagedCNN, *, priority: int, jps: float,
                        input_hw: int = 64, batch: int = 1,
                        tag: str = "", calibrate: bool = True,
                        n_sat: float = 40.0, mem_frac: float = 0.4,
                        devices: Optional[Sequence] = None) -> TaskSpec:
    """Wrap a StagedCNN into a TaskSpec whose stage payloads are jitted
    callables; t_alone is measured on this machine (AFET-style).

    The weights are placed once on each of ``devices`` (default: the
    first device). A payload runs on the device its input lives on, with
    that device's copy of the weights, so a job the backend migrates to
    another chip computes there without moving weights. Calibration
    compiles every stage for every device, records the first call on the
    first device as ``first_call_ms`` and times a second call there as
    ``t_alone_ms``."""
    devices = list(devices or jax.devices()[:1])
    params_on = {d: jax.device_put(model.params, d) for d in devices}

    def make_payload(st):
        def payload(x):     # x: an image, or UNet's (x, skips) tuple
            return st(params_on[jax.tree.leaves(x)[0].device], x)
        return payload

    x0 = np.zeros((batch, input_hw, input_hw, 3), np.float32)
    states = {d: jax.device_put(x0, d) for d in devices}
    payloads: List[Callable] = []
    first, times = [], []
    for stage in model.stages:
        fn = make_payload(jax.jit(stage))
        if calibrate:
            outs = {}
            for d in devices:
                outs[d], ms = _timed_call(fn, states[d])
                if d == devices[0]:
                    first.append(ms)
            times.append(_timed_call(fn, states[devices[0]])[1])
            states = outs
        payloads.append(fn)
    if not calibrate:
        first = [0.0] * len(payloads)
        times = [1.0] * len(payloads)
    stages = [StageProfile(name=f"{model.name}/s{j}", t_alone_ms=t,
                           n_sat=n_sat, mem_frac=mem_frac, overhead_ms=0.05,
                           payload=payloads[j], first_call_ms=first[j])
              for j, t in enumerate(times)]
    return TaskSpec(name=f"{model.name}{tag}", period_ms=1000.0 / jps,
                    priority=priority, stages=stages, batch=batch)


def staged_lm_taskspec(model, *, priority: int, jps: float,
                       n_stages: int = 4, prompt_len: int = 16,
                       batch: int = 2, tag: str = "",
                       n_sat: float = 40.0, mem_frac: float = 0.5
                       ) -> TaskSpec:
    """Wrap a staged LM decode step into a TaskSpec with real payloads.

    Each job is ONE decode step split across ``n_stages`` stage programs
    (``serving.staging.make_lm_stage_fns``). The inter-stage state that
    rides between payloads — and that ``RealtimeBackend`` reshards via
    ``serving.staging.migrate`` when the job crosses partitions — is the
    hidden activation plus the KV-cache slices touched so far: each stage
    pulls its layer slice from a prefilled donor cache with
    ``serving.staging.slice_cache`` and threads the updated slice
    forward, so a migration physically moves hidden AND cache, exactly
    the paper's zero-delay payload."""
    import jax.numpy as jnp

    from .staging import make_lm_stage_fns, slice_cache

    cfg = model.cfg
    params = model.init_params(0)
    stage_fns = make_lm_stage_fns(model, n_stages=n_stages)
    jitted = [jax.jit(fn) for fn in stage_fns]
    # prefill a donor cache once with the model's own forward; every job
    # then decodes one token against (its thread of) that cache
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, prompt_len)))
    _, donor = model.prefill(
        params, {"tokens": tokens,
                 "cache": model.init_cache(batch, prompt_len + 1)})
    pos = jnp.asarray([prompt_len], dtype=jnp.int32)

    def make_payload(i):
        def payload(state):
            if state is None or not isinstance(state, dict):
                # fresh job: one new token per sequence
                state = {"hidden": jnp.zeros((batch, 1), jnp.int32),
                         "slices": {}}
            sl = state["slices"].get(i)
            if sl is None:
                sl = slice_cache(cfg, donor, i, n_stages)
            h, new_sl = jitted[i](params, state["hidden"], sl, pos)
            return {"hidden": h, "slices": {**state["slices"], i: new_sl}}
        return payload

    times = []
    state = None
    payloads = []
    for i in range(n_stages):
        fn = make_payload(i)
        out = fn(state)                           # compile
        jax.block_until_ready(out["hidden"])
        t0 = time.perf_counter()
        out = fn(state)
        jax.block_until_ready(out["hidden"])
        times.append((time.perf_counter() - t0) * 1000.0)
        state = out
        payloads.append(fn)
    stages = [StageProfile(name=f"{cfg.name}/lm-s{j}", t_alone_ms=t,
                           n_sat=n_sat, mem_frac=mem_frac,
                           overhead_ms=0.05, payload=payloads[j])
              for j, t in enumerate(times)]
    return TaskSpec(name=f"{cfg.name}{tag}", period_ms=1000.0 / jps,
                    priority=priority, stages=stages, batch=batch)


class RealtimeEngine:
    """Thin deprecated wrapper: EngineCore + RealtimeBackend with the
    historic constructor signature. Prefer ``repro.api.DarisServer``."""

    def __init__(self, sched: DarisScheduler, horizon_ms: float,
                 input_hw: int = 64, batch: int = 1):
        warnings.warn(
            "RealtimeEngine is deprecated; build a server via repro.api."
            "ServerConfig.realtime() instead", DeprecationWarning,
            stacklevel=2)
        self.core = EngineCore(
            sched, RealtimeBackend(input_hw=input_hw, batch=batch),
            horizon_ms=horizon_ms,
            arrivals={t.index: PeriodicArrival(phase_ms=0.0)
                      for t in sched.tasks})
        self.sched = sched

    @property
    def metrics(self) -> RunMetrics:
        return self.core.metrics

    def run(self) -> RunMetrics:
        return self.core.run()
