"""End-to-end behaviour tests: the paper's headline claims hold in-sim,
checkpoint round-trips, data pipeline resume, real-execution engine."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core.scheduler import DarisScheduler, SchedulerConfig
from repro.core.task import HP, LP
from repro.runtime.contention import DeviceModel
from repro.runtime.sim import SimEngine
from repro.serving.profiles import TABLE1, device
from repro.serving.requests import table2_taskset


def _run(nc, ns, os_, dnn="resnet18", horizon=4000.0, **kw):
    sched = DarisScheduler(
        table2_taskset(dnn),
        SchedulerConfig(n_contexts=nc, n_streams=ns, oversubscription=os_,
                        **kw), device())
    return SimEngine(sched, horizon_ms=horizon, seed=0).run(), sched


def test_no_hp_misses_and_low_lp_dmr():
    m, _ = _run(6, 1, 6.0)
    assert m.dmr(HP) == 0.0                  # paper: no HP misses observed
    assert m.dmr(LP) < 0.10                  # paper: <7% worst (MPS)


def test_hp_responses_faster_than_lp():
    m, _ = _run(6, 1, 6.0)
    hp = m.resp_stats(HP)["mean"]
    lp = m.resp_stats(LP)["mean"]
    assert hp < lp                            # paper: ~2.5x faster
    assert lp / hp > 1.5


def test_oversubscription_beats_batching_baseline():
    """DARIS (no batching) exceeds the pure-batching upper baseline
    (paper: +13% for RN18); without oversubscription it falls below."""
    best = 0.0
    for nc in (4, 6, 8):
        m, _ = _run(nc, 1, float(nc))
        best = max(best, m.jps)
    assert best > TABLE1["resnet18"][1]       # beats 1025 JPS
    m_iso, _ = _run(8, 1, 1.0)
    assert m_iso.jps <= best


def test_overload_hpa_protects_hp():
    from repro.serving.requests import ratio_taskset
    upper = TABLE1["resnet18"][1]
    specs = ratio_taskset("resnet18", 0.85, 30, upper * 2.0 / 30)
    sched = DarisScheduler(specs, SchedulerConfig(
        n_contexts=6, n_streams=1, oversubscription=6.0, overload_hpa=True),
        device())
    m = SimEngine(sched, horizon_ms=3000.0, seed=0).run()
    assert m.dmr(HP) < 0.02                   # HPA: near-zero HP misses
    assert m.rejected[HP] > 0                 # at the cost of HP rejections


def test_migration_happens_under_pressure():
    m, sched = _run(6, 1, 2.0)
    assert sched.migrations > 0


def test_scheduler_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import load_scheduler_state, save_scheduler_state
    m, sched = _run(4, 1, 2.0, horizon=1500.0)
    path = str(tmp_path / "sched.msgpack")
    save_scheduler_state(sched, path)
    sched2 = DarisScheduler(
        table2_taskset("resnet18"),
        SchedulerConfig(n_contexts=4, n_streams=1, oversubscription=2.0),
        device())
    load_scheduler_state(sched2, path)
    for a, b in zip(sched.tasks, sched2.tasks):
        assert a.ctx == b.ctx
        assert a.mret.task_mret() == pytest.approx(b.mret.task_mret())


def test_params_checkpoint_roundtrip(tmp_path):
    import jax.numpy as jnp
    from repro.checkpoint import load_pytree, save_pytree
    from repro.configs import get_reduced
    from repro.models import build_model
    m = build_model(get_reduced("smollm-135m"))
    params = m.init_params(0)
    save_pytree(params, str(tmp_path / "p"), step=7)
    zeros = __import__("jax").tree.map(lambda a: jnp.zeros_like(a), params)
    restored = load_pytree(zeros, str(tmp_path / "p"))
    flat_a = __import__("jax").tree.leaves(params)
    flat_b = __import__("jax").tree.leaves(restored)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_data_pipeline_deterministic_resume():
    from repro.data.pipeline import TokenPipeline
    p1 = TokenPipeline(1000, 4, 32, seed=3)
    b0 = p1.next_batch()
    b1 = p1.next_batch()
    state = p1.state_dict()
    b2 = p1.next_batch()
    p2 = TokenPipeline(1000, 4, 32, seed=3)
    p2.load_state_dict(state)
    b2r = p2.next_batch()
    np.testing.assert_array_equal(b2["tokens"], b2r["tokens"])
    assert b0["tokens"].max() < 1000
    assert not np.array_equal(b0["tokens"], b1["tokens"])


@pytest.mark.slow
def test_realtime_engine_with_staged_lm_decode():
    """Real staged LM decode under DARIS: one decode step per job, split
    into stage programs; inter-stage state carries hidden + KV-cache
    slices (serving.staging.slice_cache) so migrations move real state."""
    from repro.api import ServerConfig
    from repro.configs import get_reduced
    from repro.models import build_model
    from repro.serving.engine import staged_lm_taskspec
    model = build_model(get_reduced("smollm-135m").replace(n_layers=8))
    spec = staged_lm_taskspec(model, priority=HP, jps=10.0, n_stages=4,
                              prompt_len=8, batch=1, tag="-hp")
    srv = (ServerConfig.realtime()
           .tasks([spec])
           .contexts(2).oversubscribe(2.0)
           .device(DeviceModel(n_units=2.0))
           .horizon_ms(1200.0)
           .build())
    m = srv.run()
    assert m.completed[HP] > 0
    assert m.resp_stats(HP)["mean"] > 0


@pytest.mark.slow
def test_realtime_engine_with_cnn_stages():
    """Real JAX execution: tiny staged CNNs under DARIS on wall clock."""
    from repro.core.scheduler import DarisScheduler, SchedulerConfig
    from repro.models.cnn import build_resnet
    from repro.serving.engine import RealtimeEngine, staged_cnn_taskspec
    model = build_resnet(18, width=8)
    specs = [
        staged_cnn_taskspec(model, priority=HP, jps=20.0, input_hw=32,
                            tag="-hp"),
        staged_cnn_taskspec(model, priority=LP, jps=20.0, input_hw=32,
                            tag="-lp0"),
    ]
    sched = DarisScheduler(specs, SchedulerConfig(
        n_contexts=2, n_streams=1, oversubscription=2.0),
        DeviceModel(n_units=2.0))
    eng = RealtimeEngine(sched, horizon_ms=1500.0, input_hw=32)
    m = eng.run()
    assert m.completed[HP] > 0
    assert m.resp_stats(HP)["mean"] > 0


# ------------------------------------------------- chip entry points (CPU)
_ROOT = Path(__file__).resolve().parents[1]


def _python(code: str, tmp_path, **env) -> subprocess.CompletedProcess:
    """Run ``code`` in a fresh interpreter, so that JAX flags and config
    set there never reach this test process."""
    full_env = {**os.environ, "PYTHONPATH": str(_ROOT / "src"),
                "JAX_PLATFORMS": "cpu", **env}
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          env=full_env, cwd=tmp_path, capture_output=True,
                          text=True, timeout=240)


def test_chip_smoke_refuses_cpu(tmp_path):
    """Without a TPU the smoke exits non-zero before building anything and
    prints no result line."""
    r = subprocess.run([sys.executable, str(_ROOT / "chip_smoke.py")],
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout == ""


def test_compile_cache_follows_env_else_checkout(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, entries land there and the
    program sets no directory of its own; unset, the cache directory is
    the fixed <checkout>/.jax_cache."""
    probe = """
        import jax, jax.numpy as jnp
        from repro.launch.serve import use_compile_cache
        use_compile_cache()
        print(jax.config.jax_compilation_cache_dir)
        print(jax.config.jax_persistent_cache_min_compile_time_secs)
        if {compile}:
            jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
    """
    cache = tmp_path / "cache"
    r = _python(probe.format(compile=True), tmp_path,
                JAX_COMPILATION_CACHE_DIR=str(cache))
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(cache), "0.0"]
    assert any(cache.iterdir())
    r = _python(probe.format(compile=False), tmp_path,
                JAX_COMPILATION_CACHE_DIR="")
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(_ROOT / ".jax_cache"), "0.0"]


def test_realtime_contexts_pinned_to_devices(tmp_path):
    """Two host devices stand in for two chips: context k runs on device
    k, fresh jobs start on their context's device, and a repartition
    reshards in-flight state across devices without changing a logit."""
    code = """
        import jax, numpy as np
        from repro.launch.serve import realtime_config
        from repro.models.cnn import build_resnet
        devs = jax.devices()
        assert len(devs) == 2
        image = np.random.default_rng(0).standard_normal(
            (1, 64, 64, 3)).astype(np.float32)
        model = build_resnet(18, width=8, n_classes=10)
        cfg, specs = realtime_config([model, model], contexts=2,
                                     seconds=1.5, hw=64,
                                     input_factory=lambda job: image)
        ref = jax.device_put(image, devs[0])
        for st in specs[0].stages:
            ref = st.payload(ref)
        ref = np.asarray(ref)
        seen, logits = set(), []
        def observed(payload, last):
            def run(x):
                out = payload(x)
                seen.add(out.device)
                if last:
                    logits.append(np.asarray(out))
                return out
            return run
        for spec in specs:
            for j, st in enumerate(spec.stages):
                st.payload = observed(st.payload, j == 3)
        for t in range(5):
            cfg.reconfigure_at(200.0 * (t + 1) + 2.0 * (t + 1), n_contexts=2)
        srv = cfg.build()
        m = srv.run()
        assert seen == set(devs), seen
        assert m.completed[0] > 0 and m.completed[1] > 0
        assert all(np.array_equal(x, ref) for x in logits)
        print(srv.backend.resharded)
    """
    r = _python(code, tmp_path,
                XLA_FLAGS="--xla_force_host_platform_device_count=2")
    assert r.returncode == 0, r.stderr[-3000:]
    assert int(r.stdout.split()[-1]) > 0        # state moved between devices
