"""Compile the served ResNet-18 stage programs at published size (width 64,
1000 classes, 224x224x3, batch 1) for one described TPU v5e chip. No chip
is attached: what the chip's compiler would refuse, or a program that does
not fit the chip's memory, fails here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.models.cnn import build_resnet

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def resnet18(one_chip):
    """Stage functions plus argument shapes placed on the described chip;
    the weights are shapes only (``eval_shape``), never materialized."""
    built = []
    params = jax.eval_shape(lambda: built.append(build_resnet(18))
                            or built[0].params)
    stages = built[0].stages

    def on_chip(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    x = jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32)
    inputs = []
    for st in stages:
        inputs.append(on_chip(x))
        x = jax.eval_shape(st, params, x)
    assert x.shape == (1, 1000)
    return stages, jax.tree.map(on_chip, params), inputs


@pytest.mark.parametrize("stage", range(4))
def test_resnet18_stage_compiles_for_v5e(stage, resnet18,
                                         no_persistent_cache):
    stages, params, inputs = resnet18
    compiled = jax.jit(stages[stage]).lower(params, inputs[stage]).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes
             - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES
