"""The yardstick's work counts against the published totals, and the
plain reference against the program's staged forward."""
import jax
import numpy as np
import pytest

import harness
import tiny

resnet = harness.load_module("models", "resnet")


@pytest.mark.parametrize("name,gflop,mparams", [("resnet18", 3.6, 11.69),
                                                ("resnet50", 8.2, 25.56)])
def test_counts_match_published_totals(name, gflop, mparams):
    cfg = harness.load_json("configs", f"{name}.json")
    costs = resnet.stage_costs(cfg)
    # He et al. Table 1 gives 1.8 and 3.8 GFLOPs of multiply-adds (x2 =
    # 3.6, 7.6); torchvision's count for the stride-on-3x3 ResNet-50 is
    # 4.1 (x2 = 8.2). 3% covers their rounding to two digits.
    assert sum(c["flops"] for c in costs) / 1e9 == pytest.approx(gflop,
                                                                  rel=0.03)
    weights = sum(c["weight_bytes"] for c in costs) / 4 / 1e6
    assert weights == pytest.approx(mparams, abs=0.01)
    _, shapes = resnet.program_stages(cfg)
    assert sum(np.prod(a.shape) for a in jax.tree.leaves(shapes)) / 1e6 \
        == pytest.approx(weights, abs=1e-6)


def test_reference_matches_program_forward():
    cfg = tiny.config()
    model, _ = resnet.program_stages(cfg)
    params = jax.jit(lambda k: resnet.init_params(cfg, k))(
        jax.random.PRNGKey(3))
    x = np.random.default_rng(0).standard_normal(
        (1, 64, 64, 3)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(lambda p, x: resnet.forward(cfg, p, x))(
            params, x))
        prog = np.asarray(jax.jit(model.forward)(params, x))
    assert ref.shape == (1, 10)
    assert np.abs(prog - ref).max() <= 1e-5 * np.abs(ref).max()
