"""The paper's benchmark DNNs in JAX: ResNet18/50, UNet, InceptionV3(-lite).

These power the *real-execution* validation path (serving/engine.py): each
model exposes ``stages`` — the paper's logical stage boundaries (ResNet ->
its 4 residual stages, §III-B1) — as separately jittable callables, which
is exactly what DARIS schedules. NHWC layout, lax.conv. InceptionV3 keeps
the multi-branch A/B/C block structure at reduced depth (the property the
paper exercises — narrow parallel branches that batch well — is preserved).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List

import jax
import jax.numpy as jnp
import numpy as np

from .layers import InitCtx, dense_init


def conv_init(ctx: InitCtx, kh, kw, cin, cout):
    fan = kh * kw * cin
    w = jax.random.truncated_normal(ctx.next(), -2, 2, (kh, kw, cin, cout),
                                    jnp.float32) / np.sqrt(fan)
    return w.astype(ctx.dtype)


def conv(x, w, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def bn_apply(p, x):
    """Inference-style norm (scale/bias only; stats folded)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=(0, 1, 2), keepdims=True)
    var = jnp.var(xf, axis=(0, 1, 2), keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def bn_init(ctx, c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def _convbn(ctx, kh, kw, cin, cout):
    return {"w": conv_init(ctx, kh, kw, cin, cout), "bn": bn_init(ctx, cout)}


def _convbn_apply(p, x, stride=1, act=True):
    y = bn_apply(p["bn"], conv(x, p["w"], stride))
    return jax.nn.relu(y) if act else y


# ---------------------------------------------------------------- ResNet
def _basic_block(ctx, cin, cout, stride):
    p = {"c1": _convbn(ctx, 3, 3, cin, cout), "c2": _convbn(ctx, 3, 3, cout, cout)}
    if stride != 1 or cin != cout:
        p["proj"] = _convbn(ctx, 1, 1, cin, cout)
    return p


def _basic_apply(p, x, stride):
    y = _convbn_apply(p["c1"], x, stride)
    y = _convbn_apply(p["c2"], y, act=False)
    sc = _convbn_apply(p["proj"], x, stride, act=False) if "proj" in p else x
    return jax.nn.relu(y + sc)


def _bottleneck_block(ctx, cin, cmid, stride):
    cout = cmid * 4
    p = {"c1": _convbn(ctx, 1, 1, cin, cmid),
         "c2": _convbn(ctx, 3, 3, cmid, cmid),
         "c3": _convbn(ctx, 1, 1, cmid, cout)}
    if stride != 1 or cin != cout:
        p["proj"] = _convbn(ctx, 1, 1, cin, cout)
    return p


def _bottleneck_apply(p, x, stride):
    y = _convbn_apply(p["c1"], x)
    y = _convbn_apply(p["c2"], y, stride)
    y = _convbn_apply(p["c3"], y, act=False)
    sc = _convbn_apply(p["proj"], x, stride, act=False) if "proj" in p else x
    return jax.nn.relu(y + sc)


def _named(model: str, stages: List[Callable]) -> List[Callable]:
    """Name stage j ``<model>_s<j>``. jit names the stage's program after
    it (``jit_resnet18_s0``), which is how a profiler trace tells the
    stage programs apart."""
    for j, fn in enumerate(stages):
        fn.__name__ = fn.__qualname__ = f"{model}_s{j}"
    return stages


@dataclasses.dataclass
class StagedCNN:
    name: str
    params: dict
    stages: List[Callable]            # stage_fn(params, x) -> x
    input_hw: int = 64
    n_classes: int = 100

    def forward(self, params, x):
        for st in self.stages:
            x = st(params, x)
        return x


def build_resnet(depth: int = 18, *, seed: int = 0, n_classes: int = 1000,
                 width: int = 64) -> StagedCNN:
    """ResNet-18/50 (He et al., 2016). The defaults are the published
    ImageNet size: base width 64, 1000 classes, for 224x224x3 inputs."""
    ctx = InitCtx(jax.random.PRNGKey(seed), jnp.float32)
    basic = depth == 18
    blocks_per = {18: (2, 2, 2, 2), 50: (3, 4, 6, 3)}[depth]
    widths = (width, width * 2, width * 4, width * 8)
    params = {"stem": _convbn(ctx, 7, 7, 3, width)}
    cin = width
    for si, (n, w) in enumerate(zip(blocks_per, widths)):
        blocks = []
        for bi in range(n):
            stride = 2 if (bi == 0 and si > 0) else 1
            if basic:
                blocks.append(_basic_block(ctx, cin, w, stride))
                cin = w
            else:
                blocks.append(_bottleneck_block(ctx, cin, w, stride))
                cin = w * 4
        params[f"stage{si}"] = blocks
    params["head"] = dense_init(ctx, (cin, n_classes))

    def make_stage(si):
        def fn(p, x):
            if si == 0:
                x = _convbn_apply(p["stem"], x, 2)
                x = jax.lax.reduce_window(
                    x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                    "SAME")
            for bi, bp in enumerate(p[f"stage{si}"]):
                stride = 2 if (bi == 0 and si > 0) else 1
                x = (_basic_apply(bp, x, stride) if basic
                     else _bottleneck_apply(bp, x, stride))
            if si == 3:
                x = jnp.mean(x, axis=(1, 2))
                x = x @ p["head"]
            return x
        return fn

    return StagedCNN(name=f"resnet{depth}", params=params,
                     stages=_named(f"resnet{depth}",
                                   [make_stage(i) for i in range(4)]),
                     n_classes=n_classes)


# ---------------------------------------------------------------- UNet
def build_unet(*, seed: int = 0, width: int = 24) -> StagedCNN:
    ctx = InitCtx(jax.random.PRNGKey(seed), jnp.float32)
    ws = (width, width * 2, width * 4, width * 8)
    params = {}
    cin = 3
    for i, w in enumerate(ws):
        params[f"down{i}"] = {"c1": _convbn(ctx, 3, 3, cin, w),
                              "c2": _convbn(ctx, 3, 3, w, w)}
        cin = w
    params["mid"] = {"c1": _convbn(ctx, 3, 3, cin, cin * 2),
                     "c2": _convbn(ctx, 3, 3, cin * 2, cin)}
    for i, w in reversed(list(enumerate(ws))):
        cin_up = ws[min(i + 1, len(ws) - 1)] + w   # upsampled x + skip
        params[f"up{i}"] = {"c1": _convbn(ctx, 3, 3, cin_up, w),
                            "c2": _convbn(ctx, 3, 3, w, w)}
    params["out"] = conv_init(ctx, 1, 1, ws[0], 2)

    def down_path(p, x, rng=(0, 2)):
        skips = x[1] if isinstance(x, tuple) else []
        x = x[0] if isinstance(x, tuple) else x
        for i in range(*rng):
            blk = p[f"down{i}"]
            x = _convbn_apply(blk["c2"], _convbn_apply(blk["c1"], x))
            skips = skips + [x]
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "SAME")
        return (x, skips)

    def stage0(p, x):
        return down_path(p, x, (0, 2))

    def stage1(p, x):
        x, skips = down_path(p, x, (2, 4))
        blk = p["mid"]
        x = _convbn_apply(blk["c2"], _convbn_apply(blk["c1"], x))
        return (x, skips)

    def up_path(p, state, rng):
        x, skips = state
        for i in rng:
            sk = skips[i]
            b, h, w, c = sk.shape
            x = jax.image.resize(x, (b, h, w, x.shape[-1]), "nearest")
            x = jnp.concatenate([x, sk], axis=-1)
            blk = p[f"up{i}"]
            x = _convbn_apply(blk["c2"], _convbn_apply(blk["c1"], x))
        return (x, skips)

    def stage2(p, state):
        return up_path(p, state, (3, 2))

    def stage3(p, state):
        x, _ = up_path(p, state, (1, 0))
        return conv(x, p["out"])

    return StagedCNN(name="unet", params=params,
                     stages=_named("unet", [stage0, stage1, stage2, stage3]))


# ------------------------------------------------------------ InceptionV3
def _inception_a(ctx, cin, w):
    return {
        "b1": _convbn(ctx, 1, 1, cin, w),
        "b2a": _convbn(ctx, 1, 1, cin, w), "b2b": _convbn(ctx, 5, 5, w, w),
        "b3a": _convbn(ctx, 1, 1, cin, w), "b3b": _convbn(ctx, 3, 3, w, w),
        "b3c": _convbn(ctx, 3, 3, w, w),
        "bp": _convbn(ctx, 1, 1, cin, w),
    }


def _inception_a_apply(p, x):
    b1 = _convbn_apply(p["b1"], x)
    b2 = _convbn_apply(p["b2b"], _convbn_apply(p["b2a"], x))
    b3 = _convbn_apply(p["b3c"], _convbn_apply(p["b3b"],
                                               _convbn_apply(p["b3a"], x)))
    pool = jax.lax.reduce_window(x, 0.0, jax.lax.add, (1, 3, 3, 1),
                                 (1, 1, 1, 1), "SAME") / 9.0
    bp = _convbn_apply(p["bp"], pool)
    return jnp.concatenate([b1, b2, b3, bp], axis=-1)


def build_inception(*, seed: int = 0, width: int = 24,
                    n_classes: int = 100) -> StagedCNN:
    ctx = InitCtx(jax.random.PRNGKey(seed), jnp.float32)
    params = {
        "stem1": _convbn(ctx, 3, 3, 3, width),
        "stem2": _convbn(ctx, 3, 3, width, width * 2),
    }
    cin = width * 2
    for i in range(3):
        params[f"a{i}"] = _inception_a(ctx, cin, width)
        cin = width * 4
    params["red"] = _convbn(ctx, 3, 3, cin, cin)
    for i in range(2):
        params[f"b{i}"] = _inception_a(ctx, cin, width * 2)
        cin = width * 8
    params["head"] = dense_init(ctx, (cin, n_classes))

    def stage0(p, x):
        x = _convbn_apply(p["stem1"], x, 2)
        x = _convbn_apply(p["stem2"], x, 1)
        return _inception_a_apply(p["a0"], x)

    def stage1(p, x):
        x = _inception_a_apply(p["a1"], x)
        return _inception_a_apply(p["a2"], x)

    def stage2(p, x):
        x = _convbn_apply(p["red"], x, 2)
        return _inception_a_apply(p["b0"], x)

    def stage3(p, x):
        x = _inception_a_apply(p["b1"], x)
        x = jnp.mean(x, axis=(1, 2))
        return x @ p["head"]

    return StagedCNN(name="inceptionv3", params=params,
                     stages=_named("inceptionv3",
                                   [stage0, stage1, stage2, stage3]))


BUILDERS = {
    "resnet18": lambda **kw: build_resnet(18, **kw),
    "resnet50": lambda **kw: build_resnet(50, **kw),
    "unet": build_unet,
    "inceptionv3": build_inception,
}
