"""ResNet family for the benchmark: weights, the plain reference, the
operation and byte counts, and the served task set.

Weights and the reference are written here from the paper (He et al.
2016, Table 1), independent of the program's ``repro.models.cnn``. The
program supplies only the system under test: its four stage functions,
which ``staged_cnn_taskspec`` wraps into the served payloads.

One departure from the paper is the program's, and the reference follows
it: every norm standardises each channel over the image's own spatial
positions (batch 1), then applies a per-channel scale and bias. A
deployed ResNet folds running statistics into that scale and bias; the
arithmetic per element is the same.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-5
F32 = 4  # bytes


def _blocks(cfg: Dict) -> List[Tuple[int, int, int, int]]:
    """(stage, cin, width, stride) of every residual block."""
    out, cin = [], cfg["base_width"]
    for si, (n, w) in enumerate(zip(cfg["blocks_per_stage"],
                                    cfg["stage_widths"])):
        for bi in range(n):
            out.append((si, cin, w, 2 if (bi == 0 and si > 0) else 1))
            cin = w * cfg["expansion"]
    return out


def _block_convs(cfg: Dict, cin: int, w: int, stride: int):
    """(name, k, cin, cout, stride) of one block's convolutions, in the
    order the block applies them; ``proj`` is the shortcut projection."""
    cout = w * cfg["expansion"]
    if cfg["block"] == "basic":
        convs = [("c1", 3, cin, w, stride), ("c2", 3, w, w, 1)]
    else:   # bottleneck, stride on the 3x3 (the program's placement)
        convs = [("c1", 1, cin, w, 1), ("c2", 3, w, w, stride),
                 ("c3", 1, w, cout, 1)]
    if stride != 1 or cin != cout:
        convs.append(("proj", 1, cin, cout, stride))
    return convs


# ------------------------------------------------------------------ weights
def init_params(cfg: Dict, key) -> Dict:
    """One task's weights, made on the device in one traced call (jit it).
    Convolutions: truncated normal over sqrt(fan-in); norm scale 1 + 0.1 N,
    bias 0.1 N (random, so that a dropped scale or bias shows); classifier
    normal over sqrt(fan-in)."""
    keys = iter(jax.random.split(key, 4096))

    def conv(k, cin, cout):
        w = jax.random.truncated_normal(next(keys), -2.0, 2.0,
                                        (k, k, cin, cout), jnp.float32)
        return w / math.sqrt(k * k * cin)

    def norm(c):
        return {"scale": 1.0 + 0.1 * jax.random.normal(next(keys), (c,)),
                "bias": 0.1 * jax.random.normal(next(keys), (c,))}

    def convbn(k, cin, cout):
        return {"w": conv(k, cin, cout), "bn": norm(cout)}

    width = cfg["base_width"]
    params = {"stem": convbn(7, cfg["in_channels"], width)}
    stages: Dict[str, list] = {f"stage{i}": []
                               for i in range(len(cfg["stage_widths"]))}
    for si, cin, w, stride in _blocks(cfg):
        stages[f"stage{si}"].append(
            {name: convbn(k, ci, co)
             for name, k, ci, co, _ in _block_convs(cfg, cin, w, stride)})
    params.update(stages)
    cin = cfg["stage_widths"][-1] * cfg["expansion"]
    params["head"] = (jax.random.normal(next(keys),
                                        (cin, cfg["num_classes"]))
                      / math.sqrt(cin))
    return params


# ---------------------------------------------------------------- reference
def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _norm(p, x):
    mu = jnp.mean(x, axis=(1, 2), keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=(1, 2), keepdims=True)
    return ((x - mu) / jnp.sqrt(var + jnp.asarray(EPS, x.dtype))
            * p["scale"] + p["bias"])


def _convbn(p, x, stride, act):
    y = _norm(p["bn"], _conv(x, p["w"], stride))
    return jnp.maximum(y, 0) if act else y


def forward(cfg: Dict, params: Dict, x):
    """Plain forward of one image batch [1, H, W, C] to logits [1, classes]
    in the dtype of ``x`` and ``params``. Matmul precision is the
    caller's (``jax.default_matmul_precision``)."""
    x = _convbn(params["stem"], x, 2, True)
    x = jax.lax.reduce_window(x, jnp.asarray(-jnp.inf, x.dtype), jax.lax.max,
                              (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    counters: Dict[int, int] = {}
    for si, cin, w, stride in _blocks(cfg):
        bi = counters.get(si, 0)
        counters[si] = bi + 1
        p = params[f"stage{si}"][bi]
        main = [c for c in _block_convs(cfg, cin, w, stride)
                if c[0] != "proj"]
        y = x
        for i, (name, _, _, _, s) in enumerate(main):
            y = _convbn(p[name], y, s, act=i < len(main) - 1)
        sc = _convbn(p["proj"], x, stride, False) if "proj" in p else x
        x = jnp.maximum(y + sc, 0)
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["head"]


# ------------------------------------------------------------- work counts
def stage_costs(cfg: Dict) -> List[Dict[str, float]]:
    """Operations and bytes of each of the four served stages, from shapes.

    FLOPs: 2 x multiply-adds of every convolution and of the classifier.
    Norms, activations, residual adds and pooling (under 1% of the
    total) are left out, so the count never exceeds the work done.
    Bytes: the least a stage must move through HBM, f32: its weights
    (convolution kernels, norm scale and bias, classifier) plus its input
    and output activations."""
    hw = cfg["image_size"]
    width = cfg["base_width"]
    # stage 0 holds the stem and the max pool (paper section III-B1)
    h = math.ceil(hw / 2)
    flops = [2.0 * h * h * 49 * cfg["in_channels"] * width, 0.0, 0.0, 0.0]
    params = [49 * cfg["in_channels"] * width + 2 * width, 0, 0, 0]
    h = math.ceil(h / 2)
    act_in = [hw * hw * cfg["in_channels"], 0, 0, 0]
    act_out = [0, 0, 0, 0]
    for si, cin, w, stride in _blocks(cfg):
        if act_in[si] == 0:
            act_in[si] = h * h * cin
        h_in = h
        for name, k, ci, co, s in _block_convs(cfg, cin, w, stride):
            if name == "proj":
                ho = math.ceil(h_in / s)
            else:
                ho = h = math.ceil(h / s)
            flops[si] += 2.0 * ho * ho * k * k * ci * co
            params[si] += k * k * ci * co + 2 * co
        act_out[si] = h * h * w * cfg["expansion"]
    cin = cfg["stage_widths"][-1] * cfg["expansion"]
    flops[3] += 2.0 * cin * cfg["num_classes"]
    params[3] += cin * cfg["num_classes"]
    act_out[3] = cfg["num_classes"]
    return [{"flops": f, "bytes": F32 * (p + a + o), "weight_bytes": F32 * p}
            for f, p, a, o in zip(flops, params, act_in, act_out)]


# ----------------------------------------------------------- served tasks
def program_stages(cfg: Dict):
    """The program's stage functions and the shapes of the weights they
    take, traced without computing anything."""
    from repro.models.cnn import build_resnet

    built = []
    shapes = jax.eval_shape(
        lambda: built.append(build_resnet(
            cfg["depth"], n_classes=cfg["num_classes"],
            width=cfg["base_width"])) or built[0].params)
    return built[0], shapes


def image_pool(cfg: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` distinct camera-frame stand-ins, standard normal, [n, 1, H, W, C].
    Never all zero: a constant image leaves the first norm nothing to
    standardise."""
    hw, c = cfg["image_size"], cfg["in_channels"]
    return rng.standard_normal((n, 1, hw, hw, c)).astype(np.float32)
