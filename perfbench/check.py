"""The comparison that decides ``correct``.

Served logits are compared with the plain reference (``models/<family>.py``)
run on the same seeded weights and image, once the window has closed:

    logit_gap_mean   the mean over the sampled jobs of each job's gap,
                     max |served - reference| / max |reference| over its
                     logits, with the reference at the configuration's
                     matmul precision (``matmul_precision`` in the
                     configuration's file)

The mean, and not the worst job's gap: at the TPU's default precision the
program rounds each product's operands to bfloat16 as the control does,
so a single job's gap swings from job to job about as widely as the two
lie apart; the mean over the sample is steady from seed to seed, and one
job of 32 off by a fifth of its largest logit still moves it past the
limit.

The control is the reference itself computed in bfloat16, the precision
below the configuration's float32, put in the served logits' place before
``correct`` is decided (``harness.run_cell(control=True)``): it has to come
out not correct. ``control.py`` reads both, seed by seed, and beside them,
for information, the gaps to the reference at "highest" precision.
"""
from __future__ import annotations

import contextlib
import functools
import json
from pathlib import Path
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def gap(served: np.ndarray, ref: np.ndarray) -> float:
    scale = float(np.abs(ref).max())
    if not scale > 0.0 or not np.isfinite(served).all():
        return float("inf")
    return float(np.abs(served.astype(np.float64) - ref).max()) / scale


def limits(bench_dir: Path, config: str) -> Dict[str, float]:
    return json.loads((bench_dir / "limits" / f"{config}.json").read_text())


class Reference:
    """Jitted plain forwards of one configuration on one device: at the
    configuration's precision (``stated``), the bfloat16 ``control``, and
    at ``highest`` precision (read by ``control.py`` only)."""

    def __init__(self, mod, cfg: Dict, device):
        self.device = device
        fwd = functools.partial(mod.forward, cfg)
        self._fwd = {w: jax.jit(fwd) for w in ("stated", "control",
                                                "highest")}
        stated = _precision(cfg["matmul_precision"])
        self._precision = {"stated": stated, "control": stated,
                           "highest": "highest"}

    def logits(self, which: str, params, image: np.ndarray) -> np.ndarray:
        x = jax.device_put(image, self.device)
        if which == "control":
            params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
            x = x.astype(jnp.bfloat16)
        prec = self._precision[which]
        ctx = (jax.default_matmul_precision(prec) if prec is not None
               else contextlib.nullcontext())
        with ctx:
            out = self._fwd[which](params, x)
        return np.asarray(out.astype(jnp.float32))


def _precision(name: str):
    return None if name == "default" else name
