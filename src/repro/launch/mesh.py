"""Production mesh builders.

A FUNCTION (not module-level constant) so importing this module never
touches jax device state. The dry-run entrypoint sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
everything else sees the real single CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # jax.make_mesh defaults to Explicit axes, which the models'
    # with_sharding_constraint calls cannot refer to
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_tiny_mesh(*, multi_pod: bool = False):
    """Reduced mesh for CI-sized subprocess tests (needs >= 8 devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_partition_meshes(n_contexts: int, oversubscription: float = 1.0,
                          *, multi_pod: bool = False):
    """DARIS spatial partitioning: split the pod's data axis into
    ``n_contexts`` (possibly overlapping) sub-meshes — the TPU analogue of
    MPS contexts with SM oversubscription (Eq. 9, DESIGN.md §2).

    Returns a list of device subsets (rows of the data axis per context).
    Chip allocation follows Eq. 9 with ceil_even on the row count; when
    OS > 1 the wrap-around allocation makes neighbouring contexts share
    rows."""
    import numpy as np
    mesh = make_production_mesh(multi_pod=multi_pod)
    devs = np.asarray(mesh.devices)
    if multi_pod:
        devs = devs.reshape(-1, *devs.shape[2:])   # fold pods into rows
    n_rows = devs.shape[0]
    rows_per_ctx = int(np.ceil(oversubscription * n_rows / n_contexts))
    rows_per_ctx += rows_per_ctx % 2               # ceil_even (Eq. 9)
    rows_per_ctx = max(2, min(rows_per_ctx, n_rows))
    out = []
    stride = n_rows / n_contexts
    for k in range(n_contexts):
        start = int(round(k * stride)) % n_rows
        rows = [(start + i) % n_rows for i in range(rows_per_ctx)]
        out.append(devs[rows])
    return out
