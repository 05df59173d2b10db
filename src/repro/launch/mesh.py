"""Production and host meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.

Every axis is ``AxisType.Auto``: the models place activations with logical
``with_sharding_constraint`` calls (``repro.models.common.shard``), which may
only name Auto axes.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple, **kw):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes), **kw)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256-chip v5e pod; multi-pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(shape: "tuple[int, int] | None" = None, *, devices=None):
    """``(data, model)`` mesh over every device of the job (``jax.devices()``).

    ``shape`` defaults to data parallelism over all of them (1×1 on one chip
    or the one-device CPU); a shape whose product differs from the device
    count raises. ``devices`` narrows the mesh to a subset, e.g.
    ``jax.devices()[:1]`` for a one-chip reference run on a four-chip host.
    """
    devices = list(jax.devices() if devices is None else devices)
    if shape is None:
        shape = (len(devices), 1)
    data, model = shape
    if data * model != len(devices):
        raise ValueError(
            f"host mesh {data}x{model} needs {data * model} devices, "
            f"have {len(devices)}"
        )
    return _auto_mesh((data, model), ("data", "model"), devices=devices)
