"""Production mesh builders.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — device count is locked at first jax init, and only
``launch/dryrun.py`` is allowed to force 512 host devices.

Every mesh is built by :func:`make_mesh`, which marks all axes
``AxisType.Auto`` (the shard_map runtime relies on auto axes).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with explicit Auto axis types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(devices=None, model: int = 2):
    """Small mesh over whatever devices exist (unit tests / smoke)."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    model = min(model, n)
    return make_mesh((n // model, model), ("data", "model"),
                     devices=devices[: (n // model) * model])


def make_host_mesh():
    """1x1 mesh on the single real device (CPU smoke tests)."""
    return make_mesh((1, 1), ("data", "model"))
