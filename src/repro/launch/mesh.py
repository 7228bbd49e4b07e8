"""Production meshes. A FUNCTION (not module constant) so importing never
touches jax device state (the dry-run must set XLA_FLAGS first).

Every axis is Auto: the model code places arrays with
`with_sharding_constraint` and lets the compiler propagate the rest,
which Explicit axes (the `jax.make_mesh` default) refuse."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests/examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return _mesh((n // model, model), ("data", "model"))
