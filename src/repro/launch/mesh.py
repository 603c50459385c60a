"""Production meshes. Functions, not module constants — importing this module never
touches jax device state (dryrun.py must set XLA_FLAGS before first jax init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """Mesh whose axes are Auto: the sharding rules annotate with
    with_sharding_constraint and leave propagation to the compiler, which
    make_mesh's default Explicit axes refuse (e.g. at the embedding gather)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Degenerate mesh over the locally available devices (tests / examples)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return _auto_mesh((n // model_axis, model_axis), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Mesh axes the batch/token dims shard over (pod composes with data)."""
    return ("pod", "data") if "pod" in mesh.shape else ("data",)
