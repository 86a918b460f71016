"""Device meshes.

`make_production_mesh` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — required because
the dry-run must set XLA_FLAGS before any device query.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the model stack pins parameter shardings and leaves every
    # activation's sharding to the partitioner (explicit axes would demand
    # an out_sharding on each contraction over a sharded dimension)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """The assigned production mesh: one pod = 16×16 = 256 chips
    (data × model); multi-pod = 2 pods = 512 chips with a leading
    'pod' axis (used for hierarchical data parallelism / optional PP)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(dp: Optional[int] = None, tp: int = 1):
    """Small mesh over whatever devices exist (tests / CPU training)."""
    n = len(jax.devices())
    if dp is None:
        dp = n // tp
    assert dp * tp <= n, f"need {dp * tp} devices, have {n}"
    return _mesh((dp, tp), ("data", "model"))


def mesh_axis_size(mesh, name) -> int:
    if isinstance(name, (tuple, list)):
        out = 1
        for n in name:
            out *= mesh_axis_size(mesh, n)
        return out
    return mesh.shape[name] if name in mesh.shape else 1
