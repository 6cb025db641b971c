"""Production meshes. A FUNCTION, not a module-level constant — importing
this module never touches jax device state (dryrun.py must set XLA_FLAGS
before any jax initialisation).

Every mesh uses `Auto` axis types: `jax.make_mesh` defaults to `Explicit`
axes, under which sharded intermediates (a ray chunk's hit mask, a
trainer's dynamic_update_slice) must carry matching shardings by hand —
this code leaves that to GSPMD."""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (one v5e pod, 256 chips) or 2x16x16 (two pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_pipeline_mesh(*, stages: int = 4, data: int = 8, model: int = 8):
    """Optional PP mesh variant (launch/pipeline.py)."""
    return _auto_mesh((stages, data, model), ("stage", "data", "model"))


def make_host_mesh():
    """Whatever this host has — used by tests and the CPU examples."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))
