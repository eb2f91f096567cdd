"""Production mesh.  A FUNCTION (not a module constant) so importing this
module never touches jax device state — required by the dry-run contract.

Every mesh here uses ``AxisType.Auto`` axes: the sharding rules
(``distributed.sharding``) annotate with constraints and let GSPMD
propagate, which ``jax.make_mesh``'s Explicit default would reject.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` over this process's devices with Auto axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod ("data","model"); multi_pod adds a 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Whatever this host actually has (tests / examples / benchmarks)."""
    n = len(jax.devices())
    mp = model_parallel if n % max(model_parallel, 1) == 0 else 1
    return make_mesh((n // mp, mp), ("data", "model"))
