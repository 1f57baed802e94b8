"""Mesh helpers over the installed JAX's sharding API (jax 0.9).

`Engine(mesh=...)` and the launch code go through these three calls so
the ambient-mesh mechanics live in one place.
"""

from __future__ import annotations

import jax


def get_ambient_mesh():
    """The abstract mesh visible at trace time. It exposes `.axis_names`
    and a dict-like `.shape`; with no ambient mesh, `axis_names` is
    empty."""
    return jax.sharding.get_abstract_mesh()


def mesh_context(mesh):
    """Context manager installing `mesh` as the ambient mesh for traces
    opened inside it (`jax.set_mesh`), so `shard_hint` constraints fire
    inside serving dispatches. A bare `with mesh:` no longer sets the
    abstract mesh: under it `shard_hint` silently did nothing."""
    return jax.set_mesh(mesh)


def make_compat_mesh(shape, axis_names, *, devices=None):
    """`jax.make_mesh` with every axis Auto: GSPMD derives the layouts of
    intermediates from the committed operands and `shard_hint`s."""
    return jax.make_mesh(shape, axis_names, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))
