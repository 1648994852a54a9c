"""The mesh surface of the distribution layer, on the installed JAX.

Three helpers, so the rest of the package has one place to ask:

* ``make_mesh(shape, axes)`` builds a mesh whose axes are all
  ``AxisType.Auto``.  ``jax.make_mesh`` defaults to ``Explicit`` axes,
  under which every ``with_sharding_constraint`` (``annotate.ann``) is an
  assert instead of a hint to the partitioner; this package's model code
  states layouts as hints, so its meshes are ``Auto``;
* ``current_mesh()`` returns the ambient concrete mesh (``jax.set_mesh``)
  or ``None``;
* ``shard_map(f, mesh, in_specs, out_specs, axis_names)`` makes the named
  axes manual and leaves the others to the partitioner.

>>> make_mesh((1,), ("data",)).axis_types
(Auto,)
"""
from __future__ import annotations

import jax
from jax._src import mesh as mesh_lib
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (see module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def current_mesh():
    """The ambient concrete mesh (``jax.set_mesh``), also while tracing
    under it, or ``None`` when no mesh is installed."""
    m = mesh_lib.get_concrete_mesh()
    return None if m.empty else m


def shard_map(f, mesh, in_specs, out_specs, axis_names=None):
    """shard_map over ``mesh``.

    ``axis_names``: the axes the body addresses collectively (manual);
    every other mesh axis is left to the partitioner.  ``None`` makes all
    axes manual.  Replication checking is off — the dispatch bodies here
    mix manual batch axes with auto model axes, which the checker cannot
    track.
    """
    manual = frozenset(axis_names or mesh.axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=manual,
                         check_vma=False)
