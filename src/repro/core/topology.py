"""Mesh helpers: the Auto-typed twin of a mesh, and arrays moved onto it.

``jax.make_mesh`` gives Explicit axes, whose sharding-in-types rules
reject gathers and ``with_sharding_constraint`` on the arrays they shard.
The library's mesh programs are ``shard_map``s and its placement rules are
constraints, so every mesh entry point (sample-sort, relational mesh ops,
sharding policies, the launch front doors) converts what a caller hands
it with these two functions.  Sits at the bottom of the stack and imports
only jax.
"""
from __future__ import annotations

__all__ = ["auto_mesh", "to_auto_mesh"]


def auto_mesh(mesh):
    """``mesh`` with every axis Auto-typed (the same devices and names)."""
    from jax.sharding import AxisType, Mesh
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def to_auto_mesh(x, mesh=None):
    """An array placed on an Explicit-axes mesh, moved (same layout) onto
    the Auto-typed twin of that mesh, or of ``mesh`` (needed under a
    trace, where the array's own mesh is abstract); anything else is
    returned unchanged."""
    import jax
    from jax.sharding import AxisType, NamedSharding
    sh = jax.typeof(x).sharding if isinstance(x, jax.core.Tracer) \
        else getattr(x, "sharding", None)
    if not isinstance(sh, NamedSharding) \
            or all(t == AxisType.Auto for t in sh.mesh.axis_types):
        return x
    target = auto_mesh(sh.mesh if mesh is None else mesh)
    return jax.device_put(x, NamedSharding(target, sh.spec))
