"""Collectives facade — the one home for the distributed sort's exchanges.

:func:`all_to_all` is the tiled bucket exchange, over one mesh axis or a
tuple of axes.  It runs inside jitted ``shard_map`` programs; the
movement it costs is counted host-side by its callers
(``samplesort.alltoall_bytes``), next to the program launch.
"""
from __future__ import annotations

from typing import Tuple, Union

import jax
import jax.numpy as jnp

__all__ = ["AxisName", "all_to_all"]

AxisName = Union[str, Tuple[str, ...]]


def _axis_arg(axis_name: AxisName):
    """lax collectives accept a name or a tuple; normalise singleton
    tuples back to the bare name for maximum version compatibility."""
    if isinstance(axis_name, tuple) and len(axis_name) == 1:
        return axis_name[0]
    return axis_name


def all_to_all(v: jnp.ndarray, axis_name: AxisName) -> jnp.ndarray:
    """(D, ...) -> (D, ...): row j of the result is what device j held in
    row ``my`` — the single bucket-exchange collective.  ``axis_name`` may
    be a tuple of mesh axes; the device order is then row-major over the
    tuple (outer axis major), matching the linear device index the
    sample-sort phases shard by."""
    return jax.lax.all_to_all(v, _axis_arg(axis_name), split_axis=0,
                              concat_axis=0, tiled=True)

