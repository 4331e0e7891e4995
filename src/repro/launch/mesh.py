"""Production mesh construction (dry-run contract, system-prompt §Multi-pod).

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax

from repro.core.topology import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (v5e-256) or 2x16x16 two-pod mesh.

    Axes: 'pod' (pure DP across DCN), 'data' (FSDP + batch), 'model' (TP/EP).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(jax.make_mesh(shape, axes))


def make_host_mesh():
    """Whatever this host offers, as a 1-D data mesh (tests / examples)."""
    n = len(jax.devices())
    return auto_mesh(jax.make_mesh((n,), ("data",)))


def dp_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
