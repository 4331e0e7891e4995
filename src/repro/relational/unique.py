"""Dedup / unique: sort -> adjacent-diff mask -> searchsorted compaction.

``np.unique`` semantics under the static-shape contract: the distinct
values come back ascending in a fixed (n,)-shaped array with a valid
count, plus optional inverse indices and per-value counts — the
``jnp.unique(size=n)`` shape discipline without its scatter-heavy
lowering.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp

from repro.relational import _core
from repro.relational.relspec import RelSpec


class Unique(NamedTuple):
    """``values[:n_unique]`` is ``np.unique(x)``; the tail holds
    ``fill_value`` (or repeats the maximum when fill_value is None, which
    keeps ``values`` globally non-decreasing — searchsorted-safe).
    ``inverse`` (optional) maps each input position to its slot in
    ``values``; ``counts`` (optional) is the multiplicity per slot."""
    values: jnp.ndarray
    n_unique: jnp.ndarray                 # int32 scalar
    inverse: Optional[jnp.ndarray] = None
    counts: Optional[jnp.ndarray] = None


def run(spec: RelSpec, x: jnp.ndarray) -> Unique:
    n = x.shape[0]
    if n == 0:
        return Unique(values=x,
                      n_unique=jnp.zeros((), jnp.int32),
                      inverse=jnp.zeros((0,), jnp.int32)
                      if spec.return_inverse else None,
                      counts=jnp.zeros((0,), jnp.int32)
                      if spec.return_counts else None)
    with _core.span(spec, n):
        method = _core.resolve_method(spec, n, x.dtype)
        s = _core.sorted_column(spec, x, method)
        with _core.post_pass():
            mask = _core.boundary_mask(s)
            uvals, n_unique, _ = _core.compact(spec, s, mask)
            inverse = counts = None
            if spec.return_inverse or spec.return_counts:
                # uvals is non-decreasing (tail repeats the max), and every
                # input value occurs in its valid prefix, so one binary
                # search recovers each element's slot — works unchanged on
                # the distributed path (no argsort needed over the mesh)
                inverse = jnp.searchsorted(uvals, x, side="left"
                                           ).astype(jnp.int32)
            if spec.return_counts:
                counts = jnp.zeros((n,), jnp.int32).at[inverse].add(1)
            return Unique(
                values=_core.pad_tail(uvals, n_unique, spec.fill_value),
                n_unique=n_unique,
                inverse=inverse if spec.return_inverse else None,
                counts=counts)
