"""repro.sort — the one front door for every sort in the system.

A sort problem is a :class:`~repro.core.sortspec.SortSpec` value; executing
one is ``run(spec, x)``.  Everything else in this module is an ergonomic
wrapper that builds the spec for you:

    import repro.sort as rsort

    rsort.sort(x)                                  # ambient default (auto)
    rsort.sort(x, method="radix", descending=True)
    rsort.argsort(x, stable=True)                  # stable permutation
    rsort.topk(logits, 50)                         # (values, indices)
    rsort.sort_kv(keys, payload)                   # payload follows keys
    rsort.segment_sort(vals, segment_ids=seg)      # ragged groups
    rsort.sort(padded, valid_lengths=lens)         # padded-row batches

    with rsort.sort_defaults(method="merge", run_len=4096):
        rsort.sort(x)                              # ambient configuration

Validation (axis range, 1 <= k <= n, incompatible field combos, unknown
methods) happens once at the spec layer; execution is delegated to
``repro.engine``, whose planner resolves "auto" through the backend
registry and caches plans per (spec statics, shape, dtype).  New engines
plug in with ``@register_backend`` — see core/sortspec.py — and are
immediately reachable from every wrapper here.

The legacy ``repro.core.sort_api`` call forms remain as deprecation shims
forwarding to these wrappers.
"""
from __future__ import annotations

from typing import Optional, Tuple, Union

import jax.numpy as jnp

from repro.core.sortspec import (  # noqa: F401  (public re-exports)
    Capabilities, SortBackend, SortSpec, backend_names, get_backend,
    register_backend, registered_backends, sort_defaults, unregister_backend)
from repro.engine.planner import clear_plan_cache  # noqa: F401
from repro.obs import trace as _obs

__all__ = [
    "run", "sort", "argsort", "topk", "sort_kv", "segment_sort",
    "SortSpec", "Capabilities", "SortBackend", "register_backend",
    "unregister_backend", "registered_backends", "backend_names",
    "get_backend", "sort_defaults", "clear_plan_cache",
]

_Arr = jnp.ndarray


def run(spec: SortSpec, x: _Arr) -> Union[_Arr, Tuple[_Arr, _Arr]]:
    """Execute ``spec`` on ``x`` inside the front door's ``sort.run`` span.
    Returns, by spec shape:

      plain sort                       sorted array
      ``indices=True``                 the sorting permutation (int32)
      ``values`` payload               (sorted keys, permuted payload)
      ``k`` set                        (top-k values, indices), descending
      ``segment_ids``/``row_splits``   (sorted values, grouped segment ids),
                                       or the permutation if ``indices=True``
      ``valid_lengths``                padded rows, valid prefixes sorted
    """
    with _obs.trace("sort.run"):
        return _run(spec, x)


def _run(spec: SortSpec, x: _Arr) -> Union[_Arr, Tuple[_Arr, _Arr]]:
    from repro import engine
    x = jnp.asarray(x)
    spec = spec.canonical(x)

    if spec.mesh is not None:
        # mesh-global path: the distributed backend dispatches sample-sort
        # vs odd-even transposition through planner.choose_distributed;
        # top-k specs run the candidate path (local select + one
        # all-gather) — never a full mesh sort
        from repro.core.sortspec import get_backend as _get
        with _obs.trace("backend.distributed"):
            if spec.k is not None:
                return _get("distributed").topk_mesh(
                    x, spec.k, spec.mesh, spec.axis_name,
                    interpret=spec.interpret)
            return _get("distributed").sort_mesh(
                x, spec.mesh, spec.axis_name, values=spec.values,
                descending=spec.descending, interpret=spec.interpret)

    if spec.valid_lengths is not None:
        if spec.indices or spec.values is not None:
            raise ValueError("valid_lengths supports value sorts only")
        if x.ndim != 2 or spec.axis != 1:
            raise ValueError("valid_lengths expects a padded (rows, L) "
                             "batch sorted along the last axis")
        return engine.sort_padded_rows(
            x, jnp.asarray(spec.valid_lengths),
            descending=spec.descending, method=spec.method,
            fill_value=spec.fill_value, run_len=spec.run_len,
            interpret=spec.interpret)

    if spec.segment_ids is not None or spec.row_splits is not None:
        if spec.axis != x.ndim - 1:
            raise ValueError("segmented sort runs along the last axis")
        seg = spec.segment_ids
        if seg is None:
            seg = engine.segment_ids_from_row_splits(
                jnp.asarray(spec.row_splits), x.shape[spec.axis])
        seg = jnp.asarray(seg)
        if spec.indices or spec.values is not None:
            order = engine.segmented_argsort(
                x, seg, descending=spec.descending, method=spec.method,
                run_len=spec.run_len, interpret=spec.interpret)
            if spec.indices:
                return order
            return (jnp.take_along_axis(x, order, axis=-1),
                    jnp.take_along_axis(spec.values, order, axis=-1))
        return engine.segmented_sort(
            x, seg, descending=spec.descending, method=spec.method,
            run_len=spec.run_len, interpret=spec.interpret)

    if spec.k is not None:
        ax = spec.axis
        if ax != x.ndim - 1:
            x = jnp.moveaxis(x, ax, -1)
        v, i = engine.topk(x, spec.k, method=spec.method,
                           run_len=spec.run_len, interpret=spec.interpret)
        if ax != v.ndim - 1:
            v, i = jnp.moveaxis(v, -1, ax), jnp.moveaxis(i, -1, ax)
        return v, i

    if spec.indices:
        return engine.argsort(x, axis=spec.axis, descending=spec.descending,
                              method=spec.method, stable=spec.stable,
                              run_len=spec.run_len, interpret=spec.interpret)
    if spec.values is not None:
        return engine.sort_kv(x, spec.values, axis=spec.axis,
                              descending=spec.descending, method=spec.method,
                              stable=spec.stable, run_len=spec.run_len,
                              interpret=spec.interpret)
    return engine.sort(x, axis=spec.axis, descending=spec.descending,
                       method=spec.method, run_len=spec.run_len,
                       interpret=spec.interpret)


# ---------------------------------------------------------------------------
# ergonomic wrappers — each builds a spec and runs it, inside the same
# ``sort.run`` span as ``run``
# ---------------------------------------------------------------------------

def sort(x: _Arr, *, axis: int = -1, descending: bool = False,
         method: Optional[str] = None, run_len: Optional[int] = None,
         interpret: Optional[bool] = None,
         valid_lengths: Optional[_Arr] = None, fill_value=0,
         mesh=None, axis_name: Optional[str] = None) -> _Arr:
    """Sort along ``axis``; with ``valid_lengths``, sort each row's valid
    prefix of a padded batch (the scheduler's fixed-shape buckets); with
    ``mesh``/``axis_name``, sort a flat array globally over the mesh
    (sample-sort; ``axis_name=None`` spans all mesh axes; odd-even
    fallback on a single axis)."""
    with _obs.trace("sort.run"):
        return _run(SortSpec(axis=axis, descending=descending,
                             method=method, run_len=run_len,
                             interpret=interpret, valid_lengths=valid_lengths,
                             fill_value=fill_value, mesh=mesh,
                             axis_name=axis_name), x)


def argsort(x: _Arr, *, axis: int = -1, descending: bool = False,
            stable: bool = False, method: Optional[str] = None,
            run_len: Optional[int] = None,
            interpret: Optional[bool] = None) -> _Arr:
    """The sorting permutation (ties keep ascending index order in both
    directions on every backend; ``stable=True`` forces a stable pipeline)."""
    with _obs.trace("sort.run"):
        return _run(SortSpec(axis=axis, descending=descending, stable=stable,
                             indices=True, method=method, run_len=run_len,
                             interpret=interpret), x)


def topk(x: _Arr, k: int, *, axis: int = -1, method: Optional[str] = None,
         run_len: Optional[int] = None, interpret: Optional[bool] = None,
         mesh=None, axis_name: Optional[str] = None) -> Tuple[_Arr, _Arr]:
    """Top-k along ``axis`` -> (values, indices), descending.  ``k`` is
    validated at the spec layer: 1 <= k <= n or ValueError.

    The plan is k-aware: "auto" picks O(n·passes) radix selection over
    sort-prefix whenever the cost model says ``k ≪ n`` pays.  With
    ``mesh``/``axis_name`` a flat array is selected globally over the mesh
    axis — local select per shard plus ONE candidate all-gather, matching
    ``jax.lax.top_k`` bit-exactly (indices are global positions)."""
    with _obs.trace("sort.run"):
        return _run(SortSpec(axis=axis, k=k, descending=True, method=method,
                             run_len=run_len, interpret=interpret,
                             mesh=mesh, axis_name=axis_name), x)


def sort_kv(keys: _Arr, values: _Arr, *, axis: int = -1,
            descending: bool = False, stable: bool = False,
            method: Optional[str] = None, run_len: Optional[int] = None,
            interpret: Optional[bool] = None,
            mesh=None, axis_name: Optional[str] = None) -> Tuple[_Arr, _Arr]:
    """Sort ``keys`` carrying ``values`` -> (sorted keys, permuted values).
    With ``mesh``/``axis_name`` the pair is sorted globally over the mesh
    axis (payload buckets ride the sample-sort exchange)."""
    with _obs.trace("sort.run"):
        return _run(SortSpec(axis=axis, descending=descending, stable=stable,
                             values=jnp.asarray(values), method=method,
                             run_len=run_len, interpret=interpret,
                             mesh=mesh, axis_name=axis_name), keys)


def segment_sort(values: _Arr, *, segment_ids: Optional[_Arr] = None,
                 row_splits: Optional[_Arr] = None, descending: bool = False,
                 method: Optional[str] = None, indices: bool = False):
    """Sort within ragged groups (flat values + segment ids or row splits).

    Returns (sorted values, grouped segment ids), or just the grouping
    permutation with ``indices=True``.
    """
    if segment_ids is None and row_splits is None:
        raise ValueError("segment_sort needs segment_ids or row_splits")
    with _obs.trace("sort.run"):
        return _run(SortSpec(descending=descending, method=method,
                             segment_ids=segment_ids, row_splits=row_splits,
                             indices=indices), values)
