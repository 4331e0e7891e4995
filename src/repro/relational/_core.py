"""Shared machinery for the relational ops: the sorted post-pass
primitives, planner resolution, and obs spans.

Every op in this package is (sort via the front door) + (an O(n) scan /
searchsorted post-pass on the sorted column), traced as one
``relational.<op>`` span holding a ``relational.sort`` and a
``relational.post_pass`` span.  The post-passes here are
scatter-free where possible (XLA:CPU serializes scatters): compaction is
a stable partition by one sort, which also avoids the random gathers a
TPU runs slowly.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs import metrics as _metrics
from repro.obs import trace as _obs
from repro.relational.relspec import RelSpec, SORT_OPS, STABLE_OPS


def boundary_mask(s: jnp.ndarray) -> jnp.ndarray:
    """(n,) sorted column -> (n,) bool, True where a new value starts.

    Numeric inequality, not encoded-key inequality: the keycodec orders
    -0.0 strictly below +0.0, but relationally they are ONE value (numpy
    semantics), so the boundary test must compare decoded values.
    """
    n = s.shape[0]
    if n == 0:
        return jnp.zeros((0,), bool)
    return jnp.concatenate(
        [jnp.ones((1,), bool), s[1:] != s[:-1]])


def compact_sorted(s: jnp.ndarray, mask: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Move the masked (first-of-run) elements of a column to the front,
    in order, WITHOUT a scatter -> (compacted, n_valid, segment_ids).

    ``compacted`` is (n,) with the masked elements in the first
    ``n_valid`` slots; the tail repeats the last element (the maximum of a
    sorted column, so the array stays globally non-decreasing —
    searchsorted-safe; ``inverse`` and the distributed post-pass both rely
    on this).  ``segment_ids[i]`` is the 0-based run id of position i.

    The compaction is one stable two-operand sort keyed on the mask, not
    a searchsorted gather: on a TPU a random gather of 2^24 elements takes
    about a quarter of a second and a searchsorted runs log2(n) of them,
    and over a mesh each of those gathers reads the whole column.
    """
    n = s.shape[0]
    csum = jnp.cumsum(mask.astype(jnp.int32))
    if n == 0:
        return s, jnp.zeros((), jnp.int32), csum - 1
    n_valid = csum[-1]
    _, front = jax.lax.sort((jnp.where(mask, 0, 1).astype(jnp.int32), s),
                            num_keys=1, is_stable=True)
    compacted = jnp.where(jnp.arange(n, dtype=jnp.int32) < n_valid, front,
                          s[-1])
    return compacted, n_valid, csum - 1


def compact(spec: RelSpec, s: jnp.ndarray, mask: jnp.ndarray
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``compact_sorted``, run shard by shard when ``spec`` puts the
    column on a mesh that splits it evenly: the global program would
    gather and sort the whole column on every device."""
    if spec.mesh is None:
        return compact_sorted(s, mask)
    from repro.core.topology import auto_mesh
    from repro.engine import samplesort
    mesh = auto_mesh(spec.mesh)
    axes = samplesort._axes_tuple(mesh, spec.axis_name)
    if s.shape[0] % samplesort._n_dev(mesh, axes):
        return compact_sorted(s, mask)
    return _compact_program(mesh, axes)(s, mask)


@functools.lru_cache(maxsize=32)
def _compact_program(mesh, axes):
    """Jitted shard_map: each device moves its first-of-run elements to
    the front of its shard (one stable sort), and one all-to-all sends
    them to their global slots, which are contiguous ranges of the
    devices' shards.  Slots are combined by summing bit patterns, each
    filled by exactly one source, so every value (-0.0 too) is exact."""
    from jax.sharding import PartitionSpec as P
    from repro.engine import collectives as coll
    from repro.engine import samplesort
    d = samplesort._n_dev(mesh, axes)
    ax = samplesort._coll_axis(axes)

    def local(s, mask):
        m = s.shape[0]
        my = samplesort._lin_index(mesh, axes)
        flags = mask.astype(jnp.int32)
        cnt = jnp.sum(flags)
        counts = jax.lax.all_gather(cnt, ax).reshape(-1)           # (d,)
        offset = jnp.sum(jnp.where(jnp.arange(d) < my, counts, 0))
        n_valid = jnp.sum(counts)
        seg = offset + samplesort._running_count(mask) - 1
        _, front = jax.lax.sort((1 - flags, s), num_keys=1, is_stable=True)
        bits = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[s.dtype.itemsize]
        front = jax.lax.bitcast_convert_type(front, bits)
        zeros = jnp.zeros((m,), bits)
        padded = jnp.concatenate([zeros, front, zeros])
        # front[j] goes to global slot offset + j: device t's slot i reads
        # j = t*m - offset + i; windows that dynamic_slice would clamp hold
        # no valid j at all
        sends = []
        for t in range(d):
            j = t * m - offset + jnp.arange(m, dtype=jnp.int32)
            win = jax.lax.dynamic_slice(padded, (m + t * m - offset,), (m,))
            sends.append(jnp.where((j >= 0) & (j < cnt), win, zeros))
        out = jnp.sum(coll.all_to_all(jnp.stack(sends), ax), axis=0,
                      dtype=bits)
        out = jax.lax.bitcast_convert_type(out, s.dtype)
        last = jax.lax.all_gather(s[-1], ax).reshape(-1)[-1]
        slot = my * m + jnp.arange(m, dtype=jnp.int32)
        return jnp.where(slot < n_valid, out, last), n_valid, seg

    spec = P(axes)
    return jax.jit(samplesort._smap(local, mesh, (spec, spec),
                                    (spec, P(), spec)))


def pad_tail(arr: jnp.ndarray, n_valid: jnp.ndarray, fill) -> jnp.ndarray:
    """Overwrite slots at index >= n_valid with ``fill`` (no-op fill=None)."""
    if fill is None:
        return arr
    idx = jnp.arange(arr.shape[0], dtype=jnp.int32)
    return jnp.where(idx < n_valid, arr, jnp.asarray(fill, arr.dtype))


# ---------------------------------------------------------------------------
# planner resolution + obs
# ---------------------------------------------------------------------------

def resolve_method(spec: RelSpec, n: int, dtype) -> Optional[str]:
    """The sort backend for ``spec``.  Distributed specs return None: the
    mesh sort dispatches through ``planner.choose_distributed`` on its own.
    Explicit methods skip pricing; "auto" goes through the relational cost
    entries (``planner.choose_relational_cached``)."""
    if spec.mesh is not None or spec.op not in SORT_OPS:
        return None
    if spec.method != "auto":
        return spec.method
    if n == 0:
        return "xla"
    from repro.engine import planner
    return planner.choose_relational_cached(spec.op, n, dtype=dtype).method


def span(spec: RelSpec, n: int):
    """Obs span for one relational op (the no-op object when obs is off
    and no profiler trace is active), plus the per-op invocation counter."""
    sp = _obs.trace(f"relational.{spec.op}", n=n,
                    method=spec.method, distributed=spec.mesh is not None)
    if _obs.enabled():
        _metrics.counter(f"relational.{spec.op}").inc()
    return sp


def post_pass():
    """The ``relational.post_pass`` span: everything an op does on the
    sorted column (boundary mask, compaction, aggregates, padding)."""
    return _obs.trace("relational.post_pass")


def sorted_column(spec: RelSpec, x: jnp.ndarray, method: Optional[str],
                  values: Optional[jnp.ndarray] = None):
    """The op's sort backbone: mesh-global sample-sort when the spec is
    distributed, the planner-picked (or pinned) local backend otherwise.
    Stable-order ops go through the stable argsort pipeline instead —
    see ``stable_order``.  Traced as the ``relational.sort`` span."""
    import repro.sort as rsort
    with _obs.trace("relational.sort"):
        if spec.mesh is not None:
            if values is not None:
                return rsort.sort_kv(x, values, mesh=spec.mesh,
                                     axis_name=spec.axis_name,
                                     interpret=spec.interpret)
            return rsort.sort(x, mesh=spec.mesh, axis_name=spec.axis_name,
                              interpret=spec.interpret)
        if values is not None:
            return rsort.sort_kv(x, values, method=method, stable=True,
                                 interpret=spec.interpret)
        return rsort.sort(x, method=method, interpret=spec.interpret)


def stable_order(x: jnp.ndarray, method: Optional[str],
                 interpret: Optional[bool]) -> jnp.ndarray:
    """Stable ascending permutation of a 1-D column via the front door
    (non-stable backends fall back to the engine's stable merge pipeline
    — exactly what ``cost_model.relational_cost_ns`` prices them at).
    Traced as the ``relational.sort`` span."""
    import repro.sort as rsort
    with _obs.trace("relational.sort"):
        return rsort.argsort(x, stable=True, method=method,
                             interpret=interpret)


__all__ = ["boundary_mask", "compact", "compact_sorted", "pad_tail",
           "resolve_method", "span", "post_pass", "sorted_column",
           "stable_order", "SORT_OPS", "STABLE_OPS"]
