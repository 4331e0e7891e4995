"""Group-by aggregate: segmented sort -> boundary flags -> segment_sum.

The survey's canonical sorter application: stable kv-sort co-locates each
key's values, boundary flags turn runs into segment ids, and
``jax.ops.segment_{sum,min,max}`` does the reductions in one pass.  The
distributed variant rides the sample-sort — after the splitter round equal
keys share a device, so the identical local post-pass IS the global
group-by.

Also home to ``group_ranks`` (the MoE dispatch primitive): each element's
arrival rank within its key group plus per-group counts — a counting sort
over a small key domain, the bit-width-aware strengthening of the paper's
4-bit sort that ``models/moe.py`` runs per batch row.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.relational import _core
from repro.relational.relspec import RelSpec

# one-hot counting stays cheaper than a sort pipeline while the O(n*G)
# one-hot tensor is small; past this domain the flat path sorts instead
ONE_HOT_MAX_GROUPS = 512


class GroupBy(NamedTuple):
    """``keys[:n_groups]`` are the distinct keys ascending; ``aggregates``
    holds one (n,)-shaped column per requested reduction (same order as
    ``agg``), each valid to ``n_groups`` and padded with ``fill_value``
    (default 0) past it."""
    keys: jnp.ndarray
    n_groups: jnp.ndarray                 # int32 scalar
    aggregates: Tuple[jnp.ndarray, ...]


class GroupRanks(NamedTuple):
    """``ranks`` is each element's 0-based arrival order within its key
    group (shape of the input); ``counts`` is (..., num_groups) group
    sizes."""
    ranks: jnp.ndarray
    counts: jnp.ndarray


def _aggregate(sv: jnp.ndarray, seg: jnp.ndarray, n: int, aggs,
               n_groups: jnp.ndarray, fill) -> Tuple[jnp.ndarray, ...]:
    """Segment reductions over the sorted values, one column per agg."""
    cnt = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), seg,
                              num_segments=n)
    fill = 0 if fill is None else fill
    outs = []
    for a in aggs:
        if a == "sum":
            r = jax.ops.segment_sum(sv, seg, num_segments=n)
        elif a == "min":
            r = jax.ops.segment_min(sv, seg, num_segments=n)
        elif a == "max":
            r = jax.ops.segment_max(sv, seg, num_segments=n)
        elif a == "count":
            r = cnt
        else:  # mean — float32 division of the exact segment sum, the
            # documented reference semantics (README "Relational kernels")
            s = jax.ops.segment_sum(sv.astype(jnp.float32), seg,
                                    num_segments=n)
            r = s / jnp.maximum(cnt, 1).astype(jnp.float32)
        outs.append(_core.pad_tail(r, n_groups, fill))
    return tuple(outs)


def run(spec: RelSpec, keys: jnp.ndarray, values: jnp.ndarray) -> GroupBy:
    n = keys.shape[0]
    if n == 0:
        empty = tuple(
            jnp.zeros((0,), jnp.int32 if a == "count"
                      else jnp.float32 if a == "mean" else values.dtype)
            for a in spec.agg)
        return GroupBy(keys=keys, n_groups=jnp.zeros((), jnp.int32),
                       aggregates=empty)
    with _core.span(spec, n):
        method = _core.resolve_method(spec, n, keys.dtype)
        # the mesh path's kv sample-sort is not stable, which is fine:
        # every supported reduction is order-free given exact arithmetic
        # (the stable local pipeline just fixes the summation order)
        sk, sv = _core.sorted_column(spec, keys, method, values=values)
        with _core.post_pass():
            mask = _core.boundary_mask(sk)
            ukeys, n_groups, seg = _core.compact(spec, sk, mask)
            aggs = _aggregate(sv, seg, n, spec.agg, n_groups,
                              spec.fill_value)
            return GroupBy(
                keys=_core.pad_tail(ukeys, n_groups, spec.fill_value),
                n_groups=n_groups, aggregates=aggs)


def run_group_ranks(spec: RelSpec, keys: jnp.ndarray,
                    constrain: Optional[Callable] = None) -> GroupRanks:
    """Arrival rank within each key group.  Small domains (and any batched
    input) use the one-hot counting sort — O(n * num_groups) exclusive
    cumsum, fully vectorized and shardable (``constrain`` lets the caller
    annotate the one-hot's sharding, e.g. MoE's dp axes).  Large flat
    domains ride the stable sort: rank = sorted position - group start.
    """
    g = spec.num_groups
    n = keys.shape[-1]
    with _core.span(spec, int(keys.size)):
        if keys.ndim > 1 or g <= ONE_HOT_MAX_GROUPS or n == 0:
            onehot = jax.nn.one_hot(keys, g, dtype=jnp.int32)
            if constrain is not None:
                onehot = constrain(onehot)
            ranks = jnp.sum((jnp.cumsum(onehot, axis=-2) - onehot) * onehot,
                            axis=-1)
            counts = jnp.sum(onehot, axis=-2)
        else:
            order = _core.stable_order(keys, spec.method, spec.interpret)
            with _core.post_pass():
                sk = keys[order]
                seg = jnp.cumsum(
                    _core.boundary_mask(sk).astype(jnp.int32)) - 1
                # group start in sorted coords = first position of each
                # run; rank = sorted position - start, scattered back to
                # input order
                starts = jnp.full((n,), n, jnp.int32).at[seg].min(
                    jnp.arange(n, dtype=jnp.int32))
                sorted_rank = jnp.arange(n, dtype=jnp.int32) - starts[seg]
                ranks = jnp.zeros((n,), jnp.int32).at[order].set(
                    sorted_rank)
                counts = jnp.zeros((g,), jnp.int32).at[
                    jnp.clip(keys, 0, g - 1)].add(1)
    return GroupRanks(ranks=ranks, counts=counts)
