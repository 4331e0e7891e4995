"""Sorted equi-join: sort both sides, merge-scan with duplicate expansion.

Sort-merge join is the survey's headline sorter application: both key
columns are stably sorted, each left element binary-searches its matching
run on the right (the merge-scan), and the duplicate-pair cross product is
expanded with a rank arithmetic pass — every step a gather, no scatters.

Pair order contract (deterministic, what the numpy reference reproduces):
pairs ascend by key; within a key, left occurrences in input order
(stability of the left sort); within one left occurrence, right
occurrences in input order.

Static-shape contract: the true pair count is data-dependent, so results
come back padded to ``size`` (default ``n_l * n_r`` — always enough) with
``fill_value`` (default -1) in the invalid tail, plus the true ``n_pairs``.
A concrete (eager) count larger than ``size`` raises rather than silently
truncating; under jit the caller owns the check.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.relational import _core
from repro.relational.relspec import RelSpec


class Join(NamedTuple):
    """``(left_idx[:n_pairs], right_idx[:n_pairs])`` enumerate the matching
    pairs by input position; the tail holds ``fill_value``."""
    left_idx: jnp.ndarray
    right_idx: jnp.ndarray
    n_pairs: jnp.ndarray                  # int32 scalar


def run(spec: RelSpec, lk: jnp.ndarray, rk: jnp.ndarray) -> Join:
    nl, nr = lk.shape[0], rk.shape[0]
    size = spec.size if spec.size is not None else max(nl * nr, 1)
    fill = -1 if spec.fill_value is None else spec.fill_value
    if nl == 0 or nr == 0:
        pad = jnp.full((size,), fill, jnp.int32)
        return Join(pad, pad, jnp.zeros((), jnp.int32))
    with _core.span(spec, nl + nr):
        method = _core.resolve_method(spec, max(nl, nr), lk.dtype)
        ol = _core.stable_order(lk, method, spec.interpret)
        orr = _core.stable_order(rk, method, spec.interpret)
        with _core.post_pass():
            sl, sr = lk[ol], rk[orr]
            # merge-scan: each left-sorted element's matching run on the
            # right
            start = jnp.searchsorted(sr, sl, side="left").astype(jnp.int32)
            stop = jnp.searchsorted(sr, sl, side="right").astype(jnp.int32)
            off = jnp.cumsum(stop - start)          # inclusive pair offsets
            n_pairs = off[-1].astype(jnp.int32)
            # duplicate-pair expansion: pair t belongs to the left-sorted
            # element li with off[li-1] <= t < off[li]; its right partner
            # is the (t - off[li-1])-th element of li's run
            t = jnp.arange(size, dtype=jnp.int32)
            li = jnp.searchsorted(off, t, side="right").astype(jnp.int32)
            li = jnp.clip(li, 0, nl - 1)
            prev = jnp.where(li > 0, off[jnp.maximum(li - 1, 0)], 0)
            ri = jnp.clip(start[li] + (t - prev), 0, nr - 1)
            valid = t < n_pairs
            out = Join(
                left_idx=jnp.where(valid, ol[li], fill).astype(jnp.int32),
                right_idx=jnp.where(valid, orr[ri], fill).astype(jnp.int32),
                n_pairs=n_pairs)
    try:                                  # eager calls get the honest error;
        concrete = int(out.n_pairs)       # traced counts stay the caller's
    except Exception:                     # responsibility (documented)
        concrete = None
    if concrete is not None and concrete > size:
        raise ValueError(
            f"join produced {concrete} pairs but size={size}; pass "
            f"size >= {concrete} (the padded output would truncate)")
    return out
