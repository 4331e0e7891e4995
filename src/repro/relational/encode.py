"""Run-length & delta encoding of sorted columns.

Both encoders sort their input first (through the planner-picked backend)
unless ``assume_sorted=True`` — they compress *sorted columns*, the form
in which dup-heavy data is maximally compressible (a sorted Zipfian token
column run-length-encodes to its vocabulary; a sorted id column
delta-encodes to small gaps).

Exactness contracts: RLE round-trips any dtype (decode rebuilds the sorted
column); delta encoding is integer-only — modular subtraction/cumsum in
the column's own dtype round-trips bit-exactly even through wraparound,
which float cancellation cannot promise (rejected at the spec layer).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.relational import _core
from repro.relational.relspec import RelSpec


class RunLength(NamedTuple):
    """``values[:n_runs]`` / ``run_lengths[:n_runs]`` describe the runs in
    order; tails hold ``fill_value`` (default: values repeat the max, run
    lengths 0)."""
    values: jnp.ndarray
    run_lengths: jnp.ndarray
    n_runs: jnp.ndarray                   # int32 scalar


class Delta(NamedTuple):
    """``deltas[0]`` is the first (smallest) element; ``deltas[i]`` the
    modular difference from its predecessor in the sorted column."""
    deltas: jnp.ndarray


def run_rle(spec: RelSpec, x: jnp.ndarray) -> RunLength:
    n = x.shape[0]
    if n == 0:
        z = jnp.zeros((0,), jnp.int32)
        return RunLength(values=x, run_lengths=z,
                         n_runs=jnp.zeros((), jnp.int32))
    with _core.span(spec, n):
        s = x if spec.assume_sorted else _core.sorted_column(
            spec, x, _core.resolve_method(spec, n, x.dtype))
        with _core.post_pass():
            mask = _core.boundary_mask(s)
            vals, n_runs, seg = _core.compact_sorted(s, mask)
            lengths = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), seg,
                                          num_segments=n)
            return RunLength(
                values=_core.pad_tail(vals, n_runs, spec.fill_value),
                run_lengths=_core.pad_tail(lengths, n_runs, 0),
                n_runs=n_runs)


def rle_decode(values: jnp.ndarray, run_lengths: jnp.ndarray,
               n: int) -> jnp.ndarray:
    """Rebuild the (sorted) column from its runs; ``n`` is the static
    output length (= the encoded column's length)."""
    ends = jnp.cumsum(run_lengths.astype(jnp.int32))
    idx = jnp.searchsorted(ends, jnp.arange(n, dtype=jnp.int32),
                           side="right")
    return values[jnp.clip(idx, 0, max(values.shape[0] - 1, 0))]


def run_delta(spec: RelSpec, x: jnp.ndarray) -> Delta:
    n = x.shape[0]
    if n == 0:
        return Delta(deltas=x)
    with _core.span(spec, n):
        s = x if spec.assume_sorted else _core.sorted_column(
            spec, x, _core.resolve_method(spec, n, x.dtype))
        with _core.post_pass():
            return Delta(deltas=jnp.concatenate([s[:1], s[1:] - s[:-1]]))


def delta_decode(deltas: jnp.ndarray) -> jnp.ndarray:
    """Modular prefix sum in the column's own dtype — the exact inverse of
    ``run_delta`` (sorted-column reconstruction)."""
    return jnp.cumsum(deltas, dtype=deltas.dtype)
