"""Histogram & quantile sketches riding the radix-select backend.

Quantiles are order statistics, and the O(n·passes) MSD radix selection
(PR 5, ``kernels/radix_select.py``) computes them without a sort: encode
the column ascending (keycodec), bottom-k select with k = the largest
needed order statistic, and read every requested quantile out of the
ascending survivor prefix.  ``q``'s order statistic is
``floor(q * (n - 1))`` — numpy's ``method="lower"``, so every answer is an
element of the column (exact for every supported dtype, no interpolation).

Histograms use the searchsorted formulation over explicit float32 bin
edges (bin of x = the edge interval containing it, rightmost bin closed —
``np.histogram``'s convention).  The edges are part of the result, so the
reference semantics are reproducible bit-for-bit: the numpy check
searchsorteds the same edges.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core import keycodec
from repro.relational import _core
from repro.relational.relspec import RelSpec


class HistogramSketch(NamedTuple):
    """``counts[b]`` = #elements in ``[edges[b], edges[b+1])`` (last bin
    closed on the right); ``edges`` is (num_bins + 1,) float32."""
    counts: jnp.ndarray
    edges: jnp.ndarray


class QuantileSketch(NamedTuple):
    """``values[i]`` is the ``qs[i]`` quantile (an element of the column,
    lower order statistic)."""
    values: jnp.ndarray


def run_histogram(spec: RelSpec, x: jnp.ndarray) -> HistogramSketch:
    bins = spec.num_bins
    n = x.shape[0]
    with _core.span(spec, n):
        xf = x.astype(jnp.float32)
        lo = jnp.asarray(spec.lo, jnp.float32) if spec.lo is not None \
            else (jnp.min(xf) if n else jnp.zeros((), jnp.float32))
        hi = jnp.asarray(spec.hi, jnp.float32) if spec.hi is not None \
            else (jnp.max(xf) if n else jnp.ones((), jnp.float32))
        hi = jnp.where(hi > lo, hi, lo + 1.0)     # degenerate range guard
        edges = lo + (hi - lo) * (
            jnp.arange(bins + 1, dtype=jnp.float32) / bins)
        if n == 0:
            out = HistogramSketch(counts=jnp.zeros((bins,), jnp.int32),
                                  edges=edges)
        else:
            idx = jnp.clip(
                jnp.searchsorted(edges, xf, side="right") - 1, 0, bins - 1)
            inside = (xf >= lo) & (xf <= edges[-1])
            counts = jnp.zeros((bins,), jnp.int32).at[idx].add(
                inside.astype(jnp.int32))
            out = HistogramSketch(counts=counts, edges=edges)
    return out


def run_quantile(spec: RelSpec, x: jnp.ndarray) -> QuantileSketch:
    n = x.shape[0]
    # lower order statistic per fraction; k = largest one we must reach
    ords = tuple(int(q * (n - 1)) for q in spec.qs)
    k = max(ords) + 1
    with _core.span(spec, n):
        enc = keycodec.encode(x, descending=False)
        kth, _ = _select(enc[None, :], k, spec.interpret)
        # ascending survivor prefix: position j IS the j-th order statistic
        vals = keycodec.decode(
            kth[0, jnp.asarray(ords, jnp.int32)], x.dtype)
        return QuantileSketch(values=vals)


def _select(enc: jnp.ndarray, k: int, interpret):
    from repro.kernels import radix_select
    return radix_select.select_topk_encoded(enc, k, interpret=interpret)
