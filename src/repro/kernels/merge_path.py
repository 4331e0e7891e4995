"""Merge-path (diagonal-partitioned) merge of sorted runs, in VMEM.

The engine's merge tree needs a merge primitive whose work is O(n) per level
instead of the bitonic merge box's O(n log n) compare-and-swaps.  Merge path
(Green/McColl/Odeh) splits the output of ``merge(a, b)`` into equal chunks by
binary-searching the merge matrix's diagonals; each chunk then depends on one
bounded window of ``a`` and one of ``b`` (|window_a| + |window_b| = chunk), so
chunks are embarrassingly parallel and perfectly load-balanced — the same
partition-then-exchange structure ADS-IMC uses across its SRAM CAS partitions
(§II-B), applied one level up the hierarchy.

Division of labour:

  host (jnp)     diagonal binary search -> per-chunk window starts/counts;
                 each chunk's ``ca`` a-elements and ``C - ca`` b-elements
                 are gathered into one row ``a-window ++ reversed b-window``,
                 which is bitonic (ascending, then descending).
  kernel (VMEM)  the bitonic network's final merge stage sorts every such
                 row: log2(C) compare-and-swap substages of lane and
                 sublane rotations (kernels/bitonic_sort.py) — no dynamic
                 scatter, no serial loop, everything vector ops.

Validity is tracked with explicit per-window counts (not key sentinels), so
inputs containing the dtype's extreme values still merge bit-exactly.  The
key-value merge orders by (key, origin), where the origin numbers the
a-window before the b-window, so ties keep a's elements first (stable) and
payloads ride along.  Keys must be NaN-free (comparisons follow min/max
semantics, like the bitonic kernels).  Ascending only — callers flip for
descending merges.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import bitonic_sort as _bs

DEFAULT_CHUNK = 256


# ---------------------------------------------------------------------------
# host side: diagonal partition + window gather
# ---------------------------------------------------------------------------

# Pairs are stored flat, each ``a ++ b`` run pair contiguous, and every
# diagonal carries the flat offsets of its pair's runs, so the search, the
# window gathers and the output are plain 1-D: the TPU compiler takes
# minutes over the relayout of a (2, 2^26) array, and over the row-batched
# form of these gathers.

def _diag_search(z, diag, base_a, base_b, l: int):
    """Merge-path split: #a-elements among the first ``diag`` merged outputs.

    z: flat pairs of ascending runs of length ``l``; diag, base_a, base_b:
    (m,) int32 diagonals and the flat offsets of their pair's a and b runs.
    Returns (m,).  Ties go to ``a`` (stable when a precedes b).  Classic
    monotone-predicate binary search, vectorised over all diagonals.
    """
    lo = jnp.maximum(0, diag - l)
    hi = jnp.minimum(diag, l)
    for _ in range(max(1, int(l).bit_length())):
        mid = (lo + hi + 1) // 2
        a_prev = _take(z, base_a + jnp.clip(mid - 1, 0, l - 1))
        b_next = _take(z, base_b + jnp.clip(diag - mid, 0, l - 1))
        # feasible(mid): can take >= mid elements of a before diag?
        feasible = (mid <= lo) | (diag - mid >= l) | (a_prev <= b_next)
        lo = jnp.where(feasible, jnp.maximum(lo, mid), lo)
        hi = jnp.where(feasible, hi, jnp.minimum(hi, mid - 1))
    return lo


def _take(x, idx):
    return x.at[idx].get(mode="promise_in_bounds")


def _gather_windows(x, starts, base, l: int, c: int):
    """(m, c) windows of the runs at flat offsets ``base`` of ``x``,
    starting at ``starts`` within their run."""
    idx = jnp.clip(starts[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :],
                   0, l - 1)
    return _take(x, base[:, None] + idx)


@functools.partial(jax.jit, static_argnames=("l", "chunk", "interpret"))
def merge_flat(z: jnp.ndarray, values: Optional[jnp.ndarray] = None, *,
               l: int, chunk: int = DEFAULT_CHUNK,
               interpret: Optional[bool] = None):
    """Merge every pair of adjacent ascending runs of length ``l`` in the
    flat ``z`` (``z[2pl:(2p+1)l]`` with ``z[(2p+1)l:(2p+2)l]``) into one
    ascending run of ``2l`` in place; ``values`` (same layout) ride along.
    Returns the merged keys, or (keys, values)."""
    total = 2 * l
    pairs = z.shape[0] // total
    c = min(chunk, total)
    nc = total // c
    # one entry per (pair, chunk), pair-major
    chunk_id = jnp.arange(pairs * nc, dtype=jnp.int32) % nc
    diag = chunk_id * c
    base_a = jnp.arange(pairs * nc, dtype=jnp.int32) // nc * total
    base_b = base_a + l
    starts_a = _diag_search(z, diag, base_a, base_b, l)
    ends_a = jnp.where(chunk_id == nc - 1, l,
                       jnp.concatenate([starts_a[1:], starts_a[:1]]))
    counts_a = (ends_a - starts_a)[:, None]
    starts_b = diag - starts_a
    # slot s of a chunk row holds a[start_a + s] for s < ca and the
    # b-window reversed, b[start_b + (cw - 1 - s)], in the last c - ca
    # slots; a non-power-of-two chunk pads the peak between them with the
    # maximal key, which keeps the row bitonic and sorts the pads last
    cw = _bs.next_pow2(c)
    slot = jnp.arange(cw, dtype=jnp.int32)[None, :]
    from_a = slot < counts_a
    from_b = slot >= cw - (c - counts_a)

    def window_row(x, fill):
        wa = _gather_windows(x, starts_a, base_a, l, cw)
        wb = _gather_windows(x, starts_b, base_b, l, cw)[:, ::-1]
        return jnp.where(from_a, wa, jnp.where(from_b, wb, fill))

    zw = window_row(z, _max_key(z.dtype))
    if values is None:
        (out,) = _bs.merge_bitonic_blocks(zw, interpret=interpret)
        return out[:, :c].reshape(-1)
    origin = jnp.where(from_a, slot,
                       jnp.where(from_b, counts_a + (cw - 1 - slot),
                                 cw + slot))
    mk, _, mv = _bs.merge_bitonic_blocks(
        zw, origin, (window_row(values, jnp.zeros((), values.dtype)),),
        interpret=interpret)
    return mk[:, :c].reshape(-1), mv[:, :c].reshape(-1)


def _max_key(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).max, dtype)
