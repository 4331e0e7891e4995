"""Merge tree over sorted runs — rung two of the out-of-core sort engine.

Pairwise merge-path merges, applied level by level over a power-of-two run
count: R runs of length L become R/2 runs of length 2L, log2(R) times.
Each level is O(n) ranking work, so the whole tree is O(n log(n/run_len)) on
top of the O(n log run_len) run generation — the O(n log n) total that the
whole-array bitonic network (O(n log^2 n) CAS count) cannot reach.

Four interchangeable merge backends:

  ``xla``     rank merge in pure jnp: each element's output position is its
              own index plus a binary-searched cross-rank in the partner run
              (searchsorted), materialised with a batched scatter.
  ``pallas``  the diagonal-partitioned VMEM kernel (kernels/merge_path.py).
  ``bitonic`` the word-parallel bitonic merge box (reshape-addressed
              min/max network).  O(n log n) compare-swaps versus the other
              backends' O(n) ranking work, but every op is a branchless
              SIMD min/max — off-TPU that beats the gather-bound rank
              merge by a wide margin, so the distributed sample-sort uses
              it as its interpret-mode merge.  Needs power-of-two run
              lengths and is NOT stable (ties follow a consistent
              left-wins predicate, payloads stay attached to their keys).
  ``sort``    XLA's stable sort of the concatenated runs.  O(n log n)
              work, but no gathers: on a TPU v5e a random gather of 2^26
              elements takes about a second, so the ranking merges lose to
              it there (merging four sorted runs of 2^24 int32 keys with
              an int32 payload: merge path 13.2 s; a two-operand stable
              sort of 2^26 int32 key/payload pairs: 0.26 s).

``xla``/``pallas``/``sort`` are ascending-stable (left run wins ties);
descending merges flip in, merge ascending, flip out.  Key-value variants carry an
int payload for argsort / top-k.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.engine import runs as _runs

MERGE_BACKENDS = ("xla", "pallas", "bitonic", "sort")


def _vsearch(sorted_rows: jnp.ndarray, queries: jnp.ndarray, side: str):
    return jax.vmap(lambda s, q: jnp.searchsorted(s, q, side=side))(
        sorted_rows, queries)


def _rank_merge(a, b, va, vb):
    """Ascending merge of (rows, L) pairs via cross-rank + gathers.

    Gather formulation (no scatter — XLA's CPU scatter is a serial loop):
    ``pa`` is each a-element's output slot; ``i[o] = #a-elements in slots
    [0..o]`` recovers, per output slot, which source to read and at what
    index, so placement is two ``take_along_axis`` plus a select.
    """
    rows, l = a.shape
    pos = jnp.arange(l, dtype=jnp.int32)
    pa = pos[None, :] + _vsearch(b, a, "left")    # a first on ties
    out_pos = jnp.broadcast_to(jnp.arange(2 * l, dtype=jnp.int32)[None, :],
                               (rows, 2 * l))
    i = _vsearch(pa, out_pos, "right")
    j = out_pos - i
    ia = jnp.clip(i - 1, 0, l - 1)
    jb = jnp.clip(j, 0, l - 1)
    from_a = jnp.diff(i, prepend=0, axis=-1) > 0
    out = jnp.where(from_a, jnp.take_along_axis(a, ia, -1),
                    jnp.take_along_axis(b, jb, -1))
    if va is None:
        return out, None
    vout = jnp.where(from_a, jnp.take_along_axis(va, ia, -1),
                     jnp.take_along_axis(vb, jb, -1))
    return out, vout


def _bitonic_box_merge(a, b, va, vb):
    """Merge box over concat(a, reverse(b)) — a bitonic sequence, so only
    the log2(2L) merge substages are needed, each a (pairs, 2, j) reshape
    view + min/max (the same reshape-addressed form as
    ``distributed_sort.bitonic_merge_halves``; gather chains would stall
    XLA's CPU compiler).  With a payload the comparator is an explicit
    a<=b predicate so payloads follow their keys through every swap."""
    rows, l = a.shape
    if l & (l - 1):
        raise ValueError(
            f"bitonic merge backend needs power-of-two run lengths, got {l}")
    n = 2 * l
    z = jnp.concatenate([a, jnp.flip(b, -1)], -1)
    w = None if va is None else jnp.concatenate([va, jnp.flip(vb, -1)], -1)
    j = n // 2
    while j >= 1:
        zv = z.reshape(rows, n // (2 * j), 2, j)
        ka, kb = zv[:, :, 0, :], zv[:, :, 1, :]
        if w is None:
            z = jnp.stack([jnp.minimum(ka, kb), jnp.maximum(ka, kb)],
                          axis=2).reshape(rows, n)
        else:
            wv = w.reshape(rows, n // (2 * j), 2, j)
            pa, pb = wv[:, :, 0, :], wv[:, :, 1, :]
            pred = ka <= kb
            z = jnp.stack([jnp.where(pred, ka, kb), jnp.where(pred, kb, ka)],
                          axis=2).reshape(rows, n)
            w = jnp.stack([jnp.where(pred, pa, pb), jnp.where(pred, pb, pa)],
                          axis=2).reshape(rows, n)
        j //= 2
    return z, w


def merge_pairs(a: jnp.ndarray, b: jnp.ndarray, *, descending: bool = False,
                backend: str = "xla", values: Tuple = (None, None),
                interpret: Optional[bool] = None):
    """Merge row-wise sorted (rows, L) a and b -> (rows, 2L) (+ payloads)."""
    if backend not in MERGE_BACKENDS:
        raise ValueError(
            f"merge backend must be one of {MERGE_BACKENDS}, got {backend!r}")
    va, vb = values
    if backend in ("pallas", "sort"):
        return _merge_flat(jnp.stack([a, b], 1),
                           None if va is None else jnp.stack([va, vb], 1),
                           descending, interpret, backend)
    if descending:
        # flip to ascending AND swap the pair: the ascending merge's
        # left-wins-ties rule turns into right-wins after the final flip,
        # so swapping roles restores "a first on equal keys" — keeping
        # stable pipelines stable in both directions.
        a, b = jnp.flip(b, -1), jnp.flip(a, -1)
        va, vb = (None if vb is None else jnp.flip(vb, -1),
                  None if va is None else jnp.flip(va, -1))
    if backend == "bitonic":
        out, vout = _bitonic_box_merge(a, b, va, vb)
    else:
        out, vout = _rank_merge(a, b, va, vb)
    if descending:
        out = jnp.flip(out, -1)
        vout = None if vout is None else jnp.flip(vout, -1)
    return (out, vout) if values[0] is not None else out


def merge_runs(run_keys: jnp.ndarray, run_vals: Optional[jnp.ndarray] = None,
               *, descending: bool = False, backend: str = "xla",
               interpret: Optional[bool] = None):
    """Collapse (rows, R, L) sorted runs into one (rows, R*L) sorted row.

    R must be a power of two (run generation guarantees it).  This is the
    k-way merge realised as a complete tournament of pairwise merge-path
    merges — log2(R) levels, each touching every element once.
    """
    rows, r, l = run_keys.shape
    if r & (r - 1):
        raise ValueError(f"run count must be a power of two, got {r}")
    if backend in ("pallas", "sort"):
        return _merge_flat(run_keys, run_vals, descending, interpret, backend)
    keys, vals = run_keys, run_vals
    while r > 1:
        kv = keys.reshape(rows * (r // 2), 2, l)
        a, b = kv[:, 0, :], kv[:, 1, :]
        if vals is None:
            merged = merge_pairs(a, b, descending=descending, backend=backend,
                                 interpret=interpret)
        else:
            vv = vals.reshape(rows * (r // 2), 2, l)
            merged, mvals = merge_pairs(
                a, b, descending=descending, backend=backend,
                values=(vv[:, 0, :], vv[:, 1, :]), interpret=interpret)
            vals = mvals.reshape(rows, r // 2, 2 * l)
        keys = merged.reshape(rows, r // 2, 2 * l)
        r //= 2
        l *= 2
    keys = keys.reshape(rows, l)
    if run_vals is None:
        return keys
    return keys, vals.reshape(rows, l)


def _merge_flat(run_keys, run_vals, descending, interpret, backend):
    """``merge_runs`` (and ``merge_pairs``, as two runs per row) on the
    flat array: level by level on the merge-path kernel (adjacent runs are
    the pairs), so no level materialises a few-row, very wide array; or as
    one stable sort of each row.  Descending flips the whole array once:
    that reverses every run and the run order, so the ascending merge's
    left-wins-ties rule still keeps the earlier run first after the flip
    back."""
    from repro.kernels import merge_path as _mp
    rows, r, l = run_keys.shape
    keys = run_keys.reshape(-1)
    vals = None if run_vals is None else run_vals.reshape(-1)
    if descending:
        keys = jnp.flip(keys)
        vals = None if vals is None else jnp.flip(vals)
    if backend == "sort":
        ops = (keys.reshape(rows, r * l),) if vals is None \
            else (keys.reshape(rows, r * l), vals.reshape(rows, r * l))
        out = jax.lax.sort(ops, dimension=1, num_keys=1, is_stable=True)
        keys = out[0].reshape(-1)
        vals = None if vals is None else out[1].reshape(-1)
        r, l = 1, r * l
    while r > 1:
        if vals is None:
            keys = _mp.merge_flat(keys, l=l, interpret=interpret)
        else:
            keys, vals = _mp.merge_flat(keys, vals, l=l, interpret=interpret)
        r //= 2
        l *= 2
    if descending:
        keys = jnp.flip(keys)
        vals = None if vals is None else jnp.flip(vals)
    keys = keys.reshape(rows, l)
    return keys if run_vals is None else (keys, vals.reshape(rows, l))


def _pad_value(dtype, descending: bool):
    """Pad that keeps a sorted run sorted when appended: the top of the
    dtype's TOTAL order in the merge direction.  For ascending floats that
    is NaN, not +inf — the sort backends and searchsorted both order NaN
    after +inf, so an inf sentinel appended after genuine NaNs would leave
    the padded run unsorted and corrupt every cross-rank.  (Descending
    runs end at -inf; genuine NaNs sort to the *front*, so the -inf
    sentinel stays correct.)"""
    if not descending and jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.nan, dtype)
    return _runs.sort_sentinel(dtype, descending)


def kway_merge(arrays: Sequence[jnp.ndarray], *, descending: bool = False,
               backend: str = "xla",
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """Merge k independently sorted 1-D arrays into one sorted array.

    Arrays may have different lengths; each is padded to a common
    power-of-two run length with the direction's total-order pad, and the
    pad is sliced off the far end of the result.
    """
    if not arrays:
        raise ValueError("need at least one array")
    arrays = [jnp.ravel(a) for a in arrays]
    dtype = arrays[0].dtype
    total = sum(a.shape[0] for a in arrays)
    l = _runs.next_pow2(max(a.shape[0] for a in arrays))
    r = _runs.next_pow2(len(arrays))
    sent = _pad_value(dtype, descending)
    padded = [jnp.pad(a, (0, l - a.shape[0]), constant_values=sent)
              for a in arrays]
    padded += [jnp.full((l,), sent, dtype)] * (r - len(arrays))
    stacked = jnp.stack(padded)[None, :, :]
    merged = merge_runs(stacked, descending=descending, backend=backend,
                        interpret=interpret)
    return merged[0, :total]


def kway_merge_kv(keys: Sequence[jnp.ndarray], vals: Sequence[jnp.ndarray],
                  *, descending: bool = False, backend: str = "xla",
                  interpret: Optional[bool] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Merge k independently sorted 1-D (key, payload) arrays.

    The key-only :func:`kway_merge` slices its sentinel padding off the far
    end of the tournament output — value-identical for keys, but with a
    payload attached a pad slot from an *earlier* run ties with a genuine
    sentinel-valued key from a later run, wins on the left-first rule, and
    displaces the genuine payload past the slice boundary.  So the kv
    variant runs the tournament on (key, concatenation-position) pairs and
    drops pad slots by position afterwards: a pad can never shadow a
    genuine element, whatever its key.  Stable for the ``xla``/``pallas``
    backends (ties keep array order, i.e. earlier array first).

    Eager-only: the final compaction is a data-dependent boolean gather —
    fine for the spill tier's host-side merge driver, not jittable.
    """
    if not keys or len(keys) != len(vals):
        raise ValueError("need matching non-empty key/payload array lists")
    keys = [jnp.ravel(a) for a in keys]
    vals = [jnp.ravel(v) for v in vals]
    for a, v in zip(keys, vals):
        if a.shape != v.shape:
            raise ValueError(
                f"key/payload length mismatch: {a.shape} vs {v.shape}")
    dtype = keys[0].dtype
    total = sum(a.shape[0] for a in keys)
    l = _runs.next_pow2(max(1, max(a.shape[0] for a in keys)))
    r = _runs.next_pow2(len(keys))
    sent = _pad_value(dtype, descending)
    pk, pp, off = [], [], 0
    for a in keys:
        m = a.shape[0]
        pk.append(jnp.pad(a, (0, l - m), constant_values=sent))
        pos = jnp.arange(off, off + m, dtype=jnp.int32)
        pp.append(jnp.pad(pos, (0, l - m), constant_values=total))
        off += m
    pk += [jnp.full((l,), sent, dtype)] * (r - len(keys))
    pp += [jnp.full((l,), total, jnp.int32)] * (r - len(keys))
    mk, mp = merge_runs(jnp.stack(pk)[None, :, :], jnp.stack(pp)[None, :, :],
                        descending=descending, backend=backend,
                        interpret=interpret)
    mk, mp = mk[0], mp[0]
    genuine = mp < total
    mk, mp = mk[genuine], mp[genuine]
    return mk, jnp.take(jnp.concatenate(vals), mp)
