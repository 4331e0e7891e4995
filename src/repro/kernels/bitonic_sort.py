"""In-VMEM bitonic sort — the paper's architecture mapped to the TPU.

ADS-IMC's premise: sorting is data-movement-bound, so execute the network
*where the data lives*.  On TPU the expensive movement is HBM <-> VMEM, so
this kernel reads each tile of rows into VMEM **once**, runs the *entire*
Batcher bitonic network on the VMEM-resident tile, and writes it back
**once** — 2 x tile_bytes of HBM traffic total, the bandwidth floor.

The CAS block becomes a vector min/max over VPU lanes: one instruction
compares W-bit words across 8x128 lanes simultaneously — the word-parallel
strengthening of the paper's column-parallel bitline logic (DESIGN.md §2).

Stage addressing uses the reshape trick instead of gathers: for a substage
with partner distance j, view the row as (n/(2j), 2, j); partners are then
the two middle-axis halves, and the sort direction is constant per outer
chunk (bit k of the element index) — everything static, MXU/VPU friendly.

The grid partitions the row blocks exactly like the paper partitions its
SRAM macro (§II-B): each grid cell is an independent "memory partition"
running its own network concurrently.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _substages(n: int):
    """Static (k, j) substage schedule of the n-input bitonic network."""
    out = []
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            out.append((k, j))
            j //= 2
        k *= 2
    return out


def _stage_dirs(n: int, k: int, j: int, descending: bool) -> jnp.ndarray:
    """descending? flag per outer chunk of the (n/(2j), 2, j) view.

    Built from an in-trace iota (not a closed-over constant) so the same
    code path works inside Pallas kernel bodies."""
    q = jax.lax.broadcasted_iota(jnp.int32, (1, n // (2 * j), 1), 1)
    desc = ((q * (2 * j)) & k) != 0
    return desc != descending if descending else desc


def _apply_network(x: jnp.ndarray, descending: bool) -> jnp.ndarray:
    """Run the full network on (rows, n); n a power of two. Pure jnp — usable
    both inside the Pallas kernel body and as the building block of the
    sort_api 'bitonic' backend."""
    rows, n = x.shape
    for (k, j) in _substages(n):
        v = x.reshape(rows, n // (2 * j), 2, j)
        a, b = v[:, :, 0, :], v[:, :, 1, :]
        desc = _stage_dirs(n, k, j, descending)
        mn, mx = jnp.minimum(a, b), jnp.maximum(a, b)
        first = jnp.where(desc, mx, mn)
        second = jnp.where(desc, mn, mx)
        x = jnp.stack([first, second], axis=2).reshape(rows, n)
    return x


def _apply_network_kv(keys: jnp.ndarray, vals: jnp.ndarray,
                      descending: bool) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Network on (rows, n) keys carrying an int payload (for argsort/topk).

    The CAS comparator is the *composite* (key, payload) order: key in the
    requested direction, payload ascending on key ties.  Payloads are unique
    indices everywhere in this repo, so the composite is a strict total
    order — which makes the (otherwise unstable) bitonic network produce the
    stable ties-keep-ascending-index result in both directions, matching the
    engine / xla tie convention.
    """
    rows, n = keys.shape
    for (k, j) in _substages(n):
        kv = keys.reshape(rows, n // (2 * j), 2, j)
        vv = vals.reshape(rows, n // (2 * j), 2, j)
        ka, kb = kv[:, :, 0, :], kv[:, :, 1, :]
        va, vb = vv[:, :, 0, :], vv[:, :, 1, :]
        # raw chunk directions: the final direction lives in the comparator,
        # so chunks flagged here are exactly "reversed w.r.t. final order"
        rev = _stage_dirs(n, k, j, False)
        key_first = (ka > kb) if descending else (ka < kb)
        prec = key_first | ((ka == kb) & (va < vb))
        a_first = prec != rev       # XOR: reversed chunks take the maximum
        kf = jnp.where(a_first, ka, kb)
        ks = jnp.where(a_first, kb, ka)
        vf = jnp.where(a_first, va, vb)
        vs = jnp.where(a_first, vb, va)
        keys = jnp.stack([kf, ks], axis=2).reshape(rows, n)
        vals = jnp.stack([vf, vs], axis=2).reshape(rows, n)
    return keys, vals


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------
#
# Mosaic lays a 2-D block out as (sublane, lane) vector registers and cannot
# reshape the lane dimension, so the kernels never use the (n/(2j), 2, j)
# view above.  Instead every logical row of n keys is stored row-major in a
# (n/128, 128) slab (several rows share a slab row when n < 128), and the
# partner i ^ j of a substage is fetched by rotating the block: along the
# lanes for j < 128, along the sublanes (whole 128-lane rows) for j >= 128.

LANES = 128
# elements per operand block: 256 KiB of 32-bit words keeps the in/out
# double buffers plus the network's temporaries well inside the default
# scoped VMEM, and one block always holds at least one whole row
BLOCK_ELEMS = 1 << 16


def _positions(shape) -> jnp.ndarray:
    """Flat element index of every slot of a (rows, 128) block."""
    r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return r * LANES + c


def _partners(arrays, pos, j, axis: int):
    """Each slot's value at flat position ``pos ^ j`` for every array in
    ``arrays``; ``j`` (a power of two, possibly traced) is below 128 for
    ``axis=1`` (a lane exchange) and a multiple of 128 for ``axis=0`` (an
    exchange of whole 128-lane rows).

    One rotation brings ``pos - j`` to each slot and the opposite one
    ``pos + j``; the rotated position array says which of the two is the
    partner, so the result does not depend on the rotation's direction
    convention."""
    d = j if axis == 1 else j // LANES
    size = pos.shape[axis]
    fwd = pltpu.roll(pos, d, axis) == (pos ^ j)
    return [jnp.where(fwd, pltpu.roll(a, d, axis),
                      pltpu.roll(a, size - d, axis)) for a in arrays]


def _cas(state, i, pos, k: int, j, axis: int, descending: bool):
    """One compare-and-swap substage (chunk size ``k``, partner distance
    ``j``) over ``state = (keys, tie, riders)``.

    Without a tie array the swap is a min/max; with it the order is the
    composite (key in the requested direction, tie ascending), a strict
    total order when ties are unique, and the riders follow their keys."""
    keys, tie, riders = state
    carried = [keys] + ([tie] if tie is not None else []) + list(riders)
    part = _partners(carried, pos, j, axis)
    # this slot should hold the element that comes first in the final
    # order: the lower slot of a pair, flipped in reversed chunks
    want_first = ((i & j) == 0) ^ ((i & k) != 0)
    p = part[0]
    if tie is None:
        if jnp.issubdtype(keys.dtype, jnp.unsignedinteger):
            # Mosaic has no unsigned min/max; the unsigned compare lowers
            less = keys < p
            lo, hi = jnp.where(less, keys, p), jnp.where(less, p, keys)
        else:
            lo, hi = jnp.minimum(keys, p), jnp.maximum(keys, p)
        first, second = (hi, lo) if descending else (lo, hi)
        return jnp.where(want_first, first, second), None, riders
    pt = part[1]
    self_first = (keys > p) if descending else (keys < p)
    self_first = self_first | ((keys == p) & (tie < pt))
    keep = self_first == want_first
    return (jnp.where(keep, keys, p), jnp.where(keep, tie, pt),
            [jnp.where(keep, r, pr) for r, pr in zip(riders, part[2:])])


def _network_2d(keys, tie, riders, pos, *, n: int, descending: bool,
                merge_only: bool):
    """The bitonic network over rows of ``n`` keys in the slab layout.

    ``merge_only`` runs just the final merge stage, which sorts a row that
    is already bitonic (ascending, then descending).  The substages of one
    merge stage run as two loops, row exchanges then lane exchanges, so
    the kernel's code does not grow with log^2(n)."""
    i = pos & (n - 1)
    state = (keys, tie, list(riders))
    for k in ([n] if merge_only else [2 << e for e in range(n.bit_length() - 1)]):
        # partner distances k/2, k/4, ..., 1: those of 128 and more move
        # whole rows, the rest move within the lanes
        n_rows = max(0, k.bit_length() - 8)
        n_lanes = (k // 2).bit_length() - n_rows
        top_lane = (k // 2) >> n_rows
        for count, top, axis in ((n_rows, k // 2, 0), (n_lanes, top_lane, 1)):
            if count:
                state = jax.lax.fori_loop(
                    0, count,
                    lambda t, st, top=top, axis=axis, k=k: _cas(
                        st, i, pos, k, jnp.int32(top) >> t, axis,
                        descending),
                    state)
    return state


def _network_kernel(*refs, n: int, descending: bool, merge_only: bool,
                    has_tie: bool, iota_tie: bool, n_riders: int):
    n_in = 1 + has_tie + n_riders
    ins, outs = refs[:n_in], refs[n_in:]
    keys = ins[0][...]
    pos = _positions(keys.shape)
    if has_tie:
        tie = ins[1][...]
    else:
        tie = (pos & (n - 1)) if iota_tie else None
    riders = [r[...] for r in ins[1 + has_tie:]]
    keys, tie, riders = _network_2d(keys, tie, riders, pos, n=n,
                                    descending=descending,
                                    merge_only=merge_only)
    for ref, val in zip(outs, [keys] + ([tie] if tie is not None else [])
                        + riders):
        ref[...] = val


def _wide_dtype(dtype):
    """The 32-bit dtype a narrower key or payload is sorted in: Mosaic only
    rotates 32-bit vectors (and v5e has no bf16 VPU), and widening is exact
    and order-preserving."""
    dtype = jnp.dtype(dtype)
    if dtype.itemsize >= 4:
        return dtype
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.dtype(jnp.float32)
    if jnp.issubdtype(dtype, jnp.unsignedinteger):
        return jnp.dtype(jnp.uint32)
    return jnp.dtype(jnp.int32)


def _block_elems(rows: int, n: int, block_rows: Optional[int]) -> int:
    """Elements per grid block: a power of two holding whole rows and a
    whole number of (8, 128) tiles."""
    want = block_rows * n if block_rows else min(BLOCK_ELEMS, rows * n)
    return max(n, 8 * LANES, next_pow2(want))


def next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def _run_network(keys, tie=None, riders=(), *, descending: bool,
                 merge_only: bool = False, iota_tie: bool = False,
                 block_rows: Optional[int] = None, interpret: bool):
    """(rows, n) operands -> the outputs of the network kernel, each
    (rows, n): keys, then the tie array (given or the in-kernel iota),
    then the riders."""
    rows, n = keys.shape
    if n & (n - 1):
        raise ValueError(f"bitonic kernels need a power-of-two row, got {n}")
    be = _block_elems(rows, n, block_rows)
    per_block = be // n
    rows_p = -(-rows // per_block) * per_block
    ins = [keys] + ([tie] if tie is not None else []) + list(riders)
    dtypes = [a.dtype for a in ins]
    if iota_tie:
        dtypes.insert(1, jnp.dtype(jnp.int32))

    def to_slab(a):
        a = a.astype(_wide_dtype(a.dtype))
        if rows_p != rows:
            a = jnp.pad(a, ((0, rows_p - rows), (0, 0)))
        return a.reshape(rows_p * n // LANES, LANES)

    slab_rows = rows_p * n // LANES
    spec = pl.BlockSpec((be // LANES, LANES), lambda b: (b, 0))
    outs = pl.pallas_call(
        functools.partial(_network_kernel, n=n, descending=descending,
                          merge_only=merge_only, has_tie=tie is not None,
                          iota_tie=iota_tie, n_riders=len(riders)),
        grid=(slab_rows * LANES // be,),
        in_specs=[spec] * len(ins),
        out_specs=[spec] * len(dtypes),
        out_shape=[jax.ShapeDtypeStruct((slab_rows, LANES), _wide_dtype(d))
                   for d in dtypes],
        interpret=interpret,
    )(*[to_slab(a) for a in ins])
    return [o.reshape(rows_p, n)[:rows].astype(d)
            for o, d in zip(outs, dtypes)]


def _interpret_default(interpret: Optional[bool]) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else interpret


@functools.partial(jax.jit,
                   static_argnames=("descending", "block_rows", "interpret"))
def sort_blocks(x: jnp.ndarray, *, descending: bool = False,
                block_rows: Optional[int] = None,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """Sort each row of (rows, n) in VMEM; n must be a power of two (ops.py
    handles padding).  ``block_rows`` sets the rows per grid block (rounded
    up to whole vector tiles).  ``interpret=None`` resolves per platform
    (interpret mode off-TPU)."""
    (out,) = _run_network(x, descending=descending, block_rows=block_rows,
                          interpret=_interpret_default(interpret))
    return out


@functools.partial(jax.jit,
                   static_argnames=("descending", "block_rows", "interpret"))
def sort_kv_blocks(keys: jnp.ndarray, vals: jnp.ndarray, *,
                   descending: bool = False,
                   block_rows: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """Key-value sort of (rows, n) by keys, ties broken by ascending payload
    (payloads are unique indices everywhere in this repo, which makes the
    result the stable order).  ``interpret=None`` resolves per platform."""
    return tuple(_run_network(keys, vals, descending=descending,
                              block_rows=block_rows,
                              interpret=_interpret_default(interpret)))


@functools.partial(jax.jit,
                   static_argnames=("descending", "block_rows", "interpret"))
def argsort_blocks(x: jnp.ndarray, *, descending: bool = False,
                   block_rows: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """(sorted rows, int32 sorting permutation) of (rows, n): the index
    payload is generated in the kernel instead of being read from HBM, and
    ties keep ascending index order in both directions."""
    return tuple(_run_network(x, descending=descending, iota_tie=True,
                              block_rows=block_rows,
                              interpret=_interpret_default(interpret)))


def topk_blocks(x: jnp.ndarray, k: int, *, block_rows: Optional[int] = None,
                interpret: Optional[bool] = None):
    """Per-row top-k of (rows, n) -> (rows, k) values + int32 indices,
    descending, ties by ascending index; n a power of two >= k."""
    v, i = argsort_blocks(x, descending=True, block_rows=block_rows,
                          interpret=interpret)
    return v[:, :k], i[:, :k]


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_bitonic_blocks(z: jnp.ndarray, tie: Optional[jnp.ndarray] = None,
                         riders: Tuple[jnp.ndarray, ...] = (), *,
                         interpret: Optional[bool] = None):
    """Sort rows of (rows, n) that are each bitonic (ascending, then
    descending) into ascending order with the final merge stage only:
    log2(n) substages instead of the full network.  With ``tie`` the order
    is (key, tie) and ``riders`` follow their keys; returns the list of
    sorted operands (keys, tie, riders)."""
    return _run_network(z, tie, riders, descending=False, merge_only=True,
                        interpret=_interpret_default(interpret))
