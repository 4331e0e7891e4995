"""LSD radix sort over encoded keys — the O(n·b) digit-serial backend.

ADS-IMC's CAS program is *bit*-serial: one pass over the operands per key
bit, constant work per pass.  This kernel is the VMEM analogue one level up:
a least-significant-digit radix sort whose passes are *digit*-serial
(``DIGIT_BITS`` bits at a time), giving O(n·b/DIGIT_BITS) total work — the
asymptotic the comparison backends (O(n log n) merge, O(n log^2 n) bitonic)
cannot reach once n outgrows the key width.

Keys must already be unsigned with order matching ``<`` on the source dtype
— that is ``core/keycodec.py``'s job (sign-flip for ints, sign-magnitude ->
lexicographic for floats, complement for descending).  This module is
ascending-only and *stable*: equal keys keep their input order, which also
makes the padding scheme safe (pads carry the max key and are appended
after the payload, so stability parks them at the far end).

Division of labour per digit pass (the classic three-phase LSD structure):

  kernel 1 (VMEM)  per-tile digit histogram (one lane-reduced compare per
                   digit value) + per-element local stable rank (the count
                   of earlier equal digits in the tile, one lane rotation
                   and compare per distance).
  host (jnp)       digit-major exclusive prefix-sum across all tiles of a
                   row -> the global base offset of every (tile, digit).
  kernel 2 (VMEM)  global position = base[digit] selected per digit value
                   + local rank.
  host (jnp)       one stable scatter materialises the permutation (flat
                   int32 indices), then keys/values move with gathers.

Tiles are whole multiples of 128 lanes and grid blocks whole multiples of
8 tile rows (pad tiles carry an out-of-range digit that no count sees), so
every block is a whole number of (8, 128) vector tiles.

The grid partitions tiles exactly like the paper partitions its SRAM macro
(§II-B): each grid cell histograms its own partition concurrently, and the
exclusive prefix-sum plays the role of the operand-exchange step between
partitions.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the kernel shape parameters (digit width, histogram tile) live in the
# tuning layer; the analytic cost model resolves the same profile, so
# pricing and kernel can't drift — and the dependency points the right way
# (kernels consume tuning; cost_model consumes tuning; neither owns the
# other's constants)
from repro.core import tuning as _tuning


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _resolve(tile: Optional[int], digit_bits: Optional[int]
             ) -> Tuple[int, int]:
    """Fill unset kernel parameters from the active tuning profile.  Runs
    *outside* the jitted entry points so a profile swap reaches fresh
    traces instead of being baked into a stale jit cache."""
    prof = None
    if tile is None or digit_bits is None:
        prof = _tuning.active()
    return (tile if tile is not None else prof.radix_tile,
            digit_bits if digit_bits is not None else prof.digit_bits)


LANES = 128
# histogram/rank tiles per grid block: rows of one (TILE_ROWS, tile) block
# stay within a few dozen vector registers through the per-digit loops
_BLOCK_ELEMS = 8192


def lane_tile(n: int, tile: int) -> int:
    """The tile width the kernels use for rows of ``n``: ``tile`` capped
    at the row and rounded up to whole 128-lane vector rows."""
    return -(-min(tile, n) // LANES) * LANES


def pass_tile_counts(n: int, dtype, tile: Optional[int] = None,
                     digit_bits: Optional[int] = None) -> Tuple[int, int]:
    """(digit passes, VMEM tiles per row) ``sort_blocks`` runs at this
    shape — analytic, from static shapes only, so observability spans and
    cost-model cross-checks can label a jitted kernel call without
    reaching inside the trace."""
    from repro.core import keycodec
    tile, digit_bits = _resolve(tile, digit_bits)
    bits = keycodec.key_bits(dtype)
    return -(-bits // digit_bits), -(-n // lane_tile(n, tile))


# ---------------------------------------------------------------------------
# kernel bodies: (tiles, C) int32 digits, one tile per row
# ---------------------------------------------------------------------------

def _histogram(d, ncols: int):
    """(br, C) digits -> (br, ncols) per-row counts of each value in
    [0, ncols); other values are not counted."""
    col = jax.lax.broadcasted_iota(jnp.int32, (d.shape[0], ncols), 1)

    def count(r, hist):
        c = jnp.sum((d == r).astype(jnp.int32), axis=1, keepdims=True)
        return jnp.where(col == r, c, hist)

    return jax.lax.fori_loop(0, ncols, count, jnp.zeros_like(col))


def _stable_rank(d):
    """Per element: how many earlier elements of its row hold the same
    digit.  Rotating by every distance visits each other element once; the
    rotated lane index tells whether it came from an earlier slot."""
    lane = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)

    def count(s, rank):
        same = (pltpu.roll(d, s, 1) == d) & (pltpu.roll(lane, s, 1) < lane)
        return rank + same.astype(jnp.int32)

    return jax.lax.fori_loop(1, d.shape[1], count, jnp.zeros_like(d))


def _hist_kernel(d_ref, hist_ref):
    hist_ref[...] = _histogram(d_ref[...], hist_ref.shape[1])


def _digit_stats_kernel(d_ref, hist_ref, rank_ref):
    """Per-tile histogram + local stable rank of each element's digit."""
    d = d_ref[...]
    hist_ref[...] = _histogram(d, hist_ref.shape[1])
    rank_ref[...] = _stable_rank(d)


def _global_pos_kernel(d_ref, base_ref, rank_ref, pos_ref):
    """Global slot = base offset of (tile, digit) + local rank."""
    d, base = d_ref[...], base_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, base.shape, 1)

    def add_base(r, pos):
        b = jnp.sum(jnp.where(col == r, base, 0), axis=1, keepdims=True)
        return pos + jnp.where(d == r, b, 0)

    pos_ref[...] = jax.lax.fori_loop(0, base.shape[1], add_base,
                                     rank_ref[...])


# ---------------------------------------------------------------------------
# pallas wrappers
# ---------------------------------------------------------------------------

def _tile_call(kernel, d, extra, out_cols, interpret: bool):
    """Run a per-tile kernel over (tiles, C) digits (plus ``extra``
    (tiles, *) operands), padding the tile count to whole 8-row blocks
    with the digit -1, which no histogram or rank counts.  ``out_cols``
    lists the width of each (tiles, width) int32 output."""
    rows, c = d.shape
    br = max(8, _BLOCK_ELEMS // c // 8 * 8)
    br = min(br, -(-rows // 8) * 8)
    rows_p = -(-rows // br) * br
    ins = [d] + list(extra)
    if rows_p != rows:
        ins = [jnp.pad(d, ((0, rows_p - rows), (0, 0)), constant_values=-1)] \
            + [jnp.pad(e, ((0, rows_p - rows), (0, 0))) for e in extra]

    def spec(width):
        return pl.BlockSpec((br, width), lambda i: (i, 0))

    outs = pl.pallas_call(
        kernel,
        grid=(rows_p // br,),
        in_specs=[spec(a.shape[1]) for a in ins],
        out_specs=[spec(w) for w in out_cols],
        out_shape=[jax.ShapeDtypeStruct((rows_p, w), jnp.int32)
                   for w in out_cols],
        interpret=interpret,
    )(*ins)
    return [o[:rows] for o in outs]


@functools.partial(jax.jit, static_argnames=("ncols", "interpret"))
def tile_hist(d: jnp.ndarray, ncols: int, interpret: bool) -> jnp.ndarray:
    """(tiles, C) int32 digits -> (tiles, ncols) counts of each value in
    [0, ncols); values outside that range are not counted."""
    (hist,) = _tile_call(_hist_kernel, d, (), [ncols], interpret)
    return hist


@functools.partial(jax.jit, static_argnames=("radix", "interpret"))
def _digit_stats(d: jnp.ndarray, radix: int, interpret: bool):
    return _tile_call(_digit_stats_kernel, d, (), [radix, d.shape[1]],
                      interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _global_pos(d: jnp.ndarray, base: jnp.ndarray, rank: jnp.ndarray,
                interpret: bool) -> jnp.ndarray:
    (pos,) = _tile_call(_global_pos_kernel, d, (base, rank), [d.shape[1]],
                        interpret)
    return pos


# ---------------------------------------------------------------------------
# host orchestration: pass loop, padding, permutation
# ---------------------------------------------------------------------------

def _pass_permutation(keys: jnp.ndarray, shift, tile: int,
                      digit_bits: int, interpret: bool) -> jnp.ndarray:
    """Stable permutation ordering ``keys`` by the digit at bit ``shift``
    (static or traced), in gather form."""
    rows, n = keys.shape
    radix = 1 << digit_bits
    n_tiles = n // tile
    digits = jax.lax.shift_right_logical(
        keys, jnp.asarray(shift).astype(keys.dtype)).astype(jnp.int32) \
        & (radix - 1)
    d = digits.reshape(rows * n_tiles, tile)
    hist, rank = _digit_stats(d, radix, interpret)
    # exclusive prefix-sum in digit-major, tile-minor order: every element
    # with a smaller digit anywhere in the row, or the same digit in an
    # earlier tile, precedes you.  Two short scans (over the row's digit
    # totals, and per digit over its tiles) instead of one over the
    # flattened (digit, tile) axis, which the TPU compiler takes long over
    h = hist.reshape(rows, n_tiles, radix)
    total = jnp.sum(h, axis=1, keepdims=True)
    base = (jnp.cumsum(total, axis=-1) - total) + (jnp.cumsum(h, axis=1) - h)
    pos = _global_pos(d, base.reshape(rows * n_tiles, radix), rank,
                      interpret).reshape(rows, n)
    # stable scatter: invert the position map once, then everything moves
    # by gathers (XLA CPU scatters serialise; one int32 scatter is the floor).
    # The scatter is flat, with unique in-bounds indices: the TPU compiler
    # takes minutes over the (row, position)-indexed form at 2^26 keys
    flat = (pos + jnp.arange(rows, dtype=jnp.int32)[:, None] * n).reshape(-1)
    src = jnp.tile(jnp.arange(n, dtype=jnp.int32), rows)
    inv = jnp.zeros((rows * n,), jnp.int32).at[flat].set(
        src, unique_indices=True, mode="promise_in_bounds")
    return inv.reshape(rows, n)


def _padded(keys, vals, tile):
    rows, n = keys.shape
    tile = lane_tile(n, tile)
    m = -(-n // tile) * tile
    if m != n:
        maxkey = jnp.array((1 << jnp.iinfo(keys.dtype).bits) - 1, keys.dtype)
        keys = jnp.pad(keys, ((0, 0), (0, m - n)), constant_values=maxkey)
        if vals is not None:
            # out-of-range marker; stability keeps pads behind real
            # elements even when genuine keys equal the pad key
            vals = jnp.pad(vals, ((0, 0), (0, m - n)),
                           constant_values=jnp.array(n, vals.dtype))
    return keys, vals, tile


@functools.partial(jax.jit,
                   static_argnames=("tile", "digit_bits", "interpret"))
def _lsd_sort(keys: jnp.ndarray, vals: Optional[jnp.ndarray], *, tile: int,
              digit_bits: int, interpret: bool):
    """All digit passes, least significant first.  The passes run as one
    loop so the program holds a single pass body, whatever the key width:
    at engine sizes the compile time of the pass's scatter and prefix sum
    dominates."""
    rows, n = keys.shape
    keys, vals, tile = _padded(keys, vals, tile)
    n_pass = -(-jnp.iinfo(keys.dtype).bits // digit_bits)

    def one_pass(p, carry):
        k, v = carry
        inv = _pass_permutation(k, p * digit_bits, tile, digit_bits,
                                interpret)
        k = jnp.take_along_axis(k, inv, axis=-1)
        if v is not None:
            v = jnp.take_along_axis(v, inv, axis=-1)
        return k, v

    keys, vals = jax.lax.fori_loop(0, n_pass, one_pass, (keys, vals))
    return keys[:, :n], (None if vals is None else vals[:, :n])


def sort_blocks(keys: jnp.ndarray, *, tile: Optional[int] = None,
                digit_bits: Optional[int] = None,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """Stable ascending LSD radix sort of each row of unsigned (rows, n).

    ``tile`` / ``digit_bits`` default to the active tuning profile; the
    resolution happens here, outside the jit, so the inner trace sees
    concrete statics and a ``tuning.set_active`` swap re-dispatches
    instead of replaying a cache keyed on stale parameters."""
    tile, digit_bits = _resolve(tile, digit_bits)
    interp = _interpret_default() if interpret is None else interpret
    out, _ = _lsd_sort(keys, None, tile=tile, digit_bits=digit_bits,
                       interpret=interp)
    return out


def sort_kv_blocks(keys: jnp.ndarray, vals: jnp.ndarray, *,
                   tile: Optional[int] = None,
                   digit_bits: Optional[int] = None,
                   interpret: Optional[bool] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Key-value variant: payloads ride their keys through every pass."""
    tile, digit_bits = _resolve(tile, digit_bits)
    interp = _interpret_default() if interpret is None else interpret
    return _lsd_sort(keys, vals, tile=tile, digit_bits=digit_bits,
                     interpret=interp)
