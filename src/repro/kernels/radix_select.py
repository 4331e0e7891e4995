"""MSD radix select — O(n·b/DIGIT_BITS) top-k without sorting.

The paper's architecture wins by *partial* data movement: §II-B partitions
sort concurrently and only the candidates that can still matter cross a
partition boundary.  For ``k ≪ n`` the same argument says a full
O(n log n) sort is the wrong tool entirely — the hardware-sorting
literature (MemSort's max-search mode; the "Sorting it out in Hardware"
survey's partial-sort taxonomy) treats min/max-search and partial sort as
first-class operating modes, and this module is their VMEM analogue:

  1. **digit refinement** (most-significant digit first): each pass
     histograms one ``DIGIT_BITS``-wide digit of the still-active
     elements (those matching the threshold prefix fixed by earlier
     passes) and walks the cumulative counts to pin the next digit of
     the k-th key.  ``ceil(b/DIGIT_BITS)`` passes of O(n) counting work
     — no element ever moves.
  2. **exact-k mask**: with the threshold key T and the residual tie
     budget r = k - #{enc < T}, the survivors are every element below T
     plus the *first r* (ascending index) elements equal to T.  Exactly
     k survive — the tie rule that makes the selection reproducible and
     lets every consumer budget on k (grad compression wire format,
     MoE capacity, sampling batch shapes).
  3. **compact + order**: survivors scatter to k slots in index order,
     then one tiny two-key ``lax.sort`` over (encoded key, index) puts
     the k candidates in output order — O(k log k) on k elements, dwarfed
     by the counting passes.

Keys go through ``core/keycodec.py`` with ``descending=True`` so "top-k
largest" is "k smallest encoded": ties therefore keep ascending index
order, matching ``jax.lax.top_k``'s lower-index-first rule bit-exactly.

The refinement has two interchangeable engines, mirroring
``engine/samplesort.bucket_bounds``:

  * ``use_kernel=True`` (TPU default) — DIGIT_BITS-wide passes on a
    per-tile one-hot histogram Pallas kernel in the style of
    ``radix_sort.tile_hist``: the grid partitions tiles exactly like
    the paper partitions its SRAM macro, inactive/pad slots carry an
    out-of-range digit that the kernel does not count.
  * ``use_kernel=False`` (host default) — radix-2 refinement, the
    faithful analogue of the paper's bit-serial CAS walk: one masked
    zero-count per key bit, pure branchless compare+reduce jnp with no
    scatter anywhere (XLA CPU scatters serialise, and an interpreted
    Pallas kernel pays the ~300x penalty the planner prices into the
    radix *sort* — selection dodges both).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from repro.core import keycodec
# kernel shape parameters (digit width, histogram tile) come from the
# tuning layer's active profile — the same object the cost model prices
# with (cost_model.selection_cost_ns), so pricing, the LSD sort kernels,
# and this module can't drift apart
from repro.core import tuning as _tuning
from repro.kernels import radix_sort as _rs

__all__ = ["select_topk", "select_topk_kv", "select_topk_encoded",
           "kth_key_encoded"]


def _kernel_default() -> bool:
    return jax.default_backend() == "tpu"


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _resolve(tile: Optional[int], digit_bits: Optional[int]
             ) -> Tuple[int, int]:
    """Fill unset kernel parameters from the active tuning profile —
    outside any jit, so profile swaps reach fresh traces."""
    prof = None
    if tile is None or digit_bits is None:
        prof = _tuning.active()
    return (tile if tile is not None else prof.radix_tile,
            digit_bits if digit_bits is not None else prof.digit_bits)


def pass_tile_counts(n: int, dtype, use_kernel: Optional[bool] = None,
                     tile: Optional[int] = None,
                     digit_bits: Optional[int] = None) -> Tuple[int, int]:
    """(refinement passes, histogram tiles per pass) of the k-th-key
    search at this shape — analytic, from static shapes only.  The
    digit-serial kernel path runs ceil(bits/digit_bits) passes over
    ceil(n/tile) VMEM tiles; the bit-serial host path runs ``bits``
    masked zero-counts with no tiling (tiles = 0)."""
    if use_kernel is None:
        use_kernel = _kernel_default()
    bits = keycodec.key_bits(dtype)
    if not use_kernel:
        return bits, 0
    tile, digit_bits = _resolve(tile, digit_bits)
    return -(-bits // digit_bits), -(-n // _rs.lane_tile(n, tile))


# ---------------------------------------------------------------------------
# masked histogram on the radix sort's per-tile counting kernel
# ---------------------------------------------------------------------------

def _masked_hist(digits: jnp.ndarray, active: jnp.ndarray, radix: int,
                 tile: int, interpret: Optional[bool]) -> jnp.ndarray:
    """(rows, n) digits + active mask -> (rows, radix) active-only counts
    on the per-tile Pallas kernel: inactive and pad slots carry the digit
    ``radix``, which the kernel does not count."""
    rows, n = digits.shape
    d = jnp.where(active, digits, radix)
    tile = _rs.lane_tile(n, tile)
    m = -(-n // tile) * tile
    if m != n:
        d = jnp.pad(d, ((0, 0), (0, m - n)), constant_values=radix)
    interp = _interpret_default() if interpret is None else interpret
    hist = _rs.tile_hist(d.reshape(rows * (m // tile), tile), radix, interp)
    return jnp.sum(hist.reshape(rows, m // tile, radix), axis=1)


# ---------------------------------------------------------------------------
# digit refinement: the k-th encoded key, no data movement
# ---------------------------------------------------------------------------

def _kth_key_digit_serial(enc: jnp.ndarray, k: int, digit_bits: int,
                          tile: int, interpret: Optional[bool]):
    """digit_bits-wide refinement on the Pallas histogram kernel — the
    TPU path: ceil(b/digit_bits) passes of per-tile VPU counting."""
    rows, _ = enc.shape
    bits = jnp.iinfo(enc.dtype).bits
    radix = 1 << digit_bits
    k_rem = jnp.full((rows,), k, jnp.int32)
    thresh = jnp.zeros((rows,), enc.dtype)
    for shift in range(bits - digit_bits, -1, -digit_bits):
        hi = shift + digit_bits
        if hi >= bits:
            active = jnp.ones(enc.shape, bool)
        else:
            sh = jnp.array(hi, enc.dtype)
            active = jax.lax.shift_right_logical(enc, sh) \
                == jax.lax.shift_right_logical(thresh, sh)[:, None]
        digits = (jax.lax.shift_right_logical(enc, jnp.array(shift, enc.dtype))
                  .astype(jnp.int32) & (radix - 1))
        hist = _masked_hist(digits, active, radix, tile, interpret)
        cum = jnp.cumsum(hist, axis=-1)
        # smallest digit whose cumulative count reaches the residual k
        d = jnp.argmax(cum >= k_rem[:, None], axis=-1).astype(jnp.int32)
        less = jnp.take_along_axis(cum - hist, d[:, None], -1)[:, 0]
        k_rem = k_rem - less
        thresh = thresh | (d.astype(enc.dtype)
                           << jnp.array(shift, enc.dtype))
    return thresh, k_rem


def _kth_key_bit_serial(enc: jnp.ndarray, k: int):
    """1-bit refinement in pure jnp — the host path, and the faithful
    radix-2 analogue of the paper's bit-serial CAS walk: per key bit, one
    masked zero-count (compare + reduction, branchless and SIMD-friendly)
    decides the threshold bit.  b passes of O(n) elementwise work and NOT
    ONE scatter — XLA's CPU scatter serialises, which is exactly why the
    digit histogram stays on the TPU kernel.  The pass loop is a
    ``fori_loop`` (the body is shift-uniform), so the compiled program is
    one pass long instead of b passes long — compile time at engine sizes
    stays flat."""
    rows, _ = enc.shape
    bits = jnp.iinfo(enc.dtype).bits
    one = jnp.array(1, enc.dtype)

    def body(i, carry):
        k_rem, thresh, active = carry
        sh = jnp.array(bits - 1, enc.dtype) - i.astype(enc.dtype)
        bit = (jax.lax.shift_right_logical(enc, sh) & one) != 0
        zeros = active & ~bit
        c0 = jnp.sum(zeros, axis=-1).astype(jnp.int32)
        take0 = k_rem <= c0
        active = jnp.where(take0[:, None], zeros, active & bit)
        k_rem = jnp.where(take0, k_rem, k_rem - c0)
        thresh = jnp.where(take0, thresh, thresh | (one << sh))
        return k_rem, thresh, active

    k_rem, thresh, _ = jax.lax.fori_loop(
        0, bits, body, (jnp.full((rows,), k, jnp.int32),
                        jnp.zeros((rows,), enc.dtype),
                        jnp.ones(enc.shape, bool)))
    return thresh, k_rem


def kth_key_encoded(enc: jnp.ndarray, k: int, *,
                    use_kernel: Optional[bool] = None,
                    tile: Optional[int] = None,
                    digit_bits: Optional[int] = None,
                    interpret: Optional[bool] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per row of unsigned ``(rows, n)``: the k-th *smallest* encoded key
    ``T`` and the residual tie budget ``r = k - #{enc < T}`` (how many
    threshold-equal elements the exact-k rule keeps)."""
    if use_kernel is None:
        use_kernel = _kernel_default()
    if use_kernel:
        tile, digit_bits = _resolve(tile, digit_bits)
        return _kth_key_digit_serial(enc, k, digit_bits, tile, interpret)
    return _kth_key_bit_serial(enc, k)


# ---------------------------------------------------------------------------
# exact-k selection over encoded keys
# ---------------------------------------------------------------------------

def select_topk_encoded(enc: jnp.ndarray, k: int, *,
                        use_kernel: Optional[bool] = None,
                        tile: Optional[int] = None,
                        digit_bits: Optional[int] = None,
                        interpret: Optional[bool] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(rows, n) unsigned encoded keys -> the k smallest per row, in
    ascending (encoded, index) order: ``(enc_topk, indices)``, both
    ``(rows, k)``.  Exactly k survive; ties keep ascending index order."""
    rows, n = enc.shape
    if not 1 <= k <= n:
        raise ValueError(
            f"topk k must satisfy 1 <= k <= n (n={n}); got k={k}")
    thresh, k_eq = kth_key_encoded(enc, k, use_kernel=use_kernel, tile=tile,
                                   digit_bits=digit_bits, interpret=interpret)
    less = enc < thresh[:, None]
    eq = enc == thresh[:, None]
    eq_rank = jnp.cumsum(eq.astype(jnp.int32), axis=-1) - 1
    take = less | (eq & (eq_rank < k_eq[:, None]))
    # compact the k survivors in index order WITHOUT a scatter: the
    # cumulative take-count is sorted per row, so the j-th survivor's
    # position is one binary search — O(k log n) gathers (XLA CPU scatters
    # serialise; a length-n scatter here would dwarf the counting passes).
    # Then one tiny two-key lexicographic sort orders the k candidates —
    # the merge step of partition-then-merge, degenerated to O(k log k)
    # because only candidates ever move.
    csum = jnp.cumsum(take.astype(jnp.int32), axis=-1)
    targets = jnp.arange(1, k + 1, dtype=jnp.int32)
    # exactly k survive, so csum[-1] == k >= every target: the search
    # always lands in range
    idx_c = jax.vmap(
        lambda c: jnp.searchsorted(c, targets, side="left"))(csum) \
        .astype(jnp.int32)
    enc_c = jnp.take_along_axis(enc, idx_c, axis=-1)
    return jax.lax.sort((enc_c, idx_c), num_keys=2)


# ---------------------------------------------------------------------------
# front doors (source dtypes through the keycodec)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "use_kernel", "tile",
                                             "digit_bits", "interpret"))
def _select_topk_impl(x: jnp.ndarray, k: int, use_kernel: Optional[bool],
                      tile: Optional[int], digit_bits: Optional[int],
                      interpret: Optional[bool]
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    enc = keycodec.encode(x, descending=True)
    enc_s, idx_s = select_topk_encoded(enc, k, use_kernel=use_kernel,
                                       tile=tile, digit_bits=digit_bits,
                                       interpret=interpret)
    return keycodec.decode(enc_s, x.dtype, descending=True), idx_s


def select_topk(x: jnp.ndarray, k: int, *,
                use_kernel: Optional[bool] = None,
                tile: Optional[int] = None,
                digit_bits: Optional[int] = None,
                interpret: Optional[bool] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k largest per row of ``(rows, n)`` -> (values, indices), values
    descending, ties by ascending index — ``jax.lax.top_k``'s convention,
    in O(n·b/digit_bits) counting work instead of a sort.

    The kernel path's ``tile`` / ``digit_bits`` resolve from the active
    tuning profile here, outside the jit, so ``tuning.set_active`` swaps
    re-dispatch instead of hitting a stale trace cache."""
    if use_kernel is None:
        use_kernel = _kernel_default()
    if use_kernel:
        tile, digit_bits = _resolve(tile, digit_bits)
    return _select_topk_impl(x, k, use_kernel, tile, digit_bits, interpret)


def select_topk_kv(keys: jnp.ndarray, values: jnp.ndarray, k: int, *,
                   use_kernel: Optional[bool] = None,
                   tile: Optional[int] = None,
                   digit_bits: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """Key-value variant: ``(topk keys, payload, indices)`` — the payload
    rides the exact-k selection by one gather through the indices."""
    if values.shape != keys.shape:
        raise ValueError(f"values shape {values.shape} must match keys "
                         f"shape {keys.shape}")
    v, i = select_topk(keys, k, use_kernel=use_kernel, tile=tile,
                       digit_bits=digit_bits, interpret=interpret)
    return v, jnp.take_along_axis(values, i, axis=-1), i
