"""Public, jit'd entry points for the Pallas sorting kernels.

Handles everything the raw kernels don't: arbitrary axes and leading dims,
non-power-of-two padding, hierarchical composition for vocab-sized top-k,
autodiff (custom VJPs — sort is a permutation, so its transpose is a
scatter), and interpret-mode fallback so the same code runs on CPU CI.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import bitonic_sort as _bs
from repro.kernels import bitserial_cas as _bc


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def _sentinel(dtype, descending: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(-jnp.inf if descending else jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.min if descending else info.max, dtype)


def _to_rows(x: jnp.ndarray, axis: int):
    """Move ``axis`` last and flatten leading dims -> (rows, n)."""
    axis = axis % x.ndim
    x = jnp.moveaxis(x, axis, -1)
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead, axis


def _from_rows(rows: jnp.ndarray, lead, axis: int):
    return jnp.moveaxis(rows.reshape(*lead, rows.shape[-1]), -1, axis)


# ---------------------------------------------------------------------------
# sort
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def bitonic_sort(x: jnp.ndarray, axis: int = -1, descending: bool = False,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """Sort along ``axis`` with the in-VMEM bitonic kernel."""
    out, _ = _sort_fwd_impl(x, axis, descending, interpret)
    return out


def _sort_fwd_impl(x, axis, descending, interpret):
    interp = _interpret_default() if interpret is None else interpret
    rows, lead, ax = _to_rows(x, axis)
    n = rows.shape[-1]
    m = _next_pow2(n)
    if m != n:
        rows = jnp.pad(rows, ((0, 0), (0, m - n)),
                       constant_values=_sentinel(x.dtype, descending))
    sk, si = _bs.argsort_blocks(rows, descending=descending,
                                interpret=interp)
    sk, si = sk[:, :n], si[:, :n]
    return _from_rows(sk, lead, ax), _from_rows(si, lead, ax)


def _sort_fwd(x, axis, descending, interpret):
    out, order = _sort_fwd_impl(x, axis, descending, interpret)
    return out, order


def _sort_bwd(axis, descending, interpret, order, g):
    shape = order.shape
    ax = axis % len(shape)
    go = jnp.moveaxis(g, ax, -1)
    oo = jnp.moveaxis(order, ax, -1)
    lead = go.shape[:-1]
    n = go.shape[-1]
    go2 = go.reshape(-1, n)
    oo2 = oo.reshape(-1, n)
    gx = jnp.zeros_like(go2)
    rows = jnp.arange(go2.shape[0])[:, None]
    gx = gx.at[rows, oo2].add(go2)
    gx = jnp.moveaxis(gx.reshape(*lead, n), -1, ax)
    return (gx,)


bitonic_sort.defvjp(_sort_fwd, _sort_bwd)


def bitonic_argsort(x: jnp.ndarray, axis: int = -1, descending: bool = False,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Argsort along ``axis`` with the in-VMEM kv kernel (int32 indices)."""
    _, order = _sort_fwd_impl(x, axis, descending, interpret)
    return order


# ---------------------------------------------------------------------------
# top-k (hierarchical for large n)
# ---------------------------------------------------------------------------

_TOPK_CHUNK = 2048


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def bitonic_topk(x: jnp.ndarray, k: int, chunk: int = _TOPK_CHUNK,
                 interpret: Optional[bool] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k along the last axis -> (values, indices), descending order.

    Large axes are processed as partitions of ``chunk`` lanes (per-partition
    kernel top-k) followed by a kv-merge of candidates — the paper's
    partition-then-merge structure (§II-B).
    """
    return _topk_impl(x, k, chunk, interpret)


def _topk_impl(x, k, chunk, interpret):
    interp = _interpret_default() if interpret is None else interpret
    rows, lead, _ = _to_rows(x, -1)
    n = rows.shape[-1]
    sent = _sentinel(x.dtype, descending=True)

    if n <= chunk:
        m = max(_next_pow2(n), _next_pow2(k))
        if m != n:
            rows = jnp.pad(rows, ((0, 0), (0, m - n)), constant_values=sent)
        v, i = _bs.topk_blocks(rows, k, interpret=interp)
        return (v.reshape(*lead, k), i.reshape(*lead, k))

    # hierarchical: per-chunk top-k, then merge candidates by key
    n_chunks = -(-n // chunk)
    m = n_chunks * chunk
    if m != n:
        rows = jnp.pad(rows, ((0, 0), (0, m - n)), constant_values=sent)
    r = rows.reshape(-1, chunk)
    kk = min(k, chunk)
    v, i = _bs.topk_blocks(r, kk, interpret=interp)
    offs = (jnp.arange(n_chunks, dtype=jnp.int32) * chunk)[None, :, None]
    v = v.reshape(-1, n_chunks, kk)
    i = i.reshape(-1, n_chunks, kk) + offs
    cand_v = v.reshape(-1, n_chunks * kk)
    cand_i = i.reshape(-1, n_chunks * kk)
    cm = _next_pow2(cand_v.shape[-1])
    if cm != cand_v.shape[-1]:
        pad = cm - cand_v.shape[-1]
        cand_v = jnp.pad(cand_v, ((0, 0), (0, pad)), constant_values=sent)
        cand_i = jnp.pad(cand_i, ((0, 0), (0, pad)), constant_values=-1)
    sv, si = _bs.sort_kv_blocks(cand_v, cand_i, descending=True,
                                interpret=interp)
    return (sv[:, :k].reshape(*lead, k), si[:, :k].reshape(*lead, k))


def _topk_fwd(x, k, chunk, interpret):
    v, i = _topk_impl(x, k, chunk, interpret)
    return (v, i), (i, jnp.shape(x)[-1], x.shape)


def _topk_bwd(k, chunk, interpret, res, g):
    idx, n, shape = res
    gv, _ = g
    lead_n = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
    gv2 = gv.reshape(lead_n, k)
    ix2 = idx.reshape(lead_n, k)
    gx = jnp.zeros((lead_n, n), dtype=gv.dtype)
    rows = jnp.arange(lead_n)[:, None]
    gx = gx.at[rows, ix2].add(gv2)
    return (gx.reshape(shape),)


bitonic_topk.defvjp(_topk_fwd, _topk_bwd)


# ---------------------------------------------------------------------------
# bit-serial CAS (faithful mode)
# ---------------------------------------------------------------------------

def bitserial_cas(a: jnp.ndarray, b: jnp.ndarray, *, width: int = 4,
                  interpret: Optional[bool] = None):
    """Elementwise (min, max) of unsigned ints via the paper's gate program."""
    interp = _interpret_default() if interpret is None else interpret
    shape = a.shape
    flat_a = a.reshape(-1)
    flat_b = b.reshape(-1)
    n = flat_a.shape[0]
    lanes = 128 if n >= 128 else n
    m = -(-n // lanes) * lanes
    if m != n:
        flat_a = jnp.pad(flat_a, (0, m - n))
        flat_b = jnp.pad(flat_b, (0, m - n))
    lo, hi = _bc.cas_blocks(flat_a.reshape(-1, lanes),
                            flat_b.reshape(-1, lanes),
                            width=width, interpret=interp)
    return (lo.reshape(-1)[:n].reshape(shape),
            hi.reshape(-1)[:n].reshape(shape))
