"""Flash attention (forward) — in-VMEM softmax-attention, Pallas.

The ADS-IMC thesis applied to attention: the S x S score matrix never
touches HBM.  Each grid cell owns one query block in VMEM and streams KV
blocks through it with the online-softmax recurrence (running max m,
normaliser l, accumulator acc — all fp32 in registers/VMEM).  HBM traffic
collapses from O(S^2) score bytes to the O(S) q/k/v/o streams, which is
exactly the term that dominates the prefill_32k roofline cells
(EXPERIMENTS.md §Roofline).

Layout: inputs are flattened to rows — q2 (B*R*G, S, H); k2/v2 (B*R, T, H).
Row r of q2 attends to kv row r // G (blocked GQA grouping, matching
attention._attend).  The grid is (rows, S/q_block); the kv stream is a
`fori_loop` whose upper bound is causal-clipped, so fully-masked blocks are
never read.

Forward-only by design: training keeps the q-chunked einsum path (its
backward is handled by remat), serving/prefill use this kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, off_ref, o_ref, *, q_block: int,
                  k_block: int, causal: bool, window: int, t_len: int,
                  scale: float):
    j = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale            # (qb, H)
    qb, h = q.shape

    # global query offset (context-parallel shards pass their shard origin)
    q_start = j * q_block + off_ref[0, 0]
    if causal:
        hi = jnp.minimum(t_len, q_start + q_block)       # last visible key+1
    else:
        hi = t_len
    n_kv = pl.cdiv(hi, k_block)

    def body(c, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(c * k_block, k_block), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(c * k_block, k_block), :].astype(jnp.float32)
        s = q @ k.T                                      # (qb, kb)
        kpos = c * k_block + jax.lax.broadcasted_iota(
            jnp.int32, (qb, k_block), 1)
        qpos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (qb, k_block), 0)
        mask = kpos < t_len
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        correction = jnp.exp(m - m_new)
        l_new = l * correction + jnp.sum(p, axis=-1)
        acc_new = acc * correction[:, None] + p @ v
        return m_new, l_new, acc_new

    m0 = jnp.full((qb,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((qb,), jnp.float32)
    acc0 = jnp.zeros((qb, h), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_kv, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-20)[:, None]
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_block",
                                             "k_block", "interpret"))
def flash_rows(q2: jnp.ndarray, k2: jnp.ndarray, v2: jnp.ndarray,
               q_offset: jnp.ndarray = None, *,
               causal: bool = True, window: int = 0, q_block: int = 512,
               k_block: int = 512, interpret: bool = False) -> jnp.ndarray:
    """q2: (RQ, S, H); k2/v2: (RK, T, H); RQ = RK * G.  S % q_block == 0.
    q_offset: scalar global origin of q2's sequence (context parallelism)."""
    rq, s, h = q2.shape
    rk, t, _ = k2.shape
    g = rq // rk
    scale = 1.0 / (h ** 0.5)
    t_pad = (-t) % k_block
    if t_pad:
        k2 = jnp.pad(k2, ((0, 0), (0, t_pad), (0, 0)))
        v2 = jnp.pad(v2, ((0, 0), (0, t_pad), (0, 0)))
    if q_offset is None:
        q_offset = jnp.zeros((), jnp.int32)
    off = jnp.reshape(q_offset.astype(jnp.int32), (1, 1))
    grid = (rq, s // q_block)
    return pl.pallas_call(
        functools.partial(_flash_kernel, q_block=q_block, k_block=k_block,
                          causal=causal, window=window, t_len=t,
                          scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_block, h), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, t + t_pad, h), lambda i, j: (i // g, 0, 0)),
            pl.BlockSpec((1, t + t_pad, h), lambda i, j: (i // g, 0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, h), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((rq, s, h), q2.dtype),
        interpret=interpret,
    )(q2, k2, v2, off)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    q_block: int = 512, k_block: int = 512,
                    q_offset=None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, S, N, H); k/v: (B, T, R, H) with N = R * G (blocked groups).
    q_offset: scalar global position of q[:, 0] (context parallelism).
    Returns (B, S, N, H)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, s, n, h = q.shape
    t, r = k.shape[1], k.shape[2]
    # rows: q (B,S,N,H) -> (B,N,S,H) -> (B*N, S, H); N = R*G blocked, so
    # q row (b*n) maps to kv row (b*r + n//g) with g = n // r
    q2 = jnp.moveaxis(q, 1, 2).reshape(b * n, s, h)
    k2 = jnp.moveaxis(k, 1, 2).reshape(b * r, t, h)
    v2 = jnp.moveaxis(v, 1, 2).reshape(b * r, t, h)
    qb = min(q_block, s)
    pad = (-s) % qb
    if pad:
        q2 = jnp.pad(q2, ((0, 0), (0, pad), (0, 0)))
    out = flash_rows(q2, k2, v2, q_offset, causal=causal, window=window,
                     q_block=qb, k_block=min(k_block, t),
                     interpret=interpret)
    out = out[:, :s].reshape(b, n, s, h)
    return jnp.moveaxis(out, 1, 2)
