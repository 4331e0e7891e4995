"""Bit-serial CAS network on TPU lanes — the paper's exact gate schedule.

This kernel executes the reconstructed 28-cycle NOR/NOT/AND/COPY program of
:mod:`repro.core.gates` with each SRAM *row* realised as a VMEM bit-plane of
shape (rows, lanes, W): the paper's column-parallelism maps to the W axis
and the array's batch parallelism maps to the 8x128 vector lanes.  One
simulated IMC cycle = one VPU op over every lane — the closest TPU-idiomatic
equivalent of bitline logic (DESIGN.md §2).

It is deliberately *not* the fast path (word-parallel min/max is ~W times
cheaper — measured in benchmarks/bench_sort_methods.py); it exists to prove
the paper's logic runs unchanged on the target substrate and to anchor the
faithful-baseline row of EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import gates
from repro.core.imc_array import Movement, OpKind, ROW_A, ROW_B, ROW_ONE, ROW_ZERO


def _exec_program(a: jnp.ndarray, b: jnp.ndarray, width: int):
    """Run the gate program on int operands of shape (rows, lanes).

    Each SRAM row is held as ``width`` separate (rows, lanes) 0/1 int32
    planes, one per bit column (MSB first), so the column movements are
    list shuffles and every gate is one lane-wise VPU op: Mosaic lays
    (sublane, lane) vectors out, not a trailing bit axis."""
    prog = gates.build_cas_program(width)
    zero, one = jnp.zeros_like(a), jnp.ones_like(a)

    def bit_planes(v):
        return [(v >> (width - 1 - c)) & 1 for c in range(width)]

    planes = {ROW_ZERO: [zero] * width, ROW_ONE: [one] * width,
              ROW_A: bit_planes(a), ROW_B: bit_planes(b)}

    for op in prog.ops:
        x = planes[op.src1]
        if op.kind is OpKind.NOR:
            r = [1 ^ (u | v) for u, v in zip(x, planes[op.src2])]
        elif op.kind is OpKind.AND:
            r = [u & v for u, v in zip(x, planes[op.src2])]
        elif op.kind is OpKind.NOT:
            r = [1 ^ u for u in x]
        else:  # COPY
            r = list(x)
        if op.movement is Movement.SHIFT_RIGHT:
            r = [one if op.fill else zero] + r[:-1]
        elif op.movement is Movement.BCAST_LAST:
            r = [r[-1]] * width
        elif op.movement is Movement.BCAST_COL:
            r = [r[op.bcast_col]] * width
        planes[op.dst] = r

    def value(bits):
        out = zero
        for bit in bits:
            out = (out << 1) | bit
        return out

    return value(planes[ROW_A]), value(planes[ROW_B])


def _cas_kernel(a_ref, b_ref, lo_ref, hi_ref, *, width: int):
    lo, hi = _exec_program(a_ref[...], b_ref[...], width)
    lo_ref[...] = lo
    hi_ref[...] = hi


@functools.partial(jax.jit, static_argnames=("width", "block_rows",
                                             "interpret"))
def cas_blocks(a: jnp.ndarray, b: jnp.ndarray, *, width: int = 4,
               block_rows: int = 8, interpret: bool = False
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Elementwise in-memory CAS of (rows, lanes) unsigned ints < 2**width."""
    rows, lanes = a.shape
    # a row block is a multiple of 8 sublanes or the whole array
    br = rows if rows <= block_rows else max(8, block_rows // 8 * 8)
    rows_p = -(-rows // br) * br
    a, b = (jnp.pad(v.astype(jnp.int32), ((0, rows_p - rows), (0, 0)))
            for v in (a, b))
    spec = pl.BlockSpec((br, lanes), lambda i: (i, 0))
    lo, hi = pl.pallas_call(
        functools.partial(_cas_kernel, width=width),
        grid=(rows_p // br,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((rows_p, lanes), jnp.int32)] * 2,
        interpret=interpret,
    )(a, b)
    return lo[:rows], hi[:rows]
