"""Row top-k: ``repro.sort.topk(x, k)`` over the last axis.

Traffic parameters: ``input``, the name of a (rows, n) float column, and
``k``, a number or the name of a configuration key.

Plain reference (numpy, nothing of ``repro``; ``lax.top_k`` is what one
backend runs, so it is no reference): per row, every value above the k-th
largest, then the k-th largest value's positions from the lowest, until k;
ordered by value descending, then position ascending.  Compared over
every call checked, each with limit 0:

    value_mismatches  returned values whose bits differ from the reference
    index_mismatches  returned positions that differ from the reference

Control: the reference computed on the logits rounded to bfloat16, the
precision below float32.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

import repro.sort as rsort
from benchkit.spec import resolve

LIMITS = {"value_mismatches": 0, "index_mismatches": 0}


def columns(traffic):
    return [traffic["input"]]


def _k(ctx):
    return int(resolve(ctx.config, ctx.traffic["k"]))


def call(ctx, inputs):
    return rsort.topk(inputs[ctx.traffic["input"]], _k(ctx))


def keys_per_call(ctx, inputs):
    return int(inputs[ctx.traffic["input"]].size)


def api_bytes_per_call(ctx, inputs):
    """The rows read once; k values and k int32 positions per row written
    once."""
    x = inputs[ctx.traffic["input"]]
    rows = x.size // x.shape[-1]
    return x.size * x.dtype.itemsize + rows * _k(ctx) * (x.dtype.itemsize + 4)


def top_k_rows(x: np.ndarray, k: int):
    n = x.shape[-1]
    kth = np.partition(x, n - k, axis=-1)[:, n - k]
    values = np.empty((x.shape[0], k), x.dtype)
    index = np.empty((x.shape[0], k), np.int32)
    for r in range(x.shape[0]):
        row = x[r]
        above = np.flatnonzero(row > kth[r])
        tied = np.flatnonzero(row == kth[r])[:k - above.size]
        cand = np.concatenate([above, tied])
        order = np.lexsort((cand, -row[cand].astype(np.float64)))
        index[r] = cand[order]
        values[r] = row[index[r]]
    return values, index


def reference(ctx, host_inputs):
    return top_k_rows(host_inputs[ctx.traffic["input"]], _k(ctx))


def control(ctx, host_inputs):
    x = host_inputs[ctx.traffic["input"]]
    low = x.astype(ml_dtypes.bfloat16).astype(x.dtype)
    return top_k_rows(low, _k(ctx))


def host_output(out):
    return tuple(np.asarray(a) for a in out)


def compare(ctx, host_inputs, out, ref):
    v, i = out
    rv, ri = ref
    if v.shape != rv.shape or i.shape != ri.shape:
        return {"value_mismatches": rv.size, "index_mismatches": ri.size}
    bits = {2: np.uint16, 4: np.uint32, 8: np.uint64}[rv.dtype.itemsize]
    return {"value_mismatches": int(np.count_nonzero(
                v.astype(rv.dtype).view(bits) != rv.view(bits))),
            "index_mismatches": int(np.count_nonzero(i != ri))}


def planned_method(ctx, inputs):
    """The planner's pick for this call (its cached plan, no new work)."""
    from repro.engine import planner
    x = inputs[ctx.traffic["input"]]
    return planner.choose_cached(x.shape[-1], x.size // x.shape[-1],
                                 x.dtype, k=_k(ctx)).method
