"""GROUP BY with SUM: ``repro.relational.group_by(keys, values,
agg="sum")``, with the cell's mesh when it has one.

Traffic parameters: ``keys`` and ``values``, the names of two int32
columns; keys must be non-negative (TPC-H keys are).

Plain reference (numpy, nothing of ``repro``): ``np.bincount`` of the
keys, and of the keys weighted by the values, over the key range; the
groups are the keys counted at least once, ascending.  Compared, each
with limit 0, over the ``n_groups`` valid slots the call returns:

    n_groups_diff   |groups returned - groups in the reference|
    key_mismatches  group keys that differ (or lie past the shorter side)
    sum_mismatches  sums that differ (or lie past the shorter side)

Control: the reference with the keys held at int16, the precision below
int32: keys that agree in their low 16 bits fall into one group.
"""
from __future__ import annotations

import numpy as np

from repro import relational

LIMITS = {"n_groups_diff": 0, "key_mismatches": 0, "sum_mismatches": 0}


def columns(traffic):
    return [traffic["keys"], traffic["values"]]


def call(ctx, inputs):
    t = ctx.traffic
    return relational.group_by(inputs[t["keys"]], inputs[t["values"]],
                               agg="sum", mesh=ctx.mesh)


def keys_per_call(ctx, inputs):
    return int(inputs[ctx.traffic["keys"]].shape[0])


def api_bytes_per_call(ctx, inputs):
    """Keys and values read once; the (n,) keys and sums and the count
    written once."""
    t = ctx.traffic
    k, v = inputs[t["keys"]], inputs[t["values"]]
    return 2 * (k.size * k.dtype.itemsize + v.size * v.dtype.itemsize) + 4


def _groups(keys, values):
    counts = np.bincount(keys)
    sums = np.bincount(keys, weights=values)
    present = np.nonzero(counts)[0]
    return present.astype(keys.dtype), sums[present].astype(np.int64)


def reference(ctx, host_inputs):
    t = ctx.traffic
    keys = host_inputs[t["keys"]]
    if keys.size and keys.min() < 0:
        raise ValueError("group_by_sum's reference needs keys >= 0")
    return _groups(keys, host_inputs[t["values"]])


def control(ctx, host_inputs):
    t = ctx.traffic
    low = host_inputs[t["keys"]].astype(np.int16).astype(np.int32)
    keys, sums = _groups(low - np.int32(np.iinfo(np.int16).min),
                         host_inputs[t["values"]])
    return keys + np.int32(np.iinfo(np.int16).min), len(keys), sums


def host_output(out):
    m = int(out.n_groups)
    return (np.asarray(out.keys)[:m], m,
            np.asarray(out.aggregates[0])[:m].astype(np.int64))


def compare(ctx, host_inputs, out, ref):
    keys, m, sums = out
    ref_keys, ref_sums = ref
    common = min(len(keys), len(ref_keys))
    extra = abs(len(keys) - len(ref_keys))
    return {"n_groups_diff": abs(int(m) - len(ref_keys)),
            "key_mismatches":
                int(np.count_nonzero(keys[:common] != ref_keys[:common]))
                + extra,
            "sum_mismatches":
                int(np.count_nonzero(sums[:common] != ref_sums[:common]))
                + extra}


def planned_method(ctx, inputs):
    """The planner's pick for this call (its cached plan, no new work)."""
    from repro.engine import planner
    k = inputs[ctx.traffic["keys"]]
    if ctx.mesh is not None:
        return "distributed/" + planner.choose_distributed_cached(
            k.shape[0], ctx.mesh.size, k.dtype).strategy
    return planner.choose_relational_cached("group_by", k.shape[0],
                                            dtype=k.dtype).method
