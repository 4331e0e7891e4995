"""Key-value sort: ``repro.sort.sort_kv(keys, values)``, ascending, with
the cell's mesh when it has one.

Traffic parameters: ``keys`` and ``values``, the names of two int32
columns; ``values`` must hold distinct row ids (``row_id``), so that the
payload says which input row each output slot came from.

Plain reference (numpy, nothing of ``repro``): the keys sorted.  The
guarantee is that keys come out ascending and each payload stays with its
key; the order of equal keys is not specified (the mesh sample-sort is
not stable).  Compared, each with limit 0:

    key_mismatches      output keys that differ from the sorted keys
    payload_mismatches  output slots whose row id is out of range, is
                        used twice, or names a row whose key is not the
                        key in that slot

Control: the reference with its comparisons made at int16, the precision
below int32: keys compared by their low 16 bits (as an int16 cast keeps
them), so rows come out ordered by the truncated key.
"""
from __future__ import annotations

import numpy as np

import repro.sort as rsort

LIMITS = {"key_mismatches": 0, "payload_mismatches": 0}


def columns(traffic):
    return [traffic["keys"], traffic["values"]]


def call(ctx, inputs):
    t = ctx.traffic
    return rsort.sort_kv(inputs[t["keys"]], inputs[t["values"]],
                         mesh=ctx.mesh)


def keys_per_call(ctx, inputs):
    return int(inputs[ctx.traffic["keys"]].shape[0])


def api_bytes_per_call(ctx, inputs):
    """Keys and payload read once, sorted keys and payload written once."""
    t = ctx.traffic
    k, v = inputs[t["keys"]], inputs[t["values"]]
    return 2 * (k.size * k.dtype.itemsize + v.size * v.dtype.itemsize)


def reference(ctx, host_inputs):
    return np.sort(host_inputs[ctx.traffic["keys"]])


def control(ctx, host_inputs):
    t = ctx.traffic
    keys = host_inputs[t["keys"]]
    order = np.argsort(keys.astype(np.int16), kind="stable")
    return keys[order], host_inputs[t["values"]][order]


def compare(ctx, host_inputs, out, ref):
    keys_in = host_inputs[ctx.traffic["keys"]]
    k, p = (np.asarray(a) for a in out)
    n = ref.shape[0]
    if k.shape != ref.shape or p.shape != ref.shape:
        return {"key_mismatches": n, "payload_mismatches": n}
    key_bad = int(np.count_nonzero(k != ref))
    in_range = (p >= 0) & (p < n)
    pc = np.where(in_range, p, 0)
    counts = np.bincount(pc[in_range], minlength=n)
    carried = in_range & (keys_in[pc] == k)
    repeated = int(np.sum(np.maximum(counts - 1, 0)))
    return {"key_mismatches": key_bad,
            "payload_mismatches": int(np.count_nonzero(~carried)) + repeated}


def host_output(out):
    return tuple(np.asarray(a) for a in out)


def planned_method(ctx, inputs):
    """The planner's pick for this call (its cached plan, no new work)."""
    from repro.engine import planner
    k = inputs[ctx.traffic["keys"]]
    if ctx.mesh is not None:
        return "distributed/" + planner.choose_distributed_cached(
            k.shape[0], ctx.mesh.size, k.dtype).strategy
    return planner.choose_cached(k.shape[0], 1, k.dtype).method
