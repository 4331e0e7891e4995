"""mesh_exchange_ms: device time per call, per chip, in the mesh
sample-sort's phase-2 program (device module ``jit_samplesort_phase2``):
the bucket send buffers, the all-to-all exchange, the merge of the
received runs and the rank rebalance, all the work that a one-chip sort
does not do.

A TPU trace names each operation ``<module>/<instruction>``.  A trace
with no operation of that module gives nothing: a one-chip cell, a
program whose phase 2 has another name, or a CPU trace (whose operations
carry no module)."""

MODULE = "jit_samplesort_phase2/"


def in_phase2(name, category):
    return name.startswith(MODULE)


def read(run):
    t = run.trace
    if t is None or not t.calls:
        return None
    if not any(in_phase2(n, c) for ops in t.ops.values()
               for n, c, _, _ in ops):
        return None
    return 1e3 * t.op_seconds(in_phase2) / len(t.calls)
