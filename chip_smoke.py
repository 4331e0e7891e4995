#!/usr/bin/env python3
"""Smoke run of the sort engine's front door on a TPU.

    python3 chip_smoke.py [--seed N]          # one chip
    python3 chip_smoke.py --chips 4           # the mesh path on four chips

Drives ``repro.sort`` and ``repro.relational`` through the planner, the
registered backends and their Pallas kernels at sizes users call real, and
checks every result bit-exactly against an independent reference
(``jnp.sort`` / ``jnp.argsort`` / ``jax.lax.top_k`` on the device, or
numpy on the host).  Data is drawn from ``--seed`` on the device.

Each phase prints one line: the operation, its size and dtype, the
planner's method, whether it matched, a wall time (a smoke timing of one
call, not a benchmark) and the device's peak memory.  One-chip phases time
a warm second call; mesh phases time their single first call, compilation
included.  The last line is one JSON object naming the device.  A phase
that does not match, or any error, ends the run with a non-zero exit code;
so does a machine whose first JAX device is not a TPU.

``--chips 4`` runs only the mesh phases over ``jax.make_mesh((4,),
("data",))``: sort, unique, sort_kv, group-by and top-k of 2^28 int32
keys (256 MiB per chip), each checked against numpy and for output shards
on four distinct devices.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# one chip
N_LARGE = 1 << 26            # 256 MiB of 32-bit keys
BATCH_SHAPE = (64, 1 << 16)  # rows at the Pallas network's width cap
TOPK_SHAPE = (128, 1 << 18)  # sampling over a vocabulary slice
TOPK_K = 64
TOPK_PALLAS_SHAPE = (128, 1 << 15)   # rows the Pallas network holds whole
N_REL = 1 << 24
N_EXPLICIT = 1 << 22
# four chips
N_MESH = 1 << 28             # 256 MiB of int32 keys per chip
MESH_KEY_RANGE = 1 << 22     # ~64 duplicates per key


class PhaseFailed(Exception):
    pass


def _bits(a):
    """Exact comparison form: floats by their bit patterns."""
    import jax
    import jax.numpy as jnp
    if jnp.issubdtype(a.dtype, jnp.floating):
        width = {2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize]
        return jax.lax.bitcast_convert_type(a, width)
    return a


def same(a, b) -> bool:
    import jax.numpy as jnp
    return a.shape == b.shape and a.dtype == b.dtype \
        and bool(jnp.array_equal(_bits(a), _bits(b)))


class Smoke:
    def __init__(self, seed: int):
        import jax
        self.key = jax.random.PRNGKey(seed)
        self.dev = jax.devices()[0]
        self.n_phases = 0

    def next_key(self):
        import jax
        self.key, k = jax.random.split(self.key)
        return k

    def phase(self, op: str, shape, dtype, method: str, fn, check,
              warm: bool = True) -> None:
        """Run ``fn`` (compile + first call) and check it; with ``warm``
        time a second call, else report the first call's time."""
        import jax
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        wall = time.perf_counter() - t0
        ok = bool(check(out))
        del out
        if warm:
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            wall = time.perf_counter() - t0
        stats = self.dev.memory_stats() or {}
        label = "smoke_wall_s" if warm else \
            "smoke_first_call_s (compilation included)"
        print(f"phase op={op} shape={tuple(shape)} dtype={dtype} "
              f"method={method} ok={ok} {label}={wall} "
              f"(smoke timing, not a benchmark) "
              f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}",
              flush=True)
        self.n_phases += 1
        if not ok:
            raise PhaseFailed(f"{op} {tuple(shape)} {dtype} ({method}) "
                              f"does not match its reference")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def _keys(s: Smoke, shape, dtype):
    """Keys with negatives and duplicates: floats from a wide normal,
    ints from a range much smaller than the row."""
    import jax
    import jax.numpy as jnp
    k = s.next_key()
    if jnp.issubdtype(dtype, jnp.floating):
        return (jax.random.normal(k, shape, jnp.float32) * 1000).astype(dtype)
    lim = max(2, shape[-1] // 8)
    return jax.random.randint(k, shape, -lim, lim, dtype)


SORT_OPS = ("sort", "argsort", "sort_kv")
KEY_DTYPES = ("float32", "int32")


def sort_phases(s: Smoke, shape, method="auto", ops=SORT_OPS,
                dtypes=KEY_DTYPES) -> None:
    """sort / argsort / sort_kv along the last axis, against the stable
    ``jnp.argsort`` (every backend keeps ties in index order for these:
    argsort by contract, sort_kv because the payload is the index)."""
    import jax.numpy as jnp
    import repro.sort as rsort
    from repro.engine import planner
    n, batch = shape[-1], 1 if len(shape) == 1 else shape[0]
    for dtype in dtypes:
        x = _keys(s, shape, dtype)
        picked = planner.choose_cached(n, batch, dtype, requested=method).method
        order = jnp.argsort(x, axis=-1, stable=True)
        ref = jnp.take_along_axis(x, order, axis=-1)
        payload = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), shape)
        runs = {
            "sort": (lambda: rsort.sort(x, method=method),
                     lambda out: same(out, ref)),
            "argsort": (lambda: rsort.argsort(x, method=method),
                        lambda out: same(out, order)),
            "sort_kv": (lambda: rsort.sort_kv(x, payload, method=method),
                        lambda out: same(out[0], ref)
                        and same(out[1], order)),
        }
        for op in ops:
            s.phase(op, shape, dtype, picked, *runs[op])


def topk_phase(s: Smoke, shape, dtype, k: int, method="auto") -> None:
    import jax
    import jax.numpy as jnp
    import repro.sort as rsort
    from repro.engine import planner
    x = _keys(s, shape, dtype)
    picked = planner.choose_cached(shape[-1], shape[0], dtype,
                                   requested=method, k=k).method
    rv, ri = jax.lax.top_k(x, k)
    s.phase("topk", shape, jnp.dtype(dtype).name, picked,
            lambda: rsort.topk(x, k, method=method),
            lambda out: same(out[0], rv) and same(out[1], ri))


def relational_phases(s: Smoke, n: int) -> None:
    """unique and group_by(sum) of int32 keys with duplicates, against
    numpy."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import relational
    from repro.engine import planner
    keys = jax.random.randint(s.next_key(), (n,), 0, n // 16, jnp.int32)
    vals = jax.random.randint(s.next_key(), (n,), 0, 100, jnp.int32)
    hk, hv = np.asarray(keys), np.asarray(vals)
    uniq = np.unique(hk)
    sums = np.bincount(hk, weights=hv)[uniq].astype(np.int64)

    def unique_ok(u):
        m = int(u.n_unique)
        return m == uniq.size and np.array_equal(np.asarray(u.values[:m]),
                                                 uniq)

    def group_ok(g):
        m = int(g.n_groups)
        return (m == uniq.size
                and np.array_equal(np.asarray(g.keys[:m]), uniq)
                and np.array_equal(np.asarray(g.aggregates[0][:m]), sums))

    for op, fn, check in (
            ("unique", lambda: relational.unique(keys), unique_ok),
            ("group_by", lambda: relational.group_by(keys, vals, agg="sum"),
             group_ok)):
        picked = planner.choose_relational_cached(op, n, 1, jnp.int32).method
        s.phase(op, (n,), "int32", picked, fn, check)


def one_chip(s: Smoke) -> None:
    import jax.numpy as jnp
    sort_phases(s, (N_LARGE,))
    sort_phases(s, BATCH_SHAPE)
    topk_phase(s, TOPK_SHAPE, jnp.bfloat16, TOPK_K)
    relational_phases(s, N_REL)
    # every kernel-backed method by name, so none is bypassed unseen, and
    # the xla backend beside them at the shapes auto chose for above
    for method in ("xla", "radix"):
        sort_phases(s, (N_LARGE,), method=method, ops=("sort",),
                    dtypes=("float32",))
    for method in ("xla", "radix", "pallas"):
        sort_phases(s, BATCH_SHAPE, method=method, ops=("sort",),
                    dtypes=("float32",))
    for method in ("xla", "select"):
        topk_phase(s, TOPK_SHAPE, jnp.bfloat16, TOPK_K, method=method)
    topk_phase(s, TOPK_PALLAS_SHAPE, jnp.float32, TOPK_K, method="pallas")
    sort_phases(s, (N_EXPLICIT,), method="radix", ops=("sort_kv",),
                dtypes=("int32",))
    sort_phases(s, (N_EXPLICIT,), method="merge", ops=("sort", "argsort"),
                dtypes=("float32",))


# ---------------------------------------------------------------------------
# four chips: the mesh path
# ---------------------------------------------------------------------------

def _on_four_devices(x) -> bool:
    shards = x.addressable_shards
    return len({sh.device for sh in shards}) == 4 \
        and all(sh.data.size == x.size // 4 for sh in shards)


def mesh_phases(s: Smoke) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    import repro.sort as rsort
    from repro import relational
    from repro.engine import planner
    mesh = jax.make_mesh((4,), ("data",))
    spec = NamedSharding(mesh, P("data"))
    n = N_MESH
    gen = jax.jit(lambda k, hi: jax.random.randint(k, (n,), 0, hi, jnp.int32),
                  static_argnums=1, out_shardings=spec)
    keys = gen(s.next_key(), MESH_KEY_RANGE)
    vals = gen(s.next_key(), 100)
    payload = jax.device_put(jnp.arange(n, dtype=jnp.int32), spec)
    hk, hv = np.asarray(keys), np.asarray(vals)
    counts = np.bincount(hk, minlength=MESH_KEY_RANGE)
    uniq = np.nonzero(counts)[0].astype(np.int32)
    ref_sorted = np.repeat(np.arange(MESH_KEY_RANGE, dtype=np.int32), counts)
    sums = np.bincount(hk, weights=hv, minlength=MESH_KEY_RANGE)[uniq]
    # lax.top_k order: values descending, ties by ascending index; the
    # k-th largest key read off the counts, then one pass for candidates
    desc = uniq[::-1]
    kth = desc[np.searchsorted(np.cumsum(counts[desc]), TOPK_K)]
    cand = np.nonzero(hk >= kth)[0]
    top_i = cand[np.lexsort((cand, -hk[cand].astype(np.int64)))][:TOPK_K]
    strategy = planner.choose_distributed_cached(n, 4, jnp.int32).strategy
    method = f"distributed/{strategy}"

    def sort_ok(out):
        return _on_four_devices(out) \
            and np.array_equal(np.asarray(out), ref_sorted)

    def sort_kv_ok(out):
        # the mesh sample-sort is not stable: keys must match exactly and
        # the payload must be a permutation that carries each key along
        k, p = out
        if not (_on_four_devices(k) and _on_four_devices(p)):
            return False
        k, p = np.asarray(k), np.asarray(p)
        seen = np.zeros(n, bool)
        seen[p] = True
        return np.array_equal(k, ref_sorted) and seen.all() \
            and np.array_equal(hk[p], k)

    def topk_ok(out):
        v, i = np.asarray(out[0]), np.asarray(out[1])
        return np.array_equal(i, top_i) and np.array_equal(v, hk[top_i])

    def unique_ok(u):
        m = int(u.n_unique)
        return m == uniq.size \
            and np.array_equal(np.asarray(u.values[:m]), uniq)

    def group_ok(g):
        m = int(g.n_groups)
        return (m == uniq.size
                and np.array_equal(np.asarray(g.keys[:m]), uniq)
                and np.array_equal(np.asarray(g.aggregates[0][:m]), sums))

    # one call each, timed with its compilation: a mesh sort of 2^28 keys
    # runs for tens of seconds.  unique and group_by reuse the programs
    # the sort and sort_kv calls compiled.
    shape = (n,)
    for op, fn, check in (
            ("sort", lambda: rsort.sort(keys, mesh=mesh), sort_ok),
            ("unique", lambda: relational.unique(keys, mesh=mesh),
             unique_ok),
            ("sort_kv", lambda: rsort.sort_kv(keys, payload, mesh=mesh),
             sort_kv_ok),
            ("group_by",
             lambda: relational.group_by(keys, vals, agg="sum", mesh=mesh),
             group_ok)):
        s.phase(op, shape, "int32", method, fn, check, warm=False)
    s.phase("topk", shape, "int32", "distributed/candidates",
            lambda: rsort.topk(keys, TOPK_K, mesh=mesh), topk_ok, warm=False)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices; JAX found {len(devices)}", file=sys.stderr)
        return 1

    from repro.core import tuning
    from repro.runtime import compile_cache
    cache_dir = compile_cache.enable()
    print(f"compile cache: {cache_dir}")
    prof = tuning.active()
    print(f"tuning profile: source={prof.source} "
          f"fingerprint={prof.fingerprint}")

    s = Smoke(args.seed)
    t0 = time.perf_counter()
    mesh_phases(s) if args.chips == 4 else one_chip(s)
    print(f"{s.n_phases} phases passed in {time.perf_counter() - t0:.1f} s "
          f"(compilation included)", flush=True)
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache: {entries} entries in {cache_dir}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
