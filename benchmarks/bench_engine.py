"""Engine benchmark: merge engine vs whole-array pallas-bitonic vs XLA.

Sweeps n from one-VMEM-tile scale to millions of elements and, for each of

  * ``xla``            jnp.sort (the off-memory reference),
  * ``pallas-bitonic`` the whole-array in-VMEM network (O(n log^2 n) CAS),
  * ``merge-engine``   tiled runs + merge-path merge tree (O(n log n)),
  * ``radix``          keycodec + Pallas LSD radix sort (O(n·b)),
  * ``auto``           whatever the planner dispatches to,

records TWO latencies:

  ``cold_ms``   first call: trace + compile + run.  The honest cost of a
                one-shot sort at a new size — the analytics workload the
                engine targets.  The whole-array network is size-specialised
                (every n compiles its own O(log^2 n)-substage program, and
                the build explodes with n), while the engine reuses
                tile-sized programs across n.
  ``warm_us``   steady-state per call after compilation.

Emits ``name,us_per_call,derived`` rows like the other suites (``cold`` rows
carry ms in the value column, labelled in the name).  The summary rows
compare merge vs pallas-bitonic at the largest n on both metrics.

A top-k leg (``topk_{sort,select,xla,auto}`` rows at k=64) compares the
sort-prefix path against the MSD radix-select backend and records the
measured select/sort crossover — the README "Selection" table and the
planner's sanity anchor.

With ``--devices D`` (or an externally set
``XLA_FLAGS=--xla_force_host_platform_device_count=D``) a distributed leg
also runs: single-round sample-sort vs D-round odd-even transposition over
the simulated mesh, plus the strategy ``planner.choose_distributed``
auto-selects per n — the measured crossover for the README table — and a
``topk_dist`` leg timing the mesh-global candidate-all-gather top-k.

  PYTHONPATH=src python -m benchmarks.bench_engine [--full] [--sizes 4096,...]
      [--devices 8]
"""
from __future__ import annotations

import time

import numpy as np

DEFAULT_SIZES = (4096, 65536, 1 << 20)
FULL_SIZES = (4096, 16384, 65536, 262144, 1 << 20, 1 << 22)

# interpret-mode radix pays the planner's ~300x penalty; cap its leg off-TPU
# so --full stays runnable (the crossover summary uses its largest timed n)
RADIX_INTERPRET_CAP = 65536


def _time_cold_warm(make_fn, x, reps: int):
    """(cold first-call seconds, warm mean seconds) for a fresh jit —
    tuple-valued fns (top-k) time their whole output tree."""
    import jax
    f = jax.jit(make_fn)
    t0 = time.perf_counter()
    jax.block_until_ready(f(x))
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(f(x))
    return cold, (time.perf_counter() - t0) / reps


def _time_cold_warm_eager(fn, x, reps: int):
    """Like ``_time_cold_warm`` but without an outer jit: the distributed
    entry point runs cached jitted phases around one host sync (the
    measured bucket capacity), so it is timed as called in practice."""
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(fn(x))
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(x))
    return cold, (time.perf_counter() - t0) / reps


TOPK_K = 64


def run_topk(sizes=DEFAULT_SIZES, k=TOPK_K):
    """Selection vs sort-prefix: the ``k ≪ n`` workload class.

    Rows per n:

      * ``topk_sort``    the sort-prefix path: full descending stable
                         argsort + gather of the k prefix — what every
                         top-k consumer paid before the selection
                         subsystem existed.
      * ``topk_select``  the MSD radix-select backend (O(n·passes)).
      * ``topk_xla``     jax.lax.top_k, for context.
      * ``topk_auto``    the k-aware planner's pick (tagged with the
                         resolved backend).

    The summary row is the acceptance metric: select vs sort-prefix warm
    speedup at the largest n.
    """
    import jax.numpy as jnp
    from repro import engine, sort as rsort

    rows, summary = [], {}
    rng = np.random.default_rng(0)

    def sort_prefix(v):
        import jax.numpy as jnp
        order = jnp.argsort(v, axis=-1, stable=True, descending=True)
        return jnp.take_along_axis(v, order, -1)[..., :k], order[..., :k]

    legs = [
        ("topk_sort", sort_prefix),
        ("topk_select", lambda v: rsort.topk(v, k, method="select")),
        ("topk_xla", lambda v: rsort.topk(v, k, method="xla")),
        ("topk_auto", lambda v: rsort.topk(v, k)),
    ]
    for n in sizes:
        if n < k:
            continue
        x = jnp.asarray(rng.standard_normal((1, n)), jnp.float32)
        reps = 3 if n <= 65536 else 1
        for name, fn in legs:
            cold, warm = _time_cold_warm(fn, x, reps)
            tag = n
            if name == "topk_auto":
                plan = engine.choose_cached(n, 1, jnp.float32, k=k)
                tag = f"{n}:{plan.method}"
            rows.append((f"engine.{name}.cold_ms.n{n}.k{k}",
                         round(cold * 1e3, 1), tag))
            rows.append((f"engine.{name}.warm_us.n{n}.k{k}",
                         round(warm * 1e6, 1), tag))
            summary[(name, n)] = (cold, warm)
    if not summary:                    # every size below k: no topk leg
        return rows
    n_max = max(n for n in sizes if n >= k)
    sc, sw = summary[("topk_select", n_max)]
    fc, fw = summary[("topk_sort", n_max)]
    rows.append((f"engine.topk_select_vs_sort_warm_speedup.n{n_max}.k{k}",
                 0.0, round(fw / sw, 2)))
    rows.append((f"engine.topk_select_vs_sort_cold_speedup.n{n_max}.k{k}",
                 0.0, round(fc / sc, 2)))
    # measured crossover: largest n where sort-prefix still wins warm
    cross = [n for n in sizes if n >= k
             and summary[("topk_sort", n)][1] < summary[("topk_select", n)][1]]
    rows.append((f"engine.topk_crossover.k{k}", 0.0,
                 f"sort_wins_to_n={max(cross) if cross else 0}"))
    return rows


def run_topk_distributed(sizes=DEFAULT_SIZES, k=TOPK_K):
    """Mesh top-k: candidate all-gather vs the local select on the
    gathered array; empty on 1-device hosts."""
    import jax
    import jax.numpy as jnp
    from repro.engine import samplesort

    n_dev = len(jax.devices())
    if n_dev < 2:
        return []
    mesh = jax.make_mesh((n_dev,), ("data",))
    rows = []
    rng = np.random.default_rng(0)
    for n in sizes:
        if n < k:
            continue
        x = jnp.asarray(rng.standard_normal(n), jnp.float32)
        reps = 3 if n <= 65536 else 1
        cold, warm = _time_cold_warm_eager(
            lambda v: samplesort.sample_topk(v, k, mesh, "data"), x, reps)
        rows.append((f"engine.topk_dist.cold_ms.n{n}.k{k}",
                     round(cold * 1e3, 1), f"D={n_dev}"))
        rows.append((f"engine.topk_dist.warm_us.n{n}.k{k}",
                     round(warm * 1e6, 1), f"D={n_dev}"))
    return rows


def run_distributed(sizes=DEFAULT_SIZES):
    """sample vs oddeven over a one-axis mesh; empty on 1-device hosts."""
    import jax
    import jax.numpy as jnp
    from repro.core import distributed_sort as ds
    from repro.engine import planner

    n_dev = len(jax.devices())
    if n_dev < 2:
        return []
    mesh = jax.make_mesh((n_dev,), ("data",))
    rows, summary = [], {}
    rng = np.random.default_rng(0)
    for n in sizes:
        n -= n % n_dev                     # oddeven needs divisibility
        x = jnp.asarray(rng.standard_normal(n), jnp.float32)
        reps = 3 if n <= 65536 else 1
        for strat in ("oddeven", "sample"):
            cold, warm = _time_cold_warm_eager(
                lambda v, s=strat: ds.distributed_sort(v, mesh, strategy=s),
                x, reps)
            rows.append((f"engine.dist_{strat}.cold_ms.n{n}",
                         round(cold * 1e3, 1), f"D={n_dev}"))
            rows.append((f"engine.dist_{strat}.warm_us.n{n}",
                         round(warm * 1e6, 1), f"D={n_dev}"))
            summary[(strat, n)] = (cold, warm)
        auto = planner.choose_distributed(n, n_dev).strategy
        rows.append((f"engine.dist_auto.n{n}", 0.0, f"{n}:{auto}"))
    n_max = max(n - n % n_dev for n in sizes)
    oc, ow = summary[("oddeven", n_max)]
    sc, sw = summary[("sample", n_max)]
    rows.append((f"engine.dist_sample_vs_oddeven_warm_speedup.n{n_max}",
                 0.0, round(ow / sw, 2)))
    rows.append((f"engine.dist_sample_vs_oddeven_cold_speedup.n{n_max}",
                 0.0, round(oc / sc, 2)))
    return rows


# the naive join materialises an (n_l, n_r) equality matrix — quadratic,
# so its leg (and the sorted join it anchors) is capped
REL_JOIN_CAP = 4096


def run_relational(sizes=DEFAULT_SIZES):
    """Relational ops vs their naive XLA one-liners.

    Rows per n (dup-heavy int32 keys, ~n/4 distinct):

      * ``rel_unique``   vs ``jnp.unique(size=n)`` (scatter-heavy lowering)
      * ``rel_group_by`` (sum) vs unique+segment_sum composed directly
      * ``rel_join``     vs the dense O(n_l*n_r) equality-matrix nonzero,
                         both capped at n=4096

    The summary rows record the warm speedup of each op over its naive
    formulation at the largest n — the README "Relational kernels" numbers.
    """
    import jax
    import jax.numpy as jnp
    from repro import relational as rel

    rows, summary = [], {}
    rng = np.random.default_rng(0)
    for n in sizes:
        keys = jnp.asarray(rng.integers(0, max(2, n // 4), n), jnp.int32)
        vals = jnp.asarray(rng.integers(0, 100, n), jnp.int32)
        reps = 3 if n <= 65536 else 1

        def naive_unique(v):
            return jnp.unique(v, size=n, fill_value=0)

        def naive_group(v):
            u, inv = jnp.unique(keys, size=n, fill_value=0,
                                return_inverse=True)
            return u, jax.ops.segment_sum(v, inv, num_segments=n)

        legs = [
            ("rel_unique", lambda v: rel.unique(v).values, naive_unique,
             keys),
            ("rel_group_by",
             lambda v: rel.group_by(keys, v, agg="sum").aggregates[0],
             naive_group, vals),
        ]
        if n <= REL_JOIN_CAP:
            nj = n
            lk, rk = keys, jnp.asarray(
                rng.integers(0, max(2, n // 4), n), jnp.int32)
            pair_cap = 16 * nj

            def naive_join(l):
                return jnp.nonzero(l[:, None] == rk[None, :],
                                   size=pair_cap, fill_value=-1)

            legs.append(
                ("rel_join",
                 lambda l: rel.join(l, rk, size=pair_cap)[:2],
                 naive_join, lk))
        for name, fn, naive, x in legs:
            cold, warm = _time_cold_warm(fn, x, reps)
            ncold, nwarm = _time_cold_warm(naive, x, reps)
            rows.append((f"engine.{name}.cold_ms.n{n}",
                         round(cold * 1e3, 1), n))
            rows.append((f"engine.{name}.warm_us.n{n}",
                         round(warm * 1e6, 1), n))
            rows.append((f"engine.{name}_naive.warm_us.n{n}",
                         round(nwarm * 1e6, 1), n))
            summary[(name, n)] = (warm, nwarm)
    for name in ("rel_unique", "rel_group_by", "rel_join"):
        ns = [n for (b, n) in summary if b == name]
        if not ns:
            continue
        w, nw = summary[(name, max(ns))]
        rows.append((f"engine.{name}_vs_naive_warm_speedup.n{max(ns)}",
                     0.0, round(nw / w, 2)))
    return rows


# the spill leg forces device chunks of this many KEY BYTES, so modest
# bench sizes exercise the real pipeline shape (many chunks + host merge)
SPILL_CHUNK_BYTES = 256 << 10


def run_spill(sizes=DEFAULT_SIZES):
    """Out-of-core tier: measured overlap-on vs overlap-off, plus dedup.

    Rows per n (f32 keys, forced 256 KiB chunks so every size spans >= 4
    device chunks):

      * ``spill_sort``          the double-buffered pipeline (overlap on)
      * ``spill_sort_serial``   the same pipeline draining each chunk
                                before the next (overlap off)
      * ``spill_overlap_speedup``  serial/overlapped warm ratio — the
                                acceptance metric: > 1 means the H2D/D2H
                                link time is hidden behind chunk sorts.
                                On hosts whose "device" is the CPU itself
                                (CI) transfers are zero-copy, there is no
                                link time to hide, and the honest value
                                sits at ~1.0; the gap opens on discrete
                                accelerators where D2H is a real DMA.
      * ``spill_dedup``         data/pipeline.global_dedup over n token
                                rows (the tier's first consumer)

    All legs are eager/host-driven, so cold==first call and warm is the
    per-call mean, like the distributed rows.
    """
    from repro.data import pipeline as data_pipeline
    from repro.engine import spill

    rows = []
    rng = np.random.default_rng(0)
    for n in sizes:
        x = rng.standard_normal(n).astype(np.float32)
        if x.nbytes < 4 * SPILL_CHUNK_BYTES:
            continue                   # under 4 chunks the leg measures noise
        reps = 3 if n <= 65536 else 1
        timing = {}
        for name, overlap in (("spill_sort", True),
                              ("spill_sort_serial", False)):
            cold, warm = _time_cold_warm_eager(
                lambda v, o=overlap: spill.spill_sort(
                    v, chunk_bytes=SPILL_CHUNK_BYTES, overlap=o), x, reps)
            rows.append((f"engine.{name}.cold_ms.n{n}",
                         round(cold * 1e3, 1), n))
            rows.append((f"engine.{name}.warm_us.n{n}",
                         round(warm * 1e6, 1), n))
            timing[name] = warm
        rows.append((f"engine.spill_overlap_speedup.n{n}", 0.0,
                     round(timing["spill_sort_serial"]
                           / timing["spill_sort"], 2)))
    # dedup consumer at a fixed shape: rows, not elements, set the scale
    n_rows, seq = 4096, 64
    toks = rng.integers(0, 50, (n_rows, seq)).astype(np.int32)
    toks[rng.integers(0, n_rows, n_rows // 4)] = toks[0]   # planted dups
    cold, warm = _time_cold_warm_eager(
        lambda t: data_pipeline.global_dedup(t, chunk_bytes=4096),
        toks, 1)
    rows.append((f"engine.spill_dedup.cold_ms.rows{n_rows}",
                 round(cold * 1e3, 1), f"seq{seq}"))
    rows.append((f"engine.spill_dedup.warm_us.rows{n_rows}",
                 round(warm * 1e6, 1), f"seq{seq}"))
    return rows


def run(sizes=DEFAULT_SIZES):
    import jax
    import jax.numpy as jnp
    from repro import engine
    from repro.core import sort_api

    rows = []
    rng = np.random.default_rng(0)
    summary = {}
    backends = [
        ("xla", lambda v: sort_api.sort(v, method="xla")),
        ("pallas_bitonic", lambda v: sort_api.sort(v, method="pallas")),
        ("merge", lambda v: engine.sort(v, method="merge")),
        ("radix", lambda v: sort_api.sort(v, method="radix")),
        ("auto", lambda v: engine.sort(v, method="auto")),
    ]
    interp = jax.default_backend() != "tpu"
    for n in sizes:
        x = jnp.asarray(rng.standard_normal((1, n)), jnp.float32)
        reps = 3 if n <= 65536 else 1
        for name, fn in backends:
            if name == "radix" and interp and n > RADIX_INTERPRET_CAP:
                continue
            cold, warm = _time_cold_warm(fn, x, reps)
            tag = (f"{n}:{engine.choose_method(n, 1)}" if name == "auto"
                   else n)
            rows.append((f"engine.{name}.cold_ms.n{n}",
                         round(cold * 1e3, 1), tag))
            rows.append((f"engine.{name}.warm_us.n{n}",
                         round(warm * 1e6, 1), tag))
            summary[(name, n)] = (cold, warm)

    n_max = max(sizes)
    mc, mw = summary[("merge", n_max)]
    pc, pw = summary[("pallas_bitonic", n_max)]
    rows.append((f"engine.merge_vs_pallas_cold_speedup.n{n_max}",
                 0.0, round(pc / mc, 2)))
    rows.append((f"engine.merge_vs_pallas_warm_speedup.n{n_max}",
                 0.0, round(pw / mw, 2)))
    radix_ns = [n for (b, n) in summary if b == "radix"]
    if radix_ns:      # every size may exceed the interpret-mode cap
        rn = max(radix_ns)
        _, rw = summary[("radix", rn)]
        rows.append((f"engine.radix_vs_xla_warm_speedup.n{rn}",
                     0.0, round(summary[("xla", rn)][1] / rw, 2)))
        rows.append((f"engine.radix_vs_merge_warm_speedup.n{rn}",
                     0.0, round(summary[("merge", rn)][1] / rw, 2)))
    rows.extend(run_topk(sizes))
    rows.extend(run_relational(sizes))
    rows.extend(run_spill(sizes))
    rows.extend(run_distributed(sizes))
    rows.extend(run_topk_distributed(sizes))
    return rows


CSV_HEADER = "name,us_per_call,derived"


def write_csv(rows, path) -> None:
    """Append rows to ``path``, writing the header line exactly once.

    Successive runs append to one trajectory file, so the header is only
    emitted when the file is new/empty — and any header lines that earlier
    tooling did append mid-file are dropped on the way through.
    """
    import pathlib
    p = pathlib.Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    existing = p.read_text() if p.exists() else ""
    lines = [ln for ln in existing.splitlines() if ln and ln != CSV_HEADER]
    out = [CSV_HEADER] + lines + \
        [",".join(str(x) for x in row) for row in rows]
    p.write_text("\n".join(out) + "\n")


def main() -> None:
    import argparse
    import os
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="sweep up to 4M elements")
    ap.add_argument("--sizes", default="",
                    help="comma-separated n values (overrides presets)")
    ap.add_argument("--devices", type=int, default=0,
                    help="simulate N host-platform devices for the "
                         "distributed rows (must be set before jax loads)")
    ap.add_argument("--out", default="",
                    help="also append rows to this CSV (header deduped)")
    ap.add_argument("--json-out", default="",
                    help="also emit the canonical BENCH_sort.json artifact "
                         "(benchmarks/emit_bench.py) at the same sizes")
    args = ap.parse_args()
    if args.devices > 1:
        # only effective if jax has not initialised yet — that is why every
        # jax import in this module lives inside the run functions
        flag = f"--xla_force_host_platform_device_count={args.devices}"
        os.environ["XLA_FLAGS"] = \
            f"{os.environ.get('XLA_FLAGS', '')} {flag}".strip()
    if args.sizes:
        sizes = tuple(int(s) for s in args.sizes.split(","))
    else:
        sizes = FULL_SIZES if args.full else DEFAULT_SIZES
    rows = run(sizes)
    print(CSV_HEADER)
    for row in rows:
        print(",".join(str(x) for x in row))
    if args.out:
        write_csv(rows, args.out)
    if args.json_out:
        from benchmarks import emit_bench
        path = emit_bench.write(emit_bench.collect(sizes), args.json_out)
        print(f"# wrote {path}")


if __name__ == "__main__":
    main()
