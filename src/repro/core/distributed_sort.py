"""Mesh-distributed sorting — the paper's partitioning scaled to devices.

§II-B partitions one SRAM macro so N/2 CAS blocks run concurrently, paying
Eq. 3-4 temp-row cycles to exchange operands between partitions.  At cluster
scale the same structure maps 1:1 onto a device mesh:

    memory partition        ->  TPU chip (sorts its shard in-VMEM)
    intra-stage parallelism ->  SPMD over the mesh axis
    temp-row exchange       ->  shard exchange over ICI

One entry point, two strategies behind it (``strategy="auto"`` prices
them with ``planner.choose_distributed``):

  ``oddeven``  odd-even transposition merge: D rounds of neighbour
               ppermute + bitonic merge-split.  Minimal per-round state,
               but every shard moves D times — the repeated
               cross-partition traffic in-memory designs exist to avoid.
               Kept as the small-(n, D) fallback (fewer collective
               launches than an all-to-all when shards are tiny);
               ascending, evenly divisible, value-only.
  ``sample``   single-round splitter-based sample-sort
               (``engine/samplesort.py``): local sort, one bucket
               all-to-all, merge-path merge, rank rebalance.  Handles
               uneven lengths, descending, and key-value payloads (the
               keycodec reduces them all to one ascending unsigned sort),
               so any request odd-even cannot express routes here
               regardless of the cost model.  Over several mesh axes it
               is the only strategy.

The odd-even collective cost is one shard (m elements) over ICI per round
per device pair: ``collective_bytes(D, m) = D * m * itemsize`` per device —
the Eq. 3-4 analogue priced by ``cost_model.collective_cost_ns``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bitonic_merge_halves(lo_sorted: jnp.ndarray, hi_sorted: jnp.ndarray):
    """Merge two ascending arrays (each length m) and return the ascending
    (low half, high half).  Uses the bitonic merge box: concat(a, reverse(b))
    is bitonic, so only the merge substages of the network are needed.

    Substages use the reshape-addressed form (a (n/(2j), 2, j) view pairs
    index i with i^j) rather than per-substage gathers: chained 1-D gathers
    send XLA's CPU pipeline into pathological compile times once shards
    reach engine scale (the same failure mode PR 1 fixed in
    ``sort_api.bitonic_sort``), while the reshape view compiles flat.
    """
    m = lo_sorted.shape[-1]
    p = 1 << max(0, (m - 1).bit_length())
    if p != m:
        # the merge box needs a power-of-two length: pad both runs with
        # the dtype's top key, which keeps them sorted and sorts last
        dt = lo_sorted.dtype
        top = jnp.array(jnp.inf if jnp.issubdtype(dt, jnp.floating)
                        else jnp.iinfo(dt).max, dt)
        pad = [(0, 0)] * (lo_sorted.ndim - 1) + [(0, p - m)]
        lo_sorted = jnp.pad(lo_sorted, pad, constant_values=top)
        hi_sorted = jnp.pad(hi_sorted, pad, constant_values=top)
    z = jnp.concatenate([lo_sorted, jnp.flip(hi_sorted, -1)], axis=-1)
    n = 2 * p
    lead = z.shape[:-1]
    j = n // 2
    while j >= 1:
        v = z.reshape(*lead, n // (2 * j), 2, j)
        lo, hi = v[..., 0, :], v[..., 1, :]
        z = jnp.stack([jnp.minimum(lo, hi), jnp.maximum(lo, hi)],
                      axis=-2).reshape(*lead, n)
        j //= 2
    return z[..., :m], z[..., m:2 * m]


def _round_permutation(n_dev: int, even_round: bool):
    """Partner index per device for one odd-even transposition round.

    A device paired with itself idles that round: the last device on even
    rounds when the count is odd, and the edge devices on odd rounds
    (device 0 always; the last device when the count is even).
    """
    perm = []
    for i in range(n_dev):
        if even_round:
            partner = i ^ 1
            if partner >= n_dev:
                partner = i  # odd device count: last device idles
        else:
            if i == 0 or (i == n_dev - 1 and n_dev % 2 == 0):
                partner = i  # edge devices idle this round
            else:
                partner = i + 1 if i % 2 == 1 else i - 1
        perm.append((i, partner))
    return perm


def distributed_sort(x: jnp.ndarray, mesh: Mesh, axis_name=None,
                     local_method: Optional[str] = "xla", *,
                     strategy: str = "auto", descending: bool = False,
                     values: Optional[jnp.ndarray] = None,
                     interpret: Optional[bool] = None):
    """Globally sort a 1-D array sharded over ``axis_name`` of ``mesh`` —
    one axis name, a tuple of axes, or ``None`` for the whole mesh.

    Returns the globally-sorted array with the same sharding (or
    ``(keys, values)`` when a payload rides along).

    ``strategy`` is ``"auto"`` (cost-model pick via
    ``planner.choose_distributed``), ``"sample"`` (single-round
    sample-sort over every named axis) or ``"oddeven"`` (D-round
    transposition merge; single-axis only).  Requests odd-even cannot
    express — uneven lengths, ``descending``, payloads — always route to
    sample-sort; forcing ``strategy="oddeven"`` for one of those raises.

    ``local_method`` accepts every registered backend name including
    ``"merge"`` and ``"auto"`` (or ``None`` for the ambient ``sort_defaults``
    method): the mesh path composes with the out-of-core engine, whose
    planner prices the *shard* size it sees inside the shard_map — so a
    vocab-scale shard gets tiled run generation + merge tree while a small
    one stays on a single-tile backend.
    """
    from repro.core.topology import auto_mesh, to_auto_mesh
    from repro.engine import planner, samplesort
    mesh = auto_mesh(mesh)
    x = to_auto_mesh(x)
    axes = samplesort._axes_tuple(mesh, axis_name)
    n_dev = samplesort._n_dev(mesh, axes)
    multi = len(axes) > 1
    n = x.shape[-1]
    needs_sample = bool(descending or values is not None or n % n_dev)
    if strategy == "auto":
        plan = planner.choose_distributed_cached(n, n_dev, x.dtype)
        # odd-even is a single-axis, even-length, ascending, value-only
        # schedule — drop it from the running when the request (or the
        # mesh shape) rules it out and take the cheapest remaining
        usable = {s: c for s, c in plan.costs.items()
                  if s != "oddeven" or not (needs_sample or multi)}
        strategy = min(usable, key=usable.__getitem__)
    if strategy not in ("sample", "oddeven"):
        raise ValueError(
            f"strategy must be 'auto', 'sample' or 'oddeven', "
            f"got {strategy!r}")
    if strategy == "sample":
        return samplesort.sample_sort(x, mesh, axes, values=values,
                                      descending=descending,
                                      local_method=local_method,
                                      interpret=interpret)
    if multi:
        raise ValueError(
            "oddeven transposition runs over ONE mesh axis; pass a single "
            f"axis name or use strategy='sample' (got axes {axes})")
    axis_name = axes[0]
    if needs_sample:
        raise ValueError(
            "oddeven strategy needs an evenly divisible, ascending, "
            "value-only sort (length % n_dev == 0, descending=False, "
            "values=None); use strategy='sample' or 'auto'")
    from repro.obs import metrics as _metrics, trace as _obs
    coll_bytes = 0
    if _obs.enabled():
        coll_bytes = n_dev * collective_bytes_per_device(
            n_dev, -(-n // n_dev), jnp.dtype(x.dtype).itemsize)
        _metrics.counter("distsort.oddeven_bytes").inc(coll_bytes)
        _metrics.counter("distsort.oddeven_sorts").inc()
    with _obs.trace("distsort.oddeven", n=n, n_dev=n_dev, bytes=coll_bytes):
        return _oddeven_fn(mesh, axis_name, local_method, interpret)(x)


@functools.lru_cache(maxsize=64)
def _oddeven_fn(mesh: Mesh, axis_name: str, local_method: Optional[str],
                interpret: Optional[bool] = None):
    """Cached jitted odd-even program — eagerly re-tracing the D-round
    loop per call costs orders of magnitude more than running it."""
    n_dev = mesh.shape[axis_name]

    def local(xs):
        from repro import sort as _front
        xs = _front.sort(xs, method=local_method, interpret=interpret)
        my = jax.lax.axis_index(axis_name)
        for r in range(n_dev):
            pairs = _round_permutation(n_dev, r % 2 == 0)
            send = [(i, p) for (i, p) in pairs]
            theirs = jax.lax.ppermute(xs, axis_name, send)
            partner = jnp.asarray([p for (_, p) in pairs])[my]
            lo, hi = bitonic_merge_halves(
                jnp.where(my < partner, xs, theirs),
                jnp.where(my < partner, theirs, xs))
            merged = jnp.where(my < partner, lo, hi)
            xs = jnp.where(my == partner, xs, merged)  # edges idle this round
        return xs

    spec = P(axis_name)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec,), out_specs=spec)
    return jax.jit(fn)


def distributed_topk(x: jnp.ndarray, k: int, mesh: Mesh,
                     axis_name=None, *,
                     interpret: Optional[bool] = None):
    """Mesh-global top-k -> ``(values, indices)``, bit-exact with
    ``jax.lax.top_k`` (values descending, ties keep the lowest global
    index).  ``axis_name`` follows ``distributed_sort``: one axis, a
    tuple, or ``None`` for the whole mesh.

    There is only one strategy here on purpose: selection makes the
    strategy question moot.  Both full-sort strategies move O(m) per
    device (odd-even D times over); the candidate path
    (``engine/samplesort.sample_topk``) moves O(D·k) in ONE all-gather —
    local radix-select per shard, tiny lexicographic candidate merge, no
    full-array sort.  That is the paper's partial-movement argument
    (§II-B: only candidates cross partitions) at mesh scale.
    """
    from repro.engine import samplesort
    return samplesort.sample_topk(x, k, mesh, axis_name,
                                  interpret=interpret)


def collective_bytes_per_device(n_dev: int, local_elems: int,
                                itemsize: int) -> int:
    """Analytic ICI volume of the merge phase (per device)."""
    return n_dev * local_elems * itemsize
