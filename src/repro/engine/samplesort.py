"""Distributed sample-sort over a device mesh.

``core/distributed_sort.py``'s odd-even transposition moves every shard D
times over the interconnect.  This module is the cluster-scale analogue of
the paper's single-exchange structure (§II-B: partitions sort concurrently
and pay the Eq. 3-4 temp-row cycles to exchange operands once per stage):
local sort -> splitters -> ONE capacity-padded bucket all-to-all -> merge ->
rank-directed rebalance.

``axis_name`` may be one mesh axis, a tuple of axes, or ``None`` for all
of them.  Over several axes the devices are ordered row-major (outer axis
major), the order tuple-axis collectives use, and the schedule is the
same: one splitter round, one bucket exchange and one rank rebalance over
every named axis.

The all-to-all needs a static per-(source, destination) bucket capacity:
between the two jitted programs the measured bucket maximum is synced to
the host, inside a ``samplesort.sync`` span (attributes ``max_bucket``,
``capacity``), and the exchange is compiled at that capacity (with the
tuning profile's slack so nearby workloads share executables).  The two
programs are named ``samplesort_phase1`` and ``samplesort_phase2``, so a
device trace tells them apart.

Everything runs on **encoded keys** (``core/keycodec.py``): signed ints,
floats and ``descending`` all reduce to one ascending unsigned sort, and
key-value payloads ride the same buckets.  Uneven global lengths are
padded with the maximal encoded key and tracked with explicit validity
counts end to end — pads can tie genuine extreme keys, so no step ever
infers validity from a sentinel comparison.

Keys must be NaN-free floats / any keycodec dtype (same contract as the
radix backend).  The sort is not stable.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import keycodec
from repro.core import tuning as _tuning
from repro.core.topology import auto_mesh, to_auto_mesh
from repro.engine import collectives as coll
from repro.engine.merge import merge_runs
from repro.obs import metrics, trace as _obs

__all__ = ["sample_sort", "sample_topk", "select_splitters", "bucket_bounds",
           "default_samples_per_shard", "alltoall_bytes_per_device",
           "topk_candidate_bytes_per_device"]

AxisArg = Union[str, Tuple[str, ...], None]


def next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def default_samples_per_shard(local_n: int, n_dev: int) -> int:
    """Regular-sampling oversampling: enough samples that splitters land
    within a small factor of the ideal quantiles, capped by the shard."""
    return max(1, min(local_n, max(8, 2 * n_dev)))


def select_splitters(samples: jnp.ndarray, n_dev: int) -> jnp.ndarray:
    """(D*s,) pooled samples -> (D-1,) global splitters (encoded keys)."""
    pooled = jnp.sort(samples.reshape(-1))
    total = pooled.shape[0]
    pos = (jnp.arange(1, n_dev) * total) // n_dev
    return pooled[pos]


def bucket_bounds(ks: jnp.ndarray, splitters: jnp.ndarray, *,
                  use_histogram: bool = False,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """(D+1,) bucket boundaries of a *sorted* shard against the splitters.

    Bucket d is ``ks[bounds[d]:bounds[d+1]]`` — the keys destined for
    device d (keys equal to a splitter go to the lower bucket).  Two
    equivalent routes:

      * ``use_histogram=False`` — binary search: the shard is sorted, so
        the boundaries are just ``searchsorted(ks, splitters, 'right')``.
      * ``use_histogram=True`` — the radix kernel's per-tile one-hot
        digit histogram (kernels/radix_sort.py) with the splitter interval
        index as the digit; boundaries are the histogram's exclusive
        prefix sum.  Same numbers, but the counting runs on the VMEM
        kernel the radix backend already ships (the TPU path).
    """
    m = ks.shape[0]
    n_dev = splitters.shape[0] + 1
    if n_dev == 1:
        return jnp.asarray([0, m], jnp.int32)
    if use_histogram:
        from repro.kernels import radix_sort as _rs
        ids = jnp.searchsorted(splitters, ks, side="left").astype(jnp.int32)
        interp = (jax.default_backend() != "tpu") if interpret is None \
            else interpret
        # tile the shard exactly like the radix passes do — one full-shard
        # row would materialise an un-tiled (1, m, D) one-hot in VMEM.
        # Pad slots carry an out-of-range bucket id (n_dev), not counted
        tile = _rs.lane_tile(m, _tuning.active().radix_tile)
        mt = -(-m // tile) * tile
        if mt != m:
            ids = jnp.pad(ids, (0, mt - m), constant_values=n_dev)
        hist = _rs.tile_hist(ids.reshape(mt // tile, tile), n_dev, interp)
        counts = jnp.sum(hist, axis=0)
    else:
        starts = jnp.searchsorted(ks, splitters, side="right")
        counts = jnp.diff(jnp.concatenate(
            [jnp.zeros(1, starts.dtype), starts,
             jnp.full((1,), m, starts.dtype)]))
    return jnp.concatenate([jnp.zeros(1, jnp.int32),
                            jnp.cumsum(counts).astype(jnp.int32)])


def _smap(f, mesh, in_specs, out_specs):
    # replication checking has no rule for pallas_call (the histogram
    # kernel and any Pallas local sort), so it is disabled; every output
    # is explicitly sharded over the axis anyway
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# axis plumbing: one axis, a tuple of axes, or the whole mesh
# ---------------------------------------------------------------------------

def _axes_tuple(mesh: Mesh, axis_name: AxisArg) -> Tuple[str, ...]:
    """Normalise ``axis_name`` to a validated tuple of mesh axis names
    (``None`` -> every mesh axis, in mesh order)."""
    if axis_name is None:
        axes = tuple(mesh.axis_names)
    elif isinstance(axis_name, str):
        axes = (axis_name,)
    else:
        axes = tuple(axis_name)
    if not axes:
        raise ValueError("axis_name must name at least one mesh axis")
    for a in axes:
        if not isinstance(a, str):
            raise TypeError(f"axis names must be strings, got {a!r}")
        if a not in mesh.axis_names:
            raise ValueError(f"axis {a!r} not in mesh axes "
                             f"{tuple(mesh.axis_names)}")
    if len(set(axes)) != len(axes):
        raise ValueError(f"duplicate axis names in {axes}")
    return axes


def _n_dev(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    d = 1
    for a in axes:
        d *= int(mesh.shape[a])
    return d


def _coll_axis(axes: Tuple[str, ...]):
    """The collective axis argument: a bare name for one axis, the tuple
    for several (row-major / outer-axis-major device order)."""
    return axes[0] if len(axes) == 1 else axes


def _lin_index(mesh: Mesh, axes: Tuple[str, ...]) -> jnp.ndarray:
    """Traced linear device index, row-major over ``axes`` — matches the
    device order of tuple-axis collectives."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * int(mesh.shape[a]) + jax.lax.axis_index(a)
    return idx.astype(jnp.int32)


def _pick_merge_backend(run_len: int) -> str:
    """Default merge backend for received runs of ``run_len`` slots (the
    exchange capacity)."""
    if jax.default_backend() == "tpu":
        # a stable sort of the received runs: the ranking merges are
        # gather-bound there (engine/merge.py)
        return "sort"
    if run_len & (run_len - 1) == 0:
        # off-TPU the gather-bound rank merge loses badly to the
        # word-parallel min/max box (capacities are pow2-rounded, so this
        # is the interpret-mode default)
        return "bitonic"
    return "xla"


# ---------------------------------------------------------------------------
# shared traced building blocks (run inside the jitted shard_map programs)
# ---------------------------------------------------------------------------

def _send_buffer(x, starts, vcnt, c, fill):
    """(p, c) capacity-padded send buffer of a sorted pool ``x`` cut into
    contiguous buckets: row j is ``x[starts[j] : starts[j] + c]`` with the
    slots at and past ``vcnt[j]`` set to ``fill``.

    Each row is one dynamic slice, a plain copy; an index array, though
    arithmetic, compiles to a general gather.  ``dynamic_slice`` clamps a
    start so the slice fits, so the pool is read extended by ``c`` fill
    slots: a bucket that starts within ``c`` of the end is read in place."""
    ext = jnp.concatenate([x, jnp.full((c,), fill, x.dtype)])
    rows = jnp.stack([jax.lax.dynamic_slice(ext, (starts[j],), (c,))
                      for j in range(starts.shape[0])])
    within = jnp.arange(c, dtype=jnp.int32)[None, :] < vcnt[:, None]
    return jnp.where(within, rows, fill)


def _exchange_merge(ks, vs, starts, vcnt, coll_axis, p, c,
                    maxkey, merge_backend, interpret):
    """The bucket exchange over ``coll_axis`` (fan-out ``p``) plus the
    merge of the received runs.

    ``ks`` is a sorted local pool cut into ``p`` contiguous buckets by
    ``starts``/``vcnt`` (genuine-key counts).  Send buffers are
    capacity-``c`` padded with ``maxkey``.

    Returns ``(mk, mv, mvalid, recv_cnt)``: merged keys (length
    ``next_pow2(p) * c``), merged payload (or None), per-slot validity
    recovered through the merge's position payload, and the (p,)
    genuine-key counts received from each source.
    """
    sendk = _send_buffer(ks, starts, vcnt, c, maxkey)
    runs = coll.all_to_all(sendk, coll_axis)                     # (p, c)
    recv_cnt = coll.all_to_all(vcnt[:, None], coll_axis)[:, 0]   # (p,)

    # the merge takes a power-of-two number of runs; p need not be one
    r_runs = next_pow2(p)
    if r_runs != p:
        runs = jnp.concatenate(
            [runs, jnp.full((r_runs - p, c), maxkey, runs.dtype)])
    # one int32 position payload rides the merge; validity flags (and the
    # user payload) are recovered by gathering through it, so ties between
    # capacity fill and genuine max keys cannot corrupt anything
    pos = jnp.arange(r_runs * c, dtype=jnp.int32).reshape(1, r_runs, c)
    mk, mpos = merge_runs(runs[None], pos, descending=False,
                          backend=merge_backend, interpret=interpret)
    mk, mpos = mk[0], mpos[0]                                    # (R*c,)

    # valid slots are a prefix of each received run
    run_valid = jnp.arange(c, dtype=jnp.int32)[None, :] < recv_cnt[:, None]
    if r_runs != p:
        run_valid = jnp.concatenate(
            [run_valid, jnp.zeros((r_runs - p, c), bool)])
    mvalid = run_valid.reshape(-1)[mpos]

    mv = None
    if vs is not None:
        sendv = _send_buffer(vs, starts, vcnt, c, jnp.zeros((), vs.dtype))
        vflat = coll.all_to_all(sendv, coll_axis).reshape(-1)
        if r_runs != p:
            vflat = jnp.concatenate(
                [vflat, jnp.zeros(((r_runs - p) * c,), vflat.dtype)])
        mv = vflat[mpos]
    return mk, mv, mvalid, recv_cnt


def _running_count(flags, width: int = 1024):
    """Inclusive running count of a 1-D bool array: ``jnp.cumsum`` over
    rows of ``width``, then over the row totals.  The TPU compiler takes
    ten times longer over one flat scan of 2^27 elements."""
    n = flags.shape[0]
    y = jnp.pad(flags.astype(jnp.int32), (0, -n % width)).reshape(-1, width)
    within = jnp.cumsum(y, axis=1)
    total = within[:, -1]
    return (within + (jnp.cumsum(total) - total)[:, None]).reshape(-1)[:n]


def _rebalance(mk, mv, mvalid, recv_cnt, coll_axis, n_dev, m, my):
    """Rank-directed rebalance of a merged pool back to equal ``m``-slot
    shards over ``n_dev`` devices: rank r lives at slot ``r % m`` of
    device ``r // m`` (``my`` is this device's linear index, matching
    ``coll_axis``'s device order).  Exactly one device owns each slot, so
    the receive reduction is a plain sum over sources (dtype pinned —
    accumulating zeros is exact, but sum would promote narrow ints).
    Slots past the global length ``n`` come back zero; the caller cuts
    them off."""
    n_slots = n_dev * m
    c_my = jnp.sum(recv_cnt).astype(jnp.int32)
    counts_all = jax.lax.all_gather(c_my, coll_axis).reshape(-1)  # (n_dev,)
    offset = jnp.sum(jnp.where(jnp.arange(n_dev) < my, counts_all, 0))
    lrank = _running_count(mvalid) - 1
    grank = offset + lrank
    flat = jnp.where(mvalid, grank, n_slots)                  # OOB -> drop
    outk = jnp.zeros((n_slots,), mk.dtype).at[flat].set(
        mk, mode="drop").reshape(n_dev, m)
    shard_k = jnp.sum(coll.all_to_all(outk, coll_axis), axis=0,
                      dtype=mk.dtype)
    shard_v = None
    if mv is not None:
        outv = jnp.zeros((n_slots,), mv.dtype).at[flat].set(
            mv, mode="drop").reshape(n_dev, m)
        shard_v = jnp.sum(coll.all_to_all(outv, coll_axis), axis=0,
                          dtype=mv.dtype)
    return shard_k, shard_v


# ---------------------------------------------------------------------------
# phase 1: local sort + splitters + bucket bounds
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=128)
def _phase1(mesh: Mesh, axes: Tuple[str, ...], n: int, kv: bool,
            padded: bool, local_method: Optional[str], s: int,
            use_histogram: bool, interpret: Optional[bool]):
    """Jitted program: encoded shard -> (sorted shard[, payload], starts,
    vcnt).  Validity follows the linear device index over ``axes``.

    Cached on its statics so repeated serving-shape calls hit the compiled
    executable; the mesh participates in the key (jax meshes hash).
    """
    n_dev = _n_dev(mesh, axes)
    m = -(-n // n_dev)

    def samplesort_phase1(*args):
        xs = args[0]
        vs = args[1] if kv else None
        my = _lin_index(mesh, axes)
        # valid = not an end-of-array pad; pads all live on the tail shards
        n_valid = jnp.clip(n - my * m, 0, m).astype(jnp.int32)

        # local sort (planner-dispatched registered backend).  Pads carry
        # the maximal encoded key; with a payload they must also stay
        # *behind* genuine max-key ties, so the kv+padded case runs the
        # stable argsort pipeline — validity stays a prefix of the shard
        from repro import sort as _front
        if kv and padded:
            order = _front.argsort(xs, stable=True, method=local_method,
                                   interpret=interpret)
            ks = jnp.take_along_axis(xs, order, -1)
            vs = jnp.take_along_axis(vs, order, -1)
        elif kv:
            ks, vs = _front.sort_kv(xs, vs, method=local_method,
                                    interpret=interpret)
        else:
            ks = _front.sort(xs, method=local_method, interpret=interpret)

        # regular samples -> pooled splitters (one tiny all-gather)
        sample_pos = ((jnp.arange(s) + 1) * m) // (s + 1)
        samples = jax.lax.all_gather(ks[sample_pos], _coll_axis(axes))
        splitters = select_splitters(samples, n_dev)

        bounds = bucket_bounds(ks, splitters, use_histogram=use_histogram,
                               interpret=interpret)
        # per-bucket count of *genuine* keys: the valid elements are a
        # prefix of the sorted shard, hence a prefix of every bucket
        vcnt = jnp.clip(jnp.minimum(bounds[1:], n_valid) - bounds[:-1],
                        0, m).astype(jnp.int32)
        starts = bounds[:-1]
        if kv:
            return ks, vs, starts, vcnt
        return ks, starts, vcnt

    spec = P(axes)
    n_out = 4 if kv else 3
    fn = _smap(samplesort_phase1, mesh, (spec, spec) if kv else (spec,),
               (spec,) * n_out)
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# phase 2: bucket exchange + merge of the received runs + rebalance
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=128)
def _phase2(mesh: Mesh, axes: Tuple[str, ...], n: int, kv: bool,
            capacity: int, key_dtype_name: str,
            val_dtype_name: Optional[str], merge_backend: str,
            interpret: Optional[bool]):
    """Jitted program: (sorted shard[, payload], starts, vcnt) -> output
    shard(s).  ``capacity`` is the static per-(source, destination) bucket
    size — phase 1's measured maximum, or m for the always-safe bound."""
    n_dev = _n_dev(mesh, axes)
    m = -(-n // n_dev)
    c = capacity
    ax = _coll_axis(axes)
    maxkey = jnp.array(jnp.iinfo(jnp.dtype(key_dtype_name)).max,
                       jnp.dtype(key_dtype_name))

    def samplesort_phase2(*args):
        if kv:
            ks, vs, starts, vcnt = args
        else:
            (ks, starts, vcnt), vs = args, None
        my = _lin_index(mesh, axes)
        mk, mv, mvalid, recv_cnt = _exchange_merge(
            ks, vs, starts, vcnt, ax, n_dev, c, maxkey,
            merge_backend, interpret)
        shard_k, shard_v = _rebalance(mk, mv, mvalid, recv_cnt, ax,
                                      n_dev, m, my)
        if kv:
            return shard_k, shard_v
        return shard_k

    spec = P(axes)
    n_in = 4 if kv else 3
    fn = _smap(samplesort_phase2, mesh, (spec,) * n_in,
               (spec, spec) if kv else spec)
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# front door
# ---------------------------------------------------------------------------

def _sync_max(vcnt) -> Optional[int]:
    """Host-sync the measured bucket maximum (None under an outer jit),
    counted as ``samplesort.host_syncs``."""
    try:
        counts = np.asarray(vcnt)
    except jax.errors.TracerArrayConversionError:
        return None
    metrics.counter("samplesort.host_syncs").inc()
    return int(np.max(counts))


def sample_sort(x: jnp.ndarray, mesh: Mesh, axis_name: AxisArg = "data", *,
                values: Optional[jnp.ndarray] = None,
                descending: bool = False,
                local_method: Optional[str] = None,
                samples_per_shard: Optional[int] = None,
                capacity: Optional[int] = None,
                capacity_slack: Optional[float] = None,
                use_histogram: Optional[bool] = None,
                merge_backend: Optional[str] = None,
                interpret: Optional[bool] = None):
    """Globally sort a 1-D array over ``axis_name`` — one mesh axis, a
    tuple of axes, or ``None`` for the whole mesh (devices in row-major
    order over the axes).  Returns the sorted array (or
    ``(keys, values)`` with a payload), same length and sharding layout
    as the input.

    ``capacity`` overrides the measured per-(source, destination) bucket
    capacity; it is validated against the realized bucket bounds and
    raises rather than silently dropping elements when too small (``m``,
    the shard length, is always sufficient).  Under an outer ``jax.jit``
    the measured mode is unavailable (it syncs counts to the host) and
    the realized bounds cannot be checked, so only ``capacity >= m`` is
    accepted there.

    ``capacity_slack`` (default: the active tuning profile's) multiplies
    the *measured* bucket maximum before pow2 rounding: >1 buys headroom
    so nearby workloads with slightly more skew reuse the same compiled
    programs instead of recompiling at the next capacity.
    """
    mesh = auto_mesh(mesh)
    x = jnp.asarray(to_auto_mesh(x))
    if x.ndim != 1:
        raise ValueError(f"sample_sort sorts flat 1-D arrays, got {x.shape}")
    if not keycodec.supports(x.dtype):
        raise ValueError(
            f"sample_sort needs a keycodec dtype {keycodec.SUPPORTED}, "
            f"got {jnp.dtype(x.dtype).name!r}")
    axes = _axes_tuple(mesh, axis_name)
    n = x.shape[0]
    n_dev = _n_dev(mesh, axes)
    m = -(-n // n_dev)                      # shard length (output = input)
    n_pad = n_dev * m
    kv = values is not None
    if kv:
        values = jnp.asarray(to_auto_mesh(values))
        if values.shape != x.shape:
            raise ValueError(f"values shape {values.shape} must match "
                             f"keys shape {x.shape}")
    if use_histogram is None:
        use_histogram = jax.default_backend() == "tpu"
    s = samples_per_shard or default_samples_per_shard(m, n_dev)
    slack = capacity_slack if capacity_slack is not None \
        else _tuning.active().capacity_slack

    enc = keycodec.encode(x, descending=descending)
    padded = n_pad != n
    if padded:
        maxkey = jnp.array(jnp.iinfo(enc.dtype).max, enc.dtype)
        enc = jnp.pad(enc, (0, n_pad - n), constant_values=maxkey)
        if kv:
            values = jnp.pad(values, (0, n_pad - n))
    kname = jnp.dtype(enc.dtype).name
    vname = jnp.dtype(values.dtype).name if kv else None
    itemsize = jnp.dtype(enc.dtype).itemsize + \
        (jnp.dtype(values.dtype).itemsize if kv else 0)

    p1 = _phase1(mesh, axes, n, kv, padded, local_method, s,
                 use_histogram, interpret)
    with _obs.trace("samplesort.phase1", n=n, n_dev=n_dev, kv=kv,
                    samples_per_shard=s):
        if kv:
            ks, vs, starts, vcnt = p1(enc, values)
        else:
            ks, starts, vcnt = p1(enc)

    # the one host sync: the realized bucket maximum sets the static
    # exchange capacity, so buffers and merge work scale with what the
    # data needs (~m/D with regular sampling) instead of the worst case m
    with _obs.trace("samplesort.sync") as sp:
        max_bucket = _sync_max(vcnt)
        cap = _capacity(max_bucket, capacity, slack, m)
        sp.set(max_bucket=max_bucket, capacity=cap)
    if merge_backend is None:
        merge_backend = _pick_merge_backend(cap)

    total_bytes = n_dev * alltoall_bytes_per_device(n_dev, m, itemsize, cap)
    if _obs.enabled() and max_bucket is not None:
        # bucket-skew accounting: vcnt is the full (D*D,) per-(source,
        # destination) genuine-key count table, already synced to the host
        # for the capacity measurement — skew 1.0 means perfectly regular
        # splitters, capacity (and the exchange bill) scales with it
        counts = np.asarray(vcnt, dtype=np.float64)
        mean_fill = float(counts.mean()) if counts.size else 0.0
        skew = float(max_bucket) / mean_fill if mean_fill else 1.0
        metrics.gauge("samplesort.bucket_skew").set(skew)
        metrics.counter("samplesort.alltoall_bytes").inc(total_bytes)
        metrics.counter("samplesort.sorts").inc()

    p2 = _phase2(mesh, axes, n, kv, cap, kname, vname, merge_backend,
                 interpret)
    with _obs.trace("samplesort.phase2", n=n, n_dev=n_dev, capacity=cap,
                    merge_backend=merge_backend,
                    bytes=total_bytes if _obs.enabled() else 0):
        if kv:
            out_k, out_v = p2(ks, vs, starts, vcnt)
        else:
            out_k = p2(ks, starts, vcnt)
    keys = keycodec.decode(out_k[:n], x.dtype, descending=descending)
    if kv:
        return keys, out_v[:n]
    return keys


def _capacity(max_bucket: Optional[int], capacity: Optional[int],
              slack: float, m: int) -> int:
    """The exchange's static capacity: the measured bucket maximum times
    ``slack``, or the caller's ``capacity`` checked against it."""
    if capacity is None:
        if max_bucket is None:
            raise ValueError(
                "sample_sort's measured-capacity mode reads the bucket "
                "counts on the host and cannot run under an outer jit; "
                f"pass capacity= (the shard length {m} is always safe)")
        return _round_capacity(int(math.ceil(max_bucket * slack)), m)
    cap = _round_capacity(capacity, m)
    if max_bucket is None and cap < m:
        # under a trace there is no way to raise later, and a too-small
        # capacity would silently drop elements — only the provably-safe
        # shard-length capacity is allowed
        raise ValueError(
            f"under an outer jit, capacity must be >= the shard length {m} "
            f"(the realized bucket maximum cannot be checked at trace "
            f"time); got {capacity}")
    if max_bucket is not None and cap < max_bucket:
        raise ValueError(
            f"capacity {capacity} is smaller than the realized maximum "
            f"bucket ({max_bucket}); the shard length {m} is always safe")
    return cap


# ---------------------------------------------------------------------------
# distributed top-k: local select -> ONE candidate all-gather -> tiny merge
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=128)
def _topk_prog(mesh: Mesh, axes: Tuple[str, ...], n: int, k: int,
               key_dtype_name: str, use_kernel: Optional[bool],
               interpret: Optional[bool]):
    """Jitted program: encoded padded shard -> replicated (enc topk, global
    indices).  Cached on its statics like the sample-sort phases."""
    from repro.kernels import radix_select as _sel
    n_dev = _n_dev(mesh, axes)
    m = -(-n // n_dev)
    kc = min(k, m)                       # per-shard candidate count
    kdt = jnp.dtype(key_dtype_name)
    maxkey = jnp.array(jnp.iinfo(kdt).max, kdt)

    def local(enc):
        my = _lin_index(mesh, axes)
        base = (my * m).astype(jnp.int32)
        # end-of-array pads all live on the tail shards; force them to the
        # maximal encoded key so the local select ranks them last, and mark
        # them with the out-of-range global index n so a pad tying a
        # genuine extreme key can never displace it in the candidate merge
        n_valid = jnp.clip(n - base, 0, m).astype(jnp.int32)
        valid = jnp.arange(m, dtype=jnp.int32) < n_valid
        e = jnp.where(valid, enc, maxkey)

        # local selection: the kc smallest encoded keys of this shard —
        # §II-B's "partitions sort concurrently", in partial-sort mode
        le, li = _sel.select_topk_encoded(e[None], kc,
                                         use_kernel=use_kernel,
                                         interpret=interpret)
        gi = jnp.where(li[0] < n_valid, base + li[0],
                       jnp.array(n, jnp.int32))

        # THE one collective: D·kc candidates (vs sample-sort's bucket
        # all-to-all of whole shards); every device then runs the same
        # tiny lexicographic merge, so the result is replicated
        ax = _coll_axis(axes)
        ce = jax.lax.all_gather(le[0], ax).reshape(-1)
        ci = jax.lax.all_gather(gi, ax).reshape(-1)
        se, si = jax.lax.sort((ce, ci), num_keys=2)
        return se[:k], si[:k]

    fn = _smap(local, mesh, (P(axes),), (P(None), P(None)))
    return jax.jit(fn)


def sample_topk(x: jnp.ndarray, k: int, mesh: Mesh,
                axis_name: AxisArg = "data", *,
                use_kernel: Optional[bool] = None,
                interpret: Optional[bool] = None):
    """Mesh-global top-k of a flat array -> ``(values, indices)``, both
    ``(k,)`` and replicated, bit-exact with ``jax.lax.top_k`` on the
    gathered array (values descending, ties keep the lowest global index).

    Movement is the whole point: each device radix-selects its shard's
    ``min(k, m)`` candidates locally (O(m·passes), no sort), ONE
    all-gather moves the ``D·min(k, m)`` candidate (key, index) pairs, and
    a two-key lexicographic sort of that tiny pool — the merge-box reduce
    over D already-sorted candidate runs — finishes on every device.  No
    full-array sort, no bucket all-to-all, no rebalance round: for
    ``k ≪ n`` the collective bill shrinks from O(m) per device to O(D·k).

    Correctness of the candidate cut: a shard with ``g`` genuine elements
    contributes ``min(kc, g)`` of them, and ``sum(min(kc, g_d)) >= k``
    whenever ``n >= k`` — so the global top-k is always inside the pool.
    """
    mesh = auto_mesh(mesh)
    x = jnp.asarray(to_auto_mesh(x))
    if x.ndim != 1:
        raise ValueError(f"sample_topk selects over flat 1-D arrays, "
                         f"got {x.shape}")
    if not keycodec.supports(x.dtype):
        raise ValueError(
            f"sample_topk needs a keycodec dtype {keycodec.SUPPORTED}, "
            f"got {jnp.dtype(x.dtype).name!r}")
    n = x.shape[0]
    if not 1 <= k <= n:
        raise ValueError(
            f"topk k must satisfy 1 <= k <= n (n={n}); got k={k}")
    axes = _axes_tuple(mesh, axis_name)
    n_dev = _n_dev(mesh, axes)
    m = -(-n // n_dev)
    enc = keycodec.encode(x, descending=True)
    if n_dev * m != n:
        maxkey = jnp.array(jnp.iinfo(enc.dtype).max, enc.dtype)
        enc = jnp.pad(enc, (0, n_dev * m - n), constant_values=maxkey)
    prog = _topk_prog(mesh, axes, n, k,
                      jnp.dtype(enc.dtype).name, use_kernel, interpret)
    cand_bytes = 0
    if _obs.enabled():
        cand_bytes = n_dev * topk_candidate_bytes_per_device(
            n_dev, k, m, jnp.dtype(enc.dtype).itemsize)
        metrics.counter("samplesort.topk_candidate_bytes").inc(cand_bytes)
    with _obs.trace("samplesort.topk", n=n, k=k, n_dev=n_dev,
                    bytes=cand_bytes):
        ev, ei = prog(enc)
    return keycodec.decode(ev, x.dtype, descending=True), ei


def topk_candidate_bytes_per_device(n_dev: int, k: int, local_elems: int,
                                    itemsize: int) -> int:
    """Analytic ICI volume of the candidate all-gather (per device): the
    ``k ≪ n`` counterpart of ``alltoall_bytes_per_device`` — D·min(k, m)
    (key, int32 index) pairs instead of capacity-padded whole buckets."""
    kc = min(k, local_elems)
    return n_dev * kc * (itemsize + 4)


def _round_capacity(cap: int, m: int) -> int:
    """Static capacity: at least one slot, padded up a little so nearby
    workloads share a compiled program, never beyond the local pool."""
    cap = max(1, cap)
    if cap >= m:
        return m
    return min(m, next_pow2(cap))


def alltoall_bytes_per_device(n_dev: int, local_elems: int,
                              itemsize: int, capacity: Optional[int] = None
                              ) -> int:
    """Analytic interconnect volume of one sample-sort round (per
    device): the capacity-padded bucket all-to-all plus the rank
    rebalance round — versus ``n_dev`` full-shard moves for odd-even
    transposition (``distributed_sort.collective_bytes_per_device``)."""
    cap = capacity if capacity is not None else \
        min(local_elems, 2 * local_elems // max(1, n_dev) + 1)
    return (n_dev * cap + n_dev * local_elems) * itemsize
