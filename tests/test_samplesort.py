"""Single-round distributed sample-sort: unit pieces, mesh runs, dispatch.

Runs correctly at any local device count: on the tier-1 single-device job
the mesh degenerates to D=1 (plus subprocess tests that force 8 and 4
simulated devices), while the CI multi-device job executes this whole file
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so every
collective (bucket all-to-all, rank rebalance, splitter all-gather) runs
at real D>1 on every push.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.sort as rsort
from repro.core import cost_model, distributed_sort as ds, keycodec
from repro.engine import planner, samplesort


def _mesh():
    return jax.make_mesh((len(jax.devices()),), ("data",))


# ---------------------------------------------------------------------------
# host-level unit pieces
# ---------------------------------------------------------------------------

def test_select_splitters_regular_quantiles():
    pooled = jnp.arange(64, dtype=jnp.uint32)
    sp = np.asarray(samplesort.select_splitters(pooled, 4))
    np.testing.assert_array_equal(sp, [16, 32, 48])
    assert samplesort.select_splitters(pooled, 1).shape == (0,)


@pytest.mark.parametrize("use_histogram", [False, True])
def test_bucket_bounds_partition_sorted_shard(use_histogram):
    """Both partition routes (binary search / radix one-hot histogram
    kernel) must cut identical contiguous buckets: elements equal to a
    splitter go to the lower bucket."""
    ks = jnp.asarray(np.sort(np.array([0, 1, 1, 3, 3, 3, 7, 9, 9, 12],
                                      np.uint32)))
    splitters = jnp.asarray([1, 3, 9], jnp.uint32)
    b = np.asarray(samplesort.bucket_bounds(
        ks, splitters, use_histogram=use_histogram))
    np.testing.assert_array_equal(b, [0, 3, 6, 9, 10])
    k = np.asarray(ks)
    for d in range(4):
        seg = k[b[d]:b[d + 1]]
        lo = -1 if d == 0 else int(splitters[d - 1])
        hi = np.inf if d == 3 else int(splitters[d])
        assert ((seg > lo) & (seg <= hi)).all()


def test_bucket_bounds_all_equal_worst_case():
    ks = jnp.full((16,), 5, jnp.uint32)
    b = np.asarray(samplesort.bucket_bounds(
        ks, jnp.full((3,), 5, jnp.uint32)))
    np.testing.assert_array_equal(b, [0, 16, 16, 16, 16])  # all to bucket 0


def test_bucket_bounds_routes_agree_random():
    rng = np.random.default_rng(3)
    ks = jnp.asarray(np.sort(rng.integers(0, 1000, 257)).astype(np.uint32))
    sp = jnp.asarray(np.sort(rng.integers(0, 1000, 7)).astype(np.uint32))
    np.testing.assert_array_equal(
        np.asarray(samplesort.bucket_bounds(ks, sp, use_histogram=False)),
        np.asarray(samplesort.bucket_bounds(ks, sp, use_histogram=True)))


# ---------------------------------------------------------------------------
# send buffers: contiguous bucket slices == the index-gather formulation
# ---------------------------------------------------------------------------

def _gather_send_buffer(x, starts, vcnt, c, fill):
    """The send buffer as an index gather: row j reads
    ``x[clip(starts[j] + i)]`` and keeps it where ``i < vcnt[j]``."""
    idx = starts[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    within = jnp.arange(c, dtype=jnp.int32)[None, :] < vcnt[:, None]
    src = jnp.clip(idx, 0, x.shape[0] - 1)
    return jnp.where(within, x[src], fill)


_P, _POOL, _CAP = 4, 37, 16
# per simulated device: bucket sizes (summing to the pool length) and the
# genuine counts; pad slots (size > count) are a prefix-valid tail, as on
# the padded last shard.  Covered: starts[j] + c past the pool's end
# (device 1's last bucket), empty buckets (device 0's second, device 2's
# last, which starts at the pool's very end), vcnt == c (devices 0 and 2)
_SIZES = [(10, 0, 11, 16), (9, 12, 7, 9), (16, 5, 16, 0), (3, 14, 12, 8)]
_VCNT = [(10, 0, 11, 16), (9, 12, 7, 9), (16, 5, 16, 0), (3, 14, 12, 5)]


def _send_pools(payload_dtype):
    """(P, POOL) sorted key pools, payloads, bucket starts and counts."""
    rng = np.random.default_rng(41)
    maxkey = np.iinfo(np.uint32).max
    ks, starts = [], []
    for sizes, cnt in zip(_SIZES, _VCNT):
        pool = [np.sort(rng.integers(j * 1000, (j + 1) * 1000, cnt[j]))
                for j in range(_P)]
        pool = [np.concatenate([b, np.full(s - len(b), maxkey)])
                for b, s in zip(pool, sizes)]
        ks.append(np.concatenate(pool).astype(np.uint32))
        starts.append(np.concatenate([[0], np.cumsum(sizes)[:-1]]))
    vs = rng.standard_normal((_P, _POOL)) * 100
    return (jnp.asarray(np.stack(ks)), jnp.asarray(vs.astype(payload_dtype)),
            jnp.asarray(np.asarray(starts, np.int32)),
            jnp.asarray(np.asarray(_VCNT, np.int32)))


def test_send_pools_cover_the_edge_buckets():
    _, _, starts, vcnt = _send_pools(np.int32)
    starts, vcnt = np.asarray(starts), np.asarray(vcnt)
    assert ((starts + _CAP > _POOL) & (vcnt > 0)).any()
    assert (vcnt == 0).any() and (starts == _POOL).any()
    assert (vcnt == _CAP).any()


@pytest.mark.parametrize("kv", [False, True])
def test_send_buffer_matches_gather(monkeypatch, kv):
    """The sliced send buffers equal the index-gather ones element for
    element, and so does the whole exchange round built on them (four
    simulated devices: the collectives run over a vmapped axis)."""
    ks, vs, starts, vcnt = _send_pools(np.int32)
    maxkey = jnp.uint32(np.iinfo(np.uint32).max)
    zero = jnp.zeros((), vs.dtype)
    for d in range(_P):
        for x, fill in ((ks[d], maxkey), (vs[d], zero)):
            np.testing.assert_array_equal(
                np.asarray(samplesort._send_buffer(
                    x, starts[d], vcnt[d], _CAP, fill)),
                np.asarray(_gather_send_buffer(
                    x, starts[d], vcnt[d], _CAP, fill)))

    def exchange(ks, vs, starts, vcnt):
        return samplesort._exchange_merge(
            ks, vs if kv else None, starts, vcnt, "d", _P, _CAP, maxkey,
            "sort", None)

    run = jax.vmap(exchange, axis_name="d")
    sliced = run(ks, vs, starts, vcnt)
    monkeypatch.setattr(samplesort, "_send_buffer", _gather_send_buffer)
    gathered = run(ks, vs, starts, vcnt)
    assert (sliced[1] is None) == (not kv)
    for a, b in zip(sliced, gathered):
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_send_buffer_lowers_without_gather():
    """A (4, c) send buffer is four slices, not a gather — and the index
    formulation it replaced does lower to one, so the check can fail."""
    x = jax.ShapeDtypeStruct((1000,), jnp.uint32)
    st = jax.ShapeDtypeStruct((4,), jnp.int32)
    fill = jax.ShapeDtypeStruct((), jnp.uint32)

    def stablehlo(f):
        return jax.jit(f, static_argnums=3).lower(
            x, st, st, 256, fill).as_text()

    assert "gather" not in stablehlo(samplesort._send_buffer)
    assert "gather" in stablehlo(_gather_send_buffer)


# ---------------------------------------------------------------------------
# one whole round (bucket exchange, merge, rank rebalance) over P simulated
# devices: the collectives run over a vmapped axis
# ---------------------------------------------------------------------------

_ROUND_SHARD = 203                     # odd and not a power of two


def _round_keys(dist, n, rng):
    """int32 keys with the shape classes of tests/test_mesh_sort_kv.py."""
    partkey = rng.integers(1, n // 30 + 2, n).astype(np.int32)
    if dist == "uniform":
        return partkey
    if dist == "all_equal":
        return np.full(n, 7, np.int32)
    if dist == "few_distinct":
        return rng.choice(np.array([-5, 10, 20], np.int32), n)
    hot = partkey.copy()
    hot[rng.random(n) < 0.4] = int(np.median(partkey))
    return hot


def _round_inputs(keys, n_dev, m):
    """What phase 1 hands the exchange, built on the host: sorted padded
    shards (pads behind genuine max-key ties), their payload row ids,
    bucket starts and genuine-key counts against pooled regular-sample
    splitters."""
    n = keys.shape[0]
    maxkey = np.iinfo(np.uint32).max
    enc = np.full(n_dev * m, maxkey, np.uint32)
    enc[:n] = np.asarray(keycodec.encode(jnp.asarray(keys)))
    rows = np.zeros(n_dev * m, np.int32)
    rows[:n] = np.arange(n)
    order = np.argsort(enc.reshape(n_dev, m), axis=1, kind="stable")
    ks = np.take_along_axis(enc.reshape(n_dev, m), order, 1)
    vs = np.take_along_axis(rows.reshape(n_dev, m), order, 1)
    s = samplesort.default_samples_per_shard(m, n_dev)
    pos = ((np.arange(s) + 1) * m) // (s + 1)
    splitters = samplesort.select_splitters(jnp.asarray(ks[:, pos]), n_dev)
    b = np.asarray(jax.jit(jax.vmap(samplesort.bucket_bounds,
                                    in_axes=(0, None)))(ks, splitters))
    n_valid = np.clip(n - np.arange(n_dev) * m, 0, m)[:, None]
    vcnt = np.clip(np.minimum(b[:, 1:], n_valid) - b[:, :-1], 0, m)
    return (enc[:n], jnp.asarray(ks), jnp.asarray(vs),
            jnp.asarray(b[:, :-1]), jnp.asarray(vcnt.astype(np.int32)))


@pytest.mark.parametrize("kv", [False, True])
@pytest.mark.parametrize("dist", ["uniform", "all_equal", "few_distinct",
                                  "hot_median"])
@pytest.mark.parametrize("n_dev", [2, 3, 4, 8])
def test_flat_round_matches_np_sort(n_dev, dist, kv):
    """``_exchange_merge`` then ``_rebalance``, as phase 2 runs them:
    every output shard is its slice of ``np.sort``, and every payload
    names a distinct input row holding its key.  Three devices merge a
    number of runs that is not a power of two; the last shard is padded;
    the skewed distributions put whole shards into one bucket."""
    m = _ROUND_SHARD
    n = n_dev * m - 3
    keys = _round_keys(dist, n, np.random.default_rng(n_dev))
    enc, ks, vs, starts, vcnt = _round_inputs(keys, n_dev, m)
    c = samplesort._round_capacity(int(np.max(np.asarray(vcnt))), m)
    maxkey = jnp.uint32(np.iinfo(np.uint32).max)

    def one_round(ks, vs, starts, vcnt):
        mk, mv, mvalid, cnt = samplesort._exchange_merge(
            ks, vs if kv else None, starts, vcnt, "d", n_dev, c, maxkey,
            "sort", None)
        return samplesort._rebalance(mk, mv, mvalid, cnt, "d", n_dev, m,
                                     jax.lax.axis_index("d"))

    out_k, out_v = jax.jit(jax.vmap(one_round, axis_name="d"))(
        ks, vs, starts, vcnt)
    assert (out_v is None) == (not kv)
    out_k = np.asarray(out_k)
    ref = np.sort(enc)
    for d in range(n_dev):
        lo, hi = d * m, min((d + 1) * m, n)
        np.testing.assert_array_equal(out_k[d, :hi - lo], ref[lo:hi],
                                      err_msg=f"shard {d}")
    if kv:
        rows = np.asarray(out_v).reshape(-1)[:n]
        assert ((rows >= 0) & (rows < n)).all()
        assert len(set(rows.tolist())) == n
        np.testing.assert_array_equal(enc[rows], out_k.reshape(-1)[:n])


# ---------------------------------------------------------------------------
# end-to-end over the local mesh (D=1 on tier-1, D=8 on the multidev job)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,dist", [
    (1024, "uniform"),        # evenly divisible by any CI device count
    (1234, "uniform"),        # uneven shards
    (333, "dup_heavy"),       # splitter ties everywhere
    (1000, "all_equal"),      # worst-case skew: one bucket takes all
    (3, "uniform"),           # n < D on the multidev job
])
def test_sample_sort_matches_np(n, dist):
    rng = np.random.default_rng(n)
    if dist == "uniform":
        x = rng.standard_normal(n).astype(np.float32)
    elif dist == "dup_heavy":
        x = rng.integers(0, 4, n).astype(np.float32)
    else:
        x = np.full(n, 2.5, np.float32)
    out = np.asarray(samplesort.sample_sort(jnp.asarray(x), _mesh()))
    np.testing.assert_array_equal(out, np.sort(x))


def test_host_syncs_counted_where_the_sync_happens():
    """The measured-capacity mode reads the bucket counts back once per
    flat sort; with a fixed capacity under an outer jit it reads none."""
    from repro.obs import metrics, trace
    x = jnp.asarray(np.random.default_rng(2).standard_normal(256),
                    jnp.float32)
    mesh = _mesh()
    m = -(-256 // len(jax.devices()))
    with trace.tracing():
        metrics.reset()
        try:
            samplesort.sample_sort(x, mesh)
            assert metrics.counter("samplesort.host_syncs").value == 1
            out = jax.jit(lambda v: samplesort.sample_sort(
                v, mesh, capacity=m))(x)
            assert metrics.counter("samplesort.host_syncs").value == 1
        finally:
            metrics.reset()
    np.testing.assert_array_equal(np.asarray(out), np.sort(np.asarray(x)))


@pytest.mark.parametrize("descending", [False, True])
def test_sample_sort_kv_uneven_extreme_keys(descending):
    """Payloads survive the bucket exchange even when genuine keys equal
    the capacity/pad fill (dtype max) — validity is explicit, never
    inferred from sentinels."""
    rng = np.random.default_rng(17)
    k = rng.integers(0, 4, 333).astype(np.int32)
    k[k == 3] = np.iinfo(np.int32).max
    v = np.arange(333, dtype=np.int32)
    sk, sv = samplesort.sample_sort(jnp.asarray(k), _mesh(),
                                    values=jnp.asarray(v),
                                    descending=descending)
    sk, sv = np.asarray(sk), np.asarray(sv)
    ref = np.sort(k)
    np.testing.assert_array_equal(sk, np.flip(ref) if descending else ref)
    np.testing.assert_array_equal(k[sv], sk)     # payload matches its key
    assert len(set(sv.tolist())) == v.size       # a true permutation


@pytest.mark.parametrize("descending", [False, True])
def test_sample_sort_kv_sort_merge_backend(descending):
    """The TPU default merge (a stable sort of the received runs) keeps
    payloads on their keys and pads behind genuine dtype-max keys."""
    rng = np.random.default_rng(19)
    k = rng.integers(0, 4, 333).astype(np.int32)
    k[k == 3] = np.iinfo(np.int32).max
    v = np.arange(333, dtype=np.int32)
    sk, sv = samplesort.sample_sort(jnp.asarray(k), _mesh(),
                                    values=jnp.asarray(v),
                                    descending=descending,
                                    merge_backend="sort")
    sk, sv = np.asarray(sk), np.asarray(sv)
    ref = np.sort(k)
    np.testing.assert_array_equal(sk, np.flip(ref) if descending else ref)
    np.testing.assert_array_equal(k[sv], sk)
    assert len(set(sv.tolist())) == v.size


@pytest.mark.parametrize("dtype", sorted(keycodec.SUPPORTED))
def test_sample_sort_every_codec_dtype(dtype):
    rng = np.random.default_rng(29)
    raw = rng.integers(0, 100, 200) if dtype.startswith("uint") \
        else rng.integers(-100, 100, 200)
    x = jnp.asarray(raw).astype(jnp.dtype(dtype))
    out = np.asarray(samplesort.sample_sort(x, _mesh())).astype(np.float64)
    np.testing.assert_array_equal(
        out, np.sort(np.asarray(x).astype(np.float64)))


def test_sample_sort_histogram_partition_path():
    rng = np.random.default_rng(31)
    x = jnp.asarray(rng.standard_normal(640), jnp.float32)
    out = np.asarray(samplesort.sample_sort(x, _mesh(), use_histogram=True))
    np.testing.assert_array_equal(out, np.sort(np.asarray(x)))


def test_sample_sort_rejects_bad_inputs():
    with pytest.raises(ValueError, match="1-D"):
        samplesort.sample_sort(jnp.zeros((2, 8), jnp.float32), _mesh())
    with pytest.raises(ValueError, match="keycodec dtype"):
        samplesort.sample_sort(jnp.zeros(8, jnp.complex64), _mesh())
    with pytest.raises(ValueError, match="values shape"):
        samplesort.sample_sort(jnp.zeros(8, jnp.float32), _mesh(),
                               values=jnp.zeros(9, jnp.int32))


# ---------------------------------------------------------------------------
# the unified entry point + planner dispatch
# ---------------------------------------------------------------------------

def test_entry_point_strategies_agree():
    mesh = _mesh()
    n_dev = mesh.shape["data"]
    x = jnp.asarray(np.random.default_rng(5).standard_normal(n_dev * 256),
                    jnp.float32)
    ref = np.sort(np.asarray(x))
    for strategy in ("auto", "sample", "oddeven"):
        out = np.asarray(ds.distributed_sort(x, mesh, strategy=strategy))
        np.testing.assert_array_equal(out, ref, err_msg=strategy)


def test_entry_point_routes_inexpressible_requests_to_sample():
    """descending / payload / uneven length cannot run on odd-even: auto
    must route to sample-sort, and forcing oddeven must refuse."""
    mesh = _mesh()
    n = mesh.shape["data"] * 16 + 1                  # uneven
    x = jnp.asarray(np.random.default_rng(7).standard_normal(n), jnp.float32)
    out = np.asarray(ds.distributed_sort(x, mesh, strategy="auto",
                                         descending=True))
    np.testing.assert_array_equal(out, np.flip(np.sort(np.asarray(x))))
    sk, sv = ds.distributed_sort(x, mesh, strategy="auto",
                                 values=jnp.arange(n, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(sk), np.sort(np.asarray(x)))
    for bad in (dict(descending=True), dict(values=jnp.zeros(n))):
        with pytest.raises(ValueError, match="oddeven strategy needs"):
            ds.distributed_sort(x, mesh, strategy="oddeven", **bad)
    with pytest.raises(ValueError, match="strategy must be"):
        ds.distributed_sort(x, mesh, strategy="bogus")


def test_choose_distributed_crossover():
    """Odd-even keeps tiny workloads (fewer collective launches); the
    single-round exchange wins once per-round merge work dominates — and
    the crossover moves with D, since odd-even pays D rounds."""
    small = planner.choose_distributed(4096, 8)
    large = planner.choose_distributed(1 << 20, 8)
    assert set(small.costs) == {"sample", "oddeven"}
    assert small.strategy == "oddeven"
    assert large.strategy == "sample"
    assert all(np.isfinite(c) for c in large.costs.values())
    # the sample advantage widens with n at fixed D: odd-even's per-round
    # merge carries the growing log factor, the exchange bill does not
    adv = [planner.choose_distributed(n, 8).costs
           for n in (1 << 18, 1 << 20, 1 << 22)]
    ratios = [c["oddeven"] / c["sample"] for c in adv]
    assert ratios == sorted(ratios)


def test_choose_distributed_cached_four_chip_cell():
    """The four-chip LINEITEM SF 30 sort plans the sample-sort, and the
    second lookup is a cache hit."""
    plan = planner.choose_distributed_cached(179_998_372, 4, jnp.int32)
    assert plan.strategy == "sample"
    assert planner.choose_distributed_cached(179_998_372, 4,
                                             jnp.int32) is plan


def test_axes_tuple_validation():
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    assert samplesort._axes_tuple(mesh, None) == ("data",)
    assert samplesort._axes_tuple(mesh, "data") == ("data",)
    with pytest.raises(ValueError):
        samplesort._axes_tuple(mesh, "nope")
    with pytest.raises(ValueError):
        samplesort._axes_tuple(mesh, ("data", "data"))


def test_collective_cost_ns_terms():
    c = cost_model.DeviceSortConstants()
    base = cost_model.collective_cost_ns(1, 0, 4, c)
    assert base == c.collective_alpha                 # pure launch latency
    one = cost_model.collective_cost_ns(1, 1000, 4, c)
    eight = cost_model.collective_cost_ns(8, 1000, 4, c)
    assert eight - base == pytest.approx(8 * (one - base))
    with pytest.raises(ValueError, match="no distributed cost model"):
        cost_model.distributed_sort_cost_ns("bogus", 100, 2)


# ---------------------------------------------------------------------------
# SortSpec mesh fields through the front door
# ---------------------------------------------------------------------------

def test_spec_mesh_front_door():
    mesh = _mesh()
    x = jnp.asarray(np.random.default_rng(9).standard_normal(777),
                    jnp.float32)
    out = np.asarray(rsort.sort(x, mesh=mesh, axis_name="data"))
    np.testing.assert_array_equal(out, np.sort(np.asarray(x)))
    # axis_name defaults to the mesh's first axis
    out = np.asarray(rsort.sort(x, mesh=mesh, descending=True))
    np.testing.assert_array_equal(out, np.flip(np.sort(np.asarray(x))))
    sk, sv = rsort.sort_kv(x, jnp.arange(777, dtype=jnp.int32), mesh=mesh)
    np.testing.assert_array_equal(np.asarray(x)[np.asarray(sv)],
                                  np.asarray(sk))


def test_spec_mesh_validation():
    mesh = _mesh()
    x1 = jnp.zeros(8, jnp.float32)
    with pytest.raises(ValueError, match="axis_name requires a mesh"):
        rsort.sort(x1, axis_name="data")
    with pytest.raises(ValueError, match="not in mesh axes"):
        rsort.sort(x1, mesh=mesh, axis_name="model")
    with pytest.raises(ValueError, match="flat 1-D"):
        rsort.sort(jnp.zeros((2, 8), jnp.float32), mesh=mesh)
    from repro.core.sortspec import SortSpec
    with pytest.raises(ValueError, match="plain and key-value"):
        rsort.run(SortSpec(indices=True, mesh=mesh), x1)
    with pytest.raises(ValueError, match="method must be 'auto'"):
        rsort.sort(x1, mesh=mesh, method="bitonic")
    # spec statics fold the mesh identity into external cache keys
    k1 = SortSpec(mesh=mesh).static_key((8,), jnp.float32)
    k2 = SortSpec().static_key((8,), jnp.float32)
    assert k1 != k2 and hash(k1) != hash(k2)


def test_scheduler_distributed_queue_orders_by_length():
    """serve.py's backlog sort over the mesh: the (length, position)
    composite value-sort must reproduce the local argsort schedule (on a
    1-device mesh it falls back to exactly that path).  Batches are
    anchored at the oldest queued request and filled with adjacent-length
    neighbours, so the check is: nothing dropped, every batch contains
    the then-oldest request, and each batch is a contiguous slice of the
    length-sorted backlog."""
    from repro.launch.serve import LengthSortedScheduler, Request
    # distributed_min lowered so the mesh path runs at test-sized backlogs
    sched = LengthSortedScheduler(4, mesh=_mesh(), distributed_min=2)
    rng = np.random.default_rng(41)
    lens = [int(v) for v in rng.integers(4, 64, 13)]
    for rid, ln in enumerate(lens):
        sched.submit(Request(rid=rid, prompt=np.zeros(ln, np.int32)))
    seen = []
    while sched.queue:
        oldest = sched.queue[0].rid
        backlog = sorted(len(r.prompt) for r in sched.queue)
        batch = sched.next_batch()
        assert any(r.rid == oldest for r in batch)       # anchor present
        got = sorted(len(r.prompt) for r in batch)
        # contiguous window of the sorted backlog lengths
        assert any(backlog[s:s + len(got)] == got
                   for s in range(len(backlog) - len(got) + 1))
        seen.extend(len(r.prompt) for r in batch)
    assert sorted(seen) == sorted(lens)          # nothing dropped


# ---------------------------------------------------------------------------
# forced 8-device run (covers real D>1 even on the single-device CI job)
# ---------------------------------------------------------------------------

def test_sample_sort_8dev_subprocess():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.engine import samplesort
from repro.core import distributed_sort as ds
mesh = jax.make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
# sharded, uneven, duplicate-heavy kv descending — the full contract
k = rng.integers(0, 9, 1003).astype(np.int32)
v = np.arange(1003, dtype=np.int32)
sk, sv = samplesort.sample_sort(jnp.asarray(k), mesh,
                                values=jnp.asarray(v), descending=True)
sk, sv = np.asarray(sk), np.asarray(sv)
assert (sk == np.flip(np.sort(k))).all()
assert (k[sv] == sk).all() and len(set(sv.tolist())) == 1003
# explicitly sharded value sort through the unified entry point
x = rng.standard_normal(8 * 512).astype(np.float32)
xs = jax.device_put(x, NamedSharding(mesh, P("data")))
out = ds.distributed_sort(xs, mesh, strategy="sample")
assert (np.asarray(out) == np.sort(x)).all()
print("SAMPLESORT_8DEV_OK")
"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "PYTHONPATH": os.path.join(repo, "src")}
    env.pop("XLA_FLAGS", None)        # the subprocess pins its own count
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=420)
    assert "SAMPLESORT_8DEV_OK" in r.stdout, r.stderr[-2000:]


def test_sample_sort_2x4_subprocess():
    """A two-axis ("host", "dev") mesh with ``axis_name=None`` sorts over
    the row-major linear device order: one exchange over both axes."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.engine import samplesort
mesh = jax.make_mesh((2, 4), ("host", "dev"))
assert samplesort._axes_tuple(mesh, None) == ("host", "dev")
assert samplesort._n_dev(mesh, ("host", "dev")) == 8
rng = np.random.default_rng(0)
n = 1003                                    # shards of 126, the last padded
k = rng.integers(0, 9, n).astype(np.int32)
v = np.arange(n, dtype=np.int32)
for desc in (False, True):
    ref = np.flip(np.sort(k)) if desc else np.sort(k)
    out = samplesort.sample_sort(jnp.asarray(k), mesh, None, descending=desc)
    assert (np.asarray(out) == ref).all()
    sk, sv = samplesort.sample_sort(jnp.asarray(k), mesh, None,
                                    values=jnp.asarray(v), descending=desc)
    sk, sv = np.asarray(sk), np.asarray(sv)
    assert (sk == ref).all()
    assert (k[sv] == sk).all() and len(set(sv.tolist())) == n
# unique composites pin the exact permutation
comp = ((k.astype(np.uint32) & 0xF) << 10) | np.arange(n, dtype=np.uint32)
out = samplesort.sample_sort(jnp.asarray(comp), mesh, None)
assert (np.asarray(out) == np.sort(comp)).all()
print("SAMPLESORT_2X4_OK")
"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "PYTHONPATH": os.path.join(repo, "src")}
    env.pop("XLA_FLAGS", None)        # the subprocess pins its own count
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=420)
    assert "SAMPLESORT_2X4_OK" in r.stdout, r.stderr[-2000:]


# ---------------------------------------------------------------------------
# forced 4-device runs with a capacity below the shard length: the tail
# buckets take the extended-slice case of the send buffers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4,), (2, 2)], ids=["4", "2x2"])
def test_sample_sort_4dev_capacity_below_shard(shape):
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.engine import samplesort
SHAPE = %r
tail_reads = []
sliced = samplesort._send_buffer
def spy(x, starts, vcnt, c, fill):
    # a bucket with genuine keys whose slice runs past the pool's end
    past = jnp.any((starts + c > x.shape[0]) & (vcnt > 0))
    jax.debug.callback(lambda b: tail_reads.append(bool(b)), past)
    return sliced(x, starts, vcnt, c, fill)
samplesort._send_buffer = spy
rng = np.random.default_rng(5)
n = 4003                                    # shards of 1001, one padded
k = rng.integers(-50, 50, n).astype(np.int32)
k[::97] = np.iinfo(np.int32).max            # genuine keys tie the fill
v = np.arange(n, dtype=np.int32)
# (2, 2) sorts flat over both axes, in the (4,) mesh's device order
mesh = jax.make_mesh(SHAPE, ("data",) if len(SHAPE) == 1 else ("outer", "inner"))
kw = dict(axis_name=None, capacity=512)     # < 1001, >= the largest bucket
sk, sv = samplesort.sample_sort(jnp.asarray(k), mesh, values=jnp.asarray(v),
                                **kw)
sk, sv = np.asarray(sk), np.asarray(sv)
jax.effects_barrier()
assert any(tail_reads), tail_reads
assert (sk == np.sort(k)).all()
assert (k[sv] == sk).all() and len(set(sv.tolist())) == n
out = np.asarray(samplesort.sample_sort(jnp.asarray(k), mesh, **kw))
assert (out == np.sort(k)).all()
print("SAMPLESORT_4DEV_OK")
""" % (shape,)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "PYTHONPATH": os.path.join(repo, "src")}
    env.pop("XLA_FLAGS", None)        # the subprocess pins its own count
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=420)
    assert "SAMPLESORT_4DEV_OK" in r.stdout, r.stderr[-2000:]
