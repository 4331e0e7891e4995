"""repro.relational — sort-powered relational kernels vs numpy references.

Every op's documented reference semantics checked element-exactly
(np.unique / scatter-reduce group-by / nested-loop join / np.histogram),
plus the RelSpec front-door validation, the planner's relational pricing,
and the three consumer rewires' helpers (MoE group_ranks, pipeline dedup,
serve batch accounting).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.relational as rel
from repro.engine import planner
from repro.relational.relspec import RelSpec


def _col(seed=0, n=64, lo=-20, hi=20, dtype=np.int32):
    return np.random.default_rng(seed).integers(lo, hi, n).astype(dtype)


# ---------------------------------------------------------------------------
# unique
# ---------------------------------------------------------------------------

def test_unique_matches_numpy():
    x = _col(1)
    ref_v, ref_inv, ref_c = np.unique(x, return_inverse=True,
                                      return_counts=True)
    u = rel.unique(x, return_inverse=True, return_counts=True)
    m = int(u.n_unique)
    assert m == len(ref_v)
    np.testing.assert_array_equal(np.asarray(u.values[:m]), ref_v)
    np.testing.assert_array_equal(np.asarray(u.inverse), ref_inv)
    np.testing.assert_array_equal(np.asarray(u.counts[:m]), ref_c)
    # tail without fill_value repeats the max -> globally non-decreasing
    tail = np.asarray(u.values[m:])
    assert (tail == ref_v[-1]).all()


def test_unique_fill_value_pads_tail():
    x = np.asarray([3, 1, 3, 1], np.int32)
    u = rel.unique(x, fill_value=-7)
    assert np.asarray(u.values).tolist() == [1, 3, -7, -7]


def test_unique_signed_zero_merges():
    z = np.asarray([0.0, -0.0, 1.0, -0.0], np.float32)
    u = rel.unique(z)
    m = int(u.n_unique)
    assert m == 2
    assert np.asarray(u.values[:m]).tolist() == [0.0, 1.0]


def test_unique_empty():
    u = rel.unique(np.zeros(0, np.int32), return_inverse=True,
                   return_counts=True)
    assert int(u.n_unique) == 0
    assert u.values.shape == (0,)
    assert u.inverse.shape == (0,) and u.counts.shape == (0,)


def test_unique_all_equal():
    x = np.full(33, 7, np.int32)
    u = rel.unique(x, return_counts=True)
    assert int(u.n_unique) == 1
    assert int(u.counts[0]) == 33


def test_unique_under_jit():
    x = jnp.asarray(_col(2))

    @jax.jit
    def f(v):
        u = rel.unique(v, return_inverse=True)
        return u.values, u.n_unique, u.inverse

    vals, m, inv = f(x)
    ref_v, ref_inv = np.unique(np.asarray(x), return_inverse=True)
    np.testing.assert_array_equal(np.asarray(vals[:int(m)]), ref_v)
    np.testing.assert_array_equal(np.asarray(inv), ref_inv)


# ---------------------------------------------------------------------------
# group_by
# ---------------------------------------------------------------------------

def test_group_by_all_aggregates_match_numpy():
    k = _col(3, n=100, lo=-8, hi=8)
    v = _col(4, n=100, lo=0, hi=50)
    ref_k, inv = np.unique(k, return_inverse=True)
    g = len(ref_k)
    gb = rel.group_by(k, v, agg=("sum", "min", "max", "count", "mean"))
    assert int(gb.n_groups) == g
    np.testing.assert_array_equal(np.asarray(gb.keys[:g]), ref_k)
    rsum = np.zeros(g, np.int64)
    np.add.at(rsum, inv, v)
    rmin = np.full(g, np.iinfo(np.int32).max)
    np.minimum.at(rmin, inv, v)
    rmax = np.full(g, np.iinfo(np.int32).min)
    np.maximum.at(rmax, inv, v)
    rcnt = np.bincount(inv, minlength=g)
    np.testing.assert_array_equal(np.asarray(gb.aggregates[0][:g]),
                                  rsum.astype(np.int32))
    np.testing.assert_array_equal(np.asarray(gb.aggregates[1][:g]), rmin)
    np.testing.assert_array_equal(np.asarray(gb.aggregates[2][:g]), rmax)
    np.testing.assert_array_equal(np.asarray(gb.aggregates[3][:g]), rcnt)
    np.testing.assert_array_equal(
        np.asarray(gb.aggregates[4][:g]),
        rsum.astype(np.float32) / rcnt.astype(np.float32))


def test_group_by_single_agg_and_empty():
    k = np.asarray([2, 2, 2], np.int32)
    v = np.asarray([1, 10, 100], np.int32)
    gb = rel.group_by(k, v, agg="sum")
    assert int(gb.n_groups) == 1 and int(gb.aggregates[0][0]) == 111
    ge = rel.group_by(np.zeros(0, np.int32), np.zeros(0, np.int32))
    assert int(ge.n_groups) == 0 and ge.aggregates[0].shape == (0,)


# ---------------------------------------------------------------------------
# join
# ---------------------------------------------------------------------------

def _ref_join(lk, rk):
    """The documented pair order: ascending key, then left input order,
    then right input order."""
    pairs = []
    for key in np.unique(lk[np.isin(lk, rk)]):
        for li in np.flatnonzero(lk == key):
            for ri in np.flatnonzero(rk == key):
                pairs.append((int(li), int(ri)))
    return pairs


def test_join_matches_reference_order():
    lk = _col(5, n=23, lo=0, hi=8)
    rk = _col(6, n=17, lo=0, hi=8)
    j = rel.join(lk, rk)
    p = int(j.n_pairs)
    got = list(zip(np.asarray(j.left_idx[:p]).tolist(),
                   np.asarray(j.right_idx[:p]).tolist()))
    assert got == _ref_join(lk, rk)


def test_join_size_fill_and_overflow():
    lk = np.asarray([1, 1], np.int32)
    rk = np.asarray([1, 1, 1], np.int32)
    j = rel.join(lk, rk, size=8, fill_value=-1)
    assert int(j.n_pairs) == 6
    assert np.asarray(j.left_idx[6:]).tolist() == [-1, -1]
    with pytest.raises(ValueError, match="pass size >= 6"):
        rel.join(lk, rk, size=4)


def test_join_empty_sides_and_no_matches():
    j = rel.join(np.zeros(0, np.int32), np.asarray([1], np.int32), size=2)
    assert int(j.n_pairs) == 0
    j2 = rel.join(np.asarray([1, 2], np.int32),
                  np.asarray([3, 4], np.int32))
    assert int(j2.n_pairs) == 0
    assert (np.asarray(j2.left_idx) == -1).all()


# ---------------------------------------------------------------------------
# rle / delta
# ---------------------------------------------------------------------------

def test_rle_round_trip_and_counts():
    x = _col(7, n=50, lo=0, hi=6)
    r = rel.run_length_encode(x)
    nr = int(r.n_runs)
    ref_v, ref_c = np.unique(x, return_counts=True)
    np.testing.assert_array_equal(np.asarray(r.values[:nr]), ref_v)
    np.testing.assert_array_equal(np.asarray(r.run_lengths[:nr]), ref_c)
    assert (np.asarray(r.run_lengths[nr:]) == 0).all()
    dec = rel.rle_decode(r.values, r.run_lengths, len(x))
    np.testing.assert_array_equal(np.asarray(dec), np.sort(x))


def test_rle_assume_sorted_skips_the_sort():
    s = np.asarray([1, 1, 2, 5, 5, 5], np.int32)
    r = rel.run_length_encode(s, assume_sorted=True)
    assert np.asarray(r.values[:int(r.n_runs)]).tolist() == [1, 2, 5]
    assert np.asarray(r.run_lengths[:3]).tolist() == [2, 1, 3]


def test_delta_round_trip_including_wraparound():
    x = np.asarray([np.iinfo(np.int32).min, -1, 0,
                    np.iinfo(np.int32).max], np.int32)
    d = rel.delta_encode(x)
    np.testing.assert_array_equal(np.asarray(rel.delta_decode(d.deltas)),
                                  np.sort(x))


# ---------------------------------------------------------------------------
# sketches
# ---------------------------------------------------------------------------

def test_histogram_matches_numpy_on_same_edges():
    x = np.random.default_rng(8).normal(size=200).astype(np.float32)
    h = rel.histogram(x, 16)
    edges = np.asarray(h.edges)
    ref, _ = np.histogram(x, bins=edges)
    np.testing.assert_array_equal(np.asarray(h.counts), ref)
    assert int(np.asarray(h.counts).sum()) == len(x)


def test_histogram_pinned_range_excludes_outliers():
    x = np.asarray([-5.0, 0.5, 1.5, 99.0], np.float32)
    h = rel.histogram(x, 2, lo=0.0, hi=2.0)
    assert np.asarray(h.counts).tolist() == [1, 1]


def test_quantiles_are_lower_order_statistics():
    x = np.random.default_rng(9).integers(-1000, 1000, 101
                                          ).astype(np.int32)
    qs = (0.0, 0.25, 0.5, 0.9, 1.0)
    q = rel.quantiles(x, qs)
    s = np.sort(x)
    ref = [s[int(f * (len(x) - 1))] for f in qs]
    np.testing.assert_array_equal(np.asarray(q.values), ref)


# ---------------------------------------------------------------------------
# group_ranks (the MoE dispatch primitive)
# ---------------------------------------------------------------------------

def _ref_ranks(keys, g):
    seen, out = {}, []
    for e in keys:
        out.append(seen.get(int(e), 0))
        seen[int(e)] = out[-1] + 1
    return out, np.bincount(keys, minlength=g)


def test_group_ranks_one_hot_path():
    keys = _col(10, n=64, lo=0, hi=7)
    gr = rel.group_ranks(keys, 7)
    ref_r, ref_c = _ref_ranks(keys, 7)
    np.testing.assert_array_equal(np.asarray(gr.ranks), ref_r)
    np.testing.assert_array_equal(np.asarray(gr.counts), ref_c)


def test_group_ranks_sort_path_matches_one_hot():
    # domain above ONE_HOT_MAX_GROUPS rides the stable sort instead
    keys = _col(11, n=200, lo=0, hi=600)
    gr = rel.group_ranks(keys, 600)
    ref_r, ref_c = _ref_ranks(keys, 600)
    np.testing.assert_array_equal(np.asarray(gr.ranks), ref_r)
    np.testing.assert_array_equal(np.asarray(gr.counts), ref_c)


def test_group_ranks_batched_and_constrained():
    keys = _col(12, n=64, lo=0, hi=5).reshape(4, 16)
    called = []
    gr = rel.group_ranks(keys, 5,
                         constrain=lambda oh: (called.append(oh.shape),
                                               oh)[1])
    assert called == [(4, 16, 5)]
    for b in range(4):
        ref_r, ref_c = _ref_ranks(keys[b], 5)
        np.testing.assert_array_equal(np.asarray(gr.ranks[b]), ref_r)
        np.testing.assert_array_equal(np.asarray(gr.counts[b]), ref_c)


# ---------------------------------------------------------------------------
# RelSpec front door: every invalid combination raises here, not deep in
# an op kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec,x,values,match", [
    (RelSpec(op="nope"), np.zeros(3, np.int32), None, "op must be"),
    (RelSpec(op="unique"), np.zeros((2, 3), np.int32), None, "1-D"),
    (RelSpec(op="unique", method="warp"), np.zeros(3, np.int32), None,
     "method must be"),
    (RelSpec(op="histogram", num_bins=4, method="radix"),
     np.zeros(3, np.float32), None, "must be 'auto'"),
    (RelSpec(op="group_by", agg=("sum", "median")), np.zeros(3, np.int32),
     np.zeros(3, np.int32), "unknown aggregates"),
    (RelSpec(op="group_by"), np.zeros(3, np.int32), None,
     "needs a values column"),
    (RelSpec(op="group_by"), np.zeros(3, np.int32), np.zeros(4, np.int32),
     "must match"),
    (RelSpec(op="join"), np.zeros(3, np.int32), np.zeros(3, np.int16),
     "dtypes must match"),
    (RelSpec(op="join", size=0), np.zeros(3, np.int32),
     np.zeros(3, np.int32), "size must be"),
    (RelSpec(op="unique", size=4), np.zeros(3, np.int32), None,
     "join-only"),
    (RelSpec(op="delta"), np.zeros(3, np.float32), None, "integer"),
    (RelSpec(op="unique", assume_sorted=True), np.zeros(3, np.int32),
     None, "rle/delta"),
    (RelSpec(op="unique", num_bins=3), np.zeros(3, np.int32), None,
     "histogram-only"),
    (RelSpec(op="group_by", return_counts=True), np.zeros(3, np.int32),
     np.zeros(3, np.int32), "unique-only"),
    (RelSpec(op="quantile"), np.zeros(3, np.float32), None, "needs qs"),
    (RelSpec(op="quantile", qs=(1.5,)), np.zeros(3, np.float32), None,
     r"\[0, 1\]"),
    (RelSpec(op="quantile", qs=(0.5,)), np.zeros(0, np.float32), None,
     "empty"),
    (RelSpec(op="unique", qs=(0.5,)), np.zeros(3, np.int32), None,
     "quantile-only"),
    (RelSpec(op="group_ranks"), np.zeros(3, np.int32), None,
     "num_groups"),
    (RelSpec(op="group_ranks", num_groups=4), np.zeros(3, np.float32),
     None, "integers"),
    (RelSpec(op="unique", axis_name="data"), np.zeros(3, np.int32), None,
     "requires a mesh"),
])
def test_relspec_validation_errors(spec, x, values, match):
    with pytest.raises(ValueError, match=match):
        spec.canonical(jnp.asarray(x),
                       None if values is None else jnp.asarray(values))


def test_relspec_mesh_rejected_for_non_mesh_ops():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    with pytest.raises(ValueError, match="has none"):
        RelSpec(op="join", mesh=mesh).canonical(
            jnp.zeros(3, jnp.int32), jnp.zeros(3, jnp.int32))


def test_relspec_canonical_is_idempotent_and_static_key_hashable():
    spec = RelSpec(op="group_by", agg="sum").canonical(
        jnp.zeros(4, jnp.int32), jnp.zeros(4, jnp.int32))
    assert spec.agg == ("sum",) and spec.method == "auto"
    spec2 = dataclasses.replace(spec)
    assert hash(spec.static_key((4,), jnp.int32)) == \
        hash(spec2.static_key((4,), jnp.int32))


# ---------------------------------------------------------------------------
# planner: relational pricing
# ---------------------------------------------------------------------------

def test_choose_relational_prices_stable_ops_at_merge_fallback():
    from repro.core import cost_model
    plan = planner.choose_relational("join", 4096, dtype=jnp.int32)
    # bitonic is non-stable: picking it would actually run the stable
    # merge pipeline, so its price must equal merge's, not its raw cost
    assert plan.costs["bitonic"] == pytest.approx(plan.costs["merge"])
    raw = cost_model.relational_cost_ns(
        "join", "bitonic", 4096, pallas_interpreted=True)
    assert raw != pytest.approx(plan.costs["bitonic"])


def test_choose_relational_respects_requested_method():
    plan = planner.choose_relational("unique", 256, dtype=jnp.int32,
                                     requested="radix")
    assert plan.method == "radix"


def test_choose_relational_rejects_sketch_ops():
    with pytest.raises(ValueError, match="sort-backed"):
        planner.choose_relational("histogram", 64)


def test_choose_relational_cached_hits():
    p1 = planner.choose_relational_cached("unique", 512, dtype=jnp.int32)
    p2 = planner.choose_relational_cached("unique", 512, dtype=jnp.int32)
    assert p1 is p2


def test_method_pin_runs_that_backend():
    x = _col(13, n=40, lo=0, hi=9)
    ref = np.unique(x)
    for method in ("xla", "merge", "radix"):
        u = rel.unique(x, method=method)
        np.testing.assert_array_equal(
            np.asarray(u.values[:int(u.n_unique)]), ref, err_msg=method)


# ---------------------------------------------------------------------------
# obs integration
# ---------------------------------------------------------------------------

def test_relational_ops_emit_spans_and_counters():
    from repro.obs import metrics, trace
    trace.enable()
    metrics.reset()
    try:
        rel.unique(_col(14, n=32))
        rel.group_by(_col(15, n=32, lo=0, hi=4), _col(16, n=32))
        assert metrics.counter("relational.unique").value == 1
        assert metrics.counter("relational.group_by").value == 1
        names = [s["name"] for s in trace.spans()]
        assert "relational.unique" in names
        assert "relational.group_by" in names
        # each op: its sort, then its post-pass, as child spans
        children = [(s["parent"], s["name"]) for s in trace.spans()
                    if s["name"] in ("relational.sort",
                                     "relational.post_pass")]
        assert children == [("relational.unique", "relational.sort"),
                            ("relational.unique", "relational.post_pass"),
                            ("relational.group_by", "relational.sort"),
                            ("relational.group_by", "relational.post_pass")]
    finally:
        metrics.reset()
        trace.disable()


# ---------------------------------------------------------------------------
# consumer rewires
# ---------------------------------------------------------------------------

def test_pipeline_dedup_rows_keeps_first_occurrences():
    from repro.data.pipeline import dedup_rows, row_fingerprints
    rows = np.asarray([[1, 2, 3], [4, 5, 6], [1, 2, 3], [7, 8, 9],
                       [4, 5, 6]], np.int32)
    keep = dedup_rows(rows)
    assert keep.tolist() == [True, True, False, True, False]
    h = row_fingerprints(rows)
    assert h.dtype == np.uint32
    assert h[0] == h[2] and h[1] == h[4] and h[0] != h[1]


def test_pipeline_iterate_dedup_hook():
    from repro.data.pipeline import DataConfig, SyntheticLM
    cfg = DataConfig(vocab_size=16, seq_len=8, global_batch=16, seed=3,
                     motif_len=4, n_motifs=2)
    ds = SyntheticLM(cfg)
    batch = next(ds.iterate(dedup=True))
    fp = {tuple(r) for r in batch["tokens"].tolist()}
    assert len(fp) == batch["tokens"].shape[0]       # no duplicate rows
    assert batch["tokens"].shape == batch["labels"].shape


def test_serve_batch_accounting_groups_by_prompt_length():
    from repro.launch.serve import Request, batch_accounting
    done = [
        Request(rid=0, prompt=np.zeros(4, np.int32),
                out=np.zeros(10, np.int32)),
        Request(rid=1, prompt=np.zeros(9, np.int32),
                out=np.zeros(20, np.int32)),
        Request(rid=2, prompt=np.zeros(4, np.int32),
                out=np.zeros(30, np.int32)),
    ]
    acct = batch_accounting(done)
    assert acct == [(4, 2, 20.0), (9, 1, 20.0)]
    assert batch_accounting([]) == []


def test_moe_forward_uses_group_ranks():
    """The rewired dispatch must reproduce the inline one-hot cumsum it
    replaced — forward parity against a direct reimplementation."""
    from repro.configs.base import MoEConfig
    from repro.models import moe

    cfg = MoEConfig(n_experts=4, top_k=2, capacity_factor=2.0,
                    d_ff_expert=8)
    key = jax.random.PRNGKey(0)
    params, _ = moe.init(key, 16, cfg, "gelu", jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 16), jnp.float32)
    out, aux = moe.apply(params, x, cfg, "gelu")
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    assert float(aux["moe_lb_loss"]) > 0.0
