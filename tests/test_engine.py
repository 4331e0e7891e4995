"""repro.engine: runs + merge-path tree + planner vs jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import sort_api, tuning
from repro.engine import merge as engine_merge
from repro.engine import planner, runs, segmented


def _rand(rng, shape, dtype):
    if np.issubdtype(dtype, np.floating):
        return (rng.standard_normal(shape) * 100).astype(dtype)
    info = np.iinfo(dtype)
    return rng.integers(info.min, info.max, size=shape, dtype=dtype,
                        endpoint=True)


# ---------------------------------------------------------------------------
# engine.sort — bit-exact vs np.sort
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [np.int32, np.uint32, np.float32])
@pytest.mark.parametrize("shape", [(1, 3000), (4, 5000), (2, 3, 4100),
                                   (1, 65536)])
@pytest.mark.parametrize("descending", [False, True])
def test_engine_sort_bit_exact(dtype, shape, descending):
    x = _rand(np.random.default_rng(hash((str(dtype), shape)) % 2**31),
              shape, dtype)
    out = np.array(engine.sort(jnp.asarray(x), method="merge",
                               descending=descending))
    ref = np.sort(x, -1)
    if descending:
        ref = np.flip(ref, -1)
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("dtype", [np.int32, np.uint32, np.float32])
def test_engine_sort_million_elements(dtype):
    n = (1 << 20) + 77                       # > 1M and non-power-of-two
    x = _rand(np.random.default_rng(11), (n,), dtype)
    out = np.array(engine.sort(jnp.asarray(x), method="merge"))
    np.testing.assert_array_equal(out, np.sort(x))


def test_engine_sort_extreme_values_survive_padding():
    """Sentinel-valued data (int max/min, inf) must still sort bit-exactly."""
    rng = np.random.default_rng(3)
    x = rng.integers(0, 100, size=5000).astype(np.int32)
    x[::97] = np.iinfo(np.int32).max
    x[1::97] = np.iinfo(np.int32).min
    out = np.array(engine.sort(jnp.asarray(x), method="merge"))
    np.testing.assert_array_equal(out, np.sort(x))


def test_engine_sort_small_run_len_deep_tree():
    x = np.random.default_rng(5).standard_normal(10000).astype(np.float32)
    out = np.array(engine.sort(jnp.asarray(x), method="merge", run_len=128))
    np.testing.assert_array_equal(out, np.sort(x))


def test_run_layout_rounds_run_len_to_pow2():
    """Regression: a non-power-of-two run_len must not reach the Pallas
    tile sort / merge kernel, which address power-of-two rows."""
    n_tiles, padded = runs.run_layout(10000, 100)
    assert padded // n_tiles == 128
    x = np.random.default_rng(6).standard_normal(10000).astype(np.float32)
    out = np.array(engine.sort(jnp.asarray(x), method="merge", run_len=100))
    np.testing.assert_array_equal(out, np.sort(x))


def test_engine_sort_axis_handling():
    x = np.random.default_rng(7).standard_normal((3000, 4)).astype(np.float32)
    out = np.array(engine.sort(jnp.asarray(x), axis=0, method="merge"))
    np.testing.assert_array_equal(out, np.sort(x, 0))


def test_engine_sort_is_differentiable():
    x = jnp.asarray(np.random.default_rng(9).standard_normal(4096),
                    jnp.float32)
    g = jax.grad(lambda v: engine.sort(v, method="merge")[-16:].sum())(x)
    exp = np.zeros(4096, np.float32)
    exp[np.argsort(np.array(x))[-16:]] = 1.0
    np.testing.assert_allclose(np.array(g), exp)


# ---------------------------------------------------------------------------
# argsort / topk
# ---------------------------------------------------------------------------

def test_engine_argsort_valid_permutation():
    x = np.random.default_rng(13).standard_normal((3, 9000)).astype(np.float32)
    order = np.array(engine.argsort(jnp.asarray(x), method="merge"))
    np.testing.assert_array_equal(np.sort(order, -1),
                                  np.broadcast_to(np.arange(9000), order.shape))
    np.testing.assert_array_equal(np.take_along_axis(x, order, -1),
                                  np.sort(x, -1))


def test_engine_argsort_stable():
    rng = np.random.default_rng(17)
    x = rng.integers(0, 8, size=20000).astype(np.int32)   # heavy ties
    order = np.array(engine.argsort(jnp.asarray(x), method="merge",
                                    stable=True))
    np.testing.assert_array_equal(order, np.argsort(x, kind="stable"))


def test_engine_argsort_stable_descending():
    """Regression: descending merges used to reverse cross-run tie order
    (flip-in/flip-out turned left-wins-ties into right-wins-ties)."""
    x = jnp.zeros(512, jnp.int32)   # all-equal keys: order must be identity
    order = np.array(engine.argsort(x, method="merge", stable=True,
                                    descending=True, run_len=128))
    np.testing.assert_array_equal(order, np.arange(512))
    rng = np.random.default_rng(19)
    y = rng.integers(0, 5, size=4000).astype(np.int32)
    order = np.array(engine.argsort(jnp.asarray(y), method="merge",
                                    stable=True, descending=True,
                                    run_len=256))
    ref = np.argsort(-y.astype(np.int64), kind="stable")
    np.testing.assert_array_equal(order, ref)


@pytest.mark.parametrize("n,k", [(5000, 7), (70000, 64), (152064, 50)])
def test_engine_topk_matches_lax(n, k):
    x = jnp.asarray(np.random.default_rng(n).standard_normal((2, n)),
                    jnp.float32)
    vr, _ = jax.lax.top_k(x, k)
    v, i = engine.topk(x, k, method="merge")
    np.testing.assert_array_equal(np.array(v), np.array(vr))
    np.testing.assert_array_equal(
        np.take_along_axis(np.array(x), np.array(i), -1), np.array(vr))


# ---------------------------------------------------------------------------
# merge primitives (both backends)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas", "sort"])
@pytest.mark.parametrize("l", [64, 256, 1024])
def test_merge_pairs_backends_agree_with_np(backend, l):
    rng = np.random.default_rng(l)
    a = np.sort(rng.standard_normal((5, l)).astype(np.float32), -1)
    b = np.sort(rng.standard_normal((5, l)).astype(np.float32), -1)
    out = np.array(engine_merge.merge_pairs(
        jnp.asarray(a), jnp.asarray(b), backend=backend))
    ref = np.sort(np.concatenate([a, b], -1), -1)
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("backend", ["xla", "pallas", "sort"])
def test_merge_pairs_kv_payloads_follow_keys(backend):
    rng = np.random.default_rng(23)
    a = np.sort(rng.standard_normal((2, 128)).astype(np.float32), -1)
    b = np.sort(rng.standard_normal((2, 128)).astype(np.float32), -1)
    va = np.arange(128, dtype=np.int32)[None].repeat(2, 0)
    vb = va + 128
    k, v = engine_merge.merge_pairs(
        jnp.asarray(a), jnp.asarray(b), backend=backend,
        values=(jnp.asarray(va), jnp.asarray(vb)))
    k, v = np.array(k), np.array(v)
    np.testing.assert_array_equal(k, np.sort(np.concatenate([a, b], -1), -1))
    both = np.concatenate([a, b], -1)
    np.testing.assert_array_equal(np.take_along_axis(both, v, -1), k)


@pytest.mark.parametrize("descending", [False, True])
@pytest.mark.parametrize("backend", ["pallas", "sort"])
def test_merge_runs_stable_ties_match_rank_merge(backend, descending):
    """Stable backends agree on tie order: earlier runs first, both ways."""
    rng = np.random.default_rng(31)
    runs = np.sort(rng.integers(0, 8, (2, 4, 64)).astype(np.int32), -1)
    if descending:
        runs = runs[..., ::-1].copy()
    pos = np.arange(2 * 4 * 64, dtype=np.int32).reshape(2, 4, 64)
    ref = engine_merge.merge_runs(jnp.asarray(runs), jnp.asarray(pos),
                                  descending=descending, backend="xla")
    out = engine_merge.merge_runs(jnp.asarray(runs), jnp.asarray(pos),
                                  descending=descending, backend=backend)
    for o, r in zip(out, ref):
        np.testing.assert_array_equal(np.array(o), np.array(r))


def test_merge_pairs_pallas_extreme_values():
    """Count-masked windows: dtype-max data must not vanish into padding."""
    a = np.full((1, 64), np.iinfo(np.int32).max, np.int32)
    b = np.sort(np.random.default_rng(1).integers(
        -50, 50, (1, 64)).astype(np.int32), -1)
    out = np.array(engine_merge.merge_pairs(
        jnp.asarray(a), jnp.asarray(b), backend="pallas"))
    np.testing.assert_array_equal(out,
                                  np.sort(np.concatenate([a, b], -1), -1))


def test_kway_merge_ragged_lengths():
    rng = np.random.default_rng(29)
    parts = [np.sort(rng.standard_normal(n).astype(np.float32))
             for n in (100, 257, 64, 1000, 3)]
    out = np.array(engine_merge.kway_merge([jnp.asarray(p) for p in parts]))
    np.testing.assert_array_equal(out, np.sort(np.concatenate(parts)))


# ---------------------------------------------------------------------------
# planner / auto dispatch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [8, 100, 2048, 40000, 1 << 18])
@pytest.mark.parametrize("dtype", [np.float32, np.int32, np.uint32])
def test_auto_never_selects_invalid_backend(n, dtype):
    method = engine.choose_method(n, 2, jnp.dtype(dtype))
    assert method in ("xla", "bitonic", "pallas", "merge", "radix")
    x = _rand(np.random.default_rng(n), (2, min(n, 50000)), dtype)
    out = np.array(sort_api.sort(jnp.asarray(x), method="auto"))
    np.testing.assert_array_equal(out, np.sort(x, -1))


def test_auto_respects_whole_array_caps():
    big = (planner.MAX_PALLAS_N * 4)
    plan = planner.choose(big, 1)
    assert plan.method in ("xla", "merge")
    assert plan.costs["merge"] < plan.costs["bitonic"]


def test_choose_merge_eligibility_uses_resolved_run_len():
    """Regression: _eligible('merge') compared n against DEFAULT_RUN_LEN
    (2048) while the plan ran with the CPU run length (8192), so auto could
    pick a degenerate single-run merge for 2048 < n <= 8192."""
    plan = planner.choose(4096, 1)
    assert plan.method != "merge"
    assert plan.run_len == tuning.active().run_len
    # with an explicit small run_len, 4096 is multiple runs again: merge
    # must be a *candidate* (picked or not is the cost model's call)
    assert planner._eligible("merge", 4096, jnp.dtype(jnp.float32), 1024)
    assert not planner._eligible("merge", 4096, jnp.dtype(jnp.float32), 8192)


def test_plan_is_executable():
    plan = planner.choose(100000, 1)
    assert plan.run_len == tuning.active().run_len
    assert plan.run_method in runs.RUN_METHODS
    assert plan.merge_backend in engine_merge.MERGE_BACKENDS


def test_calibrate_updates_constants():
    try:
        prof = planner.calibrate(tile_n=256, batch=8, reps=1,
                                 include_pallas=False)
        c = prof.constants
        assert c.xla > 0 and c.bitonic > 0 and c.merge_level > 0
        assert c.radix > 0     # analytic default kept off-TPU
        assert prof.source == "calibrated"
        assert planner.constants() is c
        assert tuning.active() is prof
        # post-calibration dispatch still returns an executable method
        assert planner.choose(100000, 1).method in (
            "xla", "bitonic", "pallas", "merge", "radix")
    finally:
        planner.reset_calibration()
    from repro.core import cost_model
    assert planner.constants() == cost_model.DeviceSortConstants()
    assert tuning.active().source == "default"


def test_sort_api_merge_and_auto_methods():
    x = jnp.asarray(np.random.default_rng(31).standard_normal((2, 5000)),
                    jnp.float32)
    ref = np.sort(np.array(x), -1)
    for method in ("merge", "auto"):
        np.testing.assert_array_equal(
            np.array(sort_api.sort(x, method=method)), ref)
        order = np.array(sort_api.argsort(x, method=method))
        np.testing.assert_array_equal(
            np.take_along_axis(np.array(x), order, -1), ref)
    v, i = sort_api.topk(x, 12, method="merge")
    np.testing.assert_array_equal(np.array(v), np.flip(ref, -1)[:, :12])


# ---------------------------------------------------------------------------
# segmented sort
# ---------------------------------------------------------------------------

def test_segmented_sort_groups_sorted():
    rng = np.random.default_rng(37)
    values = rng.standard_normal(5000).astype(np.float32)
    seg = np.sort(rng.integers(0, 17, 5000)).astype(np.int32)
    sv, sseg = segmented.segmented_sort(jnp.asarray(values),
                                        jnp.asarray(seg))
    sv, sseg = np.array(sv), np.array(sseg)
    np.testing.assert_array_equal(sseg, seg)  # contiguous input stays put
    for s in np.unique(seg):
        np.testing.assert_array_equal(sv[sseg == s],
                                      np.sort(values[seg == s]))


def test_segmented_sort_unordered_segments():
    rng = np.random.default_rng(41)
    values = rng.standard_normal(1000).astype(np.float32)
    seg = rng.integers(0, 5, 1000).astype(np.int32)    # interleaved groups
    sv, sseg = segmented.segmented_sort(jnp.asarray(values),
                                        jnp.asarray(seg))
    sv, sseg = np.array(sv), np.array(sseg)
    assert (np.diff(sseg) >= 0).all()
    for s in range(5):
        np.testing.assert_array_equal(sv[sseg == s],
                                      np.sort(values[seg == s]))


def test_segment_ids_from_row_splits():
    splits = jnp.asarray([0, 3, 3, 7, 10])
    ids = np.array(segmented.segment_ids_from_row_splits(splits, 10))
    np.testing.assert_array_equal(ids, [0, 0, 0, 2, 2, 2, 2, 3, 3, 3])


def test_sort_padded_rows_preserves_layout():
    rng = np.random.default_rng(43)
    vals = rng.standard_normal((4, 64)).astype(np.float32)
    lengths = np.array([64, 10, 0, 33])
    out = np.array(segmented.sort_padded_rows(
        jnp.asarray(vals), jnp.asarray(lengths), fill_value=-1.0))
    for r, ln in enumerate(lengths):
        np.testing.assert_array_equal(out[r, :ln], np.sort(vals[r, :ln]))
        np.testing.assert_array_equal(out[r, ln:], -1.0)


def test_group_tokens_by_expert_stable():
    rng = np.random.default_rng(47)
    eids = rng.integers(0, 8, 512).astype(np.int32)
    perm, splits = segmented.group_tokens_by_expert(jnp.asarray(eids), 8)
    perm, splits = np.array(perm), np.array(splits)
    np.testing.assert_array_equal(perm, np.argsort(eids, kind="stable"))
    for e in range(8):
        assert (eids[perm[splits[e]:splits[e + 1]]] == e).all()


# ---------------------------------------------------------------------------
# composition with the mesh path
# ---------------------------------------------------------------------------

def test_distributed_sort_local_method_auto():
    from jax.sharding import Mesh
    from repro.core import distributed_sort
    devs = np.array(jax.devices())
    mesh = Mesh(devs.reshape(-1), ("data",))
    n = devs.size * 4096
    x = jnp.asarray(np.random.default_rng(53).standard_normal(n), jnp.float32)
    out = np.array(distributed_sort.distributed_sort(
        x, mesh, "data", local_method="auto"))
    np.testing.assert_array_equal(out, np.sort(np.array(x)))


@pytest.mark.slow
def test_engine_sort_large_pallas_merge_backend():
    """Full pipeline with the Pallas merge-path kernel at a non-toy size."""
    x = np.random.default_rng(59).standard_normal(1 << 16).astype(np.float32)
    rg = runs.generate_runs(jnp.asarray(x)[None, :], 2048, method="pallas")
    out = np.array(engine_merge.merge_runs(rg, backend="pallas"))[0]
    np.testing.assert_array_equal(out, np.sort(x))
