"""Registry-wide differential fuzzing: every backend vs the jnp oracle.

Property: for ANY workload a backend's Capabilities claim to handle —
random shapes, axes, dtypes, direction, stability, k, and adversarial
value distributions (duplicate-heavy, all-equal) — the front door must
return element-exactly what ``jnp.sort`` / ``jnp.argsort`` return, with
argsort ties following the documented convention (ties keep *ascending*
index order in both directions).

The sweep is capability-driven: backends are pulled from the live
registry, so a newly registered engine is fuzzed with zero edits here,
and a backend is only exercised on workloads its declaration admits
(dtype claims, the bit-serial simulator's paper-scale n, the packed
(key, index) width limits of the imc/distributed argsort composites).

Runs on real hypothesis when installed, else on the deterministic replay
shim (tests/_hypothesis_stub.py) — the ``floats``/``tuples``/``composite``
strategies below are exactly the surface the shim grew for this suite.
"""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

import repro.sort as rsort
from repro.core import keycodec, sortspec

# dtypes spanning every codec kind (unsigned / signed / float) and width
DTYPES = ("float32", "int32", "uint16", "int8", "float16", "bfloat16")
DISTRIBUTIONS = ("uniform", "dup_heavy", "all_equal")

# the bit-serial SRAM simulator targets the paper's N=8 macro (and its
# reconstructed bitonic network only addresses power-of-two n >= 2);
# fuzzing it at engine sizes would be all simulation time for no coverage
SRAM_MAX_N = 8


def _values(seed: int, shape, dtype_name: str, dist: str) -> jnp.ndarray:
    """Integer-valued keys exactly representable in every fuzzed dtype."""
    rng = np.random.default_rng(seed)
    lo, hi = (0, 100) if dtype_name.startswith("uint") else (-100, 100)
    if dist == "uniform":
        raw = rng.integers(lo, hi, size=shape)
    elif dist == "dup_heavy":
        raw = rng.integers(0, 4, size=shape)
    else:                                    # all_equal — splitter/tie worst case
        raw = np.full(shape, rng.integers(lo, hi))
    return jnp.asarray(raw).astype(jnp.dtype(dtype_name))


@st.composite
def sort_cases(draw):
    shape = draw(st.tuples(st.integers(1, 2),
                           st.sampled_from([1, 2, 5, 8, 17, 33])))
    return {
        "seed": draw(st.integers(0, 2**31 - 1)),
        "shape": shape,
        "dtype": draw(st.sampled_from(DTYPES)),
        "dist": draw(st.sampled_from(DISTRIBUTIONS)),
        "descending": draw(st.booleans()),
        "axis": draw(st.sampled_from([-1, 0])),
        # top-k fraction of n (resolved against the sorted axis length)
        "k_frac": draw(st.floats(0.0, 1.0)),
        "stable": draw(st.booleans()),
    }


def _backends_for(dtype_name: str, n: int, *, sorts: bool = False):
    for name in sorted(sortspec.backend_names()):
        caps = sortspec.get_backend(name).capabilities
        if caps.dtypes is not None and dtype_name not in caps.dtypes:
            continue
        if caps.substrate == "sram" and (n > SRAM_MAX_N or n < 2
                                         or n & (n - 1)):
            continue
        if sorts and not caps.supports_sort:
            continue        # selection-only engines run no full sorts
        yield name, caps


def _composite_argsort_fits(name: str, dtype_name: str, n: int) -> bool:
    """imc / distributed argsort pack through keycodec.argsort_composite;
    combinations beyond its 32-bit word raise by contract — skipped here."""
    if name not in ("imc", "distributed"):
        return True
    return keycodec.composite_fits(dtype_name, n)


def _f64(a) -> np.ndarray:
    return np.asarray(a).astype(np.float64)


def _ref_argsort(x, axis, descending):
    return np.asarray(jnp.argsort(x, axis=axis, stable=True,
                                  descending=descending))


@given(sort_cases())
@settings(max_examples=5, deadline=None)
def test_fuzz_sort_matches_jnp(case):
    x = _values(case["seed"], case["shape"], case["dtype"], case["dist"])
    axis, desc = case["axis"], case["descending"]
    n = x.shape[axis]
    ref = _f64(jnp.sort(x, axis=axis))
    if desc:
        ref = np.flip(ref, axis)
    for name, _caps in _backends_for(case["dtype"], n, sorts=True):
        out = rsort.sort(x, axis=axis, descending=desc, method=name)
        np.testing.assert_array_equal(
            _f64(out), ref,
            err_msg=f"{name}/{case['dtype']}/{case['dist']}/n={n}/"
                    f"axis={axis}/desc={desc}")


@given(sort_cases())
@settings(max_examples=5, deadline=None)
def test_fuzz_argsort_tie_convention(case):
    """Element-exact vs the stable jnp.argsort in BOTH directions — the
    documented ties-keep-ascending convention.  ``stable=True`` adds the
    engine's forced-stable pipeline on top of each backend request."""
    x = _values(case["seed"], case["shape"], case["dtype"], case["dist"])
    axis, desc = case["axis"], case["descending"]
    n = x.shape[axis]
    ref = _ref_argsort(x, axis, desc)
    for name, _caps in _backends_for(case["dtype"], n, sorts=True):
        if not _composite_argsort_fits(name, case["dtype"], n):
            continue
        order = rsort.argsort(x, axis=axis, descending=desc, method=name,
                              stable=case["stable"])
        np.testing.assert_array_equal(
            np.asarray(order), ref,
            err_msg=f"{name}/{case['dtype']}/{case['dist']}/n={n}/"
                    f"axis={axis}/desc={desc}/stable={case['stable']}")


@given(sort_cases())
@settings(max_examples=5, deadline=None)
def test_fuzz_sort_kv_payload_follows_keys(case):
    """kv claims: sorted keys match the oracle and the payload is the
    applied permutation; stable backends must reproduce it exactly."""
    x = _values(case["seed"], case["shape"], case["dtype"], case["dist"])
    axis, desc = case["axis"], case["descending"]
    n = x.shape[axis]
    payload = jnp.broadcast_to(
        jnp.arange(n, dtype=jnp.int32).reshape(
            [n if a == axis % x.ndim else 1 for a in range(x.ndim)]),
        x.shape)
    key_ref = _f64(jnp.sort(x, axis=axis))
    if desc:
        key_ref = np.flip(key_ref, axis)
    for name, caps in _backends_for(case["dtype"], n, sorts=True):
        if not caps.supports_kv:
            continue
        sk, sv = rsort.sort_kv(x, payload, axis=axis, descending=desc,
                               method=name)
        msg = f"{name}/{case['dtype']}/{case['dist']}/n={n}/axis={axis}"
        np.testing.assert_array_equal(_f64(sk), key_ref, err_msg=msg)
        # payload is the permutation that produces the sorted keys
        np.testing.assert_array_equal(
            _f64(np.take_along_axis(np.asarray(x), np.asarray(sv),
                                    axis % x.ndim)),
            _f64(sk), err_msg=msg)
        if caps.stable:
            np.testing.assert_array_equal(
                np.asarray(sv), _ref_argsort(x, axis, desc), err_msg=msg)


@given(sort_cases())
@settings(max_examples=5, deadline=None)
def test_fuzz_topk_matches_lax(case):
    x = _values(case["seed"], case["shape"], case["dtype"], case["dist"])
    axis = case["axis"]
    n = x.shape[axis]
    k = max(1, min(n, round(case["k_frac"] * n)))
    xl = jnp.moveaxis(x, axis, -1)
    vr, _ = jax.lax.top_k(xl, k)
    for name, caps in _backends_for(case["dtype"], n):
        if not caps.supports_topk:
            continue
        v, i = rsort.topk(x, k, axis=axis, method=name)
        v = jnp.moveaxis(v, axis, -1)
        i = jnp.moveaxis(i, axis, -1)
        msg = f"{name}/{case['dtype']}/{case['dist']}/n={n}/k={k}"
        np.testing.assert_array_equal(_f64(v), _f64(vr), err_msg=msg)
        # indices may differ on ties, but must gather the same values
        np.testing.assert_array_equal(
            _f64(np.take_along_axis(np.asarray(xl), np.asarray(i), -1)),
            _f64(vr), err_msg=msg)


# ---------------------------------------------------------------------------
# top-k lens: exact-k everywhere (k extremes, tie floods, extreme keys, kv)
# ---------------------------------------------------------------------------

def _extreme_values(seed: int, shape, dtype_name: str) -> jnp.ndarray:
    """Keys stacked with the dtype's own extremes: max/min (and ±inf, ±0.0
    for floats) mixed into a duplicate-heavy body — the exact regime the
    threshold-mask top-k bugs lived in."""
    rng = np.random.default_rng(seed)
    dt = np.dtype(dtype_name) if dtype_name != "bfloat16" else np.float32
    if np.issubdtype(dt, np.integer):
        info = np.iinfo(dt)
        pool = np.asarray([info.max, info.min, 0, 1, info.max, info.min],
                          dtype=np.int64)
    else:
        pool = np.asarray([np.inf, -np.inf, 0.0, -0.0,
                           float(np.finfo(np.float32).max), 1.0])
    body = rng.integers(0, 3, size=np.prod(shape))
    x = pool[rng.integers(0, len(pool), size=np.prod(shape))]
    use_body = rng.random(np.prod(shape)) < 0.5
    x = np.where(use_body, body.astype(x.dtype), x)
    return jnp.asarray(x.reshape(shape)).astype(jnp.dtype(dtype_name))


@st.composite
def topk_lens_cases(draw):
    return {
        "seed": draw(st.integers(0, 2**31 - 1)),
        "n": draw(st.sampled_from([1, 2, 7, 33])),
        "dtype": draw(st.sampled_from(DTYPES)),
        "dist": draw(st.sampled_from(("dup_heavy", "all_equal", "extreme"))),
        "k_mode": draw(st.sampled_from(("one", "half", "all"))),
    }


@given(topk_lens_cases())
@settings(max_examples=6, deadline=None)
def test_fuzz_topk_lens_exact_k(case):
    """Every backend claiming topk (selection engines included) vs
    ``jax.lax.top_k`` at the k extremes over adversarial keys.  Exactly k
    come back, values element-exact; selection backends must also match
    lax's tie rule (lowest index first) index-exactly."""
    n = case["n"]
    k = {"one": 1, "half": max(1, n // 2), "all": n}[case["k_mode"]]
    if case["dist"] == "extreme":
        x = _extreme_values(case["seed"], (2, n), case["dtype"])
    else:
        x = _values(case["seed"], (2, n), case["dtype"], case["dist"])
    vr, ir = jax.lax.top_k(x, k)
    for name, caps in _backends_for(case["dtype"], n):
        if not caps.supports_topk:
            continue
        v, i = rsort.topk(x, k, method=name)
        msg = f"{name}/{case['dtype']}/{case['dist']}/n={n}/k={k}"
        assert v.shape == (2, k) and i.shape == (2, k), msg
        np.testing.assert_array_equal(_f64(v), _f64(vr), err_msg=msg)
        np.testing.assert_array_equal(
            _f64(np.take_along_axis(np.asarray(x), np.asarray(i), -1)),
            _f64(vr), err_msg=msg)
        if caps.selection:
            # exact-k tie convention: the selection subsystem reproduces
            # lax.top_k's lowest-index-first rule bit-exactly
            np.testing.assert_array_equal(np.asarray(i), np.asarray(ir),
                                          err_msg=msg)


@given(topk_lens_cases())
@settings(max_examples=5, deadline=None)
def test_fuzz_topk_lens_kv_payload(case):
    """The selection kernel's kv variant: the payload rides the exact-k
    selection — gathering the payload through the returned indices equals
    the kv output, under tie floods and extreme keys."""
    from repro.kernels import radix_select as _sel
    n = case["n"]
    k = {"one": 1, "half": max(1, n // 2), "all": n}[case["k_mode"]]
    if case["dist"] == "extreme":
        x = _extreme_values(case["seed"], (2, n), case["dtype"])
    else:
        x = _values(case["seed"], (2, n), case["dtype"], case["dist"])
    payload = jnp.asarray(
        np.random.default_rng(case["seed"] ^ 0xABC).integers(
            -999, 999, (2, n)).astype(np.int32))
    v, pv, i = _sel.select_topk_kv(x, payload, k)
    vr, ir = jax.lax.top_k(x, k)
    msg = f"{case['dtype']}/{case['dist']}/n={n}/k={k}"
    np.testing.assert_array_equal(_f64(v), _f64(vr), err_msg=msg)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir), err_msg=msg)
    np.testing.assert_array_equal(
        np.asarray(pv),
        np.take_along_axis(np.asarray(payload), np.asarray(ir), -1),
        err_msg=msg)


# ---------------------------------------------------------------------------
# spill lens: the out-of-core tier vs the jnp oracle at forced tiny chunks
# ---------------------------------------------------------------------------

from repro.engine import spill as _spill  # noqa: E402

# small enough that every fuzzed n spans several chunks (f32: 16 elems),
# large enough to clear tuning.MIN_SPILL_THRESHOLD_BYTES
SPILL_CHUNK_BYTES = 64
# bfloat16 rides the pipeline as its uint16 keycodec encoding — fuzzing
# it here pins the host-side encode/decode mirror bit-exactly
SPILL_DTYPES = ("float32", "int32", "uint16", "int8", "float16",
                "bfloat16")


@st.composite
def spill_cases(draw):
    return {
        "seed": draw(st.integers(0, 2**31 - 1)),
        # uneven tails on purpose: primes and off-by-ones around the
        # 16/32/64-element chunk sizes the forced threshold produces
        "n": draw(st.sampled_from([1, 15, 16, 17, 33, 100, 257])),
        "dtype": draw(st.sampled_from(SPILL_DTYPES)),
        "dist": draw(st.sampled_from(DISTRIBUTIONS)),
        "descending": draw(st.booleans()),
    }


@given(spill_cases())
@settings(max_examples=6, deadline=None)
def test_fuzz_spill_sort_matches_jnp(case):
    x = _values(case["seed"], (case["n"],), case["dtype"], case["dist"])
    desc = case["descending"]
    ref = _f64(jnp.sort(x))
    if desc:
        ref = ref[::-1]
    out = _spill.spill_sort(np.asarray(x), descending=desc,
                            chunk_bytes=SPILL_CHUNK_BYTES)
    np.testing.assert_array_equal(
        _f64(out), ref,
        err_msg=f"spill/{case['dtype']}/{case['dist']}/n={case['n']}/"
                f"desc={desc}")


@given(spill_cases())
@settings(max_examples=6, deadline=None)
def test_fuzz_spill_argsort_is_stable(case):
    """The kv spill path claims stability: the permutation must be
    element-exact against the stable jnp.argsort in both directions —
    across chunk boundaries, where a tie between runs is decided by the
    host merge's cursor arithmetic rather than one device sort."""
    x = _values(case["seed"], (case["n"],), case["dtype"], case["dist"])
    desc = case["descending"]
    order = _spill.spill_argsort(np.asarray(x), descending=desc,
                                 chunk_bytes=SPILL_CHUNK_BYTES)
    np.testing.assert_array_equal(
        np.asarray(order), _ref_argsort(x, -1, desc),
        err_msg=f"spill/{case['dtype']}/{case['dist']}/n={case['n']}/"
                f"desc={desc}")


# ---------------------------------------------------------------------------
# relational lens: every repro.relational op vs its numpy reference
# ---------------------------------------------------------------------------

import repro.relational as rel  # noqa: E402

REL_DTYPES = ("float32", "int32", "uint16", "int8")
# empty-group / dup-heavy / all-equal / signed-zero distributions are the
# regimes where a compaction or boundary-mask bug would hide
REL_DISTRIBUTIONS = ("uniform", "dup_heavy", "all_equal", "signed_zero")


def _rel_values(seed: int, n: int, dtype_name: str, dist: str):
    if dist == "signed_zero":
        if not dtype_name.startswith("float"):
            dist = "dup_heavy"              # ints have one zero
        else:
            rng = np.random.default_rng(seed)
            x = np.where(rng.random(n) < 0.5, -0.0,
                         rng.integers(0, 3, n).astype(np.float64))
            return jnp.asarray(x).astype(jnp.dtype(dtype_name))
    return _values(seed, (n,), dtype_name, dist)


@st.composite
def rel_cases(draw):
    return {
        "seed": draw(st.integers(0, 2**31 - 1)),
        "n": draw(st.sampled_from([0, 1, 2, 7, 33])),
        "dtype": draw(st.sampled_from(REL_DTYPES)),
        "dist": draw(st.sampled_from(REL_DISTRIBUTIONS)),
    }


@given(rel_cases())
@settings(max_examples=6, deadline=None)
def test_fuzz_relational_unique_matches_numpy(case):
    x = np.asarray(_rel_values(case["seed"], case["n"], case["dtype"],
                               case["dist"]))
    ref_v, ref_inv, ref_c = np.unique(x, return_inverse=True,
                                      return_counts=True)
    u = rel.unique(x, return_inverse=True, return_counts=True)
    m = int(u.n_unique)
    msg = f"{case['dtype']}/{case['dist']}/n={case['n']}"
    assert m == len(ref_v), msg
    np.testing.assert_array_equal(_f64(u.values[:m]), _f64(ref_v),
                                  err_msg=msg)
    np.testing.assert_array_equal(np.asarray(u.inverse), ref_inv,
                                  err_msg=msg)
    np.testing.assert_array_equal(np.asarray(u.counts[:m]), ref_c,
                                  err_msg=msg)


@given(rel_cases())
@settings(max_examples=6, deadline=None)
def test_fuzz_relational_group_by_matches_scatter_reference(case):
    k = np.asarray(_rel_values(case["seed"], case["n"], case["dtype"],
                               case["dist"]))
    v = np.random.default_rng(case["seed"] ^ 0x5EED).integers(
        0, 50, case["n"]).astype(np.int32)          # the kv payload
    gb = rel.group_by(k, v, agg=("sum", "min", "max", "count", "mean"))
    ref_k, inv = np.unique(k, return_inverse=True)
    g = len(ref_k)
    msg = f"{case['dtype']}/{case['dist']}/n={case['n']}"
    assert int(gb.n_groups) == g, msg
    np.testing.assert_array_equal(_f64(gb.keys[:g]), _f64(ref_k),
                                  err_msg=msg)
    rsum = np.zeros(g, np.int64)
    np.add.at(rsum, inv, v)
    rmin = np.full(g, np.iinfo(np.int32).max)
    np.minimum.at(rmin, inv, v)
    rmax = np.full(g, np.iinfo(np.int32).min)
    np.maximum.at(rmax, inv, v)
    rcnt = np.bincount(inv, minlength=g)
    refs = (rsum.astype(np.int32), rmin, rmax, rcnt,
            rsum.astype(np.float32)
            / np.maximum(rcnt, 1).astype(np.float32))
    for got, want in zip(gb.aggregates, refs):
        np.testing.assert_array_equal(np.asarray(got[:g]), want[:g]
                                      if g else want, err_msg=msg)


@given(rel_cases())
@settings(max_examples=6, deadline=None)
def test_fuzz_relational_join_matches_searchsorted_reference(case):
    lk = np.asarray(_rel_values(case["seed"], case["n"], case["dtype"],
                                case["dist"]))
    rk = np.asarray(_rel_values(case["seed"] ^ 0xA5A5, max(1, case["n"]),
                                case["dtype"], case["dist"]))
    j = rel.join(lk, rk)
    p = int(j.n_pairs)
    # reference via stable sorts + searchsorted runs (the documented pair
    # order: ascending key, left input order, right input order)
    ol = np.argsort(lk, kind="stable")
    orr = np.argsort(rk, kind="stable")
    sl, sr = lk[ol], rk[orr]
    pairs = []
    for pos, key in enumerate(sl):
        a, b = np.searchsorted(sr, key, "left"), \
            np.searchsorted(sr, key, "right")
        pairs.extend((int(ol[pos]), int(orr[t])) for t in range(a, b))
    got = list(zip(np.asarray(j.left_idx[:p]).tolist(),
                   np.asarray(j.right_idx[:p]).tolist()))
    assert got == pairs, f"{case['dtype']}/{case['dist']}/n={case['n']}"


@given(rel_cases())
@settings(max_examples=6, deadline=None)
def test_fuzz_relational_rle_and_histogram(case):
    x = np.asarray(_rel_values(case["seed"], case["n"], case["dtype"],
                               case["dist"]))
    msg = f"{case['dtype']}/{case['dist']}/n={case['n']}"
    r = rel.run_length_encode(x)
    dec = rel.rle_decode(r.values, r.run_lengths, case["n"])
    np.testing.assert_array_equal(_f64(dec), _f64(np.sort(x)),
                                  err_msg=msg)
    assert int(np.asarray(r.run_lengths).sum()) == case["n"], msg
    h = rel.histogram(x, 8)
    ref, _ = np.histogram(x.astype(np.float32),
                          bins=np.asarray(h.edges))
    np.testing.assert_array_equal(np.asarray(h.counts), ref, err_msg=msg)
