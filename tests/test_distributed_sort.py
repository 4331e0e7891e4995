"""distributed_sort round plumbing + bitonic kv tie-break edge cases.

Correctness-critical branches that were previously untested: the odd-even
transposition partner tables (edge devices must idle, partners must pair up
symmetrically, for even AND odd device counts) and the tie-break rule of the
word-parallel kv bitonic sort (equal keys keep the self payload, so argsort
stays a permutation under heavy ties).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distributed_sort as ds
from repro.core import sort_api


# ---------------------------------------------------------------------------
# _round_permutation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_dev", [1, 2, 3, 4, 5, 7, 8, 16])
@pytest.mark.parametrize("even_round", [True, False])
def test_round_permutation_is_symmetric_involution(n_dev, even_round):
    pairs = ds._round_permutation(n_dev, even_round)
    partner = {i: p for i, p in pairs}
    assert sorted(partner) == list(range(n_dev))
    for i, p in partner.items():
        assert 0 <= p < n_dev                  # never addresses off the mesh
        assert partner[p] == i                 # pairing is mutual


@pytest.mark.parametrize("n_dev", [2, 3, 4, 5, 8, 9])
def test_round_permutation_edge_idling(n_dev):
    even = dict(ds._round_permutation(n_dev, True))
    odd = dict(ds._round_permutation(n_dev, False))
    # odd rounds: device 0 idles; last device idles iff count is even
    assert odd[0] == 0
    assert (odd[n_dev - 1] == n_dev - 1) == (n_dev % 2 == 0)
    # even rounds: last device idles iff count is odd
    assert (even[n_dev - 1] == n_dev - 1) == (n_dev % 2 == 1)
    # non-edge devices all participate
    active_even = sum(1 for i, p in even.items() if p != i)
    active_odd = sum(1 for i, p in odd.items() if p != i)
    assert active_even == 2 * (n_dev // 2)
    assert active_odd == 2 * ((n_dev - 1) // 2)


def test_round_permutations_cover_all_adjacent_links():
    """Across one even+odd round pair every adjacent device link is used."""
    n_dev = 6
    links = set()
    for even_round in (True, False):
        for i, p in ds._round_permutation(n_dev, even_round):
            if p != i:
                links.add((min(i, p), max(i, p)))
    assert links == {(i, i + 1) for i in range(n_dev - 1)}


def test_odd_even_transposition_sorts_on_host():
    """Drive the round tables through a pure-numpy merge-split simulation:
    after n_dev rounds the shard concatenation must be globally sorted."""
    rng = np.random.default_rng(0)
    for n_dev in (2, 3, 4, 5, 8):
        shards = [np.sort(rng.standard_normal(16)) for _ in range(n_dev)]
        for r in range(n_dev):
            pairs = ds._round_permutation(n_dev, r % 2 == 0)
            for i, p in pairs:
                if p <= i:
                    continue
                both = np.sort(np.concatenate([shards[i], shards[p]]))
                shards[i], shards[p] = both[:16], both[16:]
        flat = np.concatenate(shards)
        np.testing.assert_array_equal(flat, np.sort(flat))


def test_bitonic_merge_halves():
    rng = np.random.default_rng(1)
    lo = jnp.asarray(np.sort(rng.standard_normal(32)), jnp.float32)
    hi = jnp.asarray(np.sort(rng.standard_normal(32)), jnp.float32)
    out_lo, out_hi = ds.bitonic_merge_halves(lo, hi)
    ref = np.sort(np.concatenate([np.array(lo), np.array(hi)]))
    np.testing.assert_array_equal(np.array(out_lo), ref[:32])
    np.testing.assert_array_equal(np.array(out_hi), ref[32:])


@pytest.mark.parametrize("m,dtype", [(192, np.int32), (3, np.float32),
                                     (100, np.uint32)])
def test_bitonic_merge_halves_non_power_of_two(m, dtype):
    """Shards of a non-power-of-two length merge exactly (the odd-even
    mesh sort of 768 keys over 4 devices runs 192-key halves)."""
    rng = np.random.default_rng(m)
    lo = np.sort(rng.integers(0, 50, m).astype(dtype))
    hi = np.sort(rng.integers(0, 50, m).astype(dtype))
    out_lo, out_hi = ds.bitonic_merge_halves(jnp.asarray(lo), jnp.asarray(hi))
    ref = np.sort(np.concatenate([lo, hi]))
    np.testing.assert_array_equal(np.array(out_lo), ref[:m])
    np.testing.assert_array_equal(np.array(out_hi), ref[m:])


# ---------------------------------------------------------------------------
# bitonic kv tie-break
# ---------------------------------------------------------------------------

def test_bitonic_kv_constant_keys_keep_payload_permutation():
    keys = jnp.zeros((2, 16), jnp.float32)
    vals = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    sk, sv = sort_api.bitonic_sort(keys, values=vals)
    np.testing.assert_array_equal(np.array(sk), np.zeros((2, 16)))
    # every payload survives exactly once (the tie rule never duplicates)
    np.testing.assert_array_equal(np.sort(np.array(sv), -1),
                                  np.broadcast_to(np.arange(16), (2, 16)))


@pytest.mark.parametrize("descending", [False, True])
def test_bitonic_kv_heavy_ties_valid_permutation(descending):
    rng = np.random.default_rng(2)
    keys = rng.integers(0, 3, size=(4, 33)).astype(np.int32)  # many ties
    vals = np.broadcast_to(np.arange(33, dtype=np.int32), (4, 33))
    sk, sv = sort_api.bitonic_sort(jnp.asarray(keys),
                                   values=jnp.asarray(vals),
                                   descending=descending)
    sk, sv = np.array(sk), np.array(sv)
    ref = np.sort(keys, -1)
    if descending:
        ref = np.flip(ref, -1)
    np.testing.assert_array_equal(sk, ref)
    np.testing.assert_array_equal(np.sort(sv, -1),
                                  np.broadcast_to(np.arange(33), (4, 33)))
    # payloads must point at positions holding their own key value
    np.testing.assert_array_equal(np.take_along_axis(keys, sv, -1), sk)


def test_argsort_pallas_routes_to_kernel_and_agrees():
    """Regression: method='pallas' used to silently fall through to the jnp
    path; it must hit the kv kernel and still produce a valid argsort."""
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, 37)),
                    jnp.float32)
    order = np.array(sort_api.argsort(x, method="pallas"))
    np.testing.assert_array_equal(
        np.take_along_axis(np.array(x), order, -1), np.sort(np.array(x), -1))


def test_argsort_imc_wide_keys_raise():
    """imc argsort packs (key, index) into one array word: 32-bit keys
    leave no index bits, so the composite path must refuse clearly (narrow
    keys work — see test_sort_conformance.test_imc_argsort_conformance)."""
    x = jnp.asarray(np.arange(8, dtype=np.uint32))
    with pytest.raises(ValueError, match="32-bit"):
        sort_api.argsort(x, method="imc")
    with pytest.raises(ValueError, match="method must be one of"):
        sort_api.argsort(x, method="nope")
