"""Distributed relational ops: mesh dedup/group-by == single-device.

The composition argument under test: the sample-sort splitter round
co-locates equal keys on one device, so the op's local post-pass (boundary
mask -> compaction -> segment reduce) IS the global answer — no second
collective.  Acceptance is element-exact agreement with the single-device
op (and through it the numpy reference).

The in-process tests run on whatever devices this host offers (a 1-device
mesh still exercises the full mesh code path); the subprocess test forces
8 simulated devices so every CI run covers real D>1, and the
TIER1_MULTIDEV job runs this whole file at D=8.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.relational as rel


def _mesh():
    return jax.make_mesh((len(jax.devices()),), ("data",))


def _cases():
    rng = np.random.default_rng(0)
    return [
        rng.integers(-40, 40, 1003).astype(np.int32),   # uneven n
        rng.integers(0, 5, 2048).astype(np.int32),      # dup-heavy
        np.full(512, 7, np.int32),                      # all-equal
        np.where(rng.random(777) < 0.4, -0.0,
                 rng.integers(0, 9, 777)).astype(np.float32),  # signed zeros
        # an even split over the mesh: the per-shard compaction path
        np.where(rng.random(768) < 0.3, -0.0,
                 rng.integers(-3, 600, 768)).astype(np.float32),
    ]


def test_mesh_unique_matches_single_device():
    mesh = _mesh()
    for x in _cases():
        u = rel.unique(x, mesh=mesh, return_inverse=True,
                       return_counts=True)
        ref_v, ref_inv, ref_c = np.unique(x, return_inverse=True,
                                          return_counts=True)
        m = int(u.n_unique)
        msg = f"{x.dtype}/n={len(x)}"
        assert m == len(ref_v), msg
        np.testing.assert_array_equal(np.asarray(u.values[:m]), ref_v,
                                      err_msg=msg)
        np.testing.assert_array_equal(np.asarray(u.inverse), ref_inv,
                                      err_msg=msg)
        np.testing.assert_array_equal(np.asarray(u.counts[:m]), ref_c,
                                      err_msg=msg)


def test_mesh_group_by_matches_single_device():
    mesh = _mesh()
    rng = np.random.default_rng(1)
    for k in _cases():
        v = rng.integers(0, 100, len(k)).astype(np.int32)
        got = rel.group_by(k, v, agg=("sum", "min", "max", "count"),
                           mesh=mesh)
        want = rel.group_by(k, v, agg=("sum", "min", "max", "count"))
        g = int(got.n_groups)
        msg = f"{k.dtype}/n={len(k)}"
        assert g == int(want.n_groups), msg
        np.testing.assert_array_equal(np.asarray(got.keys[:g]),
                                      np.asarray(want.keys[:g]),
                                      err_msg=msg)
        for a, b in zip(got.aggregates, want.aggregates):
            np.testing.assert_array_equal(np.asarray(a[:g]),
                                          np.asarray(b[:g]), err_msg=msg)


def test_mesh_spec_validation():
    mesh = _mesh()
    x = jnp.zeros(16, jnp.int32)
    from repro.relational.relspec import RelSpec
    with pytest.raises(ValueError, match="has none"):
        RelSpec(op="rle", mesh=mesh).canonical(x)
    with pytest.raises(ValueError, match="'auto' or 'distributed'"):
        rel.unique(x, mesh=mesh, method="radix")
    with pytest.raises(ValueError, match="not in mesh axes"):
        rel.unique(x, mesh=mesh, axis_name="model")
    with pytest.raises(ValueError, match="keycodec dtype"):
        rel.unique(jnp.zeros(8, bool), mesh=mesh)


@pytest.mark.slow          # ~25s: 8-device subprocess restart + suite
def test_distributed_relational_8dev_subprocess():
    """Forced 8-device run: dedup and group-by agree with the
    single-device ops over uneven, duplicate-heavy, and signed-zero
    columns — equal keys straddling shard boundaries is exactly where a
    sloppy splitter round would break the local-op == global-op claim."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
import repro.relational as rel
mesh = jax.make_mesh((8,), ("data",))
assert len(jax.devices()) == 8
rng = np.random.default_rng(0)
cases = [
    rng.integers(-40, 40, 1003).astype(np.int32),
    rng.integers(0, 5, 2048).astype(np.int32),
    np.full(512, 7, np.int32),
    np.where(rng.random(777) < 0.4, -0.0,
             rng.integers(0, 9, 777)).astype(np.float32),
]
for x in cases:
    u = rel.unique(x, mesh=mesh, return_counts=True)
    ref_v, ref_c = np.unique(x, return_counts=True)
    m = int(u.n_unique)
    assert m == len(ref_v), (x.dtype, m, len(ref_v))
    assert (np.asarray(u.values[:m]) == ref_v).all()
    assert (np.asarray(u.counts[:m]) == ref_c).all()
    v = rng.integers(0, 100, len(x)).astype(np.int32)
    got = rel.group_by(x, v, agg=("sum", "count"), mesh=mesh)
    want = rel.group_by(x, v, agg=("sum", "count"))
    g = int(got.n_groups)
    assert g == int(want.n_groups)
    assert (np.asarray(got.keys[:g]) == np.asarray(want.keys[:g])).all()
    for a, b in zip(got.aggregates, want.aggregates):
        assert (np.asarray(a[:g]) == np.asarray(b[:g])).all()
print("DIST_RELATIONAL_8DEV_OK")
"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "PYTHONPATH": os.path.join(repo, "src")}
    env.pop("XLA_FLAGS", None)        # the subprocess pins its own count
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=420)
    assert "DIST_RELATIONAL_8DEV_OK" in r.stdout, r.stderr[-2000:]
