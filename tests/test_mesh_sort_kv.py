"""The mesh key-value sort at the shape class of TPC-H LINEITEM partitioned
over four chips (``sort_kv(l_partkey, row_id)``), on four virtual CPU
devices in a subprocess, as ``test_samplesort.py`` does: keys against
``np.sort``, payloads against the input rows, the flat path's spans and
its named device programs."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a shard length that is odd and not a power of two, 4 chips
SHARD = 4999
N = 4 * SHARD

SCRIPT = r"""
import glob, json, os, sys, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
import repro.sort as rsort
from repro.obs import trace as obs

out_dir, n = sys.argv[1], int(sys.argv[2])
mesh = jax.make_mesh((4,), ("data",))
rows = NamedSharding(mesh, P("data"))
rng = np.random.default_rng(20261018)
partkey = rng.integers(1, n // 30 + 1, n).astype(np.int32)  # ~30 rows a key
hot = partkey.copy()
hot[rng.random(n) < 0.4] = int(np.median(partkey))
cases = {
    "partkey_uniform": partkey,
    "all_equal": np.full(n, 7, np.int32),
    "few_distinct": rng.choice(np.array([-5, 10, 20], np.int32), n),
    "hot_median": hot,
}
row_id = jnp.asarray(np.arange(n, dtype=np.int32))
for name, keys in cases.items():
    k, v = rsort.sort_kv(jax.device_put(keys, rows),
                         jax.device_put(row_id, rows), mesh=mesh)
    np.savez(os.path.join(out_dir, name + ".npz"), keys=keys,
             out_keys=np.asarray(k), out_rows=np.asarray(v))

with obs.tracing():
    obs.clear()
    rsort.sort_kv(jax.device_put(partkey, rows), jax.device_put(row_id, rows),
                  mesh=mesh)
    spans = obs.spans()

trace_dir = tempfile.mkdtemp()
jax.profiler.start_trace(trace_dir)
jax.block_until_ready(rsort.sort_kv(jax.device_put(partkey, rows),
                                    jax.device_put(row_id, rows), mesh=mesh))
jax.profiler.stop_trace()
from jax.profiler import ProfileData
path = glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True)[0]
modules = sorted({str(v) for plane in ProfileData.from_file(path).planes
                  for line in plane.lines for ev in line.events
                  for key, v in ev.stats if key == "hlo_module"})
print(json.dumps({"spans": spans, "modules": modules}, default=str))
"""


@pytest.fixture(scope="module")
def mesh_run(tmp_path_factory):
    out = tmp_path_factory.mktemp("mesh_sort_kv")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(out), str(N)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return out, json.loads(p.stdout.strip().splitlines()[-1])


CASES = ["partkey_uniform", "all_equal", "few_distinct", "hot_median"]


def _case(mesh_run, name):
    out, _ = mesh_run
    with np.load(out / f"{name}.npz") as f:
        return f["keys"], f["out_keys"], f["out_rows"]


def _splitters(keys):
    """The flat path's splitters, in numpy: 8 regular samples from each
    sorted shard, pooled, cut at the 1/4, 2/4 and 3/4 positions."""
    shards = np.sort(keys.reshape(4, SHARD), axis=1)
    pos = ((np.arange(8) + 1) * SHARD) // 9
    pooled = np.sort(shards[:, pos].reshape(-1))
    return pooled[(np.arange(1, 4) * pooled.size) // 4]


@pytest.mark.parametrize("name", CASES)
def test_mesh_sort_kv_keys_match_numpy(mesh_run, name):
    keys, out_keys, _ = _case(mesh_run, name)
    assert out_keys.shape == (N,) and out_keys.dtype == np.int32
    np.testing.assert_array_equal(out_keys, np.sort(keys))


@pytest.mark.parametrize("name", CASES)
def test_mesh_sort_kv_payload_names_distinct_rows_with_its_key(mesh_run,
                                                               name):
    keys, out_keys, out_rows = _case(mesh_run, name)
    assert out_rows.shape == (N,)
    assert ((out_rows >= 0) & (out_rows < N)).all()
    assert np.unique(out_rows).size == N
    np.testing.assert_array_equal(keys[out_rows], out_keys)


@pytest.mark.parametrize("name", ["all_equal", "few_distinct", "hot_median"])
def test_cases_put_many_keys_on_a_splitter(mesh_run, name):
    """Keys equal to a splitter go to the lower bucket, so these cases
    send a large share of the rows to one bucket boundary."""
    keys, _, _ = _case(mesh_run, name)
    on_splitter = np.isin(keys, _splitters(keys)).mean()
    assert on_splitter >= 0.4
    assert np.isin(_case(mesh_run, "partkey_uniform")[0],
                   _splitters(_case(mesh_run, "partkey_uniform")[0])
                   ).mean() < 0.01


def test_flat_call_records_one_sync_span(mesh_run):
    _, res = mesh_run
    spans = res["spans"]
    syncs = [s for s in spans if s["name"] == "samplesort.sync"]
    assert len(syncs) == 1
    attrs = syncs[0]["attrs"]
    assert 0 < attrs["max_bucket"] <= attrs["capacity"] <= SHARD
    assert syncs[0]["parent"] == "backend.distributed"
    (backend,) = [s for s in spans if s["name"] == "backend.distributed"]
    assert backend["parent"] == "sort.run" and backend["depth"] == 1
    assert syncs[0]["depth"] == 2 and syncs[0]["call"] == backend["call"]
    names = [s["name"] for s in spans]
    assert "samplesort.phase2" in names and "distsort.oddeven" not in names


def test_flat_phases_are_named_device_programs(mesh_run):
    _, res = mesh_run
    assert {"jit_samplesort_phase1",
            "jit_samplesort_phase2"} <= set(res["modules"])
    assert "jit_local" not in res["modules"]
