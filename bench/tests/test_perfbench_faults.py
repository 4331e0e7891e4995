"""The harness end to end at a tiny size on the CPU, its look for a chip
skipped: a sound run is correct, and a run whose timed path is broken
underneath the front door comes out not correct, for each fault the cell
can have."""
import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import pytest

from perfbench_tiny import BENCH, ONE_CHIP, ROOT, run_tiny

import repro.relational
import repro.sort


def _altered_sort_kv(orig):
    def f(keys, values, **kw):
        k, v = orig(keys, values, **kw)
        return k, v.at[0].set(v[1])
    return f


def _half_sort_kv(orig):
    def f(keys, values, **kw):
        h = keys.shape[0] // 2
        k, v = orig(keys[:h], values[:h], **kw)
        return (jnp.concatenate([k, keys[h:]]),
                jnp.concatenate([v, values[h:]]))
    return f


def _altered_group_by(orig):
    def f(keys, values, **kw):
        g = orig(keys, values, **kw)
        return g._replace(aggregates=(g.aggregates[0].at[0].add(1),))
    return f


def _half_group_by(orig):
    def f(keys, values, **kw):
        h = keys.shape[0] // 2
        return orig(keys[:h], values[:h], **kw)
    return f


def _altered_topk(orig):
    def f(x, k, **kw):
        v, i = orig(x, k, **kw)
        return v, i.at[0, 0].add(1)
    return f


def _half_topk(orig):
    def f(x, k, **kw):
        h = x.shape[0] // 2
        v, i = orig(x[:h], k, **kw)
        return (jnp.concatenate([v, jnp.zeros_like(v)]),
                jnp.concatenate([i, jnp.zeros_like(i)]))
    return f


FAULTS = {
    "partkey_sort_sf10": (repro.sort, "sort_kv",
                          {"answer_altered": _altered_sort_kv,
                           "half_left_out": _half_sort_kv}),
    "q18_groupby_sf10": (repro.relational, "group_by",
                         {"answer_altered": _altered_group_by,
                          "half_left_out": _half_group_by}),
    "dsv3_topk_sampling": (repro.sort, "topk",
                           {"answer_altered": _altered_topk,
                            "half_left_out": _half_topk}),
}


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("workload", ONE_CHIP)
def test_sound_run_is_correct(workload, trace):
    r = run_tiny(workload, trace=trace)
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] >= 1
    assert list(r)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())
    assert r["device"]["count"] == 1
    if trace:
        assert r["device"]["busy_s"] > 0
        assert "device_idle_share" in r["metrics"]
        assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert {"keys_per_s", "setup_s"} <= set(r["metrics"])


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out"])
@pytest.mark.parametrize("workload", ONE_CHIP)
def test_broken_timed_path_is_not_correct(workload, fault, monkeypatch):
    module, name, faults = FAULTS[workload]
    monkeypatch.setattr(module, name, faults[fault](getattr(module, name)))
    r = run_tiny(workload)
    assert r["correct"] is False
    assert r["failed"] >= 1
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


MESH_CELL = {"name": "partkey_sort_sf30_4chip",
             "config": "tpch_sf30_lineitem_4chip", "traffic": "partkey_sort",
             "chips": 4, "why": "the sort global over four chips"}

MESH_SCRIPT = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import repro.sort
from perfbench_tiny import run_tiny

orig = repro.sort.sort_kv
results = {"sound": run_tiny("partkey_sort_sf30_4chip")}

def local_only(keys, values, mesh=None, **kw):
    def part(k, v):
        return jax.lax.sort((k, v), num_keys=1)
    return jax.shard_map(part, mesh=mesh, in_specs=(P("data"), P("data")),
                         out_specs=(P("data"), P("data")))(keys, values)

repro.sort.sort_kv = local_only
results["exchange_left_out"] = run_tiny("partkey_sort_sf30_4chip")

def altered(keys, values, **kw):
    k, v = orig(keys, values, **kw)
    return k.at[0].add(1), v
repro.sort.sort_kv = altered
results["answer_altered"] = run_tiny("partkey_sort_sf30_4chip")
print(json.dumps({k: [r["correct"], r["device"]["count"], r["checks"]]
                  for k, r in results.items()}))
"""


def test_four_chip_cell_faults(tmp_path):
    """On four virtual CPU devices, in a copy of the benchmark that holds
    the four-chip cell."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if MESH_CELL["name"] not in {w["name"] for w in bench["workloads"]}:
        bench["workloads"].append(MESH_CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"),
                                           str(tmp_path / "bench" / "tests")]))
    p = subprocess.run([sys.executable, "-c", MESH_SCRIPT,
                        str(tmp_path / "bench" / "tests")], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=tmp_path)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["sound"][0] is True and res["sound"][1] == 4
    assert res["exchange_left_out"][0] is False
    assert res["exchange_left_out"][2]["key_mismatches"]["value"] > 0
    assert res["answer_altered"][0] is False
