"""The mesh exchange layer's readers: ``mesh_exchange_ms`` (device time
in the sample-sort's phase-2 program) and ``mesh_sync_idle_ms`` (chip 0
idle under the ``samplesort.sync`` span), on traces made by hand, on one
recorded on four TPU v5e chips (committed under ``data/``) and on a CPU
profiler trace of a mesh ``sort_kv`` over four virtual devices."""
import json
import math
import os
import subprocess
import sys
import types

from perfbench_tiny import BENCH, ROOT
from benchkit import spec, trace

DATA = BENCH / "tests" / "data"


def _run(reduced):
    return types.SimpleNamespace(trace=reduced, chips=reduced.chips,
                                 calls=reduced.calls)


def _read(name, reduced):
    return spec.metric_module(name).read(_run(reduced))


def _mesh_trace(host):
    """Two chips, one call from 0 to 100 ns: phase 1 on both chips, a
    gap on chip 0 from 40 to 55, phase 2 after it."""
    ops = {d: [("jit_samplesort_phase1/sort.3", "sort", 0, 40 + d),
               ("jit_samplesort_phase2/all-to-all.1", "all-to-all", 55, 70),
               ("jit_samplesort_phase2/fusion.4", "fusion[scatter]", 70,
                90 - 10 * d)]
           for d in (0, 1)}
    return trace.Reduced(start=0, end=100, chips=2, calls=[(0, 100)],
                         host=host, ops=ops)


SPANS = [("sort.run", 0, 100), ("backend.distributed", 1, 99),
         ("samplesort.phase1", 2, 10), ("samplesort.sync", 10, 50),
         ("samplesort.phase2", 50, 60)]


def test_mesh_readers_by_hand():
    r = _mesh_trace(SPANS)
    # phase 2: 15 + 20 ns on chip 0, 15 + 10 on chip 1, one call
    assert math.isclose(_read("mesh_exchange_ms", r), (35 + 25) / 2 * 1e-6)
    # chip 0 idles 40..55; the innermost span is samplesort.sync to 50
    assert math.isclose(_read("mesh_sync_idle_ms", r), 10e-6)
    assert math.isclose(_read("collective_ms", r), 15e-6)


def test_mesh_readers_read_nothing_without_their_program_or_span():
    no_sync = _mesh_trace([s for s in SPANS if s[0] != "samplesort.sync"])
    assert _read("mesh_sync_idle_ms", no_sync) is None
    assert _read("mesh_exchange_ms", no_sync) is not None
    one_chip = trace.Reduced(start=0, end=100, chips=1, calls=[(0, 100)],
                             host=[("sort.run", 0, 100)],
                             ops={0: [("jit__xla_sort_kv/sort.8", "sort",
                                       5, 95)]})
    assert _read("mesh_exchange_ms", one_chip) is None
    assert _read("mesh_sync_idle_ms", one_chip) is None
    for name in ("mesh_exchange_ms", "mesh_sync_idle_ms"):
        assert spec.metric_module(name).read(
            types.SimpleNamespace(trace=None)) is None


def test_recorded_v5e_four_chip_mesh_trace():
    """Two calls of the mesh rsort.sort_kv over 4 x 65,536 int32 pairs on
    four v5e chips: both flat programs on every chip, the spans on the
    calling thread, and every mesh reader reads a number."""
    r = trace.reduce_dir_file(str(DATA / "v5e4_mesh_sort_kv.xplane.pb"), 4)
    assert len(r.calls) == 2 and sorted(r.ops) == [0, 1, 2, 3]
    for ops in r.ops.values():
        modules = {n.split("/")[0] for n, _, _, _ in ops}
        assert {"jit_samplesort_phase1", "jit_samplesort_phase2"} <= modules
    assert {"sort.run", "backend.distributed", "samplesort.sync"} <= {
        n for n, _, _ in r.host}
    per_call_busy_ms = 1e3 * r.busy_s(0) / len(r.calls)
    exchange = _read("mesh_exchange_ms", r)
    collective = _read("collective_ms", r)
    assert 0 < collective < exchange < per_call_busy_ms
    assert 0 < _read("mesh_sync_idle_ms", r) < 1e3 * r.window_s / 2


def test_recorded_one_chip_trace_has_no_mesh_layer():
    r = trace.reduce_dir_file(str(DATA / "v5e_sort_kv.xplane.pb"), 1)
    assert _read("mesh_exchange_ms", r) is None
    assert _read("mesh_sync_idle_ms", r) is None


CPU_SCRIPT = r"""
import json, os, sys, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = sys.argv[1:3]
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import repro.sort as rsort
from benchkit import spec, trace

mesh = jax.make_mesh((4,), ("data",))
rows = NamedSharding(mesh, P("data"))
n = 4 * 999
k = jax.device_put(jax.random.randint(jax.random.key(3), (n,), 1, 134), rows)
v = jax.device_put(jnp.arange(n, dtype=jnp.int32), rows)
jax.block_until_ready(rsort.sort_kv(k, v, mesh=mesh))
d = tempfile.mkdtemp()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
jax.profiler.start_trace(d, profiler_options=opts)
with jax.profiler.TraceAnnotation(trace.WINDOW):
    for i in range(2):
        with jax.profiler.TraceAnnotation(trace.CALL, call=i):
            jax.block_until_ready(rsort.sort_kv(k, v, mesh=mesh))
jax.profiler.stop_trace()
r = trace.reduce_dir(d, 4)
run = type("Run", (), {"trace": r, "chips": 4, "calls": r.calls})()
print(json.dumps({
    "calls": len(r.calls),
    "spans": sorted({n for n, _, _ in r.host if n.startswith(
        ("sort.", "backend.", "samplesort."))}),
    "metrics": {m: spec.metric_module(m).read(run) for m in (
        "mesh_sync_idle_ms", "mesh_exchange_ms", "collective_ms")}}))
"""


def test_cpu_trace_of_a_mesh_sort_kv():
    """On the CPU the operations carry no module, so the phase-2 reader
    finds nothing; the sync reader reads the span."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", CPU_SCRIPT, str(ROOT / "src"),
                        str(BENCH)], env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["calls"] == 2
    assert {"sort.run", "backend.distributed", "samplesort.phase1",
            "samplesort.sync", "samplesort.phase2"} <= set(res["spans"])
    m = res["metrics"]
    assert m["mesh_sync_idle_ms"] >= 0
    assert m["collective_ms"] > 0
    assert m["mesh_exchange_ms"] is None
