"""The reduction from profiler trace to metrics, on a small trace recorded
on a TPU v5e (committed under ``data/``) and on one recorded here."""
import math
import types

from perfbench_tiny import BENCH
from benchkit import spec, trace

DATA = BENCH / "tests" / "data"


def _run(reduced, **kw):
    fields = dict(trace=reduced, chips=reduced.chips, calls=reduced.calls,
                  api_bytes_per_call=0, peaks=None)
    fields.update(kw)
    return types.SimpleNamespace(**fields)


def _read(name, run):
    return spec.metric_module(name).read(run)


def test_union_and_gaps_by_hand():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    r = trace.Reduced(start=0, end=100, chips=1, calls=[(0, 40), (50, 100)],
                      host=[("PjitFunction(f)", 42, 48)],
                      ops={0: [("sort.1", "sort", 10, 30),
                               ("fusion.2", "fusion[gather]", 20, 35),
                               ("all-to-all.3", "all-to-all", 60, 70)]})
    assert r.busy(0) == [(10, 35), (60, 70)]
    assert r.gaps(0) == [(0, 10), (35, 60), (70, 100)]
    assert math.isclose(_read("device_idle_share", _run(r)), 65.0)
    assert math.isclose(_read("gather_scatter_ms", _run(r)),
                        15e-6 / 2)
    assert math.isclose(_read("collective_ms", _run(r)), 10e-6 / 2)
    # host gaps: call 1 spans 40 ns with 25 busy, call 2 50 with 10
    assert math.isclose(_read("host_gap_ms", _run(r)), (15 + 40) / 2 * 1e-6)
    assert math.isclose(_read("call_ms_p95", _run(r)), 50e-6)
    b = trace.breakdown(r)
    assert b["device_ops"][0] == ["sort.1 [sort]", 20e-9]
    labels = dict(b["idle_gaps"])
    assert math.isclose(labels["bench.call"], 40e-9)    # (0,10), (70,100)
    assert math.isclose(labels["PjitFunction(f)"], 25e-9)   # (35,60)


def test_roofline_share_reads_the_peaks_table():
    r = trace.Reduced(start=0, end=1e9, chips=1, calls=[(0, 1e9)], host=[],
                      ops={0: [("fusion", "", 0, 5e8)]})
    run = _run(r, api_bytes_per_call=819e9 * 0.25,
               peaks={"hbm_bytes_per_s": 819e9})
    assert math.isclose(_read("hbm_roofline_share", run), 50.0)
    assert _read("hbm_roofline_share", _run(r)) is None


def test_nothing_to_read_gives_nothing():
    run = types.SimpleNamespace(trace=None)
    for name in ("device_idle_share", "hbm_roofline_share",
                 "gather_scatter_ms", "host_gap_ms", "collective_ms",
                 "call_ms_p95"):
        assert _read(name, run) is None
    assert trace.reduce_events({"spans": [], "host": [], "ops": {}}, 1) is None


def test_recorded_v5e_topk_trace():
    """Eight calls of rsort.topk over 256 x 129,280 f32 on a v5e."""
    r = trace.reduce_dir_file(str(DATA / "v5e_topk.xplane.pb"), 1)
    assert len(r.calls) == 8
    busy = r.busy_s(0)
    assert 0 < busy <= r.window_s
    idle = _read("device_idle_share", _run(r))
    assert math.isclose(idle, 100 * (1 - busy / r.window_s))
    assert 0 < _read("host_gap_ms", _run(r)) < 1e3 * r.window_s / 8
    cats = {n: c for ops in r.ops.values() for n, c, _, _ in ops}
    assert cats["jit_top_k/custom-call"] == "custom-call"
    assert _read("gather_scatter_ms", _run(r)) == 0
    b = trace.breakdown(r)
    assert b["device_ops"][0][0] == "jit_top_k/custom-call [custom-call]"
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert sum(v for _, v in b["idle_gaps"]) <= r.window_s - busy + 1e-9


def test_recorded_v5e_sort_kv_trace():
    """Two calls of rsort.sort_kv over 2^24 int32 pairs on a v5e: an
    argsort, then two gathers fused by XLA (``fusion[...gather]``)."""
    r = trace.reduce_dir_file(str(DATA / "v5e_sort_kv.xplane.pb"), 1)
    cats = {n: c for ops in r.ops.values() for n, c, _, _ in ops}
    assert cats["jit_argsort/sort.11"] == "sort"
    assert "gather" in cats["jit_take_along_axis/fusion"]
    gs = _read("gather_scatter_ms", _run(r))
    sort_ms = 1e3 * r.op_seconds(lambda n, c: c == "sort") / len(r.calls)
    assert 0 < gs < 1e3 * r.busy_s(0) / len(r.calls)
    assert sort_ms > 0
    assert _read("collective_ms", _run(r)) == 0
    assert _read("device_idle_share", _run(r)) < 5


def test_cpu_trace_loads(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a: jnp.sort(a) + 1)
    x = jnp.arange(4096)[::-1]
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        for i in range(3):
            with jax.profiler.TraceAnnotation(trace.CALL, call=i):
                jax.block_until_ready(f(x))
    jax.profiler.stop_trace()
    r = trace.reduce_dir(str(tmp_path), 1)
    assert r is not None
    assert len(r.calls) == 3
    assert all(r.start <= a <= b <= r.end for a, b in r.calls)
    assert r.busy_s(0) > 0
