"""Observability contract (repro.obs): the design rules trace.py promises.

  * spans nest via the contextvar stack and record depth/parent;
  * histograms answer percentiles within one bucket width of numpy;
  * disabled mode allocates nothing, records nothing, and leaves traced
    function outputs bit-identical;
  * spans reach an active JAX profiler trace whether recording is on or
    not, and one call's spans share its call sequence number;
  * spans block on the device only while the autotune loop is armed;
  * the planner emits exactly one ``plan_decision`` event per cache miss
    and zero per cache hit.
"""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.relational as rel
import repro.sort as rsort
from repro.core import tuning
from repro.engine import planner
from repro.obs import metrics, report, trace


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends disabled with empty stores — obs state
    is process-global and must not leak between tests (or into the rest
    of the suite)."""
    trace.disable()
    trace.clear()
    metrics.reset()
    planner.clear_plan_cache()
    yield
    trace.disable()
    trace.clear()
    metrics.reset()
    planner.clear_plan_cache()


@pytest.fixture()
def armed(monkeypatch):
    """The closed-loop autotuner armed, as ``REPRO_AUTOTUNE=1`` arms it:
    the one mode in which spans fence."""
    monkeypatch.setattr(tuning, "_autotune_live", True)


@pytest.fixture()
def unarmed(monkeypatch):
    monkeypatch.setattr(tuning, "_autotune_live", False)


@pytest.fixture()
def syncs(monkeypatch):
    """Counts ``jax.block_until_ready`` calls."""
    seen = []
    real = jax.block_until_ready

    def counting(x):
        seen.append(1)
        return real(x)
    monkeypatch.setattr(jax, "block_until_ready", counting)
    return seen


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_depth_and_parent():
    with trace.tracing():
        with trace.trace("outer", n=4):
            with trace.trace("inner"):
                with trace.trace("leaf"):
                    pass
            with trace.trace("sibling"):
                pass
    by_name = {s["name"]: s for s in trace.spans()}
    assert by_name["outer"]["depth"] == 0
    assert by_name["outer"]["parent"] is None
    assert by_name["inner"]["depth"] == 1
    assert by_name["inner"]["parent"] == "outer"
    assert by_name["leaf"]["depth"] == 2
    assert by_name["leaf"]["parent"] == "inner"
    assert by_name["sibling"]["parent"] == "outer"
    # completion order: children land before their parents
    names = [s["name"] for s in trace.spans()]
    assert names.index("leaf") < names.index("inner") < names.index("outer")
    assert by_name["outer"]["attrs"] == {"n": 4}


def test_span_fence_records_device_time_eagerly(armed):
    x = jnp.arange(1024, dtype=jnp.float32)
    with trace.tracing():
        with trace.trace("eager") as sp:
            sp.fence(jnp.sort(x))
    (rec,) = trace.spans()
    assert rec["device_ms"] is not None
    assert rec["wall_ms"] >= rec["device_ms"] >= 0.0


def test_span_fence_does_not_block_unless_armed(unarmed, syncs):
    x = jnp.arange(1024, dtype=jnp.float32)
    with trace.tracing():
        with trace.trace("eager") as sp:
            out = sp.fence(jnp.sort(x))
    assert out is not None and syncs == []
    (rec,) = trace.spans()
    assert rec["device_ms"] is None and rec["wall_ms"] >= 0.0


def test_span_fence_is_jit_safe(armed):
    """Under jit the fence sees tracers: it must not block (device_ms
    stays None) and the traced function must stay compilable."""
    x = jnp.arange(1024, dtype=jnp.float32)

    def fn(v):
        with trace.trace("traced") as sp:
            return sp.fence(jnp.sort(v))

    with trace.tracing():
        out = jax.jit(fn)(x)
        out.block_until_ready()
    recs = [s for s in trace.spans() if s["name"] == "traced"]
    assert recs and all(r["device_ms"] is None for r in recs)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.sort(np.arange(1024, dtype=np.float32)))


def test_span_set_attaches_mid_span_attrs():
    with trace.tracing():
        with trace.trace("s") as sp:
            sp.set(buckets=7)
    (rec,) = trace.spans()
    assert rec["attrs"]["buckets"] == 7


def test_to_json_round_trips():
    with trace.tracing():
        with trace.trace("j", dtype=jnp.float32, arr=np.int32(3)):
            pass
        trace.record_event("k", value=np.float64(1.5))
    doc = json.loads(trace.to_json())
    assert doc["spans"][0]["name"] == "j"
    assert doc["events"][0]["kind"] == "k"


# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------

def test_histogram_percentiles_match_numpy():
    """Log-interpolated bucket percentiles vs numpy on lognormal samples:
    accurate to roughly one bucket width (~7% with 32 buckets/decade)."""
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=2.0, sigma=1.5, size=20_000)
    h = metrics.Histogram("t")
    with trace.tracing():
        for v in samples:
            h.observe(v)
    assert h.count == len(samples)
    np.testing.assert_allclose(h.sum, samples.sum(), rtol=1e-9)
    for p in (50, 90, 99):
        est, ref = h.percentile(p), np.percentile(samples, p)
        assert abs(est - ref) / ref < 0.1, (p, est, ref)
    assert h.min == samples.min() and h.max == samples.max()
    assert h.percentile(0) == h.min and h.percentile(100) == h.max


def test_histogram_snapshot_and_registry():
    with trace.tracing():
        metrics.counter("c").inc(3)
        metrics.gauge("g").set(2.5)
        metrics.histogram("h").observe(1.0)
    snap = metrics.snapshot()
    assert snap["c"] == {"type": "counter", "value": 3.0}
    assert snap["g"]["value"] == 2.5
    assert snap["h"]["count"] == 1
    with pytest.raises(TypeError):
        metrics.gauge("c")        # name already taken by another type
    json.loads(metrics.to_json())


# ---------------------------------------------------------------------------
# disabled mode
# ---------------------------------------------------------------------------

def test_disabled_is_allocation_free_and_records_nothing():
    assert not trace.enabled() and not trace.profiler_active()
    # one shared no-op singleton: no per-call span allocation
    assert trace.trace("a", n=1) is trace.trace("b", k=2)
    with trace.trace("x") as sp:
        assert sp.fence(jnp.arange(4)) is not None
    trace.record_event("kind", field=1)
    metrics.counter("dead").inc(5)
    metrics.histogram("dead_h").observe(1.0)
    assert trace.spans() == [] and trace.events() == []
    assert metrics.snapshot()["dead"]["value"] == 0.0
    assert metrics.snapshot()["dead_h"]["count"] == 0


def test_disabled_output_bit_identical():
    """Instrumented entry points must return bit-identical outputs with
    observability off vs on — tracing observes, never perturbs."""
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 512)),
                    jnp.float32)
    off = rsort.sort(x)
    off_v, off_i = rsort.topk(x, 16)
    with trace.tracing():
        on = rsort.sort(x)
        on_v, on_i = rsort.topk(x, 16)
    np.testing.assert_array_equal(np.asarray(off), np.asarray(on))
    np.testing.assert_array_equal(np.asarray(off_v), np.asarray(on_v))
    np.testing.assert_array_equal(np.asarray(off_i), np.asarray(on_i))
    # the enabled run recorded; the disabled one did not
    assert any(s["name"] == "engine.sort" for s in trace.spans())


def test_disabled_overhead_is_noise():
    """The acceptance bound: with tracing disabled the entire per-call
    instrumentation is one module-flag check returning the shared
    singleton plus a no-op context manager.  Bound the primitive hard —
    at < 5us per span even a hot path crossing several spans per sort
    adds microseconds to a millisecond-scale n=64K sort (well inside
    run-to-run noise)."""
    assert not trace.enabled()
    reps = 50_000
    t0 = time.perf_counter()
    for _ in range(reps):
        with trace.trace("hot", n=65536) as sp:
            sp.fence(None)
    per_call = (time.perf_counter() - t0) / reps
    assert per_call < 5e-6, f"{per_call * 1e6:.2f}us per disabled span"


# ---------------------------------------------------------------------------
# profiler annotations and call identity
# ---------------------------------------------------------------------------

def test_spans_annotate_an_active_profiler_trace_without_recording(
        tmp_path):
    """With recording off, a span is real only while a profiler trace is
    active; it records nothing in memory either way."""
    assert trace.trace("a") is trace.trace("b")
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert trace.profiler_active()
        with trace.trace("annotated", n=3) as sp:
            assert isinstance(sp, trace.Span)
            sp.set(hit=True)
    finally:
        jax.profiler.stop_trace()
    assert trace.spans() == []
    assert trace.trace("a") is trace.trace("b")


def test_one_calls_spans_share_its_call_number():
    x = jnp.asarray(np.random.default_rng(4).standard_normal((2, 256)),
                    jnp.float32)
    with trace.tracing():
        rsort.topk(x, 8)
        rsort.topk(x, 8)
    recs = trace.spans()
    roots = [s for s in recs if s["depth"] == 0]
    assert [s["name"] for s in roots] == ["sort.run", "sort.run"]
    assert roots[0]["call"] != roots[1]["call"]
    names = {s["name"] for s in recs}
    assert {"planner.choose", "engine.topk"} <= names
    assert any(n.startswith("backend.") for n in names)
    first = recs[:recs.index(roots[0]) + 1]
    assert {s["call"] for s in first} == {roots[0]["call"]}
    hits = [s["attrs"]["hit"] for s in recs if s["name"] == "planner.choose"]
    assert hits == [False, True]


def _front_door_call(op):
    rng = np.random.default_rng(5)
    keys = jnp.asarray(rng.integers(0, 16, 256), jnp.int32)
    vals = jnp.asarray(rng.integers(0, 9, 256), jnp.int32)
    if op == "sort_kv":
        return rsort.sort_kv(keys, vals)
    if op == "topk":
        return rsort.topk(keys.astype(jnp.float32).reshape(2, 128), 8)
    return rel.group_by(keys, vals, agg="sum")


@pytest.mark.parametrize("op", ["sort_kv", "topk", "group_by"])
def test_front_door_does_not_block_unless_armed(op, unarmed, syncs):
    with trace.tracing():
        out = _front_door_call(op)
    assert syncs == []
    assert all(s["device_ms"] is None for s in trace.spans())
    assert all(np.asarray(leaf).size for leaf in jax.tree.leaves(out))


def test_armed_autotune_fences_the_engine_span(armed, syncs):
    with trace.tracing():
        _front_door_call("topk")
    (eng,) = [s for s in trace.spans() if s["name"] == "engine.topk"]
    assert eng["device_ms"] is not None and syncs


# ---------------------------------------------------------------------------
# planner decision events
# ---------------------------------------------------------------------------

def test_planner_decision_event_once_per_miss_zero_per_hit():
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 2048)),
                    jnp.float32)
    with trace.tracing():
        rsort.sort(x)                       # miss: plans + records
        assert len(trace.events("plan_decision")) == 1
        rsort.sort(x)                       # hit: no new decision
        assert len(trace.events("plan_decision")) == 1
        rsort.topk(x, 8)                    # different workload: new miss
        decisions = trace.events("plan_decision")
        assert len(decisions) == 2
    d0 = decisions[0]
    assert d0["n"] == 2048 and d0["method"] in d0["costs"]
    assert d0["predicted_ns"] == d0["costs"][d0["method"]] > 0
    assert decisions[1]["k"] == 8
    assert metrics.counter("planner.decisions").value == 2
    assert metrics.counter("planner.plan_cache_hits").value == 1


def test_cost_observation_pairs_predicted_with_measured(armed):
    x = jnp.asarray(np.random.default_rng(2).standard_normal((1, 4096)),
                    jnp.float32)
    with trace.tracing():
        rsort.sort(x)
    (obs,) = trace.events("cost_observation")
    assert obs["op"] == "sort" and obs["measured_ns"] > 0
    assert obs["error"] == pytest.approx(
        obs["measured_ns"] / obs["predicted_ns"])
    assert metrics.histogram("planner.cost_model_error").count == 1


# ---------------------------------------------------------------------------
# reports
# ---------------------------------------------------------------------------

def test_reports_render(armed):
    x = jnp.asarray(np.random.default_rng(3).standard_normal((1, 2048)),
                    jnp.float32)
    with trace.tracing():
        rsort.sort(x)
        metrics.histogram("serve.e2e_ms").observe(12.0)
    md = report.render_markdown()
    assert "planner.decisions" in md and "engine.sort" in md
    assert "serve.e2e_ms" in report.slo_report()
    cm = report.cost_model_report()
    assert "cost_model_error" in cm
