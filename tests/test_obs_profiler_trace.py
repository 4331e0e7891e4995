"""The program's layer spans in the profiler trace, read the way the
benchmark reads a traced run (``bench/benchkit``), and the two per-layer
metrics that read them (``frontdoor_idle_ms``, ``postpass_dispatches``)."""
import pathlib
import sys
import types

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from benchkit import spec, trace  # noqa: E402


def _read(name, reduced):
    run = types.SimpleNamespace(trace=reduced)
    return spec.metric_module(name).read(run)


def _named(name):
    return lambda n: n == name


def _within(host, child, parent):
    """Every event ``child`` names lies inside an event ``parent`` names
    (both predicates on the name)."""
    kids = [(a, b) for n, a, b in host if child(n)]
    outer = [(a, b) for n, a, b in host if parent(n)]
    return bool(kids) and all(any(pa <= a and b <= pb for pa, pb in outer)
                              for a, b in kids)


def test_program_spans_reach_the_trace(tmp_path, monkeypatch):
    """One top-k and one GROUP BY, traced as the benchmark traces a window,
    with in-memory recording off: the program's spans sit on the calling
    thread's line and nest layer inside layer."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import repro.relational as rel
    import repro.sort as rsort
    from repro.obs import trace as obs

    monkeypatch.delenv("REPRO_OBS", raising=False)
    monkeypatch.setattr(obs, "_ENABLED", False)
    obs.clear()                 # records an earlier test left in the process
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((4, 512)), jnp.float32)
    keys = jnp.asarray(rng.integers(0, 50, 1024), jnp.int32)
    vals = jnp.asarray(rng.integers(1, 51, 1024), jnp.int32)
    calls = [lambda: rsort.topk(logits, 8),
             lambda: rel.group_by(keys, vals, agg="sum")]
    for f in calls:                       # compile outside the trace
        jax.block_until_ready(f())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        for i, f in enumerate(calls):
            with jax.profiler.TraceAnnotation(trace.CALL, call=i):
                jax.block_until_ready(f())
    jax.profiler.stop_trace()
    assert obs.trace("a") is obs.trace("b")     # off again, no allocation
    assert obs.spans() == []

    host = trace.load_events(trace.find_xplane(str(tmp_path)))["host"]
    names = {n for n, _, _ in host}
    assert {"sort.run", "planner.choose", "engine.topk", "relational.group_by",
            "relational.sort", "relational.post_pass"} <= names
    assert any(n.startswith("backend.") for n in names)
    engine = lambda n: n.startswith("engine.")            # noqa: E731
    assert _within(host, engine, _named("sort.run"))
    assert _within(host, _named("planner.choose"),
                   lambda n: engine(n) or n == "relational.group_by")
    assert _within(host, lambda n: n.startswith("backend."), engine)
    assert _within(host, lambda n: n.startswith("relational.")
                   and n != "relational.group_by",
                   _named("relational.group_by"))
    # the group-by's sort goes through the front door inside its sort span
    assert _within(host, _named("engine.sort_kv"), _named("sort.run"))
    runs = [(a, b) for n, a, b in host if n == "sort.run"]
    sorts = [(a, b) for n, a, b in host if n == "relational.sort"]
    assert any(sa <= a and b <= sb for a, b in runs for sa, sb in sorts)
    post = [(a, b) for n, a, b in host if n == "relational.post_pass"]
    assert any(pa <= a and b <= pb for n, a, b in host
               if n.startswith("PjitFunction(") for pa, pb in post)

    r = trace.reduce_dir(str(tmp_path), 1)
    assert len(r.calls) == 2
    assert _read("frontdoor_idle_ms", r) >= 0
    dispatches = _read("postpass_dispatches", r)
    assert dispatches > 0 and (2 * dispatches) == int(2 * dispatches)


def _topk_call(t0):
    """One front-door call's host events at offset ``t0``: JAX dispatch
    events inside program spans, each with JAX's nested duplicate."""
    return [("sort.run", t0 + 10, t0 + 60),
            ("engine.topk", t0 + 20, t0 + 55),
            ("planner.choose", t0 + 22, t0 + 30),
            ("PjitFunction(f)", t0 + 24, t0 + 28),
            ("backend.xla", t0 + 32, t0 + 50),
            ("PjitFunction(top_k)", t0 + 33, t0 + 40)]


def test_frontdoor_idle_ms_by_hand():
    r = trace.Reduced(start=0, end=200, chips=1, calls=[(0, 100), (100, 200)],
                      host=_topk_call(0) + _topk_call(100),
                      ops={0: [("topk", "", 40, 52), ("topk", "", 140, 152)]})
    # per call the chip idles in (0, 40) and (52, 100); of that the
    # innermost program span is sort.run in [10, 20) and [55, 60), and
    # planner.choose in [22, 30), the PjitFunction inside it included
    assert _read("frontdoor_idle_ms", r) == pytest.approx(23e-6)


def test_frontdoor_idle_ms_puts_the_device_on_the_host_clock():
    """Device runs recorded before their dispatch (as a v5e trace records
    them, 0.1 to 1.3 ms early, drifting) are moved later call by call:
    by 10 ns in the first call and 6 ns in the second."""
    def call(t0):
        return _topk_call(t0) + [("PjitFunction(top_k)", t0 + 33.5, t0 + 39)]
    early = [("jit_f/copy", "copy", 14, 16),
             ("jit_top_k/custom-call", "", 30, 42)]
    r = trace.Reduced(start=0, end=200, chips=1, calls=[(0, 100), (100, 200)],
                      host=call(0) + call(100),
                      ops={0: [(n, c, a + t0, b + t0) for t0 in (0, 104)
                               for n, c, a, b in early]})
    # both calls' runs land at (24, 26) and (40, 52): per call the chip
    # idles in (0, 24), (26, 40) and (52, 100); the innermost front-door
    # spans are [10, 20), [22, 30) and [55, 60), of which idle: 10 + 6 + 5
    assert _read("frontdoor_idle_ms", r) == pytest.approx(21e-6)


def test_postpass_dispatches_by_hand():
    def call(t0):
        return [("relational.group_by", t0, t0 + 90),
                ("relational.sort", t0 + 5, t0 + 30),
                ("PjitFunction(sort)", t0 + 6, t0 + 20),
                ("PjitFunction(sort)", t0 + 7, t0 + 19),
                ("relational.post_pass", t0 + 35, t0 + 80),
                ("PjitFunction(ne)", t0 + 36, t0 + 40),
                ("PjitFunction(ne)", t0 + 36.5, t0 + 39),
                ("PjitFunction(cumsum)", t0 + 42, t0 + 50),
                ("PjitFunction(cumsum)", t0 + 42.5, t0 + 49),
                ("PjitFunction(outer)", t0 + 52, t0 + 70),
                ("PjitFunction(inner)", t0 + 55, t0 + 60),
                ("ParseArguments", t0 + 71, t0 + 72)]
    r = trace.Reduced(start=0, end=200, chips=1, calls=[(0, 100), (100, 200)],
                      host=call(0) + call(100),
                      ops={0: [("sort", "sort", 10, 20)]})
    # ne, cumsum and outer (holding inner); the sort's dispatch is not in
    # the post-pass
    assert _read("postpass_dispatches", r) == 3


@pytest.mark.parametrize("name", ["frontdoor_idle_ms", "postpass_dispatches"])
def test_new_readers_without_their_spans_give_nothing(name):
    assert _read(name, None) is None
    r = trace.Reduced(start=0, end=100, chips=1, calls=[(0, 100)],
                      host=[("PjitFunction(top_k)", 10, 40)],
                      ops={0: [("topk", "", 20, 60)]})
    assert _read(name, r) is None
