"""Run one cell once: set up, warm up, measure a window, check, report.

Set-up runs from process start until the window opens: JAX start-up, the
inputs made on the device from the seed, and a warm-up call on every input
set (which compiles, or loads from the persistent cache).  The window is a
closed loop: one call after another through the public front door, each
waited for with ``jax.block_until_ready``, until the call in flight at the
deadline completes.  After the window the device's peak memory is read,
the outputs kept for checking and the inputs go to the host, the device
arrays are freed, and the plain reference of the operation is computed
on the host and compared.

With ``--trace 1`` the window runs under the JAX profiler, each call
inside a ``bench.call`` span, and the cell's per-layer metrics are read
from the trace; with ``--trace 0`` its end-to-end metrics are printed.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import random
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from benchkit import spec as _spec

CACHE_DIR = _spec.ROOT / ".jax_cache"


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Ctx:
    """What an operation module sees of its cell."""
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    mesh: Any = None


@dataclass
class Run:
    """What the metric readers read."""
    chips: int
    setup_s: float
    window_s: float
    calls: List[tuple]                 # (start, end) on the host clock
    keys_per_call: int
    api_bytes_per_call: int
    peak_bytes: Optional[int]
    peaks: Optional[Dict[str, Any]]
    trace: Any = None                  # benchkit.trace.Reduced


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def configure_jax_cache() -> None:
    """The persistent compilation cache at a fixed path in the checkout,
    for every program however short its compile (the front door's small
    eager programs too).  Set before JAX starts, so the program under test
    finds the same directory in ``JAX_COMPILATION_CACHE_DIR``."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices_for(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def load_peaks(kind: str, require: bool) -> Optional[Dict[str, Any]]:
    with open(_spec.BENCH_DIR / "peaks.json") as f:
        table = json.load(f)
    if kind in table:
        return table[kind]
    if require:
        raise _spec.SpecError(f"device kind {kind!r} is not in peaks.json")
    return None


def peak_bytes(devs) -> Optional[int]:
    """The allocator's peak over ``devs`` (the fullest chip), if reported."""
    peaks = [int(s["peak_bytes_in_use"])
             for s in (d.memory_stats() or {} for d in devs)
             if "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


def merged(base: Dict[str, Any], over: Optional[Dict[str, Any]]):
    out = dict(base)
    out.update(over or {})
    return out


class JaxEvents:
    """Counts JAX's monitoring events per phase ("setup", "window"):
    backend compilations with their seconds, and persistent-cache hits
    and misses."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax
        self.phase = "setup"
        self.counts: Dict[str, Dict[str, float]] = {}
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_time)

    def _add(self, key, value):
        d = self.counts.setdefault(self.phase, {})
        d[key] = d.get(key, 0) + value

    def _on_event(self, event, **kw):
        if event in (self.HIT, self.MISS):
            self._add("cache_hits" if event == self.HIT else "cache_misses",
                      1)

    def _on_time(self, event, duration, **kw):
        if event == self.COMPILE:
            self._add("compiles", 1)
            self._add("compile_s", duration)

    def get(self, phase, key):
        return self.counts.get(phase, {}).get(key, 0)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             started: float, require_tpu: bool = True,
             config_over: Optional[Dict[str, Any]] = None,
             traffic_over: Optional[Dict[str, Any]] = None,
             log=print) -> Dict[str, Any]:
    """One run of one cell; returns the result object of the last line.
    ``config_over`` and ``traffic_over`` replace keys of the cell's files
    (the tests run cells at a tiny size on the CPU this way)."""
    import jax
    import numpy as np

    cell = _spec.load_cell(workload)
    chips = cell.chips
    devs = devices_for(chips, require_tpu)
    kind = devs[0].device_kind
    peaks = load_peaks(kind, require_tpu)
    config = merged(cell.config, config_over)
    traffic = merged(cell.traffic, traffic_over)
    op = _spec.op_module(traffic)
    mesh = None
    if chips > 1:
        mesh = jax.make_mesh((chips,), ("data",), devices=devs)
    ctx = Ctx(config=config, traffic=traffic, mesh=mesh)

    from benchkit import gen
    events = JaxEvents()
    marks = [("jax_start", time.time())]
    pool = int(traffic.get("pool", 1))
    inputs = gen.make_inputs(config, op.columns(traffic), pool, seed, mesh)
    jax.block_until_ready(inputs)
    marks.append(("inputs", time.time()))
    peak_inputs = peak_bytes(devs)
    keys = op.keys_per_call(ctx, inputs[0])
    api_bytes = op.api_bytes_per_call(ctx, inputs[0])
    for _ in range(int(traffic.get("warm_calls", 1))):
        for item in inputs:
            jax.block_until_ready(op.call(ctx, item))
    marks.append(("warm_up", time.time()))

    checked = traffic.get("checked_calls", 1)
    capacity = None if checked == "all" else int(checked)
    rng = random.Random(seed)
    kept: List[tuple] = []            # (pool index, output) of calls checked
    calls: List[tuple] = []
    tracer = _Tracer(trace)
    setup_s = time.time() - started
    events.phase = "window"
    t_open = time.perf_counter()
    deadline = t_open + seconds
    with tracer:
        i = 0
        while True:
            p = i % pool
            t0 = time.perf_counter()
            with tracer.call(i):
                out = jax.block_until_ready(op.call(ctx, inputs[p]))
            t1 = time.perf_counter()
            calls.append((t0, t1))
            if capacity is None or len(kept) < capacity:
                kept.append((p, out))
            else:                      # reservoir sample of the calls
                j = rng.randrange(i + 1)
                if j < capacity:
                    kept[j] = (p, out)
            del out
            i += 1
            if t1 >= deadline:
                break
    window_s = calls[-1][1] - t_open
    events.phase = "after"

    peak = peak_bytes(devs)

    host_outputs = [(p, op.host_output(out)) for p, out in kept]
    used = sorted({p for p, _ in kept})
    host_inputs = {p: gen.to_host(inputs[p]) for p in used}
    del kept, inputs
    times_ms = [1e3 * (b - a) for a, b in calls]
    log(f"cell {workload}: op={traffic['op']} chips={chips} "
        f"keys_per_call={keys} api_bytes_per_call={api_bytes} "
        f"pool={pool} method={op.planned_method(ctx, host_inputs[used[0]])}")
    steps, t = [], started
    for name, at in marks:
        steps.append(f"{name}={at - t:.3f}s")
        t = at
    log(f"setup: {' '.join(steps)} "
        f"compiles={events.get('setup', 'compiles')} "
        f"compile_s={events.get('setup', 'compile_s'):.3f} "
        f"cache_hits={events.get('setup', 'cache_hits')} "
        f"cache_misses={events.get('setup', 'cache_misses')} "
        f"peak_bytes_after_inputs={peak_inputs}")
    log(f"window: calls={len(calls)} window_s={window_s!r} "
        f"setup_s={setup_s!r} "
        f"compiles_in_window={events.get('window', 'compiles')} "
        f"call_ms_median={float(np.median(times_ms))!r} "
        f"call_ms_max={max(times_ms)!r}")
    log("call_ms: " + " ".join(f"{t:.3f}" for t in times_ms))

    refs = {p: op.reference(ctx, host_inputs[p]) for p in used}
    totals = {name: 0 for name in op.LIMITS}
    failed = 0
    for p, out in host_outputs:
        got = op.compare(ctx, host_inputs[p], out, refs[p])
        bad = False
        for name, value in got.items():
            totals[name] += value
            bad |= value > op.LIMITS[name]
        failed += bad
    checks = {name: {"value": totals[name], "limit": op.LIMITS[name]}
              for name in op.LIMITS}
    correct = failed == 0 and bool(host_outputs)

    run = Run(chips=chips, setup_s=setup_s, window_s=window_s, calls=calls,
              keys_per_call=keys, api_bytes_per_call=api_bytes,
              peak_bytes=peak, peaks=peaks)
    metrics: Dict[str, Any] = {}
    breakdown = None
    device = {"platform": devs[0].platform, "kind": kind, "count": chips,
              "memory_peak_bytes": peak if peak is not None else 0}
    if trace:
        from benchkit import trace as _trace
        run.trace = tracer.reduced(chips)
        if run.trace is not None:
            device["busy_s"] = run.trace.busy_s_mean()
            device["window_s"] = run.trace.window_s
            breakdown = _trace.breakdown(run.trace)
        wanted = cell.per_layer
    else:
        wanted = cell.end_to_end
    for m in wanted:
        value = _spec.metric_module(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    result = {"correct": correct, "attempted": len(calls),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


class _Tracer:
    """The JAX profiler around the window (or nothing, untraced)."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if on else None

    def __enter__(self):
        if self.on:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # no event per Python call
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._window = jax.profiler.TraceAnnotation("bench.window")
            self._window.__enter__()
        return self

    def __exit__(self, *exc):
        if self.on:
            import jax
            self._window.__exit__(*exc)
            jax.profiler.stop_trace()

    def call(self, i: int):
        if self.on:
            import jax
            return jax.profiler.TraceAnnotation("bench.call", call=i)
        return contextlib.nullcontext()

    def reduced(self, chips: int):
        """The window's trace, reduced; the trace's files are deleted."""
        from benchkit import trace as _trace
        try:
            return _trace.reduce_dir(self.dir, chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def print_result(result: Dict[str, Any]) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}={c['value']} limit={c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None, started: Optional[float] = None) -> int:
    started = time.time() if started is None else started
    args = parse_args(argv)
    configure_jax_cache()
    src = _spec.ROOT / "src"
    if src.is_dir():
        sys.path.insert(0, str(src))
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), started=started)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except _spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print_result(result)
    return 0
