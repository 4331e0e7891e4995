"""Cost-model dispatch — picks the sorting backend from (n, batch, dtype).

Extends the paper-constant cost model (core/cost_model.py) with per-tile
constants that can be *measured* on the running backend, then prices every
eligible backend and returns the cheapest as an executable plan.
``method="auto"`` on the public API is a thin wrapper over this module.

Eligibility is a pure capability query against the backend registry
(core/sortspec.py): each backend declares the dtypes it sorts correctly,
an optional auto-dispatch size cap, and whether auto may pick it at all —
there are no per-backend validity rules here, so a third-party backend
registered with ``@register_backend`` is priced and dispatched without any
planner edits.  Pricing likewise goes through ``SortBackend.cost_ns``
(defaulting to the analytic model; unknown backends price at +inf until
they override it).

Resolved plans are cached per (n, batch, dtype, requested, run_len) and
invalidated on calibration or registry changes, so repeated serving-shape
calls skip re-planning entirely.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import cost_model, sortspec
from repro.core import tuning as _tuning
from repro.core.backends import MAX_BITONIC_N, MAX_PALLAS_N  # noqa: F401
from repro.engine import runs as _runs
from repro.obs import metrics as _metrics
from repro.obs import trace as _obs


@dataclasses.dataclass(frozen=True)
class Plan:
    """Executable dispatch decision for one (n, batch, dtype) workload."""
    method: str                  # any auto-dispatchable registered backend
    run_len: int                 # engine tile size (merge method only)
    run_method: str              # backend sorting each run
    merge_backend: str           # "xla" | "pallas" merge primitive
    costs: Dict[str, float]      # estimated ns per candidate


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def constants() -> cost_model.DeviceSortConstants:
    """The cost constants every plan is priced with — the active tuning
    profile's (per-platform defaults until ``calibrate()`` measures real
    ones or a persisted profile matches the device fingerprint)."""
    return _tuning.active().constants


def _eligible(method: str, n: int, dtype, run_len: int) -> bool:
    """Generic capability query: may auto hand (n, dtype) to ``method``?"""
    return sortspec.get_backend(method).eligible(n, dtype, run_len)


def _auto_candidates() -> Dict[str, sortspec.SortBackend]:
    return {name: be for name, be in sortspec.registered_backends().items()
            if be.capabilities.auto_dispatch}


def choose(n: int, batch: int = 1, dtype=jnp.float32, *,
           requested: str = "auto",
           run_len: Optional[int] = None,
           k: Optional[int] = None) -> Plan:
    """Resolve ``requested`` ("auto" or a concrete method) into a Plan.

    With ``k`` set the workload is a top-k and every candidate is priced
    through ``SortBackend.topk_cost_ns``: selection backends answer with
    the O(n·passes) ``cost_model.selection_cost_ns``, sort backends with
    the sort-prefix contract (their full sort cost), and the xla backend
    with the *native* ``lax.top_k`` price off-TPU — so auto lands on
    radix-select once ``k ≪ n`` on TPU, on the tuned native selection on
    hosts (where it beats everything — the ``topk_xla`` rows in
    results_engine_cpu.csv), and on a plain sort when k approaches n.

    Every resolved plan is recorded as a structured ``plan_decision``
    event when observability is on (repro.obs) — candidate cost table,
    chosen backend, predicted ns — so dispatch is auditable after the
    fact; ``choose_cached`` hits skip both re-pricing and the event.
    """
    prof = _tuning.active()
    rl = run_len or prof.run_len
    consts = prof.constants
    interp = not on_tpu()
    candidates = _auto_candidates()
    costs = {
        name: (be.topk_cost_ns(n, k, batch, dtype, run_len=rl,
                               consts=consts, interpreted=interp)
               if k is not None
               else be.cost_ns(n, batch, dtype, run_len=rl, consts=consts,
                               interpreted=interp))
        for name, be in candidates.items()
    }
    # out-of-core routing is by *feasibility*, not price: key bytes beyond
    # the active profile's spill threshold do not fit the device backends'
    # working set (input + runs + merge ping-pong), so the spill tier is
    # the only honest plan above it and never a candidate below it.
    # Top-k stays on the device paths (a dataset-scale top-k wants
    # per-chunk selection + candidate merge — ROADMAP follow-through).
    itemsize = jnp.dtype(dtype).itemsize
    oversized = (k is None
                 and n * batch * itemsize > prof.spill_threshold_bytes
                 and sortspec.get_backend("spill").eligible(n, dtype, rl))
    if k is None and (oversized or requested == "spill"):
        costs["spill"] = cost_model.spill_sort_cost_ns(
            n, batch, itemsize, consts=consts)
    if requested == "auto":
        if oversized:
            method = "spill"
        else:
            def _valid(name: str) -> bool:
                caps = candidates[name].capabilities
                if not candidates[name].eligible(n, dtype, rl):
                    return False
                # selection switch-over: below the tuned floor the
                # O(n·passes) counting constant never beats a tiny sort,
                # and the modeled crossover is noisy at small n — auto
                # skips selection engines there (explicit
                # requested="select" is still honoured)
                if k is not None and caps.selection and n < prof.select_min_n:
                    return False
                # sort plans need a sorter; top-k plans need a topk path
                return caps.supports_topk if k is not None \
                    else caps.supports_sort
            valid = [m for m in costs if _valid(m)]
            method = min(valid, key=costs.__getitem__)
    else:
        method = requested
    run_method = "pallas" if (on_tpu() and _eligible("pallas", rl, dtype, rl)) \
        else "xla"
    merge_backend = "pallas" if on_tpu() else "xla"
    plan = Plan(method=method, run_len=rl, run_method=run_method,
                merge_backend=merge_backend, costs=costs)
    _record_decision(plan, n=n, batch=batch, dtype=dtype, requested=requested,
                     k=k)
    return plan


def _record_decision(plan: Plan, *, n: int, batch: int, dtype,
                     requested: str, k: Optional[int]) -> None:
    """One structured event per resolved plan (cache misses only — hits
    never reach ``choose``).  No-op unless observability is enabled."""
    if not _obs.enabled():
        return
    _obs.record_event(
        "plan_decision", n=n, batch=batch, dtype=jnp.dtype(dtype).name,
        requested=requested, k=k, method=plan.method,
        predicted_ns=plan.costs.get(plan.method),
        costs={m: c for m, c in plan.costs.items()},
        run_len=plan.run_len, backend=jax.default_backend())
    _metrics.counter("planner.decisions").inc()


def choose_method(n: int, batch: int = 1, dtype=jnp.float32) -> str:
    """Just the backend name — what the public "auto" resolves to."""
    return choose(n, batch, dtype).method


# ---------------------------------------------------------------------------
# relational dispatch — which sorting backend carries each relational op
# ---------------------------------------------------------------------------

def choose_relational(op: str, n: int, batch: int = 1, dtype=jnp.float32, *,
                      requested: str = "auto") -> Plan:
    """Resolve the sort backbone for a relational op (repro.relational).

    Prices every auto-dispatchable sort backend with
    ``cost_model.relational_cost_ns``.  Order-sensitive ops (join's
    duplicate-pair order, group-by's arrival-order aggregation,
    group_ranks) run the engine's *stable* pipeline: a non-stable backend
    would be silently substituted by the forced-stable merge fallback at
    execution time (``engine.argsort``/``sort_kv`` with ``stable=True``),
    so the planner prices those candidates at that fallback — the honest
    cost of actually picking them — instead of their raw sort cost.
    """
    from repro.core import keycodec
    from repro.relational.relspec import SORT_OPS, STABLE_OPS
    if op not in SORT_OPS:
        raise ValueError(
            f"choose_relational plans the sort-backed ops "
            f"{tuple(sorted(SORT_OPS))}, got {op!r}")
    prof = _tuning.active()
    rl = prof.run_len
    consts = prof.constants
    interp = not on_tpu()
    kb = keycodec.key_bits(dtype) if keycodec.supports(dtype) else 32
    candidates = {name: be for name, be in _auto_candidates().items()
                  if be.capabilities.supports_sort}
    costs: Dict[str, float] = {}
    for name, be in candidates.items():
        effective = name
        if op in STABLE_OPS and not be.capabilities.stable \
                and name != "merge":
            effective = "merge"
        try:
            costs[name] = cost_model.relational_cost_ns(
                op, effective, n, batch, run_len=rl, key_bits=kb,
                consts=consts, pallas_interpreted=interp)
        except ValueError:
            costs[name] = float("inf")   # unknown backend: never auto-picked
    if requested == "auto":
        valid = [m for m in costs
                 if candidates[m].eligible(n, dtype, rl)
                 and costs[m] != float("inf")]
        method = min(valid, key=costs.__getitem__)
    else:
        method = requested
    run_method = "pallas" if (on_tpu() and _eligible("pallas", rl, dtype, rl)) \
        else "xla"
    plan = Plan(method=method, run_len=rl, run_method=run_method,
                merge_backend="pallas" if on_tpu() else "xla", costs=costs)
    if _obs.enabled():
        _obs.record_event(
            "relational_plan_decision", op=op, n=n, batch=batch,
            dtype=jnp.dtype(dtype).name, requested=requested,
            method=plan.method, predicted_ns=plan.costs.get(plan.method),
            costs=dict(plan.costs), backend=jax.default_backend())
        _metrics.counter("planner.relational_decisions").inc()
    return plan


def choose_relational_cached(op: str, n: int, batch: int = 1,
                             dtype=jnp.float32, *,
                             requested: str = "auto") -> Plan:
    """``choose_relational`` memoized in the shared plan cache — same
    invalidation rules (calibration generation, registry generation)."""
    with _obs.trace("planner.choose") as sp:
        key = ("rel", op, n, batch, jnp.dtype(dtype).name, requested,
               _tuning.generation(), sortspec.registry_generation(),
               jax.default_backend())
        plan = _cached(sp, key)
        if plan is None:
            plan = _PLAN_CACHE[key] = choose_relational(
                op, n, batch, dtype, requested=requested)
    return plan


# ---------------------------------------------------------------------------
# distributed dispatch — sample-sort vs odd-even
# ---------------------------------------------------------------------------

DIST_STRATEGIES = ("sample", "oddeven")


@dataclasses.dataclass(frozen=True)
class DistPlan:
    """Dispatch decision for a mesh-global sort of n over n_dev devices."""
    strategy: str                # "sample" | "oddeven"
    n_dev: int
    costs: Dict[str, float]      # estimated ns per strategy


def choose_distributed(n: int, n_dev: int, dtype=jnp.float32) -> DistPlan:
    """Price the distributed strategies and return the cheapest one.

    Odd-even transposition pays D collective launches but only a bitonic
    merge box per round; sample-sort pays two capacity-padded all-to-alls
    plus one merge-path tree.  Small (n, D) therefore stays on odd-even
    and large workloads cross over to the single-round exchange — the
    mesh-level mirror of the engine's run-length crossover.
    """
    itemsize = jnp.dtype(dtype).itemsize
    consts = constants()
    costs = {
        s: cost_model.distributed_sort_cost_ns(s, n, n_dev, itemsize,
                                               consts=consts)
        for s in DIST_STRATEGIES
    }
    return DistPlan(strategy=min(costs, key=costs.__getitem__),
                    n_dev=n_dev, costs=costs)


def choose_distributed_cached(n: int, n_dev: int,
                              dtype=jnp.float32) -> DistPlan:
    """``choose_distributed`` memoized alongside the single-device plans —
    same invalidation rules (calibration state, registry generation)."""
    with _obs.trace("planner.choose") as sp:
        key = ("dist", n, n_dev, jnp.dtype(dtype).name,
               _tuning.generation(), sortspec.registry_generation(),
               jax.default_backend())
        plan = _cached(sp, key)
        if plan is None:
            plan = _PLAN_CACHE[key] = choose_distributed(n, n_dev, dtype)
    return plan


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------

_PLAN_CACHE: Dict[tuple, Plan] = {}


def _cached(sp, key: tuple):
    """The plan cached under ``key`` or None; marks the ``planner.choose``
    span ``sp`` with ``hit`` and counts hits."""
    plan = _PLAN_CACHE.get(key)
    sp.set(hit=plan is not None)
    if plan is not None and _obs.enabled():
        _metrics.counter("planner.plan_cache_hits").inc()
    return plan


def choose_cached(n: int, batch: int = 1, dtype=jnp.float32, *,
                  requested: str = "auto",
                  run_len: Optional[int] = None,
                  k: Optional[int] = None) -> Plan:
    """``choose`` memoized on the workload statics (``k`` included — a
    top-k plan and a sort plan for the same row shape differ).

    Serving paths hit the same (shape, dtype, spec) combination every step;
    this skips re-pricing entirely.  The cache key folds in the calibration
    state and the registry generation, so ``calibrate()`` or registering a
    new backend transparently re-plans.
    """
    with _obs.trace("planner.choose") as sp:
        key = (n, batch, jnp.dtype(dtype).name, requested, run_len, k,
               _tuning.generation(), sortspec.registry_generation(),
               jax.default_backend())
        plan = _cached(sp, key)
        if plan is None:
            plan = _PLAN_CACHE[key] = choose(
                n, batch, dtype, requested=requested, run_len=run_len, k=k)
    return plan


def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()


# ---------------------------------------------------------------------------
# autotuner: probe every registered backend, sweep the parameter space,
# fit the constants, persist the winning profile
# ---------------------------------------------------------------------------

def _time_ns(fn, reps: int = 3) -> float:
    fn()  # compile / warm up
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e9


def _probe_registered(x, sel_k: int, reps: int,
                      include_pallas: bool) -> Dict[str, float]:
    """One warm sort (and top-k, where supported) probe per *registered*
    auto-dispatchable backend at the calibration shape -> {probe: ns}.

    This is the raw measurement table a persisted profile carries
    (``TuningProfile.probe_ns``): third-party backends registered via
    ``@register_backend`` are probed too, so the profile stays an audit
    of the whole registry, not just the built-in constant fit.
    """
    n = x.shape[-1]
    vmem_only = () if include_pallas else ("pallas", "radix")
    table: Dict[str, float] = {}
    for name, be in sortspec.registered_backends().items():
        caps = be.capabilities
        if not caps.auto_dispatch or name in vmem_only:
            continue
        try:
            if caps.supports_sort:
                f = jax.jit(lambda v, b=be: b.sort(v))
                table[f"{name}.sort.n{n}"] = _time_ns(
                    lambda: jax.block_until_ready(f(x)), reps)
            if caps.supports_topk and sel_k <= n:
                f = jax.jit(lambda v, b=be: b.topk(v, sel_k)[0])
                table[f"{name}.topk.n{n}.k{sel_k}"] = _time_ns(
                    lambda: jax.block_until_ready(f(x)), reps)
        except Exception:
            # a broken third-party backend must not sink the whole
            # calibration; one of this repo's must not drop out of the
            # profile unseen
            if type(be).__module__.startswith("repro."):
                raise
            continue
    return table


def _sweep_digit_bits(x, reps: int) -> Tuple[int, Dict[str, float]]:
    """Time the LSD radix kernel at each candidate digit width and return
    the fastest.  Wider digits mean fewer passes but a (1 << digit_bits)
    times larger one-hot histogram tensor per tile — the classic radix
    trade the paper makes at the CAS level with its bit-serial W."""
    from repro.core import keycodec
    from repro.kernels import radix_sort as _rs
    enc = keycodec.encode(x, descending=False)
    table: Dict[str, float] = {}
    for db in (4, 8):
        f = jax.jit(lambda v, d=db: _rs.sort_blocks(v, digit_bits=d))
        table[f"digit_bits={db}"] = _time_ns(
            lambda: jax.block_until_ready(f(enc)), reps)
    best = min((4, 8), key=lambda d: table[f"digit_bits={d}"])
    return best, table


def _sweep_radix_tile(x, digit_bits: int, reps: int
                      ) -> Tuple[int, Dict[str, float]]:
    """Time the LSD radix kernel at each candidate histogram tile and
    return the fastest.  Bigger tiles amortise grid launch overhead but
    grow the per-tile one-hot histogram tensor (tile x (1 << digit_bits))
    a VMEM partition has to hold — the same partition-size trade §II-B
    makes when it splits the macro into N/2 CAS blocks."""
    from repro.core import keycodec
    from repro.kernels import radix_sort as _rs
    enc = keycodec.encode(x, descending=False)
    grid = tuple(t for t in (128, 256, 512) if t <= enc.shape[-1])
    if not grid:
        return _tuning.DEFAULT_RADIX_TILE, {}
    table: Dict[str, float] = {}
    for t in grid:
        f = jax.jit(lambda v, t=t: _rs.sort_blocks(
            v, tile=t, digit_bits=digit_bits))
        table[f"radix_tile={t}"] = _time_ns(
            lambda: jax.block_until_ready(f(enc)), reps)
    best = min(grid, key=lambda t: table[f"radix_tile={t}"])
    return best, table


def _sweep_merge_fanin(tile_n: int, reps: int
                       ) -> Tuple[int, Dict[str, float]]:
    """Time the spill tier's grouped merge tournament at each candidate
    width over 16 chunk-sized runs and return the fastest.

    A wide tournament merges everything in one round but pads every run
    to a power-of-two level count; narrow rounds launch more merges and
    move the data log_f(R) times.  The crossover is a device property
    (launch overhead vs bandwidth), so it is measured here and consumed
    by ``spill._merge_phase`` via the profile's ``merge_fanin``."""
    import numpy as np
    from repro.engine import merge as _merge
    rng = np.random.default_rng(3)
    n_runs = 16
    runs = [jnp.asarray(np.sort(rng.standard_normal(tile_n)
                                .astype(np.float32)))
            for _ in range(n_runs)]
    vals = [jnp.arange(tile_n, dtype=jnp.int32) for _ in range(n_runs)]
    from repro.engine.spill import _grouped_kway_kv
    table: Dict[str, float] = {}
    grid = (2, 4, 8, 16)
    for fanin in grid:
        def run(f=fanin):
            mk, mv = _grouped_kway_kv(list(runs), list(vals), f,
                                      descending=False, interpret=None)
            jax.block_until_ready((mk, mv))
        table[f"merge_fanin={fanin}"] = _time_ns(run, reps)
    best = min(grid, key=lambda f: table[f"merge_fanin={f}"])
    return best, table


def _sweep_run_len(tile_n: int, batch: int, reps: int
                   ) -> Tuple[Optional[int], Dict[str, float]]:
    """Time the full engine pipeline (run generation + merge tree) over a
    run-length grid at an 8-tile probe size and return the fastest.

    Longer runs trade cheap vectorised tile-sort work for fewer
    gather-bound merge levels; the crossover is a property of the
    substrate (the reason the old hardcoded TPU/CPU split existed) and
    this measures it instead of guessing it."""
    import numpy as np
    from repro.engine import merge as _merge
    n_probe = 8 * tile_n
    rows = max(1, batch // 8)
    v = jnp.asarray(
        np.random.default_rng(1).standard_normal((rows, n_probe)),
        jnp.float32)
    grid = sorted({rl for rl in (tile_n // 2, tile_n, 2 * tile_n,
                                 4 * tile_n)
                   if 256 <= rl <= n_probe // 2})
    if not grid:
        return None, {}
    table: Dict[str, float] = {}
    for rl in grid:
        f = jax.jit(lambda w, r=rl: _merge.merge_runs(
            _runs.generate_runs(w, r, method="xla"), backend="xla"))
        table[f"run_len={rl}"] = _time_ns(
            lambda: jax.block_until_ready(f(v)), reps)
    best = min(grid, key=lambda r: table[f"run_len={r}"])
    return best, table


def _sweep_capacity_slack(reps: int) -> Tuple[Optional[float],
                                              Dict[str, float]]:
    """Time the distributed sample-sort at each candidate bucket-capacity
    slack (multi-device only — with one device there is no exchange to
    size).  Slack > 1 pads the measured bucket maximum so near-identical
    workloads reuse one compiled phase-2 program; the sweep measures
    whether the larger exchange buys back its cost in recompiles."""
    import numpy as np
    devs = jax.devices()
    if len(devs) < 2:
        return None, {}
    from repro.engine.samplesort import sample_sort
    from repro.launch.mesh import make_host_mesh
    n_dev = len(devs)
    mesh = make_host_mesh()
    x = jnp.asarray(np.random.default_rng(2).standard_normal(1024 * n_dev),
                    jnp.float32)
    table: Dict[str, float] = {}
    for slack in (1.0, 1.25, 1.5):
        table[f"capacity_slack={slack}"] = _time_ns(
            lambda s=slack: jax.block_until_ready(
                sample_sort(x, mesh, "data", capacity_slack=s)), reps)
    best = min(table, key=table.__getitem__)
    return float(best.split("=")[1]), table


def _fit_select_min_n(consts: cost_model.DeviceSortConstants,
                      digit_bits: int, tile: int) -> int:
    """Analytic switch-over: the smallest power-of-two n at which the
    *measured* selection constant beats the cheapest non-selection top-k
    path (k=64, f32).  Below it, auto never dispatches a selection
    engine — the counting passes cannot amortise."""
    k = 64
    for exp in range(6, 21):
        n = 1 << exp
        if n <= k:
            continue
        sel = cost_model.selection_cost_ns(
            n, k, 32, consts=consts, digit_bits=digit_bits, tile=tile)
        alt = cost_model.device_sort_cost_ns("xla", n, consts=consts)
        if not on_tpu():
            alt = min(alt, cost_model.xla_topk_cost_ns(n, k, consts=consts))
        if sel < alt:
            return n
    return _tuning.DEFAULT_SELECT_MIN_N


def calibrate(tile_n: int = 2048, batch: int = 64, reps: int = 3, *,
              include_pallas: Optional[bool] = None,
              sweep_params: bool = True,
              persist: bool = False,
              path=None) -> _tuning.TuningProfile:
    """Autotune this device: probe every registered backend, sweep the
    kernel parameter space, fit the per-element constants, install (and
    optionally persist) the winning :class:`~repro.core.tuning.TuningProfile`.

    Stages:

      1. **probe** — one warm timing per registered auto-dispatchable
         backend (sort + top-k) at the calibration shape; the raw table
         rides the profile as ``probe_ns``.
      2. **fit** — rescale the analytic leading constants (xla, bitonic,
         merge, radix, select, native top-k) to the measurements, exactly
         the closed-form inversion the paper does from Table I/II to ns.
      3. **sweep** (``sweep_params=True``) — measure the discrete knobs:
         radix ``digit_bits`` in {4, 8} and the histogram ``radix_tile``
         in {128, 256, 512} (kernel paths only), the engine ``run_len``
         grid, the spill tier's ``merge_fanin`` tournament width in
         {2, 4, 8, 16}, and the sample-sort ``capacity_slack`` (multi-
         device only); fit the selection switch-over from the measured
         constants.  Every sweep's raw timing table rides the profile's
         ``sweeps`` audit dict.
      4. **install** — ``tuning.set_active`` swaps the profile in (every
         cached plan dies via the generation counter); ``persist=True``
         writes the schema-versioned JSON (``path`` or the profile cache)
         so the *next* process starts from measurements, not guesses.

    The Pallas probes (whole-array bitonic AND the radix kernel) only run
    on a real TPU by default: interpret-mode timings say nothing about
    kernel speed (the analytic constant plus the interpret penalty already
    prices those paths) and a single interpreted tile sort can take minutes
    on CPU.
    """
    import numpy as np
    from repro.engine import merge as _merge
    if include_pallas is None:
        include_pallas = on_tpu()
    be = sortspec.get_backend
    x = jnp.asarray(np.random.default_rng(0).standard_normal((batch, tile_n)),
                    jnp.float32)
    elems = batch * tile_n
    lg = cost_model._log2(tile_n)

    xla_f = jax.jit(lambda v: be("xla").sort(v))
    bit_f = jax.jit(lambda v: be("bitonic").sort(v))
    half = tile_n // 2
    mrg_f = jax.jit(lambda v: _merge.merge_pairs(
        jnp.sort(v[:, :half]), jnp.sort(v[:, half:]), backend="xla"))

    xla_ns = _time_ns(lambda: xla_f(x).block_until_ready(), reps)
    bit_ns = _time_ns(lambda: bit_f(x).block_until_ready(), reps)
    mrg_ns = _time_ns(lambda: mrg_f(x).block_until_ready(), reps)

    # parameter sweeps run BEFORE the constant fit so the radix/select
    # constants are normalised by the pass count the tuned digit width
    # actually implies
    defaults = _tuning.default_profile()
    digit_bits, tile = defaults.digit_bits, defaults.radix_tile
    run_len, slack = defaults.run_len, defaults.capacity_slack
    merge_fanin = defaults.merge_fanin
    sweeps: Dict[str, Dict[str, float]] = {}
    if sweep_params:
        if include_pallas:
            digit_bits, tbl = _sweep_digit_bits(x, reps)
            sweeps["digit_bits"] = tbl
            tile, tbl = _sweep_radix_tile(x, digit_bits, reps)
            if tbl:
                sweeps["radix_tile"] = tbl
        rl, tbl = _sweep_run_len(tile_n, batch, reps)
        if rl is not None:
            run_len, sweeps["run_len"] = rl, tbl
        merge_fanin, tbl = _sweep_merge_fanin(tile_n, reps)
        sweeps["merge_fanin"] = tbl
        sl, tbl = _sweep_capacity_slack(reps)
        if sl is not None:
            slack, sweeps["capacity_slack"] = sl, tbl

    # selection probe: runs everywhere (off-TPU the select uses its jnp
    # histogram path, so the timing is honest without a real TPU)
    from repro.core import keycodec as _kc
    sel_k = min(64, tile_n)
    sel_f = jax.jit(lambda v: be("select").topk(v, sel_k)[0])
    sel_ns = _time_ns(lambda: sel_f(x).block_until_ready(), reps)
    sel_passes = -(-_kc.key_bits(x.dtype) // digit_bits)
    # strip the modeled O(k log k) ordering term with the constant this
    # same calibration will price it at (the measured xla one, not the
    # default — selection_cost_ns re-adds the term using the measured
    # constants); floor at 10% of the measurement so a noisy probe can
    # never produce a free selection
    sel_kterm = (xla_ns / (elems * lg)) * batch \
        * sel_k * cost_model._log2(sel_k)
    sel_c = max(sel_ns - sel_kterm, 0.1 * sel_ns) / (elems * sel_passes)

    # native top-k probe (same shapes): off-TPU this is XLA:CPU's tuned
    # selection and the measured constant keeps the k-aware plan honest;
    # on TPU the xla backend prices top-k at sort-prefix, so the probe is
    # only bookkeeping there (same 10% floor logic as the select probe)
    xtk_f = jax.jit(lambda v: be("xla").topk(v, sel_k)[0])
    xtk_ns = _time_ns(lambda: xtk_f(x).block_until_ready(), reps)
    xtk_c = max(xtk_ns - sel_kterm, 0.1 * xtk_ns) / elems

    dc = defaults.constants
    pal_c, rad_c = dc.pallas, dc.radix
    if include_pallas:
        from repro.core import keycodec
        from repro.kernels import radix_sort as _rs
        pal_f = jax.jit(lambda v: be("pallas").sort(v))
        pal_ns = _time_ns(lambda: pal_f(x).block_until_ready(), reps)
        pal_c = pal_ns / (elems * lg * lg)
        rad_f = jax.jit(lambda v: _rs.sort_blocks(
            keycodec.encode(v, descending=False), digit_bits=digit_bits))
        rad_ns = _time_ns(lambda: rad_f(x).block_until_ready(), reps)
        passes = -(-keycodec.key_bits(x.dtype) // digit_bits)
        rad_c = rad_ns / (elems * passes)
        if not on_tpu():  # fold into (constant x penalty) form
            pal_c /= dc.pallas_interpret_penalty
            rad_c /= dc.pallas_interpret_penalty
    consts = cost_model.DeviceSortConstants(
        xla=xla_ns / (elems * lg),
        bitonic=bit_ns / (elems * lg * lg),
        pallas=pal_c,
        radix=rad_c,
        select=sel_c,
        xla_topk=xtk_c,
        merge_run=xla_ns / (elems * lg),
        merge_level=mrg_ns / elems,
    )
    select_min_n = _fit_select_min_n(consts, digit_bits, tile) \
        if sweep_params else defaults.select_min_n

    probe_ns = _probe_registered(x, sel_k, reps, include_pallas)
    probe_ns.update({"xla.merge_pairs": mrg_ns})

    profile = _tuning.TuningProfile(
        fingerprint=_tuning.device_fingerprint(),
        constants=consts,
        digit_bits=digit_bits,
        radix_tile=tile,
        run_len=run_len,
        capacity_slack=slack,
        select_min_n=select_min_n,
        merge_fanin=merge_fanin,
        source="calibrated",
        probe_ns=probe_ns,
        sweeps=sweeps or None,
    )
    if persist:
        _tuning.save(profile, path)
    _tuning.set_active(profile)
    clear_plan_cache()
    return profile


def reset_calibration() -> None:
    """Back to the built-in per-platform defaults (and re-plan): the
    inverse of ``calibrate``, ignoring any persisted profile."""
    _tuning.set_active(_tuning.default_profile())
    clear_plan_cache()
