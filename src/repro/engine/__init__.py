"""repro.engine — out-of-core sort engine: tiled runs and a merge tree.

Completes the memory hierarchy between one VMEM tile (kernels/bitonic_sort)
and the device mesh (engine/samplesort, core/distributed_sort):

    SRAM array  ->  VMEM tile  ->  engine runs + merge tree  ->  mesh shards

``sort`` / ``argsort`` / ``topk`` / ``sort_kv`` here accept any array size:
tiled run generation (runs.py) sorts VMEM-sized pieces with a registered
backend, a merge-path merge tree (merge.py, kernels/merge_path.py) combines
them in O(n log n) total work, and the cost-model planner (planner.py)
decides when the hierarchy pays for itself versus handing the whole array
to one backend.  The engine is the *execution* layer under the SortSpec
front door (repro.sort): plans come from ``planner.choose_cached`` and
single-backend work is delegated through the registry
(core/sortspec.py), never by backend name.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import sortspec
from repro.engine import merge as merge  # noqa: F401  (re-export)
from repro.engine import planner, runs
from repro.engine.merge import kway_merge, merge_pairs, merge_runs  # noqa: F401
from repro.engine.planner import (  # noqa: F401
    DistPlan, Plan, calibrate, choose, choose_cached, choose_distributed,
    choose_distributed_cached, choose_method, clear_plan_cache)
from repro.engine.samplesort import sample_sort  # noqa: F401
from repro.engine.segmented import (  # noqa: F401
    group_tokens_by_expert, segment_ids_from_row_splits, segmented_argsort,
    segmented_sort, sort_padded_rows)


# the same axis-flattening helpers the kernel entry points use
from repro.kernels.ops import _from_rows, _to_rows
from repro.obs import trace as _obs


def _obs_finish(sp, op: str, plan: planner.Plan, n: int, batch: int,
                k: Optional[int] = None) -> None:
    """Pair a fenced span with its plan: record the predicted-vs-measured
    ``cost_observation`` event and the ``cost_model_error`` ratio metric.

    The 313ms-vs-3.4ms top-k inversion class of bug surfaces here as a
    two-orders-of-magnitude error ratio instead of hiding in a CSV.  No-op
    unless the span was fenced (the autotune loop armed with
    ``REPRO_AUTOTUNE=1``, its only reader, and no outer jit); records only
    with observability on.  The first
    call at a new shape includes compile time — cold and warm observations
    both land in the histogram, like the bench's cold/warm split.
    """
    if sp.device_ms is None:
        return
    predicted = plan.costs.get(plan.method)
    if not predicted or predicted != predicted or predicted == float("inf"):
        return
    measured_ns = sp.device_ms * 1e6
    error = measured_ns / predicted
    _obs.record_event("cost_observation", op=op, n=n, batch=batch, k=k,
                      method=plan.method, predicted_ns=predicted,
                      measured_ns=measured_ns, error=error)
    from repro.obs import metrics as _metrics
    _metrics.histogram("planner.cost_model_error").observe(error)
    # closed-loop autotuning (opt-in, REPRO_AUTOTUNE=1): when the error
    # histogram says the active constants have drifted off this device,
    # re-probe and swap in a fresh profile — see tuning.refresh_if_stale
    from repro.core import tuning as _tuning
    _tuning.maybe_refresh()


def _spill_fallback(plan: planner.Plan, x2) -> planner.Plan:
    """The spill tier is host-driven (blocking D2H, data-dependent merge
    cursors) and cannot run under an outer ``jit``: for tracer inputs a
    spill plan degrades to the on-device merge pipeline — the best plan
    that *can* execute in the trace, at the caller's own memory risk."""
    if plan.method == "spill" and isinstance(x2, jax.core.Tracer):
        return dataclasses.replace(plan, method="merge")
    return plan


# ---------------------------------------------------------------------------
# merge pipeline over rows form — what the "merge" backend executes
# ---------------------------------------------------------------------------

def merge_sort_rows(x2: jnp.ndarray, *, descending: bool, plan: planner.Plan,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """(rows, n) -> sorted rows via run generation + the merge tree."""
    rg = runs.generate_runs(x2, plan.run_len, method=plan.run_method,
                            descending=descending, interpret=interpret)
    merged = merge_runs(rg, descending=descending,
                        backend=plan.merge_backend, interpret=interpret)
    return merged[:, :x2.shape[-1]]


def merge_sort_rows_kv(k2: jnp.ndarray, v2: jnp.ndarray, *, descending: bool,
                       plan: planner.Plan, stable: bool = False,
                       interpret: Optional[bool] = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Key-value merge pipeline.  ``stable=True`` forces a stable tile sort
    ("xla" run backend) so the whole pipeline is stable (merge-path merges
    are stable by construction)."""
    run_method = "xla" if stable else plan.run_method
    rk, rv = runs.generate_runs_kv(k2, v2, plan.run_len, method=run_method,
                                   descending=descending, interpret=interpret)
    mk, mv = merge_runs(rk, rv, descending=descending,
                        backend=plan.merge_backend, interpret=interpret)
    n = k2.shape[-1]
    return mk[:, :n], mv[:, :n]


# ---------------------------------------------------------------------------
# public entry points (any array size, planner-dispatched)
# ---------------------------------------------------------------------------

def _backend_span(method: str):
    """The ``backend.<method>`` span around the one backend an engine call
    runs ("merge" is the engine's own run + merge-tree pipeline)."""
    return _obs.trace("backend." + method)


def sort(x: jnp.ndarray, *, axis: int = -1, descending: bool = False,
         method: str = "auto", run_len: Optional[int] = None,
         interpret: Optional[bool] = None) -> jnp.ndarray:
    """Sort along ``axis``; sizes beyond one tile go through runs + merges.

    ``method`` is "auto" (cost-model pick), "merge" (force the engine), or
    any registered backend name to delegate to.
    """
    with _obs.trace("engine.sort") as sp:
        x2, lead, ax = _to_rows(x, axis)
        batch, n = x2.shape
        plan = _spill_fallback(
            planner.choose_cached(n, batch, x.dtype, requested=method,
                                  run_len=run_len), x2)
        sp.set(n=n, batch=batch, method=plan.method)
        with _backend_span(plan.method):
            if plan.method == "merge":
                out = merge_sort_rows(x2, descending=descending, plan=plan,
                                      interpret=interpret)
            else:
                out = sortspec.get_backend(plan.method).sort(
                    x2, descending=descending, plan=plan,
                    interpret=interpret)
        sp.fence(out)
        out = _from_rows(out, lead, ax)
    _obs_finish(sp, "sort", plan, n, batch)
    return out


def sort_kv(keys: jnp.ndarray, values: jnp.ndarray, *, axis: int = -1,
            descending: bool = False, method: str = "auto",
            stable: bool = False, run_len: Optional[int] = None,
            interpret: Optional[bool] = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sort ``keys`` along ``axis`` carrying ``values`` with them.

    ``stable=True`` forces the engine's stable pipeline regardless of the
    planner's backend preference — segmented sort and MoE grouping rely on
    equal keys keeping their input order.
    """
    with _obs.trace("engine.sort_kv") as sp:
        k2, lead, ax = _to_rows(keys, axis)
        v2, _, _ = _to_rows(values, axis)
        batch, n = k2.shape
        plan = _spill_fallback(
            planner.choose_cached(n, batch, keys.dtype, requested=method,
                                  run_len=run_len), k2)
        sp.set(n=n, batch=batch, method=plan.method)
        be = None
        if plan.method != "merge":
            be = sortspec.get_backend(plan.method)
            if stable and not be.capabilities.stable:
                be = None
        if be is not None:
            with _backend_span(plan.method):
                sk, sv = be.sort_kv(k2, v2, descending=descending,
                                    plan=plan, interpret=interpret)
        else:
            with _backend_span("merge"):
                sk, sv = merge_sort_rows_kv(k2, v2, descending=descending,
                                            plan=plan, stable=stable,
                                            interpret=interpret)
        sp.fence((sk, sv))
        out = _from_rows(sk, lead, ax), _from_rows(sv, lead, ax)
    _obs_finish(sp, "sort_kv", plan, n, batch)
    return out


def argsort(x: jnp.ndarray, *, axis: int = -1, descending: bool = False,
            method: str = "auto", stable: bool = False,
            run_len: Optional[int] = None,
            interpret: Optional[bool] = None) -> jnp.ndarray:
    """Sorting permutation along ``axis`` via the key-value engine path.

    ``stable=True`` forces a stable pipeline: a stable backend if the plan
    resolved to one, else stable tile sort + merge-path merges (stable by
    construction), regardless of the planner's preference.
    """
    with _obs.trace("engine.argsort") as sp:
        x2, lead, ax = _to_rows(x, axis)
        batch, n = x2.shape
        plan = _spill_fallback(
            planner.choose_cached(n, batch, x.dtype, requested=method,
                                  run_len=run_len), x2)
        sp.set(n=n, batch=batch, method=plan.method)
        be = None
        if plan.method != "merge":
            be = sortspec.get_backend(plan.method)
            if stable and not be.capabilities.stable:
                be = None
        if be is not None:
            with _backend_span(plan.method):
                order = be.argsort(x2, descending=descending, plan=plan,
                                   interpret=interpret)
        else:
            with _backend_span("merge"):
                idx = jnp.broadcast_to(
                    jnp.arange(n, dtype=jnp.int32)[None, :], x2.shape)
                _, order = merge_sort_rows_kv(x2, idx, descending=descending,
                                              plan=plan, stable=stable,
                                              interpret=interpret)
        sp.fence(order)
        order = _from_rows(order, lead, ax)
    _obs_finish(sp, "argsort", plan, n, batch)
    return order


def topk(x: jnp.ndarray, k: int, *, method: str = "auto",
         run_len: Optional[int] = None,
         interpret: Optional[bool] = None
         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k along the last axis -> (values, indices), descending.

    The plan is k-aware: ``method="auto"`` weighs O(n·passes) radix
    selection (the "select" backend) against sort-prefix on every sort
    backend, so ``k ≪ n`` workloads never pay for a full sort.  Engine
    path: per-run top-k candidates (the paper's partition-then-merge,
    §II-B) followed by a key-value merge tree over the k-prefixes.
    """
    with _obs.trace("engine.topk") as sp:
        x2, lead, _ = _to_rows(x, -1)
        batch, n = x2.shape
        if not 1 <= k <= n:
            raise ValueError(
                f"topk k must satisfy 1 <= k <= n (n={n}); got k={k}")
        plan = planner.choose_cached(n, batch, x.dtype, requested=method,
                                     run_len=run_len, k=k)
        sp.set(n=n, batch=batch, k=k, method=plan.method)
        with _backend_span(plan.method):
            if plan.method != "merge":
                v, i = sortspec.get_backend(plan.method).topk(
                    x2, k, plan=plan, interpret=interpret)
            else:
                idx = jnp.broadcast_to(
                    jnp.arange(n, dtype=jnp.int32)[None, :], x2.shape)
                rk, rv = runs.generate_runs_kv(x2, idx, plan.run_len,
                                               method=plan.run_method,
                                               descending=True,
                                               interpret=interpret)
                # candidate prefixes: only the first k of each run can
                # reach the top k
                kk = runs.next_pow2(min(k, rk.shape[-1]))
                ck, cv = rk[..., :kk], rv[..., :kk]
                mk, mv = merge_runs(ck, cv, descending=True,
                                    backend=plan.merge_backend,
                                    interpret=interpret)
                v, i = mk[:, :k], mv[:, :k]
        sp.fence((v, i))
        out = v.reshape(*lead, k), i.reshape(*lead, k)
    _obs_finish(sp, "topk", plan, n, batch, k)
    return out
