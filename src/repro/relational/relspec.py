"""RelSpec — the one front-door contract for every relational op.

Mirror of :class:`repro.core.sortspec.SortSpec` one workload class up: a
relational problem (dedup, group-by, join, run-length/delta encoding,
histogram/quantile sketch) is a single frozen :class:`RelSpec` value, and
``canonical()`` is the ONE place every front-door error is raised — op
combinations, dtype support, aggregate names, mesh constraints — never deep
inside an op kernel.

The hardware-sorting survey (Jalilvand et al., PAPERS.md) treats these ops
as first-class applications of a sorter; the spec layer keeps that framing
honest: every op here is a sort (or a radix selection) plus an O(n)
post-pass, and ``method`` names the *sorting backend* the op rides —
``"auto"`` resolves through ``planner.choose_relational`` with the new
``cost_model.relational_cost_ns`` entries.

Static-shape contract (the jax constraint every op shares): results whose
true size is data-dependent (unique values, groups, join pairs, runs) come
back as fixed-size padded arrays plus a valid count, exactly like
``jnp.unique(size=..., fill_value=...)``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple, Union

import jax.numpy as jnp

__all__ = ["RelSpec", "OPS", "AGGS", "SORT_OPS", "STABLE_OPS", "SKETCH_OPS"]

# every relational op the subsystem executes
OPS = ("unique", "group_by", "join", "rle", "delta", "histogram",
       "quantile", "group_ranks")

# ops whose backbone is a full sort (planner-priced backend choice);
# sketches ride the radix-select / searchsorted machinery instead and
# accept no per-op backend override
SORT_OPS = frozenset({"unique", "group_by", "join", "rle", "delta"})
SKETCH_OPS = frozenset({"histogram", "quantile"})

# ops that need a *stable* order pipeline (duplicate-pair order for join,
# deterministic within-group aggregation order and arrival ranks): the
# planner prices non-stable backends at the forced-stable merge fallback
# the engine would actually run
STABLE_OPS = frozenset({"group_by", "join", "group_ranks"})

# group-by reductions (mean is sum/count in float32 — the documented
# reference semantics, see README "Relational kernels")
AGGS = ("sum", "min", "max", "count", "mean")

# ops that compose over a device mesh: after the sample-sort splitter
# round equal keys are co-located, so the local post-pass IS the global op
MESH_OPS = frozenset({"unique", "group_by"})


@dataclasses.dataclass(frozen=True, eq=False)
class RelSpec:
    """One relational problem.  Field groups:

      op                      which relational kernel
      agg                     group_by reductions (name or tuple of names)
      return_inverse/counts   unique extras (np.unique-style)
      size                    join output capacity (static; default n_l*n_r)
      fill_value              what pads invalid tail slots (op-specific
                              default when None — see each op's docstring)
      assume_sorted           rle/delta: input is already sorted, skip the
                              sort (the ops encode *sorted columns*)
      num_bins / lo / hi      histogram shape
      qs                      quantile fractions in [0, 1]
      num_groups              group_ranks key domain (0 <= key < num_groups)
      mesh / axis_name        distributed variant (unique/group_by only)
      method / interpret      sorting-backend knobs (None -> "auto")

    ``eq=False`` keeps the spec hashable by identity (mesh objects ride
    along); planner caching keys on the statics it derives from the spec.
    """
    op: str = "unique"
    agg: Union[str, Tuple[str, ...]] = ("sum",)
    return_inverse: bool = False
    return_counts: bool = False
    size: Optional[int] = None
    fill_value: Any = None
    assume_sorted: bool = False
    num_bins: Optional[int] = None
    lo: Any = None
    hi: Any = None
    qs: Optional[Tuple[float, ...]] = None
    num_groups: Optional[int] = None
    mesh: Any = None
    axis_name: Optional[str] = None
    method: Optional[str] = None
    interpret: Optional[bool] = None

    # -- validation + canonicalization (the one place it happens) -----------
    def canonical(self, x: jnp.ndarray,
                  values: Optional[jnp.ndarray] = None) -> "RelSpec":
        from repro.core import keycodec
        from repro.core.sortspec import backend_names
        if self.op not in OPS:
            raise ValueError(f"op must be one of {OPS}, got {self.op!r}")
        op = self.op

        # ---- shape: every column op is 1-D; group_ranks allows batch dims
        if op == "group_ranks":
            if x.ndim < 1:
                raise ValueError("group_ranks expects (..., n) keys")
            if self.num_groups is None or int(self.num_groups) < 1:
                raise ValueError(
                    f"group_ranks needs num_groups >= 1, "
                    f"got {self.num_groups}")
            if not jnp.issubdtype(x.dtype, jnp.integer):
                raise ValueError(
                    f"group_ranks keys must be integers, got {x.dtype}")
        elif x.ndim != 1:
            raise ValueError(
                f"relational op {op!r} works on flat 1-D columns; "
                f"got a {x.ndim}-d input")

        # ---- method: a registered sorting backend or auto; sketches ride
        # the selection / searchsorted machinery and take no override
        method = self.method if self.method is not None else "auto"
        if op in SKETCH_OPS:
            if method != "auto":
                raise ValueError(
                    f"{op} rides the radix-select backend; method must be "
                    f"'auto', got {method!r}")
        else:
            names = backend_names() + ("auto",)
            if method not in names:
                raise ValueError(
                    f"method must be one of {names}, got {method!r}")

        # ---- mesh: only the ops where local op == global op compose
        axis_name = self.axis_name
        if axis_name is not None and self.mesh is None:
            raise ValueError("axis_name requires a mesh")
        if self.mesh is not None:
            if op not in MESH_OPS:
                raise ValueError(
                    f"distributed relational variants exist for "
                    f"{tuple(sorted(MESH_OPS))}; op {op!r} has none")
            # one axis, a tuple of axes, or None -> the whole mesh —
            # normalised by the shared helper so the relational mesh ops
            # accept exactly what distributed_sort does
            from repro.engine.samplesort import _axes_tuple
            axis_name = _axes_tuple(self.mesh, axis_name)
            if method not in ("auto", "distributed"):
                raise ValueError(
                    "mesh-distributed relational ops run the 'distributed' "
                    f"sort; method must be 'auto' or 'distributed', "
                    f"got {method!r}")
            if not keycodec.supports(x.dtype):
                raise ValueError(
                    f"distributed {op} needs a keycodec dtype "
                    f"({keycodec.SUPPORTED}), got {x.dtype}")

        # ---- per-op field combos
        if (self.return_inverse or self.return_counts) and op != "unique":
            raise ValueError(
                "return_inverse/return_counts are unique-only fields")
        if self.size is not None:
            if op != "join":
                raise ValueError("size is a join-only field (static output "
                                 "capacity for the expanded pairs)")
            if int(self.size) < 1:
                raise ValueError(f"join size must be >= 1, got {self.size}")
        if self.assume_sorted and op not in ("rle", "delta"):
            raise ValueError("assume_sorted applies to the sorted-column "
                             "encoders (rle/delta) only")
        if op == "delta" and not jnp.issubdtype(x.dtype, jnp.integer):
            raise ValueError(
                f"delta encoding round-trips exactly for integer columns "
                f"only (modular cumsum); got {x.dtype}")
        if op == "group_by":
            agg = (self.agg,) if isinstance(self.agg, str) else \
                tuple(self.agg)
            if not agg:
                raise ValueError("group_by needs at least one aggregate")
            bad = [a for a in agg if a not in AGGS]
            if bad:
                raise ValueError(
                    f"unknown aggregates {bad}; supported: {AGGS}")
            if values is None:
                raise ValueError("group_by needs a values column")
            if values.shape != x.shape:
                raise ValueError(
                    f"group_by values shape {values.shape} must match "
                    f"keys shape {x.shape}")
        else:
            agg = self.agg if isinstance(self.agg, tuple) else (self.agg,)
        if op == "join":
            if values is None:
                raise ValueError("join needs a right key column")
            if values.ndim != 1:
                raise ValueError(
                    f"join keys are flat 1-D columns; right side is "
                    f"{values.ndim}-d")
            if values.dtype != x.dtype:
                raise ValueError(
                    f"join key dtypes must match: left {x.dtype}, "
                    f"right {values.dtype}")
        if op == "histogram":
            if self.num_bins is None or int(self.num_bins) < 1:
                raise ValueError(
                    f"histogram needs num_bins >= 1, got {self.num_bins}")
        elif self.num_bins is not None:
            raise ValueError("num_bins is a histogram-only field")
        qs = self.qs
        if op == "quantile":
            if qs is None:
                raise ValueError("quantile needs qs (fractions in [0, 1])")
            qs = (qs,) if isinstance(qs, float) else tuple(float(q)
                                                           for q in qs)
            if not qs or any(not 0.0 <= q <= 1.0 for q in qs):
                raise ValueError(
                    f"quantile fractions must lie in [0, 1], got {qs}")
            if not keycodec.supports(x.dtype):
                raise ValueError(
                    f"quantile sketches ride the radix-select backend and "
                    f"need a keycodec dtype ({keycodec.SUPPORTED}), "
                    f"got {x.dtype}")
            if x.shape[0] == 0:
                raise ValueError("quantiles of an empty column are "
                                 "undefined")
        elif qs is not None:
            raise ValueError("qs is a quantile-only field")

        return dataclasses.replace(
            self, op=op, agg=agg, method=method, axis_name=axis_name,
            qs=qs, size=None if self.size is None else int(self.size),
            num_bins=None if self.num_bins is None else int(self.num_bins),
            num_groups=None if self.num_groups is None
            else int(self.num_groups))

    def static_key(self, shape, dtype) -> tuple:
        """Hashable reduction to the statics an external cache may key on
        (mirrors ``SortSpec.static_key``)."""
        mesh_key = None if self.mesh is None else (
            tuple(zip(self.mesh.axis_names, self.mesh.devices.shape)),
            tuple(d.id for d in self.mesh.devices.flat))
        return (self.op, self.agg, self.return_inverse, self.return_counts,
                self.size, self.fill_value, self.assume_sorted,
                self.num_bins, self.lo, self.hi, self.qs, self.num_groups,
                mesh_key, self.axis_name, self.method, self.interpret,
                tuple(shape), jnp.dtype(dtype).name)
