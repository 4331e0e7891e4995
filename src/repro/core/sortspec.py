"""Unified sort problem description + pluggable backend registry.

This module is the system's *one front door contract*: every sort in the
repo — flat, key-value, top-k, segmented/ragged, padded-row — is described
by a single frozen :class:`SortSpec` value, and every engine that can
execute one is a :class:`SortBackend` announcing what it can do through a
declared :class:`Capabilities` record.

The design follows the hardware-sorting survey's framing (sorters are
characterized by declared capabilities — stability, key width, capacity —
not by their call sites) and the PIM-practicality argument that in-memory
engines need a clean host-side abstraction: the planner and the public API
never special-case a backend by name.  ``repro.engine.planner`` asks the
registry which backends are *eligible* for a workload and prices the
survivors; adding a new engine is one ``@register_backend`` class — no
dispatch code changes anywhere.

Layering (no heavy imports here; backends lazy-import their kernels):

    repro.sort          front door: run(spec, x) + sort/argsort/topk/...
    repro.core.sortspec THIS — SortSpec, Capabilities, registry, defaults
    repro.core.backends the six built-in SortBackend implementations
    repro.engine        out-of-core pipeline + cost-model planner
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any, Dict, FrozenSet, Optional, Tuple

import jax.numpy as jnp

__all__ = [
    "Capabilities", "SortSpec", "SortBackend", "register_backend",
    "unregister_backend", "get_backend", "registered_backends",
    "backend_names", "registry_generation", "sort_defaults", "default",
]


def next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


# ---------------------------------------------------------------------------
# capabilities
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Capabilities:
    """What a backend declares it can do.  The planner trusts this record —
    tests/test_sortspec.py sweeps every registered backend and fails CI if a
    claim is untruthful (wrong-dtype sorts, fake stability).

    ``dtypes`` is the set of dtype *names* the backend sorts correctly;
    ``None`` means "any comparable dtype" (the backend is a comparison sort
    with no encoding step).  ``max_n`` caps the power-of-two padded row size
    the *planner* may hand the backend under ``method="auto"`` — explicit
    requests are still honoured beyond it (benchmarks do exactly that).
    ``auto_dispatch=False`` removes the backend from auto dispatch entirely
    (e.g. the cycle-accurate bit-serial simulator).

    ``selection=True`` declares an O(n·passes) top-k *selection* engine
    (partial sort — the survey's min/max-search operating mode): the
    planner prices its top-k specs with ``cost_model.selection_cost_ns``
    instead of the full-sort model.  ``supports_sort=False`` marks a
    selection-only engine: plain sort/argsort specs are rejected at the
    spec layer and the planner never hands it a sort workload.
    """
    dtypes: Optional[FrozenSet[str]] = None
    stable: bool = False
    max_n: Optional[int] = None
    supports_kv: bool = True
    supports_topk: bool = True
    supports_segments: bool = True
    supports_sort: bool = True
    selection: bool = False
    auto_dispatch: bool = True
    substrate: str = "host"        # "host" | "vmem" | "sram" | "hierarchy"


# ---------------------------------------------------------------------------
# backend protocol + registry
# ---------------------------------------------------------------------------

class SortBackend:
    """Base class every sorting engine plugs in through.

    Concrete backends implement ``sort`` (and optionally ``sort_kv`` /
    ``argsort`` / ``topk``) over *rows form*: a 2-D ``(rows, n)`` array,
    sorting along the last axis.  Axis handling, flattening, padding policy
    and spec validation all live above this layer (repro.sort), so a new
    backend is nothing but its kernel call plus a Capabilities record:

        @register_backend
        class SampleSortBackend(SortBackend):
            name = "sample"
            capabilities = Capabilities(stable=False, substrate="vmem")
            def sort(self, rows, *, descending=False, plan=None,
                     interpret=None):
                return my_kernel(rows, descending)
    """

    name: str = "?"
    capabilities: Capabilities = Capabilities()

    # -- planner queries ----------------------------------------------------
    def eligible(self, n: int, dtype, run_len: Optional[int] = None) -> bool:
        """Generic capability query: may ``auto`` hand (n, dtype) to us?"""
        caps = self.capabilities
        if caps.dtypes is not None and jnp.dtype(dtype).name not in caps.dtypes:
            return False
        if caps.max_n is not None and next_pow2(n) > caps.max_n:
            return False
        return True

    def cost_ns(self, n: int, batch: int, dtype, *, run_len: int,
                consts=None, interpreted: bool = False) -> float:
        """Estimated ns for (batch, n); default defers to the analytic cost
        model and prices unknown backends at +inf (never auto-picked until
        they override this or teach the model their asymptotics)."""
        from repro.core import cost_model, keycodec
        kb = keycodec.key_bits(dtype) if keycodec.supports(dtype) else 32
        try:
            return cost_model.device_sort_cost_ns(
                self.name, n, batch, run_len=run_len, consts=consts,
                pallas_interpreted=interpreted, key_bits=kb)
        except ValueError:
            return float("inf")

    def topk_cost_ns(self, n: int, k: int, batch: int, dtype, *, run_len: int,
                     consts=None, interpreted: bool = False) -> float:
        """Estimated ns for a top-k of (batch, n).  Default contracts:
        selection engines (``capabilities.selection``) price the
        O(n·passes) partial-sort model; sort engines price the sort-prefix
        path (full sort, then slice k).  Backends with a genuinely
        different top-k lowering override this — the xla backend prices
        native ``lax.top_k`` off-TPU, which is how the planner's k-aware
        ``auto`` can never again lose to an unpriced native path."""
        from repro.core import cost_model, keycodec
        if self.capabilities.selection:
            kb = keycodec.key_bits(dtype) if keycodec.supports(dtype) else 32
            return cost_model.selection_cost_ns(n, k, kb, batch,
                                                consts=consts)
        return self.cost_ns(n, batch, dtype, run_len=run_len, consts=consts,
                            interpreted=interpreted)

    # -- execution (rows form: (rows, n), last axis) ------------------------
    def sort(self, rows: jnp.ndarray, *, descending: bool = False,
             plan=None, interpret: Optional[bool] = None) -> jnp.ndarray:
        raise NotImplementedError(f"{self.name} backend implements no sort")

    def sort_kv(self, keys: jnp.ndarray, values: jnp.ndarray, *,
                descending: bool = False, plan=None,
                interpret: Optional[bool] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        raise NotImplementedError(
            f"{self.name} backend has no key-value path "
            f"(capabilities.supports_kv={self.capabilities.supports_kv})")

    def argsort(self, rows: jnp.ndarray, *, descending: bool = False,
                plan=None, interpret: Optional[bool] = None) -> jnp.ndarray:
        idx = jnp.broadcast_to(
            jnp.arange(rows.shape[-1], dtype=jnp.int32), rows.shape)
        _, order = self.sort_kv(rows, idx, descending=descending, plan=plan,
                                interpret=interpret)
        return order

    def topk(self, rows: jnp.ndarray, k: int, *, plan=None,
             interpret: Optional[bool] = None
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        idx = jnp.broadcast_to(
            jnp.arange(rows.shape[-1], dtype=jnp.int32), rows.shape)
        sk, sv = self.sort_kv(rows, idx, descending=True, plan=plan,
                              interpret=interpret)
        return sk[..., :k], sv[..., :k]

    # -- shared validation helper -------------------------------------------
    def check_dtype(self, dtype) -> None:
        caps = self.capabilities
        name = jnp.dtype(dtype).name
        if caps.dtypes is not None and name not in caps.dtypes:
            raise ValueError(
                f"{self.name} method supports {tuple(sorted(caps.dtypes))}, "
                f"got {name!r}")


_REGISTRY: Dict[str, SortBackend] = {}
_GENERATION: int = 0


def register_backend(cls):
    """Class decorator: instantiate ``cls`` and register it under
    ``cls.name``.  Re-registering a name replaces the previous backend (so
    notebooks can iterate) and invalidates cached plans."""
    global _GENERATION
    backend = cls() if isinstance(cls, type) else cls
    if not backend.name or backend.name in ("?", "auto"):
        raise ValueError(f"backend needs a usable name, got {backend.name!r}")
    _REGISTRY[backend.name] = backend
    _GENERATION += 1
    return cls


def unregister_backend(name: str) -> None:
    global _GENERATION
    _REGISTRY.pop(name, None)
    _GENERATION += 1


_builtins_loaded = False


def _bootstrap() -> None:
    # flag-gated (not `if not _REGISTRY`): registering a third-party backend
    # before first lookup must not suppress built-in registration
    global _builtins_loaded
    if not _builtins_loaded:
        _builtins_loaded = True
        from repro.core import backends  # noqa: F401  (registers built-ins)


def registered_backends() -> Dict[str, SortBackend]:
    _bootstrap()
    return dict(_REGISTRY)


def backend_names() -> Tuple[str, ...]:
    _bootstrap()
    return tuple(_REGISTRY)


def get_backend(name: str) -> SortBackend:
    _bootstrap()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"method must be one of {backend_names() + ('auto',)}, "
            f"got {name!r}") from None


def registry_generation() -> int:
    """Bumped on every (un)registration — plan caches key on this."""
    return _GENERATION


# ---------------------------------------------------------------------------
# ambient defaults
# ---------------------------------------------------------------------------

_DEFAULT_KEYS = ("method", "run_len", "interpret")
# contextvar (not a module global): a `with sort_defaults(...)` entered on
# one serving thread must not change dispatch for concurrent callers
_DEFAULTS: contextvars.ContextVar[Dict[str, Any]] = contextvars.ContextVar(
    "repro_sort_defaults", default={"method": "auto"})


@contextlib.contextmanager
def sort_defaults(**overrides):
    """Ambient configuration for specs that leave fields unset::

        with sort_defaults(method="merge", run_len=4096):
            repro.sort.sort(x)        # runs the engine with 4K runs

    Nests (inner contexts shadow outer), restores on exit, and is scoped to
    the current thread/context (contextvars)."""
    unknown = set(overrides) - set(_DEFAULT_KEYS)
    if unknown:
        raise ValueError(
            f"sort_defaults accepts {_DEFAULT_KEYS}, got {sorted(unknown)}")
    token = _DEFAULTS.set({**_DEFAULTS.get(), **overrides})
    try:
        yield
    finally:
        _DEFAULTS.reset(token)


def default(key: str):
    """Current ambient default for ``key`` (None if unset)."""
    return _DEFAULTS.get().get(key)


# ---------------------------------------------------------------------------
# the spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class SortSpec:
    """The full sort problem in one value.

    Field groups (all optional beyond the defaults):

      axis / descending / stable   ordering contract
      k                            top-k selection (1 <= k <= n, validated)
      values                       payload array carried with the keys
      indices                      return the sorting permutation (argsort)
      segment_ids / row_splits     ragged: sort within each segment
      valid_lengths                padded rows: sort each row's valid prefix
      fill_value                   what overwrites the padded tail
      mesh / axis_name             distributed: sort globally over a mesh
                                   axis (single-round sample-sort / odd-even
                                   fallback, planner-priced); with ``k`` the
                                   spec is a mesh-global top-k (local select
                                   + one candidate all-gather)
      method / run_len / interpret execution knobs (None -> ambient default)

    ``eq=False`` keeps the dataclass hashable-by-identity even though it may
    carry arrays; :meth:`static_key` reduces the spec to its hashable
    statics plus the operand's (shape, dtype) for caching layers.
    """
    axis: int = -1
    descending: bool = False
    stable: bool = False
    k: Optional[int] = None
    values: Optional[jnp.ndarray] = None
    indices: bool = False
    segment_ids: Optional[jnp.ndarray] = None
    row_splits: Optional[jnp.ndarray] = None
    valid_lengths: Optional[jnp.ndarray] = None
    fill_value: Any = 0
    mesh: Any = None               # jax.sharding.Mesh for distributed sorts
    axis_name: Optional[str] = None
    method: Optional[str] = None
    run_len: Optional[int] = None
    interpret: Optional[bool] = None

    # -- validation + canonicalization (the one place it happens) -----------
    def canonical(self, x: jnp.ndarray) -> "SortSpec":
        """Resolve ambient defaults, normalize the axis, and validate the
        whole problem against ``x`` — every front-door error is raised here,
        not deep inside a kernel."""
        ndim = x.ndim
        if ndim == 0:
            raise ValueError("cannot sort a 0-d array")
        if not -ndim <= self.axis < ndim:
            raise ValueError(
                f"axis {self.axis} out of range for {ndim}-d input")
        axis = self.axis % ndim
        method = self.method if self.method is not None else default("method")
        names = backend_names() + ("auto",)
        if method not in names:
            raise ValueError(
                f"method must be one of {names}, got {method!r}")
        axis_name = self.axis_name
        if axis_name is not None and self.mesh is None:
            raise ValueError("axis_name requires a mesh")
        if self.mesh is not None:
            # one axis name, a tuple of axes, or None -> the whole
            # mesh; normalised to a validated tuple by the same helper
            # every distributed consumer uses
            from repro.engine.samplesort import _axes_tuple
            axis_name = _axes_tuple(self.mesh, axis_name)
            if ndim != 1:
                raise ValueError(
                    "mesh-distributed specs sort flat 1-D arrays; "
                    f"got a {ndim}-d input")
            if (self.indices or self.stable
                    or self.segment_ids is not None
                    or self.row_splits is not None
                    or self.valid_lengths is not None):
                raise ValueError(
                    "mesh-distributed specs support plain and key-value "
                    "sorts plus top-k selection (no indices/stable/"
                    "segments/valid_lengths)")
            if method not in ("auto", "distributed"):
                raise ValueError(
                    f"mesh-distributed specs run the 'distributed' "
                    f"backend; method must be 'auto' or 'distributed', "
                    f"got {method!r}")
            method = "distributed"
        k = self.k
        n = x.shape[axis]
        if k is not None:
            k = int(k)
            if not 1 <= k <= n:
                raise ValueError(
                    f"topk k must satisfy 1 <= k <= n (n={n}); got k={k}")
        if self.segment_ids is not None and self.row_splits is not None:
            raise ValueError("pass segment_ids or row_splits, not both")
        ragged = self.segment_ids is not None or self.row_splits is not None
        if self.valid_lengths is not None and ragged:
            raise ValueError(
                "valid_lengths (padded rows) and segment_ids/row_splits "
                "(ragged) are mutually exclusive")
        if k is not None and (ragged or self.valid_lengths is not None):
            raise ValueError("top-k over segmented/padded specs is not "
                             "supported; sort then slice per segment")
        if k is not None and (self.values is not None or self.indices
                              or self.stable):
            raise ValueError("top-k specs return (values, indices) on their "
                             "own; values/indices/stable do not combine "
                             "with k")
        if self.values is not None and self.indices:
            raise ValueError("indices=True builds its own index payload; "
                             "pass either values or indices, not both")
        if self.values is not None and self.values.shape != x.shape:
            raise ValueError(
                f"values shape {self.values.shape} must match keys shape "
                f"{x.shape}")
        if method != "auto":
            # one-place validation: an op the backend declares unsupported
            # fails here, not deep inside a kernel ("auto" only ever
            # resolves to capability-eligible backends)
            caps = get_backend(method).capabilities
            if k is not None and not caps.supports_topk:
                raise ValueError(
                    f"{method} backend does not support top-k "
                    f"(capabilities.supports_topk=False)")
            if k is None and not caps.supports_sort:
                raise ValueError(
                    f"{method} backend is selection-only "
                    f"(capabilities.supports_sort=False); it runs top-k "
                    f"specs (k=...), not full sorts")
            if self.values is not None and not caps.supports_kv:
                raise ValueError(
                    f"{method} backend does not support key-value payloads "
                    f"(capabilities.supports_kv=False)")
            if ragged and not caps.supports_segments:
                raise ValueError(
                    f"{method} backend does not support segmented sorts "
                    f"(capabilities.supports_segments=False)")
        run_len = self.run_len if self.run_len is not None \
            else default("run_len")
        interpret = self.interpret if self.interpret is not None \
            else default("interpret")
        # top-k is inherently a descending selection (largest k)
        descending = True if k is not None else self.descending
        return dataclasses.replace(self, axis=axis, method=method, k=k,
                                   descending=descending, run_len=run_len,
                                   axis_name=axis_name, interpret=interpret)

    def static_key(self, shape, dtype) -> tuple:
        """Hashable reduction of the spec to its statics + the operand's
        (shape, dtype) — array-valued fields contribute only their presence,
        since a plan never depends on payload *values*.  The built-in plan
        cache (``planner.choose_cached``) keys on the statics it derives
        from the spec; this method is the equivalent key for external
        caching layers (e.g. a serving tier memoizing compiled steps)."""
        # axis layout AND device identity: two same-shape submeshes over
        # disjoint devices must not share an externally cached executable
        mesh_key = None if self.mesh is None else (
            tuple(zip(self.mesh.axis_names, self.mesh.devices.shape)),
            tuple(d.id for d in self.mesh.devices.flat))
        return (self.axis, self.descending, self.stable, self.k,
                self.values is not None, self.indices,
                self.segment_ids is not None, self.row_splits is not None,
                self.valid_lengths is not None, self.fill_value, self.method,
                mesh_key, self.axis_name,
                self.run_len, self.interpret, tuple(shape),
                jnp.dtype(dtype).name)
