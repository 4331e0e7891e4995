"""Interconnect topology — the tiered link structure under a device mesh.

The paper's accounting (Eq. 3-4, Tables I/II) prices *operand movement*
per link crossed, and Mutlu et al. (PAPERS.md) generalise the lesson: the
win comes from restructuring computation around the memory/interconnect
hierarchy instead of treating it as flat.  PR 4's distributed sample-sort
still assumed exactly that flat picture — one axis of D devices with a
uniform per-byte link cost — which production meshes violate: intra-host
ICI runs ~10x faster than the inter-host DCN.

This module is the explicit model of that hierarchy, mirroring the
``repro.core.tuning`` layer one concern over:

  * :class:`TopologyAxis` — one mesh axis with its tier (``"ici"`` or
    ``"dcn"``), measured/assumed ``bandwidth_bytes_per_s`` and
    ``latency_ns``.
  * :class:`Topology` — a frozen, schema-versioned record of the axes of
    one mesh, keyed by the device fingerprint + mesh signature and
    JSON-persistable exactly like a ``TuningProfile``.
  * ``from_mesh`` / ``for_mesh`` — derive a default topology from a
    ``jax.sharding.Mesh`` (outermost axis = DCN when the mesh is
    multi-axis, everything inside it = ICI), or resolve the active /
    persisted one matching the mesh signature.
  * ``calibrate`` — a ping/all-to-all microbenchmark that probes each
    axis's launch latency and per-byte rate from two transfer sizes.
  * an **active topology** ambient with a generation counter folded into
    the planner's distributed-plan cache keys, so swapping topologies
    transparently re-plans flat-vs-hierarchical decisions.

Layering: sits beside ``tuning`` at the bottom of the stack.  It imports
only ``tuning`` (for the fingerprint and the default link constants) and
jax lazily inside the mesh/probe helpers; ``cost_model``, ``planner``,
``engine.collectives`` and ``engine.samplesort`` all consume it.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import re
import threading
from typing import Dict, Optional, Sequence, Tuple

from repro.core import tuning as _tuning

__all__ = [
    "SCHEMA", "TIER_ICI", "TIER_DCN", "TopologyAxis", "Topology",
    "TopologyError", "from_mesh", "for_mesh", "calibrate", "active",
    "set_active", "generation", "save", "load", "load_for_mesh",
    "persisted_path", "topology_path", "search_dirs", "cache_dir",
]

SCHEMA = "repro.topology/v1"

TOPOLOGY_DIR_ENV = "REPRO_TOPOLOGY_DIR"   # highest-priority topology dir

TIER_ICI = "ici"    # fast intra-host interconnect
TIER_DCN = "dcn"    # slow inter-host data-center network
_VALID_TIERS = (TIER_ICI, TIER_DCN)

# DCN defaults relative to the tuning layer's ICI link constants: the
# motivating production skew is ~10x slower per byte and ~10x the launch
# latency (collective_per_byte=0.02 ns/B ~ 50 GB/s ICI => 5 GB/s DCN).
DCN_SLOWDOWN = 10.0


class TopologyError(ValueError):
    """A topology that cannot be trusted: wrong schema version, malformed
    JSON, or axis values outside the validated ranges."""


@dataclasses.dataclass(frozen=True)
class TopologyAxis:
    """One mesh axis and the link tier its collectives run over."""
    name: str
    size: int
    tier: str
    bandwidth_bytes_per_s: float
    latency_ns: float

    def __post_init__(self):
        if not self.name:
            raise TopologyError("axis name must be non-empty")
        if self.size < 1:
            raise TopologyError(f"axis {self.name!r} size must be >= 1, "
                                f"got {self.size}")
        if self.tier not in _VALID_TIERS:
            raise TopologyError(f"axis {self.name!r} tier must be one of "
                                f"{_VALID_TIERS}, got {self.tier!r}")
        if not self.bandwidth_bytes_per_s > 0:
            raise TopologyError(f"axis {self.name!r} bandwidth must be > 0, "
                                f"got {self.bandwidth_bytes_per_s}")
        if self.latency_ns < 0:
            raise TopologyError(f"axis {self.name!r} latency must be >= 0, "
                                f"got {self.latency_ns}")

    @property
    def per_byte_ns(self) -> float:
        """The cost-model form of the bandwidth: ns per byte moved."""
        return 1e9 / self.bandwidth_bytes_per_s


@dataclasses.dataclass(frozen=True)
class Topology:
    """The tiered link structure of one device mesh.

    ``axes`` are ordered outermost-first, matching the mesh's axis order:
    for a 2x4 ``("host", "device")`` mesh the DCN axis comes first.
    ``source`` records provenance (``"default"`` / ``"calibrated"`` /
    ``"persisted"``) and ``probe_ns`` keeps the raw microbenchmark table a
    calibrated topology was fitted from, so a persisted file is auditable.
    """
    fingerprint: str
    axes: Tuple[TopologyAxis, ...]
    source: str = "default"
    probe_ns: Optional[Dict[str, float]] = None
    schema: str = SCHEMA

    def __post_init__(self):
        if self.schema != SCHEMA:
            raise TopologyError(
                f"unknown topology schema {self.schema!r} "
                f"(expected {SCHEMA!r})")
        axes = tuple(a if isinstance(a, TopologyAxis) else TopologyAxis(**a)
                     for a in self.axes)
        object.__setattr__(self, "axes", axes)
        if not axes:
            raise TopologyError("topology must have at least one axis")
        names = [a.name for a in axes]
        if len(set(names)) != len(names):
            raise TopologyError(f"duplicate axis names: {names}")

    # -- structure ----------------------------------------------------------
    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(a.name for a in self.axes)

    @property
    def n_devices(self) -> int:
        n = 1
        for a in self.axes:
            n *= a.size
        return n

    @property
    def is_hierarchical(self) -> bool:
        """True when the mesh has >= 2 non-degenerate axes — i.e. a second
        splitter round across the outer tier is even expressible."""
        return sum(1 for a in self.axes if a.size > 1) >= 2

    def axis(self, name: str) -> TopologyAxis:
        for a in self.axes:
            if a.name == name:
                return a
        raise KeyError(f"no axis {name!r} in topology "
                       f"{self.axis_names}")

    def signature(self) -> Tuple[Tuple[str, int], ...]:
        """The (name, size) shape a mesh must match to use this topology."""
        return tuple((a.name, a.size) for a in self.axes)

    # -- serialization ------------------------------------------------------
    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Topology":
        if not isinstance(d, dict):
            raise TopologyError(f"topology document must be an object, "
                                f"got {type(d).__name__}")
        if d.get("schema") != SCHEMA:
            raise TopologyError(f"unknown topology schema {d.get('schema')!r} "
                                f"(expected {SCHEMA!r})")
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise TopologyError(
                f"unknown topology fields {sorted(unknown)} "
                f"(schema {SCHEMA})")
        if "fingerprint" not in d or not isinstance(d["fingerprint"], str):
            raise TopologyError("topology is missing its device fingerprint")
        d = dict(d)
        axes = d.get("axes")
        if not isinstance(axes, (list, tuple)):
            raise TopologyError("topology axes must be a list")
        afields = {f.name for f in dataclasses.fields(TopologyAxis)}
        built = []
        for a in axes:
            if not isinstance(a, dict):
                raise TopologyError("each topology axis must be an object")
            bad = set(a) - afields
            if bad:
                raise TopologyError(
                    f"unknown axis fields {sorted(bad)} (schema {SCHEMA})")
            try:
                built.append(TopologyAxis(**a))
            except TypeError as e:
                raise TopologyError(f"malformed topology axis: {e}") from e
        d["axes"] = tuple(built)
        try:
            return cls(**d)
        except TypeError as e:
            raise TopologyError(f"malformed topology: {e}") from e


# ---------------------------------------------------------------------------
# mesh derivation
# ---------------------------------------------------------------------------

def _default_rates(tier: str) -> Tuple[float, float]:
    """(bandwidth B/s, latency ns) defaults per tier, derived from the
    active tuning profile's collective constants so a calibrated profile's
    link fit flows into default topologies too."""
    c = _tuning.active().constants
    bw = 1e9 / c.collective_per_byte
    lat = c.collective_alpha
    if tier == TIER_DCN:
        return bw / DCN_SLOWDOWN, lat * DCN_SLOWDOWN
    return bw, lat


def auto_mesh(mesh):
    """``mesh`` with every axis Auto-typed (the same devices and names).

    ``jax.make_mesh`` gives Explicit axes, whose sharding-in-types rules
    reject gathers and ``with_sharding_constraint`` on the arrays they
    shard.  The library's mesh programs are ``shard_map``s and its
    placement rules are constraints, so they run on the Auto-typed twin
    of whatever mesh a caller builds."""
    from jax.sharding import AxisType, Mesh
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def to_auto_mesh(x, mesh=None):
    """An array placed on an Explicit-axes mesh, moved (same layout) onto
    the Auto-typed twin of that mesh, or of ``mesh`` (needed under a
    trace, where the array's own mesh is abstract); anything else is
    returned unchanged."""
    import jax
    from jax.sharding import AxisType, NamedSharding
    sh = jax.typeof(x).sharding if isinstance(x, jax.core.Tracer) \
        else getattr(x, "sharding", None)
    if not isinstance(sh, NamedSharding) \
            or all(t == AxisType.Auto for t in sh.mesh.axis_types):
        return x
    target = auto_mesh(sh.mesh if mesh is None else mesh)
    return jax.device_put(x, NamedSharding(target, sh.spec))


def _mesh_signature(mesh, axis_names=None) -> Tuple[Tuple[str, int], ...]:
    names = tuple(axis_names) if axis_names is not None \
        else tuple(mesh.axis_names)
    for nm in names:
        if nm not in mesh.axis_names:
            raise TopologyError(f"axis {nm!r} not in mesh axes "
                                f"{tuple(mesh.axis_names)}")
    return tuple((nm, int(mesh.shape[nm])) for nm in names)


def from_mesh(mesh, axis_names: Optional[Sequence[str]] = None,
              *, fingerprint: Optional[str] = None) -> Topology:
    """The default topology for ``mesh``: outermost axis is the DCN tier
    when the mesh is multi-axis (matching ``jax.make_mesh``'s convention of
    hosts-outermost), every inner axis is ICI; a single-axis mesh is pure
    ICI.  ``axis_names`` restricts/reorders to a subset of the mesh axes
    (outer first), defaulting to all of them in mesh order."""
    sig = _mesh_signature(mesh, axis_names)
    axes = []
    for i, (nm, size) in enumerate(sig):
        tier = TIER_DCN if (i == 0 and len(sig) > 1) else TIER_ICI
        bw, lat = _default_rates(tier)
        axes.append(TopologyAxis(name=nm, size=size, tier=tier,
                                 bandwidth_bytes_per_s=bw, latency_ns=lat))
    return Topology(fingerprint=fingerprint or _tuning.device_fingerprint(),
                    axes=tuple(axes), source="default")


def for_mesh(mesh, axis_names: Optional[Sequence[str]] = None) -> Topology:
    """Resolve the topology the stack should price ``mesh`` with: the
    active ambient one when its signature matches, else a persisted file
    keyed by (fingerprint, signature), else the ``from_mesh`` default.
    Never returns None — there is always at least the default picture."""
    sig = _mesh_signature(mesh, axis_names)
    act = active()
    if act is not None and act.signature() == sig:
        return act
    persisted = load_for_mesh(sig)
    if persisted is not None:
        return persisted
    return from_mesh(mesh, axis_names)


# ---------------------------------------------------------------------------
# persistence (mirrors tuning.py: env dir -> user cache -> repo baselines)
# ---------------------------------------------------------------------------

def _repo_topology_dir() -> pathlib.Path:
    # src/repro/core/topology.py -> repo root / benchmarks / topologies
    return pathlib.Path(__file__).resolve().parents[3] / "benchmarks" \
        / "topologies"


def cache_dir() -> pathlib.Path:
    env = os.environ.get(TOPOLOGY_DIR_ENV)
    if env:
        return pathlib.Path(env)
    xdg = os.environ.get("XDG_CACHE_HOME")
    base = pathlib.Path(xdg) if xdg else pathlib.Path.home() / ".cache"
    return base / "repro" / "topologies"


def search_dirs() -> Tuple[pathlib.Path, ...]:
    dirs = []
    env = os.environ.get(TOPOLOGY_DIR_ENV)
    if env:
        dirs.append(pathlib.Path(env))
    else:
        dirs.append(cache_dir())
    dirs.append(_repo_topology_dir())
    return tuple(dirs)


def _filename(fingerprint: str,
              signature: Tuple[Tuple[str, int], ...]) -> str:
    # one file per (device fingerprint, mesh signature): the same machine
    # legitimately hosts many mesh shapes, each with its own calibration
    shape = "-".join(f"{nm}{sz}" for nm, sz in signature)
    return re.sub(r"[^A-Za-z0-9._-]+", "_", f"{fingerprint}.{shape}") \
        + ".json"


def topology_path(topology: Topology,
                  directory: Optional[os.PathLike] = None) -> pathlib.Path:
    d = pathlib.Path(directory) if directory is not None else cache_dir()
    return d / _filename(topology.fingerprint, topology.signature())


def save(topology: Topology,
         path: Optional[os.PathLike] = None) -> pathlib.Path:
    """Persist ``topology`` as schema-versioned JSON; returns the path."""
    p = pathlib.Path(path) if path is not None else topology_path(topology)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(topology.to_dict(), indent=2, allow_nan=False,
                            sort_keys=True) + "\n")
    return p


def load(path: os.PathLike) -> Topology:
    """Load one topology file.  Raises :class:`TopologyError` on schema
    mismatch or a malformed document (never silently trusts stale data)."""
    try:
        doc = json.loads(pathlib.Path(path).read_text())
    except (OSError, ValueError) as e:
        raise TopologyError(f"cannot read topology {path}: {e}") from e
    return Topology.from_dict(doc)


def persisted_path(signature: Tuple[Tuple[str, int], ...],
                   fingerprint: Optional[str] = None
                   ) -> Optional[pathlib.Path]:
    fp = fingerprint or _tuning.device_fingerprint()
    for d in search_dirs():
        p = d / _filename(fp, tuple(signature))
        if not p.is_file():
            continue
        try:
            t = load(p)
            if t.fingerprint == fp and t.signature() == tuple(signature):
                return p
        except TopologyError:
            continue
    return None


def load_for_mesh(signature: Tuple[Tuple[str, int], ...],
                  fingerprint: Optional[str] = None) -> Optional[Topology]:
    """The persisted topology matching (fingerprint, mesh signature), or
    None.  A file whose stored identity does not match is rejected — the
    planner falls back to defaults rather than mispricing every plan."""
    p = persisted_path(tuple(signature), fingerprint)
    if p is None:
        return None
    return dataclasses.replace(load(p), source="persisted")


# ---------------------------------------------------------------------------
# active-topology ambient (generation feeds the planner's dist-plan cache)
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_active: Optional[Topology] = None
_generation = 0


def active() -> Optional[Topology]:
    """The ambient topology, or None.  Unlike the tuning profile there is
    no lazy default — a topology only means something relative to a mesh,
    so resolution happens per-mesh in :func:`for_mesh`."""
    return _active


def set_active(topology: Optional[Topology]) -> None:
    """Swap the ambient topology (``None`` = forget).  Bumps the
    generation counter the planner folds into distributed plan-cache keys,
    so flat-vs-hierarchical decisions priced under the old link rates
    die with it."""
    global _active, _generation
    with _LOCK:
        _active = topology
        _generation += 1


def generation() -> int:
    """Monotonic counter for plan-cache keys."""
    return _generation


# ---------------------------------------------------------------------------
# calibration: ping / all-to-all microbenchmark
# ---------------------------------------------------------------------------

def _time_ns(fn, *args, reps: int = 3) -> float:
    import time
    import jax
    out = fn(*args)
    jax.block_until_ready(out)          # compile + warm outside the clock
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e9


def calibrate(mesh, axis_names: Optional[Sequence[str]] = None, *,
              small_bytes: int = 1 << 10, large_bytes: int = 1 << 20,
              reps: int = 3, persist: bool = False,
              set_as_active: bool = True) -> Topology:
    """Probe each mesh axis's link tier with a two-point all-to-all
    microbenchmark and fit (latency_ns, bandwidth_bytes_per_s) per axis.

    For every non-degenerate axis the probe times a tiled all-to-all at a
    small and a large per-device payload; the slope between the two points
    is the per-byte rate and the intercept the launch latency (the
    ping half of ping/all-to-all).  Degenerate (size-1) axes keep the
    tier defaults — there is no link to measure.  The raw timings land in
    ``probe_ns`` so a persisted calibration is auditable.

    On a simulated mesh (forced host-platform device count) the numbers
    describe the simulation, not real links — still useful for exercising
    the machinery, not for real dispatch decisions.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    base = from_mesh(mesh, axis_names)
    probe: Dict[str, float] = {}
    axes_out = []
    for i, ax in enumerate(base.axes):
        if ax.size <= 1:
            axes_out.append(ax)
            continue

        def probe_bytes(nbytes: int, name=ax.name, size=ax.size) -> float:
            # per-device payload: `size` rows of nbytes/size each, f32
            per_row = max(1, nbytes // (4 * size))

            def body(v):
                return jax.lax.all_to_all(v, name, split_axis=0,
                                          concat_axis=0, tiled=True)
            fn = jax.shard_map(body, mesh=mesh, in_specs=(P(name),),
                               out_specs=P(name), check_vma=False)
            x = jnp.zeros((size * size * per_row,), jnp.float32)
            return _time_ns(jax.jit(fn), x, reps=reps), 4 * size * per_row

        (t0, b0), (t1, b1) = probe_bytes(small_bytes), \
            probe_bytes(large_bytes)
        probe[f"{ax.name}.alltoall_{b0}B_ns"] = t0
        probe[f"{ax.name}.alltoall_{b1}B_ns"] = t1
        if b1 > b0 and t1 > t0:
            per_byte = (t1 - t0) / (b1 - b0)
            lat = max(0.0, t0 - per_byte * b0)
        else:                           # degenerate fit: keep defaults
            per_byte = ax.per_byte_ns
            lat = ax.latency_ns
        axes_out.append(dataclasses.replace(
            ax, bandwidth_bytes_per_s=1e9 / per_byte, latency_ns=lat))

    topo = dataclasses.replace(base, axes=tuple(axes_out),
                               source="calibrated", probe_ns=probe or None)
    if persist:
        save(topo)
    if set_as_active:
        set_active(topo)
    return topo
