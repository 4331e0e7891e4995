"""Per-device tuning profiles — measured constants + tunable kernel knobs.

The paper's whole argument is cycle accounting: Tables I/II price every op
and every temp-row movement cycle, and the comparison figures only hold
because those constants describe the *actual* substrate (Mutlu et al.'s
practicality argument: the PIM win evaporates when the cost model is wrong
about the hardware).  The software stack has the same exposure one level
up — the planner's dispatch decisions are only as good as the per-element
constants and kernel shape parameters they are priced with.

This module is the one home for all of that state:

  * :class:`DeviceSortConstants` — the ns-per-element leading constants of
    every software backend (previously ``cost_model.DeviceSortConstants``;
    the cost model now *consumes* this layer instead of owning it).
  * :class:`TuningProfile` — a frozen record of those constants **plus**
    the tunable kernel parameters (radix ``digit_bits``, histogram tile,
    engine run length, sample-sort capacity slack, selection switch-over),
    keyed by a device fingerprint (platform, device kind, jax version) and
    schema-versioned for JSON persistence.
  * an **active profile** ambient: ``active()`` lazily resolves the
    profile for the running device — a persisted profile when one matches
    the fingerprint, the per-platform defaults otherwise — and every
    consumer (cost model, kernels, engine, sample-sort) reads its
    parameters from it.  ``set_active`` bumps a generation counter that
    the planner folds into its plan-cache keys, so swapping profiles
    transparently re-plans.
  * persistence: ``save``/``load``/``load_for_device`` with a search path
    of ``$REPRO_TUNING_DIR``, the user cache (``~/.cache/repro/profiles``)
    and the repo's committed baselines (``benchmarks/profiles/``).
  * the observability feedback hook: :func:`refresh_if_stale` re-probes
    (``planner.calibrate``) when the ``planner.cost_model_error``
    histogram's p90 drifts outside the trust band, closing the loop the
    obs subsystem opened.

Layering: this module is the *bottom* of the sorting stack — it imports
nothing from ``cost_model`` / ``planner`` / the kernels at module level
(they all import it), and jax only lazily inside the fingerprint helpers.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import re
import threading
import time
from typing import Dict, Optional, Tuple

__all__ = [
    "SCHEMA", "DeviceSortConstants", "TuningProfile", "ProfileError",
    "device_fingerprint", "default_profile", "active", "set_active",
    "generation", "save", "load", "load_for_device", "persisted_path",
    "profile_path", "search_dirs", "refresh_if_stale", "maybe_refresh",
]

SCHEMA = "repro.tuning.profile/v1"

PROFILE_DIR_ENV = "REPRO_TUNING_DIR"     # highest-priority profile dir
AUTOTUNE_ENV = "REPRO_AUTOTUNE"          # "1" => maybe_refresh() is live

# ---- tunable-parameter defaults (the "default profile") ----------------------
# These are the *only* hardcoded homes of the kernel shape constants; every
# other module (cost_model pricing, the radix kernels, the engine's run
# generation, sample-sort capacity policy) resolves them through the active
# profile.
DEFAULT_DIGIT_BITS = 8          # radix 256: 4 passes for 32-bit keys
DEFAULT_RADIX_TILE = 256        # elements per histogram partition
DEFAULT_RUN_LEN = 2048          # engine tile: one VMEM tile on TPU
DEFAULT_CPU_RUN_LEN = 8192      # host tile: measured jnp sweet spot
DEFAULT_CAPACITY_SLACK = 1.0    # sample-sort bucket capacity multiplier
DEFAULT_SELECT_MIN_N = 1024     # auto never picks selection below this n
# k-way merge fan-in: how many sorted runs one merge tournament consumes
# at a time before cascading (the spill tier's host merge groups runs in
# fan-in-sized batches; planner.calibrate() sweeps this)
DEFAULT_MERGE_FANIN = 16
# Out-of-core spill tier: arrays whose key payload exceeds this many bytes
# auto-route to repro.engine.spill (chunked device sorts + host k-way
# merge).  The default is sized for a ~16 GiB accelerator with headroom
# for the sort's own scratch (runs + merge ping-pong ~ 4x the input).
DEFAULT_SPILL_THRESHOLD_BYTES = 4 << 30
# floor: a chunk must hold at least a handful of elements of the widest
# key dtype (8 B) for the chunk/merge machinery to be meaningful; tests
# force tiny thresholds (e.g. 256 B) to exercise many-chunk paths cheaply
MIN_SPILL_THRESHOLD_BYTES = 64

_VALID_DIGIT_BITS = (1, 2, 4, 8)

# observability feedback band: re-probe when cost_model_error p90 leaves
# [1/threshold, threshold] after at least min-observations samples
REFRESH_P90_THRESHOLD = 4.0
REFRESH_MIN_OBSERVATIONS = 32
# minimum seconds between drift-triggered recalibrations: a calibrate()
# sweep is milliseconds-to-seconds of probe sorts, so a persistently noisy
# drift signal (e.g. a co-tenant stealing the device) must not turn the
# closed loop into a calibration storm
REFRESH_COOLDOWN_S = 300.0


@dataclasses.dataclass(frozen=True)
class DeviceSortConstants:
    """ns-per-element leading constants for each software backend.

    Asymptotics are fixed per backend (``cost_model``); these are the
    measured leading constants ``planner.calibrate()`` fits on the live
    device.  The defaults are coarse seeds good enough for dispatch
    ordering.
    """
    xla: float = 6.0             # comparison sort: c * n log2 n
    bitonic: float = 1.2         # word-parallel jnp network: c * n log2^2 n
    pallas: float = 0.25         # VMEM-resident network: c * n log2^2 n
    merge_run: float = 6.0       # run generation: c * n log2 run_len
    merge_level: float = 12.0    # one merge-path level: c * n
    radix: float = 12.0          # LSD digit pass: c * n * passes
    # MSD select, c * n * pass units.  The constant is seeded from the
    # measured CPU bit-serial path (which runs digit_bits 1-bit
    # refinements per pass unit), putting the modeled select/sort-prefix
    # crossover at n ~ 1-2k for f32/k=64 — where the bench measures it
    select: float = 15.0
    # native lax.top_k on substrates where it lowers to a tuned O(n)
    # selection (XLA:CPU): c * n.  Seeded from the measured 3.4ms at n=1M
    # (results_engine_cpu.csv topk_xla rows); on TPU lax.top_k is
    # sort-based and the xla backend keeps the sort-prefix price instead
    xla_topk: float = 3.5
    pallas_interpret_penalty: float = 300.0   # CPU interpret-mode multiplier
    # mesh collectives (distributed dispatch): one collective round costs
    # alpha (launch/latency) + bytes-moved-per-device / bandwidth.  The
    # dcn pair prices nothing: it stays in the schema because persisted
    # profiles carry it and from_dict rejects unknown constants.
    collective_alpha: float = 2_000.0         # ns per collective launch
    collective_per_byte: float = 0.02         # ns/byte (~50 GB/s ICI link)
    dcn_alpha: float = 20_000.0               # ns per cross-host launch
    dcn_per_byte: float = 0.2                 # ns/byte (~5 GB/s DCN link)
    # spill tier (out-of-core): host<->device link bandwidth term and the
    # host-side k-way merge constant.  0.0625 ns/byte ~ 16 GB/s, a
    # PCIe-gen4-class x16 link; the merge constant prices one host
    # cursor-partition + device block-merge pass per element
    pcie_per_byte: float = 0.0625
    host_merge_level: float = 8.0


# TPU seeds: leading constants fitted to warm single calls on one TPU v5e
# (JAX 0.9.0).  xla: jnp.sort of 64 x 65536 f32 rows, 5.58 ms; pallas:
# the bitonic network on the same rows, 11.4 ms; radix: 2^26 f32 keys,
# 9.07 s; select: top-64 of 128 x 2^18 bf16 rows, 0.220 s.  A 2^26 jnp.sort
# took 1.27 s, 9x what the n log n model predicts from the small-row fit,
# but still the cheapest plan at that size.  The merge constants keep their
# host seeds (not measured on a chip yet).
TPU_CONSTANTS = DeviceSortConstants(xla=0.0832, pallas=0.0106, radix=33.8,
                                    select=3.28)


class ProfileError(ValueError):
    """A persisted profile that cannot be trusted: wrong schema version,
    malformed JSON, or field values outside the validated ranges."""


@dataclasses.dataclass(frozen=True)
class TuningProfile:
    """One device's measured cost constants + tuned kernel parameters.

    ``source`` records provenance: ``"default"`` (built-in per-platform
    seeds), ``"calibrated"`` (``planner.calibrate`` ran in this process),
    ``"persisted"`` (loaded from disk).  ``probe_ns`` and ``sweeps`` keep
    the raw measurement tables the autotuner derived the winners from, so
    a persisted profile is auditable.
    """
    fingerprint: str
    constants: DeviceSortConstants = DeviceSortConstants()
    digit_bits: int = DEFAULT_DIGIT_BITS
    radix_tile: int = DEFAULT_RADIX_TILE
    run_len: int = DEFAULT_RUN_LEN
    capacity_slack: float = DEFAULT_CAPACITY_SLACK
    select_min_n: int = DEFAULT_SELECT_MIN_N
    merge_fanin: int = DEFAULT_MERGE_FANIN
    spill_threshold_bytes: int = DEFAULT_SPILL_THRESHOLD_BYTES
    source: str = "default"
    probe_ns: Optional[Dict[str, float]] = None
    sweeps: Optional[Dict[str, Dict[str, float]]] = None
    schema: str = SCHEMA

    def __post_init__(self):
        if self.schema != SCHEMA:
            raise ProfileError(
                f"unknown profile schema {self.schema!r} (expected {SCHEMA!r})")
        if self.digit_bits not in _VALID_DIGIT_BITS:
            raise ProfileError(
                f"digit_bits must be one of {_VALID_DIGIT_BITS}, "
                f"got {self.digit_bits}")
        if self.radix_tile < 128 or self.radix_tile % 128:
            # the radix kernels tile rows in whole 128-lane vector rows
            raise ProfileError(f"radix_tile must be a positive multiple of "
                               f"128, got {self.radix_tile}")
        if self.run_len < 2:
            raise ProfileError(f"run_len too small: {self.run_len}")
        if self.capacity_slack < 1.0:
            # slack < 1 would undersize exchange buffers and drop elements
            raise ProfileError(
                f"capacity_slack must be >= 1.0, got {self.capacity_slack}")
        if self.select_min_n < 0:
            raise ProfileError(
                f"select_min_n must be >= 0, got {self.select_min_n}")
        if self.merge_fanin < 2:
            # a 1-way "merge" never terminates the cascade
            raise ProfileError(
                f"merge_fanin must be >= 2, got {self.merge_fanin}")
        if self.spill_threshold_bytes < MIN_SPILL_THRESHOLD_BYTES:
            raise ProfileError(
                f"spill_threshold_bytes must be >= "
                f"{MIN_SPILL_THRESHOLD_BYTES}, "
                f"got {self.spill_threshold_bytes}")

    # -- serialization ------------------------------------------------------
    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TuningProfile":
        if not isinstance(d, dict):
            raise ProfileError(f"profile document must be an object, "
                               f"got {type(d).__name__}")
        if d.get("schema") != SCHEMA:
            raise ProfileError(
                f"unknown profile schema {d.get('schema')!r} "
                f"(expected {SCHEMA!r})")
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ProfileError(
                f"unknown profile fields {sorted(unknown)} (schema {SCHEMA})")
        if "fingerprint" not in d or not isinstance(d["fingerprint"], str):
            raise ProfileError("profile is missing its device fingerprint")
        d = dict(d)
        consts = d.get("constants")
        if consts is not None:
            if not isinstance(consts, dict):
                raise ProfileError("profile constants must be an object")
            cfields = {f.name for f in dataclasses.fields(DeviceSortConstants)}
            bad = set(consts) - cfields
            if bad:
                raise ProfileError(
                    f"unknown cost constants {sorted(bad)} (schema {SCHEMA})")
            d["constants"] = DeviceSortConstants(
                **{k: float(v) for k, v in consts.items()})
        try:
            return cls(**d)
        except TypeError as e:
            raise ProfileError(f"malformed profile: {e}") from e


# ---------------------------------------------------------------------------
# device fingerprint + per-platform defaults
# ---------------------------------------------------------------------------

def device_fingerprint() -> str:
    """(platform, device kind, jax version) — the key a persisted profile
    is trusted under.  Constants measured on one substrate say nothing
    about another, and a jax upgrade can change every lowering."""
    import jax
    devs = jax.devices()
    kind = devs[0].device_kind if devs else "unknown"
    fp = f"{jax.default_backend()}/{kind}/jax-{jax.__version__}"
    return fp.replace(" ", "-")


def default_profile() -> TuningProfile:
    """The built-in seeds for the running platform — what the stack uses
    until a calibration runs or a persisted profile matches."""
    import jax
    tpu = jax.default_backend() == "tpu"
    return TuningProfile(
        fingerprint=device_fingerprint(),
        constants=TPU_CONSTANTS if tpu else DeviceSortConstants(),
        run_len=DEFAULT_RUN_LEN if tpu else DEFAULT_CPU_RUN_LEN,
        source="default")


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------

def _repo_profile_dir() -> pathlib.Path:
    # src/repro/core/tuning.py -> repo root / benchmarks / profiles
    return pathlib.Path(__file__).resolve().parents[3] / "benchmarks" \
        / "profiles"


def cache_dir() -> pathlib.Path:
    """Where ``calibrate(persist=True)`` writes by default:
    ``$REPRO_TUNING_DIR`` when set, else ``~/.cache/repro/profiles``."""
    env = os.environ.get(PROFILE_DIR_ENV)
    if env:
        return pathlib.Path(env)
    xdg = os.environ.get("XDG_CACHE_HOME")
    base = pathlib.Path(xdg) if xdg else pathlib.Path.home() / ".cache"
    return base / "repro" / "profiles"


def search_dirs() -> Tuple[pathlib.Path, ...]:
    """Profile lookup order: env override, user cache, repo baselines."""
    dirs = []
    env = os.environ.get(PROFILE_DIR_ENV)
    if env:
        dirs.append(pathlib.Path(env))
    else:
        dirs.append(cache_dir())
    dirs.append(_repo_profile_dir())
    return tuple(dirs)


def _filename(fingerprint: str) -> str:
    return re.sub(r"[^A-Za-z0-9._-]+", "_", fingerprint) + ".json"


def profile_path(fingerprint: Optional[str] = None,
                 directory: Optional[os.PathLike] = None) -> pathlib.Path:
    """Canonical file path for a fingerprint's profile."""
    fp = fingerprint or device_fingerprint()
    d = pathlib.Path(directory) if directory is not None else cache_dir()
    return d / _filename(fp)


def save(profile: TuningProfile,
         path: Optional[os.PathLike] = None) -> pathlib.Path:
    """Persist ``profile`` as schema-versioned JSON; returns the path."""
    p = pathlib.Path(path) if path is not None \
        else profile_path(profile.fingerprint)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(profile.to_dict(), indent=2, allow_nan=False,
                            sort_keys=True) + "\n")
    return p


def load(path: os.PathLike) -> TuningProfile:
    """Load one profile file.  Raises :class:`ProfileError` on a schema
    mismatch or malformed document (never silently trusts stale data)."""
    try:
        doc = json.loads(pathlib.Path(path).read_text())
    except (OSError, ValueError) as e:
        raise ProfileError(f"cannot read profile {path}: {e}") from e
    return TuningProfile.from_dict(doc)


def persisted_path(fingerprint: Optional[str] = None
                   ) -> Optional[pathlib.Path]:
    """First path in the search order holding a *valid* profile whose
    fingerprint matches, or None."""
    fp = fingerprint or device_fingerprint()
    for d in search_dirs():
        p = d / _filename(fp)
        if not p.is_file():
            continue
        try:
            if load(p).fingerprint == fp:
                return p
        except ProfileError:
            continue
    return None


def load_for_device(fingerprint: Optional[str] = None
                    ) -> Optional[TuningProfile]:
    """The persisted profile for this device, or None.  A file whose
    stored fingerprint does not match (mislabelled or copied from another
    machine) is rejected — constants fall back to the defaults rather
    than mispricing every plan."""
    fp = fingerprint or device_fingerprint()
    p = persisted_path(fp)
    if p is None:
        return None
    return dataclasses.replace(load(p), source="persisted")


# ---------------------------------------------------------------------------
# active-profile ambient
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_active: Optional[TuningProfile] = None
_generation = 0


def active() -> TuningProfile:
    """The profile the stack currently runs on.  Resolved lazily on first
    use: a persisted profile matching the device fingerprint wins, else
    the per-platform defaults.  ``planner.calibrate()`` replaces it via
    :func:`set_active`."""
    global _active
    if _active is None:
        with _LOCK:
            if _active is None:
                prof = load_for_device()
                _set(prof if prof is not None else default_profile())
    return _active


def _set(profile: Optional[TuningProfile]) -> None:
    global _active, _generation, _last_refresh_t
    _active = profile
    _generation += 1
    # Installing/resetting a profile starts a fresh refresh epoch: the
    # drift-refresh cooldown stamp must not leak from one install to the
    # next (a calibrate in one test would silently suppress drift
    # refreshes in the next for REFRESH_COOLDOWN_S).  refresh_if_stale
    # re-stamps *after* its calibrate() returns, so the cooldown it
    # enforces always refers to the profile it installed.
    _last_refresh_t = None


def set_active(profile: Optional[TuningProfile]) -> None:
    """Swap the active profile (``None`` = forget and lazily re-resolve).
    Bumps the generation counter, which the planner folds into every
    plan-cache key — cached plans priced under the old profile die."""
    with _LOCK:
        _set(profile)


def generation() -> int:
    """Monotonic counter for cache keys; forces resolution first so a plan
    cached before the lazy load cannot outlive it."""
    active()
    return _generation


# ---------------------------------------------------------------------------
# observability feedback: re-probe on cost-model drift
# ---------------------------------------------------------------------------

# monotonic stamp of the last drift-triggered calibrate (None = never);
# tests reset it by assigning None
_last_refresh_t: Optional[float] = None


def refresh_if_stale(threshold: float = REFRESH_P90_THRESHOLD,
                     min_count: int = REFRESH_MIN_OBSERVATIONS, *,
                     persist: bool = True,
                     cooldown_s: float = REFRESH_COOLDOWN_S,
                     now_fn=None,
                     **calibrate_kwargs) -> Optional[TuningProfile]:
    """Re-run the autotuner when measured/predicted cost drift says the
    active constants no longer describe this device.

    Reads the ``planner.cost_model_error`` histogram (PR 6's obs
    subsystem: one measured/predicted ratio per fenced engine call).  With
    at least ``min_count`` observations and a p90 outside
    ``[1/threshold, threshold]``, runs ``planner.calibrate(persist=...)``
    — which swaps the active profile, invalidates cached plans, and (by
    default) persists the fresh profile — then clears the histogram so
    the next drift measurement starts clean.  Returns the new profile, or
    None when the constants still hold (or there is too little signal).

    Refreshes are rate-limited: after a drift-triggered calibrate, further
    triggers within ``cooldown_s`` (monotonic clock; ``now_fn`` injectable
    for tests) return None WITHOUT clearing the histogram — the drift
    evidence keeps accumulating and the refresh fires as soon as the
    cooldown lapses.  ``cooldown_s=0`` disables the limit.
    """
    global _last_refresh_t
    from repro.obs import metrics
    h = metrics.histogram("planner.cost_model_error")
    if h.count < min_count:
        return None
    p90 = h.percentile(90)
    if p90 is None or (1.0 / threshold) <= p90 <= threshold:
        return None
    # cooldown check AFTER the signal checks: the rate-limited counter
    # counts refreshes that *would* have fired, nothing else
    now = (now_fn or time.monotonic)()
    if _last_refresh_t is not None and cooldown_s > 0 \
            and now - _last_refresh_t < cooldown_s:
        metrics.counter("tuning.refreshes_rate_limited").inc()
        return None
    from repro.engine import planner
    prof = planner.calibrate(persist=persist, **calibrate_kwargs)
    _last_refresh_t = now
    h.clear()
    metrics.counter("tuning.refreshes").inc()
    from repro.obs import trace
    trace.record_event("tuning_refresh", p90=p90, threshold=threshold,
                       fingerprint=prof.fingerprint, source=prof.source)
    return prof


_autotune_live: Optional[bool] = None


def autotune_armed() -> bool:
    """True iff ``REPRO_AUTOTUNE=1`` opts the process into closed-loop
    re-probing.  Spans fence (block on the device) only then: the loop's
    ``planner.cost_model_error`` signal is what reads their device time."""
    global _autotune_live
    if _autotune_live is None:
        _autotune_live = os.environ.get(AUTOTUNE_ENV) == "1"
    return _autotune_live


def maybe_refresh() -> None:
    """Zero-cost hook the engine calls after every cost observation: a
    no-op unless ``REPRO_AUTOTUNE=1`` opts the process into closed-loop
    re-probing (calibration mid-serve is deliberate, never a surprise)."""
    if autotune_armed():
        refresh_if_stale()
