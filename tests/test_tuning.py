"""Tuning-profile layer (repro.core.tuning): persistence, resolution,
parameter threading, autotuner round-trip, and the obs feedback loop.

The subsystem's contract in one line: every kernel shape constant and cost
constant the stack dispatches on comes from one measured, persisted,
fingerprint-keyed object — so these tests check the *wiring* (kernels,
planner, cost model, sample-sort all read the active profile) as much as
the object itself.
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cost_model, tuning
from repro.engine import planner
from repro.kernels import radix_select, radix_sort


@pytest.fixture(autouse=True)
def _isolated(tmp_path, monkeypatch):
    """Each test gets an empty profile dir and a fresh ambient: no test can
    see the developer's cache or another test's installed profile."""
    monkeypatch.setenv(tuning.PROFILE_DIR_ENV, str(tmp_path / "profiles"))
    tuning.set_active(None)
    planner.clear_plan_cache()
    yield
    tuning.set_active(None)
    planner.clear_plan_cache()


# ---------------------------------------------------------------------------
# profile object: round-trip + validation
# ---------------------------------------------------------------------------

def test_json_round_trip_preserves_everything():
    prof = tuning.TuningProfile(
        fingerprint="cpu/test/jax-0",
        constants=tuning.DeviceSortConstants(xla=7.5, select=11.0),
        digit_bits=4, radix_tile=128, run_len=4096,
        capacity_slack=1.25, select_min_n=512, source="calibrated",
        probe_ns={"xla.sort.n256": 123.0},
        sweeps={"digit_bits": {"4": 100.0, "8": 200.0}})
    again = tuning.TuningProfile.from_dict(
        json.loads(json.dumps(prof.to_dict())))
    assert again == prof


def test_save_load_round_trip_on_disk(tmp_path):
    prof = tuning.TuningProfile(fingerprint="cpu/test/jax-0", run_len=4096)
    path = tuning.save(prof, tmp_path / "p.json")
    assert tuning.load(path) == prof


@pytest.mark.parametrize("mutation", [
    {"schema": "repro.tuning.profile/v999"},
    {"schema": None},
    {"digit_bits": 3},
    {"digit_bits": 0},
    {"radix_tile": 4},
    {"radix_tile": 192},
    {"run_len": 1},
    {"capacity_slack": 0.5},
    {"select_min_n": -1},
    {"not_a_field": 1},
    {"constants": {"warp_speed": 9.0}},
])
def test_from_dict_rejects_bad_documents(mutation):
    doc = tuning.TuningProfile(fingerprint="cpu/test/jax-0").to_dict()
    doc.update(mutation)
    with pytest.raises(tuning.ProfileError):
        tuning.TuningProfile.from_dict(doc)


def test_from_dict_rejects_missing_fingerprint():
    doc = tuning.TuningProfile(fingerprint="cpu/test/jax-0").to_dict()
    del doc["fingerprint"]
    with pytest.raises(tuning.ProfileError):
        tuning.TuningProfile.from_dict(doc)


def test_load_rejects_malformed_file(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    with pytest.raises(tuning.ProfileError):
        tuning.load(bad)
    with pytest.raises(tuning.ProfileError):
        tuning.load(tmp_path / "missing.json")


# ---------------------------------------------------------------------------
# resolution: persisted wins, mismatches fall back to defaults
# ---------------------------------------------------------------------------

def test_active_resolves_defaults_when_nothing_persisted():
    prof = tuning.active()
    assert prof.source == "default"
    assert prof.fingerprint == tuning.device_fingerprint()
    assert prof == tuning.default_profile()


def test_persisted_profile_wins_resolution():
    mine = dataclasses.replace(tuning.default_profile(), run_len=4096)
    tuning.save(mine)                       # default path = isolated dir
    tuning.set_active(None)
    prof = tuning.active()
    assert prof.source == "persisted"
    assert prof.run_len == 4096
    assert tuning.persisted_path() is not None


def test_foreign_fingerprint_is_rejected(tmp_path, monkeypatch):
    """A profile copied from another machine (fingerprint mismatch with its
    filename slot) must not be trusted: resolution falls back to defaults."""
    other = tuning.TuningProfile(fingerprint="tpu/v5e/jax-9.9", run_len=64)
    # write it into this device's filename slot, simulating a bad copy
    tuning.save(other, tuning.profile_path(tuning.device_fingerprint()))
    assert tuning.load_for_device() is None
    assert tuning.persisted_path() is None
    assert tuning.active().source == "default"


def test_corrupt_persisted_file_falls_back(tmp_path):
    p = tuning.profile_path(tuning.device_fingerprint())
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text("{broken")
    assert tuning.load_for_device() is None
    assert tuning.active().source == "default"


def test_generation_bumps_on_swap():
    g0 = tuning.generation()
    tuning.set_active(dataclasses.replace(tuning.active(), run_len=4096))
    assert tuning.generation() > g0


# ---------------------------------------------------------------------------
# parameter threading: kernels / cost model / planner read the profile
# ---------------------------------------------------------------------------

def test_kernels_consume_profile_digit_bits():
    """Swap in digit_bits=4 and the radix kernels must run 8 passes (visible
    via pass_tile_counts) and still sort correctly."""
    tuning.set_active(dataclasses.replace(tuning.active(), digit_bits=4,
                                          radix_tile=128))
    passes, tiles = radix_sort.pass_tile_counts(1000, np.uint32)
    assert passes == 8                      # 32 bits / 4 per pass
    assert tiles == -(-1000 // 128)
    x = np.random.default_rng(0).integers(0, 2**32, (2, 500),
                                          dtype=np.uint32)
    out = np.asarray(radix_sort.sort_blocks(jnp.asarray(x)))
    np.testing.assert_array_equal(out, np.sort(x, -1))


def test_explicit_digit_bits_overrides_profile():
    x = np.random.default_rng(1).integers(0, 2**32, (1, 300),
                                          dtype=np.uint32)
    out = np.asarray(radix_sort.sort_blocks(jnp.asarray(x), digit_bits=2,
                                            tile=64))
    np.testing.assert_array_equal(out, np.sort(x, -1))


def test_selection_consumes_profile_digit_bits():
    x = np.random.default_rng(2).standard_normal((1, 400)).astype(np.float32)
    tuning.set_active(dataclasses.replace(tuning.active(), digit_bits=4,
                                          radix_tile=128))
    v, _ = radix_select.select_topk(jnp.asarray(x), 16, use_kernel=True,
                                    interpret=True)
    ref = np.sort(x, -1)[:, ::-1][:, :16]
    np.testing.assert_array_equal(np.asarray(v), ref)


def test_cost_model_prices_from_profile():
    """Halving digit_bits doubles the pass count, so the radix price must
    rise — the model reads the active profile, not a module constant."""
    n = 1 << 16
    c8 = cost_model.device_sort_cost_ns("radix", n)
    tuning.set_active(dataclasses.replace(tuning.active(), digit_bits=4))
    c4 = cost_model.device_sort_cost_ns("radix", n)
    assert c4 > c8
    # explicit digit_bits bypasses the ambient
    assert cost_model.device_sort_cost_ns("radix", n, digit_bits=8) \
        == pytest.approx(c8)


def test_planner_reads_run_len_and_select_floor():
    tuning.set_active(dataclasses.replace(tuning.active(), run_len=1024,
                                          select_min_n=1 << 30))
    assert planner.choose(100000, 1).run_len == 1024
    # the selection floor removes "select" from auto top-k plans below it
    plan = planner.choose(1 << 20, 1, k=64)
    assert plan.method != "select"
    # explicit requests still route to the selection engine
    forced = planner.choose(4096, 1, requested="select", k=16)
    assert forced.method == "select"


# ---------------------------------------------------------------------------
# autotuner: calibrate -> persist -> fresh process -> identical plans
# ---------------------------------------------------------------------------

def test_calibrate_persists_and_fresh_process_loads(tmp_path):
    prof = planner.calibrate(tile_n=256, batch=4, reps=1, persist=True,
                             sweep_params=False)
    path = tuning.persisted_path()
    assert path is not None
    plan = planner.choose(100000, 1, jnp.dtype(jnp.float32))
    code = (
        "import json, sys\n"
        "import jax.numpy as jnp\n"
        "from repro.core import tuning\n"
        "from repro.engine import planner\n"
        "prof = tuning.active()\n"
        "plan = planner.choose(100000, 1, jnp.dtype(jnp.float32))\n"
        "print(json.dumps({'source': prof.source,\n"
        "                  'fingerprint': prof.fingerprint,\n"
        "                  'xla': prof.constants.xla,\n"
        "                  'method': plan.method,\n"
        "                  'run_len': plan.run_len}))\n")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        check=True, env={**os.environ, "PYTHONPATH": "src",
                         tuning.PROFILE_DIR_ENV: str(path.parent)},
        cwd=str(tuning._repo_profile_dir().parents[1]))
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["source"] == "persisted"
    assert got["fingerprint"] == prof.fingerprint
    assert got["xla"] == pytest.approx(prof.constants.xla)
    # the loaded profile reproduces this process's plan
    assert got["method"] == plan.method
    assert got["run_len"] == plan.run_len


def test_calibrate_records_audit_trail():
    prof = planner.calibrate(tile_n=256, batch=4, reps=1,
                             include_pallas=False)
    assert prof.source == "calibrated"
    assert prof.probe_ns and all(v > 0 for v in prof.probe_ns.values())
    assert prof.sweeps is not None          # sweep_params defaults True
    assert "run_len" in prof.sweeps
    # the digit-width sweep needs the radix kernel: gated on include_pallas
    # (interpret mode prices it dishonestly off-TPU)
    assert "digit_bits" not in prof.sweeps


@pytest.mark.slow          # ~30s: interpret-mode pallas probe grid
def test_calibrate_sweeps_digit_bits_with_pallas():
    prof = planner.calibrate(tile_n=128, batch=2, reps=1,
                             include_pallas=True)
    assert "digit_bits" in prof.sweeps
    assert set(prof.sweeps["digit_bits"]) == {"digit_bits=4", "digit_bits=8"}
    assert prof.digit_bits in (4, 8)


# ---------------------------------------------------------------------------
# obs feedback loop: drift -> re-probe -> clean slate
# ---------------------------------------------------------------------------

@pytest.fixture()
def _obs_on():
    from repro.obs import metrics, trace
    trace.enable()
    metrics.reset()
    tuning._last_refresh_t = None           # cooldown slate per test
    yield metrics
    tuning._last_refresh_t = None
    metrics.reset()
    trace.disable()


def test_refresh_needs_enough_signal(_obs_on):
    h = _obs_on.histogram("planner.cost_model_error")
    for _ in range(tuning.REFRESH_MIN_OBSERVATIONS - 1):
        h.observe(100.0)                    # wildly drifted but too few
    assert tuning.refresh_if_stale() is None


def test_refresh_in_band_is_a_noop(_obs_on):
    h = _obs_on.histogram("planner.cost_model_error")
    for _ in range(tuning.REFRESH_MIN_OBSERVATIONS):
        h.observe(1.1)                      # healthy model
    assert tuning.refresh_if_stale() is None
    assert h.count == tuning.REFRESH_MIN_OBSERVATIONS   # kept, not cleared


def test_refresh_on_drift_recalibrates_and_clears(_obs_on, monkeypatch):
    h = _obs_on.histogram("planner.cost_model_error")
    for _ in range(tuning.REFRESH_MIN_OBSERVATIONS):
        h.observe(50.0)                     # p90 far above threshold
    fresh = dataclasses.replace(tuning.default_profile(),
                                source="calibrated")
    calls = {}

    def _fake_calibrate(**kw):
        calls.update(kw)
        tuning.set_active(fresh)
        return fresh

    monkeypatch.setattr(planner, "calibrate", _fake_calibrate)
    got = tuning.refresh_if_stale(persist=False, tile_n=256)
    assert got is fresh
    assert calls == {"persist": False, "tile_n": 256}
    assert h.count == 0                     # slate cleared for next window
    assert _obs_on.counter("tuning.refreshes").value == 1


def _drift(h, ratio=50.0):
    for _ in range(tuning.REFRESH_MIN_OBSERVATIONS):
        h.observe(ratio)


def test_refresh_cooldown_rate_limits(_obs_on, monkeypatch):
    h = _obs_on.histogram("planner.cost_model_error")
    fresh = dataclasses.replace(tuning.default_profile(),
                                source="calibrated")
    calls = []
    monkeypatch.setattr(planner, "calibrate",
                        lambda **kw: (calls.append(kw), fresh)[1])
    clock = {"t": 1000.0}

    _drift(h)
    assert tuning.refresh_if_stale(persist=False,
                                   now_fn=lambda: clock["t"]) is fresh
    assert len(calls) == 1 and h.count == 0

    # drifts again inside the cooldown: refused, evidence kept
    _drift(h)
    assert tuning.refresh_if_stale(persist=False,
                                   now_fn=lambda: clock["t"]) is None
    assert len(calls) == 1
    assert h.count == tuning.REFRESH_MIN_OBSERVATIONS    # NOT cleared
    assert _obs_on.counter(
        "tuning.refreshes_rate_limited").value == 1

    # clock lapses past the cooldown: the held-back refresh fires
    clock["t"] += tuning.REFRESH_COOLDOWN_S + 1.0
    assert tuning.refresh_if_stale(persist=False,
                                   now_fn=lambda: clock["t"]) is fresh
    assert len(calls) == 2 and h.count == 0
    assert _obs_on.counter("tuning.refreshes").value == 2


def test_refresh_cooldown_checked_after_signal(_obs_on, monkeypatch):
    # a healthy in-band signal inside the cooldown is a plain no-op: the
    # rate-limited counter only counts refreshes that WOULD have fired
    h = _obs_on.histogram("planner.cost_model_error")
    monkeypatch.setattr(tuning, "_last_refresh_t", 1000.0)
    for _ in range(tuning.REFRESH_MIN_OBSERVATIONS):
        h.observe(1.1)
    assert tuning.refresh_if_stale(now_fn=lambda: 1001.0) is None
    assert _obs_on.counter(
        "tuning.refreshes_rate_limited").value == 0


def test_profile_reset_clears_refresh_cooldown(_obs_on, monkeypatch):
    """Regression: ``_last_refresh_t`` used to survive ``set_active`` —
    after a profile reset/reinstall the stale stamp rate-limited the first
    refresh of the NEW profile epoch for a full cooldown, even though the
    timestamp described a calibration of a profile that no longer exists.
    Installing or clearing a profile must start a fresh refresh epoch."""
    h = _obs_on.histogram("planner.cost_model_error")
    fresh = dataclasses.replace(tuning.default_profile(),
                                source="calibrated")
    calls = []
    monkeypatch.setattr(planner, "calibrate",
                        lambda **kw: (calls.append(kw),
                                      tuning.set_active(fresh), fresh)[2])
    clock = {"t": 1000.0}
    _drift(h)
    assert tuning.refresh_if_stale(persist=False,
                                   now_fn=lambda: clock["t"]) is fresh
    assert len(calls) == 1
    # the refresh stamp survives its own calibrate()'s set_active ...
    assert tuning._last_refresh_t == clock["t"]
    # ... but an explicit reset/reinstall clears it
    tuning.set_active(None)
    assert tuning._last_refresh_t is None
    # still inside the OLD cooldown window on the fake clock: the fresh
    # epoch must refresh immediately instead of being rate-limited
    clock["t"] += 1.0
    _drift(h)
    assert tuning.refresh_if_stale(persist=False,
                                   now_fn=lambda: clock["t"]) is fresh
    assert len(calls) == 2
    assert _obs_on.counter("tuning.refreshes_rate_limited").value == 0


def test_refresh_cooldown_zero_disables(_obs_on, monkeypatch):
    h = _obs_on.histogram("planner.cost_model_error")
    fresh = dataclasses.replace(tuning.default_profile(),
                                source="calibrated")
    calls = []
    monkeypatch.setattr(planner, "calibrate",
                        lambda **kw: (calls.append(kw), fresh)[1])
    for _ in range(2):
        _drift(h)
        assert tuning.refresh_if_stale(persist=False, cooldown_s=0.0,
                                       now_fn=lambda: 1000.0) is fresh
    assert len(calls) == 2


def test_maybe_refresh_is_gated_by_env(monkeypatch, _obs_on):
    h = _obs_on.histogram("planner.cost_model_error")
    for _ in range(tuning.REFRESH_MIN_OBSERVATIONS):
        h.observe(50.0)
    monkeypatch.setattr(tuning, "_autotune_live", False)
    tuning.maybe_refresh()                  # opt-out: must not calibrate
    assert h.count == tuning.REFRESH_MIN_OBSERVATIONS
