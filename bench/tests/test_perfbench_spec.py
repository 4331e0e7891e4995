"""BENCHMARK.json: every entry resolves to its files, and names, units and
limits keep to the allowed characters and sizes."""
import json
import re

import pytest

from perfbench_tiny import BENCH, ROOT
from benchkit import spec

BENCH_JSON = json.loads((ROOT / "BENCHMARK.json").read_text())
TEXT_RE = re.compile(r"^[^\t\n]{1,200}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


def test_top_level_keys():
    assert set(BENCH_JSON) == {"command", "paths", "run_seconds", "configs",
                               "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH_JSON["run_seconds"] <= 51
    assert len(json.dumps(BENCH_JSON)) <= 64 * 1024


def test_command_and_paths_stay_inside_the_benchmark():
    cmd, paths = BENCH_JSON["command"], BENCH_JSON["paths"]
    assert 1 <= len(paths) <= 16 and len(cmd) <= 32
    for p in paths:
        assert PATH_RE.match(p) and ".." not in p and not p.startswith("/")
        assert (ROOT / p).is_dir()
    for word in cmd:
        assert TEXT_RE.match(word) and not word.startswith("/")
        if "/" in word:
            assert any(word.startswith(p + "/") for p in paths)
            assert (ROOT / word).is_file()


@pytest.mark.parametrize("entry", BENCH_JSON["configs"],
                         ids=lambda c: c["name"])
def test_config_resolves(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert spec.NAME_RE.match(entry["name"])
    assert entry["file"] == f"bench/configs/{entry['name']}.json"
    data = json.loads((ROOT / entry["file"]).read_text())
    assert data["name"] == entry["name"]
    assert data["source"] == entry["source"]
    assert data["reduced"] == entry["reduced"]
    assert TEXT_RE.match(entry["source"]) and TEXT_RE.match(entry["why"])
    assert all(spec.NAME_RE.match(k) for k in entry["reduced"])
    assert data["guarantees"] and data["assumed"]
    used = [w for w in BENCH_JSON["workloads"]
            if w["config"] == entry["name"]]
    assert used, "every configuration is used by some cell"


@pytest.mark.parametrize("w", BENCH_JSON["workloads"],
                         ids=lambda w: w["name"])
def test_workload_resolves(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert spec.NAME_RE.match(w[key])
    assert w["chips"] in (1, 4) and TEXT_RE.match(w["why"])
    cell = spec.load_cell(w["name"])
    op = spec.op_module(cell.traffic)
    for fn in ("columns", "call", "keys_per_call", "api_bytes_per_call",
               "reference", "control", "compare", "host_output",
               "planned_method"):
        assert callable(getattr(op, fn)), fn
    for col in op.columns(cell.traffic):
        assert col in cell.config["columns"]
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer


def test_names_unique_and_pairs_once():
    for key in ("configs", "workloads"):
        names = [e["name"] for e in BENCH_JSON[key]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCH_JSON["end_to_end"]
               + BENCH_JSON["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in BENCH_JSON["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BENCH_JSON["workloads"])
    assert four <= max(1, len(BENCH_JSON["workloads"]) // 2)


@pytest.mark.parametrize("m", BENCH_JSON["end_to_end"] + BENCH_JSON["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(m):
    e2e = m in BENCH_JSON["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if e2e else {"layer", "moves"}
    assert set(m) <= allowed
    assert spec.NAME_RE.match(m["name"]) and spec.UNIT_RE.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    if e2e:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert TEXT_RE.match(m["layer"])
        assert m["moves"] in {e["name"] for e in BENCH_JSON["end_to_end"]}
    cells = {w["name"] for w in BENCH_JSON["workloads"]}
    assert set(m.get("workloads", [])) <= cells
    assert callable(spec.metric_module(m["name"]).read)


def test_setup_metric_present_and_bounded():
    setup = [m for m in BENCH_JSON["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and setup[0]["bound"] <= 0.25


def test_layers_are_named_alike():
    layers = {}
    for m in BENCH_JSON["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_files_under_paths_use_name_characters():
    for path in BENCH.rglob("*"):
        if "__pycache__" in path.parts:
            continue
        rel = path.relative_to(ROOT).as_posix()
        assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel


def test_peaks_table_names_its_source():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    v5e = peaks["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud documentation" in v5e["source"]


def test_unknown_names_are_refused():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no_such_cell")
    with pytest.raises(spec.SpecError):
        spec.metric_module("no_such_metric")
    with pytest.raises(spec.SpecError):
        spec.metric_module("../escape")
