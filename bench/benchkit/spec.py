"""Find a cell's parts by name.

``BENCHMARK.json`` at the root of the checkout names every cell
(``workloads``), its configuration and its traffic mix; each of those is a
file of its own under ``bench/``:

    bench/configs/<config>.json      sizes, columns, guarantees, source
    bench/traffic/<traffic>.json     the operation and its parameters
    bench/benchkit/ops/<op>.py       front-door call, reference, comparison
    bench/metrics/<metric>.py        one reader per metric

A new configuration, mix or metric is a new file and a new entry; no file
here changes for it.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re
from dataclasses import dataclass
from typing import Any, Dict, List

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


@dataclass(frozen=True)
class Cell:
    workload: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _read_json(path: pathlib.Path) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file: {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: {e}") from None


def _check_name(name: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(f"bad name {name!r}")
    return name


def metrics_for(bench: Dict[str, Any], workload: str, kind: str
                ) -> List[Dict[str, Any]]:
    """The metrics of ``kind`` ("end_to_end" or "per_layer") that
    ``workload`` reports.  A metric with a ``workloads`` key is reported
    in those cells; an end-to-end metric without one in every cell; a
    per-layer metric without one in every cell that reports the
    end-to-end metric it moves."""
    e2e = [m for m in bench.get("end_to_end", [])
           if workload in m.get("workloads", [workload])]
    if kind == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench.get("per_layer", [])
            if workload in m.get("workloads", [workload])
            and ("workloads" in m or m["moves"] in moved)]


def load_cell(workload: str) -> Cell:
    """Resolve ``workload`` to its entry, configuration and traffic."""
    bench = _read_json(ROOT / "BENCHMARK.json")
    entries = [w for w in bench.get("workloads", [])
               if w.get("name") == workload]
    if len(entries) != 1:
        raise SpecError(f"workload {workload!r} is not in BENCHMARK.json")
    w = entries[0]
    config = _read_json(
        BENCH_DIR / "configs" / f"{_check_name(w['config'])}.json")
    traffic = _read_json(
        BENCH_DIR / "traffic" / f"{_check_name(w['traffic'])}.json")
    if int(config.get("chips", w["chips"])) != int(w["chips"]):
        raise SpecError(f"{workload}: the configuration is for "
                        f"{config['chips']} chips, the cell asks for "
                        f"{w['chips']}")
    return Cell(workload=w, config=config, traffic=traffic,
                end_to_end=metrics_for(bench, workload, "end_to_end"),
                per_layer=metrics_for(bench, workload, "per_layer"))


def load_module(path: pathlib.Path, label: str):
    """Import one file by path (a metric reader or an operation)."""
    if not path.is_file():
        raise SpecError(f"missing file: {path}")
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def op_module(traffic: Dict[str, Any]):
    name = _check_name(traffic["op"])
    return load_module(BENCH_DIR / "benchkit" / "ops" / f"{name}.py",
                       f"benchkit_op_{name}")


def metric_module(name: str):
    _check_name(name)
    return load_module(BENCH_DIR / "metrics" / f"{name}.py",
                       f"bench_metric_{name}")


def resolve(config: Dict[str, Any], value):
    """A size given as a number, or as the name of a configuration key."""
    if isinstance(value, str):
        if value not in config:
            raise SpecError(f"configuration has no key {value!r}")
        return config[value]
    return value
