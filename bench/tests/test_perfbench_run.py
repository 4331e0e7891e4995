"""``bench/run.py`` as the benchmark is run: it refuses to run without a
TPU, and a new cell needs only new files and new entries."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench_tiny import BENCH, ROOT

BENCH_JSON = json.loads((ROOT / "BENCHMARK.json").read_text())


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


@pytest.mark.parametrize("w", BENCH_JSON["workloads"],
                         ids=lambda w: w["name"])
def test_refuses_without_a_tpu(w, tmp_path):
    cmd = BENCH_JSON["command"] + ["--workload", w["name"], "--seed",
                                   str(2 ** 31 + 3), "--seconds", "1",
                                   "--trace", "0"]
    p = subprocess.run(cmd, cwd=ROOT, env=_cpu_env(TMPDIR=str(tmp_path)),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_without_the_system(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cmd = BENCH_JSON["command"] + ["--workload", "partkey_sort_sf10",
                                   "--seed", "1", "--seconds", "1",
                                   "--trace", "0"]
    env = _cpu_env()
    env.pop("PYTHONPATH", None)
    p = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


THROWAWAY = r"""
import json, sys, time
sys.path.insert(0, sys.argv[1])
from benchkit import cell
r = cell.run_cell("throwaway_cell", 5, 0.2, False, started=time.time(),
                  require_tpu=False, log=lambda *a: None)
print(json.dumps(r))
"""


def test_new_mix_config_and_metric_are_files_and_entries(tmp_path):
    """A throwaway configuration, mix and metric, added to a copy of the
    benchmark as new files and new entries only, run end to end."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "configs" / "throwaway_rows.json").write_text(
        json.dumps({"name": "throwaway_rows", "chips": 1, "rows": 2048,
                    "columns": {"k": {"kind": "uniform_int", "low": -50,
                                      "high": 50},
                                "row_id": {"kind": "row_id"}}}))
    (tmp_path / "bench" / "traffic" / "throwaway_sort.json").write_text(
        json.dumps({"op": "sort_kv", "keys": "k", "values": "row_id",
                    "pool": 2, "warm_calls": 1, "checked_calls": 3}))
    (tmp_path / "bench" / "metrics" / "throwaway_calls.py").write_text(
        "def read(run):\n    return float(len(run.calls))\n")
    bench["configs"].append({"name": "throwaway_rows", "source": "test",
                             "file": "bench/configs/throwaway_rows.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "throwaway_cell",
                               "config": "throwaway_rows",
                               "traffic": "throwaway_sort", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "throwaway_calls", "unit": "calls",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["throwaway_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = _cpu_env(PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", THROWAWAY,
                        str(tmp_path / "bench")], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is True
    assert r["metrics"]["throwaway_calls"]["value"] == r["attempted"]
    assert {"keys_per_s", "setup_s"} <= set(r["metrics"])
