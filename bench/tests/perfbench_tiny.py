"""Tiny sizes of each cell, for running the harness on the CPU in tests.

``TINY[workload]`` is ``(config_over, traffic_over)`` for
``benchkit.cell.run_cell``: the same generator, operation, reference and
comparison as the cell, at a size the CPU runs in a second.
"""
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def _columns(config: str, **over):
    with open(BENCH / "configs" / f"{config}.json") as f:
        cols = dict(json.load(f)["columns"])
    cols.update(over)
    return cols


TINY = {
    "q18_groupby_sf10": (
        {"rows": 4096, "columns": _columns(
            "tpch_sf10_lineitem",
            l_orderkey={"kind": "tpch_orderkey", "orders": 10000,
                        "lines_min": 1, "lines_max": 7})}, {}),
    "partkey_sort_sf10": ({"rows": 4096}, {}),
    "partkey_sort_sf30_4chip": ({"rows": 4096}, {}),
    "dsv3_topk_sampling": ({"decode_batch": 8, "vocab_size": 1000}, {}),
}

ONE_CHIP = ("q18_groupby_sf10", "partkey_sort_sf10", "dsv3_topk_sampling")


def run_tiny(workload: str, seed: int = 7, seconds: float = 0.2,
             trace: bool = False, **kw):
    import time
    from benchkit import cell
    config_over, traffic_over = TINY[workload]
    return cell.run_cell(workload, seed, seconds, trace, started=time.time(),
                         require_tpu=False, config_over=config_over,
                         traffic_over=traffic_over, log=lambda *a: None,
                         **kw)
