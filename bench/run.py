#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; everything
else is found by name under ``bench/`` (see ``benchkit/spec.py``).  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared beside its
limit.  Exits non-zero, printing no result, where JAX finds no TPU or
fewer chips than the cell asks for.
"""
import time

PROCESS_START = time.time()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    from benchkit import cell
    return cell.main(argv, started=PROCESS_START)


if __name__ == "__main__":
    sys.exit(main())
