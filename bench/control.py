#!/usr/bin/env python3
"""Run a cell's control at the cell's own size: the plain reference one
precision below the configuration's (or with one stated guarantee
broken), put in the program's place and compared as a run compares the
program.  Every limit must fail it.

    python3 bench/control.py --workload <name> --seeds 1,2,3

Prints one JSON line per seed with each number compared beside its limit
and whether the control came out correct (it must not).  Needs the chips
the cell asks for: the inputs are made on them, as in a run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from benchkit import cell, gen, spec
    cell.configure_jax_cache()
    sys.path.insert(0, str(spec.ROOT / "src"))
    import jax
    c = spec.load_cell(args.workload)
    devs = cell.devices_for(c.chips, require_tpu=True)
    mesh = (jax.make_mesh((c.chips,), ("data",), devices=devs)
            if c.chips > 1 else None)
    ctx = cell.Ctx(config=c.config, traffic=c.traffic, mesh=mesh)
    op = spec.op_module(c.traffic)
    all_failed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        inputs = gen.make_inputs(c.config, op.columns(c.traffic), 1, seed,
                                 mesh)[0]
        host = gen.to_host(inputs)
        del inputs
        numbers = op.compare(ctx, host, op.control(ctx, host),
                             op.reference(ctx, host))
        correct = all(numbers[k] <= op.LIMITS[k] for k in op.LIMITS)
        all_failed &= not correct
        print(json.dumps({
            "workload": args.workload, "seed": seed, "control": True,
            "correct": correct, "seconds": time.time() - t0,
            "checks": {k: {"value": numbers[k], "limit": op.LIMITS[k]}
                       for k in op.LIMITS}}), flush=True)
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
