#!/usr/bin/env python3
"""Run one cell several times, each run its own process, and report the
spread of every metric.

    python3 bench/spread.py --workload <name> --seeds 11,12,13 \
        [--sets 2] [--traced-seeds 21,22] [--seconds 10] [--out FILE]

Each set runs ``bench/run.py`` once per seed, in order; with ``--sets 2``
the same seeds run again as a second set.  ``--traced-seeds`` adds runs
with ``--trace 1`` after the sets.  For every metric the summary gives each
set's values, median and spread: the distance between the first and third
quartile (``statistics.quantiles(values, n=4)``) as a share of the median.
This process never imports JAX, so each run has the chips to itself.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
RUN_TIMEOUT_S = 1300


def one_run(workload: str, seed: int, seconds: float, trace: int):
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
           workload, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    t0 = time.time()
    try:
        p = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=RUN_TIMEOUT_S, cwd=os.path.dirname(HERE))
        rc, out, err = p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        rc, out, err = 124, e.stdout or "", e.stderr or ""
        out = out.decode() if isinstance(out, bytes) else out
        err = err.decode() if isinstance(err, bytes) else err
    wall = time.time() - t0
    lines = out.strip().splitlines()
    result = None
    if rc == 0 and lines:
        try:
            result = json.loads(lines[-1])
        except json.JSONDecodeError:
            result = None
    return {"seed": seed, "trace": trace, "rc": rc, "wall_s": wall,
            "result": result, "stdout_head": [ln[:400] for ln in lines[:-1]],
            "stderr_tail": err[-3000:]}


def spread(values):
    if len(values) < 2:
        return None
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med if med else None


def summarize(runs):
    out = {}
    for r in runs:
        if r["result"] is None:
            continue
        for name, m in r["result"]["metrics"].items():
            out.setdefault(name, {}).setdefault(r["set"], []).append(
                m["value"])
    summary = {}
    for name, by_set in out.items():
        summary[name] = {
            str(s): {"values": v, "median": statistics.median(v),
                     "spread": spread(v)} for s, v in sorted(by_set.items())}
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--traced-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    traced = [int(s) for s in args.traced_seeds.split(",") if s]
    runs = []
    plan = [(s, seed, 0) for s in range(args.sets) for seed in seeds]
    plan += [("traced", seed, 1) for seed in traced]
    for set_id, seed, trace in plan:
        r = one_run(args.workload, seed, args.seconds, trace)
        r["set"] = set_id
        runs.append(r)
        res = r["result"] or {}
        print(json.dumps({"set": set_id, "seed": seed, "trace": trace,
                          "rc": r["rc"], "wall_s": round(r["wall_s"], 1),
                          "correct": res.get("correct"),
                          "attempted": res.get("attempted"),
                          "metrics": {k: v["value"] for k, v in
                                      res.get("metrics", {}).items()},
                          "checks": res.get("checks")}), flush=True)
        if r["result"] is None:
            print(r["stderr_tail"][-1500:], file=sys.stderr, flush=True)
    summary = summarize([r for r in runs if r["trace"] == 0])
    print(json.dumps({"workload": args.workload, "summary": summary}),
          flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "runs": runs,
                       "summary": summary}, f, indent=1)
    return 0 if all(r["result"] for r in runs) else 1


if __name__ == "__main__":
    sys.exit(main())
