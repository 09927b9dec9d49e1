#!/usr/bin/env python3
"""Run one cell several times, one process after another, and report how
far its metrics spread: the numbers a bound is set from.

    python3 benchmarks/lark_bench/spread.py --workload <cell> \
        --seeds <s1,s2,...> --seconds <s> [--sets 2] [--trace 0] \
        [--out <file.jsonl>]

Each set runs run.py once per seed, in the order given; every set uses
the same seeds.  A run's result line, its exit code and the end of its
standard error go to --out, one JSON line per run.  Then, per set and
metric: the median and the spread, the distance between the first and
third quartile (statistics.quantiles(n=4)) as a share of the median.  A
run that prints no result or is not correct ends it, with exit code 1.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(BENCH_DIR))


def spread(values) -> float:
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def one_run(args, seed: int) -> dict:
    cmd = [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
           args.workload, "--seed", str(seed), "--seconds",
           str(args.seconds), "--trace", str(args.trace)]
    t = time.perf_counter()
    p = subprocess.run(cmd, cwd=CHECKOUT, capture_output=True, text=True)
    wall = time.perf_counter() - t
    lines = p.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        result = None
    return {"workload": args.workload, "seed": seed, "rc": p.returncode,
            "wall_s": wall, "result": result,
            "stderr": "\n".join(p.stderr.splitlines()[-40:])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    sets = []
    for k in range(args.sets):
        runs = []
        for seed in seeds:
            r = one_run(args, seed)
            res = r["result"] or {}
            print(json.dumps({"set": k, "seed": seed, "rc": r["rc"],
                              "wall_s": r["wall_s"],
                              "correct": res.get("correct"),
                              "metrics": res.get("metrics")}), flush=True)
            if args.out:
                with open(args.out, "a") as fh:
                    fh.write(json.dumps(dict(r, set=k)) + "\n")
            runs.append(r)
            if not res.get("correct"):
                print(r["stderr"], file=sys.stderr, flush=True)
                return 1
        sets.append(runs)
    for k, runs in enumerate(sets):
        done = [r["result"] for r in runs]
        names = sorted({m for d in done for m in d["metrics"]})
        for m in names:
            vals = [d["metrics"][m]["value"] for d in done
                    if m in d["metrics"]]
            line = {"set": k, "metric": m, "n": len(vals),
                    "median": statistics.median(vals), "values": vals}
            if len(vals) >= 2:
                line["spread"] = spread(vals)
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
