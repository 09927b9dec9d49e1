#!/usr/bin/env python3
"""The readings that the limits of a cell are set from (limits/<cell>.json).

    python3 benchmarks/lark_bench/readings.py --workload <cell> \
        --seeds <s1,s2,...>

In one process, for each seed: one call of the program at the cell's own
size, as the window makes it, compared with the plain reference on the
seed's sample of trials (the lower readings: what sound runs read); and
the control, the reference with a broken guarantee put in the program's
place, compared the same way (the upper readings).  The control commits
to one cluster replica fewer than the configuration's rf, so a partition
can lose every holder of its latest copy while PAC still counts it
available.  One JSON line per seed.  The benchmark's own runs do not run
this; it needs the chip like run.py.
"""
import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH_DIR)
    import run
    try:
        cell, _ = run.prepare(args.workload, require_tpu=True)
    except run.NoDevice as e:
        run.log(f"readings: {e}")
        return 3
    from larkbench import compare, program
    cs = program.chunk_steps(cell["engine"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        res = program.call(cell, seed=seed, chunks=cell["chunks_per_call"])
        view = program.view(cell, res)
        sample = compare.sample_trials(seed, cell["trials"],
                                       cell["reference_trials"])
        ref = run.reference(cell, seed, sample)
        lower = compare.readings(
            view, ref, sample, partitions=cell["partitions"],
            horizon=cell["horizon"], calls_differ=0,
            failed=compare.failed_trials(view, chunk_steps=cs),
            min_waves=cell.get("min_restart_waves", 0))
        ctrl = run.reference(cell, seed, sample, acks=cell["rf"] - 1)
        upper = compare.readings(
            compare.as_view(ctrl), ref, list(range(len(sample))),
            partitions=cell["partitions"], horizon=cell["horizon"],
            calls_differ=0, failed=0,
            min_waves=cell.get("min_restart_waves", 0))
        print(json.dumps({"cell": cell["name"], "seed": seed,
                          "program": lower, "control": upper,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
