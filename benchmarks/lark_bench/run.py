#!/usr/bin/env python3
"""Benchmark of the LARK Monte Carlo engines on the chip.

    python3 benchmarks/lark_bench/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A cell (BENCHMARK.json `workloads`) is one deployment (configs/) under
one traffic mix (traffic/) on 1 or 4 chips.  A run:

  set-up   process start, compile cache, two warm-up calls of the
           cell's engine entry point for one chunk at the cell's shapes
           (the first compiles, or loads from the cache; on a trials mesh
           the second runs two chunks); its end is `setup_s`
  window   calls of that entry point with the same seed and shapes, each
           `chunks_per_call` whole chunks, until `--seconds` have passed
           (`--trace 1`: one call, under the profiler)
  check    the first call's result against the plain reference
           (larkbench/reference/<engine>.py, or the module the cell's
           config names) on a sample of trials drawn from the seed;
           every later call must equal the first bit for bit

The last line of standard output is one JSON object: correct, attempted,
failed, metrics (end-to-end with --trace 0, per-layer with --trace 1),
device, breakdown (--trace 1), and checks (each number compared with its
limit).  Without a TPU, or with fewer chips than the cell asks for, or
without the program's src/ in the checkout, it exits non-zero and prints
no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import concurrent.futures  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(BENCH_DIR))
CACHE_DIRNAME = ".jax-compilation-cache"
# one-chunk calls before the window: the first compiles (or loads from
# the cache) every program a call runs, the second runs from the cache
WARM_UP_CALLS = 2


def warm_up_chunks(cell: dict, i: int) -> int:
    """Chunks of warm-up call i.  On a trials mesh the last one runs two:
    a later chunk there takes its carry in the mesh's layout, which is a
    program of its own, so one-chunk calls would leave it to the window."""
    if i == WARM_UP_CALLS - 1 and cell["chips"] > 1:
        return min(2, cell["chunks_per_call"])
    return 1


class NoDevice(Exception):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless $JAX_COMPILATION_CACHE_DIR names one (JAX then reads
    it itself).  Same rule as the program's launch/compile_cache.py, and
    every program is kept, however fast it compiled: a chunk program that
    compiles in under JAX's default second would otherwise be compiled
    again by every call."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_stamp(jax, chips: int, *, require_tpu: bool) -> dict:
    """Platform, kind and count as JAX reports them (the program's
    experiments/provenance.device_geometry, copied); no TPU or too few
    chips is an error, never a fallback."""
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips; JAX found "
                       f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(jax, chips: int) -> int:
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def drive(cell, seed, seconds, trace_dir):
    """Warm-up calls, then the window, in one loop that does the same
    between every two calls and logs nothing until it ends: on the chip
    the persistent cache's key of a call's programs changes with what the
    process did just before the call, so other work between the warm-up
    and the window makes the window's first call compile again.  Returns
    (results of the window's calls, setup end, window seconds, log lines).

    The window is calls of `chunks_per_call` chunks until `seconds` have
    passed; with `trace_dir`, one call under the profiler, which starts
    before the last warm-up call so that the traced call follows a call.
    """
    import jax
    from larkbench import compiles, program, trace
    counter = compiles.Counter()
    results, lines = [], []
    setup_end = window_end = None
    i = 0
    try:
        while True:
            warm = i < WARM_UP_CALLS
            if trace_dir is not None and i == WARM_UP_CALLS - 1:
                # host spans from JAX's own TraceMe events only: the Python
                # function tracer would slow the host path it explains
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            chunks = warm_up_chunks(cell, i) if warm \
                else cell["chunks_per_call"]
            span = trace.WARM_UP_SPAN if warm else trace.WINDOW_SPAN
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(span):
                res = program.call(cell, seed=seed, chunks=chunks)
            end = time.perf_counter()
            lines.append(f"{'warm-up' if warm else 'window'} call {i} "
                         f"({chunks} chunks): {end - t!r} s; "
                         f"{counter.take()}")
            i += 1
            if warm:
                setup_end = end
                continue
            results.append(res)
            window_end = end
            if trace_dir is not None or end - setup_end >= seconds:
                break
    finally:
        if trace_dir is not None and i >= WARM_UP_CALLS - 1:
            jax.profiler.stop_trace()
    return results, setup_end, window_end - setup_end, lines


def reference(cell, seed, sample, **kw):
    """The plain reference on the sampled trials, in one block a chip,
    each block on its own chip and all at once (`kw`: the control's
    broken guarantee)."""
    import jax

    from larkbench import compare, program, spec
    ref_mod = spec.reference(cell)
    kw.update(seed=seed, chunks=cell["chunks_per_call"],
              chunk_steps=program.chunk_steps(cell["engine"]))
    if cell["chips"] == 1:
        return ref_mod.simulate(cell, trials=sample, **kw)

    def block(device, trials):
        with jax.default_device(device):
            return ref_mod.simulate(cell, trials=trials, **kw)
    blocks = np.array_split(np.asarray(sample), cell["chips"])
    with concurrent.futures.ThreadPoolExecutor(len(blocks)) as pool:
        parts = list(pool.map(block, jax.devices()[:len(blocks)], blocks))
    return compare.join(parts)


def check(cell, seed, results):
    """Compare the window's results with the reference: (correct,
    attempted, failed, rows of [name, value, limit])."""
    from larkbench import compare, program
    cs = program.chunk_steps(cell["engine"])
    views = [program.view(cell, r) for r in results]
    failed = sum(compare.failed_trials(v, chunk_steps=cs) for v in views)
    differ = sum(1 for r in results[1:] if compare.diff(results[0], r))
    sample = compare.sample_trials(seed, cell["trials"],
                                   cell["reference_trials"])
    t = time.perf_counter()
    ref = reference(cell, seed, sample)
    log(f"# reference: {len(sample)} trials in "
        f"{time.perf_counter() - t!r} s")
    values = compare.readings(views[0], ref, sample,
                              partitions=cell["partitions"],
                              horizon=cell["horizon"], calls_differ=differ,
                              failed=failed,
                              min_waves=cell.get("min_restart_waves", 0))
    ok, rows = compare.judge(values, cell["limits"])
    return ok, cell["trials"] * len(results), failed, rows


def layer_metrics(bench, cell, device, trace_dir, steps):
    """Per-layer metrics of this cell from the trace, by their readers;
    a reader that finds nothing returns None and the metric is left out."""
    from larkbench import spec, trace
    per_dev = cell["trials"] // cell["chips"]
    xplane = trace.xplane_path(trace_dir)
    summ = trace.summarize(trace.read(xplane),
                           spec.kernel_classifier(cell, per_dev))
    ctx = {"summary": summ, "steps": steps, "cell": cell, "xplane": xplane,
           "peaks": spec.peaks(device["kind"]),
           "kernel_bytes": {k.KIND: k.bytes_per_call(cell, per_dev)
                            for k in spec.kernel_counts()}}
    metrics = {}
    for m in bench["per_layer"]:
        if cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        v = spec.metric_reader(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    devs = summ["devices"].values()
    busy = sum(d["busy_ns"] for d in devs) / max(len(devs), 1) / 1e9
    return metrics, busy, summ["window_ns"] / 1e9, {
        "device_ops": summ["device_ops"], "idle_gaps": summ["idle_gaps"]}


def prepare(workload: str, *, require_tpu: bool = True,
            cells: str = CHECKOUT):
    """(cell, device): the cell's definition from the checkout `cells`
    (tests point it at a small one), its reference module's file found,
    the compile cache, and the device stamp; raises NoDevice without the
    program or the chips."""
    src = os.path.join(CHECKOUT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise NoDevice(f"no program under {src}")
    for p in (src, BENCH_DIR):
        if p not in sys.path:
            sys.path.insert(0, p)
    from larkbench import spec
    cell = spec.cell(workload, cells)
    spec.reference(cell, load=False)
    cache = enable_cache()
    import jax
    device = device_stamp(jax, cell["chips"], require_tpu=require_tpu)
    log(f"# cell {cell['name']}: {cell['engine']} x {cell['trials']} "
        f"trials on {device['kind']} x{device['count']}; cache {cache}")
    return cell, device


def run(args, *, require_tpu: bool = True, cells: str = CHECKOUT) -> dict:
    """One run of a cell (see the module docstring)."""
    cell, device = prepare(args.workload, require_tpu=require_tpu,
                           cells=cells)
    import jax

    from larkbench import program, spec
    bench = spec.benchmark(cells)
    if args.trace:
        spec.peaks(device["kind"])           # an unknown kind fails early

    trace_dir = tempfile.mkdtemp(prefix="lark_bench_trace_") \
        if args.trace else None
    try:
        results, setup_end, window_s, lines = drive(
            cell, args.seed, args.seconds, trace_dir)
        setup_s = setup_end - T_START
        for line in lines:
            log(f"# {line}")
        ticks = program.partition_ticks(cell, results)
        log(f"# setup_s {setup_s!r}; window: {len(results)} calls in "
            f"{window_s!r} s; ticks/trial {results[0].ticks}")
        device["memory_peak_bytes"] = memory_peak(jax, cell["chips"])
        out = {}
        if args.trace:
            steps = cell["chunks_per_call"] \
                * program.chunk_steps(cell["engine"])
            metrics, busy_s, traced_s, breakdown = layer_metrics(
                bench, cell, device, trace_dir, steps)
            device["busy_s"], device["window_s"] = busy_s, traced_s
            out["breakdown"] = breakdown
        else:
            metrics = {
                "partition_ticks_per_s": {"value": ticks / window_s,
                                          "unit": "ticks/s"},
                "setup_s": {"value": setup_s, "unit": "s"}}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    ok, attempted, failed, rows = check(cell, args.seed, results)
    result = {"correct": bool(ok), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    result.update(out)
    for name, v, lim in rows:
        log(f"check {name} {v!r} limit {lim!r}")
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in rows}
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except NoDevice as e:
        log(f"lark_bench: {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
