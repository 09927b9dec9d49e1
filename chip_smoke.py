#!/usr/bin/env python3
"""Smoke run of the batched Monte Carlo engines on a TPU.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --chips 4 [--seed N]

Drives the engines through the entry points a user calls
(simulate_availability_batched, simulate_downtime_batched,
simulate_client_latency) at the paper's §5.1 cluster: n=155 nodes,
P=4096 partitions (one Aerospike namespace), rf=2, 64 trials,
chunk_steps=512, a bounded max_steps of three chunks.  Phases:

  availability  i.i.d. failures
  downtime      reconfiguring rebuilds, zipf partition sizes, finite
                per-node catch-up bandwidth, the four-engine protocol zoo
                (lark, quorum, hermes, spinnaker), rolling restart
  latency       the client-latency layer, key_zipf=0.99, read_frac=0.5

One chip: every phase runs on backend="pallas", packed=True (the fused
step megakernel, compiled by Mosaic) and on backend="jax", packed=False
(the plain jnp reference) from the same seed; every output — integer
counts and float fractions alike — must match bit for bit.  Every pallas
chunk program must contain a compiled kernel (tpu_custom_call) and every
jax one none.  One fused autotune race must report source="measured".

--chips 4: only the downtime and latency phases, pallas packed, at
devices=4 (trials sharded over a "trials" mesh) against devices=1; the
results must match bit for bit.

Lines before the last are labels for a reader (per phase: compile
seconds, run seconds, the device kind, the tiles), not benchmark
metrics.  The last line is one JSON object: {"ok": true, "device":
{"platform": "tpu", "kind": ..., "count": ...}}.  Without a TPU, or
without the repo's src/ next to this file, it exits non-zero and prints
no result.  One process holds the chip; nothing is run in a child.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

N_NODES, PARTITIONS, RF = 155, 4096, 2
TRIALS, CHUNK_STEPS, CHUNKS = 64, 512, 3
P_FAIL = 1e-3


class SmokeFailure(Exception):
    pass


class ChunkProbe:
    """Wraps the engines' chunk-runner factory: the first call of each
    runner lowers and compiles the jitted chunk program on the run's own
    arguments (timed, and its HLO kept for the kernel check), and every
    call runs that one executable (timed)."""

    def __init__(self, jax):
        self.jax = jax
        self.runs = []

    def wrap(self, make):
        def make_runner(step, carry, **kw):
            fn = make(step, carry, **kw)
            rec = {"compile_s": None, "chunk_s": [], "kernel": None}
            self.runs.append(rec)
            exe = []

            def run(c, s0):
                if not exe:
                    t = time.perf_counter()
                    exe.append(fn.lower(c, s0).compile())
                    rec["compile_s"] = time.perf_counter() - t
                    rec["kernel"] = "tpu_custom_call" in exe[0].as_text()
                t = time.perf_counter()
                out = self.jax.block_until_ready(exe[0](c, s0))
                rec["chunk_s"].append(time.perf_counter() - t)
                return out
            return run
        return make_runner

    def take(self):
        runs, self.runs = self.runs, []
        return runs


def _diff(a, b, path="result"):
    """Paths where a and b differ, comparing arrays and floats by their
    bytes (bitwise: -0.0 != 0.0, and a NaN equals only the same NaN)."""
    import numpy as np
    if dataclasses.is_dataclass(a):
        out = []
        for f in dataclasses.fields(a):
            if f.name not in ("backend", "devices"):
                out += _diff(getattr(a, f.name), getattr(b, f.name),
                             f"{path}.{f.name}")
        return out
    if isinstance(a, dict):
        if set(a) != set(b):
            return [f"{path} (keys)"]
        out = []
        for k in a:
            out += _diff(a[k], b[k], f"{path}[{k!r}]")
        return out
    if isinstance(a, (list, tuple)) and not isinstance(a, str):
        if len(a) != len(b):
            return [f"{path} (length)"]
        out = []
        for i, (x, y) in enumerate(zip(a, b)):
            out += _diff(x, y, f"{path}[{i}]")
        return out
    if a is None or isinstance(a, (str, bool)):
        return [] if a == b else [path]
    x, y = np.asarray(a), np.asarray(b)
    same = x.shape == y.shape and x.dtype == y.dtype \
        and x.tobytes() == y.tobytes()
    return [] if same else [path]


def _phases(seed: int):
    """(name, engine entry point, keyword arguments) of every phase."""
    from repro.core.availability_batched import simulate_availability_batched
    from repro.core.client_latency import simulate_client_latency
    from repro.core.downtime_batched import simulate_downtime_batched
    from repro.core.scenarios import get_scenario

    common = dict(n=N_NODES, partitions=PARTITIONS, rf=RF, p=P_FAIL,
                  trials=TRIALS, seed=seed, chunk_steps=CHUNK_STEPS,
                  max_steps=CHUNKS * CHUNK_STEPS + 1)
    rolling = get_scenario("rolling-restart").kwargs(n=N_NODES, rf=RF,
                                                     p=P_FAIL)
    return [
        ("availability", simulate_availability_batched,
         dict(common, trajectory=True)),
        ("downtime", simulate_downtime_batched,
         dict(common, rebuild_model="reconfig", size_dist="zipf",
              size_skew=1.0, node_bandwidth_gibps=1.0,
              engines=("lark", "quorum", "hermes", "spinnaker"),
              lease_ticks=4, view_change_ticks=4, trajectory=True,
              **rolling)),
        ("latency", simulate_client_latency,
         dict(common, key_zipf=0.99, read_frac=0.5)),
    ]


def _run(probe, name, fn, kw, **variant):
    res = fn(**kw, **variant)
    runs = probe.take()
    if not runs:
        raise SmokeFailure(f"{name}: no chunk program ran")
    label = ",".join(f"{k}={v}" for k, v in variant.items())
    for i, r in enumerate(runs):
        chunk = sorted(r["chunk_s"])
        print(f"# {name} [{label}] program {i}: compile_s="
              f"{r['compile_s']!r} run_s={sum(chunk)!r} chunks="
              f"{len(chunk)} median_chunk_s={chunk[len(chunk) // 2]!r}",
              flush=True)
    want = variant.get("backend") == "pallas"
    for r in runs:
        if r["kernel"] is not want:
            raise SmokeFailure(
                f"{name} [{label}]: chunk program "
                f"{'lacks' if want else 'has'} a compiled Pallas kernel "
                "(tpu_custom_call)")
    return res


def _compare(name, a, b, what):
    bad = _diff(a, b)
    if bad:
        raise SmokeFailure(f"{name}: {what} differ in {len(bad)} "
                           f"field(s): {', '.join(bad[:12])}")
    print(f"# {name}: {what} bit-identical", flush=True)


def _autotune():
    from repro.kernels.ops import autotune_fused_blocks
    t = time.perf_counter()
    r = autotune_fused_blocks(TRIALS, PARTITIONS, N_NODES, rf=RF, voters=RF,
                              n_real=N_NODES, kernel="fused_downtime_roster")
    if r.source != "measured":
        raise SmokeFailure(f"fused autotune race: source={r.source!r}")
    print(f"# autotune fused_downtime_roster B={TRIALS} P={PARTITIONS}: "
          f"tile=({r.block_t}, {r.block_p}) candidates={len(r.timings_us)} "
          f"seconds={time.perf_counter() - t!r}", flush=True)


def smoke(chips: int, seed: int, probe) -> None:
    from repro.kernels.ops import fused_default_blocks
    bt, bp = fused_default_blocks(TRIALS // chips, PARTITIONS, N_NODES,
                                  rf=RF, kernel="fused_downtime_roster")
    print(f"# default fused tile per device: ({bt}, {bp})", flush=True)
    pallas = dict(backend="pallas", packed=True)
    for name, fn, kw in _phases(seed):
        if chips == 1:
            got = _run(probe, name, fn, kw, **pallas)
            ref = _run(probe, name, fn, kw, backend="jax", packed=False)
            _compare(name, got, ref, "pallas-packed and jax-unpacked")
        elif name != "availability":
            got = _run(probe, name, fn, kw, devices=chips, **pallas)
            ref = _run(probe, name, fn, kw, devices=1, **pallas)
            _compare(name, got, ref, f"devices={chips} and devices=1")
        if name == "latency":
            for k in ("req_total", "p99_lark", "p99_quorum", "slo_quorum"):
                v = getattr(got, k)
                if not math.isfinite(v):
                    raise SmokeFailure(f"latency: {k}={v!r} is not finite")
    if chips == 1:
        _autotune()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the sharded downtime and latency phases "
                         "against devices=1, nothing else")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(ROOT)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 3
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips; "
              f"JAX found {len(devs)}", file=sys.stderr)
        return 3
    kind = devs[0].device_kind
    print(f"# device: {kind} x{len(devs)}, jax {jax.__version__}",
          flush=True)

    from repro.core import availability_batched, downtime_batched
    probe = ChunkProbe(jax)
    runner = probe.wrap(availability_batched._make_chunk_runner)
    availability_batched._make_chunk_runner = runner
    downtime_batched._make_chunk_runner = runner

    t = time.perf_counter()
    try:
        smoke(args.chips, args.seed, probe)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"# total seconds: {time.perf_counter() - t!r}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
