"""Find a cell's pieces by name: its deployment (configs/), traffic mix
(traffic/), comparison limits (limits/), per-layer metric readers
(metrics/), kernel byte counts (kernels/) and the device peaks (peaks/).

Every piece is a file of its own, so a new cell, mix, metric, kernel or
device is added by adding files; nothing here names one.  A config and a
mix make one cell at most, so a mix laid out for a trials mesh is a file
of its own that states its `"chips"`; a workload that asks for another
count is a SpecError.

A deployment whose engine needs more than the harness sets brings it in
its config file, also by adding files:

  "engine_knobs": {...}      passed to the engine's entry point as they
                             are written (JSON lists stay lists), after
                             the keys program.kwargs sets; a knob that
                             names one of those keys is a SpecError
  "reference": {"<engine>": "<module>"}
                             the plain reference of that engine is
                             larkbench/reference/<module>.py, a new file
                             that may import from reference/common.py
                             and copy what it changes; without the entry
                             it is larkbench/reference/<engine>.py, and
                             a named module that is not there is a
                             SpecError before any run
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(os.path.dirname(BENCH_DIR))


class SpecError(Exception):
    pass


def _load_json(path: str) -> dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise SpecError(f"missing file {os.path.relpath(path, CHECKOUT)}")


def benchmark(checkout: str = CHECKOUT) -> dict:
    return _load_json(os.path.join(checkout, "BENCHMARK.json"))


def cell(name: str, checkout: str = CHECKOUT, bench: dict = None) -> dict:
    """Everything one cell runs with, flattened: the deployment's sizes,
    the traffic's engine and knobs, the chips, the horizon, the trial
    count (trials per chip x chips) and the trials the reference runs
    (the mix's `reference_trials` a chip, at most every trial)."""
    bench = bench or benchmark(checkout)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; have "
                        f"{', '.join(sorted(work))}")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = _load_json(os.path.join(checkout, configs[w["config"]]["file"]))
    here = os.path.join(checkout, os.path.relpath(BENCH_DIR, CHECKOUT))
    traffic = _load_json(os.path.join(here, "traffic",
                                      w["traffic"] + ".json"))
    if traffic.get("chips", w["chips"]) != w["chips"]:
        raise SpecError(f"traffic {w['traffic']!r} is for {traffic['chips']} "
                        f"chips; workload {name!r} asks for {w['chips']}")
    out = {k: v for k, v in conf.items()
           if k not in ("assumed", "reduced", "guarantees", "source",
                        "deployment", "name")}
    out.update(traffic)
    out["name"] = name
    out["chips"] = w["chips"]
    out["trials"] = conf["trials_per_chip"] * w["chips"]
    out["reference_trials"] = min(traffic["reference_trials"] * w["chips"],
                                  out["trials"])
    out["horizon"] = conf["horizon_ticks"][traffic["horizon"]]
    out["limits"] = _load_json(os.path.join(here, "limits",
                                            name + ".json"))
    return out


def reference(cell: dict, *, load: bool = True):
    """The plain reference module of the cell's engine: the one its
    config names under `reference`, else larkbench.reference.<engine>;
    with load=False only its name, once its file is found."""
    engine = cell["engine"]
    full = "larkbench.reference." + cell.get("reference", {}).get(engine,
                                                                   engine)
    if importlib.util.find_spec(full) is None:
        raise SpecError(f"no reference module {full} for engine {engine!r}")
    return importlib.import_module(full) if load else full


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """metrics/<name>.py: `read(ctx) -> float | None`."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    if not os.path.exists(path):
        raise SpecError(f"no reader metrics/{name}.py")
    return _module(path, "larkbench_metric_" + name.replace(".", "_")
                   .replace("-", "_"))


def kernel_counts() -> list:
    """Every kernels/<kind>.py: `KIND`, `match(event name, cell,
    trials_per_device)` and `bytes_per_call(cell, trials_per_device)`."""
    kdir = os.path.join(BENCH_DIR, "kernels")
    return [_module(os.path.join(kdir, f), "larkbench_kernel_" + f[:-3])
            for f in sorted(os.listdir(kdir))
            if f.endswith(".py") and not f.startswith("_")]


def peaks(device_kind: str) -> dict:
    """peaks/<device kind, spaces as _>.json; an unknown kind is an
    error, never a default."""
    slug = re.sub(r"[^A-Za-z0-9_.-]", "_", device_kind)
    path = os.path.join(BENCH_DIR, "peaks", slug + ".json")
    if not os.path.exists(path):
        raise SpecError(f"no peaks for device kind {device_kind!r} "
                        f"(peaks/{slug}.json)")
    return _load_json(path)


def kernel_classifier(cell: dict, trials: int):
    """name -> kernel kind of the first kernels/ file that claims it."""
    kernels = kernel_counts()

    def kind(name: str):
        for k in kernels:
            if k.match(name, cell, trials):
                return k.KIND
        return None
    return kind
