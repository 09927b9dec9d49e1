"""The system under test: the public engine entry points, called as a
user calls them, and their results read into the shape the reference
returns.

Only what a user sets is set here (the deployment, the scenario, the
protocols, the seed, the trial count and mesh, backend="pallas",
packed=True, a step budget, trajectory=True); tile, chunk length and the
rest stay at the program's defaults.  The stopping rule is switched off
by a min_ticks above the horizon, so every call runs whole chunks.  A
deployment's own engine knobs (its config's `engine_knobs`) follow, as
they are written there.
"""
from __future__ import annotations

import inspect

import numpy as np

from larkbench.spec import SpecError

ENGINES = ("availability", "downtime", "latency")


def entry(engine: str):
    if engine == "availability":
        from repro.core.availability_batched import \
            simulate_availability_batched
        return simulate_availability_batched
    if engine == "downtime":
        from repro.core.downtime_batched import simulate_downtime_batched
        return simulate_downtime_batched
    if engine == "latency":
        from repro.core.client_latency import simulate_client_latency
        return simulate_client_latency
    raise ValueError(f"unknown engine {engine!r}; expected one of {ENGINES}")


def chunk_steps(engine: str) -> int:
    """The program's default chunk length (steps per device call); the
    latency engine passes it through to the downtime engine."""
    fn = entry("downtime" if engine == "latency" else engine)
    return inspect.signature(fn).parameters["chunk_steps"].default


def kwargs(cell: dict, *, seed: int, chunks: int) -> dict:
    """Keyword arguments of one call that runs `chunks` whole chunks; the
    cell's `engine_knobs` come last, and one that names a key set here is
    an error, never an override."""
    kw = dict(n=cell["n"], partitions=cell["partitions"], rf=cell["rf"],
              p=cell["p"], downtime=cell["downtime"], trials=cell["trials"],
              seed=seed, max_ticks=cell["horizon"],
              min_ticks=cell["horizon"] + 1, backend="pallas", packed=True,
              devices=cell["chips"], trajectory=True,
              max_steps=chunks * chunk_steps(cell["engine"]) + 1,
              **cell["scenario_knobs"])
    if cell["engine"] in ("downtime", "latency"):
        kw.update(dupres_ticks=cell["dupres_ticks"],
                  hist_bins=cell["hist_bins"],
                  rebuild_model=cell["rebuild_model"],
                  node_bandwidth_gibps=cell["node_bandwidth_gibps"])
        if cell["rebuild_model"] == "reconfig":
            kw.update(rebuild_ticks_per_gib=cell["rebuild_ticks_per_gib"],
                      size_dist=cell["size_dist"],
                      size_skew=cell["size_skew"])
        else:
            kw.update(rebuild_steps=cell["rebuild_steps"])
    if cell["engine"] == "downtime":
        kw.update(engines=tuple(cell["protocols"]),
                  lease_ticks=cell["lease_ticks"],
                  view_change_ticks=cell["view_change_ticks"])
    if cell["engine"] == "latency":
        kw.update(key_zipf=cell["key_zipf"], read_frac=cell["read_frac"],
                  requests_per_tick=cell["requests_per_tick"],
                  slo_ticks=cell["slo_ticks"])
    knobs = cell.get("engine_knobs", {})
    clash = sorted(set(knobs) & set(kw))
    if clash:
        raise SpecError(f"engine_knobs {clash} would override what the "
                        f"harness sets")
    kw.update(knobs)
    return kw


def call(cell: dict, *, seed: int, chunks: int):
    return entry(cell["engine"])(**kwargs(cell, seed=seed, chunks=chunks))


def partition_ticks(cell: dict, results) -> float:
    """Simulated partition-ticks of the calls: partitions x trials x the
    mean elapsed ticks per trial that each call reports."""
    return sum(cell["partitions"] * cell["trials"] * float(r.ticks)
               for r in results)


def view(cell: dict, res) -> dict:
    """A result as the reference reports one: per-trial elapsed ticks,
    fractions and trajectories, pooled event counts and histograms."""
    per_trial = {}
    if cell["engine"] == "latency":
        per_trial = {f"latency_{k}": v
                     for k, v in res.downtime.latency_raw.items()
                     if k != "now"}
        res = res.downtime
    traj = dict(res.trajectory)
    if cell["engine"] == "availability":
        fractions = {"u_lark_trials": res.u_lark_trials,
                     "u_maj_trials": res.u_maj_trials}
        events = {"lark_events": res.lark_events,
                  "maj_events": res.maj_events}
        hists = {}
        pooled = {"u_lark": res.u_lark, "u_maj": res.u_maj}
    else:
        names = list(res.engines)
        fractions = {f"pause_{x}_trials": res.engine_stats(x)["pause_trials"]
                     for x in names}
        events = {f"{x}_events": res.engine_stats(x)["events"]
                  for x in names}
        hists = {f"hist_{x}": np.asarray(res.engine_stats(x)["hist"])
                 for x in names}
        pooled = {f"pause_{x}": res.engine_stats(x)["pause"]
                  for x in names}
    return {"now": np.maximum(traj["times"][-1].astype(np.int64), 1),
            "ticks": res.ticks, "fractions": fractions, "events": events,
            "hists": hists, "pooled": pooled, "trajectory": traj,
            "per_trial": per_trial}
