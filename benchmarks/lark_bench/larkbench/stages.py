"""Reduce a trace of the window to the program's own names: the device
time of each stage of the simulated step, and the device's idle time in
each phase of the traced engine call.

The program puts a `jax.named_scope` around each stage of its scanned
step (`lark_node_advance`, `lark_rank_gather`, ...).  XLA keeps the scope
path in each op's `op_name` (op_names.py reads it from the trace).  An
op belongs to the last stage named in its path; a fusion is named after
its root op, so it counts under its root's stage.  The program's host
spans (`lark.call`, `lark.setup`, `lark.chunk_program`, `lark.dispatch`,
`lark.drain`, `lark.stop_test`) carry the call's id and the chunk as
stats.

A program without these names gives no stage and no span here, and each
reader then returns None.  Idle time is the part of a span's interval
that the union of device-op intervals leaves uncovered (trace.py's
arithmetic).
"""
from __future__ import annotations

import functools

from larkbench import op_names, trace

STAGES = ("lark_node_advance", "lark_rank_gather", "lark_step_eval",
          "lark_roster", "lark_node_counts", "lark_protocols",
          "lark_latency")
CALL_SPAN = "lark.call"
SETUP_SPANS = ("lark.setup", "lark.chunk_program")
DRAIN_SPAN = "lark.drain"
SPAN_PREFIX = "lark."


def stage_of(op_name) -> str | None:
    """The last step stage named in a scope path, or None."""
    for part in reversed(str(op_name).split("/")):
        if part in STAGES:
            return part
    return None


def reduce_planes(planes, op_name_of: dict) -> dict:
    """{"window": (start, end) | None, "devices": {id: {"ops": [(start,
    end)], "stage_ns": {stage: leaf-op time}}}, "spans": [(name, start,
    end, {stat: value})]}: device ops and stage times inside the window
    (trace.py's reduction), and the program's host spans with their
    stats.  `op_name_of` maps a device-op event name to its op_name."""
    planes = list(planes)
    base = trace.reduce_planes(planes)
    window = base["window"]
    devices = {}
    for dev, ops in base["devices"].items():
        if window is not None:
            ops = [o for o in ops if o[1] > window[0] and o[0] < window[1]]
        stage_ns = {}
        for s, e, name in ops:
            stage = stage_of(op_name_of.get(name))
            if stage is not None and trace._opcode(
                    trace.op_label(name)) not in trace.CONTAINERS:
                stage_ns[stage] = stage_ns.get(stage, 0.0) + (e - s)
        devices[dev] = {"ops": [(s, e) for s, e, _ in ops],
                        "stage_ns": stage_ns}
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
              dict(ev.stats))
             for plane in planes if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events
             if ev.name.startswith(SPAN_PREFIX)]
    return {"window": window, "devices": devices,
            "spans": sorted(spans, key=lambda sp: sp[1])}


@functools.lru_cache(maxsize=2)
def read(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes,
                         op_names.read(path))


def of(ctx) -> dict | None:
    """The reduction of the run's trace (ctx["xplane"], which run.py
    passes), None without one."""
    path = ctx.get("xplane")
    return read(path) if path else None


def stage_ms_per_step(ctx, stage: str):
    """Leaf-op device time under `stage` per simulated step, the mean
    over the chips; None when no op ran under it."""
    tr = of(ctx)
    if tr is None or not tr["devices"] or ctx["steps"] <= 0:
        return None
    times = [d["stage_ns"].get(stage, 0.0) for d in tr["devices"].values()]
    if not any(times):
        return None
    return sum(times) / len(times) / ctx["steps"] / 1e6


def traced_call(tr):
    """(call span, the spans of its phases) of the one engine call in the
    window, or None."""
    if tr["window"] is None:
        return None
    lo, hi = tr["window"]
    calls = [sp for sp in tr["spans"]
             if sp[0] == CALL_SPAN and sp[1] >= lo and sp[2] <= hi]
    if len(calls) != 1:
        return None
    call = calls[0]
    return call, [sp for sp in tr["spans"] if sp[0] != CALL_SPAN
                  and sp[3].get("call") == call[3].get("call")
                  and call[1] <= sp[1] and sp[2] <= call[2]]


def call_setup_idle_s(ctx):
    """Device-idle seconds inside the traced call's set-up and
    chunk-program spans, the mean over the chips."""
    tr = of(ctx)
    got = traced_call(tr) if tr and tr["devices"] else None
    if got is None:
        return None
    spans = [sp for sp in got[1] if sp[0] in SETUP_SPANS]
    if not spans:
        return None
    idle = [sum((e - s) - trace.union_ns(d["ops"], s, e)
                for _, s, e, _ in spans) for d in tr["devices"].values()]
    return sum(idle) / len(idle) / 1e9


def drain_idle_ms_per_chunk(ctx):
    """For each drain of the traced call, the longest device-idle gap
    that overlaps it (chunk k's last op to chunk k+1's first, clipped to
    the call), summed over the drains and divided by their number; the
    mean over the chips, in ms."""
    tr = of(ctx)
    got = traced_call(tr) if tr and tr["devices"] else None
    if got is None:
        return None
    (_, lo, hi, _), phases = got
    drains = [sp for sp in phases if sp[0] == DRAIN_SPAN]
    if not drains:
        return None
    per_dev = []
    for d in tr["devices"].values():
        idle = trace.gaps(d["ops"], lo, hi)
        per_dev.append(sum(max([ge - gs for gs, ge in idle
                                if gs < e and ge > s], default=0.0)
                           for _, s, e, _ in drains))
    return sum(per_dev) / len(per_dev) / len(drains) / 1e6
