"""Reduce a JAX profiler trace of the window to device busy time, device
operations and kernel events.

A trace holds one plane per device (`/device:TPU:<i>`) whose "XLA Ops"
line carries every operation the device ran, and a host plane
(`/host:CPU`) whose threads carry host spans, among them the
benchmark's own `lark_bench.window` annotation around the traced call.
Times of all planes are on one clock, in nanoseconds.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "lark_bench.window"
WARM_UP_SPAN = "lark_bench.warm_up"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"


def xplane_path(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


#: device ops that contain others (their time is their body's)
CONTAINERS = ("while", "conditional", "call")


def op_label(name: str) -> str:
    """A short label for an op event named by its HLO text: the
    instruction name, its opcode, and its result shape when that is one
    array ("fusion.30 fusion pred[634880,64]"); other names unchanged."""
    if not name.startswith("%") or " = " not in name:
        return name
    instr, rest = name[1:].split(" = ", 1)
    shape = ""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:
        shape, _, rest = rest.partition(" ")
        shape = shape.split("{")[0]
    opcode = rest.split("(")[0]
    return " ".join(x for x in (instr, opcode, shape) if x)


def _opcode(label: str) -> str:
    parts = label.split(" ")
    return parts[1] if len(parts) > 1 else ""


def read(path: str) -> dict:
    """{"window": (start, end) | None, "devices": {id: [(start, end,
    name)]}, "host": [(start, end, name)]} from one .xplane.pb."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return reduce_planes(pd.planes)


def reduce_planes(planes) -> dict:
    devices, host, window = {}, [], None
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name) for ev in line.events]
            devices[int(m.group(2))] = sorted(ops)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    iv = (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    if ev.name == WINDOW_SPAN:
                        window = iv[:2]
                    else:
                        host.append(iv)
    return {"window": window, "devices": devices, "host": host}


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of [start, end) intervals clipped to [lo, hi)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float):
    """Idle [start, end) stretches between clipped intervals."""
    out, t = [], lo
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_label(host, s: float, e: float) -> str:
    """What the host was doing in [s, e): the shortest host span that
    covers at least half of it, else the one that overlaps it most."""
    best, best_ov, cover, cover_len = "(no host span)", 0.0, None, None
    for hs, he, name in host:
        ov = min(he, e) - max(hs, s)
        if ov <= 0:
            continue
        if ov > best_ov:
            best, best_ov = name, ov
        if 2 * ov >= e - s and (cover is None or he - hs < cover_len):
            cover, cover_len = name, he - hs
    return cover if cover is not None else best


def summarize(tr: dict, kernel_of, *, top: int = 10) -> dict:
    """Per-device busy time, kernel time and calls by kernel kind, the
    device operations that took most time (leaf ops only: a while loop's
    time is its body's), and the longest idle gaps labelled by the host
    span that covers most of each.  `kernel_of(name)` gives the kernel
    kind of a device-op event, or None."""
    if tr["window"] is None:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    lo, hi = tr["window"]
    per_dev = {}
    op_time = {}
    all_gaps = []
    for dev, ops in sorted(tr["devices"].items()):
        inside = [o for o in ops if o[1] > lo and o[0] < hi]
        busy = union_ns(inside, lo, hi)
        kern = {}
        for s, e, name in inside:
            label = op_label(name)
            if _opcode(label) not in CONTAINERS:
                op_time[label] = op_time.get(label, 0.0) + (e - s)
            kind = kernel_of(name)
            if kind is not None:
                t, c = kern.get(kind, (0.0, 0))
                kern[kind] = (t + (e - s), c + 1)
        per_dev[dev] = {"busy_ns": busy, "kernels": kern}
        all_gaps += [(e - s, s, e) for s, e in gaps(inside, lo, hi)]
    all_gaps.sort(reverse=True)
    idle = [[host_label(tr["host"], s, e), length / 1e9]
            for length, s, e in all_gaps[:top]]
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {"window_ns": hi - lo, "devices": per_dev,
            "device_ops": [[n, t / 1e9 / max(len(per_dev), 1)]
                           for n, t in ops],
            "idle_gaps": idle}
