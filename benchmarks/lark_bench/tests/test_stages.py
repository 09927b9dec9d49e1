"""Tests of the reduction of a trace to the program's own names
(larkbench/stages.py, larkbench/op_names.py) and of the six readers
built on it, on the CPU: a traced v5e window call of rf2-zoo-rolling,
trimmed (testdata/trace_rf2-zoo-rolling.json; its "what" says how),
the recording of a program without names
(testdata/trace_rf2-avail-iid.json), a trace file written here by the
CPU profiler, and an XSpace file built here.

    python3 -m pytest benchmarks/lark_bench/tests/test_stages.py -q
"""
import json
import os
import sys
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from larkbench import op_names, spec, stages, trace  # noqa: E402

ZOO = os.path.join(BENCH, "testdata", "trace_rf2-zoo-rolling.json")
UNNAMED = os.path.join(BENCH, "testdata", "trace_rf2-avail-iid.json")

STAGE_METRICS = {"node_advance_ms_per_step": "lark_node_advance",
                 "rank_gather_ms_per_step": "lark_rank_gather",
                 "roster_ms_per_step": "lark_roster",
                 "node_counts_ms_per_step": "lark_node_counts"}
PHASE_METRICS = ("drain_idle_ms_per_chunk", "call_setup_idle_s")


def _recorded(path):
    """(planes shaped like the profiler's, {event name: op_name}, steps)
    of a recorded extract; a host event may carry its stats."""
    with open(path) as fh:
        raw = json.load(fh)

    def ev(name, start, duration, stats=None):
        return SimpleNamespace(name=name, start_ns=start,
                               duration_ns=duration,
                               stats=list((stats or {}).items()))
    planes = [SimpleNamespace(name=p["name"], lines=[
        SimpleNamespace(name=ln["name"],
                        events=[ev(*e) for e in ln["events"]])
        for ln in p["lines"]]) for p in raw["planes"]]
    return planes, raw.get("op_names", {}), raw.get("steps")


@pytest.fixture(scope="module")
def zoo():
    planes, names, steps = _recorded(ZOO)
    return stages.reduce_planes(planes, names), planes, names, steps


def _ctx(monkeypatch, tr, steps):
    """A reader's context whose trace reduces to `tr`."""
    monkeypatch.setattr(stages, "of", lambda ctx: tr)
    return {"steps": steps, "summary": {"window_ns": 1}}


@pytest.mark.parametrize("op_name,stage", [
    ("jit(_chunk)/while/body/closed_call/lark_roster/gather:",
     "lark_roster"),
    ("jit(_chunk)/while/body/closed_call/lark_step_eval/"
     "lark_fused_downtime/pallas_call:", "lark_step_eval"),
    ("jit(_chunk)/while/body/closed_call/lark_node_advance/"
     "jit(searchsorted)/vmap(vmap())/while/body/closed_call/gather:",
     "lark_node_advance"),
    ("jit(wrapped)/lark_fused_downtime/pallas_call:", None),
    ("jit(_chunk)/while/body/dynamic_update_slice:", None),
    (None, None),
])
def test_stage_of(op_name, stage):
    assert stages.stage_of(op_name) == stage


def _leaf(name):
    return trace._opcode(trace.op_label(name)) not in trace.CONTAINERS


def test_recorded_ops_fall_under_their_stage(zoo):
    """On the chip each heavy op of the zoo step lands in the stage that
    issued it: the seat gathers of the up mask in the roster, the
    (trials, partitions) int32 gathers in the node counts, the
    geometric draw in the node advance, the rank-space gather in its
    own stage, and the Mosaic kernel in the step eval."""
    _, planes, names, _ = zoo
    want = {"pred[524288]": "lark_roster",
            "s32[262144]": "lark_node_counts",
            "f32[9920]": "lark_node_advance",
            "pred[634880,64]": "lark_rank_gather"}
    seen = set()
    for ev in planes[0].lines[0].events:
        label = trace.op_label(ev.name)
        shape = label.split(" ")[-1]
        if "fusion" in label and shape in want:
            assert stages.stage_of(names[ev.name]) == want[shape], label
            seen.add(shape)
        if 'custom_call_target="tpu_custom_call"' in ev.name:
            assert stages.stage_of(names[ev.name]) == "lark_step_eval"
            seen.add("kernel")
    assert seen == set(want) | {"kernel"}


def test_stage_time_is_leaf_op_time(zoo):
    """Each stage's time is the summed duration of its leaf ops inside
    the window, counted here event by event."""
    tr, planes, names, _ = zoo
    lo, hi = tr["window"]
    expect = {}
    for ev in planes[0].lines[0].events:
        stage = stages.stage_of(names.get(ev.name))
        if stage and _leaf(ev.name) and ev.start_ns < hi \
                and ev.start_ns + ev.duration_ns > lo:
            expect[stage] = expect.get(stage, 0) + ev.duration_ns
    assert tr["devices"][0]["stage_ns"] == pytest.approx(expect)
    assert set(expect) == set(stages.STAGES) - {"lark_latency"}


def test_recorded_call_phases(zoo):
    tr, *_ = zoo
    (name, lo, hi, args), phases = stages.traced_call(tr)
    assert name == "lark.call" and args["engine"] == "downtime"
    assert tr["window"][0] <= lo < hi <= tr["window"][1]
    chunks = sorted(a["chunk"] for n, *_, a in phases if n == "lark.drain")
    assert chunks == list(range(3))
    count = {n: sum(1 for p in phases if p[0] == n)
             for n in ("lark.setup", "lark.chunk_program", "lark.dispatch",
                       "lark.stop_test")}
    assert count == {"lark.setup": 1, "lark.chunk_program": 1,
                     "lark.dispatch": 2, "lark.stop_test": 3}


def _covered(ops, t):
    return any(s <= t < e for s, e in ops)


def _brute_idle(ops, lo, hi):
    """Uncovered length of [lo, hi) and its uncovered stretches, from
    every op boundary (no interval merging)."""
    pts = sorted({lo, hi} | {x for s, e in ops for x in (s, e)
                             if lo < x < hi})
    idle, runs = 0.0, []
    for a, b in zip(pts, pts[1:]):
        if not _covered(ops, a):
            idle += b - a
            if runs and runs[-1][1] == a:
                runs[-1] = (runs[-1][0], b)
            else:
                runs.append((a, b))
    return idle, runs


def test_phase_idle_by_brute_force(monkeypatch, zoo):
    tr, *_ = zoo
    ops = tr["devices"][0]["ops"]
    (_, clo, chi, _), phases = stages.traced_call(tr)
    setup = sum(_brute_idle(ops, s, e)[0] for n, s, e, _ in phases
                if n in ("lark.setup", "lark.chunk_program"))
    _, runs = _brute_idle(ops, clo, chi)
    drains = [(s, e) for n, s, e, _ in phases if n == "lark.drain"]
    drain = sum(max([b - a for a, b in runs if a < e and b > s],
                    default=0.0) for s, e in drains) / len(drains)
    ctx = _ctx(monkeypatch, tr, 1536)
    assert stages.call_setup_idle_s(ctx) == pytest.approx(setup / 1e9)
    assert stages.drain_idle_ms_per_chunk(ctx) == pytest.approx(drain / 1e6)
    assert 0 < drain / 1e6 < 100 and 0 < setup / 1e9 < 10


def test_recorded_phases_read_as_on_the_chip(monkeypatch, zoo):
    """The fixture keeps every idle gap of 20 us or more, so it reads
    the drain gaps as the harness read the whole trace on the chip, and
    the set-up idle within the gaps it merged."""
    tr, _, _, steps = zoo
    ctx = _ctx(monkeypatch, tr, steps)
    assert stages.drain_idle_ms_per_chunk(ctx) == \
        pytest.approx(13.479170666666667, rel=1e-12)
    assert stages.call_setup_idle_s(ctx) == \
        pytest.approx(1.143809528, rel=1e-3)


@pytest.mark.parametrize("name", list(STAGE_METRICS) + list(PHASE_METRICS))
def test_readers_on_recorded_trace(monkeypatch, zoo, name):
    tr, _, _, steps = zoo
    ctx = _ctx(monkeypatch, tr, steps)
    got = spec.metric_reader(name).read(ctx)
    if name in STAGE_METRICS:
        want = tr["devices"][0]["stage_ns"][STAGE_METRICS[name]] \
            / steps / 1e6
    else:
        want = getattr(stages, name)(ctx)
    assert got is not None and got == pytest.approx(want) and got > 0


@pytest.mark.parametrize("name", list(STAGE_METRICS) + list(PHASE_METRICS))
def test_readers_silent_without_names(monkeypatch, name):
    """A program that names no stage and opens no span (the recording
    of one) leaves every reader with nothing to read."""
    planes, names, _ = _recorded(UNNAMED)
    assert names == {}
    ctx = _ctx(monkeypatch, stages.reduce_planes(planes, names), 3072)
    assert spec.metric_reader(name).read(ctx) is None


def test_absent_stage_and_extra_call_are_silent(monkeypatch, zoo):
    tr, _, _, steps = zoo
    ctx = _ctx(monkeypatch, tr, steps)
    assert stages.stage_ms_per_step(ctx, "lark_latency") is None
    call = next(sp for sp in tr["spans"] if sp[0] == "lark.call")
    two = dict(tr, spans=tr["spans"] + [call])
    ctx = _ctx(monkeypatch, two, steps)
    assert stages.drain_idle_ms_per_chunk(ctx) is None
    assert stages.call_setup_idle_s(ctx) is None
    ctx = _ctx(monkeypatch, dict(tr, window=None), steps)
    assert stages.drain_idle_ms_per_chunk(ctx) is None


def test_op_names_read_from_event_metadata(tmp_path):
    """op_names reads the `tf_op` stat of each device plane's event
    metadata, held as a string or as a reference to a stat name, and
    nothing from other planes or stats."""
    space = op_names._xspace_class()()
    dev = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "hlo_category"),
                      (3, "jit(_chunk)/lark_roster/gather:")):
        dev.stat_metadata.add(key=key).value.name = name
    md = dev.event_metadata.add(key=7).value
    md.name = "%fusion.1 = pred[524288] fusion()"
    md.stats.add(metadata_id=2, str_value="loop fusion")
    md.stats.add(metadata_id=1, str_value="jit(_chunk)/lark_protocols/and:")
    md = dev.event_metadata.add(key=8).value
    md.name = "%fusion.2 = s32[262144] fusion()"
    md.stats.add(metadata_id=1, ref_value=3)
    host = space.planes.add(name="/host:CPU")
    host.stat_metadata.add(key=1).value.name = "tf_op"
    md = host.event_metadata.add(key=1).value
    md.name = "host op"
    md.stats.add(metadata_id=1, str_value="not a device op")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    assert op_names.read(str(path)) == {
        "%fusion.1 = pred[524288] fusion()": "jit(_chunk)/lark_protocols/and:",
        "%fusion.2 = s32[262144] fusion()": "jit(_chunk)/lark_roster/gather:"}


def test_trace_file_is_found_by_its_window(tmp_path):
    """The readers read the trace file the harness names in
    ctx["xplane"], window and spans as recorded, and nothing without
    one.  (The name dates from a search by window that went; the
    repository's test run collects this test by it.)"""
    import jax
    d = tmp_path / "trace"
    jax.profiler.start_trace(str(d))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("lark.call", call=4,
                                          engine="downtime"):
            with jax.profiler.TraceAnnotation("lark.drain", call=4,
                                              chunk=0):
                pass
    jax.profiler.stop_trace()
    path = trace.xplane_path(str(d))
    lo, hi = trace.read(path)["window"]
    ctx = {"steps": 1, "summary": {"window_ns": hi - lo}, "xplane": path}
    tr = stages.of(ctx)
    assert tr is stages.read(path) and tr["window"] == (lo, hi)
    assert stages.of(dict(ctx, xplane=None)) is None
    spans = {name: args for name, _, _, args in tr["spans"]}
    assert spans["lark.call"] == {"call": 4, "engine": "downtime"}
    assert spans["lark.drain"] == {"call": 4, "chunk": 0}
    # a CPU trace has no device plane: nothing for any reader
    for name in list(STAGE_METRICS) + list(PHASE_METRICS):
        assert spec.metric_reader(name).read(ctx) is None
