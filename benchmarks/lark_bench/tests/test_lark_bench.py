"""Tests of the benchmark's harness on the CPU: the trace reducer on a
recorded trace, the metric arithmetic, the ticks count, discovery of
cells and their pieces by name, and the refusal to run without a TPU.

    python3 -m pytest benchmarks/lark_bench/tests -q
"""
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

from larkbench import compare, spec, trace  # noqa: E402

RECORDED = os.path.join(BENCH, "testdata", "trace_rf2-avail-iid.json")


def _planes(path):
    """A recorded extract as objects shaped like the profiler's planes."""
    with open(path) as fh:
        raw = json.load(fh)
    ev = lambda n, s, d: SimpleNamespace(name=n, start_ns=s, duration_ns=d)
    return [SimpleNamespace(name=p["name"], lines=[
        SimpleNamespace(name=ln["name"],
                        events=[ev(*e) for e in ln["events"]])
        for ln in p["lines"]]) for p in raw["planes"]]


def _brute_union(intervals, lo, hi):
    pts = sorted({lo, hi} | {max(lo, min(hi, x)) for s, e, *_ in intervals
                             for x in (s, e)})
    return sum(b - a for a, b in zip(pts, pts[1:])
               if any(s <= a and b <= e for s, e, *_ in intervals))


# -- trace reduction ---------------------------------------------------------

def test_reducer_on_recorded_trace():
    tr = trace.reduce_planes(_planes(RECORDED))
    lo, hi = tr["window"]
    assert tr["devices"], "the recorded trace has a TPU plane"
    cell = spec.cell("rf2-avail-iid")
    summ = trace.summarize(tr, spec.kernel_classifier(cell, 64))
    assert summ["window_ns"] == hi - lo
    for dev, ops in tr["devices"].items():
        d = summ["devices"][dev]
        assert 0 < d["busy_ns"] <= summ["window_ns"]
        assert d["busy_ns"] == pytest.approx(_brute_union(ops, lo, hi))
        kernel_ops = [o for o in ops if "tpu_custom_call" in o[2]
                      and o[1] > lo and o[0] < hi]
        t, calls = d["kernels"].get("fused_pac", (0.0, 0))
        assert calls == len(kernel_ops)
        assert t == sum(e - s for s, e, _ in kernel_ops) <= d["busy_ns"]
    assert len(summ["device_ops"]) <= 10 and len(summ["idle_gaps"]) <= 10
    idle = sum(s for _, s in summ["idle_gaps"])
    assert idle <= summ["window_ns"] / 1e9


def test_union_and_gaps():
    iv = [(0, 10, "a"), (5, 20, "b"), (30, 40, "c"), (35, 36, "d"),
          (50, 70, "e")]
    assert trace.union_ns(iv, 0, 60) == 20 + 10 + 10
    assert trace.gaps(iv, 0, 60) == [(20, 30), (40, 50)]
    assert trace.union_ns(iv, 0, 60) == _brute_union(iv, 0, 60)
    assert trace.union_ns([], 0, 5) == 0


# -- metric arithmetic --------------------------------------------------------

def _ctx(busy_ns, kernels, *, window_ns=1e9, steps=1000, kbytes=8.19e5):
    return {"summary": {"window_ns": window_ns, "devices": {
        i: {"busy_ns": b, "kernels": k}
        for i, (b, k) in enumerate(zip(busy_ns, kernels))}},
        "steps": steps, "peaks": {"hbm_bytes_per_s": 819e9},
        "kernel_bytes": {"fused_pac": kbytes}}


@pytest.mark.parametrize("name,expect", [
    ("device_idle_pct", 100.0 * (1 - (0.6 + 0.8) / 2)),
    ("device_ms_per_step", (0.6e9 + 0.8e9) / 2 / 1000 / 1e6),
    ("kernel_ms_per_step", (0.2e9 + 0.1e9) / 2 / 1000 / 1e6),
    # 1000 calls x 8.19e5 bytes at 819 GB/s take 1 ms: 0.5% of 0.2 s,
    # 1% of 0.1 s
    ("kernel_hbm_roofline_pct", (0.5 + 1.0) / 2),
])
def test_metric_readers(name, expect):
    ctx = _ctx([0.6e9, 0.8e9], [{"fused_pac": (0.2e9, 1000)},
                                {"fused_pac": (0.1e9, 1000)}])
    assert spec.metric_reader(name).read(ctx) == pytest.approx(expect)


@pytest.mark.parametrize("name", ["kernel_ms_per_step",
                                  "kernel_hbm_roofline_pct"])
def test_readers_find_nothing(name):
    """A reader with nothing to read returns None, never 0."""
    assert spec.metric_reader(name).read(_ctx([1e8], [{}])) is None


def test_roofline_without_byte_count_is_silent():
    ctx = _ctx([1e8], [{"other_kernel": (1e7, 10)}])
    assert spec.metric_reader("kernel_hbm_roofline_pct").read(ctx) is None


def test_kernel_byte_counts():
    cell = spec.cell("rf2-avail-iid")
    counts = {k.KIND: k.bytes_per_call(cell, 64) for k in
              spec.kernel_counts()}
    # (64, 5, 4096) uint32 up, full and creps words + two int32 rows:
    # the 18 MB of the fused branch of ops.step_hbm_bytes
    assert counts["fused_pac"] == 3 * 64 * 5 * 4096 * 4 + 2 * 64 * 4096 * 4
    assert counts["fused_downtime_roster"] == 0
    zoo = spec.cell("rf2-zoo-rolling")
    b = {k.KIND: k.bytes_per_call(zoo, 64) for k in spec.kernel_counts()}
    words = 3 * 64 * 5 * 4096 * 4
    rows = 7 * 64 * 4096 * 4
    roster = 64 * 2 * 4096 * 4
    counts_io = 2 * 64 * 4096 * 4 + 64 * 256 * 4
    assert b["fused_downtime_roster"] == words + rows + roster + counts_io


@pytest.mark.parametrize("name,cell,kind", [
    ('%closed_call.6 = (s32[64,4096], s32[64,4096], u32[64,5,4096]) '
     'custom-call(u32[64,5,4096] %a, u32[64,5,4096] %b), '
     'custom_call_target="tpu_custom_call"', "rf2-avail-iid", "fused_pac"),
    ('%closed_call.11 = (s32[64,4096]) custom-call(u32[64,5,4096] %a, '
     's32[64,2,4096]{2,1,0} %r), custom_call_target="tpu_custom_call"',
     "rf2-zoo-rolling", "fused_downtime_roster"),
    ('%closed_call.3 = (s32[64,4096]) custom-call(u32[64,5,4096] %a), '
     'custom_call_target="tpu_custom_call"', "rf2-zoo-rolling", None),
    ('%fusion.30 = pred[634880,64] fusion(pred[64,155] %g)',
     "rf2-avail-iid", None),
])
def test_kernel_events_are_classified(name, cell, kind):
    assert spec.kernel_classifier(spec.cell(cell), 64)(name) == kind


def test_ticks_count():
    """partition_ticks_per_s counts partitions x trials x mean ticks per
    trial, over the window's calls."""
    from larkbench import program
    cell = {"partitions": 4096, "trials": 64}
    calls = [SimpleNamespace(ticks=30000), SimpleNamespace(ticks=11632)]
    assert program.partition_ticks(cell, calls) == 4096 * 64 * 41632


# -- the comparison ------------------------------------------------------------

@pytest.mark.parametrize("trials,count", [(64, 8), (256, 8), (8, 8), (8, 3)])
def test_sample_trials_cover_strata(trials, count):
    s = compare.sample_trials(2 ** 31 + 12345, trials, count)
    assert len(set(s)) == count and s.min() >= 0 and s.max() < trials
    edges = np.linspace(0, trials, count + 1).astype(int)
    assert all(lo <= x < hi for x, lo, hi in zip(s, edges[:-1], edges[1:]))
    assert list(s) == list(compare.sample_trials(2 ** 31 + 12345, trials,
                                                 count))


def _ref(S=2, steps=4):
    return {"now": np.full(S, 100), "partitions": 4,
            "fractions": {"u_lark_trials": np.full(S, 0.25)},
            "sums": {"u_lark": np.full(S, 100.0)},
            "events": {"lark_events": np.full(S, 3)}, "hists": {},
            "trajectory": {"times": np.arange(steps * S).reshape(steps, S)}}


def test_readings_exact_and_altered():
    ref = _ref()
    view = compare.as_view(ref)
    r = compare.readings(view, ref, [0, 1], partitions=4, horizon=1000,
                         calls_differ=0, failed=0)
    assert r == {"traj_mismatch": 0, "frac_rel_gap": 0.0,
                 "pooled_mismatch": 0, "pooled_rel_gap": 0.0,
                 "calls_differ": 0, "horizon_trials": 0, "failed": 0}
    bad = compare.as_view(_ref())
    bad["trajectory"]["times"][2, 1] += 1
    bad["fractions"]["u_lark_trials"][0] *= 1.5
    bad["events"]["lark_events"] += 1
    r = compare.readings(bad, ref, [0, 1], partitions=4, horizon=100,
                         calls_differ=1, failed=0)
    assert r["traj_mismatch"] == 1 and r["frac_rel_gap"] == 0.5
    assert r["pooled_mismatch"] == 1 and r["horizon_trials"] == 2
    ok, rows = compare.judge(r, {k: 0 for k in r})
    assert not ok and len(rows) == len(r)


def test_restart_waves_are_counted():
    """A rolling-restart mix must hold `min_restart_waves` waves in every
    sampled trial's window; the reference counts the waves fired."""
    from larkbench.reference.common import Cluster
    ref = dict(_ref(S=3), restarts=np.array([2, 1, 5]))
    r = compare.readings(compare.as_view(ref), ref, [0, 1, 2], partitions=4,
                         horizon=1000, calls_differ=0, failed=0, min_waves=2)
    assert r["restart_short"] == 1
    assert "restart_short" not in compare.readings(
        compare.as_view(ref), ref, [0, 1, 2], partitions=4, horizon=1000,
        calls_differ=0, failed=0)
    cl = Cluster(n=5, partitions=4, p=1e-3, downtime=10, seed=3,
                 horizon=10_000, restart_period=2000)
    # next wave due at 2000 (none fired), 4000 (one), 8000 (three)
    assert list(cl.waves([2000, 4000, 8000])) == [0, 1, 3]


def test_judge_needs_every_limit():
    with pytest.raises(KeyError):
        compare.judge({"traj_mismatch": 0}, {})


def test_diff_is_bitwise():
    a = {"x": np.float64(0.0), "y": [1, 2]}
    assert compare.diff(a, {"x": np.float64(0.0), "y": [1, 2]}) == []
    assert compare.diff(a, {"x": np.float64(-0.0), "y": [1, 2]})


# -- discovery by name -------------------------------------------------------

def test_every_cell_and_piece_is_found():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        c = spec.cell(w["name"])
        assert c["trials"] == c["trials_per_chip"] * w["chips"]
        assert c["horizon"] in (3_000_000, 1_000_000)
        assert set(c["limits"]) >= {"traj_mismatch", "frac_rel_gap"}
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]).read)
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v9 imaginary")
    with pytest.raises(spec.SpecError):
        spec.cell("no-such-cell")


def test_benchmark_file_shape():
    bench = spec.benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = {m["name"] for m in bench["end_to_end"]}
    assert {"partition_ticks_per_s", "setup_s"} <= names
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(CHECKOUT, c["file"]))
        assert c["file"].startswith(bench["paths"][0] + "/")
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_mix_for_a_mesh_runs_on_its_chips(tmp_path):
    """A mix that states its chips runs only on that many; the four-chip
    cell's mix is the zoo's, laid out for four."""
    def mix(name):
        with open(os.path.join(BENCH, "traffic", name + ".json")) as fh:
            return json.load(fh)
    zoo, mesh = mix("zoo-rolling"), mix("zoo-rolling-mesh")
    assert mesh.pop("chips") == 4 and mesh == zoo
    bench = spec.benchmark()
    for w in bench["workloads"]:
        if w["traffic"] == "zoo-rolling-mesh":
            w["chips"] = 1
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks" / "lark_bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec.cell("rf2-zoo-rolling", str(root))
    with pytest.raises(spec.SpecError, match="is for 4 chips"):
        spec.cell("rf2-zoo-rolling-4chip", str(root))


def test_new_cell_by_adding_files_only(tmp_path):
    """A cell is added by a config, a traffic mix and a limits file and
    one BENCHMARK.json entry; no code changes.  The config may bring its
    engine knobs and name its reference module."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks" / "lark_bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = spec.benchmark()
    here = root / "benchmarks" / "lark_bench"
    conf = json.loads((here / "configs" / "sc-rf2-n155.json").read_text())
    conf.update(name="sc-rf2-n93", n=93, engine_knobs={"racks": [0, 1, 2]},
                reference={"availability": "availability"})
    (here / "configs" / "sc-rf2-n93.json").write_text(json.dumps(conf))
    mix = json.loads((here / "traffic" / "avail-iid.json").read_text())
    mix.update(scenario="rack-pairs",
               scenario_knobs={"pair_fail_prob": 0.5})
    (here / "traffic" / "avail-rack-pairs.json").write_text(json.dumps(mix))
    lim = (here / "limits" / "rf2-avail-iid.json").read_text()
    (here / "limits" / "n93-avail-rack.json").write_text(lim)
    bench["configs"].append({"name": "sc-rf2-n93", "source": "x",
                             "file": "benchmarks/lark_bench/configs/"
                                     "sc-rf2-n93.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "n93-avail-rack",
                               "config": "sc-rf2-n93",
                               "traffic": "avail-rack-pairs", "chips": 1,
                               "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    c = spec.cell("n93-avail-rack", str(root))
    assert c["n"] == 93 and c["scenario_knobs"] == {"pair_fail_prob": 0.5}
    assert c["engine"] == "availability" and c["chips"] == 1
    assert c["engine_knobs"] == {"racks": [0, 1, 2]}
    assert spec.reference(c).__name__ == "larkbench.reference.availability"


def test_warm_up_builds_a_mesh_cells_later_chunk():
    """On a trials mesh the last warm-up call runs a second chunk, whose
    program the window's calls would otherwise build; one-chip cells warm
    up with one-chunk calls."""
    import run
    for w in spec.benchmark()["workloads"]:
        c = spec.cell(w["name"])
        got = [run.warm_up_chunks(c, i) for i in range(run.WARM_UP_CALLS)]
        assert got == ([1, 2] if c["chips"] > 1 else [1, 1]), w["name"]


# -- a deployment's own engine knobs and reference -----------------------------

#: each accepted cell's call of its engine at seed 2**32 + 17, with its
#: own chunks per call, as the harness has made it since it was written
GOLDEN_KWARGS = {
    "rf2-avail-iid": {
        "n": 155, "partitions": 4096, "rf": 2, "p": 0.001, "downtime": 10,
        "trials": 64, "seed": 4294967313, "max_ticks": 3000000,
        "min_ticks": 3000001, "backend": "pallas", "packed": True,
        "devices": 1, "trajectory": True, "max_steps": 3073},
    "rf2-zoo-rolling": {
        "n": 155, "partitions": 4096, "rf": 2, "p": 0.001, "downtime": 10,
        "trials": 64, "seed": 4294967313, "max_ticks": 1000000,
        "min_ticks": 1000001, "backend": "pallas", "packed": True,
        "devices": 1, "trajectory": True, "max_steps": 1537,
        "restart_period": 2000, "wave_width": 1, "dupres_ticks": 1,
        "hist_bins": 16, "rebuild_model": "reconfig",
        "node_bandwidth_gibps": 1.0, "rebuild_ticks_per_gib": 100,
        "size_dist": "zipf", "size_skew": 1.0,
        "engines": ("lark", "quorum", "hermes", "spinnaker"),
        "lease_ticks": 40, "view_change_ticks": 200},
    "rf3-latency-ycsb-a": {
        "n": 155, "partitions": 4096, "rf": 3, "p": 0.001, "downtime": 10,
        "trials": 64, "seed": 4294967313, "max_ticks": 1000000,
        "min_ticks": 1000001, "backend": "pallas", "packed": True,
        "devices": 1, "trajectory": True, "max_steps": 2049,
        "pair_fail_prob": 0.5, "dupres_ticks": 1, "hist_bins": 16,
        "rebuild_model": "fixed", "node_bandwidth_gibps": 1.0,
        "rebuild_steps": 100, "key_zipf": 0.99, "read_frac": 0.5,
        "requests_per_tick": 32.0, "slo_ticks": 8},
}
GOLDEN_REFERENCE = {"rf2-avail-iid": "larkbench.reference.availability",
                    "rf2-zoo-rolling": "larkbench.reference.downtime",
                    "rf3-latency-ycsb-a": "larkbench.reference.latency"}


@pytest.mark.parametrize("name", sorted(GOLDEN_KWARGS))
def test_accepted_cells_call_as_before(name):
    """The engine's keyword arguments, in order, and the reference module
    of each accepted cell."""
    from larkbench import program
    c = spec.cell(name)
    kw = program.kwargs(c, seed=2 ** 32 + 17, chunks=c["chunks_per_call"])
    assert list(kw.items()) == list(GOLDEN_KWARGS[name].items())
    assert spec.reference(c).__name__ == GOLDEN_REFERENCE[name]


def _deployment(tmp_path, **extra):
    """A checkout whose one new cell, n93-avail, runs a new config with the
    keys `extra` on the avail-iid mix; added as files only."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks" / "lark_bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    here = root / "benchmarks" / "lark_bench"
    conf = json.loads((here / "configs" / "sc-rf2-n155.json").read_text())
    conf.update(name="sc-rf2-n93", n=93, **extra)
    (here / "configs" / "sc-rf2-n93.json").write_text(json.dumps(conf))
    (here / "limits" / "n93-avail.json").write_text(
        (here / "limits" / "rf2-avail-iid.json").read_text())
    bench = spec.benchmark()
    bench["configs"].append({"name": "sc-rf2-n93", "source": "x",
                             "file": "benchmarks/lark_bench/configs/"
                                     "sc-rf2-n93.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "n93-avail", "config": "sc-rf2-n93",
                               "traffic": "avail-iid", "chips": 1,
                               "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def test_deployment_brings_knobs_and_reference(tmp_path, monkeypatch):
    """A config's `engine_knobs` reach the entry point after the harness's
    keys, as written; its `reference` names the module that run.check
    calls, here a stub."""
    import importlib.machinery
    import types

    import run
    from larkbench import program
    racks = [i % 3 for i in range(93)]
    root = _deployment(tmp_path, engine_knobs={"racks": racks},
                       reference={"availability": "stub_n93"})
    c = spec.cell("n93-avail", root)
    assert c["engine_knobs"] == {"racks": racks}
    seen = {}
    monkeypatch.setattr(program, "chunk_steps", lambda engine: 512)
    monkeypatch.setattr(program, "entry", lambda engine: lambda **kw:
                        seen.update(engine=engine, kw=kw))
    program.call(c, seed=5, chunks=1)
    assert seen["engine"] == "availability"
    assert list(seen["kw"])[-1] == "racks" and seen["kw"]["racks"] == racks
    assert seen["kw"]["n"] == 93 and seen["kw"]["seed"] == 5

    stub = types.ModuleType("larkbench.reference.stub_n93")
    stub.__spec__ = importlib.machinery.ModuleSpec(stub.__name__, None)
    calls = []

    def simulate(cell, *, seed, trials, chunks, chunk_steps):
        calls.append((cell["name"], seed, list(trials), chunks))
        return dict(_ref(S=len(trials)), partitions=cell["partitions"])
    stub.simulate = simulate
    monkeypatch.setitem(sys.modules, stub.__name__, stub)
    assert spec.reference(c) is stub
    monkeypatch.setattr(program, "view", lambda cell, res: res)
    view = compare.as_view(dict(_ref(S=c["trials"]),
                                partitions=c["partitions"]))
    ok, attempted, failed, rows = run.check(c, 7, [view])
    assert calls == [("n93-avail", 7, list(range(c["trials"])),
                      c["chunks_per_call"])]
    assert ok and attempted == c["trials"] and failed == 0, rows


@pytest.mark.parametrize("key", ["seed", "trials", "devices", "rf",
                                 "max_steps", "backend"])
def test_knob_that_collides_is_refused(tmp_path, key):
    from larkbench import program
    root = _deployment(tmp_path, engine_knobs={key: 1})
    with pytest.raises(spec.SpecError, match=key):
        program.kwargs(spec.cell("n93-avail", root), seed=5, chunks=1)


def test_missing_reference_is_refused_before_any_run(tmp_path):
    import run
    root = _deployment(tmp_path, reference={"availability": "no_such_ref"})
    with pytest.raises(spec.SpecError, match="no_such_ref"):
        run.prepare("n93-avail", require_tpu=False, cells=root)


# -- no chip, no result ------------------------------------------------------

def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmarks/lark_bench/run.py", "--workload",
         "rf2-avail-iid", "--seed", "4294967311", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_exits_without_tpu_and_prints_nothing(tmp_path):
    p = _run_cli(CHECKOUT, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_exits_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "lark_bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
