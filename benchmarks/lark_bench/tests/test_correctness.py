"""The comparison that decides `correct` must fail what it is there to
catch.  On the CPU, at a size a test run holds (31 nodes, 128
partitions, 8 trials, every trial sampled), with the Pallas kernels
interpreted:

- the control: the reference with a broken guarantee (a commit reaches
  one cluster replica fewer than rf) put in the program's place, read
  against the reference, fails the cell's limits;
- a whole run of the harness (all but its look for a chip) with the
  timed path broken underneath comes out not correct, once for each
  fault these cells can have: a step that returns its state unchanged,
  half of the batch left out (the rest copied in its place), and an
  answer altered where it is produced (an availability bit, and in the
  latency cell a first-touch charge); a sound run comes out correct.

    python3 -m pytest benchmarks/lark_bench/tests/test_correctness.py -q
"""
import json
import os
import shutil
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(CHECKOUT, "src"))

import run  # noqa: E402
from larkbench import compare, program, spec  # noqa: E402

CELLS = ("rf2-avail-iid", "rf2-zoo-rolling", "rf3-latency-ycsb-a")


def make_small(root):
    """A checkout at `root` holding the benchmark's cells cut to 31 nodes,
    128 partitions and 8 trials a chip, each call one chunk, 8 trials
    sampled (every trial of a one-chip cell)."""
    here = root / "benchmarks" / "lark_bench"
    shutil.copytree(BENCH, here, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "testdata"))
    bench = spec.benchmark()
    for c in bench["configs"]:
        path = root / c["file"]
        conf = json.loads(path.read_text())
        conf.update(n=31, partitions=128, trials_per_chip=8)
        path.write_text(json.dumps(conf))
    for t in {w["traffic"] for w in bench["workloads"]}:
        path = here / "traffic" / f"{t}.json"
        mix = json.loads(path.read_text())
        mix.update(chunks_per_call=1, reference_trials=8)
        path.write_text(json.dumps(mix))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    return make_small(tmp_path_factory.mktemp("small"))


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))


def _run(small, name, seed=2 ** 32 + 17):
    args = run.parse(["--workload", name, "--seed", str(seed),
                      "--seconds", "0", "--trace", "0"])
    return run.run(args, require_tpu=False, cells=small)


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_limits(small, name):
    import importlib
    cell = spec.cell(name, small)
    ref_mod = importlib.import_module(
        f"larkbench.reference.{cell['engine']}")
    cs = program.chunk_steps(cell["engine"])
    for seed in (11, 2 ** 31 + 5, 2 ** 33 + 1):
        kw = dict(seed=seed, trials=np.arange(cell["trials"]), chunks=1,
                  chunk_steps=cs)
        ref = ref_mod.simulate(cell, **kw)
        ctrl = ref_mod.simulate(cell, acks=cell["rf"] - 1, **kw)
        values = compare.readings(
            compare.as_view(ctrl), ref, np.arange(cell["trials"]),
            partitions=cell["partitions"], horizon=cell["horizon"],
            calls_differ=0, failed=0)
        ok, _ = compare.judge(values, cell["limits"])
        assert not ok, values
        assert values["frac_rel_gap"] > 10 * cell["limits"]["frac_rel_gap"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(small, name):
    res = _run(small, name)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 8 and res["failed"] == 0


def _module(cell):
    from repro.core import availability_batched, downtime_batched
    return availability_batched if cell == CELLS[0] else downtime_batched


def _state_unchanged(mod, monkeypatch):
    make = mod._make_step

    def make_frozen(*a, **kw):
        step = make(*a, **kw)
        return lambda carry, s: (carry, step(carry, s)[1])
    monkeypatch.setattr(mod, "_make_step", make_frozen)


def _half_batch(mod, monkeypatch):
    import jax
    make = mod._make_chunk_runner

    def make_half(step, carry, **kw):
        run_chunk = make(step, carry, **kw)

        def half(leaf, axis):
            h = leaf.shape[axis] // 2
            idx = np.concatenate([np.arange(h), np.arange(h)])
            return jax.numpy.take(leaf, idx, axis=axis)

        def run_half(c, s0):
            c, ys = run_chunk(c, s0)
            return (tuple(half(x, 0) for x in c),
                    tuple(half(y, 1) for y in ys))
        return run_half
    monkeypatch.setattr(mod, "_make_chunk_runner", make_half)


def _answer_altered(mod, monkeypatch):
    step_eval = mod.step_eval

    def altered(spec_, *a, **kw):
        out = step_eval(spec_, *a, **kw)
        lark = out.lark.at[:, 0].set(~out.lark[:, 0])
        return out._replace(lark=lark)
    monkeypatch.setattr(mod, "step_eval", altered)


def _charge_altered(mod, monkeypatch):
    """The latency op's first-touch charge off by one part in 2^10."""
    step = mod.client_latency_step

    def altered(*a, **kw):
        nd, dup, qh, qs, qq = step(*a, **kw)
        return nd, dup * (1 + 2.0 ** -10), qh, qs, qq
    monkeypatch.setattr(mod, "client_latency_step", altered)


def test_window_without_restart_waves_fails(small, tmp_path):
    """A rolling-restart window that ends before its first wave does not
    run the restart path, and is not correct."""
    root = tmp_path / "late"
    shutil.copytree(small, root)
    path = root / "benchmarks" / "lark_bench" / "traffic" / "zoo-rolling.json"
    mix = json.loads(path.read_text())
    mix["scenario_knobs"]["restart_period"] = 10 ** 5
    path.write_text(json.dumps(mix))
    res = _run(str(root), "rf2-zoo-rolling")
    assert not res["correct"]
    assert res["checks"]["restart_short"]["value"] == 8, res["checks"]


@pytest.mark.parametrize("name,fault", [
    (name, fault) for name in CELLS
    for fault in (_state_unchanged, _half_batch, _answer_altered)]
    + [("rf3-latency-ycsb-a", _charge_altered)])
def test_fault_is_caught(small, name, fault, monkeypatch):
    fault(_module(name), monkeypatch)
    res = _run(small, name)
    assert not res["correct"], res["checks"]
