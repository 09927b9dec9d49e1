"""The four-chip cell's comparison must fail what it is there to catch.

rf2-zoo-rolling-4chip, the zoo mix on sc-rf2-n155 over a trials mesh of
four chips (traffic/zoo-rolling-mesh.json): the engine shards its trials over four chips and the drain
gathers them back.  Here that path runs on four host devices of the CPU
(XLA_FLAGS=
--xla_force_host_platform_device_count=4, which must be set before JAX
is imported, so every run of the harness is a subprocess, as in
tests/test_sharded.py), at the size of test_correctness.py's `small`
checkout: 31 nodes, 128 partitions, 8 trials a chip (32 on the mesh),
one chunk a call, every trial sampled (the mix's 8 a chip), and the
reference run in one block a device, as on the chip.

- the control, the reference with one acknowledging replica fewer, on
  the cell's own sample, fails the cell's limits;
- a whole run of the harness (all but its look for a chip) is correct
  when sound, and not correct with the timed path broken underneath: a
  step that returns its state unchanged, half of the batch left out, the
  gather from the other chips left out (chip 0's trials in every chip's
  place), the pooled counts taken over chip 0's trials alone, and an
  availability bit altered where it is produced.

    python3 -m pytest benchmarks/lark_bench/tests/test_four_chip.py -q
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
CHECKOUT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(CHECKOUT, "src"))

from larkbench import compare, program, spec  # noqa: E402

CELL = "rf2-zoo-rolling-4chip"
CHIPS = 4


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """test_correctness.py's small checkout."""
    from test_correctness import make_small
    return make_small(tmp_path_factory.mktemp("small4"))


def _gather_left_out(mod, monkeypatch):
    """Every chip's shard of the carry and of the step outputs replaced
    by chip 0's: what the host pools when the drain never fetches the
    other chips' trials."""
    import jax
    make = mod._make_chunk_runner

    def make_one(step, carry, *, devices, **kw):
        run_chunk = make(step, carry, devices=devices, **kw)

        def first(leaf, axis):
            per = leaf.shape[axis] // devices
            idx = np.tile(np.arange(per), devices)
            return jax.numpy.take(leaf, idx, axis=axis)

        def run_one(c, s0):
            c, ys = run_chunk(c, s0)
            return (tuple(first(x, 0) for x in c),
                    tuple(first(y, 1) for y in ys))
        return run_one
    monkeypatch.setattr(mod, "_make_chunk_runner", make_one)


def _pooled_wrong(mod, monkeypatch):
    """The pause-event counts and pause-length histograms of lark and
    quorum (carry leaves 16-19, which the drain sums over trials) left
    with chip 0's trials alone: every trial's fractions and trajectory
    stay right, so only the pooled numbers can see it."""
    make = mod._make_chunk_runner

    def make_one(step, carry, *, devices, **kw):
        run_chunk = make(step, carry, devices=devices, **kw)

        def run_one(c, s0):
            c, ys = run_chunk(c, s0)
            per = c[0].shape[0] // devices
            return tuple(x.at[per:].set(0) if i in (16, 17, 18, 19) else x
                         for i, x in enumerate(c)), ys
        return run_one
    monkeypatch.setattr(mod, "_make_chunk_runner", make_one)


SCRIPT = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{tests!r}, {bench!r}]
    import pytest
    import run
    import test_correctness
    import test_four_chip
    from repro.core import downtime_batched
    fault = {fault!r}
    if fault:
        broken = getattr(test_four_chip, fault, None) \\
            or getattr(test_correctness, fault)
        broken(downtime_batched, pytest.MonkeyPatch())
    args = run.parse(["--workload", {cell!r}, "--seed", "4294967329",
                      "--seconds", "0", "--trace", "0"])
    print(json.dumps(run.run(args, require_tpu=False, cells={small!r})))
""")


FAULTS = ("_state_unchanged", "_half_batch", "_gather_left_out",
          "_pooled_wrong", "_answer_altered")


@pytest.fixture(scope="module")
def runs(small, tmp_path_factory):
    """{fault or None: the run's result}: the sound run and one run for
    each fault, each a process of its own on four CPU devices, started
    together and all waited for."""
    procs = {}
    try:
        for fault in (None,) + FAULTS:
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       XLA_FLAGS="--xla_force_host_platform_device_count=4",
                       JAX_COMPILATION_CACHE_DIR=str(
                           tmp_path_factory.mktemp("cc")),
                       PYTHONPATH=os.path.join(CHECKOUT, "src"))
            script = SCRIPT.format(tests=TESTS, bench=BENCH, fault=fault,
                                   cell=CELL, small=small)
            procs[fault] = subprocess.Popen(
                [sys.executable, "-c", script], env=env, cwd=CHECKOUT,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        out = {}
        for fault, p in procs.items():
            stdout, stderr = p.communicate(timeout=300)
            assert p.returncode == 0, stderr[-4000:]
            out[fault] = json.loads(stdout.strip().splitlines()[-1])
        return out
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.wait()


def test_cell_is_the_zoo_on_four_chips(small):
    cell, zoo = spec.cell(CELL, small), spec.cell("rf2-zoo-rolling", small)
    assert cell["chips"] == CHIPS and cell["trials"] == CHIPS * zoo["trials"]
    assert program.kwargs(cell, seed=3, chunks=1) == dict(
        program.kwargs(zoo, seed=3, chunks=1), devices=CHIPS,
        trials=CHIPS * zoo["trials"])
    assert cell["reference_trials"] == cell["trials"]


def test_control_fails_the_limits(small):
    """The control on the cell's own sample, every trial."""
    cell = spec.cell(CELL, small)
    ref_mod = spec.reference(cell)
    cs = program.chunk_steps(cell["engine"])
    for seed in (11, 2 ** 31 + 5, 2 ** 33 + 1):
        sample = compare.sample_trials(seed, cell["trials"],
                                       cell["reference_trials"])
        kw = dict(seed=seed, trials=sample, chunks=1, chunk_steps=cs)
        ref = ref_mod.simulate(cell, **kw)
        ctrl = ref_mod.simulate(cell, acks=cell["rf"] - 1, **kw)
        values = compare.readings(
            compare.as_view(ctrl), ref, np.arange(len(sample)),
            partitions=cell["partitions"], horizon=cell["horizon"],
            calls_differ=0, failed=0,
            min_waves=cell["min_restart_waves"])
        ok, _ = compare.judge(values, cell["limits"])
        assert not ok, values
        assert values["frac_rel_gap"] > 10 * cell["limits"]["frac_rel_gap"]


def test_sound_run_is_correct(runs):
    res = runs[None]
    assert res["correct"], res["checks"]
    assert res["attempted"] == CHIPS * 8 and res["failed"] == 0
    assert "pooled_mismatch" in res["checks"]


def test_pooled_fault_shows_only_in_the_pooled_numbers(runs):
    checks = runs["_pooled_wrong"]["checks"]
    assert checks["pooled_mismatch"]["value"] > 0, checks
    assert checks["traj_mismatch"]["value"] == 0
    assert checks["frac_rel_gap"]["value"] == 0


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_caught(runs, fault):
    res = runs[fault]
    assert not res["correct"], res["checks"]
