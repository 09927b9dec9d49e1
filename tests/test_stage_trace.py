"""The engines name their step stages and call phases for the profiler.

- Every step variant's chunk program carries the `jax.named_scope`s of
  its stages (core/stages.py STAGES), and no other stage's.
- Every Monte Carlo `pallas_call` carries its kernel's name.
- A profiler trace of one engine call holds its host spans: one
  `lark.call` with the set-up, the chunk program, a dispatch per later
  chunk, and a drain and a stop test per chunk, all with one call id.
- On the numpy backend the names import nothing from jax.

Small shapes, the jax backend, on the CPU.
"""
import functools
import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core import availability_batched as ab
from repro.core import downtime_batched as db
from repro.core.client_latency import simulate_client_latency
from repro.core.stages import SPANS, STAGES
from repro.kernels import bitpack, fused_step
from repro.kernels import pac_eval as pk

N, P, B, RF = 31, 128, 8, 2
W = bitpack.n_words(N)
BASE = dict(n=N, partitions=P, rf=RF, trials=B, seed=3, backend="jax")
RECONFIG = dict(rebuild_model="reconfig", size_dist="zipf",
                node_bandwidth_gibps=1.0)
ZOO = dict(RECONFIG, packed=True, restart_period=200,
           engines=("lark", "quorum", "hermes", "spinnaker"),
           lease_ticks=4, view_change_ticks=4)
LATENCY = dict(node_bandwidth_gibps=1.0, key_zipf=0.99, read_frac=0.5,
               pair_fail_prob=0.5, packed=True)

ENTRY = {"availability": ab.simulate_availability_batched,
         "downtime": db.simulate_downtime_batched,
         "latency": simulate_client_latency}

CORE = {"lark_node_advance", "lark_rank_gather", "lark_step_eval",
        "lark_protocols"}
RECONFIG_STAGES = CORE | {"lark_roster", "lark_node_counts"}

VARIANTS = [
    ("availability", "availability", dict(packed=False), CORE),
    ("availability-packed", "availability", dict(packed=True), CORE),
    ("downtime-fixed", "downtime", dict(packed=False), CORE),
    ("downtime-fixed-bandwidth", "downtime",
     dict(packed=True, node_bandwidth_gibps=1.0),
     CORE | {"lark_node_counts"}),
    ("downtime-reconfig", "downtime",
     dict(RECONFIG, packed=False, node_bandwidth_gibps=float("inf")),
     RECONFIG_STAGES),
    ("downtime-reconfig-packed", "downtime", dict(RECONFIG, packed=True),
     RECONFIG_STAGES),
    ("zoo", "downtime", ZOO, RECONFIG_STAGES),
    ("latency", "latency", LATENCY,
     CORE | {"lark_node_counts", "lark_latency"}),
]


class _Lowered(Exception):
    """Raised in place of the first chunk, once its program is lowered."""

    def __init__(self, text):
        super().__init__("lowered")
        self.text = text


@pytest.fixture
def lower_first_chunk(monkeypatch):
    """Make an engine call raise _Lowered with its chunk program's text
    (debug info on) instead of running its first chunk."""
    real = ab._make_chunk_runner

    def make(step, carry, **kw):
        fn = real(step, carry, **kw)

        def run(c, s0):
            raise _Lowered(fn.lower(c, s0).as_text(debug_info=True))
        return run
    monkeypatch.setattr(ab, "_make_chunk_runner", make)
    monkeypatch.setattr(db, "_make_chunk_runner", make)


@pytest.mark.parametrize("name,engine,knobs,expect",
                         VARIANTS, ids=[v[0] for v in VARIANTS])
def test_chunk_program_holds_its_stages(lower_first_chunk, name, engine,
                                        knobs, expect):
    assert expect <= set(STAGES)
    with pytest.raises(_Lowered) as got:
        ENTRY[engine](**BASE, **knobs, chunk_steps=4, max_steps=5)
    # a location's name is the scope path of its op: "lark_roster/gather"
    scopes = set(re.findall(r'["/](lark_[a-z_]+)/', got.value.text))
    assert scopes == expect


def _words():
    return jax.ShapeDtypeStruct((B, W, P), jnp.uint32)


def _rows(dtype):
    return jax.ShapeDtypeStruct((B, P), dtype)


def _tile(dtype, cols=128):
    return jax.ShapeDtypeStruct((P, cols), dtype)


KERNELS = [
    ("lark_fused_pac", functools.partial(
        fused_step.fused_pac_eval, rf=RF, voters=3, n_real=N, block_t=8,
        block_p=128, interpret=True), (_words(), _words())),
    ("lark_fused_downtime", functools.partial(
        fused_step.fused_downtime_eval, rf=RF, n_real=N, block_t=8,
        block_p=128, interpret=True), (_words(), _words())),
    ("lark_pac_eval", functools.partial(
        pk.pac_eval, rf=RF, voters=3, n_real=N, block_p=128,
        interpret=True), (_tile(jnp.bool_), _tile(jnp.bool_))),
    ("lark_downtime_eval", functools.partial(
        pk.downtime_eval, rf=RF, n_real=N, block_p=128, interpret=True),
     (_tile(jnp.bool_), _tile(jnp.bool_))),
    ("lark_node_count", functools.partial(
        pk.node_count, n_real=N, interpret=True),
     (_rows(jnp.int32), _rows(jnp.bool_))),
    ("lark_latency_charge", functools.partial(
        pk.latency_charge, nbins=8, slo_ticks=8, interpret=True),
     (jax.ShapeDtypeStruct((B, 4, P), jnp.float32),
      jax.ShapeDtypeStruct((B, 4, P), jnp.float32), _rows(jnp.bool_),
      _rows(jnp.bool_), _rows(jnp.int32),
      jax.ShapeDtypeStruct((B,), jnp.int32),
      jax.ShapeDtypeStruct((P,), jnp.float32),
      jax.ShapeDtypeStruct((4,), jnp.float32))),
]


@pytest.mark.parametrize("name,fn,args", KERNELS,
                         ids=[k[0] for k in KERNELS])
def test_pallas_call_carries_its_name(name, fn, args):
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    assert f"/{name}/pallas_call" in text


def _host_spans(trace_dir):
    """[(name, start_ns, end_ns, {stat: value})] of the lark.* host spans
    in the one trace under trace_dir."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in SPANS:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


@pytest.mark.parametrize("engine,knobs", [
    ("availability", dict(packed=True)),
    ("downtime", ZOO),
    ("latency", LATENCY),
])
def test_call_phases_are_spans(tmp_path, engine, knobs):
    chunks, chunk_steps = 3, 4
    with jax.profiler.trace(str(tmp_path)):
        ENTRY[engine](**BASE, **knobs, chunk_steps=chunk_steps,
                      max_steps=chunks * chunk_steps + 1)
    spans = _host_spans(str(tmp_path))
    by = {name: [s for s in spans if s[0] == name] for name in SPANS}
    call, = by["lark.call"]
    _, lo, hi, args = call
    assert args["engine"] == engine and args["trials"] == B
    assert args["partitions"] == P and args["chunk_steps"] == chunk_steps
    counts = {name: len(v) for name, v in by.items()}
    assert counts == {"lark.call": 1, "lark.setup": 1,
                      "lark.chunk_program": 1, "lark.dispatch": chunks - 1,
                      "lark.drain": chunks, "lark.stop_test": chunks}
    for name, s, e, a in spans:
        assert lo <= s <= e <= hi, name
        assert a["call"] == args["call"], name
    chunk_ids = {name: sorted(a["chunk"] for _, _, _, a in by[name])
                 for name in ("lark.dispatch", "lark.drain",
                              "lark.stop_test")}
    assert chunk_ids == {"lark.dispatch": list(range(1, chunks)),
                         "lark.drain": list(range(chunks)),
                         "lark.stop_test": list(range(chunks))}
    assert all(a["ticks"] > 0 for _, _, _, a in by["lark.drain"])
    assert [a["stopped"] for _, _, _, a in sorted(by["lark.stop_test"],
                                                 key=lambda s: s[1])] \
        == [0] * chunks


def test_numpy_backend_imports_no_jax():
    """Stages and spans are null contexts on the numpy backend: a numpy
    call of each engine imports no jax module that was not imported
    before it."""
    code = """
import sys
from repro.core.availability_batched import simulate_availability_batched
from repro.core.downtime_batched import simulate_downtime_batched
def jax_mods():
    return {m for m in sys.modules if m.split(".")[0] in ("jax", "jaxlib")}
before = jax_mods()
kw = dict(n=15, partitions=32, trials=4, seed=1, backend="numpy",
          chunk_steps=4, max_steps=9)
simulate_availability_batched(**kw)
simulate_downtime_batched(rebuild_model="reconfig",
                          node_bandwidth_gibps=1.0,
                          engines=("lark", "quorum", "hermes", "spinnaker"),
                          **kw)
print(sorted(jax_mods() - before))
"""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip().splitlines()[-1] == "[]"


def _gathers_under(text, stages):
    """Names of the stages in `stages` that hold a `stablehlo.gather` in
    the lowered program `text` (debug info on).  An op is under a stage
    when its own location names the stage, or when a call op under the
    stage reaches the function it is in (jnp wraps some ops, such as
    take_along_axis, in private functions whose ops carry no scope)."""
    defs = dict(re.findall(r"^(#loc\d+) = (.*)$", text, re.M))

    def names(loc, seen=()):
        if loc in seen or loc not in defs:
            return set()
        body = defs[loc]
        out = set(re.findall(r'"([^"]*)"\(', body))
        for ref in re.findall(r"#loc\d+", body):
            out |= names(ref, seen + (loc,))
        return out

    def under(loc):
        return {s for s in stages for name in names(loc)
                if re.search(rf"(^|/){s}/", name)}

    ops = []                    # (function, op, location)
    func = None
    for line in text.splitlines():
        head = re.match(r"\s*func\.func (?:public |private )?@([\w.$-]+)\(",
                        line)
        if head:
            func = head.group(1)
            continue
        loc = re.search(r"loc\((#loc\d+)\)\s*$", line)
        if func is None or loc is None:
            continue
        call = re.search(r"\bcall @([\w.$-]+)\(", line)
        if call:
            ops.append((func, ("call", call.group(1)), loc.group(1)))
        elif "stablehlo.gather" in line:
            ops.append((func, ("gather",), loc.group(1)))

    # stages each function is reached under, through any chain of calls
    reach = {}
    changed = True
    while changed:
        changed = False
        for func, op, loc in ops:
            if op[0] != "call":
                continue
            got = under(loc) | reach.get(func, set())
            if not got <= reach.get(op[1], set()):
                reach[op[1]] = reach.get(op[1], set()) | got
                changed = True
    found = set()
    for func, op, loc in ops:
        if op[0] == "gather":
            found |= under(loc) | reach.get(func, set())
    return found


LOOKUP_VARIANTS = [v for v in VARIANTS
                   if v[0] in ("downtime-fixed-bandwidth",
                               "downtime-reconfig",
                               "downtime-reconfig-packed", "zoo",
                               "latency")]


@pytest.mark.parametrize("name,engine,knobs,expect", LOOKUP_VARIANTS,
                         ids=[v[0] for v in LOOKUP_VARIANTS])
def test_roster_and_node_counts_hold_no_gather(lower_first_chunk, name,
                                               engine, knobs, expect):
    """The roster and node-count stages look up per-(trial, partition)
    values with lane compares, never with a gather: on a TPU v5e a
    gather with one index an element costs about 10 ns an element.
    (`up[:, succ]` under lark_rank_gather stays a gather.)"""
    with pytest.raises(_Lowered) as got:
        ENTRY[engine](**BASE, **knobs, chunk_steps=4, max_steps=5)
    text = got.value.text
    assert "lark_rank_gather" in _gathers_under(text, ("lark_rank_gather",))
    assert _gathers_under(text, ("lark_roster", "lark_node_counts")) \
        == set()
