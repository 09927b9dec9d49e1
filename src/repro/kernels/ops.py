"""Jit'd dispatch wrappers: Pallas kernel on TPU, pure-jnp oracle elsewhere.

Two layers live here:

*Model kernels* (flash_attention, mlstm, rglru): the CPU container
validates them in ``interpret=True`` mode (tests) while
models/benchmarks/dry-runs use the jnp oracle path — identical math, so
the lowered HLO is an honest stand-in and the TPU kernel is a drop-in
swap.  Set ``REPRO_FORCE_PALLAS=interpret`` to route model code through
the interpreted kernels (slow; tests only).

*Monte Carlo batch ops* (paper §5.1 / §6 hot loops): ``pac_eval_batch``
and ``downtime_eval_batch`` evaluate (R, n_pad) rank-space cluster-state
tiles under a uniform three-backend contract —

  backend="numpy"   vectorized numpy (the scalar event engine's math,
                    shared via pac_np.py, jax-import-free)
  backend="jax"     pure-jnp oracle (jit-friendly; used inside lax.scan)
  backend="pallas"  kernels/pac_eval.py — compiled on TPU, interpret
                    mode on CPU

Invariants (pinned by tests/test_availability_batched.py and
tests/test_downtime_batched.py, stated in docs/ARCHITECTURE.md): all
three backends are bit-identical (comparisons/cumsums only, no float
math); padding columns >= n_real never affect outputs; and the Pallas
``block_p`` tiling — including the deterministic ``autotune_block_p``
choice — changes throughput, never results.

The Monte Carlo ops are consolidated behind one entry point: a frozen
``StepSpec`` (metric, rf/voters, rebuild model, packed layout) dispatched
by ``step_eval(spec, up, full, ...)``.  ``StepSpec(packed=True)`` selects
the bit-packed (B, W, P) uint32 word layout (kernels/bitpack.py) and, on
the pallas backend, the fused step megakernel (kernels/fused_step.py)
that folds eval + roster gather + rebuild node counts into one
pallas_call.  The legacy per-kernel functions ``pac_eval_batch`` /
``downtime_eval_batch`` / ``rebuild_node_counts`` remain as thin
deprecated wrappers over step_eval.
"""
from __future__ import annotations

import functools
import os
import warnings
from dataclasses import dataclass
from typing import Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import bitpack, ref
from . import latency as _latency
from .pac_eval import lane_block, legal_blocks, trial_block

_FORCE = os.environ.get("REPRO_FORCE_PALLAS", "")

PAC_BACKENDS = ("numpy", "jax", "pallas")


def pallas_interpret() -> bool:
    """Whether the Monte Carlo Pallas kernels run interpreted: False on a
    TPU (compiled by Mosaic), True on the CPU (the test and CI
    platform).  Any other platform raises — a kernel must never fall
    back quietly to a path that hides the device."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas Monte Carlo kernels compile for a TPU or interpret on "
        f"the CPU; JAX's default backend is {platform!r}")


def _mode() -> str:
    """'kernel' | 'interpret' | 'ref' (interpret only when forced)."""
    if _FORCE == "interpret":
        return "interpret"
    if _FORCE == "ref":
        return "ref"
    return "ref" if pallas_interpret() else "kernel"


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: Optional[float] = None):
    mode = _mode()
    if mode != "ref":
        from . import flash_attention as fk
        return fk.flash_attention(q, k, v, causal=causal, window=window,
                                  scale=scale, interpret=(mode == "interpret"))
    return ref.attention_ref(q, k, v, causal=causal, window=window, scale=scale)


def mlstm_chunkwise(q, k, v, log_f, log_i, *, chunk: int = 256, initial=None):
    mode = _mode()
    if mode != "ref":
        from . import mlstm_chunk as mk
        return mk.mlstm_chunkwise(q, k, v, log_f, log_i, chunk=chunk,
                                  initial=initial, interpret=(mode == "interpret"))
    return ref.mlstm_chunkwise(q, k, v, log_f, log_i, chunk=chunk, initial=initial)


def mlstm_step(q, k, v, log_f, log_i, state):
    return ref.mlstm_step(q, k, v, log_f, log_i, state)


def rglru_scan(x, log_a):
    mode = _mode()
    if mode != "ref":
        from . import rglru_scan as rk
        return rk.rglru_scan(x, log_a, interpret=(mode == "interpret"))
    return ref.rglru_scan_ref(x, log_a)


def rglru_step(x, log_a, h):
    return ref.rglru_step(x, log_a, h)


def pac_eval(up, succ, full, rf: int, *, voters=None,
             conditions: Tuple[str, ...] = ("simple_majority",)):
    """Node-space PAC over (P, n) (protocol-level users)."""
    return ref.pac_eval_ref(up, succ, full, rf, voters=voters,
                            conditions=conditions)


# ---------------------------------------------------------------------------
# Unified PAC backend layer (§5.1 availability Monte Carlo).
#
# All three backends evaluate the same rank-space tile contract as
# ref.pac_eval_rank_ref: inputs (R, n_pad) bool where R is any flattened
# batch (e.g. trials * partitions) and columns >= n_real are padding;
# outputs (lark (R,), maj (R,), creps (R, n_pad)).  "numpy" is the
# vectorized refactor of the event engine's evaluate() and is shared with
# core/availability.py, so the scalar event loop and the batched device
# loop literally run the same availability math.  It lives in pac_np.py
# (numpy-only) so the event engine never pays the jax import.
# ---------------------------------------------------------------------------

from .pac_np import (downtime_eval_rank_np,  # noqa: E402  (re-export)
                     pac_eval_rank_np, rebuild_node_counts_np)


#: VMEM (bytes, one copy of every block) a kernel tile may hold — half
#: the 16 MiB Mosaic scopes on a v5e, since Pallas double-buffers blocks
VMEM_BUDGET = 8 * 2 ** 20


def _pac_lane_pad(n_pad: int) -> int:
    """Node axis padded up to a lane multiple for the VPU tile."""
    return n_pad + (-n_pad % 128)


def pac_vmem_bytes(block_p: int, n_pad: int) -> int:
    """VMEM the PAC kernel holds live for one (block_p, n_lanes) block:
    three int32 tiles (up, full, creps), the float32 prefix-count tile
    and its (n_lanes, n_lanes) triangular operand, and the lane-padded
    (block_p, 1) row outputs — the budget the autotuner's candidate
    enumeration respects."""
    n_lanes = _pac_lane_pad(n_pad)
    return (block_p * n_lanes * 4 * 4 + n_lanes * n_lanes * 4
            + 2 * block_p * 128 * 4)


def _pallas_block_p(R: int, n_pad: int = 128) -> int:
    """Default row block of the unpacked kernels: the largest legal block
    <= 256 (a power-of-two multiple of 8 dividing R, or all R rows)."""
    bp = max(legal_blocks(R, 8, 256))
    if pac_vmem_bytes(bp, n_pad) > VMEM_BUDGET:
        raise ValueError(f"no legal row block tiles R={R} rows within the "
                         f"VMEM budget (largest legal block {bp})")
    return bp


def block_p_candidates(R: int, n_pad: int, *, max_block: int = 1024,
                       vmem_limit_bytes: int = VMEM_BUDGET):
    """Legal block_p values (legal_blocks) that tile R rows within the
    VMEM budget; raises when there is none.

    Deterministic pure function of its arguments — the autotuner measures
    exactly this set, so two runs on the same shape always race the same
    candidates.
    """
    cands = tuple(bp for bp in legal_blocks(R, 8, max_block)
                  if pac_vmem_bytes(bp, n_pad) <= vmem_limit_bytes)
    if not cands:
        raise ValueError(f"no legal block_p tiles R={R} rows within "
                         f"{vmem_limit_bytes} bytes of VMEM")
    return cands


@dataclass(frozen=True)
class AutotuneResult:
    block_p: int
    timings_us: Mapping[int, float]   # candidate -> median µs/call
    source: str                       # "measured" | "heuristic-fallback"


_AUTOTUNE_CACHE: dict = {}


#: kernels the block_p autotuner can race — the §5.1 PAC kernel and the
#: §6 downtime kernel (plus its roster-carrying reconfig variant); all
#: three share the (R, n_pad) tile contract, so candidate sets transfer
AUTOTUNE_KERNELS = ("pac", "downtime", "downtime_roster")


def _measure_pac_block(R: int, n_pad: int, bp: int, *, rf: int, voters: int,
                       n_real: int, iters: int,
                       kernel: str = "pac") -> float:
    """Median µs/call of one Pallas Monte Carlo kernel (`kernel` selects
    pac_eval / downtime_eval / its roster variant) at one block size, on a
    deterministic synthetic tile (counter-hash density pattern, no RNG
    state)."""
    import time

    from . import pac_eval as pk
    n_lanes = _pac_lane_pad(n_pad)
    idx = (jnp.arange(R, dtype=jnp.uint32)[:, None] * jnp.uint32(n_lanes)
           + jnp.arange(n_lanes, dtype=jnp.uint32)[None, :])
    up = (idx * jnp.uint32(2654435761) % jnp.uint32(97)) < 90   # ~93% up,
    full = (idx * jnp.uint32(40503) % jnp.uint32(89)) < 30      # fixed pattern
    interpret = pallas_interpret()
    if kernel == "pac":
        fn = jax.jit(functools.partial(
            pk.pac_eval, rf=rf, voters=voters, n_real=n_real, block_p=bp,
            interpret=interpret))
        args = (up, full)
    elif kernel in ("downtime", "downtime_roster"):
        kw = dict(rf=rf, n_real=n_real, block_p=bp, interpret=interpret)
        if kernel == "downtime_roster":
            # identity roster, rank axis lane-padded with the sentinel the
            # engine's pallas path uses (ops.downtime_eval_batch)
            rf_pad = rf + (-rf % 128)
            ranks = jnp.arange(rf_pad, dtype=jnp.int32)[None, :]
            kw["roster"] = jnp.broadcast_to(
                jnp.where(ranks < rf, ranks, jnp.int32(n_lanes)),
                (R, rf_pad))
        fn = jax.jit(functools.partial(pk.downtime_eval, **kw))
        args = (up, full)
    else:
        raise ValueError(f"unknown autotune kernel {kernel!r}; expected "
                         f"one of {AUTOTUNE_KERNELS}")
    jax.block_until_ready(fn(*args))           # compile + warmup
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e6)
    times.sort()
    return times[len(times) // 2]


def autotune_block_p(R: int, n_pad: int, *, rf: int, voters: int,
                     n_real: int, candidates=None, iters: int = 9,
                     measure=None, force: bool = False,
                     kernel: str = "pac") -> AutotuneResult:
    """Pick the fastest Pallas block_p for an (R, n_pad) Monte Carlo tile.

    `kernel` selects which kernel is raced: "pac" (§5.1 availability),
    "downtime" (§6 commit-pause), or "downtime_roster" (the reconfiguring
    baseline's roster-carrying variant) — the sweep threads its --metric /
    --rebuild-model so the tuner times the kernel the grid will actually
    run.  Deterministic by construction: the candidate set is a pure
    function of the shape, each candidate's time is a median over `iters`
    calls, ties break toward the smaller block, and the choice is cached
    per (shape, params, kernel, candidates) so every later call in the
    process returns the same answer.  Off-TPU the Pallas kernel runs in
    interpret mode, where timings measure the interpreter rather than the
    kernel — so without `force` (or an injected `measure` fn, used by
    tests) the tuner falls back to the static heuristic instead of
    publishing noise.
    """
    if kernel not in AUTOTUNE_KERNELS:
        raise ValueError(f"unknown autotune kernel {kernel!r}; expected "
                         f"one of {AUTOTUNE_KERNELS}")
    cands = tuple(candidates) if candidates is not None \
        else block_p_candidates(R, n_pad)
    if not cands:
        raise ValueError("autotune_block_p needs at least one candidate")
    for bp in cands:
        if R % bp:
            raise ValueError(f"candidate block_p {bp} does not divide R={R}")
    # injected-measure calls (tests) bypass the cache: a deterministic fake
    # is repeatable on its own, and caching across *different* fakes with
    # the same shape would return stale choices.  The key leads with the
    # tuner family + kernel kind + the full tile geometry, so a fused-2D
    # race and a block_p race on the same shape can never alias (the PR 4
    # wrong-kernel race fix, generalized to the fused tuner below)
    key = ("block_p", kernel, R, n_pad, rf, voters, n_real, cands, force)
    if measure is None and key in _AUTOTUNE_CACHE:
        return _AUTOTUNE_CACHE[key]
    if measure is None:
        if pallas_interpret() and not force:
            res = AutotuneResult(block_p=_pallas_block_p(R, n_pad),
                                 timings_us={},
                                 source="heuristic-fallback")
            _AUTOTUNE_CACHE[key] = res
            return res
        measure = functools.partial(_measure_pac_block, rf=rf,
                                    voters=voters, n_real=n_real,
                                    iters=iters, kernel=kernel)
        timings = {bp: measure(R, n_pad, bp) for bp in cands}
        best = min(sorted(timings), key=lambda bp: (timings[bp], bp))
        res = AutotuneResult(block_p=best, timings_us=timings,
                             source="measured")
        _AUTOTUNE_CACHE[key] = res
        return res
    timings = {bp: float(measure(R, n_pad, bp)) for bp in cands}
    best = min(sorted(timings), key=lambda bp: (timings[bp], bp))
    return AutotuneResult(block_p=best, timings_us=timings,
                          source="measured")


def _pac_eval_unpacked(up_succ, full_succ, *, rf: int, voters: int,
                       n_real: int, backend: str = "jax",
                       block_p: Optional[int] = None):
    """Dispatch a (R, n_pad) rank-space PAC tile to the chosen backend.

    backend:
      numpy   vectorized numpy (the event engine's evaluate logic)
      jax     pure-jnp oracle (jit-friendly; use inside lax.scan)
      pallas  kernels/pac_eval.py — compiled on TPU, interpret mode on CPU

    block_p (pallas only) overrides the static block-size heuristic —
    typically an `autotune_block_p(...)` choice.  Results are elementwise,
    so every block size yields identical outputs; only throughput changes.
    """
    if backend == "numpy":
        return pac_eval_rank_np(up_succ, full_succ, rf=rf, voters=voters,
                                n_real=n_real)
    if backend == "jax":
        return ref.pac_eval_rank_ref(up_succ, full_succ, rf=rf,
                                     voters=voters, n_real=n_real)
    if backend == "pallas":
        from . import pac_eval as pk
        R, n_pad = up_succ.shape
        lanes = -n_pad % 128                      # pad node axis to a lane
        if lanes:                                 # multiple for the VPU tile
            up_succ = jnp.pad(up_succ, ((0, 0), (0, lanes)))
            full_succ = jnp.pad(full_succ, ((0, 0), (0, lanes)))
        interpret = pallas_interpret()
        lark, maj, creps = pk.pac_eval(up_succ, full_succ, rf=rf,
                                       voters=voters, n_real=n_real,
                                       block_p=block_p
                                       or _pallas_block_p(R, n_pad),
                                       interpret=interpret)
        return lark, maj, creps[:, :n_pad]
    raise ValueError(f"unknown PAC backend {backend!r}; "
                     f"expected one of {PAC_BACKENDS}")


def _downtime_eval_unpacked(up_succ, full_succ, *, rf: int, n_real: int,
                            backend: str = "jax",
                            block_p: Optional[int] = None, roster=None,
                            want_repmask: bool = False,
                            want_rleader: bool = False):
    """Dispatch the §6 downtime engine's per-step evaluation of a
    (R, n_pad) rank-space tile to the chosen backend.

    Extends the pac_eval_batch contract with the state the commit-pause
    engine (core/downtime_batched.py) tracks between steps — the
    quorum-log baseline's f+1-copy replica-set majority and up-count, and
    the acting leader's rank and latest-copy bit (for the dup-res
    penalty).  Returns (lark, qmaj, leader, leader_full, nrep, *extras,
    creps); see pac_np.downtime_eval_rank_np for per-output semantics
    (want_repmask / want_rleader are the protocol-zoo extras — Hermes
    membership bitmask, Spinnaker electable roster leader).

    roster (R, rf) int32, optional: the reconfiguring baseline's carried
    replica-set ranks — qmaj/nrep are then evaluated over those ranks
    instead of the implicit first rf lanes (`--rebuild-model reconfig`).
    Passing the identity roster [0..rf-1] reproduces the static baseline
    bit for bit.

    The same invariants as pac_eval_batch hold: all three backends are
    bit-identical (pure comparisons/cumsums, no float math), and block_p
    (pallas) only tiles the rows — any autotune_block_p choice for an
    (R, n_pad) PAC tile is valid here, which is why the sweep reuses one
    autotuned block size for both metrics.
    """
    if backend == "numpy":
        return downtime_eval_rank_np(up_succ, full_succ, rf=rf,
                                     n_real=n_real, roster=roster,
                                     want_repmask=want_repmask,
                                     want_rleader=want_rleader)
    if backend == "jax":
        return ref.downtime_eval_rank_ref(up_succ, full_succ, rf=rf,
                                          n_real=n_real, roster=roster,
                                          want_repmask=want_repmask,
                                          want_rleader=want_rleader)
    if backend == "pallas":
        from . import pac_eval as pk
        R, n_pad = up_succ.shape
        lanes = -n_pad % 128
        if lanes:
            up_succ = jnp.pad(up_succ, ((0, 0), (0, lanes)))
            full_succ = jnp.pad(full_succ, ((0, 0), (0, lanes)))
        if roster is not None:
            # pad the rank axis to a lane multiple; the pad value is the
            # tile width, a rank no lane iota ever matches (never read:
            # the kernel only visits the first rf roster columns)
            rpad = -roster.shape[1] % 128
            roster = jnp.pad(roster.astype(jnp.int32),
                             ((0, 0), (0, rpad)),
                             constant_values=n_pad + lanes)
        interpret = pallas_interpret()
        outs = pk.downtime_eval(
            up_succ, full_succ, rf=rf, n_real=n_real,
            block_p=block_p or _pallas_block_p(R, n_pad),
            interpret=interpret,
            roster=roster, want_repmask=want_repmask,
            want_rleader=want_rleader)
        return tuple(outs[:-1]) + (outs[-1][:, :n_pad],)
    raise ValueError(f"unknown PAC backend {backend!r}; "
                     f"expected one of {PAC_BACKENDS}")


def _rebuild_node_counts_impl(recruit, active, *, n_real: int,
                              backend: str = "jax"):
    """Per-node in-flight rebuild counts for the §6 bandwidth-contended
    rebuild model: recruit (B, P) int32 node ids (values outside
    [0, n_real) — the engine's no-recruit sentinel — are ignored), active
    (B, P) bool -> counts (B, n_real) int32, where counts[b, node] is the
    number of partitions of trial b whose active catch-up ingests on
    `node`.

    This is the downtime engine's first *cross-partition* reduction
    inside a step — but it stays strictly within a trial (rows never
    mix), so it commutes with trials-axis sharding; the 8-device proof
    lives in tests/test_sharded.py.  All three backends are bit-identical:
    the numpy/jnp implementations scatter-add, the Pallas kernel
    (kernels/pac_eval.py: node_count) sums one-hot products over the
    partition columns — exact counts either way.
    """
    if backend == "numpy":
        return rebuild_node_counts_np(recruit, active, n_real=n_real)
    if backend == "jax":
        return ref.rebuild_node_counts_ref(recruit, active, n_real=n_real)
    if backend == "pallas":
        from . import pac_eval as pk
        counts = pk.node_count(recruit, active, n_real=n_real,
                               interpret=pallas_interpret())
        return counts[:, :n_real]
    raise ValueError(f"unknown PAC backend {backend!r}; "
                     f"expected one of {PAC_BACKENDS}")


def client_latency_step(dirty, dt_i, avail, qok, rem, *, pow_tables, kf,
                        lamw, nbins: int, slo_ticks: int,
                        backend: str = "jax"):
    """The client-latency layer's post-step op (core/client_latency.py):
    one event interval of dirty-key decay + LARK first-touch charges and
    closed-form quorum rebuild-wait charges, under the same uniform
    three-backend contract as the other Monte Carlo batch ops.

    dirty (B, P, NB) float32 carried dirty-key fractions; dt_i (B,) int32
    interval lengths; avail / qok (B, P) bool (partition serving / replica
    majority up, both at interval start); rem (B, P) int32 remaining
    rebuild wall-ticks at interval start.  pow_tables / kf / lamw are the
    host-precomputed float32 workload tables (kernels/latency.py).
    Returns (new_dirty, dup, qhist, qslo, qsum) — see
    latency_step_ref for shapes.

    All three backends are bit-identical: the math is elementwise
    exactly-rounded float32 (shared verbatim from kernels/latency.py;
    the pallas path precomputes the decay factors with the identical jnp
    chain, then runs the charge kernel over flattened (trial, partition)
    rows).  No reduction crosses partitions — pooling happens host-side
    at chunk drains — so trials-axis sharding commutes exactly.
    """
    if backend == "numpy":
        return _latency.latency_step_ref(
            dirty, dt_i, avail, qok, rem, pow_tables=pow_tables, kf=kf,
            lamw=lamw, nbins=nbins, slo_ticks=slo_ticks, xp=np)
    if backend == "jax":
        out = _latency.latency_step_ref(
            dirty, dt_i, avail, qok, rem, pow_tables=pow_tables, kf=kf,
            lamw=lamw, nbins=nbins, slo_ticks=slo_ticks, xp=jnp)
        # XLA's CPU backend contracts `acc + rate * (a - b)` into an FMA,
        # which rounds differently from numpy's separate mul-then-add.
        # The engine accumulates every charge we return, so pin the op
        # boundary: nothing may fuse across it.
        return jax.lax.optimization_barrier(out)
    if backend == "pallas":
        from . import pac_eval as pk
        # the kernel runs bucket-major (B, NB, P): partitions on lanes.
        # The decay chain is the identical elementwise jnp product, taken
        # over the transposed tables
        decay = _latency.decay_from_dt(
            dt_i, jnp.swapaxes(pow_tables, 1, 2), jnp)
        nd, dup, qh, qs, qq = pk.latency_charge(
            jnp.swapaxes(dirty, 1, 2), decay, avail, qok, rem, dt_i, lamw,
            kf, nbins=nbins, slo_ticks=slo_ticks,
            interpret=pallas_interpret())
        # same FMA-contraction pin as the jax branch: in interpret mode
        # the kernel body inlines into the surrounding jit
        return jax.lax.optimization_barrier(
            (jnp.swapaxes(nd, 1, 2), jnp.swapaxes(dup, 1, 2),
             jnp.swapaxes(qh, 1, 2), qs, qq))
    raise ValueError(f"unknown PAC backend {backend!r}; "
                     f"expected one of {PAC_BACKENDS}")


# ---------------------------------------------------------------------------
# Unified step API: StepSpec -> step_eval.
#
# One frozen spec names everything the per-step evaluation depends on —
# metric, replication/voter counts, rebuild model, and the state layout
# (boolean tiles vs bit-packed words) — and one dispatcher maps it onto
# the backend matrix.  The three legacy entry points below are thin
# deprecated wrappers over this.
# ---------------------------------------------------------------------------

STEP_METRICS = ("availability", "downtime")
STEP_REBUILD_MODELS = ("fixed", "reconfig")
#: protocol-zoo engines a downtime StepSpec can additionally evaluate —
#: each adds one int32 row output between nrep and creps
STEP_ENGINES = ("hermes", "spinnaker")


@dataclass(frozen=True)
class StepSpec:
    """Everything the per-step kernel dispatch depends on, in one frozen
    value (hashable: usable as a cache/jit key).

    metric         "availability" (§5.1 PAC + majority baseline) or
                   "downtime" (§6 commit-pause: + leader/nrep outputs)
    rf             replication factor (roster width)
    n_real         real node count; lanes/bits >= n_real are padding
    voters         majority-baseline voter count; None resolves to the
                   paper's 2*(rf-1)+1 for availability and rf for
                   downtime (the quorum-log replica-set vote)
    rebuild_model  "fixed" or "reconfig"; reconfig is what carries a
                   roster into the eval and (with bandwidth contention)
                   folds rebuild node counts into the step
    packed         False: boolean (R, n_pad) tiles.  True: bit-packed
                   (B, W, P) uint32 words (kernels/bitpack.py) — layout
                   only, every output bit-identical
    dupres_ticks / rebuild_steps
                   §6 engine knobs carried for provenance (they shape
                   the step *around* the eval, not the eval itself;
                   kept here so one spec names the whole step)
    engines        protocol-zoo engines riding the downtime eval
                   (subset of STEP_ENGINES).  "hermes" requests the
                   first-rf membership bitmask (repmask; needs rf <= 30
                   so the mask fits a non-negative int32); "spinnaker"
                   requests the electable roster leader (rleader; needs
                   rebuild_model="reconfig" — it elects among the
                   carried roster).  Both extras land between nrep and
                   creps in every kernel body.
    """
    metric: str
    rf: int
    n_real: int
    voters: Optional[int] = None
    rebuild_model: str = "fixed"
    packed: bool = False
    dupres_ticks: int = 0
    rebuild_steps: int = 0
    engines: tuple = ()

    def __post_init__(self):
        if self.metric not in STEP_METRICS:
            raise ValueError(f"unknown step metric {self.metric!r}; "
                             f"expected one of {STEP_METRICS}")
        if self.rebuild_model not in STEP_REBUILD_MODELS:
            raise ValueError(
                f"unknown rebuild_model {self.rebuild_model!r}; "
                f"expected one of {STEP_REBUILD_MODELS}")
        if not 1 <= self.rf <= self.n_real:
            raise ValueError(
                f"rf={self.rf} must be in [1, n_real={self.n_real}]")
        if self.voters is not None and self.voters < 1:
            raise ValueError(f"voters={self.voters} must be >= 1")
        if self.dupres_ticks < 0 or self.rebuild_steps < 0:
            raise ValueError("dupres_ticks / rebuild_steps must be >= 0")
        object.__setattr__(self, "engines", tuple(self.engines))
        for e in self.engines:
            if e not in STEP_ENGINES:
                raise ValueError(f"unknown step engine {e!r}; "
                                 f"expected a subset of {STEP_ENGINES}")
        if len(set(self.engines)) != len(self.engines):
            raise ValueError(f"duplicate step engines: {self.engines}")
        if self.engines and self.metric != "downtime":
            raise ValueError("protocol-zoo engines are downtime-metric "
                             "outputs; availability spec can't request "
                             f"{self.engines}")
        if "hermes" in self.engines and self.rf > 30:
            raise ValueError(f"hermes needs rf <= 30 (membership bitmask "
                             f"in a non-negative int32); got rf={self.rf}")
        if "spinnaker" in self.engines and self.rebuild_model != "reconfig":
            raise ValueError("spinnaker elects among the carried roster; "
                             "it requires rebuild_model='reconfig'")

    @property
    def resolved_voters(self) -> int:
        if self.voters is not None:
            return self.voters
        return 2 * (self.rf - 1) + 1 if self.metric == "availability" \
            else self.rf

    @property
    def fused_kernel(self) -> str:
        """The fused-kernel kind this spec dispatches to (autotune key)."""
        if self.metric == "availability":
            return "fused_pac"
        return "fused_downtime_roster" if self.rebuild_model == "reconfig" \
            else "fused_downtime"

    @property
    def want_repmask(self) -> bool:
        return "hermes" in self.engines

    @property
    def want_rleader(self) -> bool:
        return "spinnaker" in self.engines


class StepOutputs(NamedTuple):
    """step_eval's full output surface; slots a spec doesn't produce are
    None (availability: leader/leader_full/nrep; no recruit: counts;
    engines without hermes/spinnaker: repmask/rleader — and rleader stays
    None on roster-less calls even under a spinnaker spec, since it
    elects among the carried roster)."""
    lark: object
    maj: object
    leader: object = None
    leader_full: object = None
    nrep: object = None
    creps: object = None
    counts: object = None
    repmask: object = None
    rleader: object = None


def _packed_planes(words, xp):
    W = words.shape[1]
    return [words[:, k, :] for k in range(W)]


def _take_extras(outs, want_repmask: bool, want_rleader: bool):
    """Pull the protocol-zoo extras out of a kernel's (lark, qmaj, leader,
    leader_full, nrep, *extras, creps[, counts]) tuple."""
    k = 5
    repmask = rleader = None
    if want_repmask:
        repmask = outs[k]
        k += 1
    if want_rleader:
        rleader = outs[k]
    return repmask, rleader


def step_eval(spec: StepSpec, up, full, *, roster=None, recruit=None,
              active=None, backend: str = "jax",
              block_p: Optional[int] = None,
              block_t: Optional[int] = None) -> StepOutputs:
    """Evaluate one Monte Carlo step under `spec` on the chosen backend.

    Boolean layout (spec.packed=False): up/full are (R, n_pad) bool
    rank-space tiles, roster (R, rf) int32, and outputs are (R,) /
    (R, n_pad) — exactly the legacy pac_eval_batch / downtime_eval_batch
    contract.  recruit/active ((B, P) int32/bool) additionally request
    the bandwidth-model node counts (legacy rebuild_node_counts).

    Packed layout (spec.packed=True): up/full are (B, W, P) uint32 word
    planes (bit b of word k = succession rank 32k+b; pack with
    bitpack.pack_words + moveaxis), roster is the engine's carried
    (B, P, rf) int32 rank tensor, and row outputs are (B, P) with creps
    returned as (B, W, P) words.  backend="pallas" runs the fused step
    megakernel — one pallas_call for eval + roster + counts; numpy/jax
    run the identical bitpack.py math plane-wise.  Counts inputs stay
    unpacked (B, P) in every layout.

    Every cell of the (metric x backend x layout) matrix is bit-identical
    to every other; packing and fusion change bytes moved, never results
    (tests/test_bitpack.py, tests/test_step_api.py).
    """
    if spec.metric == "downtime" and spec.rebuild_model != "reconfig" \
            and roster is not None:
        raise ValueError("roster is only meaningful for "
                         "rebuild_model='reconfig'")
    if (recruit is None) != (active is None):
        raise ValueError("recruit and active must be passed together")
    if spec.metric == "availability" and recruit is not None:
        raise ValueError("rebuild node counts are a downtime-engine "
                         "output; availability spec can't request them")

    # rleader elects among the carried roster, so a roster-less call
    # (e.g. the engines' t=0 init eval) simply doesn't produce it
    want_rm = spec.want_repmask
    want_rl = spec.want_rleader and roster is not None

    if not spec.packed:
        counts = None
        if recruit is not None:
            counts = _rebuild_node_counts_impl(recruit, active,
                                               n_real=spec.n_real,
                                               backend=backend)
        if spec.metric == "availability":
            lark, maj, creps = _pac_eval_unpacked(
                up, full, rf=spec.rf, voters=spec.resolved_voters,
                n_real=spec.n_real, backend=backend, block_p=block_p)
            return StepOutputs(lark=lark, maj=maj, creps=creps,
                               counts=counts)
        outs = _downtime_eval_unpacked(
            up, full, rf=spec.rf, n_real=spec.n_real, backend=backend,
            block_p=block_p, roster=roster, want_repmask=want_rm,
            want_rleader=want_rl)
        repmask, rleader = _take_extras(outs, want_rm, want_rl)
        return StepOutputs(lark=outs[0], maj=outs[1], leader=outs[2],
                           leader_full=outs[3], nrep=outs[4],
                           creps=outs[-1], counts=counts,
                           repmask=repmask, rleader=rleader)

    # ---- packed (B, W, P) word layout ----
    if backend not in PAC_BACKENDS:
        raise ValueError(f"unknown PAC backend {backend!r}; "
                         f"expected one of {PAC_BACKENDS}")
    B, W, P = up.shape
    if backend == "pallas":
        from . import fused_step
        interpret = pallas_interpret()
        bt, bp = block_t, block_p
        if not (bt and bp):
            t0, p0 = fused_default_blocks(B, P, spec.n_real, rf=spec.rf,
                                          kernel=spec.fused_kernel)
            bt, bp = bt or t0, bp or p0
        if spec.metric == "availability":
            lark, maj, crepsw = fused_step.fused_pac_eval(
                up, full, rf=spec.rf, voters=spec.resolved_voters,
                n_real=spec.n_real, block_t=bt, block_p=bp,
                interpret=interpret)
            return StepOutputs(lark=lark, maj=maj, creps=crepsw)
        rost = None if roster is None else jnp.moveaxis(roster, -1, 1)
        outs = fused_step.fused_downtime_eval(
            up, full, rf=spec.rf, n_real=spec.n_real, block_t=bt,
            block_p=bp, interpret=interpret, roster=rost,
            recruit=recruit, active=active, want_repmask=want_rm,
            want_rleader=want_rl)
        repmask, rleader = _take_extras(outs, want_rm, want_rl)
        ncr = 6 + int(want_rm) + int(want_rl)
        counts = outs[ncr][:, :spec.n_real] if recruit is not None \
            else None
        return StepOutputs(lark=outs[0], maj=outs[1], leader=outs[2],
                           leader_full=outs[3], nrep=outs[4],
                           creps=outs[ncr - 1], counts=counts,
                           repmask=repmask, rleader=rleader)

    xp = np if backend == "numpy" else jnp
    u, f = _packed_planes(up, xp), _packed_planes(full, xp)
    counts = None
    if recruit is not None:
        counts = _rebuild_node_counts_impl(recruit, active,
                                           n_real=spec.n_real,
                                           backend=backend)
    if spec.metric == "availability":
        lark, maj, creps = bitpack.pac_eval_packed(
            u, f, rf=spec.rf, voters=spec.resolved_voters,
            n_real=spec.n_real, xp=xp)
        return StepOutputs(lark=lark, maj=maj,
                           creps=xp.stack(creps, axis=1), counts=counts)
    rost = None if roster is None else \
        [roster[..., j] for j in range(spec.rf)]
    outs = bitpack.downtime_eval_packed(
        u, f, rf=spec.rf, n_real=spec.n_real, roster=rost,
        want_repmask=want_rm, want_rleader=want_rl, xp=xp)
    repmask, rleader = _take_extras(outs, want_rm, want_rl)
    return StepOutputs(lark=outs[0], maj=outs[1], leader=outs[2],
                       leader_full=outs[3], nrep=outs[4],
                       creps=xp.stack(outs[-1], axis=1), counts=counts,
                       repmask=repmask, rleader=rleader)


# ---------------------------------------------------------------------------
# 2-D fused-kernel autotuner (block_trials x block_p) with fused-kernel
# VMEM accounting — the block_p tuner generalized to the megakernel.
# ---------------------------------------------------------------------------

#: fused-kernel kinds the 2-D tuner can race (StepSpec.fused_kernel)
FUSED_KERNELS = ("fused_pac", "fused_downtime", "fused_downtime_roster")


def fused_vmem_bytes(block_t: int, block_p: int, n_pad: int, *,
                     rf: int = 3, kernel: str = "fused_pac") -> int:
    """VMEM live for one fused (block_t, W, block_p) step tile: packed
    up/full inputs + creps output (3 word tiles, uint32), the row
    outputs, and — per kernel kind — the roster tile, recruit/active
    rows, and the revisited (block_t, n_lanes) counts block.  The packed
    budget is dominated by 3*W words where the boolean kernel held
    4 n_lanes-wide int32 tiles — the fusion's VMEM headroom is what lets
    block_t * block_p grow past the 1-D tuner's ceiling."""
    W = bitpack.n_words(n_pad)
    n_lanes = _pac_lane_pad(n_pad)
    words = 3 * block_t * W * block_p * 4
    rows = 6 * block_t * block_p * 4
    if kernel == "fused_downtime_roster":
        rows += block_t * rf * block_p * 4            # roster tile
        rows += 2 * block_t * block_p * 4             # recruit + active
        rows += block_t * n_lanes * 4                 # counts accumulator
        rows += n_lanes * block_p * 4                 # one-hot operand
    return words + rows


def fused_block_candidates(B: int, P: int, n_pad: int, *, rf: int = 3,
                           kernel: str = "fused_pac",
                           max_block_t: int = 16, max_block_p: int = 1024,
                           vmem_limit_bytes: int = VMEM_BUDGET):
    """Legal (block_t, block_p) pairs — block_t on the (8-sublane) trial
    axis, block_p on the (128-lane) partition axis, per legal_blocks —
    that tile (B, P) within the fused-kernel VMEM budget; raises when
    there is none.  Deterministic pure function of the shape, like
    block_p_candidates."""
    cands = tuple(
        (bt, bp) for bt in legal_blocks(B, 8, max_block_t)
        for bp in legal_blocks(P, 128, max_block_p)
        if fused_vmem_bytes(bt, bp, n_pad, rf=rf,
                            kernel=kernel) <= vmem_limit_bytes)
    if not cands:
        raise ValueError(f"no legal fused tile covers (B={B}, P={P}) "
                         f"within {vmem_limit_bytes} bytes of VMEM")
    return cands


def fused_default_blocks(B: int, P: int, n_pad: int, *, rf: int = 3,
                         kernel: str = "fused_pac") -> Tuple[int, int]:
    """The untuned fused tile: 8 trials (or all B) x the largest legal
    lane block <= 256 (or all P) — legal for any (B, P); raises when it
    overflows the VMEM budget."""
    bt, bp = trial_block(B), lane_block(P, 256)
    if fused_vmem_bytes(bt, bp, n_pad, rf=rf, kernel=kernel) > VMEM_BUDGET:
        raise ValueError(f"no legal fused tile covers (B={B}, P={P}) "
                         f"within the VMEM budget (default {bt}x{bp})")
    return bt, bp


@dataclass(frozen=True)
class FusedAutotuneResult:
    block_t: int
    block_p: int
    timings_us: Mapping[Tuple[int, int], float]
    source: str                       # "measured" | "heuristic-fallback"


def _measure_fused_block(B: int, P: int, n_pad: int, bt: int, bp: int, *,
                         rf: int, voters: int, n_real: int, iters: int,
                         kernel: str) -> float:
    """Median µs/call of the fused megakernel at one (bt, bp) tile, on the
    same deterministic counter-hash density pattern the 1-D tuner uses,
    packed to words."""
    import time

    from . import fused_step
    idx = (jnp.arange(B * P, dtype=jnp.uint32)[:, None]
           * jnp.uint32(n_pad)
           + jnp.arange(n_pad, dtype=jnp.uint32)[None, :])
    up = ((idx * jnp.uint32(2654435761) % jnp.uint32(97)) < 90) \
        .reshape(B, P, n_pad)
    full = ((idx * jnp.uint32(40503) % jnp.uint32(89)) < 30) \
        .reshape(B, P, n_pad)
    upw = jnp.moveaxis(bitpack.pack_words(up, jnp), -1, 1)
    fullw = jnp.moveaxis(bitpack.pack_words(full, jnp), -1, 1)
    interpret = pallas_interpret()
    if kernel == "fused_pac":
        fn = jax.jit(functools.partial(
            fused_step.fused_pac_eval, rf=rf, voters=voters,
            n_real=n_real, block_t=bt, block_p=bp, interpret=interpret))
        args = (upw, fullw)
    elif kernel in ("fused_downtime", "fused_downtime_roster"):
        kw = dict(rf=rf, n_real=n_real, block_t=bt, block_p=bp,
                  interpret=interpret)
        fn = jax.jit(functools.partial(fused_step.fused_downtime_eval,
                                       **kw))
        if kernel == "fused_downtime_roster":
            roster = jnp.broadcast_to(
                jnp.arange(rf, dtype=jnp.int32)[None, :, None],
                (B, rf, P))
            recruit = (jnp.arange(B * P, dtype=jnp.int32) % (n_real + 1)) \
                .reshape(B, P)
            active = (recruit % 3) != 0
            args = (upw, fullw)
            fn = jax.jit(functools.partial(
                fused_step.fused_downtime_eval, roster=roster,
                recruit=recruit, active=active, **kw))
        else:
            args = (upw, fullw)
    else:
        raise ValueError(f"unknown fused autotune kernel {kernel!r}; "
                         f"expected one of {FUSED_KERNELS}")
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e6)
    times.sort()
    return times[len(times) // 2]


def autotune_fused_blocks(B: int, P: int, n_pad: int, *, rf: int,
                          voters: int, n_real: int, candidates=None,
                          iters: int = 9, measure=None,
                          force: bool = False,
                          kernel: str = "fused_pac") -> FusedAutotuneResult:
    """Pick the fastest (block_t, block_p) pair for the fused megakernel
    on a (B, P) packed grid.

    Mirrors autotune_block_p's determinism contract: pure-function
    candidate set, median-of-iters timing, ties toward the smaller tile
    (block_t then block_p), per-(shape, params, kernel) process cache,
    heuristic fallback off-TPU unless forced.  The cache key is tagged
    "fused" and includes the kernel kind and the full 2-D geometry, so it
    can never alias a 1-D block_p entry — the wrong-kernel race fix
    extends to the fused family.
    """
    if kernel not in FUSED_KERNELS:
        raise ValueError(f"unknown fused autotune kernel {kernel!r}; "
                         f"expected one of {FUSED_KERNELS}")
    cands = tuple(candidates) if candidates is not None else \
        fused_block_candidates(B, P, n_pad, rf=rf, kernel=kernel)
    if not cands:
        raise ValueError("autotune_fused_blocks needs at least one "
                         "candidate")
    for bt, bp in cands:
        if B % bt or P % bp:
            raise ValueError(f"candidate ({bt}, {bp}) does not tile "
                             f"(B={B}, P={P})")
    key = ("fused", kernel, B, P, n_pad, rf, voters, n_real, cands, force)
    if measure is None and key in _AUTOTUNE_CACHE:
        return _AUTOTUNE_CACHE[key]
    if measure is None:
        if pallas_interpret() and not force:
            bt, bp = fused_default_blocks(B, P, n_pad, rf=rf, kernel=kernel)
            res = FusedAutotuneResult(block_t=bt, block_p=bp,
                                      timings_us={},
                                      source="heuristic-fallback")
            _AUTOTUNE_CACHE[key] = res
            return res
        measure = functools.partial(_measure_fused_block, rf=rf,
                                    voters=voters, n_real=n_real,
                                    iters=iters, kernel=kernel)
        timings = {c: measure(B, P, n_pad, *c) for c in cands}
        best = min(sorted(timings), key=lambda c: (timings[c], c))
        res = FusedAutotuneResult(block_t=best[0], block_p=best[1],
                                  timings_us=timings, source="measured")
        _AUTOTUNE_CACHE[key] = res
        return res
    timings = {c: float(measure(B, P, n_pad, *c)) for c in cands}
    best = min(sorted(timings), key=lambda c: (timings[c], c))
    return FusedAutotuneResult(block_t=best[0], block_p=best[1],
                               timings_us=timings, source="measured")


def step_hbm_bytes(spec: StepSpec, B: int, P: int, n_pad: int) -> dict:
    """Analytic HBM bytes one step's eval pipeline moves, unfused-boolean
    vs fused-packed — the roofline story behind the megakernel.

    Unfused counts every separate launch the boolean path pays: the eval
    kernel reads up/full/valid int32 lane tiles and writes the creps lane
    tile (+ row outputs), the reconfig roster rides as a lane-padded
    int32 tile, and the bandwidth model's node-count kernel re-reads
    recruit/active in its own pass.  Fused-packed moves three W-word
    uint32 tensors (up, full, creps) plus rows — once.  Ratio ~= the
    round-trip win the kernel_bench fused rows measure.
    """
    R = B * P
    n_lanes = _pac_lane_pad(n_pad)
    reconfig = spec.metric == "downtime" and spec.rebuild_model == "reconfig"
    rows_out = (2 if spec.metric == "availability" else 5) * R * 4
    # boolean path: pac_eval.py reads up/full and writes creps as lane
    # tiles (bool arrays cross the kernel boundary as int32)
    unfused = 3 * R * n_lanes * 4 + rows_out
    if reconfig:
        rf_pad = spec.rf + (-spec.rf % 128)
        unfused += R * rf_pad * 4                       # roster tile
        unfused += 2 * R * 4 + B * n_lanes * 4          # node_count pass
    W = bitpack.n_words(n_pad)
    fused = 3 * B * W * P * 4 + rows_out
    if reconfig:
        fused += B * spec.rf * P * 4                    # unpadded roster
        fused += 2 * B * P * 4 + B * n_lanes * 4        # folded counts
    return {"unfused_bytes": unfused, "fused_bytes": fused,
            "ratio": unfused / fused}


# ---------------------------------------------------------------------------
# Legacy per-kernel entry points — thin deprecated wrappers over step_eval.
# ---------------------------------------------------------------------------

def _deprecated(old: str):
    warnings.warn(
        f"kernels.ops.{old} is deprecated; build a StepSpec and call "
        "kernels.ops.step_eval (one entry point for every metric/"
        "backend/layout)", DeprecationWarning, stacklevel=3)


def pac_eval_batch(up_succ, full_succ, *, rf: int, voters: int, n_real: int,
                   backend: str = "jax", block_p: Optional[int] = None):
    """Deprecated: StepSpec(metric="availability") + step_eval.

    Kept as a thin wrapper so existing callers get the identical
    (lark, maj, creps) tuple; see _pac_eval_unpacked for the contract.
    """
    _deprecated("pac_eval_batch")
    spec = StepSpec(metric="availability", rf=rf, voters=voters,
                    n_real=n_real)
    out = step_eval(spec, up_succ, full_succ, backend=backend,
                    block_p=block_p)
    return out.lark, out.maj, out.creps


def downtime_eval_batch(up_succ, full_succ, *, rf: int, n_real: int,
                        backend: str = "jax",
                        block_p: Optional[int] = None, roster=None):
    """Deprecated: StepSpec(metric="downtime") + step_eval.

    Kept as a thin wrapper so existing callers get the identical
    (lark, qmaj, leader, leader_full, nrep, creps) tuple; see
    _downtime_eval_unpacked for the contract.
    """
    _deprecated("downtime_eval_batch")
    spec = StepSpec(metric="downtime", rf=rf, n_real=n_real,
                    rebuild_model="reconfig" if roster is not None
                    else "fixed")
    out = step_eval(spec, up_succ, full_succ, roster=roster,
                    backend=backend, block_p=block_p)
    return (out.lark, out.maj, out.leader, out.leader_full, out.nrep,
            out.creps)


def rebuild_node_counts(recruit, active, *, n_real: int,
                        backend: str = "jax"):
    """Deprecated: thin wrapper over the counts path step_eval folds into
    the fused kernel; see _rebuild_node_counts_impl for the contract."""
    _deprecated("rebuild_node_counts")
    return _rebuild_node_counts_impl(recruit, active, n_real=n_real,
                                     backend=backend)
