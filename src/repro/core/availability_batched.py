"""Batched device-resident availability Monte Carlo — paper §5.1 at scale.

Advances B independent failure trajectories x P partitions per device step.
Instead of a scalar heapq event loop (core/availability.py), every trial
keeps vectorized state — up mask (B, n), next-event times (B, n), frozen
holder masks (B, P, n) — and each step jumps every trial to its own next
event (``jax.lax.scan`` over event steps, chunked), evaluating PAC /
majority / current-replica conditions as one (B*P, n) rank-space tile
through the unified backend layer in kernels/ops.py:

  backend="numpy"   python chunk loop, vectorized numpy PAC (the event
                    engine's evaluate() math, shared code)
  backend="jax"     jit + lax.scan with the pure-jnp PAC oracle
  backend="pallas"  same scan, PAC via the Pallas kernel (compiled on TPU,
                    interpret mode on CPU)

All backends draw randomness from the same counter-based hash (splitmix-
style, implemented identically in numpy and jnp) keyed by the *global*
(trial, node) lane index, so for a given seed the three produce
bit-identical trajectories — and so do sharded runs: with ``devices=D``
the trials axis is split across a 1-D "trials" mesh (shard_map over
launch/mesh.make_trials_mesh), each shard scanning its B/D trials with its
own slice of the carried lane-offset vector.  Because no step computation
crosses trials and every variate is a pure function of (seed, step, global
lane), a D-device run is bit-identical to the single-device run — the
cross-device agreement tests hold it to that (validate on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

Model semantics match the event engine: geometric inter-failure gaps per
node, fixed downtime, whole-cluster SimpleMajority PAC with frozen holders
while unavailable, majority-of-2f+1 baseline, CI early stopping.  The one
intentional difference: simultaneous same-tick events are applied together
before re-evaluating (the scalar engine interleaves evaluations between
same-tick events), which can freeze a marginally different holder set on
coincident failures — a zero-measure-in-time difference that is invisible
at the CI tolerances used here.

Scenario knobs beyond the paper's i.i.d. grid (named policies over these
live in core/scenarios.py):
  pair_fail_prob  correlated dual failures: when a node fails, its pair
                  partner (2i <-> 2i+1) fails at the same tick with this
                  probability (shared rack / power domain).
  restart_period  rolling restart: every `restart_period` ticks the next
                  `wave_width` nodes in id order are taken down for their
                  downtime (§5.3's zero-downtime rolling-restart claim,
                  as a Monte Carlo scenario).
  wave_width      nodes per restart wave (1 = serial rolling restart).
  p_node          per-node failure probability (heterogeneous MTTF);
                  overrides the scalar `p` for gap scheduling — one
                  geometric CDF table per distinct value (per-class
                  tables selected by node masks), so use a few tiers,
                  not n distinct rates.
  downtime_node   per-node downtime ticks (flapping nodes recover fast);
                  overrides the scalar `downtime`.

The node-trajectory advance (`_make_node_advance` / `_initial_node_state`)
is the single source of randomness for every engine in this stack: the
§6 downtime engine (core/downtime_batched.py) imports it, consumes the
identical variate stream, and therefore replays bit-identical node
trajectories for equal knobs — the invariant that makes its zero-knob
degeneracy tests exact.  Extend the closure rather than drawing ad-hoc
randomness in a new engine; see docs/ARCHITECTURE.md.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, Optional

import numpy as np

from ..kernels import bitpack
from ..kernels.ops import PAC_BACKENDS, StepSpec, step_eval
from .availability import t975
from .succession import succession_matrix_fast

_GEO_SALT = 0x9E3779B9
_PAIR_SALT = 0x85EBCA6B


# ---------------------------------------------------------------------------
# Counter-based RNG, identical under numpy and jax.numpy (uint32 ops wrap).
# ---------------------------------------------------------------------------

def _mix32(x, xp):
    """lowbias32-style avalanche on uint32 arrays."""
    x = x ^ (x >> 16)
    x = x * xp.uint32(0x21F0AAAD)
    x = x ^ (x >> 15)
    x = x * xp.uint32(0xD35A2D97)
    x = x ^ (x >> 15)
    return x


def _uniforms(seed_mix, step_u32, salt: int, lane0, n: int, xp):
    """(B, n) uniforms in [0, 1) from (seed, step, global lane) — stateless.

    ``lane0[b]`` is trial b's first *global* lane id (global_trial * n), so
    the variate a (trial, node) pair sees depends only on its global index,
    never on how the trials axis is sharded — this is what makes a
    shard_map'd run bit-identical to the single-device run.

    The step is hashed into a per-step *key* rather than multiplied into a
    flat counter: a `step * count + lane` counter wraps mod 2^32 and would
    replay the exact variate stream every 2^32/count steps (reachable on
    full-scale grids); keyed lane hashing has no such period.  Scalars are
    kept as 1-element arrays: numpy warns on wrapping *scalar* uint32
    arithmetic but wraps array arithmetic silently (and wrapping is exactly
    what a counter hash wants).
    """
    step_u32 = xp.reshape(step_u32, (1,)).astype(xp.uint32)
    key = _mix32(step_u32 ^ seed_mix ^ xp.uint32(salt), xp)
    lanes = (lane0[:, None] + xp.arange(n, dtype=xp.uint32)[None, :]) \
        * xp.uint32(0x9E3779B9)
    h = _mix32(_mix32(lanes ^ key, xp) ^ seed_mix, xp)
    return (h >> 8).astype(xp.float32) * xp.float32(1.0 / (1 << 24))


def _geometric_breaks(p: float, gap_cap: int) -> np.ndarray:
    """CDF breakpoints for Geom(p) inversion by searchsorted.

    A log-based inverse (floor(log1p(-u)/log1p(-p))) is NOT bit-stable
    across numpy and XLA (libm log1p differs by ulps, and a flipped floor
    forks the whole trajectory).  searchsorted is pure comparisons against
    a shared constant table, so every backend draws identical variates.

    The table covers every value a 24-bit uniform can reach OR stops at
    `gap_cap` entries, whichever is smaller.  The caller passes gap_cap >
    horizon + downtime: a clamped draw schedules its event past the
    horizon where it can never fire, so the truncation is behaviorally
    invisible while keeping the table O(horizon) instead of O(1/p)
    (p=1e-7 would otherwise build a multi-GB table).
    """
    k_max = int(math.ceil(math.log(2.0 ** -25) / math.log1p(-p))) + 2
    k_max = min(k_max, gap_cap)
    k = np.arange(1, k_max + 1, dtype=np.float64)
    return (-np.expm1(k * math.log1p(-p))).astype(np.float32)  # 1-(1-p)^k


def _geometric(u, breaks, xp):
    """Geom(p) on {1, 2, ...}: g = #{k : cdf(k) <= u} + 1."""
    return (xp.searchsorted(breaks, u, side="right") + 1).astype(xp.int32)


def _geo_tables(p_arr: np.ndarray, gap_cap: int, xp):
    """Per-node-class Geom(p) tables: (node masks, CDF tables) per unique p.

    Heterogeneous MTTF keeps one table per distinct failure probability
    (scenarios use a handful of tiers, never n distinct values) and selects
    per node with a mask — all comparisons, so cross-backend bit-identity
    is preserved.
    """
    uniq, inv = np.unique(p_arr, return_inverse=True)
    masks = [xp.asarray(inv == k) for k in range(len(uniq))]
    tables = [xp.asarray(_geometric_breaks(float(pv), gap_cap))
              for pv in uniq]
    return masks, tables


def _geometric_multi(u, geo_masks, geo_tables, xp):
    geo = _geometric(u, geo_tables[0], xp)
    for m, tbl in zip(geo_masks[1:], geo_tables[1:]):
        geo = xp.where(m[None, :], _geometric(u, tbl, xp), geo)
    return geo


# ---------------------------------------------------------------------------
# Result
# ---------------------------------------------------------------------------

@dataclass
class BatchedAvailabilityResult:
    p: float
    rf: int
    n: int
    partitions: int
    trials: int
    backend: str
    ticks: int                    # mean elapsed ticks per trial
    u_lark: float                 # pooled over trials
    u_maj: float
    lark_events: int
    maj_events: int
    ci_lark: float
    ci_maj: float
    stopped_early: bool
    devices: int = 1
    u_lark_trials: np.ndarray = field(repr=False, default=None)
    u_maj_trials: np.ndarray = field(repr=False, default=None)
    trajectory: Optional[Dict[str, np.ndarray]] = field(repr=False,
                                                        default=None)

    @property
    def improvement(self) -> float:
        return self.u_maj / self.u_lark if self.u_lark > 0 else math.inf


# ---------------------------------------------------------------------------
# Node-trajectory advance, written once for both array namespaces and shared
# with the downtime engine (core/downtime_batched.py): any engine built on it
# replays bit-identical failure/recovery trajectories for the same seed.
# ---------------------------------------------------------------------------

def _make_node_advance(xp, *, n: int, horizon: int, dt_vec, geo_masks,
                       geo_tables, seed_mix, pair_fail_prob: float,
                       pair_perm, restart_period: int, wave_width: int):
    """Closure advancing the node up/down state to the next event.

    advance(now, up, ev_t, rr_t, rr_idx, lane0, s) ->
        (t_clamp, dt, active, up, ev_t, rr_t, rr_idx)

    All randomness is drawn here (geometric gap redraws, correlated-pair
    coin flips), keyed by (seed, step s, global lane) — the invariant every
    engine on top of this must preserve is that it consumes *no* extra
    randomness, so availability and downtime runs with equal knobs see the
    same trajectory, and sharded runs match single-device bit for bit.
    """
    def advance(now, up, ev_t, rr_t, rr_idx, lane0, s):
        node_next = xp.min(ev_t, axis=1)                     # (B,)
        t_next = node_next if not restart_period else \
            xp.minimum(node_next, rr_t)
        active = t_next < horizon
        t_clamp = xp.minimum(t_next, xp.int32(horizon))
        dt = (t_clamp - now).astype(xp.float32)

        hit = (ev_t == t_next[:, None]) & active[:, None]
        fail_hit = hit & up
        rec_hit = hit & ~up
        if restart_period:
            rr_hit = active & (rr_t == t_next)
            offs = (xp.arange(n, dtype=xp.int32)[None, :]
                    - rr_idx[:, None]) % n
            tgt = offs < wave_width
            fail_hit = fail_hit | (tgt & up & rr_hit[:, None])
            rr_idx = xp.where(rr_hit, (rr_idx + wave_width) % n, rr_idx)
            rr_t = xp.where(rr_hit, rr_t + restart_period, rr_t)
        s_u32 = xp.asarray(s).astype(xp.uint32)
        if pair_fail_prob > 0.0:
            u2 = _uniforms(seed_mix, s_u32, _PAIR_SALT, lane0, n, xp)
            pf = fail_hit[:, pair_perm] & up & ~fail_hit & ~rec_hit & \
                (u2 < pair_fail_prob)
            fail_hit = fail_hit | pf
        up = (up & ~fail_hit) | rec_hit
        geo = _geometric_multi(
            _uniforms(seed_mix, s_u32, _GEO_SALT, lane0, n, xp),
            geo_masks, geo_tables, xp)
        ev_t = xp.where(fail_hit, t_clamp[:, None] + dt_vec[None, :],
                        xp.where(rec_hit, t_clamp[:, None] + geo, ev_t))
        return t_clamp, dt, active, up, ev_t, rr_t, rr_idx
    return advance


def _initial_node_state(xp, *, B: int, n: int, seed_mix, geo_masks,
                        geo_tables, restart_period: int, horizon: int):
    """(lane0, up0, ev0, rr_t0) — everyone up, first failures at geometric
    gaps drawn at step counter 0 (scan steps start at 1).  lane0 is the
    global first-lane index per trial, carried so each shard keeps its
    global identity after the trials axis is split."""
    lane0 = xp.arange(B, dtype=xp.uint32) * xp.uint32(n)
    up0 = xp.ones((B, n), dtype=bool)
    ev0 = _geometric_multi(
        _uniforms(seed_mix, xp.asarray(0, dtype=xp.uint32), _GEO_SALT,
                  lane0, n, xp),
        geo_masks, geo_tables, xp)
    rr_t0 = xp.full((B,), restart_period if restart_period else horizon + 1,
                    dtype=xp.int32)
    return lane0, up0, ev0, rr_t0


def _initial_full_state(xp, backend: str, eval_fn, up0, succ, *, B: int,
                        P: int, n: int, rf: int, packed: bool = False):
    """t=0 'has the latest copy' mask, shared by both engines: roster
    replicas full, one evaluation on that state, then available (PAC-ok)
    partitions refresh to the committed replica set.  eval_fn is pac_fn or
    dt_fn — both return the LARK mask first and creps last.  Returns
    (full0, eval outputs).

    packed=True carries the holder mask as (B, W, P) uint32 words instead
    of (B, P, n) bool; eval_fn then takes/returns word tensors and
    (B, P)-shaped rows (layout only — same bits)."""
    if packed:
        masks = bitpack.prefix_masks(rf, n)
        full0 = (xp.zeros((B, len(masks), P), dtype=xp.uint32)
                 + xp.asarray(masks, dtype=xp.uint32)[None, :, None])
        upw = xp.moveaxis(bitpack.pack_words(up0[:, succ], xp), -1, 1)
        outs = eval_fn(upw, full0)
        lark0, creps0 = outs[0], outs[-1]
        full0 = xp.where(lark0[:, None, :], creps0, full0)
        return full0, outs
    full0 = xp.zeros((B, P, n), dtype=bool)
    if backend == "numpy":
        full0[:, :, :rf] = True
    else:
        full0 = full0.at[:, :, :rf].set(True)
    outs = eval_fn(up0[:, succ].reshape(B * P, n), full0.reshape(B * P, n))
    lark0, creps0 = outs[0], outs[-1]
    full0 = xp.where(lark0.reshape(B, P)[:, :, None],
                     creps0.reshape(B, P, n), full0)
    return full0, outs


# ---------------------------------------------------------------------------
# Shared driver scaffolding: argument validation, per-run constants, and the
# chunk runners.  The downtime engine reuses all of it, so a retune of any
# trajectory-affecting constant (seed mixing, geometric tables, max_steps
# heuristic, shard specs) lands in both engines at once — a drift here would
# break the exact cross-engine degeneracies tests/test_downtime_batched.py
# pins.
# ---------------------------------------------------------------------------

def _validate_batched_args(*, backend: str, devices: int, trials: int,
                           wave_width: int, n: int):
    if backend not in PAC_BACKENDS:
        raise ValueError(f"backend must be one of {PAC_BACKENDS}")
    if devices < 1:
        raise ValueError("devices must be >= 1")
    if devices > 1 and backend == "numpy":
        raise ValueError("multi-device sharding needs a jax backend "
                         "('jax' or 'pallas'); numpy has no device mesh")
    if trials % devices:
        raise ValueError(f"trials ({trials}) must divide evenly across "
                         f"devices ({devices})")
    if not 1 <= wave_width <= n:
        raise ValueError("wave_width must be in [1, n]")


def _engine_setup(backend: str, *, n: int, partitions: int, seed: int,
                  p: float, downtime: int, p_node, downtime_node,
                  max_ticks: int):
    """(xp, succ, seed_mix, geo_masks, geo_tables, dt_vec, pair_perm,
    p_arr, dt_arr) — every deterministic per-run constant both engines
    share."""
    succ_np = succession_matrix_fast(partitions, range(n), seed=seed)
    if backend == "numpy":
        xp, succ = np, succ_np
    else:
        import jax.numpy as jnp
        xp, succ = jnp, jnp.asarray(succ_np)

    p_arr = np.full(n, p, dtype=np.float64) if p_node is None \
        else np.asarray(p_node, dtype=np.float64)
    dt_arr = np.full(n, downtime, dtype=np.int64) if downtime_node is None \
        else np.asarray(downtime_node, dtype=np.int64)
    if p_arr.shape != (n,) or dt_arr.shape != (n,):
        raise ValueError("p_node / downtime_node must have shape (n,)")
    if not ((p_arr > 0) & (p_arr < 1)).all() or (dt_arr < 1).any():
        raise ValueError("p_node must lie in (0, 1) and downtime_node >= 1")

    seed_mix = _mix32(xp.asarray([(seed & 0xFFFFFFFF) ^ 0x6A09E667],
                                 dtype=xp.uint32), xp)
    geo_masks, geo_tables = _geo_tables(
        p_arr, max_ticks + int(dt_arr.max()) + 2, xp)
    dt_vec = xp.asarray(dt_arr, dtype=xp.int32)
    pair_perm = np.arange(n)
    pair_perm[:n - n % 2] ^= 1
    return (xp, succ, seed_mix, geo_masks, geo_tables, dt_vec, pair_perm,
            p_arr, dt_arr)


def _default_max_steps(p_arr, dt_arr, *, n: int, horizon: int,
                       restart_period: int) -> int:
    """Step budget: ~3x the expected event count plus slack."""
    p_eff = float(p_arr.mean())
    per_trial = 2.0 * n * horizon / (1.0 / p_eff + float(dt_arr.mean()))
    if restart_period:
        per_trial += 2.0 * horizon / restart_period
    return int(3 * per_trial) + 2000


def _make_chunk_runner(step, carry, *, chunk_steps: int, devices: int,
                       shard: bool, n_outputs: int):
    """jit'd (carry, s0) -> (carry, ys) scanning `chunk_steps` steps,
    optionally shard_map'd over the trials mesh (dim 0 of every carry
    leaf; outputs stack steps in front)."""
    import jax
    import jax.numpy as jnp

    def _chunk(c, s0):
        return jax.lax.scan(
            step, c, s0 + jnp.arange(chunk_steps, dtype=jnp.int32))

    if shard:
        from jax.sharding import PartitionSpec

        from ..launch.mesh import make_trials_mesh
        mesh = make_trials_mesh(devices)
        cspec = tuple(PartitionSpec("trials") for _ in carry)
        yspec = tuple(PartitionSpec(None, "trials")
                      for _ in range(n_outputs))
        return jax.jit(jax.shard_map(
            _chunk, mesh=mesh,
            in_specs=(cspec, PartitionSpec()),
            out_specs=(cspec, yspec), check_vma=False))
    return jax.jit(_chunk)


def _run_chunk_numpy(step, carry, s0: int, chunk_steps: int):
    """The numpy backends' python chunk loop (same contract as the jit'd
    runner)."""
    ys = []
    for s in range(s0, s0 + chunk_steps):
        carry, y = step(carry, np.int32(s))
        ys.append(y)
    return carry, tuple(np.stack(col) for col in zip(*ys))


# ---------------------------------------------------------------------------
# The per-event step, written once for both array namespaces.
# ---------------------------------------------------------------------------

def _make_step(xp, pac_fn, succ, *, n: int, P: int, horizon: int,
               dt_vec, geo_masks, geo_tables, seed_mix,
               pair_fail_prob: float, pair_perm, restart_period: int,
               wave_width: int, packed: bool = False):
    advance = _make_node_advance(
        xp, n=n, horizon=horizon, dt_vec=dt_vec, geo_masks=geo_masks,
        geo_tables=geo_tables, seed_mix=seed_mix,
        pair_fail_prob=pair_fail_prob, pair_perm=pair_perm,
        restart_period=restart_period, wave_width=wave_width)

    def step(carry, s):
        (now, up, ev_t, full, dnl, dnm, lpt, mpt, le, me, rr_t, rr_idx,
         lane0) = carry
        B = up.shape[0]               # local trials (a shard of the batch)
        t_clamp, dt, active, up, ev_t, rr_t, rr_idx = advance(
            now, up, ev_t, rr_t, rr_idx, lane0, s)
        lpt = lpt + xp.sum(dnl, axis=1).astype(xp.float32) * dt
        mpt = mpt + xp.sum(dnm, axis=1).astype(xp.float32) * dt
        now = t_clamp

        if packed:
            # packed variant: the node advance is unchanged (it works in
            # (B, n) node space); only the per-partition holder state and
            # its eval move to (B, W, P) uint32 words
            upw = xp.moveaxis(bitpack.pack_words(up[:, succ], xp), -1, 1)
            lark, maj, crepsw = pac_fn(upw, full)
            full = xp.where(lark[:, None, :], crepsw, full)
        else:
            lark, maj, creps = pac_fn(up[:, succ].reshape(B * P, n),
                                      full.reshape(B * P, n))
            lark = lark.reshape(B, P)
            maj = maj.reshape(B, P)
            full = xp.where(lark[:, :, None], creps.reshape(B, P, n), full)
        # outage events are per-partition down-transitions (the downtime
        # engine's lgo/qgo rule): a net per-trial count delta would cancel
        # a partition recovering in the same step another fails and
        # undercount, starving the min_events early-stop
        le = le + xp.sum(~dnl & ~lark, axis=1).astype(xp.int32)
        me = me + xp.sum(~dnm & ~maj, axis=1).astype(xp.int32)
        dnl = ~lark
        dnm = ~maj
        new_unl = xp.sum(dnl, axis=1).astype(xp.int32)
        new_unm = xp.sum(dnm, axis=1).astype(xp.int32)
        nodes_up = xp.sum(up, axis=1).astype(xp.int32)
        carry = (now, up, ev_t, full, dnl, dnm, lpt, mpt, le, me,
                 rr_t, rr_idx, lane0)
        return carry, (t_clamp, new_unl, new_unm, nodes_up)
    return step


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def simulate_availability_batched(
        *, n: int = 155, partitions: int = 4096, rf: int = 2,
        p: float = 1e-3, downtime: int = 10, trials: int = 8,
        min_ticks: int = 50_000, max_ticks: int = 3_000_000,
        eps_abs: float = 5e-6, eps_rel: float = 0.05,
        min_events: int = 200, seed: int = 0, backend: str = "jax",
        pair_fail_prob: float = 0.0, restart_period: int = 0,
        wave_width: int = 1, p_node=None, downtime_node=None,
        devices: int = 1, pac_block_p: Optional[int] = None,
        chunk_steps: int = 512, max_steps: Optional[int] = None,
        trajectory: bool = False, voters: Optional[int] = None,
        use_shard_map: Optional[bool] = None, packed: bool = False,
        block_t: Optional[int] = None) -> BatchedAvailabilityResult:
    """Batched Monte Carlo over `trials` trajectories sharing one succession
    matrix (seeded); failure randomness is independent per trial.

    devices > 1 shards the trials axis over a 1-D "trials" mesh
    (launch/mesh.make_trials_mesh) via shard_map — bit-identical to
    devices=1 for the same seed.  `use_shard_map` forces the shard_map
    code path even on one device (tests).

    voters overrides the baseline quorum size (default 2*(rf-1)+1, the
    paper's 2f+1 voter set).  voters=rf evaluates majority over the f+1
    roster replicas — the instantaneous-availability limit of the
    downtime engine's equal-storage quorum-log baseline, which the
    property tests in tests/test_downtime_batched.py pin exactly.

    packed=True switches the carried holder masks and the per-step eval
    to the bit-packed (B, W, P) uint32 word layout (kernels/bitpack.py);
    on backend="pallas" the step then runs the fused megakernel
    (kernels/fused_step.py) with tile (block_t, block_p) — layout and
    fusion only, trajectories bit-identical to packed=False.
    """
    _validate_batched_args(backend=backend, devices=devices, trials=trials,
                           wave_width=wave_width, n=n)
    shard = use_shard_map if use_shard_map is not None else devices > 1
    B, P, horizon = trials, partitions, max_ticks
    voters = voters if voters is not None else 2 * (rf - 1) + 1
    if not 1 <= voters <= n:
        raise ValueError("voters must be in [1, n]")
    (xp, succ, seed_mix, geo_masks, geo_tables, dt_vec, pair_perm,
     p_arr, dt_arr) = _engine_setup(
        backend, n=n, partitions=P, seed=seed, p=p, downtime=downtime,
        p_node=p_node, downtime_node=downtime_node, max_ticks=max_ticks)
    spec = StepSpec(metric="availability", rf=rf, voters=voters, n_real=n,
                    packed=packed)

    def pac_fn(u, f):
        o = step_eval(spec, u, f, backend=backend, block_p=pac_block_p,
                      block_t=block_t)
        return o.lark, o.maj, o.creps

    step = _make_step(xp, pac_fn, succ, n=n, P=P, horizon=horizon,
                      dt_vec=dt_vec, geo_masks=geo_masks,
                      geo_tables=geo_tables, seed_mix=seed_mix,
                      pair_fail_prob=pair_fail_prob, pair_perm=pair_perm,
                      restart_period=restart_period, wave_width=wave_width,
                      packed=packed)

    # initial state: everyone up, roster replicas full
    lane0, up0, ev0, rr_t0 = _initial_node_state(
        xp, B=B, n=n, seed_mix=seed_mix, geo_masks=geo_masks,
        geo_tables=geo_tables, restart_period=restart_period,
        horizon=horizon)
    full0, (lark0, maj0, _creps0) = _initial_full_state(
        xp, backend, pac_fn, up0, succ, B=B, P=P, n=n, rf=rf,
        packed=packed)
    zi = xp.zeros((B,), dtype=xp.int32)
    zf = xp.zeros((B,), dtype=xp.float32)
    carry = (zi, up0, ev0, full0,
             ~lark0.reshape(B, P),                 # dnl (per-partition)
             ~maj0.reshape(B, P),                  # dnm
             zf, zf, zi, zi, rr_t0, zi, lane0)

    if backend != "numpy":
        import jax.numpy as jnp
        run_chunk = _make_chunk_runner(step, carry, chunk_steps=chunk_steps,
                                       devices=devices, shard=shard,
                                       n_outputs=4)

    if max_steps is None:
        max_steps = _default_max_steps(p_arr, dt_arr, n=n, horizon=horizon,
                                       restart_period=restart_period)

    lpt_tot = np.zeros(B)
    mpt_tot = np.zeros(B)
    le_tot = me_tot = 0
    traj = [] if trajectory else None
    stopped = False
    s0 = 1
    while s0 < max_steps:
        if backend == "numpy":
            carry, ys = _run_chunk_numpy(step, carry, s0, chunk_steps)
        else:
            carry, ys = run_chunk(carry, jnp.int32(s0))
        s0 += chunk_steps
        if trajectory:
            traj.append(tuple(np.asarray(c) for c in ys))
        # drain per-chunk accumulators into float64/int totals
        now = np.asarray(carry[0], dtype=np.int64)
        lpt_tot += np.asarray(carry[6], dtype=np.float64)
        mpt_tot += np.asarray(carry[7], dtype=np.float64)
        le_tot += int(np.asarray(carry[8]).sum())
        me_tot += int(np.asarray(carry[9]).sum())
        carry = carry[:6] + (zf, zf, zi, zi) + carry[10:]
        if (now >= horizon).all():
            break
        # pooled CI early stop, mirroring the event engine's rule.  This is
        # deliberately the NOMINAL binomial width — the same stopping
        # semantics (and therefore comparable tick counts / wall-clock) as
        # the scalar engine — while the *reported* ci_lark/ci_maj use the
        # honest across-trial spread, which is typically wider.
        if now.mean() >= min_ticks and le_tot >= min_events \
                and me_tot >= min_events:
            pt = float(P) * float(now.sum())
            u_l, u_m = lpt_tot.sum() / pt, mpt_tot.sum() / pt
            hw_l = 1.96 * math.sqrt(max(u_l * (1 - u_l), 1e-30) / pt)
            hw_m = 1.96 * math.sqrt(max(u_m * (1 - u_m), 1e-30) / pt)
            if hw_l <= max(eps_abs, eps_rel * u_l) and \
                    hw_m <= max(eps_abs, eps_rel * u_m):
                stopped = True
                break

    now = np.maximum(np.asarray(carry[0], dtype=np.int64), 1)
    pt_b = P * now.astype(np.float64)
    pt = float(pt_b.sum())
    u_l = float(lpt_tot.sum()) / pt
    u_m = float(mpt_tot.sum()) / pt
    u_l_trials = lpt_tot / pt_b
    u_m_trials = mpt_tot / pt_b
    # honest CI from the spread of independent trials (captures the
    # node-failure correlation across partitions that the binomial width
    # misses), floored by the pooled binomial width for tiny batches
    hw_l = hw_m = 0.0
    if B >= 3:
        t = t975(B - 1) / math.sqrt(B)
        hw_l = t * float(u_l_trials.std(ddof=1))
        hw_m = t * float(u_m_trials.std(ddof=1))
    traj_out = None
    if trajectory:
        cols = [np.concatenate([c[i] for c in traj]) for i in range(4)]
        traj_out = {"times": cols[0], "unavail_lark": cols[1],
                    "unavail_maj": cols[2], "nodes_up": cols[3]}
    return BatchedAvailabilityResult(
        p=p, rf=rf, n=n, partitions=P, trials=B, backend=backend,
        ticks=int(now.mean()), u_lark=u_l, u_maj=u_m,
        lark_events=le_tot, maj_events=me_tot,
        ci_lark=max(hw_l,
                    1.96 * math.sqrt(max(u_l * (1 - u_l), 1e-30) / pt)),
        ci_maj=max(hw_m,
                   1.96 * math.sqrt(max(u_m * (1 - u_m), 1e-30) / pt)),
        stopped_early=stopped, devices=devices,
        u_lark_trials=u_l_trials, u_maj_trials=u_m_trials,
        trajectory=traj_out)
