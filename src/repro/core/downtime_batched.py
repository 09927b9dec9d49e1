"""Batched commit-pause / downtime engine — paper §6 at Monte Carlo scale.

Paper anchor: §6's equal-storage-budget argument.  Both systems keep only
f+1 data copies; LARK keeps committing through data-node failures (PAC
reasons over the whole cluster, partitions are ready immediately after a
leader change, at most a per-key duplicate-resolution round trip when the
new leader lacks the latest copy), while quorum-log protocols
(Raft/Paxos/VR-style) commit through a majority of a *fixed replica set*
and must pause commits to rebuild a replica after losing one.  The
instantaneous engine (core/availability_batched.py) measures how often
each protocol *is* available; this engine measures commit-pause
*durations* — how long writes stall, and why.

Runs B trials x P partitions through the exact counter-RNG trajectories of
the availability engine (the node-advance closure is imported from it, and
consumes the identical randomness stream), then carries two per-partition
protocol state machines per step instead of an instantaneous average:

  LARK         paused iff PAC (SimpleMajority) fails; ready the instant
               PAC holds again.  When the acting leader (first up node in
               succession order) changes while the partition is available
               and the new leader lacks the latest copy, an optional
               dup-res penalty of `dupres_ticks` commit-paused ticks is
               charged (the paper's one-round-trip duplicate resolution).
  quorum-log   paused iff a majority of the f+1-copy replica set is down,
               OR a rebuild is in progress.  Two baseline models
               (`rebuild_model`):

               fixed     the replica set is the first rf succession
                         nodes, statically; every replica loss starts a
                         constant `rebuild_steps`-tick countdown during
                         which commits pause (log-based replica catch-up
                         under an equal storage budget).  A finite
                         `node_bandwidth_gibps` makes concurrent
                         catch-ups replaying onto the same node share
                         its ingest bandwidth exactly like the reconfig
                         model below (the log replays onto the lost
                         replica's own node — the lowest lost
                         succession lane); inf — the default — is the
                         unshared constant-countdown model, bit for
                         bit.
               reconfig  the replica set is a carried per-partition
                         *roster* of succession ranks.  After a replica
                         loss the protocol recruits the next up node in
                         succession order (Spinnaker/VR-style
                         reconfiguration onto live nodes), and the
                         catch-up countdown is proportional to the
                         partition's data size: `rebuild_ticks_per_gib`
                         x a per-partition size in GiB drawn
                         deterministically at t=0 (shared by all trials
                         — one cluster dataset, many failure
                         trajectories) from a configurable `size_dist`:
                         uniform [1, 2) GiB, or hot-partition-skewed
                         zipf / lognormal shapes (`size_skew`), all
                         pinned to the same 1.5 GiB mean so skew moves
                         bytes between partitions without changing the
                         equal-storage total.  Concurrent catch-ups
                         ingesting on one recruit node share its
                         `node_bandwidth_gibps` evenly (each advances
                         min(1, bandwidth / k) countdown-ticks per tick
                         in 1/256 fixed-point quanta; inf — the default
                         — is the unshared parallel-rebuild model, bit
                         for bit).  A loss during catch-up restarts the
                         clock; a down roster member with no up
                         replacement available keeps its seat until one
                         appears (late recruitment does not restart the
                         clock — the catch-up was already charged to the
                         loss).  Sizes come from the same counter-hash
                         family as the trajectory RNG under a dedicated
                         salt, so the node-advance randomness stream is
                         untouched and trajectories stay bit-identical
                         to the fixed model's.

Outputs per protocol: the mean commit-pause fraction (paused
partition-ticks / total partition-ticks — with dupres_ticks=0 and
rebuild_steps=0 these degenerate *exactly* to the instantaneous engine's
u_lark and its voters=rf u_maj, a property tests pin bit-for-bit), pause
event counts, and a histogram of completed pause durations in
power-of-two tick buckets (bucket k counts durations in [2^k, 2^(k+1)),
the top bucket open-ended; runs still open at the horizon are censored
and not counted).

Invariants this engine must preserve (see docs/ARCHITECTURE.md):
  * It consumes no randomness beyond the shared node-advance closure, so
    for equal knobs its node trajectory is bit-identical to the
    availability engine's — and across numpy / jax / pallas backends, and
    across any `devices` sharding of the trials axis (same shard_map over
    launch/mesh.make_trials_mesh, same carried global lane offsets).
  * All per-step protocol state is integer/boolean (pause accumulators
    are float32 counts * dt, matching the availability engine's
    arithmetic), so cross-backend equality is exact, not approximate.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, Optional

import numpy as np

from ..kernels import bitpack
from ..kernels.ops import (StepSpec, _rebuild_node_counts_impl,
                           client_latency_step, step_eval)
from .availability import t975
from .availability_batched import (_default_max_steps, _engine_setup,
                                   _initial_full_state, _initial_node_state,
                                   _make_chunk_runner, _make_node_advance,
                                   _mix32, _run_chunk_numpy, _uniforms,
                                   _validate_batched_args)

_SIZE_SALT = 0x94D049BB

REBUILD_MODELS = ("fixed", "reconfig")

#: the protocol zoo: every engine one run can report.  lark and quorum are
#: the paper's §6 pair and always simulated; hermes (Katsarakis et al. —
#: broadcast replication, all replicas serve linearizable reads under
#: membership leases, writes block on a suspected replica until the lease
#: epoch advances) and spinnaker (Rao et al. — Paxos with reconfiguration,
#: a view-change log-reconciliation pause on leader loss) are optional
#: contrast engines riding the same node trajectories.
ENGINES = ("lark", "quorum", "hermes", "spinnaker")

#: the necessity hooks: each disables exactly one transition predicate of
#: the zoo state machines so tests can prove the predicate is load-bearing
#: (tests/test_condition_necessity.py style)
DISABLE_PREDICATES = ("lease-expiry", "view-change-trigger",
                      "roster-recruit")

#: per-partition data-size distributions for the reconfiguring baseline.
#: All three pin the same mean (the uniform model's 1.5 GiB), so every
#: distribution describes the same total dataset under the §6
#: equal-storage budget — skew moves bytes between partitions, never
#: adds them.
SIZE_DISTS = ("uniform", "zipf", "lognormal")

_SIZE_MEAN_GIB = 1.5      # the uniform [1, 2) mean every dist is pinned to

#: largest accepted size_skew: (1 - u)^(-skew) reaches 2^(24 * skew) at
#: the 24-bit uniform's top draw, which overflows float64 (and silently
#: NaN-poisons the mean rescale) just past skew ~42 — cap well below it
_SIZE_SKEW_MAX = 32.0

#: fixed-point scale for bandwidth-shared catch-up countdowns: one
#: countdown tick = _REB_SCALE work units, so a contended rebuild can
#: advance in 1/_REB_SCALE-tick quanta while staying pure int32 math
#: (invariant 4 in docs/ARCHITECTURE.md).  An uncontended rebuild
#: advances _REB_SCALE units/tick — arithmetically identical to the
#: plain-tick countdown, which is what makes node_bandwidth_gibps=inf
#: bit-exact against the unshared model.
_REB_SCALE = 256
_REB_BIG = np.int32(2 ** 30)   # "never finishes" remaining-ticks sentinel

#: largest accepted key_zipf (the client-latency workload's key-popularity
#: exponent): beyond this the zipf mass is so concentrated that the
#: float64 rank weights r^-s underflow for all but the first few keys and
#: the partition weight table degenerates to a handful of point masses
_KEY_ZIPF_MAX = 8.0

#: largest accepted write_skew (the client-latency workload's
#: per-partition write-mix Pareto exponent, core/client_latency.py):
#: same concentration rationale as _KEY_ZIPF_MAX — past this the
#: bounded-Pareto draws collapse the write mix onto a handful of
#: saturated (write fraction 1) partitions and the mean pin degenerates
_WRITE_SKEW_MAX = 8.0


@dataclass(frozen=True)
class DowntimeParams:
    """The §6 engine's protocol/rebuild knobs, validated in one place.

    These eight values are mutually constrained (the skew/bandwidth knobs
    describe the reconfiguring baseline's data-sized catch-ups and are
    rejected under rebuild_model="fixed"; bandwidth has a fixed-point
    quantum floor; ...), and they used to be threaded as loose keywords
    from benchmarks/availability_sweep.py all the way into
    simulate_downtime_batched, with the rules enforced at the bottom.
    One frozen dataclass now owns both the values and the rules: every
    entry point (CLI, engine, tests) constructs it and gets the identical
    ValueError set — see simulate_downtime_batched's docstring for
    per-knob semantics.
    """
    dupres_ticks: int = 1
    rebuild_steps: int = 100
    hist_bins: int = 16
    rebuild_model: str = "fixed"
    rebuild_ticks_per_gib: int = 100
    size_dist: str = "uniform"
    size_skew: float = 1.0
    node_bandwidth_gibps: float = math.inf
    # client-latency workload knobs (core/client_latency.py; inert for the
    # plain downtime metric — the defaults are the zero-request limit).
    # slo_ticks uses a strict `>` (a request violates iff its added
    # latency exceeds the threshold), so slo_ticks=0 is a *live* edge
    # threshold — every request with any positive added latency violates
    # — and doubles as the inert non-latency sentinel only because
    # requests_per_tick=0 offers no requests to violate it.
    # write_skew skews the per-partition write fraction around
    # 1 - read_frac (0 = exactly uniform); slo_curve_bins requests a
    # violation-fraction curve over thresholds 2^j - 1, j < bins (0 =
    # the single slo_ticks point only).
    key_zipf: float = 0.0
    read_frac: float = 1.0
    requests_per_tick: float = 0.0
    slo_ticks: int = 0
    write_skew: float = 0.0
    slo_curve_bins: int = 0
    # protocol-zoo knobs: which engines to report, and their pause costs
    # (lease_ticks — Hermes membership-lease epoch length; a suspected
    # replica blocks writes until it elapses.  view_change_ticks —
    # Spinnaker's log-reconciliation pause after a leader loss.)
    engines: tuple = ("lark", "quorum")
    lease_ticks: int = 0
    view_change_ticks: int = 0

    def __post_init__(self):
        object.__setattr__(self, "engines", tuple(self.engines))
        if not self.engines:
            raise ValueError("engines must name at least one protocol")
        for e in self.engines:
            if e not in ENGINES:
                raise ValueError(f"unknown engine {e!r}; expected a "
                                 f"subset of {ENGINES}")
        if len(set(self.engines)) != len(self.engines):
            raise ValueError(f"duplicate engines: {self.engines}")
        if self.lease_ticks < 0 or self.view_change_ticks < 0:
            raise ValueError("lease_ticks and view_change_ticks must "
                             "be >= 0")
        if self.lease_ticks > 0 and not self.hermes:
            raise ValueError("lease_ticks models the hermes engine's "
                             "membership leases; add 'hermes' to engines")
        if self.view_change_ticks > 0 and not self.spinnaker:
            raise ValueError("view_change_ticks models the spinnaker "
                             "engine's view changes; add 'spinnaker' to "
                             "engines")
        if self.spinnaker and not self.reconfig:
            raise ValueError("the spinnaker engine elects among the "
                             "reconfiguring baseline's roster; use "
                             "rebuild_model='reconfig'")
        if self.dupres_ticks < 0 or self.rebuild_steps < 0:
            raise ValueError("dupres_ticks and rebuild_steps must be >= 0")
        if not 2 <= self.hist_bins <= 30:
            raise ValueError("hist_bins must be in [2, 30]")
        if self.rebuild_model not in REBUILD_MODELS:
            raise ValueError(
                f"rebuild_model must be one of {REBUILD_MODELS}")
        if self.rebuild_ticks_per_gib < 0:
            raise ValueError("rebuild_ticks_per_gib must be >= 0")
        if self.size_dist not in SIZE_DISTS:
            raise ValueError(f"size_dist must be one of {SIZE_DISTS}")
        if not 0 <= self.size_skew <= _SIZE_SKEW_MAX:
            raise ValueError(
                f"size_skew must be in [0, {_SIZE_SKEW_MAX:g}]")
        if not self.node_bandwidth_gibps >= 1.0 / _REB_SCALE:
            raise ValueError(
                f"node_bandwidth_gibps must be >= 1/{_REB_SCALE} "
                "(the fixed-point rate quantum — below it even an "
                "uncontended catch-up rounds to zero progress; "
                "inf disables bandwidth sharing)")
        if not self.reconfig and self.size_dist != "uniform":
            raise ValueError(
                "size_dist models the reconfiguring baseline's "
                "data-sized catch-ups; use rebuild_model='reconfig' "
                "(node_bandwidth_gibps applies to both rebuild models)")
        if not 0 <= self.key_zipf <= _KEY_ZIPF_MAX:
            raise ValueError(
                f"key_zipf must be in [0, {_KEY_ZIPF_MAX:g}] (the zipf "
                "key-popularity exponent; 0 is uniform)")
        if not 0 <= self.read_frac <= 1:
            raise ValueError("read_frac must be in [0, 1]")
        if not (self.requests_per_tick >= 0
                and math.isfinite(self.requests_per_tick)):
            raise ValueError("requests_per_tick must be finite and >= 0")
        if self.slo_ticks < 0:
            raise ValueError("slo_ticks must be >= 0 (0 is a live "
                             "threshold under the strict-> rule: every "
                             "request with positive added latency "
                             "violates it)")
        if not 0 <= self.write_skew <= _WRITE_SKEW_MAX:
            raise ValueError(
                f"write_skew must be in [0, {_WRITE_SKEW_MAX:g}] (the "
                "per-partition write-mix Pareto exponent; 0 is exactly "
                "uniform)")
        if not 0 <= self.slo_curve_bins <= self.hist_bins:
            raise ValueError(
                "slo_curve_bins must be in [0, hist_bins] — the curve's "
                "2^j - 1 thresholds are derived from the power-of-two "
                "latency histogram and cannot outrun its buckets")

    @property
    def reconfig(self) -> bool:
        return self.rebuild_model == "reconfig"

    @property
    def bandwidth_shared(self) -> bool:
        return math.isfinite(self.node_bandwidth_gibps)

    @property
    def hermes(self) -> bool:
        return "hermes" in self.engines

    @property
    def spinnaker(self) -> bool:
        return "spinnaker" in self.engines


def _norm_ppf(u: np.ndarray) -> np.ndarray:
    """Inverse standard-normal CDF (Acklam's rational approximation,
    |rel err| < 1.2e-9) — vectorized host-side numpy, no scipy.  Only
    used to shape the deterministic lognormal size table, so approximation
    error just perturbs the (arbitrary) distribution shape; determinism
    is what matters."""
    a = (-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00)
    b = (-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01)
    c = (-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00)
    d = (7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00)
    u = np.clip(np.asarray(u, dtype=np.float64), 2.0 ** -25, 1 - 2.0 ** -25)
    lo, hi = u < 0.02425, u > 1 - 0.02425
    mid = ~(lo | hi)
    z = np.empty_like(u)
    q = np.sqrt(-2.0 * np.log(np.where(lo, u, 0.5)))
    z_lo = (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
            + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = np.sqrt(-2.0 * np.log(np.where(hi, 1 - u, 0.5)))
    z_hi = -(((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
             + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = u - 0.5
    r = q * q
    z_mid = (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
             + a[5]) * q / \
        (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r + b[4]) * r + 1)
    z[lo] = z_lo[lo]
    z[hi] = z_hi[hi]
    z[mid] = z_mid[mid]
    return z


def partition_sizes_gib(seed: int, partitions: int, *,
                        dist: str = "uniform",
                        skew: float = 1.0) -> np.ndarray:
    """Deterministic per-partition data sizes in GiB.

    dist selects the shape (SIZE_DISTS):
      uniform    uniform in [1, 2) — the original baseline, byte-identical
                 to the pre-skew table (the skew knob is inert here).
      zipf       bounded Pareto hot-partition skew: raw = (1 - u)^(-skew),
                 rescaled so the sample mean is exactly the uniform mean
                 (1.5 GiB).  skew=0 degenerates to every partition at
                 exactly 1.5 GiB; larger skews concentrate the dataset in
                 a few huge partitions and push the rest below 1 GiB.
      lognormal  raw = exp(skew * z(u)) with z the inverse normal CDF,
                 mean-rescaled the same way (skew is the log-space sigma).

    The mean pin keeps the total dataset — the §6 equal-storage budget —
    identical across distributions: skew redistributes bytes, never adds
    them.  Draws come once at t=0 from the same counter-hash family as
    the trajectory RNG but under a dedicated salt and partition-indexed
    lanes, so the node-advance randomness stream is untouched (invariant
    3 in docs/ARCHITECTURE.md) and every size distribution replays the
    exact node trajectories of every other.  Always computed host-side in
    numpy — every backend receives the identical table.
    """
    if dist not in SIZE_DISTS:
        raise ValueError(f"dist must be one of {SIZE_DISTS}; got {dist!r}")
    if not 0 <= skew <= _SIZE_SKEW_MAX:
        raise ValueError(f"skew must be in [0, {_SIZE_SKEW_MAX:g}] "
                         f"(larger Pareto exponents overflow the float64 "
                         f"size table); got {skew!r}")
    seed_mix = _mix32(np.asarray([(seed & 0xFFFFFFFF) ^ 0x6A09E667],
                                 dtype=np.uint32), np)
    u = _uniforms(seed_mix, np.asarray(0, dtype=np.uint32), _SIZE_SALT,
                  np.zeros(1, dtype=np.uint32), partitions, np)[0] \
        .astype(np.float64)
    if dist == "uniform":
        return 1.0 + u
    if dist == "zipf":
        raw = (1.0 - u) ** (-skew)
    else:                                        # lognormal
        raw = np.exp(skew * _norm_ppf(u))
    return raw * (_SIZE_MEAN_GIB / raw.mean())


def _partition_rebuild_ticks(seed: int, partitions: int,
                             ticks_per_gib: int, *,
                             dist: str = "uniform", skew: float = 1.0,
                             cap: Optional[int] = None) -> np.ndarray:
    """(P,) int32 catch-up countdowns for the reconfiguring baseline:
    floor(ticks_per_gib x size_gib), clamped to >= 1 tick whenever a
    rebuild costs anything at all (skewed draws push partitions below
    1 GiB, and a catch-up of epsilon bytes still takes one tick — without
    the clamp a sub-GiB partition would rebuild for free and its pause
    run would degenerate to the dropped zero-length case).  `cap`
    (the engine passes horizon + 1) bounds the table so the fixed-point
    work units stay in int32; a countdown beyond the horizon can never
    complete in-simulation, so the clamp is observationally invisible.
    With the uniform dist and ticks_per_gib == rebuild_steps every
    catch-up is >= the fixed model's constant (sizes >= 1 GiB), and both
    clamps are no-ops — the pre-skew table, bit for bit."""
    t = np.floor(ticks_per_gib *
                 partition_sizes_gib(seed, partitions, dist=dist, skew=skew))
    if ticks_per_gib > 0:
        t = np.maximum(t, 1.0)
    if cap is not None:
        t = np.minimum(t, float(cap))
    return t.astype(np.int32)


# ---------------------------------------------------------------------------
# Result
# ---------------------------------------------------------------------------

@dataclass
class BatchedDowntimeResult:
    p: float
    rf: int
    n: int
    partitions: int
    trials: int
    backend: str
    ticks: int                       # mean elapsed ticks per trial
    pause_lark: float                # mean commit-pause fraction, pooled
    pause_quorum: float
    lark_events: int                 # pause-start events (incl. dup-res)
    quorum_events: int
    ci_lark: float                   # 95% half-widths on the fractions
    ci_quorum: float
    dupres_ticks: int
    rebuild_steps: int
    stopped_early: bool
    devices: int = 1
    rebuild_model: str = "fixed"
    rebuild_ticks_per_gib: int = 0   # reconfig only; 0 under "fixed"
    size_dist: str = "uniform"       # reconfig only; "uniform" under "fixed"
    size_skew: float = 0.0           # zipf/lognormal only; 0 elsewhere
    node_bandwidth_gibps: float = math.inf   # reconfig only; inf = unshared
    hist_edges: np.ndarray = field(repr=False, default=None)   # (nbins,)
    hist_lark: np.ndarray = field(repr=False, default=None)    # (nbins,)
    hist_quorum: np.ndarray = field(repr=False, default=None)
    pause_lark_trials: np.ndarray = field(repr=False, default=None)
    pause_quorum_trials: np.ndarray = field(repr=False, default=None)
    #: protocol-zoo outputs — None/0 unless the matching engine was in
    #: `engines` (lark/quorum keep their dedicated fields above)
    engines: tuple = ("lark", "quorum")
    lease_ticks: int = 0
    view_change_ticks: int = 0
    pause_hermes: Optional[float] = None
    hermes_events: int = 0
    ci_hermes: float = 0.0
    pause_spinnaker: Optional[float] = None
    spinnaker_events: int = 0
    ci_spinnaker: float = 0.0
    hist_hermes: np.ndarray = field(repr=False, default=None)
    hist_spinnaker: np.ndarray = field(repr=False, default=None)
    pause_hermes_trials: np.ndarray = field(repr=False, default=None)
    pause_spinnaker_trials: np.ndarray = field(repr=False, default=None)
    trajectory: Optional[Dict[str, np.ndarray]] = field(repr=False,
                                                        default=None)
    #: raw per-trial client-latency accumulators (only when the engine is
    #: driven through core/client_latency.py): dup (B, NB) expected LARK
    #: first-touch charges per key bucket, qhist (B, nbins) quorum
    #: rebuild-wait requests per power-of-two latency bucket, qslo (B,)
    #: requests over the SLO, qsum (B,) total latency ticks, now (B,)
    #: elapsed ticks — all pooled over partitions host-side in float64
    latency_raw: Optional[Dict[str, np.ndarray]] = field(repr=False,
                                                         default=None)

    @property
    def availability_ratio(self) -> float:
        """Quorum-log pause over LARK pause — the §6 headline ratio."""
        return self.pause_quorum / self.pause_lark if self.pause_lark > 0 \
            else math.inf

    def engine_stats(self, engine: str) -> Dict[str, object]:
        """Uniform per-engine view: pause fraction, CI half-width, event
        count, duration histogram, and per-trial fractions for any member
        of ENGINES (raises if the engine wasn't simulated)."""
        if engine not in self.engines:
            raise ValueError(f"engine {engine!r} was not simulated "
                             f"(engines={self.engines})")
        by = {
            "lark": (self.pause_lark, self.ci_lark, self.lark_events,
                     self.hist_lark, self.pause_lark_trials),
            "quorum": (self.pause_quorum, self.ci_quorum,
                       self.quorum_events, self.hist_quorum,
                       self.pause_quorum_trials),
            "hermes": (self.pause_hermes, self.ci_hermes,
                       self.hermes_events, self.hist_hermes,
                       self.pause_hermes_trials),
            "spinnaker": (self.pause_spinnaker, self.ci_spinnaker,
                          self.spinnaker_events, self.hist_spinnaker,
                          self.pause_spinnaker_trials),
        }[engine]
        return {"pause": by[0], "ci_pause": by[1], "events": by[2],
                "hist": by[3], "pause_trials": by[4]}


# ---------------------------------------------------------------------------
# The per-event step.
# ---------------------------------------------------------------------------

def _hist_add(xp, hist_bins: int, hist, mask, d):
    """Scatter completed pause durations d (B, P) where mask into
    power-of-two buckets (bucket k counts [2^k, 2^(k+1)), top bucket
    open-ended) — comparisons only, so every backend bins identically.
    Duration-0 runs (opened and closed at the same tick by coincident
    events) are not pauses and are dropped, never mis-binned into the
    [1, 2) bucket."""
    mask = mask & (d > 0)
    b = xp.zeros(d.shape, dtype=xp.int32)
    for k in range(1, hist_bins):
        b = b + (d >= (1 << k)).astype(xp.int32)
    oh = (b[:, :, None] == xp.arange(hist_bins, dtype=xp.int32)
          [None, None, :]) & mask[:, :, None]
    return hist + xp.sum(oh, axis=1).astype(xp.int32)


def _make_step(xp, dt_fn, advance, succ, *, n: int, P: int, rf: int,
               dupres_ticks: int, rebuild_steps: int, hist_bins: int,
               rebuild_model: str = "fixed", rebuild_ticks=None,
               bandwidth_fp=None, cnt_fn=None, rebuild_fp=None,
               packed: bool = False,
               lat_fn=None, engines: tuple = (), lease_ticks: int = 0,
               view_change_ticks: int = 0, disable=frozenset()):
    hermes = "hermes" in engines
    spinnaker = "spinnaker" in engines
    # necessity hooks: each strips one transition predicate so tests can
    # prove it is load-bearing; production runs pass an empty set
    lease_on = "lease-expiry" not in disable
    vc_on = "view-change-trigger" not in disable
    recruit_on = "roster-recruit" not in disable

    def hist_add(hist, mask, d):
        return _hist_add(xp, hist_bins, hist, mask, d)

    def lat_interval(lat, dt_i, ldn, qmaj_prev, rem):
        """Charge the client-latency layer for one event interval from
        interval-start state (requests in [now, t_clamp) see the carried
        protocol state; both protocols only flip at events).  The lat
        leaves ride at the tail of the scan carry; layout-independent
        (consumes only (B, P) row state), so packed and unpacked carries
        charge identically."""
        if lat_fn is None:
            return lat
        return lat_fn(lat, dt_i, ~ldn, qmaj_prev, rem)

    def lat_dirty_reset(lat, pen):
        """A leader change onto a stale leader makes every key of the
        partition dirty: its next touch pays the dup-res round."""
        if lat_fn is None or pen is None:
            return lat
        return (xp.where(pen[:, :, None], xp.float32(1.0), lat[0]),) \
            + lat[1:]

    # -- shared protocol blocks.  Both rebuild models run these verbatim
    # (the models differ only in how the replica set and the rebuild
    # countdown are derived), so a retune lands in both state machines at
    # once — the LARK-bit-identity-across-models and fixed-model-baseline
    # pins in tests/test_downtime_batched.py depend on that.

    def interval_pause(now, dt, dt_i, ldn, qrep, qreb, qdn, qt0, lpt, qpt,
                       qhist, rate=None):
        """Pause time over [now, t_clamp) from interval-start state.
        LARK matches the availability engine's lpt arithmetic exactly
        (count * dt in float32); quorum adds the rebuild overlap —
        min(remaining, dt) extra paused ticks per majority-up partition —
        and a rebuild expiring mid-interval ends a quorum pause run
        between events (PAC state can only flip at events, so LARK runs
        never end mid-interval).

        rate=None is the fixed model's plain-tick countdown (qreb in
        ticks, one tick of progress per tick).  A rate array puts qreb in
        _REB_SCALE fixed-point work units: each partition's catch-up
        advances dt * rate units over the interval (rate is the
        bandwidth share its recruit node grants, <= _REB_SCALE), finishes
        when cumulative progress covers the remaining units, and its
        remaining wall-ticks are ceil(units / rate) — at rate ==
        _REB_SCALE every expression reduces to the plain-tick arithmetic
        exactly, which is what keeps node_bandwidth_gibps=inf
        bit-identical to the unshared model."""
        lpt = lpt + xp.sum(ldn, axis=1).astype(xp.float32) * dt
        qmaj_prev = 2 * xp.sum(qrep, axis=2) > rf             # (B, P)
        qpt = qpt + xp.sum(~qmaj_prev, axis=1).astype(xp.float32) * dt
        if rate is None:
            rem = qreb                       # remaining wall-ticks
            prog = dt_i[:, None]             # progress over the interval
        else:
            # the divisor is floored at 1 only to keep numpy's eager
            # where-evaluation from dividing by zero; rate == 0 (a
            # starved rebuild) still selects the never-finishes sentinel
            safe_rate = xp.maximum(rate, 1)
            rem = xp.where(qreb > 0,
                           xp.where(rate > 0,
                                    (qreb + safe_rate - 1) // safe_rate,
                                    _REB_BIG),
                           0)
            prog = dt_i[:, None] * rate
        qpt = qpt + xp.sum(xp.where(
            qmaj_prev, xp.minimum(rem, dt_i[:, None]), 0)
            .astype(xp.float32), axis=1)
        ends_mid = qdn & qmaj_prev & (qreb > 0) & (prog >= qreb)
        qhist = hist_add(qhist, ends_mid, (now[:, None] + rem) - qt0)
        qdn = qdn & ~ends_mid
        qreb = xp.maximum(qreb - prog, 0)
        # qmaj_prev / rem are the interval-start majority mask and
        # remaining rebuild wall-ticks — the client-latency layer charges
        # this interval's requests from exactly these values
        return lpt, qpt, qreb, qdn, qhist, qmaj_prev, rem

    def lark_transitions(t_clamp, lark, ldr, lfull, ldn, lt0, leader, lpt,
                         lev, lhist):
        """Close LARK runs that came back, open new ones, and charge the
        dup-res penalty: available partition, new acting leader, and the
        leader lacks the latest copy (pre-refresh full mask) -> one round
        trip of paused commits, charged instantaneously.  The baseline
        only tracks the leader *while available* (no commits flow during
        a pause), so a leadership move inside an outage is still charged
        when service resumes under the new stale leader."""
        lhist = hist_add(lhist, ldn & lark, t_clamp[:, None] - lt0)
        lgo = ~ldn & ~lark
        lt0 = xp.where(lgo, t_clamp[:, None], lt0)
        lev = lev + xp.sum(lgo, axis=1).astype(xp.int32)
        ldn = ~lark
        pen = None
        if dupres_ticks > 0:
            pen = (ldr != leader) & lark & ~lfull
            npen = xp.sum(pen, axis=1).astype(xp.int32)
            lpt = lpt + npen.astype(xp.float32) * xp.float32(dupres_ticks)
            lev = lev + npen
            lhist = hist_add(lhist, pen,
                             xp.full(pen.shape, dupres_ticks,
                                     dtype=xp.int32))
        leader = xp.where(lark, ldr, leader)
        return ldn, lt0, leader, lpt, lev, lhist, pen

    def pause_transitions(t_clamp, pause, dn, t0, ev, hist):
        """Close pause runs whose condition cleared, open new ones (a
        pause-start is one counted event) — the post-event transition
        block every protocol-zoo engine shares with the quorum baseline
        (identical op order, so a degenerate-knob engine reproduces the
        baseline's run accounting bit for bit)."""
        hist = hist_add(hist, dn & ~pause, t_clamp[:, None] - t0)
        go = ~dn & pause
        t0 = xp.where(go, t_clamp[:, None], t0)
        ev = ev + xp.sum(go, axis=1).astype(xp.int32)
        return pause, t0, ev, hist

    def quorum_transitions(t_clamp, qmaj, qreb, qdn, qt0, qev, qhist):
        return pause_transitions(t_clamp, ~qmaj | (qreb > 0), qdn, qt0,
                                 qev, qhist)

    # -- protocol-zoo engines.  Each carries 7 leaves of its own —
    # (dn bool, t0 i32, 2 engine-specific (B, P) i32 states, pt f32 (B,),
    # ev i32 (B,), hist i32 (B, hist_bins)) — and consumes NO randomness:
    # both ride the identical node trajectories (invariant 3), which is
    # what makes the degenerate-knob limits exact rather than statistical.

    def knob_interval(now, dt, dt_i, base_dn, rem_x, dn, t0, pt, hist, *,
                      expire=True):
        """Interval pause charge for a knob-pause engine over
        [now, t_clamp): full dt where the engine's base condition held at
        interval start (same expression as the lark/quorum charges, so a
        zero-knob engine accrues bit-identically), plus min(countdown,
        dt) extra paused ticks where it didn't but a knob countdown was
        running; a countdown expiring mid-interval with the base
        condition clear closes the pause run between events."""
        pt = pt + xp.sum(base_dn, axis=1).astype(xp.float32) * dt
        pt = pt + xp.sum(
            xp.where(~base_dn, xp.minimum(rem_x, dt_i[:, None]), 0)
            .astype(xp.float32), axis=1)
        if expire:
            ends_mid = dn & ~base_dn & (rem_x > 0) & \
                (dt_i[:, None] >= rem_x)
            hist = hist_add(hist, ends_mid, (now[:, None] + rem_x) - t0)
            dn = dn & ~ends_mid
        return pt, dn, hist

    def hermes_interval(now, dt, dt_i, ldn, hstate):
        """Hermes charges: down for the whole interval wherever PAC was
        down at interval start (reads need a serving partition just like
        LARK — all replicas serve, so the availability condition is
        LARK's), plus the remaining lease-epoch wait where writes were
        blocked on a suspected replica."""
        hdn, ht0, hmask, hlease, hpt, hev, hhist = hstate
        hpt, hdn, hhist = knob_interval(now, dt, dt_i, ldn, hlease, hdn,
                                        ht0, hpt, hhist, expire=lease_on)
        if lease_on:
            hlease = xp.maximum(hlease - dt_i[:, None], 0)
        return (hdn, ht0, hmask, hlease, hpt, hev, hhist)

    def hermes_post(t_clamp, lark, repm, hstate):
        """Post-event Hermes transition: any member of the carried
        membership view going down is a suspicion — writes block until
        the lease epoch advances (lease_ticks later) — and the view
        re-forms on the surviving replicas."""
        hdn, ht0, hmask, hlease, hpt, hev, hhist = hstate
        loss_h = (hmask & ~repm) != 0
        if lease_ticks > 0:
            hlease = xp.where(loss_h, xp.int32(lease_ticks), hlease)
        hmask = repm
        hpause = ~lark | (hlease > 0)
        hdn, ht0, hev, hhist = pause_transitions(t_clamp, hpause, hdn,
                                                 ht0, hev, hhist)
        return (hdn, ht0, hmask, hlease, hpt, hev, hhist)

    def spinnaker_interval(now, dt, dt_i, qmaj_prev, rem0, sstate):
        """Spinnaker charges: the quorum baseline's interval accounting
        (majority-down + remaining catch-up wall-ticks) with the
        view-change reconciliation countdown overlaid — the pause ends
        when the later of the two clears, so the interval-start remaining
        wait is their max."""
        sdn, st0, sldr, svc, spt, sev, shist = sstate
        spt, sdn, shist = knob_interval(
            now, dt, dt_i, ~qmaj_prev, xp.maximum(rem0, svc), sdn, st0,
            spt, shist)
        svc = xp.maximum(svc - dt_i[:, None], 0)
        return (sdn, st0, sldr, svc, spt, sev, shist)

    def spinnaker_post(t_clamp, qmaj, qreb, rup_post, roster, rlead,
                       sstate):
        """Post-event Spinnaker transition: losing the elected leader
        (no longer an up roster member) triggers a view change — the new
        leader (minimum up roster rank, from the kernel's rleader output)
        pauses commits for view_change_ticks of log reconciliation on
        top of any catch-up."""
        sdn, st0, sldr, svc, spt, sev, shist = sstate
        valid = xp.any((roster == sldr[:, :, None]) & rup_post, axis=2)
        new_sldr = xp.where(valid, sldr, rlead)
        trigger = ~valid & (sldr < n) & (new_sldr < n) & \
            (new_sldr != sldr)
        if view_change_ticks > 0 and vc_on:
            svc = xp.where(trigger, xp.int32(view_change_ticks), svc)
        sldr = new_sldr
        spause = ~qmaj | (qreb > 0) | (svc > 0)
        sdn, st0, sev, shist = pause_transitions(t_clamp, spause, sdn,
                                                 st0, sev, shist)
        return (sdn, st0, sldr, svc, spt, sev, shist)

    def step(carry, s):
        (now, up, ev_t, full, rr_t, rr_idx, lane0, ldn, lt0, qrep, qreb,
         qdn, qt0, leader, lpt, qpt, lev, qev, lhist, qhist) = carry[:20]
        k = 20
        hstate = None
        if hermes:
            hstate = carry[k:k + 7]
            k += 7
        lat = carry[k:]
        B = up.shape[0]               # local trials (a shard of the batch)
        t_clamp, dt, active, up, ev_t, rr_t, rr_idx = advance(
            now, up, ev_t, rr_t, rr_idx, lane0, s)
        dt_i = t_clamp - now                                  # (B,) int32
        lpt, qpt, qreb, qdn, qhist, qmaj_prev, rem0 = interval_pause(
            now, dt, dt_i, ldn, qrep, qreb, qdn, qt0, lpt, qpt, qhist)
        if hermes:
            hstate = hermes_interval(now, dt, dt_i, ldn, hstate)
        lat = lat_interval(lat, dt_i, ldn, qmaj_prev, rem0)
        now = t_clamp

        # -- re-evaluate both protocols on the post-event cluster state
        up_succ = up[:, succ]                                 # (B, P, n)
        rep_new = up_succ[:, :, :rf]                          # replica lanes
        repm = None
        if packed:
            upw = xp.moveaxis(bitpack.pack_words(up_succ, xp), -1, 1)
            out_t = dt_fn(upw, full)
            lark, qmaj, ldr, lfull = out_t[:4]
            crepsw = out_t[-1]
            if hermes:
                repm = out_t[5]
            full = xp.where(lark[:, None, :], crepsw, full)
        else:
            out_t = dt_fn(
                up_succ.reshape(B * P, n), full.reshape(B * P, n))
            lark = out_t[0].reshape(B, P)
            qmaj = out_t[1].reshape(B, P)
            ldr = out_t[2].reshape(B, P)
            lfull = out_t[3].reshape(B, P)
            if hermes:
                repm = out_t[5].reshape(B, P)
            full = xp.where(lark[:, :, None],
                            out_t[-1].reshape(B, P, n), full)

        ldn, lt0, leader, lpt, lev, lhist, pen = lark_transitions(
            t_clamp, lark, ldr, lfull, ldn, lt0, leader, lpt, lev, lhist)
        lat = lat_dirty_reset(lat, pen)

        # -- any replica loss (a replica-set lane going up -> down, even
        # if masked by a simultaneous recovery of another lane)
        # (re)starts the constant rebuild countdown
        if rebuild_steps > 0:
            loss = xp.any(qrep & ~rep_new, axis=2)
            qreb = xp.where(loss, xp.int32(rebuild_steps), qreb)
        qdn, qt0, qev, qhist = quorum_transitions(
            t_clamp, qmaj, qreb, qdn, qt0, qev, qhist)
        qrep = rep_new
        if hermes:
            hstate = hermes_post(t_clamp, lark, repm, hstate)

        carry = (now, up, ev_t, full, rr_t, rr_idx, lane0, ldn, lt0,
                 qrep, qreb, qdn, qt0, leader, lpt, qpt, lev, qev,
                 lhist, qhist) + (hstate if hermes else ()) + lat
        out = (t_clamp, xp.sum(ldn, axis=1).astype(xp.int32),
               xp.sum(qdn, axis=1).astype(xp.int32),
               xp.sum(up, axis=1).astype(xp.int32))
        if hermes:
            out = out + (xp.sum(hstate[0], axis=1).astype(xp.int32),)
        return carry, out

    def step_fixed_bw(carry, s):
        """The fixed model with per-node bandwidth-contended rebuilds:
        `step`'s state machines verbatim, except qreb is carried in
        _REB_SCALE fixed-point work units (restart value `rebuild_fp`)
        and each interval's progress rate is the bandwidth share the
        rebuilding node grants — the identical rate block the reconfig
        steps run, so the two models' contention math can never drift
        apart.  The replica set is static, so the ingesting node is the
        lost replica's own (the log replays onto the lowest lost
        succession lane); it rides in a carried `recruit` leaf exactly
        like the reconfig carry.  bandwidth_fp=None never dispatches
        here — the legacy `step` runs untouched, which is what keeps
        node_bandwidth_gibps=inf bit-identical to the unshared model.
        Like step_reconfig_packed, the post-event evaluation runs before
        the interval charges (one fused dt_fn call on the packed pallas
        path folds eval + node counts); the counts and interval_pause
        still see interval-start carry state, so this is a pure dataflow
        reorder of `step`."""
        (now, up, ev_t, full, rr_t, rr_idx, lane0, ldn, lt0, qrep, qreb,
         qdn, qt0, leader, lpt, qpt, lev, qev, lhist, qhist,
         recruit) = carry[:21]
        k = 21
        hstate = None
        if hermes:
            hstate = carry[k:k + 7]
            k += 7
        lat = carry[k:]
        B = up.shape[0]               # local trials (a shard of the batch)
        t_clamp, dt, active, up, ev_t, rr_t, rr_idx = advance(
            now, up, ev_t, rr_t, rr_idx, lane0, s)
        dt_i = t_clamp - now                                  # (B,) int32

        # -- post-event cluster state + the in-flight node counts from
        # the carried interval-start recruit/qreb (the same reduction as
        # the reconfig steps; one fused call when packed)
        up_succ = up[:, succ]                                 # (B, P, n)
        rep_new = up_succ[:, :, :rf]                          # replica lanes
        inflight = (qreb > 0) & (recruit < n)
        repm = None
        if packed:
            upw = xp.moveaxis(bitpack.pack_words(up_succ, xp), -1, 1)
            out_t = dt_fn(upw, full, None, recruit, inflight)
            lark, qmaj, ldr, lfull = out_t[:4]
            counts = out_t[-1]
            crepsw = out_t[-2]
            if hermes:
                repm = out_t[5]
        else:
            out_t = dt_fn(up_succ.reshape(B * P, n),
                          full.reshape(B * P, n), None, recruit, inflight)
            lark = out_t[0].reshape(B, P)
            qmaj = out_t[1].reshape(B, P)
            ldr = out_t[2].reshape(B, P)
            lfull = out_t[3].reshape(B, P)
            counts = out_t[-1]
            if hermes:
                repm = out_t[5].reshape(B, P)
        kk = xp.take_along_axis(counts,
                                xp.clip(recruit, 0, n - 1), axis=1)
        # sentinel-recruit partitions must not inherit node n-1's
        # in-flight count from the clipped gather (see step_reconfig)
        kk = xp.where(recruit < n, xp.maximum(kk, 1), 1)
        rate = xp.minimum(xp.int32(_REB_SCALE),
                          xp.int32(bandwidth_fp) // kk)

        lpt, qpt, qreb, qdn, qhist, qmaj_prev, rem0 = interval_pause(
            now, dt, dt_i, ldn, qrep, qreb, qdn, qt0, lpt, qpt, qhist,
            rate=rate)
        if hermes:
            hstate = hermes_interval(now, dt, dt_i, ldn, hstate)
        lat = lat_interval(lat, dt_i, ldn, qmaj_prev, rem0)
        now = t_clamp

        if packed:
            full = xp.where(lark[:, None, :], crepsw, full)
        else:
            full = xp.where(lark[:, :, None],
                            out_t[-2].reshape(B, P, n), full)
        ldn, lt0, leader, lpt, lev, lhist, pen = lark_transitions(
            t_clamp, lark, ldr, lfull, ldn, lt0, leader, lpt, lev, lhist)
        lat = lat_dirty_reset(lat, pen)

        # -- a replica loss (re)starts the constant countdown, now in
        # fixed-point units, and pins the rebuild to the lost replica's
        # node: the lowest replica lane that went up -> down this step
        # (simultaneous losses replay onto the first — one log stream
        # per partition, like the reconfig model's single recruit)
        if rebuild_fp is not None and rebuild_fp > 0:
            lost = qrep & ~rep_new                            # (B, P, rf)
            loss = xp.any(lost, axis=2)
            qreb = xp.where(loss, xp.int32(rebuild_fp), qreb)
            rank = xp.min(xp.where(lost,
                                   xp.arange(rf, dtype=xp.int32)
                                   [None, None, :], xp.int32(rf)), axis=2)
            node = succ[xp.arange(P, dtype=xp.int32)[None, :],
                        xp.clip(rank, 0, rf - 1)]
            recruit = xp.where(loss, node, recruit)
        qdn, qt0, qev, qhist = quorum_transitions(
            t_clamp, qmaj, qreb, qdn, qt0, qev, qhist)
        qrep = rep_new
        if hermes:
            hstate = hermes_post(t_clamp, lark, repm, hstate)

        carry = (now, up, ev_t, full, rr_t, rr_idx, lane0, ldn, lt0,
                 qrep, qreb, qdn, qt0, leader, lpt, qpt, lev, qev,
                 lhist, qhist, recruit) + (hstate if hermes else ()) + lat
        out = (t_clamp, xp.sum(ldn, axis=1).astype(xp.int32),
               xp.sum(qdn, axis=1).astype(xp.int32),
               xp.sum(up, axis=1).astype(xp.int32))
        if hermes:
            out = out + (xp.sum(hstate[0], axis=1).astype(xp.int32),)
        return carry, out

    lanes_n = xp.arange(n, dtype=xp.int32)

    def recruit_roster(up_succ, rup, roster):
        """Replace every down roster member with the first up node in
        succession order not already in the roster (if none is up, the
        seat is kept until a later step finds one).  Returns the new
        roster plus (new_rank, took) — the most recent recruit's
        succession rank per partition and whether any seat was filled."""
        if not recruit_on:       # necessity hook: no seat is ever filled
            return (roster, xp.full(rup.shape[:2], n, dtype=xp.int32),
                    xp.zeros(rup.shape[:2], dtype=bool))
        in_roster = xp.zeros(up_succ.shape, dtype=bool)
        for j in range(rf):
            in_roster = in_roster | (lanes_n[None, None, :]
                                     == roster[:, :, j, None])
        slot = xp.arange(rf, dtype=xp.int32)
        new_rank = xp.full(rup.shape[:2], n, dtype=xp.int32)
        took = xp.zeros(rup.shape[:2], dtype=bool)
        for j in range(rf):
            need = ~rup[:, :, j]
            cand = up_succ & ~in_roster
            repl = xp.min(xp.where(cand, lanes_n[None, None, :],
                                   xp.int32(n)), axis=2)
            take = need & (repl < n)
            old_j = roster[:, :, j]
            new_j = xp.where(take, repl, old_j)
            in_roster = in_roster & ~(take[:, :, None] &
                                      (lanes_n[None, None, :]
                                       == old_j[:, :, None]))
            in_roster = in_roster | (take[:, :, None] &
                                     (lanes_n[None, None, :]
                                      == new_j[:, :, None]))
            roster = xp.where((slot == j)[None, None, :],
                              new_j[:, :, None], roster)
            new_rank = xp.where(take, repl, new_rank)
            took = took | take
        return roster, new_rank, took

    def step_reconfig(carry, s):
        """The reconfiguring baseline: identical to `step` (same shared
        protocol blocks) except the quorum-log replica set is the carried
        per-partition roster of succession ranks (reconfigured onto live
        nodes after losses) and the catch-up countdown is the
        per-partition `rebuild_ticks` table, in _REB_SCALE fixed-point
        work units so concurrent catch-ups ingesting on one recruit node
        can share its bandwidth (rate = min(full speed, bandwidth / k)
        recomputed at every event boundary from the carried recruit node
        ids; bandwidth_fp=None skips the reduction and runs every rebuild
        at full speed — the unshared model, bit for bit).  LARK's code
        path is untouched, so LARK outputs are bit-identical across
        rebuild models and bandwidth settings."""
        (now, up, ev_t, full, rr_t, rr_idx, lane0, ldn, lt0, qrep, qreb,
         qdn, qt0, leader, lpt, qpt, lev, qev, lhist, qhist,
         roster, recruit) = carry[:22]
        ke = 22
        hstate = sstate = None
        if hermes:
            hstate = carry[ke:ke + 7]
            ke += 7
        if spinnaker:
            sstate = carry[ke:ke + 7]
            ke += 7
        lat = carry[ke:]
        B = up.shape[0]               # local trials (a shard of the batch)
        t_clamp, dt, active, up, ev_t, rr_t, rr_idx = advance(
            now, up, ev_t, rr_t, rr_idx, lane0, s)
        dt_i = t_clamp - now                                  # (B,) int32
        # -- per-node bandwidth contention over this interval: in-flight
        # catch-ups ingesting on the same recruit node split its
        # bandwidth evenly (the in-flight set only changes at events, so
        # the share is constant within an interval; a catch-up whose
        # recruit is unknown — lost during a no-candidate stretch — runs
        # uncontended).  The node-count reduction is the engine's only
        # cross-partition coupling; it stays within each trial, so
        # trials-axis sharding commutes with it (docs/ARCHITECTURE.md).
        if bandwidth_fp is None:
            rate = xp.full((B, P), _REB_SCALE, dtype=xp.int32)
        else:
            inflight = (qreb > 0) & (recruit < n)
            counts = cnt_fn(recruit, inflight)                # (B, n)
            k = xp.take_along_axis(counts,
                                   xp.clip(recruit, 0, n - 1), axis=1)
            # sentinel-recruit partitions must not inherit node n-1's
            # in-flight count from the clipped gather: no known ingest
            # node means no contention
            k = xp.where(recruit < n, xp.maximum(k, 1), 1)
            rate = xp.minimum(xp.int32(_REB_SCALE),
                              xp.int32(bandwidth_fp) // k)
        lpt, qpt, qreb, qdn, qhist, qmaj_prev, rem0 = interval_pause(
            now, dt, dt_i, ldn, qrep, qreb, qdn, qt0, lpt, qpt, qhist,
            rate=rate)
        if hermes:
            hstate = hermes_interval(now, dt, dt_i, ldn, hstate)
        if spinnaker:
            sstate = spinnaker_interval(now, dt, dt_i, qmaj_prev, rem0,
                                        sstate)
        lat = lat_interval(lat, dt_i, ldn, qmaj_prev, rem0)
        now = t_clamp

        # -- post-event cluster state; fresh losses are roster members
        # that were up at interval start and are down now
        up_succ = up[:, succ]                                 # (B, P, n)
        rup = xp.take_along_axis(up_succ, roster, axis=2)     # (B, P, rf)
        loss_any = xp.any(qrep & ~rup, axis=2)

        # -- recruit: every down roster member is replaced by the first
        # up node in succession order not already in the roster
        roster, new_rank, took = recruit_roster(up_succ, rup, roster)

        # -- each fresh loss (re)starts the data-sized catch-up countdown
        qreb = xp.where(loss_any, rebuild_ticks[None, :], qreb)
        # -- the ingesting node is the most recently recruited member
        # (ranks are per-partition succession indices; bandwidth is per
        # physical node, so map through the succession matrix).  A loss
        # with no candidate leaves the seat — and the ingest node —
        # unknown until late recruitment fills it.
        new_node = succ[xp.arange(P, dtype=xp.int32)[None, :],
                        xp.clip(new_rank, 0, n - 1)]
        recruit = xp.where(took, new_node,
                           xp.where(loss_any, xp.int32(n), recruit))

        # -- roster-aware per-step evaluation on the reconfigured roster
        out_t = dt_fn(
            up_succ.reshape(B * P, n), full.reshape(B * P, n),
            roster.reshape(B * P, rf))
        lark = out_t[0].reshape(B, P)
        qmaj = out_t[1].reshape(B, P)
        ldr = out_t[2].reshape(B, P)
        lfull = out_t[3].reshape(B, P)
        repm = out_t[5].reshape(B, P) if hermes else None
        rlead = out_t[5 + int(hermes)].reshape(B, P) if spinnaker \
            else None
        full = xp.where(lark[:, :, None], out_t[-1].reshape(B, P, n),
                        full)

        ldn, lt0, leader, lpt, lev, lhist, pen = lark_transitions(
            t_clamp, lark, ldr, lfull, ldn, lt0, leader, lpt, lev, lhist)
        lat = lat_dirty_reset(lat, pen)
        qdn, qt0, qev, qhist = quorum_transitions(
            t_clamp, qmaj, qreb, qdn, qt0, qev, qhist)
        qrep = xp.take_along_axis(up_succ, roster, axis=2)
        if hermes:
            hstate = hermes_post(t_clamp, lark, repm, hstate)
        if spinnaker:
            sstate = spinnaker_post(t_clamp, qmaj, qreb, qrep, roster,
                                    rlead, sstate)

        carry = (now, up, ev_t, full, rr_t, rr_idx, lane0, ldn, lt0,
                 qrep, qreb, qdn, qt0, leader, lpt, qpt, lev, qev,
                 lhist, qhist, roster, recruit) \
            + (hstate if hermes else ()) \
            + (sstate if spinnaker else ()) + lat
        out = (t_clamp, xp.sum(ldn, axis=1).astype(xp.int32),
               xp.sum(qdn, axis=1).astype(xp.int32),
               xp.sum(up, axis=1).astype(xp.int32))
        if hermes:
            out = out + (xp.sum(hstate[0], axis=1).astype(xp.int32),)
        if spinnaker:
            out = out + (xp.sum(sstate[0], axis=1).astype(xp.int32),)
        return carry, out

    def step_reconfig_packed(carry, s):
        """step_reconfig over packed (B, W, P) holder words, reordered so
        the whole post-event evaluation — both protocols, the roster
        membership, and the bandwidth model's in-flight node counts — is
        ONE dt_fn call (one fused pallas_call on that backend).  Pure
        dataflow reorder of the unfused step: the reconfiguration runs
        first (it needs only the advanced up mask and carried roster
        state), the counts still see the carried interval-start
        recruit/qreb, and interval_pause still sees interval-start
        protocol state — trajectories are bit-identical."""
        (now, up, ev_t, full, rr_t, rr_idx, lane0, ldn, lt0, qrep, qreb,
         qdn, qt0, leader, lpt, qpt, lev, qev, lhist, qhist,
         roster, recruit) = carry[:22]
        ke = 22
        hstate = sstate = None
        if hermes:
            hstate = carry[ke:ke + 7]
            ke += 7
        if spinnaker:
            sstate = carry[ke:ke + 7]
            ke += 7
        lat = carry[ke:]
        B = up.shape[0]               # local trials (a shard of the batch)
        t_clamp, dt, active, up, ev_t, rr_t, rr_idx = advance(
            now, up, ev_t, rr_t, rr_idx, lane0, s)
        dt_i = t_clamp - now                                  # (B,) int32

        # post-event cluster state + reconfiguration up front (same rules
        # as step_reconfig, via the shared recruit_roster closure)
        up_succ = up[:, succ]                                 # (B, P, n)
        rup = xp.take_along_axis(up_succ, roster, axis=2)     # (B, P, rf)
        loss_any = xp.any(qrep & ~rup, axis=2)
        roster, new_rank, took = recruit_roster(up_succ, rup, roster)

        # the single per-step eval: packed words + reconfigured roster
        # (+ carried recruit/in-flight for the contention counts).  The
        # protocol-zoo extras sit between nrep and crepsw, so the fixed
        # landmarks are out_t[:4] and the crepsw/counts tail offsets.
        ne = int(hermes) + int(spinnaker)
        upw = xp.moveaxis(bitpack.pack_words(up_succ, xp), -1, 1)
        if bandwidth_fp is None:
            out_t = dt_fn(upw, full, roster)
            rate = xp.full((B, P), _REB_SCALE, dtype=xp.int32)
        else:
            inflight = (qreb > 0) & (recruit < n)
            out_t = dt_fn(upw, full, roster, recruit, inflight)
            counts = out_t[6 + ne]
            k = xp.take_along_axis(counts,
                                   xp.clip(recruit, 0, n - 1), axis=1)
            k = xp.where(recruit < n, xp.maximum(k, 1), 1)
            rate = xp.minimum(xp.int32(_REB_SCALE),
                              xp.int32(bandwidth_fp) // k)
        lark, qmaj, ldr, lfull = out_t[:4]
        crepsw = out_t[5 + ne]
        repm = out_t[5] if hermes else None
        rlead = out_t[5 + int(hermes)] if spinnaker else None

        lpt, qpt, qreb, qdn, qhist, qmaj_prev, rem0 = interval_pause(
            now, dt, dt_i, ldn, qrep, qreb, qdn, qt0, lpt, qpt, qhist,
            rate=rate)
        if hermes:
            hstate = hermes_interval(now, dt, dt_i, ldn, hstate)
        if spinnaker:
            sstate = spinnaker_interval(now, dt, dt_i, qmaj_prev, rem0,
                                        sstate)
        lat = lat_interval(lat, dt_i, ldn, qmaj_prev, rem0)
        now = t_clamp

        qreb = xp.where(loss_any, rebuild_ticks[None, :], qreb)
        new_node = succ[xp.arange(P, dtype=xp.int32)[None, :],
                        xp.clip(new_rank, 0, n - 1)]
        recruit = xp.where(took, new_node,
                           xp.where(loss_any, xp.int32(n), recruit))

        full = xp.where(lark[:, None, :], crepsw, full)
        ldn, lt0, leader, lpt, lev, lhist, pen = lark_transitions(
            t_clamp, lark, ldr, lfull, ldn, lt0, leader, lpt, lev, lhist)
        lat = lat_dirty_reset(lat, pen)
        qdn, qt0, qev, qhist = quorum_transitions(
            t_clamp, qmaj, qreb, qdn, qt0, qev, qhist)
        qrep = xp.take_along_axis(up_succ, roster, axis=2)
        if hermes:
            hstate = hermes_post(t_clamp, lark, repm, hstate)
        if spinnaker:
            sstate = spinnaker_post(t_clamp, qmaj, qreb, qrep, roster,
                                    rlead, sstate)

        carry = (now, up, ev_t, full, rr_t, rr_idx, lane0, ldn, lt0,
                 qrep, qreb, qdn, qt0, leader, lpt, qpt, lev, qev,
                 lhist, qhist, roster, recruit) \
            + (hstate if hermes else ()) \
            + (sstate if spinnaker else ()) + lat
        out = (t_clamp, xp.sum(ldn, axis=1).astype(xp.int32),
               xp.sum(qdn, axis=1).astype(xp.int32),
               xp.sum(up, axis=1).astype(xp.int32))
        if hermes:
            out = out + (xp.sum(hstate[0], axis=1).astype(xp.int32),)
        if spinnaker:
            out = out + (xp.sum(sstate[0], axis=1).astype(xp.int32),)
        return carry, out

    if rebuild_model == "reconfig":
        return step_reconfig_packed if packed else step_reconfig
    if bandwidth_fp is not None:
        return step_fixed_bw
    return step


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def _pool_partitions(acc, weight=None):
    """Sum a (B, P, ...) float32 charge accumulator over partitions in
    float64, in sequence along P.  The C-order copy pins that order: a
    one-device TPU array can reach the host with its device layout's
    strides (partitions innermost), and numpy then sums that axis
    pairwise — a different float64 rounding of the same values, which
    broke the devices 1-vs-N identity of the latency pools on a v5e."""
    x = np.ascontiguousarray(acc, dtype=np.float64)
    return (x if weight is None else x * weight).sum(axis=1)


def simulate_downtime_batched(
        *, n: int = 155, partitions: int = 4096, rf: int = 2,
        p: float = 1e-3, downtime: int = 10, trials: int = 8,
        min_ticks: int = 50_000, max_ticks: int = 3_000_000,
        eps_abs: float = 5e-6, eps_rel: float = 0.05,
        min_events: int = 200, seed: int = 0, backend: str = "jax",
        dupres_ticks: int = 1, rebuild_steps: int = 100,
        hist_bins: int = 16,
        rebuild_model: str = "fixed", rebuild_ticks_per_gib: int = 100,
        size_dist: str = "uniform", size_skew: float = 1.0,
        node_bandwidth_gibps: float = math.inf,
        pair_fail_prob: float = 0.0, restart_period: int = 0,
        wave_width: int = 1, p_node=None, downtime_node=None,
        devices: int = 1, pac_block_p: Optional[int] = None,
        chunk_steps: int = 512, max_steps: Optional[int] = None,
        trajectory: bool = False,
        use_shard_map: Optional[bool] = None,
        params: Optional[DowntimeParams] = None, packed: bool = False,
        block_t: Optional[int] = None,
        engines: tuple = ("lark", "quorum"), lease_ticks: int = 0,
        view_change_ticks: int = 0,
        _disable_predicates: tuple = (),
        _lat_plan=None) -> BatchedDowntimeResult:
    """Batched §6 commit-pause Monte Carlo over `trials` trajectories.

    Accepts the availability engine's cluster/scenario knobs unchanged
    (every core/scenarios.py policy runs here too), plus the protocol/
    rebuild knobs below.  They can be passed individually (legacy
    keywords) or as one pre-validated `params=DowntimeParams(...)` —
    when `params` is given it takes precedence and the individual
    keywords are ignored; either way DowntimeParams owns the validation
    rules, so every entry point raises the identical errors.

    packed=True carries the holder masks as bit-packed (B, W, P) uint32
    words and evaluates each step through kernels/bitpack.py — on
    backend="pallas" via the fused step megakernel (one pallas_call for
    both protocols + roster + rebuild node counts; tile (block_t,
    block_p)).  Layout/fusion only: trajectories are bit-identical to
    packed=False on every backend.

    dupres_ticks   LARK's per-leader-change duplicate-resolution cost in
                   ticks (0 disables; then LARK pause == instantaneous
                   PAC unavailability exactly).  The charge is
                   instantaneous, so a cost comparable to the horizon can
                   push the raw pause integral past wall time; reported
                   fractions are clipped to [0, 1].
    rebuild_model  "fixed" (default): static first-rf replica set with a
                   constant rebuild countdown — the pre-roster baseline,
                   bit-identical to it.  "reconfig": replica-set
                   reconfiguration onto live nodes with a data-sized
                   catch-up (see the module docstring).
    rebuild_steps  fixed-model rebuild countdown after a replica loss
                   (0 disables; then quorum pause == plain
                   majority-of-replica-set unavailability exactly).
                   Ignored under rebuild_model="reconfig".
    rebuild_ticks_per_gib
                   reconfig-model catch-up cost per GiB of partition
                   data; per-partition sizes come from `size_dist`
                   (partition_sizes_gib).  Ignored under
                   rebuild_model="fixed".
    size_dist      per-partition data-size distribution for the reconfig
                   catch-ups (SIZE_DISTS): "uniform" (the [1, 2) GiB
                   baseline, default), "zipf" (hot-partition Pareto
                   skew), or "lognormal" — all pinned to the uniform
                   mean of 1.5 GiB so the equal-storage budget is
                   identical across distributions.  Reconfig only.
    size_skew      shape parameter of the skewed dists (Pareto exponent /
                   log-space sigma); 0 collapses either to a constant
                   1.5 GiB.  Inert under size_dist="uniform".
    node_bandwidth_gibps
                   per-node catch-up ingest bandwidth, in units of
                   full-speed catch-up streams (1 stream == 1 GiB/s at
                   one tick per second; `rebuild_ticks_per_gib` prices a
                   GiB at that full-speed rate).  Concurrent catch-ups
                   recruited onto the same node split it evenly: each
                   advances min(1, bandwidth / k) countdown-ticks per
                   tick, quantized to 1/256 (pure int32 fixed-point, so
                   cross-backend bit-identity holds; a share below the
                   quantum — k > 256 x bandwidth — rounds to zero and
                   the catch-up stalls until contention eases, which is
                   why bandwidth itself must be >= 1/256).  The default
                   inf disables sharing and is bit-identical to the
                   unshared parallel-rebuild model.  Applies to both
                   rebuild models: under rebuild_model="fixed" a lost
                   replica's log replays onto its *own* node (lowest
                   lost succession rank), so concurrent fixed-model
                   rebuilds landing on one node split its bandwidth the
                   same way reconfig catch-ups do.
    hist_bins      power-of-two duration buckets ([1,2), [2,4), ...,
                   top bucket open-ended).

    devices > 1 shards trials over the same 1-D "trials" mesh as the
    availability engine — bit-identical to devices=1 for the same seed.

    engines selects the protocol zoo to report (ENGINES; lark and quorum
    are the paper's pair and always simulated — listing extra engines
    adds their state machines on the *same* node trajectories, changing
    no lark/quorum output bit).  lease_ticks prices the hermes engine's
    membership-lease epoch (0 pins hermes to the zero-knob LARK trace
    exactly, given dupres_ticks=0); view_change_ticks prices the
    spinnaker engine's leader-loss log reconciliation (0 pins spinnaker
    to the reconfig quorum baseline exactly).  _disable_predicates
    (private, DISABLE_PREDICATES) strips single transition predicates for
    the necessity tests.

    _lat_plan (private; set by core/client_latency.py) appends the
    client-latency layer's per-(trial, partition) float32 accumulators to
    the scan carry and fills `latency_raw` on the result — the downtime
    outputs themselves are untouched (the layer reads protocol state,
    never writes it).
    """
    _validate_batched_args(backend=backend, devices=devices, trials=trials,
                           wave_width=wave_width, n=n)
    if params is None:
        params = DowntimeParams(
            dupres_ticks=dupres_ticks, rebuild_steps=rebuild_steps,
            hist_bins=hist_bins, rebuild_model=rebuild_model,
            rebuild_ticks_per_gib=rebuild_ticks_per_gib,
            size_dist=size_dist, size_skew=size_skew,
            node_bandwidth_gibps=node_bandwidth_gibps,
            engines=engines, lease_ticks=lease_ticks,
            view_change_ticks=view_change_ticks)
    dupres_ticks, rebuild_steps = params.dupres_ticks, params.rebuild_steps
    hist_bins, rebuild_model = params.hist_bins, params.rebuild_model
    rebuild_ticks_per_gib = params.rebuild_ticks_per_gib
    size_dist, size_skew = params.size_dist, params.size_skew
    node_bandwidth_gibps = params.node_bandwidth_gibps
    reconfig = params.reconfig
    bandwidth_shared = params.bandwidth_shared
    engines = params.engines
    lease_ticks = params.lease_ticks
    view_change_ticks = params.view_change_ticks
    hermes_on, spinnaker_on = params.hermes, params.spinnaker
    disable = frozenset(_disable_predicates)
    unknown = disable - set(DISABLE_PREDICATES)
    if unknown:
        raise ValueError(f"unknown disable predicates {sorted(unknown)}; "
                         f"expected a subset of {DISABLE_PREDICATES}")
    if (reconfig or bandwidth_shared) \
            and max_ticks > (2 ** 31 - 1) // _REB_SCALE - 2:
        raise ValueError("max_ticks too large for the fixed-point "
                         f"catch-up countdowns (<= "
                         f"{(2 ** 31 - 1) // _REB_SCALE - 2})")
    shard = use_shard_map if use_shard_map is not None else devices > 1
    B, P, horizon = trials, partitions, max_ticks
    (xp, succ, seed_mix, geo_masks, geo_tables, dt_vec, pair_perm,
     p_arr, dt_arr) = _engine_setup(
        backend, n=n, partitions=P, seed=seed, p=p, downtime=downtime,
        p_node=p_node, downtime_node=downtime_node, max_ticks=max_ticks)
    zoo = tuple(e for e in ("hermes", "spinnaker") if e in engines)
    spec = StepSpec(metric="downtime", rf=rf, n_real=n,
                    rebuild_model=rebuild_model, packed=packed,
                    dupres_ticks=dupres_ticks, rebuild_steps=rebuild_steps,
                    engines=zoo)

    def dt_fn(u, f, roster=None, recruit=None, active=None):
        o = step_eval(spec, u, f, roster=roster, recruit=recruit,
                      active=active, backend=backend, block_p=pac_block_p,
                      block_t=block_t)
        extras = ()
        if o.repmask is not None:
            extras = extras + (o.repmask,)
        if o.rleader is not None:
            extras = extras + (o.rleader,)
        base = (o.lark, o.maj, o.leader, o.leader_full, o.nrep) \
            + extras + (o.creps,)
        return (base + (o.counts,)) if recruit is not None else base

    rebuild_ticks = xp.asarray(_partition_rebuild_ticks(
        seed, P, rebuild_ticks_per_gib, dist=size_dist, skew=size_skew,
        cap=max_ticks + 1) * np.int32(_REB_SCALE)) if reconfig else None
    bandwidth_fp = int(min(math.floor(_REB_SCALE * node_bandwidth_gibps),
                           int(_REB_BIG))) if bandwidth_shared else None
    cnt_fn = (lambda rec, act: _rebuild_node_counts_impl(
        rec, act, n_real=n, backend=backend)) if bandwidth_shared else None
    # fixed-model restart value in fixed-point work units; the horizon
    # cap keeps rebuild_steps * _REB_SCALE inside int32 and is
    # observationally invisible (a countdown past the horizon can never
    # complete in-simulation), mirroring _partition_rebuild_ticks's cap
    rebuild_fp = int(min(rebuild_steps, max_ticks + 1)) * _REB_SCALE \
        if (bandwidth_shared and not reconfig) else None
    advance = _make_node_advance(
        xp, n=n, horizon=horizon, dt_vec=dt_vec, geo_masks=geo_masks,
        geo_tables=geo_tables, seed_mix=seed_mix,
        pair_fail_prob=pair_fail_prob, pair_perm=pair_perm,
        restart_period=restart_period, wave_width=wave_width)
    lat_fn = None
    if _lat_plan is not None:
        lat_pow = xp.asarray(_lat_plan.pow_tables)
        lat_kf = xp.asarray(_lat_plan.kf)
        lat_lamw = xp.asarray(_lat_plan.lamw)
        lat_nbins, lat_slo = _lat_plan.nbins, _lat_plan.slo_ticks

        def lat_fn(lat, dt_i, avail, qok, rem):
            nd, di, hi, si, qi = client_latency_step(
                lat[0], dt_i, avail, qok, rem, pow_tables=lat_pow,
                kf=lat_kf, lamw=lat_lamw, nbins=lat_nbins,
                slo_ticks=lat_slo, backend=backend)
            return (nd, lat[1] + di, lat[2] + hi, lat[3] + si,
                    lat[4] + qi)
    step = _make_step(xp, dt_fn, advance, succ, n=n, P=P, rf=rf,
                      dupres_ticks=dupres_ticks,
                      rebuild_steps=rebuild_steps, hist_bins=hist_bins,
                      rebuild_model=rebuild_model,
                      rebuild_ticks=rebuild_ticks,
                      bandwidth_fp=bandwidth_fp, cnt_fn=cnt_fn,
                      rebuild_fp=rebuild_fp,
                      packed=packed, lat_fn=lat_fn, engines=zoo,
                      lease_ticks=lease_ticks,
                      view_change_ticks=view_change_ticks,
                      disable=disable)

    # initial state: everyone up, roster replicas full, both protocols
    # evaluated once at t=0 (identical to the availability engine's init;
    # the t=0 roster is [0..rf-1] per partition, so the non-roster init
    # evaluation is exact for both rebuild models)
    lane0, up0, ev0, rr_t0 = _initial_node_state(
        xp, B=B, n=n, seed_mix=seed_mix, geo_masks=geo_masks,
        geo_tables=geo_tables, restart_period=restart_period,
        horizon=horizon)
    full0, outs0 = _initial_full_state(
        xp, backend, dt_fn, up0, succ, B=B, P=P, n=n, rf=rf, packed=packed)
    lark0 = outs0[0].reshape(B, P)
    qmaj0 = outs0[1].reshape(B, P)
    ldr0 = outs0[2].reshape(B, P)
    zi = xp.zeros((B,), dtype=xp.int32)
    zf = xp.zeros((B,), dtype=xp.float32)
    zbp = xp.zeros((B, P), dtype=xp.int32)
    zh = xp.zeros((B, hist_bins), dtype=xp.int32)
    carry = (zi, up0, ev0, full0, rr_t0, zi, lane0,
             ~lark0, zbp,                              # ldn, lt0
             up0[:, succ[:, :rf]],                     # qrep (all up)
             zbp,                                      # qreb
             ~qmaj0, zbp,                              # qdn, qt0
             ldr0.astype(xp.int32),                    # leader
             zf, zf, zi, zi, zh, zh)
    if reconfig:
        roster0 = xp.broadcast_to(
            xp.arange(rf, dtype=xp.int32)[None, None, :], (B, P, rf))
        if backend == "numpy":
            roster0 = np.ascontiguousarray(roster0)
        # no catch-up in flight at t=0, so no recruit node to ingest on
        recruit0 = xp.full((B, P), n, dtype=xp.int32)
        carry = carry + (roster0, recruit0)
    elif bandwidth_shared:
        # fixed model with bandwidth contention carries only the
        # rebuilding-node leaf (the replica set itself is static)
        carry = carry + (xp.full((B, P), n, dtype=xp.int32),)
    h0 = len(carry)                   # hermes leaves start here (if any)
    if hermes_on:
        # the t=0 membership view is the kernel's repmask on the initial
        # state; the pause mask starts exactly at LARK's (no lease runs)
        hmask0 = outs0[5].reshape(B, P).astype(xp.int32)
        carry = carry + (~lark0, zbp, hmask0, zbp, zf, zi, zh)
    s0_i = len(carry)                 # spinnaker leaves start here
    if spinnaker_on:
        # rank 0 leads at t=0 (everyone up, roster [0..rf-1]); no view
        # change in flight, so the pause mask starts at the quorum
        # baseline's
        carry = carry + (~qmaj0, zbp, zbp, zbp, zf, zi, zh)
    lat_i = len(carry)                # lat leaves ride at the carry tail
    if _lat_plan is not None:
        nb = _lat_plan.kf.shape[0]
        lz_nb = xp.zeros((B, P, nb), dtype=xp.float32)
        lz_hb = xp.zeros((B, P, _lat_plan.nbins), dtype=xp.float32)
        lz_bp = xp.zeros((B, P), dtype=xp.float32)
        # dirty starts clean (no leader has changed yet), charges at zero
        carry = carry + (lz_nb, lz_nb, lz_hb, lz_bp, lz_bp)

    if backend != "numpy":
        import jax.numpy as jnp
        run_chunk = _make_chunk_runner(step, carry, chunk_steps=chunk_steps,
                                       devices=devices, shard=shard,
                                       n_outputs=4 + int(hermes_on)
                                       + int(spinnaker_on))

    if max_steps is None:
        max_steps = _default_max_steps(p_arr, dt_arr, n=n, horizon=horizon,
                                       restart_period=restart_period)

    # per-chunk accumulator reset map: the base protocol accumulators at
    # fixed offsets 14..19 plus, when enabled, each zoo engine's
    # (pause-time, events, histogram) leaves at offset +4..+6 of its block
    acc_reset = {14: zf, 15: zf, 16: zi, 17: zi, 18: zh, 19: zh}
    if hermes_on:
        acc_reset.update({h0 + 4: zf, h0 + 5: zi, h0 + 6: zh})
    if spinnaker_on:
        acc_reset.update({s0_i + 4: zf, s0_i + 5: zi, s0_i + 6: zh})

    lpt_tot = np.zeros(B)
    qpt_tot = np.zeros(B)
    lev_tot = qev_tot = 0
    lhist_tot = np.zeros(hist_bins, dtype=np.int64)
    qhist_tot = np.zeros(hist_bins, dtype=np.int64)
    if hermes_on:
        hpt_tot = np.zeros(B)
        hev_tot = 0
        hhist_tot = np.zeros(hist_bins, dtype=np.int64)
    if spinnaker_on:
        spt_tot = np.zeros(B)
        sev_tot = 0
        shist_tot = np.zeros(hist_bins, dtype=np.int64)
    if _lat_plan is not None:
        lat_dup = np.zeros((B, _lat_plan.kf.shape[0]))
        lat_qhist = np.zeros((B, _lat_plan.nbins))
        lat_qslo = np.zeros(B)
        lat_qsum = np.zeros(B)
        lat_wfp = None
        if _lat_plan.wfp is not None:
            # skewed write mix: pool a second, write-fraction-weighted
            # view of the same dup charges (hermes pays dup-res on writes
            # only, so its share is per-partition under write_skew)
            lat_wfp = np.asarray(_lat_plan.wfp, dtype=np.float64)
            lat_dupw = np.zeros((B, _lat_plan.kf.shape[0]))
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
        lpt_tot += np.asarray(carry[14], dtype=np.float64)
        qpt_tot += np.asarray(carry[15], dtype=np.float64)
        lev_tot += int(np.asarray(carry[16]).sum())
        qev_tot += int(np.asarray(carry[17]).sum())
        lhist_tot += np.asarray(carry[18], dtype=np.int64).sum(axis=0)
        qhist_tot += np.asarray(carry[19], dtype=np.int64).sum(axis=0)
        if hermes_on:
            hpt_tot += np.asarray(carry[h0 + 4], dtype=np.float64)
            hev_tot += int(np.asarray(carry[h0 + 5]).sum())
            hhist_tot += np.asarray(carry[h0 + 6],
                                    dtype=np.int64).sum(axis=0)
        if spinnaker_on:
            spt_tot += np.asarray(carry[s0_i + 4], dtype=np.float64)
            sev_tot += int(np.asarray(carry[s0_i + 5]).sum())
            shist_tot += np.asarray(carry[s0_i + 6],
                                    dtype=np.int64).sum(axis=0)
        if _lat_plan is not None:
            # pool the per-(trial, partition) float32 charge accumulators
            # over partitions here, host-side in float64 — a fixed
            # summation order independent of backend and device sharding
            # (the dirty fractions persist; the charges restart per chunk)
            lt_ = carry[lat_i:]
            lat_dup += _pool_partitions(lt_[1])
            if lat_wfp is not None:
                lat_dupw += _pool_partitions(lt_[1], lat_wfp[None, :, None])
            lat_qhist += _pool_partitions(lt_[2])
            lat_qslo += _pool_partitions(lt_[3])
            lat_qsum += _pool_partitions(lt_[4])
            carry = carry[:lat_i] + (lt_[0], lz_nb, lz_hb, lz_bp, lz_bp)
        carry = tuple(acc_reset.get(i, c) for i, c in enumerate(carry))
        if (now >= horizon).all():
            break
        # pooled CI early stop, mirroring the availability engine's rule
        # (nominal binomial width; reported CIs use across-trial spread)
        if now.mean() >= min_ticks and lev_tot >= min_events \
                and qev_tot >= min_events:
            pt = float(P) * float(now.sum())
            u_l = min(lpt_tot.sum() / pt, 1.0)
            u_q = min(qpt_tot.sum() / pt, 1.0)
            hw_l = 1.96 * math.sqrt(max(u_l * (1 - u_l), 1e-30) / pt)
            hw_q = 1.96 * math.sqrt(max(u_q * (1 - u_q), 1e-30) / pt)
            if hw_l <= max(eps_abs, eps_rel * u_l) and \
                    hw_q <= max(eps_abs, eps_rel * u_q):
                stopped = True
                break

    now = np.maximum(np.asarray(carry[0], dtype=np.int64), 1)
    pt_b = P * now.astype(np.float64)
    pt = float(pt_b.sum())
    # fractions by construction, except the instantaneous dup-res charge
    # can overshoot wall time under extreme dupres_ticks — clip so the
    # reported values and the binomial u*(1-u) CI terms stay meaningful
    u_l = min(float(lpt_tot.sum()) / pt, 1.0)
    u_q = min(float(qpt_tot.sum()) / pt, 1.0)
    u_l_trials = np.minimum(lpt_tot / pt_b, 1.0)
    u_q_trials = np.minimum(qpt_tot / pt_b, 1.0)
    hw_l = hw_q = 0.0
    if B >= 3:
        t = t975(B - 1) / math.sqrt(B)
        hw_l = t * float(u_l_trials.std(ddof=1))
        hw_q = t * float(u_q_trials.std(ddof=1))
    traj_out = None
    if trajectory:
        names = ["times", "paused_lark", "paused_quorum", "nodes_up"]
        if hermes_on:
            names.append("paused_hermes")
        if spinnaker_on:
            names.append("paused_spinnaker")
        cols = [np.concatenate([c[i] for c in traj])
                for i in range(len(names))]
        traj_out = dict(zip(names, cols))
    lat_raw = None
    if _lat_plan is not None:
        lat_raw = {"dup": lat_dup, "qhist": lat_qhist, "qslo": lat_qslo,
                   "qsum": lat_qsum, "now": now.copy()}
        if lat_wfp is not None:
            lat_raw["dupw"] = lat_dupw

    def _engine_stats(pt_tot):
        u = min(float(pt_tot.sum()) / pt, 1.0)
        u_trials = np.minimum(pt_tot / pt_b, 1.0)
        hw = 0.0
        if B >= 3:
            hw = t975(B - 1) / math.sqrt(B) * float(u_trials.std(ddof=1))
        ci = max(hw, 1.96 * math.sqrt(max(u * (1 - u), 1e-30) / pt))
        return u, ci, u_trials

    zoo_kw = {}
    if hermes_on:
        u_h, ci_h, u_h_trials = _engine_stats(hpt_tot)
        zoo_kw.update(pause_hermes=u_h, ci_hermes=ci_h,
                      hermes_events=hev_tot, hist_hermes=hhist_tot,
                      pause_hermes_trials=u_h_trials)
    if spinnaker_on:
        u_s, ci_s, u_s_trials = _engine_stats(spt_tot)
        zoo_kw.update(pause_spinnaker=u_s, ci_spinnaker=ci_s,
                      spinnaker_events=sev_tot, hist_spinnaker=shist_tot,
                      pause_spinnaker_trials=u_s_trials)
    return BatchedDowntimeResult(
        p=p, rf=rf, n=n, partitions=P, trials=B, backend=backend,
        ticks=int(now.mean()), pause_lark=u_l, pause_quorum=u_q,
        lark_events=lev_tot, quorum_events=qev_tot,
        ci_lark=max(hw_l,
                    1.96 * math.sqrt(max(u_l * (1 - u_l), 1e-30) / pt)),
        ci_quorum=max(hw_q,
                      1.96 * math.sqrt(max(u_q * (1 - u_q), 1e-30) / pt)),
        dupres_ticks=dupres_ticks, rebuild_steps=rebuild_steps,
        stopped_early=stopped, devices=devices,
        rebuild_model=rebuild_model,
        rebuild_ticks_per_gib=rebuild_ticks_per_gib if reconfig else 0,
        size_dist=size_dist if reconfig else "uniform",
        size_skew=size_skew if size_dist in ("zipf", "lognormal") else 0.0,
        node_bandwidth_gibps=node_bandwidth_gibps,
        hist_edges=np.asarray([1 << k for k in range(hist_bins)],
                              dtype=np.int64),
        hist_lark=lhist_tot, hist_quorum=qhist_tot,
        pause_lark_trials=u_l_trials, pause_quorum_trials=u_q_trials,
        engines=engines, lease_ticks=lease_ticks,
        view_change_ticks=view_change_ticks,
        trajectory=traj_out, latency_raw=lat_raw, **zoo_kw)
