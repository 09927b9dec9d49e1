"""Client-traffic commit-latency engine — what requests see under failover.

The §6 engines measure *partition*-level pause fractions; the paper's
headline claim ("at most a per-key duplicate-resolution round trip when
the new leader lacks the latest copy") is about *client-visible* latency.
This layer runs a batched per-key request workload over the exact
counter-RNG trajectories of core/downtime_batched.py and reports the
commit latency distribution a request stream experiences:

  LARK     a request pays `dupres_ticks` iff it is the FIRST touch of its
           key since a leader change onto a stale leader; every later
           touch commits at zero added latency.  Modeled analytically:
           each (trial, partition) carries a dirty-key fraction per
           key-popularity bucket (N_KEY_BUCKETS zipf-rank bands of the
           partition's KEYS_PER_PARTITION keys), reset to 1 at a
           stale-leader change and decayed per event interval by the
           bucket's touch probability — O(B*P) carry, no per-request
           sampling, and the first-touch count is exact in expectation.
  quorum   every WRITE arriving while a rebuild is in flight (replica
           majority up, commits stalled on the catch-up) waits out the
           remaining rebuild: a write landing tau ticks into the
           interval pays rem - tau ticks.  Reads and writes to
           majority-down partitions are unavailability, not latency, and
           are not charged.
  hermes   the contrast model (Katsarakis et al., PAPERS.md): local
           reads NEVER pay the round trip; the write path pays the same
           per-key first-touch charge as LARK.  Derived host-side as the
           write-fraction share of LARK's charges.

Workload model: a cluster-wide request rate of `requests_per_tick`,
split over partitions by hashing `KEYS_PER_PARTITION * partitions` zipf-
popularity keys (exponent `key_zipf`; 0 = uniform) onto partitions under
a dedicated counter-RNG salt — the node-trajectory randomness stream is
untouched (invariant 3, docs/ARCHITECTURE.md), so every workload replays
the identical failure trajectories.  `read_frac` splits the rate into
reads and writes.  Outputs are p50/p99/p999 commit latency (over the
full request distribution, zeros included — the bucketed percentile is
the smallest power-of-two bucket lower edge whose CDF covers the
quantile, so p999 >= p99 >= p50 by construction), the SLO-violation
fraction (requests strictly over `slo_ticks`; slo_ticks=0 counts every
request with any added latency), and the mean added latency, each
per protocol, plus the quorum latency histogram next to the engine's
pause histograms.

Three sharpening knobs, each byte-identical to the prior model at its
degenerate setting: `write_skew` draws every partition's write fraction
around 1 - read_frac (mean-pinned Pareto factors under _WRITE_SALT,
independent of key popularity; 0 = the exactly-uniform mix),
`slo_curve_bins` reports the full SLO-violation curve over the
power-of-two threshold sweep 2^j - 1 derived from the same bucketed
histograms (the `slo_ticks` scalar IS the curve at its threshold,
exactly; 0 = scalar only), and `node_bandwidth_gibps` applies to
rebuild_model="fixed" as well — concurrent fixed-model rebuilds
replaying onto one node split its bandwidth exactly like the reconfig
catch-ups (inf = the unshared legacy model, bit-for-bit).

Zero-knob limit (pinned exactly by tests/test_client_latency.py):
dupres_ticks=0 never dirties a key, read_frac=1 zeroes the write rate —
p50/p99/p999, means, and SLO fractions are all exactly 0 on every
backend.

Bit-identity: the in-scan state is per-(trial, partition) float32
updated by exactly-rounded elementwise ops (kernels/latency.py has the
full contract); partition pooling happens host-side in float64 at chunk
drains.  Trajectories, raw accumulators, and therefore every reported
number are bit-identical across numpy / jax / pallas, packed and
unpacked carries, and devices 1-vs-N trials sharding.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from ..kernels.latency import decay_pow_tables
from .availability import t975
from .availability_batched import _mix32, _uniforms
from .downtime_batched import (BatchedDowntimeResult, DowntimeParams,
                               simulate_downtime_batched)

#: dedicated counter-RNG salt for the key -> partition hash (invariant 3:
#: per-run constants may draw from the counter-hash family under their own
#: salt without perturbing node trajectories)
_KEY_SALT = 0xC2B2AE35

#: dedicated counter-RNG salt for the per-partition write-fraction draw
#: (`write_skew`) — its own stream, so the write mix is independent of
#: both the node trajectories and the key -> partition hash
_WRITE_SALT = 0x85EBCA6B

#: keys per partition in the workload model.  A module constant, not a
#: knob: it only sets the granularity of the analytic dirty-key carry
#: (the bucket key counts K * f_b), and 1024 keys over N_KEY_BUCKETS
#: zipf-rank bands already separates hot keys (touched — and re-dirtied —
#: within a few ticks of a failover) from the cold tail.
KEYS_PER_PARTITION = 1024

#: zipf-rank bands per partition: bucket b spans ranks
#: (K^(b/4), K^((b+1)/4)] — geometric edges, so the hot head gets its own
#: tiny bucket and the cold tail its own huge one
N_KEY_BUCKETS = 4

#: the reported latency quantiles
LATENCY_QUANTILES = (0.5, 0.99, 0.999)


def partition_request_weights(seed: int, partitions: int, *,
                              key_zipf: float = 0.0,
                              keys_per_partition: int = KEYS_PER_PARTITION
                              ) -> np.ndarray:
    """(P,) float64 request-probability weights, summing to 1.

    Zipf key popularity mapped onto partitions: key rank r (of
    NK = partitions * keys_per_partition cluster-wide keys) carries
    popularity r^-key_zipf and lands on the partition drawn by its
    counter-hash under _KEY_SALT; a partition's weight is its keys'
    popularity share.  key_zipf=0 short-circuits to the exactly-uniform
    1/P table.  The normalization pins the mean weight to exactly 1/P —
    skew moves traffic between partitions, never adds offered load
    (property-tested in tests/test_client_latency.py).  Always host-side
    numpy: every backend receives the identical table."""
    if partitions <= 0:
        raise ValueError("partitions must be >= 1")
    if key_zipf == 0:
        return np.full(partitions, 1.0 / partitions)
    nk = partitions * keys_per_partition
    pop = np.arange(1, nk + 1, dtype=np.float64) ** (-float(key_zipf))
    seed_mix = _mix32(np.asarray([(seed & 0xFFFFFFFF) ^ 0x6A09E667],
                                 dtype=np.uint32), np)
    u = _uniforms(seed_mix, np.asarray(0, dtype=np.uint32), _KEY_SALT,
                  np.zeros(1, dtype=np.uint32), nk, np)[0] \
        .astype(np.float64)
    part = np.minimum((u * partitions).astype(np.int64), partitions - 1)
    w = np.bincount(part, weights=pop, minlength=partitions)
    return w / w.sum()


def partition_write_fractions(seed: int, partitions: int, *,
                              read_frac: float = 0.8,
                              write_skew: float = 0.0) -> np.ndarray:
    """(P,) float64 per-partition write fractions, mean-pinned to
    1 - read_frac.

    write_skew=0 short-circuits to the exactly-constant
    `1 - read_frac` table (the legacy uniform mix, bit-for-bit).
    Otherwise each partition draws a Pareto-shaped factor
    (1 - u)^-write_skew under _WRITE_SALT and the table is
    min(c * draw, 1) with c the unique waterfilling scale that pins the
    MEAN write fraction to `1 - read_frac` exactly — a fraction cannot
    exceed 1 (every request a write), and a naive rescale-then-clip
    would collapse the mean under the heavy Pareto tail, so the scale
    is solved against the saturation (property-tested across skews in
    tests/test_client_latency.py).  The draw is independent of key
    popularity — the partition request *rate* stays
    `partition_request_weights`, only its read/write split moves.
    Always host-side numpy: every backend receives the identical
    table."""
    if partitions <= 0:
        raise ValueError("partitions must be >= 1")
    target = 1.0 - read_frac
    if write_skew == 0 or target == 0.0 or target == 1.0:
        return np.full(partitions, target)
    seed_mix = _mix32(np.asarray([(seed & 0xFFFFFFFF) ^ 0x6A09E667],
                                 dtype=np.uint32), np)
    u = _uniforms(seed_mix, np.asarray(0, dtype=np.uint32), _WRITE_SALT,
                  np.zeros(1, dtype=np.uint32), partitions, np)[0] \
        .astype(np.float64)
    raw = (1.0 - u) ** (-float(write_skew))
    # exact waterfilling: with the m largest draws saturated at 1, the
    # scale solving mean = target is (target*P - m) / sum(rest); the
    # first m where that scale leaves draw m itself unsaturated is
    # consistent, and then mean(w) = (m + (target*P - m)) / P = target
    r = np.sort(raw)[::-1]
    tail = r[::-1].cumsum()[::-1]                 # tail[m] = sum r[m:]
    m = np.arange(partitions, dtype=np.float64)
    cm = (target * partitions - m) / tail
    msat = int(np.argmax(cm * r < 1.0))           # first consistent m
    return np.minimum(cm[msat] * raw, 1.0)


def key_bucket_shares(key_zipf: float, *,
                      keys_per_partition: int = KEYS_PER_PARTITION,
                      n_buckets: int = N_KEY_BUCKETS):
    """Within-partition key-popularity buckets: (f, g) float64 arrays of
    key-count fractions and traffic shares per zipf-rank band (geometric
    edges at K^(b/n)).  key_zipf=0 gives g == f exactly (uniform traffic
    per key), which is what makes the uniform workload's per-key touch
    rate identical across buckets."""
    K = keys_per_partition
    edges = [0]
    for b in range(1, n_buckets):
        e = int(round(K ** (b / n_buckets)))
        edges.append(min(max(e, edges[-1] + 1), K - (n_buckets - b)))
    edges.append(K)
    pop = np.arange(1, K + 1, dtype=np.float64) ** (-float(key_zipf))
    tot = pop.sum()
    f = np.asarray([(edges[b + 1] - edges[b]) / K
                    for b in range(n_buckets)])
    g = np.asarray([pop[edges[b]:edges[b + 1]].sum() / tot
                    for b in range(n_buckets)])
    return f, g


@dataclass(frozen=True)
class _LatencyPlan:
    """Host-precomputed workload tables the downtime driver builds in its
    set-up (simulate_downtime_batched's `_lat_plan_of`): per-bucket key
    counts, per-partition float32 write rates, and the decay power
    tables — everything the in-scan latency update consumes."""
    nbins: int
    slo_ticks: int
    kf: np.ndarray           # (NB,) float32 keys per bucket (K * f_b)
    lamw: np.ndarray         # (P,) float32 write requests/tick
    pow_tables: np.ndarray   # (nbits, P, NB) float32 decay squares
    #: (P,) float64 per-partition write fractions, or None under the
    #: uniform mix (write_skew=0) — consumed host-side at chunk drains
    #: to weight hermes' write-path share of the dup charges
    wfp: Optional[np.ndarray] = None


def _percentile(masses, total: float, q: float) -> float:
    """Smallest latency value whose CDF covers quantile q, over a
    distribution of `total` requests with point `masses` [(value, count)]
    at positive latencies and the rest at exactly 0.  Walking the sorted
    values makes q -> value non-decreasing, so p999 >= p99 >= p50 always
    holds on emitted rows.

    Boundary semantics (pinned by adversarial tests): the walk takes the
    smallest value whose cumulative mass *reaches* q * total (`>=`, not
    `>`), so a CDF landing exactly on the quantile selects that value,
    not the next one; an all-zero-mass distribution returns 0.0 for
    every q; and a total smaller than the charged mass still terminates
    (the zero mass is clamped at 0)."""
    if total <= 0:
        return 0.0
    masses = sorted((m for m in masses if m[1] > 0), key=lambda m: m[0])
    charged = sum(m[1] for m in masses)
    cdf = max(total - charged, 0.0)
    need = q * total
    if cdf >= need:
        return 0.0
    for value, count in masses:
        cdf += count
        if cdf >= need:
            return float(value)
    return float(masses[-1][0]) if masses else 0.0


@dataclass
class BatchedLatencyResult:
    """Client-visible commit-latency summary over `trials` trajectories.

    Latencies are in ticks of *added* commit latency (0 = the request
    committed at baseline speed).  Percentiles are over the full request
    distribution including the zero-latency mass; quorum values are
    power-of-two bucket lower edges (the engine bins remaining rebuild
    waits, it does not keep every distinct wait).  `req_total` is the
    offered load: requests_per_tick x elapsed ticks, summed over trials.
    """
    p: float
    rf: int
    n: int
    partitions: int
    trials: int
    backend: str
    devices: int
    ticks: int
    stopped_early: bool
    rebuild_model: str
    dupres_ticks: int
    key_zipf: float
    read_frac: float
    requests_per_tick: float
    slo_ticks: int
    req_total: float
    lat_lark: float                  # mean added latency, ticks/request
    lat_quorum: float
    lat_hermes: float
    ci_lat_lark: float               # 95% across-trial half-widths
    ci_lat_quorum: float
    p50_lark: float
    p99_lark: float
    p999_lark: float
    p50_quorum: float
    p99_quorum: float
    p999_quorum: float
    p50_hermes: float
    p99_hermes: float
    p999_hermes: float
    slo_lark: float                  # fraction of requests > slo_ticks
    slo_quorum: float
    slo_hermes: float
    write_skew: float = 0.0
    slo_curve_bins: int = 0
    node_bandwidth_gibps: float = math.inf
    #: SLO curves (slo_curve_bins > 0 only): violation fractions over
    #: the power-of-two threshold sweep 2^j - 1, j = 0..bins-1 — each
    #: curve is non-increasing in the threshold, and at the j whose
    #: threshold equals slo_ticks the curve value IS the scalar slo_*
    slo_curve_edges: np.ndarray = field(repr=False, default=None)
    slo_curve_lark: np.ndarray = field(repr=False, default=None)
    slo_curve_quorum: np.ndarray = field(repr=False, default=None)
    slo_curve_hermes: np.ndarray = field(repr=False, default=None)
    hist_edges: np.ndarray = field(repr=False, default=None)
    hist_quorum_req: np.ndarray = field(repr=False, default=None)
    lat_lark_trials: np.ndarray = field(repr=False, default=None)
    lat_quorum_trials: np.ndarray = field(repr=False, default=None)
    downtime: BatchedDowntimeResult = field(repr=False, default=None)


def make_latency_plan(seed: int, partitions: int, params: DowntimeParams,
                      max_ticks: int) -> _LatencyPlan:
    """Build the host-side workload tables for one run (all float32 by
    the time they enter the scan; the float64 -> float32 rounding happens
    once, here, identically for every backend)."""
    w = partition_request_weights(seed, partitions,
                                  key_zipf=params.key_zipf)
    f, g = key_bucket_shares(params.key_zipf)
    lam = params.requests_per_tick * w
    wfp = None
    if params.write_skew > 0:
        wfp = partition_write_fractions(seed, partitions,
                                        read_frac=params.read_frac,
                                        write_skew=params.write_skew)
        lamw = (lam * wfp).astype(np.float32)
    else:
        lamw = (lam * (1.0 - params.read_frac)).astype(np.float32)
    # same subnormal flush as the decay tables (kernels/latency.py):
    # XLA's DAZ would silently zero these, numpy would not
    lamw[lamw < np.float32(1e-30)] = 0.0
    return _LatencyPlan(
        nbins=params.hist_bins, slo_ticks=params.slo_ticks,
        kf=(KEYS_PER_PARTITION * f).astype(np.float32),
        lamw=lamw,
        pow_tables=decay_pow_tables(lam, g, f, KEYS_PER_PARTITION,
                                    max_ticks),
        wfp=wfp)


def simulate_client_latency(
        *, partitions: int = 4096, seed: int = 0,
        max_ticks: int = 3_000_000,
        key_zipf: float = 1.0, read_frac: float = 0.8,
        requests_per_tick: float = 32.0, slo_ticks: int = 8,
        write_skew: float = 0.0, slo_curve_bins: int = 0,
        dupres_ticks: int = 1, rebuild_steps: int = 100,
        hist_bins: int = 16, rebuild_model: str = "fixed",
        rebuild_ticks_per_gib: int = 100, size_dist: str = "uniform",
        size_skew: float = 1.0,
        node_bandwidth_gibps: float = math.inf,
        params: Optional[DowntimeParams] = None,
        **kwargs) -> BatchedLatencyResult:
    """Run the §6 downtime Monte Carlo with the client-latency layer
    attached and summarize what the request stream saw.

    Accepts every simulate_downtime_batched knob (cluster, scenario,
    backend/devices/packed, chunking) via **kwargs, plus the workload
    knobs above — all validated in DowntimeParams, so the CLI, this
    entry point, and tests raise identical errors.  `params` takes
    precedence over the individual protocol/workload keywords when given,
    exactly as in simulate_downtime_batched."""
    if params is None:
        params = DowntimeParams(
            dupres_ticks=dupres_ticks, rebuild_steps=rebuild_steps,
            hist_bins=hist_bins, rebuild_model=rebuild_model,
            rebuild_ticks_per_gib=rebuild_ticks_per_gib,
            size_dist=size_dist, size_skew=size_skew,
            node_bandwidth_gibps=node_bandwidth_gibps,
            key_zipf=key_zipf, read_frac=read_frac,
            requests_per_tick=requests_per_tick, slo_ticks=slo_ticks,
            write_skew=write_skew, slo_curve_bins=slo_curve_bins)
    res = simulate_downtime_batched(
        partitions=partitions, seed=seed, max_ticks=max_ticks,
        params=params, _lat_plan_of=make_latency_plan, **kwargs)

    raw = res.latency_raw
    now = raw["now"].astype(np.float64)                       # (B,)
    req_b = params.requests_per_tick * now
    req = float(req_b.sum())
    dup_b = raw["dup"].sum(axis=1)                            # (B,)
    dup_tot = float(dup_b.sum())
    qhist = raw["qhist"].sum(axis=0)                          # (nbins,)
    qslo_tot = float(raw["qslo"].sum())
    qsum_tot = float(raw["qsum"].sum())
    wf = 1.0 - params.read_frac
    dup_cost = float(params.dupres_ticks)
    # skewed write mix: the engine pooled a second, write-fraction-
    # weighted view of the dup charges; its absence (write_skew=0) keeps
    # the legacy uniform-mix hermes expressions byte-identical
    dupw_tot = float(raw["dupw"].sum()) if "dupw" in raw else None

    if req > 0:
        lat_lark = dup_cost * dup_tot / req
        lat_quorum = qsum_tot / req
        lal_b = dup_cost * dup_b / req_b
        laq_b = raw["qsum"] / req_b
        slo_lark = (dup_tot / req) if dup_cost > params.slo_ticks else 0.0
        slo_quorum = qslo_tot / req
        if dupw_tot is not None:
            lat_hermes = dup_cost * dupw_tot / req
            slo_hermes = (dupw_tot / req) \
                if dup_cost > params.slo_ticks else 0.0
        else:
            lat_hermes = wf * lat_lark
            slo_hermes = wf * slo_lark
    else:
        lat_lark = lat_quorum = slo_lark = slo_quorum = 0.0
        lat_hermes = slo_hermes = 0.0
        lal_b = np.zeros_like(req_b)
        laq_b = np.zeros_like(req_b)
    ci_l = ci_q = 0.0
    B = res.trials
    if B >= 3:
        t = t975(B - 1) / math.sqrt(B)
        ci_l = t * float(lal_b.std(ddof=1))
        ci_q = t * float(laq_b.std(ddof=1))

    hermes_mass = dupw_tot if dupw_tot is not None else wf * dup_tot
    lark_masses = [(params.dupres_ticks, dup_tot)]
    hermes_masses = [(params.dupres_ticks, hermes_mass)]
    quorum_masses = [(1 << k, float(qhist[k]))
                     for k in range(params.hist_bins)]
    pcts = {}
    for name, masses in (("lark", lark_masses), ("quorum", quorum_masses),
                         ("hermes", hermes_masses)):
        for q in LATENCY_QUANTILES:
            key = f"p{q * 1000:g}".replace("p500", "p50").replace(
                "p990", "p99")
            pcts[f"{key}_{name}"] = _percentile(masses, req, q)

    curve_edges = curve_lark = curve_quorum = curve_hermes = None
    if params.slo_curve_bins > 0:
        # violation-fraction curves over the threshold sweep 2^j - 1.
        # A wait pays > 2^j - 1 iff it pays >= 2^j iff it landed in
        # histogram bucket >= j, so the quorum curve is the qhist tail
        # sums.  At the bin whose threshold equals slo_ticks the in-scan
        # scalar (one f32 product per interval) and the tail sum (per-
        # bucket f32 accumulators) agree only up to accumulation order,
        # so the scalar is substituted there — "the old scalar IS the
        # curve at slo_ticks" holds exactly — and the neighbors are
        # clamped (an ulp-level correction at most) to keep the curve
        # monotone non-increasing by construction.
        J = params.slo_curve_bins
        curve_edges = np.asarray([(1 << j) - 1 for j in range(J)],
                                 dtype=np.int64)
        if req > 0:
            tail = qhist[::-1].cumsum()[::-1]
            curve_quorum = tail[:J] / req
            curve_lark = np.asarray(
                [(dup_tot / req) if dup_cost > t else 0.0
                 for t in curve_edges])
            if dupw_tot is not None:
                curve_hermes = np.asarray(
                    [(dupw_tot / req) if dup_cost > t else 0.0
                     for t in curve_edges])
            else:
                curve_hermes = wf * curve_lark
            js = np.flatnonzero(curve_edges == params.slo_ticks)
            if js.size:
                j = int(js[0])
                curve_quorum[j] = slo_quorum
                curve_quorum[:j] = np.maximum(curve_quorum[:j],
                                              slo_quorum)
                curve_quorum[j + 1:] = np.minimum(curve_quorum[j + 1:],
                                                  slo_quorum)
        else:
            curve_lark = np.zeros(J)
            curve_quorum = np.zeros(J)
            curve_hermes = np.zeros(J)

    return BatchedLatencyResult(
        p=res.p, rf=res.rf, n=res.n, partitions=res.partitions,
        trials=res.trials, backend=res.backend, devices=res.devices,
        ticks=res.ticks, stopped_early=res.stopped_early,
        rebuild_model=res.rebuild_model,
        dupres_ticks=params.dupres_ticks, key_zipf=params.key_zipf,
        read_frac=params.read_frac,
        requests_per_tick=params.requests_per_tick,
        slo_ticks=params.slo_ticks, req_total=req,
        write_skew=params.write_skew,
        slo_curve_bins=params.slo_curve_bins,
        node_bandwidth_gibps=params.node_bandwidth_gibps,
        lat_lark=lat_lark, lat_quorum=lat_quorum,
        lat_hermes=lat_hermes,
        ci_lat_lark=ci_l, ci_lat_quorum=ci_q,
        p50_lark=pcts["p50_lark"], p99_lark=pcts["p99_lark"],
        p999_lark=pcts["p999_lark"],
        p50_quorum=pcts["p50_quorum"], p99_quorum=pcts["p99_quorum"],
        p999_quorum=pcts["p999_quorum"],
        p50_hermes=pcts["p50_hermes"], p99_hermes=pcts["p99_hermes"],
        p999_hermes=pcts["p999_hermes"],
        slo_lark=slo_lark, slo_quorum=slo_quorum,
        slo_hermes=slo_hermes,
        slo_curve_edges=curve_edges, slo_curve_lark=curve_lark,
        slo_curve_quorum=curve_quorum, slo_curve_hermes=curve_hermes,
        hist_edges=np.asarray([1 << k for k in range(params.hist_bins)],
                              dtype=np.int64),
        hist_quorum_req=qhist,
        lat_lark_trials=lal_b, lat_quorum_trials=laq_b,
        downtime=res)
