"""Batched §6 downtime/commit-pause engine: cross-backend and shard_map
bit-identity, the dup-res and rebuild degeneracy properties (pause
fractions must collapse *exactly* to the instantaneous engine's
integrals when the knobs are zeroed), protocol-semantics monotonicity,
duration-histogram accounting, and the reconfiguring quorum-log
baseline (roster reconfiguration + data-sized catch-ups)."""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core.availability_batched import simulate_availability_batched
from repro.core.downtime_batched import (SIZE_DISTS, _count_at,
                                         _hist_add,
                                         _partition_rebuild_ticks,
                                         _rank_node, _seat_up,
                                         partition_sizes_gib,
                                         simulate_downtime_batched)
from repro.core.scenarios import get_scenario, scenario_names
from repro.core.succession import succession_matrix_fast
from repro.kernels.ops import (PAC_BACKENDS, downtime_eval_batch,
                               rebuild_node_counts)

RNG = np.random.default_rng(17)

_KW = dict(n=13, partitions=32, rf=2, p=5e-3, trials=3, max_ticks=4_000,
           min_ticks=10**9, chunk_steps=64, max_steps=600, seed=11,
           trajectory=True)


# ---------------------------------------------------------------------------
# per-step op: backend agreement
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rf,n_real,n_pad", [(2, 23, 23), (3, 19, 40)])
def test_downtime_eval_backends_agree(rf, n_real, n_pad):
    R = 128
    up = RNG.random((R, n_pad)) < 0.8
    full = RNG.random((R, n_pad)) < 0.4
    up[0] = False                       # dead partition: leader sentinel
    outs = {}
    for b in PAC_BACKENDS:
        u = up if b == "numpy" else jnp.asarray(up)
        f = full if b == "numpy" else jnp.asarray(full)
        outs[b] = tuple(np.asarray(o) for o in downtime_eval_batch(
            u, f, rf=rf, n_real=n_real, backend=b))
    for b in PAC_BACKENDS[1:]:
        for i, (a, c) in enumerate(zip(outs[PAC_BACKENDS[0]], outs[b])):
            assert np.array_equal(a, c), (b, i)
    lark, qmaj, leader, lfull, nrep, creps = outs["numpy"]
    assert leader[0] == n_real and not lfull[0]          # no node up
    assert ((2 * nrep > rf) == qmaj).all()
    assert (nrep <= rf).all()
    assert not creps[:, n_real:].any()                   # padding untouched
    # the leader is the first up node: rank-space argmax over the up mask
    up_m = up & (np.arange(n_pad) < n_real)
    exp = np.where(up_m.any(axis=1), up_m.argmax(axis=1), n_real)
    assert np.array_equal(leader, exp)


@pytest.mark.parametrize("rf,n_real,n_pad", [(2, 23, 23), (3, 19, 40)])
def test_roster_aware_eval_backends_agree(rf, n_real, n_pad):
    """The reconfiguring baseline's per-step op: qmaj/nrep over a carried
    roster of succession ranks, bit-identical across all three backends,
    and exactly the static result for the identity roster."""
    R = 128
    up = RNG.random((R, n_pad)) < 0.8
    full = RNG.random((R, n_pad)) < 0.4
    roster = np.stack([RNG.permutation(n_real)[:rf] for _ in range(R)]) \
        .astype(np.int32)
    outs = {}
    for b in PAC_BACKENDS:
        u = up if b == "numpy" else jnp.asarray(up)
        f = full if b == "numpy" else jnp.asarray(full)
        ro = roster if b == "numpy" else jnp.asarray(roster)
        outs[b] = tuple(np.asarray(o) for o in downtime_eval_batch(
            u, f, rf=rf, n_real=n_real, backend=b, roster=ro))
    for b in PAC_BACKENDS[1:]:
        for i, (a, c) in enumerate(zip(outs[PAC_BACKENDS[0]], outs[b])):
            assert np.array_equal(a, c), (b, i)
    lark, qmaj, leader, lfull, nrep, creps = outs["numpy"]
    # nrep/qmaj really count the roster members, nothing else
    up_m = up & (np.arange(n_pad) < n_real)
    exp_nrep = np.take_along_axis(up_m, roster, axis=1).sum(axis=1)
    assert np.array_equal(nrep, exp_nrep)
    assert np.array_equal(qmaj, 2 * exp_nrep > rf)
    # roster-independent outputs match the non-roster op exactly
    base = tuple(np.asarray(o) for o in downtime_eval_batch(
        up, full, rf=rf, n_real=n_real, backend="numpy"))
    for i in (0, 2, 3, 5):                    # lark, leader, lfull, creps
        assert np.array_equal(outs["numpy"][i], base[i]), i
    # identity roster == static first-rf replica set, bit for bit
    ident = np.broadcast_to(np.arange(rf, dtype=np.int32), (R, rf)).copy()
    with_id = tuple(np.asarray(o) for o in downtime_eval_batch(
        up, full, rf=rf, n_real=n_real, backend="numpy", roster=ident))
    for a, c in zip(base, with_id):
        assert np.array_equal(a, c)


# ---------------------------------------------------------------------------
# bit-identical seeded trajectories across backends and sharding
# ---------------------------------------------------------------------------

# (cross-backend / packed-layout / shard-map identity now lives in the
# consolidated matrix: tests/test_conformance.py)


def test_sharding_and_knob_validation():
    with pytest.raises(ValueError, match="numpy"):
        simulate_downtime_batched(backend="numpy", devices=2, **_KW)
    with pytest.raises(ValueError, match="divide"):
        simulate_downtime_batched(backend="jax", devices=2, **_KW)
    with pytest.raises(ValueError, match="dupres_ticks"):
        simulate_downtime_batched(backend="numpy", dupres_ticks=-1, **_KW)
    with pytest.raises(ValueError, match="hist_bins"):
        simulate_downtime_batched(backend="numpy", hist_bins=1, **_KW)


# ---------------------------------------------------------------------------
# degeneracy properties: zeroed knobs collapse to instantaneous integrals
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=2, max_value=3),
       st.sampled_from([3e-3, 8e-3]),
       st.integers(min_value=0, max_value=3))
def test_zero_knobs_degenerate_to_instantaneous_integrals(rf, p, seed):
    """Satellite acceptance: dupres_ticks=0 makes LARK downtime equal the
    instantaneous-PAC unavailability integral, and rebuild_steps=0 makes
    the quorum-log baseline plain majority-of-replica-set availability
    (voters=rf in the instantaneous engine) — exactly, not statistically,
    because both engines replay the same counter-RNG trajectory."""
    kw = dict(n=11, partitions=16, p=p, trials=2, max_ticks=1_500,
              min_ticks=10**9, chunk_steps=32, max_steps=200, seed=seed,
              backend="numpy", trajectory=True)
    dt = simulate_downtime_batched(rf=rf, dupres_ticks=0, rebuild_steps=0,
                                   **kw)
    av = simulate_availability_batched(rf=rf, voters=rf, **kw)
    assert dt.pause_lark == av.u_lark
    assert dt.pause_quorum == av.u_maj
    assert np.array_equal(dt.pause_lark_trials, av.u_lark_trials)
    assert np.array_equal(dt.pause_quorum_trials, av.u_maj_trials)
    assert np.array_equal(dt.trajectory["times"], av.trajectory["times"])
    assert np.array_equal(dt.trajectory["paused_lark"],
                          av.trajectory["unavail_lark"])
    assert np.array_equal(dt.trajectory["paused_quorum"],
                          av.trajectory["unavail_maj"])
    # event-count accounting regression: both engines count per-partition
    # down-transitions, so at zero knobs the counts are *equal*, not just
    # close — the availability engine's old net-per-trial delta counting
    # cancelled a partition recovering in the same step another failed
    assert dt.lark_events == av.lark_events
    assert dt.quorum_events == av.maj_events


def test_dupres_and_rebuild_only_add_pause():
    base = simulate_downtime_batched(dupres_ticks=0, rebuild_steps=0, **_KW)
    dup = simulate_downtime_batched(dupres_ticks=5, rebuild_steps=0, **_KW)
    reb = simulate_downtime_batched(dupres_ticks=0, rebuild_steps=50, **_KW)
    reb2 = simulate_downtime_batched(dupres_ticks=0, rebuild_steps=200,
                                     **_KW)
    assert dup.pause_lark > base.pause_lark
    assert dup.pause_quorum == base.pause_quorum     # knob is LARK-only
    assert reb.pause_quorum > base.pause_quorum
    assert reb2.pause_quorum > reb.pause_quorum      # monotone in rebuild
    assert reb.pause_lark == base.pause_lark         # knob is quorum-only


def test_lark_outpauses_nothing_quorum_pays_rebuilds():
    """The §6 headline: equal storage budget, same trajectory — LARK's
    commit-pause fraction stays well below the rebuilding quorum-log's."""
    r = simulate_downtime_batched(backend="numpy", **_KW)
    assert r.pause_lark < r.pause_quorum
    assert r.availability_ratio > 2.0


# ---------------------------------------------------------------------------
# duration-histogram accounting
# ---------------------------------------------------------------------------

def test_histogram_accounting():
    r = simulate_downtime_batched(backend="numpy", **_KW)
    assert r.hist_edges.tolist() == [1 << k for k in range(16)]
    # every completed run was opened by a counted pause-start event
    # (runs still open at the horizon are censored, so <=)
    assert 0 < int(r.hist_lark.sum()) <= r.lark_events
    assert 0 < int(r.hist_quorum.sum()) <= r.quorum_events
    # dup-res penalties land in the bucket holding dupres_ticks
    zero = simulate_downtime_batched(dupres_ticks=0, **_KW)
    pen8 = simulate_downtime_batched(dupres_ticks=8, **_KW)
    extra = pen8.hist_lark - zero.hist_lark
    assert extra[3] > 0                        # bucket [8, 16)
    assert (extra[:3] == 0).all() and (extra[4:] == 0).all()


def test_quorum_rebuild_durations_reflect_the_countdown():
    """With a failure-free rebuild window, every quorum pause caused by a
    single replica loss lasts >= rebuild_steps ticks — the histogram mass
    sits at or above the rebuild bucket."""
    r = simulate_downtime_batched(
        n=12, partitions=32, rf=3, p=1e-3, trials=2, max_ticks=20_000,
        min_ticks=10**9, seed=7, backend="numpy", dupres_ticks=0,
        rebuild_steps=64)
    assert int(r.hist_quorum.sum()) > 0
    assert r.hist_quorum[:6].sum() == 0        # no run shorter than 64


# ---------------------------------------------------------------------------
# scenario registry compatibility
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", scenario_names())
def test_every_scenario_runs_under_the_downtime_engine(name):
    sc = get_scenario(name)
    rf, p = sc.grid[0]
    r = simulate_downtime_batched(
        rf=rf, p=p, n=13, partitions=32, trials=2, max_ticks=2_000,
        min_ticks=10**9, chunk_steps=32, max_steps=120, seed=5,
        backend="numpy", **sc.kwargs(n=13, rf=rf, p=p))
    assert 0.0 <= r.pause_lark and 0.0 <= r.pause_quorum <= 1.0


@pytest.mark.slow
def test_batched_downtime_matches_reduced_scale_expectations():
    """Reduced-grid row at the sweep's scale: LARK pause ~ u_lark level,
    quorum pays heavily for rebuilds."""
    r = simulate_downtime_batched(
        n=63, partitions=512, rf=2, p=3e-3, trials=4, max_ticks=120_000,
        min_ticks=20_000, seed=0, backend="jax")
    assert 0 < r.pause_lark < 0.1
    assert r.pause_quorum > r.pause_lark
    assert r.availability_ratio > 5


# ---------------------------------------------------------------------------
# histogram binning edges (zero-length runs are not pauses)
# ---------------------------------------------------------------------------

def test_hist_add_binning_edges():
    """Power-of-two bucket edges, including the regression cases: d=0
    (a run opened and closed at the same tick by coincident events) must
    be dropped, not mis-binned into [1, 2); 2^k lands in bucket k; the
    top bucket is open-ended."""
    bins = 16
    cases = [(0, None), (1, 0), (2, 1), (3, 1)] + \
        [(1 << k, k) for k in range(2, bins)] + \
        [((1 << (bins - 1)) + 1, bins - 1), ((1 << bins), bins - 1)]
    d = np.array([[c[0] for c in cases]], dtype=np.int64)
    mask = np.ones_like(d, dtype=bool)
    hist = _hist_add(np, bins, np.zeros((1, bins), dtype=np.int32), mask, d)
    expected = np.zeros(bins, dtype=np.int32)
    for _, bucket in cases:
        if bucket is not None:
            expected[bucket] += 1
    assert np.array_equal(hist[0], expected)
    assert int(hist.sum()) == sum(1 for _, b in cases if b is not None)


def test_hist_add_masks_zero_duration_even_when_selected():
    # the d=0 drop applies inside the mask, so a coincident open/close
    # that *is* flagged as a completed run still contributes nothing
    bins = 4
    d = np.array([[0, 0, 5]])
    mask = np.array([[True, True, True]])
    hist = _hist_add(np, bins, np.zeros((1, bins), dtype=np.int32), mask, d)
    assert hist[0].tolist() == [0, 0, 1, 0]


# ---------------------------------------------------------------------------
# the reconfiguring quorum-log baseline
# ---------------------------------------------------------------------------

def test_partition_sizes_are_deterministic_and_bounded():
    s1 = partition_sizes_gib(11, 256)
    s2 = partition_sizes_gib(11, 256)
    assert np.array_equal(s1, s2)
    assert ((s1 >= 1.0) & (s1 < 2.0)).all()
    assert len(np.unique(s1)) > 200              # actually varied
    assert not np.array_equal(s1, partition_sizes_gib(12, 256))
    t = _partition_rebuild_ticks(11, 256, 100)
    assert t.dtype == np.int32
    assert ((t >= 100) & (t < 200)).all()
    assert (_partition_rebuild_ticks(11, 256, 0) == 0).all()


def test_fixed_model_is_the_default_and_unchanged():
    """`--rebuild-model fixed` is the degenerate case: the default-args
    run and an explicit fixed run are the same computation, bit for bit
    (the committed BENCH_downtime.json pins this against the pre-roster
    baseline at sweep scale)."""
    base = simulate_downtime_batched(**_KW)
    fixed = simulate_downtime_batched(rebuild_model="fixed", **_KW)
    for k in base.trajectory:
        assert np.array_equal(base.trajectory[k], fixed.trajectory[k]), k
    assert base.pause_lark == fixed.pause_lark
    assert base.pause_quorum == fixed.pause_quorum
    assert np.array_equal(base.hist_lark, fixed.hist_lark)
    assert np.array_equal(base.hist_quorum, fixed.hist_quorum)
    assert base.rebuild_model == "fixed"
    assert base.rebuild_ticks_per_gib == 0       # knob inert under fixed


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=2, max_value=3),
       st.sampled_from([3e-3, 8e-3]),
       st.integers(min_value=0, max_value=3))
def test_reconfig_never_pauses_less_than_fixed_on_iid_grid(rf, p, seed):
    """With matched knobs (rebuild_ticks_per_gib == rebuild_steps and
    partition sizes >= 1 GiB, so every catch-up >= the fixed constant),
    the reconfiguring baseline pauses at least as much as the static one
    on the same i.i.d. short-downtime trajectory: the roster tracks live
    nodes, which exposes more up-time to failure (more losses, each with
    a >= catch-up) — and LARK, which has no replica set to rebuild, is
    bit-identical.  This is a regime property, not a theorem: under long
    node downtimes (flapping / hetero-mttf scenarios) reconfiguration
    avoids the static set's long majority-down stalls and pauses *less*
    (see docs/ARCHITECTURE.md); the whole rf x p x seed space asserted
    here was verified exhaustively, so hypothesis sampling cannot flake."""
    kw = dict(n=11, partitions=16, p=p, trials=2, max_ticks=1_500,
              min_ticks=10**9, chunk_steps=32, max_steps=200, seed=seed,
              backend="numpy", trajectory=True, dupres_ticks=1)
    fx = simulate_downtime_batched(rf=rf, rebuild_steps=100, **kw)
    rc = simulate_downtime_batched(rf=rf, rebuild_model="reconfig",
                                   rebuild_ticks_per_gib=100, **kw)
    assert np.array_equal(fx.trajectory["times"], rc.trajectory["times"])
    assert rc.pause_quorum >= fx.pause_quorum
    assert (rc.pause_quorum_trials >= fx.pause_quorum_trials).all()
    assert rc.pause_lark == fx.pause_lark
    assert rc.lark_events == fx.lark_events
    assert np.array_equal(rc.hist_lark, fx.hist_lark)
    assert np.array_equal(rc.trajectory["paused_lark"],
                          fx.trajectory["paused_lark"])


def test_reconfig_zero_ticks_degenerates_to_roster_availability():
    """rebuild_ticks_per_gib=0 is free instant reconfiguration: every
    loss immediately recruits an up node, so with plenty of spare nodes
    the roster majority never fails and the baseline's pause collapses to
    zero — strictly below the static fixed-set baseline, which keeps
    paying for its dead members.  (The catch-up cost is the *only* thing
    that makes the reconfiguring baseline pause; that is the point of the
    §6 data-sized-rebuild comparison.)"""
    kw = dict(n=13, partitions=32, rf=2, p=2e-2, trials=3, max_ticks=4_000,
              min_ticks=10**9, chunk_steps=64, max_steps=600, seed=3,
              backend="numpy", dupres_ticks=0)
    fx = simulate_downtime_batched(rebuild_steps=0, **kw)
    rc = simulate_downtime_batched(rebuild_model="reconfig",
                                   rebuild_ticks_per_gib=0, **kw)
    assert fx.pause_quorum > 0                   # the static set does pause
    assert rc.pause_quorum < fx.pause_quorum
    assert rc.pause_quorum == 0.0                # n=13 always has 2 up nodes


def test_reconfig_validation():
    with pytest.raises(ValueError, match="rebuild_model"):
        simulate_downtime_batched(rebuild_model="paxos", **_KW)
    with pytest.raises(ValueError, match="rebuild_ticks_per_gib"):
        simulate_downtime_batched(rebuild_model="reconfig",
                                  rebuild_ticks_per_gib=-1, **_KW)


# ---------------------------------------------------------------------------
# hot-partition size distributions
# ---------------------------------------------------------------------------

def test_size_dists_share_the_uniform_mean_budget():
    """Every distribution pins the uniform model's 1.5 GiB mean: skew
    redistributes bytes between partitions, never changes the total
    dataset the §6 equal-storage comparison is about."""
    for dist, skew in [("zipf", 0.0), ("zipf", 1.0), ("zipf", 2.5),
                       ("lognormal", 0.0), ("lognormal", 1.5)]:
        s = partition_sizes_gib(11, 1024, dist=dist, skew=skew)
        assert s.shape == (1024,)
        assert (s >= 0).all()
        assert abs(s.mean() - 1.5) < 1e-12, (dist, skew)
        assert np.array_equal(s, partition_sizes_gib(11, 1024, dist=dist,
                                                     skew=skew))


def test_uniform_dist_is_the_original_table_bit_for_bit():
    base = partition_sizes_gib(11, 256)
    assert np.array_equal(base, partition_sizes_gib(11, 256,
                                                    dist="uniform"))
    # the skew knob is inert under uniform
    assert np.array_equal(base, partition_sizes_gib(11, 256,
                                                    dist="uniform",
                                                    skew=7.0))


def test_zero_skew_collapses_to_constant_uniform_mean():
    """Satellite: --size-skew 0 zipf matches the uniform moments — the
    mean is *exactly* the uniform 1.5 GiB (every partition constant)."""
    for dist in ("zipf", "lognormal"):
        s = partition_sizes_gib(11, 256, dist=dist, skew=0.0)
        assert (s == 1.5).all(), dist


def test_skew_produces_hot_partitions_and_sub_gib_tails():
    uni = partition_sizes_gib(11, 2048, dist="uniform")
    zipf = partition_sizes_gib(11, 2048, dist="zipf", skew=1.0)
    logn = partition_sizes_gib(11, 2048, dist="lognormal", skew=1.0)
    for s in (zipf, logn):
        assert s.max() > uni.max()        # a few hot partitions...
        assert (s < 1.0).mean() > 0.25    # ...push the bulk below 1 GiB
    # more skew = hotter head, at the same total
    zipf2 = partition_sizes_gib(11, 2048, dist="zipf", skew=2.0)
    assert zipf2.max() > zipf.max()


def test_size_dist_validation():
    with pytest.raises(ValueError, match="dist"):
        partition_sizes_gib(11, 64, dist="pareto")
    with pytest.raises(ValueError, match="skew"):
        partition_sizes_gib(11, 64, dist="zipf", skew=-0.5)
    # skews past the float64 overflow point are rejected, not NaN-poisoned
    with pytest.raises(ValueError, match="skew"):
        partition_sizes_gib(11, 64, dist="zipf", skew=100.0)
    with pytest.raises(ValueError, match="size_skew"):
        simulate_downtime_batched(rebuild_model="reconfig",
                                  size_dist="zipf", size_skew=100.0, **_KW)
    with pytest.raises(ValueError, match="size_dist"):
        simulate_downtime_batched(rebuild_model="reconfig",
                                  size_dist="pareto", **_KW)
    # the size knobs describe reconfig catch-ups only; bandwidth sharing
    # now applies to the fixed model too
    with pytest.raises(ValueError, match="reconfig"):
        simulate_downtime_batched(size_dist="zipf", **_KW)
    simulate_downtime_batched(node_bandwidth_gibps=1.0, **_KW)
    with pytest.raises(ValueError, match="quantum"):
        simulate_downtime_batched(node_bandwidth_gibps=0.003, **_KW)
    with pytest.raises(ValueError, match="node_bandwidth_gibps"):
        simulate_downtime_batched(rebuild_model="reconfig",
                                  node_bandwidth_gibps=0.0, **_KW)
    # below the 1/256 fixed-point quantum every catch-up would round to
    # zero progress and silently never finish — rejected, not degenerate
    with pytest.raises(ValueError, match="quantum"):
        simulate_downtime_batched(rebuild_model="reconfig",
                                  node_bandwidth_gibps=0.003, **_KW)
    simulate_downtime_batched(rebuild_model="reconfig",
                              node_bandwidth_gibps=1.0 / 256, **_KW)
    assert "uniform" in SIZE_DISTS and "zipf" in SIZE_DISTS


def test_sub_gib_countdowns_clamp_to_one_tick():
    """Satellite: skewed draws go below 1 GiB; a catch-up of any size
    still costs at least one tick (ticks_per_gib > 0), while a free
    rebuild (ticks_per_gib == 0) stays free."""
    sizes = partition_sizes_gib(11, 2048, dist="zipf", skew=2.0)
    assert (sizes * 100 < 1.0).any()      # sub-tick raw countdowns exist
    t = _partition_rebuild_ticks(11, 2048, 100, dist="zipf", skew=2.0)
    assert t.dtype == np.int32
    assert (t >= 1).all()
    assert (t == 1).any()                 # the clamp actually fired
    assert (_partition_rebuild_ticks(11, 2048, 0, dist="zipf",
                                     skew=2.0) == 0).all()
    # the cap keeps huge hot-partition countdowns in int32 territory
    capped = _partition_rebuild_ticks(11, 2048, 10**6, dist="zipf",
                                      skew=2.5, cap=4_001)
    assert capped.max() == 4_001


def test_one_tick_rebuilds_bin_into_the_first_bucket():
    """Edge-binning satellite: with every partition sub-GiB enough that
    its clamped countdown is exactly 1 tick, completed single-loss
    quorum pauses are real 1-tick pauses — counted in bucket [1, 2),
    never dropped with the zero-length runs."""
    kw = dict(n=12, partitions=32, rf=3, p=1e-3, trials=2, max_ticks=20_000,
              min_ticks=10**9, seed=7, backend="numpy", dupres_ticks=0,
              rebuild_model="reconfig", rebuild_ticks_per_gib=1,
              size_dist="zipf", size_skew=3.0)
    t = _partition_rebuild_ticks(7, 32, 1, dist="zipf", skew=3.0)
    assert (t == 1).mean() > 0.7          # the bulk clamps to one tick
    assert (1 * partition_sizes_gib(7, 32, dist="zipf",
                                    skew=3.0) < 1).any()
    r = simulate_downtime_batched(**kw)
    assert int(r.hist_quorum.sum()) > 0
    assert r.hist_quorum[0] > 0           # mass in [1, 2)


# ---------------------------------------------------------------------------
# the per-node reduction op (bandwidth-contended rebuilds)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,P,n_real", [(3, 32, 13), (4, 100, 31)])
def test_rebuild_node_counts_backends_agree(B, P, n_real):
    rec = RNG.integers(0, n_real + 1, (B, P)).astype(np.int32)  # incl sentinel
    act = RNG.random((B, P)) < 0.4
    outs = {}
    for b in PAC_BACKENDS:
        r = rec if b == "numpy" else jnp.asarray(rec)
        a = act if b == "numpy" else jnp.asarray(act)
        outs[b] = np.asarray(rebuild_node_counts(r, a, n_real=n_real,
                                                 backend=b))
    exp = np.zeros((B, n_real), np.int32)
    for i in range(B):
        for p_ in range(P):
            if act[i, p_] and rec[i, p_] < n_real:
                exp[i, rec[i, p_]] += 1
    for b in PAC_BACKENDS:
        assert np.array_equal(outs[b], exp), b
    # inactive partitions and sentinel/out-of-range ids contribute nothing
    assert outs["numpy"].sum() == int((act & (rec < n_real)).sum())


def test_rebuild_node_counts_never_crosses_trials():
    """The reduction that makes bandwidth contention work is per-trial:
    permuting whole trial rows permutes the output rows and nothing
    else — the property that lets trials-axis sharding commute with it."""
    rec = RNG.integers(0, 9, (4, 64)).astype(np.int32)
    act = RNG.random((4, 64)) < 0.5
    base = rebuild_node_counts(rec, act, n_real=8, backend="numpy")
    perm = np.array([2, 0, 3, 1])
    swapped = rebuild_node_counts(rec[perm], act[perm], n_real=8,
                                  backend="numpy")
    assert np.array_equal(swapped, base[perm])


# ---------------------------------------------------------------------------
# bandwidth-contended rebuilds (engine level)
# ---------------------------------------------------------------------------

_SKEW_KW = dict(_KW, rebuild_model="reconfig", rebuild_ticks_per_gib=64,
                size_dist="zipf", size_skew=1.2, node_bandwidth_gibps=1.0)


def test_infinite_bandwidth_is_the_unshared_model_bit_for_bit():
    """Satellite degenerate limit: --size-dist uniform
    --node-bandwidth-gibps inf is the PR-4 reconfig baseline (the
    committed BENCH_downtime_reconfig.json pins the same thing at sweep
    scale, across devices 1 vs 8)."""
    import math
    kw = dict(_KW, rebuild_model="reconfig", rebuild_ticks_per_gib=64)
    base = simulate_downtime_batched(**kw)
    expl = simulate_downtime_batched(size_dist="uniform",
                                     node_bandwidth_gibps=math.inf, **kw)
    for k in base.trajectory:
        assert np.array_equal(base.trajectory[k], expl.trajectory[k]), k
    assert base.pause_lark == expl.pause_lark
    assert base.pause_quorum == expl.pause_quorum
    assert np.array_equal(base.hist_quorum, expl.hist_quorum)
    assert np.array_equal(base.hist_lark, expl.hist_lark)
    assert base.quorum_events == expl.quorum_events
    assert base.node_bandwidth_gibps == math.inf
    assert base.size_skew == 0.0          # knob inert under uniform


def test_zero_skew_zipf_matches_uniform_within_ci():
    """Satellite: zipf at skew 0 (constant 1.5 GiB) must land within the
    runs' combined CI of the uniform baseline — same mean catch-up cost,
    same trajectories, only the per-partition spread differs."""
    kw = dict(_KW, rebuild_model="reconfig", rebuild_ticks_per_gib=64)
    uni = simulate_downtime_batched(**kw)
    z0 = simulate_downtime_batched(size_dist="zipf", size_skew=0.0, **kw)
    assert np.array_equal(uni.trajectory["times"], z0.trajectory["times"])
    assert z0.pause_lark == uni.pause_lark         # LARK has no sizes
    assert abs(z0.pause_quorum - uni.pause_quorum) <= \
        uni.ci_quorum + z0.ci_quorum


def test_bandwidth_contention_only_adds_quorum_pause():
    """Sharing a recruit's ingest bandwidth can only stretch catch-ups:
    quorum pause is monotone down in bandwidth, per trial, and LARK —
    which rebuilds nothing — is bit-identical at every setting."""
    kw = dict(_KW, rebuild_model="reconfig", rebuild_ticks_per_gib=64,
              size_dist="zipf", size_skew=1.2)
    inf_r = simulate_downtime_batched(**kw)
    bw2 = simulate_downtime_batched(node_bandwidth_gibps=2.0, **kw)
    bw1 = simulate_downtime_batched(node_bandwidth_gibps=1.0, **kw)
    assert bw1.pause_quorum >= bw2.pause_quorum >= inf_r.pause_quorum
    assert bw1.pause_quorum > inf_r.pause_quorum   # contention really bites
    assert (bw1.pause_quorum_trials >= inf_r.pause_quorum_trials).all()
    for r in (bw1, bw2):
        assert r.pause_lark == inf_r.pause_lark
        assert np.array_equal(r.hist_lark, inf_r.hist_lark)
        assert np.array_equal(r.trajectory["paused_lark"],
                              inf_r.trajectory["paused_lark"])
        assert np.array_equal(r.trajectory["times"],
                              inf_r.trajectory["times"])


def test_skew_plus_contention_heavier_pause_tail():
    """The acceptance criterion at test scale: zipf sizes + unit
    bandwidth shift quorum pause-duration mass into strictly higher
    power-of-two buckets than the uniform/inf baseline on the same
    trajectory (hot partitions rebuild for longer, and concurrent
    catch-ups serialize)."""
    kw = dict(_KW, rebuild_model="reconfig", rebuild_ticks_per_gib=64)
    base = simulate_downtime_batched(**kw)
    skew = simulate_downtime_batched(size_dist="zipf", size_skew=1.2,
                                     node_bandwidth_gibps=1.0, **kw)
    top = lambda h: max(i for i, v in enumerate(h) if v)
    assert top(skew.hist_quorum) > top(base.hist_quorum)
    cut = top(base.hist_quorum)
    assert skew.hist_quorum[cut:].sum() > base.hist_quorum[cut:].sum()


def test_shard_map_path_identical_with_bandwidth_contention():
    plain = simulate_downtime_batched(backend="jax", **_SKEW_KW)
    mesh1 = simulate_downtime_batched(backend="jax", devices=1,
                                      use_shard_map=True, **_SKEW_KW)
    for k in plain.trajectory:
        assert np.array_equal(plain.trajectory[k], mesh1.trajectory[k]), k
    assert plain.pause_quorum == mesh1.pause_quorum
    assert np.array_equal(plain.hist_quorum, mesh1.hist_quorum)


# ---------------------------------------------------------------------------
# per-(trial, partition) lookups: lane compares equal the indexed forms
# ---------------------------------------------------------------------------

def _lookup_inputs(n, rf, seed):
    """Random step-shaped inputs at B=4, P=192: an up mask in rank space,
    rosters of distinct ranks, per-node counts, recruits with the
    sentinel n, and ranks at and past both clip edges."""
    rng = np.random.default_rng(seed)
    B, P = 4, 192
    up_succ = rng.random((B, P, n)) < 0.7
    roster = np.argsort(rng.random((B, P, n)), axis=2)[:, :, :rf]
    roster[0, :, 0] = n - 1                  # the last rank is a seat too
    counts = rng.integers(0, 50, (B, n)).astype(np.int32)
    recruit = rng.integers(0, n + 1, (B, P)).astype(np.int32)
    recruit[:, ::5] = n                      # no known ingest node
    recruit[1, :n] = np.arange(n)            # every node at least once
    rank = rng.integers(-1, n + 2, (B, P)).astype(np.int32)
    rank[0, :6] = [0, rf - 1, rf, n - 1, n, -1]
    succ = succession_matrix_fast(P, range(n), seed=seed).astype(np.int32)
    return (up_succ, roster.astype(np.int32), counts, recruit, rank, succ)


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jnp"])
@pytest.mark.parametrize("rf", [2, 3])
@pytest.mark.parametrize("n", [31, 155])
@pytest.mark.parametrize("lookup", ["seat_up", "count_at", "rank_node"])
def test_lane_lookups_equal_the_gathers_they_replace(lookup, n, rf, xp):
    up_succ, roster, counts, recruit, rank, succ = _lookup_inputs(
        n, rf, seed=1000 * n + rf)
    if lookup == "seat_up":
        got = [_seat_up(xp, xp.asarray(up_succ), xp.asarray(roster))]
        want = [np.take_along_axis(up_succ, roster, axis=2)]
    elif lookup == "count_at":
        got = [_count_at(xp, xp.asarray(counts), xp.asarray(recruit))]
        gathered = np.take_along_axis(counts, np.clip(recruit, 0, n - 1),
                                      axis=1)
        want = [np.where(recruit < n, gathered, 0)]
        # the contention divisor the steps derive from it is unchanged
        assert np.array_equal(
            np.where(recruit < n, np.maximum(np.asarray(got[0]), 1), 1),
            np.where(recruit < n, np.maximum(gathered, 1), 1))
    else:
        got = [_rank_node(xp, xp.asarray(succ), xp.asarray(rank), w)
               for w in (rf, n)]
        want = [succ[np.arange(succ.shape[0])[None, :],
                     np.clip(rank, 0, w - 1)] for w in (rf, n)]
    for g, w in zip(got, want):
        g = np.asarray(g)
        assert g.dtype == (np.bool_ if lookup == "seat_up" else np.int32)
        assert g.shape == w.shape
        assert np.array_equal(g, w)
