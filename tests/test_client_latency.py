"""Client-latency engine invariants (core/client_latency.py).

The load-bearing guarantees, each pinned here:
  * the zipf workload tables are mean-pinned (weights sum to exactly 1;
    key_zipf=0 is the exactly-uniform 1/P table) — skew moves traffic
    between partitions, never adds offered load;
  * the zero-knob limit (dupres_ticks=0, uniform keys, 100% reads) lands
    at exactly 0 added latency on every reported column;
  * all three backends, packed and unpacked carries, produce
    bit-identical raw accumulators (the devices 1-vs-8 half lives in
    tests/test_sharded.py);
  * percentiles/means are monotone in dupres_ticks and in zipf skew
    (LARK's charged fraction falls as traffic concentrates on hot keys);
  * p999 >= p99 >= p50 on every emitted row, adversarially sampled.
"""
import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.client_latency import (_percentile, key_bucket_shares,
                                       partition_request_weights,
                                       partition_write_fractions,
                                       simulate_client_latency)
from repro.core.downtime_batched import DowntimeParams, \
    simulate_downtime_batched

# small but failure-rich: rf=2 at a high p on a tiny cluster produces
# leader changes, rebuilds, and majority-down spells within a few
# thousand ticks
_KW = dict(n=6, rf=2, p=2e-4, partitions=64, trials=4, max_ticks=12_000,
           min_ticks=12_000, chunk_steps=64, seed=3,
           dupres_ticks=4, requests_per_tick=8.0, key_zipf=1.0,
           read_frac=0.8, slo_ticks=2)


def _raw(r):
    return r.downtime.latency_raw


# ---------------------------------------------------------------------------
# workload tables
# ---------------------------------------------------------------------------

def test_uniform_weights_exact():
    w = partition_request_weights(0, 128, key_zipf=0.0)
    assert np.all(w == 1.0 / 128)


@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=1, max_value=64),
       st.integers(min_value=0, max_value=12),
       st.integers(min_value=0, max_value=1000))
def test_weights_mean_pinned(partitions, zipf_quarters, seed):
    """Sum(w) == 1 to float64 round-off for any skew/seed/P — i.e. the
    mean weight is pinned at 1/P and skew never changes offered load."""
    w = partition_request_weights(seed, partitions,
                                  key_zipf=zipf_quarters / 4.0,
                                  keys_per_partition=64)
    assert w.shape == (partitions,)
    assert np.all(w >= 0)
    assert abs(w.sum() - 1.0) < 1e-12


def test_bucket_shares_partition_unity():
    for z in (0.0, 0.7, 1.0, 2.5):
        f, g = key_bucket_shares(z)
        assert abs(f.sum() - 1.0) < 1e-12
        assert abs(g.sum() - 1.0) < 1e-12
        assert np.all(f > 0) and np.all(g > 0)
    # uniform popularity: traffic share == key-count share exactly
    f0, g0 = key_bucket_shares(0.0)
    assert np.allclose(f0, g0, rtol=0, atol=1e-15)


def test_params_validation():
    with pytest.raises(ValueError):
        DowntimeParams(key_zipf=-0.1)
    with pytest.raises(ValueError):
        DowntimeParams(key_zipf=100.0)
    with pytest.raises(ValueError):
        DowntimeParams(read_frac=1.5)
    with pytest.raises(ValueError):
        DowntimeParams(read_frac=-0.01)
    with pytest.raises(ValueError):
        DowntimeParams(requests_per_tick=-1.0)
    with pytest.raises(ValueError):
        DowntimeParams(requests_per_tick=math.inf)
    with pytest.raises(ValueError):
        DowntimeParams(slo_ticks=-1)


# ---------------------------------------------------------------------------
# zero-knob limit and plain-downtime inertness
# ---------------------------------------------------------------------------

def test_zero_knob_limit_exactly_zero():
    r = simulate_client_latency(backend="jax", **{
        **_KW, "dupres_ticks": 0, "key_zipf": 0.0, "read_frac": 1.0})
    for col in ("lat_lark", "lat_quorum", "lat_hermes",
                "p50_lark", "p99_lark", "p999_lark",
                "p50_quorum", "p99_quorum", "p999_quorum",
                "p50_hermes", "p99_hermes", "p999_hermes",
                "slo_lark", "slo_quorum", "slo_hermes"):
        assert getattr(r, col) == 0.0, col
    assert np.all(_raw(r)["dup"] == 0.0)
    assert np.all(_raw(r)["qhist"] == 0.0)


def test_plain_downtime_has_no_latency_state():
    """Without a latency plan the engine must not grow its carry or
    allocate accumulators — the workload knobs are inert defaults."""
    r = simulate_downtime_batched(
        n=6, rf=2, p=2e-4, partitions=32, trials=2, max_ticks=4_000,
        min_ticks=4_000, chunk_steps=64, seed=0, backend="numpy")
    assert r.latency_raw is None


# ---------------------------------------------------------------------------
# backend matrix / packed-carry bit-identity
# ---------------------------------------------------------------------------

def test_backend_matrix_bit_identical():
    base = simulate_client_latency(backend="numpy", **_KW)
    for backend in ("jax", "pallas"):
        other = simulate_client_latency(backend=backend, **_KW)
        for k in ("dup", "qhist", "qslo", "qsum", "now"):
            assert np.array_equal(_raw(base)[k], _raw(other)[k]), \
                (backend, k)
        assert base.lat_lark == other.lat_lark
        assert base.lat_quorum == other.lat_quorum
        assert base.p999_quorum == other.p999_quorum


def test_packed_carry_bit_identical():
    base = simulate_client_latency(backend="jax", **_KW)
    packed = simulate_client_latency(backend="jax", packed=True, **_KW)
    for k in ("dup", "qhist", "qslo", "qsum", "now"):
        assert np.array_equal(_raw(base)[k], _raw(packed)[k]), k
    assert base.lat_lark == packed.lat_lark
    assert base.slo_quorum == packed.slo_quorum


def test_shard_map_path_identical_on_one_device():
    base = simulate_client_latency(backend="jax", **_KW)
    sharded = simulate_client_latency(backend="jax", use_shard_map=True,
                                      devices=1, **_KW)
    for k in ("dup", "qhist", "qslo", "qsum", "now"):
        assert np.array_equal(_raw(base)[k], _raw(sharded)[k]), k


# ---------------------------------------------------------------------------
# monotonicity
# ---------------------------------------------------------------------------

def test_latency_monotone_in_dupres_ticks():
    """LARK percentiles/mean/SLO are non-decreasing in the dup-res cost:
    the charged request fraction is dupres-independent (the dirty-key
    process never sees the price), so the mean scales linearly and the
    percentile values ride the charge upward."""
    prev = None
    for d in (0, 1, 2, 4, 8):
        r = simulate_client_latency(backend="jax", **{**_KW,
                                                      "dupres_ticks": d})
        cur = (r.lat_lark, r.p50_lark, r.p99_lark, r.p999_lark,
               r.lat_hermes, r.slo_lark)
        if prev is not None:
            assert all(c >= p for c, p in zip(cur, prev)), (d, prev, cur)
        prev = cur


def test_lark_latency_monotone_in_zipf_skew():
    """More key skew -> strictly less LARK dup-res traffic: concentrating
    requests on a few hot keys means a failover dirties the same K keys
    but far fewer distinct keys ever get touched (hot ones are cleaned
    within a tick or two, the cold tail is never read), so the charged
    fraction — and with it mean/percentiles/SLO — falls."""
    prev = None
    for z in (0.0, 0.5, 1.0, 2.0):
        r = simulate_client_latency(backend="jax", **{**_KW,
                                                      "key_zipf": z})
        cur = (r.lat_lark, r.p99_lark, r.p999_lark, r.slo_lark)
        if prev is not None:
            assert all(c <= p for c, p in zip(cur, prev)), (z, prev, cur)
        prev = cur


# ---------------------------------------------------------------------------
# percentile ordering — unit-level adversarial + emitted rows
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(st.integers(min_value=0, max_value=10**9),
       st.integers(min_value=1, max_value=8))
def test_percentile_walk_ordering(seed, n_masses):
    """p999 >= p99 >= p50 for arbitrary point-mass distributions,
    including zero-total, all-zero-latency, and charged > total edge
    noise."""
    rng = np.random.default_rng(seed)
    masses = [(float(rng.integers(0, 100)), float(rng.uniform(0, 50)))
              for _ in range(n_masses)]
    total = float(rng.uniform(0, 2) * sum(m[1] for m in masses) + 1e-9)
    p50 = _percentile(masses, total, 0.5)
    p99 = _percentile(masses, total, 0.99)
    p999 = _percentile(masses, total, 0.999)
    assert 0.0 <= p50 <= p99 <= p999


def test_emitted_rows_percentiles_ordered():
    """Every row the sweep emits must satisfy the ordering for all three
    protocols — run a grid of workload corners and check each."""
    corners = [
        {},                                          # defaults of _KW
        {"read_frac": 0.0},                          # all writes
        {"read_frac": 1.0},                          # all reads
        {"key_zipf": 0.0},
        {"key_zipf": 3.0, "dupres_ticks": 16},
        {"requests_per_tick": 0.5, "slo_ticks": 0},
    ]
    for c in corners:
        r = simulate_client_latency(backend="numpy", **{**_KW, **c})
        for proto in ("lark", "quorum", "hermes"):
            p50 = getattr(r, f"p50_{proto}")
            p99 = getattr(r, f"p99_{proto}")
            p999 = getattr(r, f"p999_{proto}")
            assert 0.0 <= p50 <= p99 <= p999, (c, proto, p50, p99, p999)
        assert 0.0 <= r.slo_lark <= 1.0
        assert 0.0 <= r.slo_quorum <= 1.0
        assert r.slo_hermes <= r.slo_lark


# ---------------------------------------------------------------------------
# cross-metric consistency
# ---------------------------------------------------------------------------

def test_hermes_is_write_fraction_of_lark():
    r = simulate_client_latency(backend="jax", **_KW)
    assert r.lat_hermes == (1.0 - _KW["read_frac"]) * r.lat_lark
    assert r.slo_hermes == (1.0 - _KW["read_frac"]) * r.slo_lark


def test_charged_fraction_bounded_by_offered_load():
    """The analytic first-touch count can never exceed offered requests
    (1 - e^-x <= x per bucket-interval), and quorum can never charge more
    SLO violations than writes arrive."""
    r = simulate_client_latency(backend="jax", **_KW)
    raw = _raw(r)
    req = _KW["requests_per_tick"] * raw["now"].sum()
    assert raw["dup"].sum() <= req * 1.0000001
    assert raw["qslo"].sum() <= req * (1 - _KW["read_frac"]) * 1.0000001


# ---------------------------------------------------------------------------
# _percentile boundary semantics (adversarial pins)
# ---------------------------------------------------------------------------

def test_percentile_exact_cdf_landing_takes_value():
    """A cumulative mass landing *exactly* on q * total selects that
    value (the walk uses >=), never the next one up."""
    masses = [(1.0, 32.0), (2.0, 32.0)]
    assert _percentile(masses, 64.0, 0.5) == 1.0
    assert _percentile(masses, 64.0, 0.75) == 2.0
    # the zero-latency mass exactly covering q returns 0.0, not the
    # smallest positive value
    assert _percentile([(5.0, 1.0)], 100.0, 0.99) == 0.0
    assert _percentile([(5.0, 1.0)], 100.0, 0.995) == 5.0


def test_percentile_zero_mass_and_zero_total():
    assert _percentile([], 100.0, 0.999) == 0.0
    assert _percentile([(3.0, 0.0)], 100.0, 0.5) == 0.0
    assert _percentile([(3.0, 1.0)], 0.0, 0.5) == 0.0
    assert _percentile([(3.0, 1.0)], -1.0, 0.999) == 0.0


def test_percentile_single_bucket_and_overcharged_total():
    # one point mass covering everything: every quantile lands on it
    for q in (0.5, 0.99, 0.999):
        assert _percentile([(7.0, 10.0)], 10.0, q) == 7.0
    # charged mass exceeding the total (float drift): the zero mass is
    # clamped at 0 and the walk still terminates on the charged values
    assert _percentile([(3.0, 200.0)], 100.0, 0.5) == 3.0
    assert _percentile([(3.0, 200.0)], 100.0, 0.999) == 3.0
    # unsorted input is sorted by value before walking
    assert _percentile([(9.0, 1.0), (2.0, 99.0)], 100.0, 0.5) == 2.0


# ---------------------------------------------------------------------------
# strict-> SLO threshold (slo_ticks=0 is a live edge, not a sentinel)
# ---------------------------------------------------------------------------

def test_slo_strict_threshold_semantics():
    """A request violates iff its added latency strictly exceeds
    slo_ticks: LARK's charge is exactly dupres_ticks per dup-res, so
    slo_ticks == dupres_ticks charges nothing and slo_ticks just below
    charges every dup-res."""
    at = simulate_client_latency(backend="numpy",
                                 **{**_KW, "slo_ticks": 4})
    below = simulate_client_latency(backend="numpy",
                                    **{**_KW, "slo_ticks": 3})
    assert at.slo_lark == 0.0 and at.slo_hermes == 0.0
    assert below.slo_lark > 0.0
    # slo_ticks=0 is live under strict >: any positive added latency
    # violates, so the LARK fraction equals any other threshold below
    # dupres_ticks and quorum counts at least as many waits
    live = simulate_client_latency(backend="numpy",
                                   **{**_KW, "slo_ticks": 0})
    assert live.slo_lark == below.slo_lark > 0.0
    assert live.slo_quorum >= below.slo_quorum


# ---------------------------------------------------------------------------
# SLO curves
# ---------------------------------------------------------------------------

def test_slo_curve_monotone_and_endpoint_exact():
    r = simulate_client_latency(
        backend="numpy", **{**_KW, "slo_ticks": 3, "slo_curve_bins": 8})
    edges = np.asarray(r.slo_curve_edges)
    assert edges.tolist() == [(1 << j) - 1 for j in range(8)]
    for curve in (r.slo_curve_lark, r.slo_curve_quorum,
                  r.slo_curve_hermes):
        c = np.asarray(curve)
        assert c.shape == (8,)
        assert np.all((c >= 0.0) & (c <= 1.0))
        assert np.all(np.diff(c) <= 0.0)        # non-increasing
    # slo_ticks=3 sits on curve edge 2^2 - 1: the curve reproduces the
    # scalar columns there bitwise
    j = int(np.flatnonzero(edges == 3)[0])
    assert r.slo_curve_lark[j] == r.slo_lark
    assert r.slo_curve_quorum[j] == r.slo_quorum
    assert r.slo_curve_hermes[j] == r.slo_hermes


def test_slo_curve_off_threshold_still_monotone():
    # slo_ticks=2 is not a 2^j - 1 edge: no substitution happens, the
    # curve must still be monotone and bounded
    r = simulate_client_latency(backend="numpy",
                                **{**_KW, "slo_curve_bins": 6})
    for curve in (r.slo_curve_lark, r.slo_curve_quorum,
                  r.slo_curve_hermes):
        c = np.asarray(curve)
        assert np.all(np.diff(c) <= 0.0) and np.all((c >= 0) & (c <= 1))


def test_slo_curve_off_by_default():
    r = simulate_client_latency(backend="numpy", **_KW)
    assert r.slo_curve_bins == 0
    assert r.slo_curve_edges is None and r.slo_curve_lark is None


# ---------------------------------------------------------------------------
# per-partition write mix
# ---------------------------------------------------------------------------

def test_write_fractions_uniform_at_zero_skew():
    w = partition_write_fractions(7, 64, read_frac=0.8, write_skew=0.0)
    assert np.all(w == 1.0 - 0.8)


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=2, max_value=128),
       st.integers(min_value=1, max_value=32),
       st.integers(min_value=0, max_value=4),
       st.integers(min_value=0, max_value=1000))
def test_write_fractions_mean_pinned(partitions, skew_quarters,
                                     rf_quarters, seed):
    """mean(w) == 1 - read_frac to float64 round-off for any skew, even
    deep into saturation (the waterfilling pin), and every fraction stays
    a valid probability."""
    rf = rf_quarters / 4.0
    w = partition_write_fractions(seed, partitions, read_frac=rf,
                                  write_skew=skew_quarters / 4.0)
    assert w.shape == (partitions,)
    assert np.all((w >= 0.0) & (w <= 1.0))
    assert abs(w.mean() - (1.0 - rf)) < 1e-12


def test_write_skew_leaves_lark_path_untouched():
    """The write mix reweights lamw (the quorum/hermes write-arrival
    table) only — LARK's dup-res charges ride the full request stream
    and must stay bit-identical under skew."""
    base = simulate_client_latency(backend="numpy", **_KW)
    sk = simulate_client_latency(backend="numpy", write_skew=1.0, **_KW)
    assert np.array_equal(_raw(base)["dup"], _raw(sk)["dup"])
    assert sk.lat_lark == base.lat_lark
    assert sk.p999_lark == base.p999_lark
    assert "dupw" in _raw(sk) and "dupw" not in _raw(base)
    assert sk.write_skew == 1.0 and base.write_skew == 0.0


# ---------------------------------------------------------------------------
# fixed-model bandwidth contention
# ---------------------------------------------------------------------------

def test_fixed_bandwidth_contention_changes_waits():
    """A tight shared-bandwidth budget stretches fixed-model rebuilds,
    so quorum waits grow; the knob must actually bite."""
    base = simulate_client_latency(backend="numpy", **_KW)
    tight = simulate_client_latency(backend="numpy",
                                    node_bandwidth_gibps=0.25, **_KW)
    assert tight.rebuild_model == "fixed"
    assert math.isfinite(tight.node_bandwidth_gibps)
    assert tight.lat_quorum > base.lat_quorum


def test_new_knobs_backend_matrix_bit_identical():
    """All three knobs live at once: numpy, jax, jax-packed, and pallas
    must agree bit-for-bit on every raw accumulator and on the curve."""
    kw = {**_KW, "write_skew": 1.0, "node_bandwidth_gibps": 0.5,
          "slo_curve_bins": 8}
    base = simulate_client_latency(backend="numpy", **kw)
    for backend, extra in (("jax", {}), ("jax", {"packed": True}),
                           ("pallas", {})):
        other = simulate_client_latency(backend=backend, **extra, **kw)
        for k in ("dup", "dupw", "qhist", "qslo", "qsum", "now"):
            assert np.array_equal(_raw(base)[k], _raw(other)[k]), \
                (backend, extra, k)
        assert base.lat_lark == other.lat_lark
        assert base.lat_quorum == other.lat_quorum
        assert base.lat_hermes == other.lat_hermes
        assert np.array_equal(base.slo_curve_quorum,
                              other.slo_curve_quorum)


def test_partition_pool_sums_in_sequence_whatever_the_layout():
    """Chunk drains pool the float32 charge accumulators over partitions
    in float64, in sequence along P, whatever strides the device array
    reaches the host with (a one-device TPU array arrives partitions-
    innermost, where a plain numpy sum would go pairwise and round the
    same values differently)."""
    from repro.core.downtime_batched import _pool_partitions
    rng = np.random.default_rng(5)
    x = (rng.random((3, 300, 4)) ** 20 * 1000).astype(np.float32)
    strided = np.moveaxis(np.ascontiguousarray(np.moveaxis(x, 1, -1)), -1, 1)
    assert not strided.flags.c_contiguous
    want = np.zeros((3, 4))
    for p in range(x.shape[1]):
        want += x[:, p].astype(np.float64)
    assert np.array_equal(_pool_partitions(x), want)
    assert np.array_equal(_pool_partitions(strided), want)
    w = rng.random((1, 300, 1))
    want_w = np.zeros((3, 4))
    for p in range(x.shape[1]):
        want_w += x[:, p].astype(np.float64) * w[:, p]
    assert np.array_equal(_pool_partitions(strided, w), want_w)
