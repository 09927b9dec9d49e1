"""Deterministic PAC block-size autotuner: candidate enumeration, choice
stability, the interpret-safe CPU fallback, and block-size invariance of
the kernel results — for both the 1-D block_p tuner and the fused
megakernel's 2-D (block_t, block_p) tuner."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import (_AUTOTUNE_CACHE, autotune_block_p,
                               autotune_fused_blocks, block_p_candidates,
                               fused_block_candidates, fused_vmem_bytes,
                               pac_eval_batch, pac_vmem_bytes)
from repro.kernels.pac_eval import pac_eval

RNG = np.random.default_rng(3)


def test_candidates_are_a_pure_function_of_the_shape():
    a = block_p_candidates(4096, 64)
    b = block_p_candidates(4096, 64)
    assert a == b and a
    assert all(4096 % bp == 0 for bp in a)
    assert all(pac_vmem_bytes(bp, 64) <= 8 * 2 ** 20 for bp in a)
    # a tighter VMEM budget prunes the big blocks
    small = block_p_candidates(4096, 64, vmem_limit_bytes=pac_vmem_bytes(64, 64))
    assert max(small) <= 64


def test_candidates_never_empty():
    # odd row counts still get the heuristic block
    assert block_p_candidates(7 * 31, 64)


def test_autotune_same_candidates_same_choice():
    fake = lambda R, n, bp: {16: 9.0, 32: 4.0, 64: 4.0, 128: 6.0}[bp]
    kw = dict(rf=3, voters=5, n_real=63, candidates=(16, 32, 64, 128),
              measure=fake)
    r1 = autotune_block_p(1024, 64, **kw)
    r2 = autotune_block_p(1024, 64, **kw)
    assert r1.block_p == r2.block_p == 32       # tie 32/64 -> smaller block
    assert r1 == r2
    assert r1.source == "measured"
    assert r1.timings_us[128] == 6.0


def test_autotune_rejects_non_tiling_candidates():
    with pytest.raises(ValueError, match="divide"):
        autotune_block_p(1000, 64, rf=2, voters=3, n_real=63,
                         candidates=(33,), measure=lambda *a: 1.0)


def test_autotune_cpu_fallback_is_deterministic_heuristic():
    # no injected measure + no TPU -> static heuristic, never a timing race
    r1 = autotune_block_p(2048, 64, rf=2, voters=3, n_real=63)
    r2 = autotune_block_p(2048, 64, rf=2, voters=3, n_real=63)
    assert r1.source == "heuristic-fallback"
    assert r1.block_p == r2.block_p == 256
    assert r1.timings_us == {}


@pytest.mark.slow
def test_forced_measurement_path_runs_off_tpu():
    # force=True exercises the real timing harness (interpret mode here:
    # functional coverage, not a timing proxy)
    r = autotune_block_p(128, 64, rf=2, voters=3, n_real=31,
                         candidates=(64, 128), iters=1, force=True)
    assert r.source == "measured"
    assert r.block_p in (64, 128)
    assert set(r.timings_us) == {64, 128}


@pytest.mark.slow
@pytest.mark.parametrize("kernel", ["downtime", "downtime_roster"])
def test_forced_measurement_races_the_downtime_kernels(kernel):
    """--metric downtime autotunes the kernel the grid actually runs (and
    the roster variant under --rebuild-model reconfig), not pac_eval."""
    r = autotune_block_p(128, 64, rf=3, voters=5, n_real=31,
                         candidates=(64, 128), iters=1, force=True,
                         kernel=kernel)
    assert r.source == "measured"
    assert set(r.timings_us) == {64, 128}


def test_kernel_selection_is_part_of_the_cache_key_and_validated():
    fake = lambda R, n, bp: float(bp)
    kw = dict(rf=2, voters=3, n_real=63, candidates=(32, 64), measure=fake)
    a = autotune_block_p(512, 64, kernel="pac", **kw)
    b = autotune_block_p(512, 64, kernel="downtime", **kw)
    assert a.block_p == b.block_p == 32          # same fake, same choice
    with pytest.raises(ValueError, match="autotune kernel"):
        autotune_block_p(512, 64, kernel="mystery", **kw)


# ---------------------------------------------------------------------------
# fused megakernel 2-D (block_t, block_p) tuner
# ---------------------------------------------------------------------------

def test_fused_candidates_pure_function_of_shape_and_budget():
    a = fused_block_candidates(8, 4096, 160, rf=3,
                               kernel="fused_downtime_roster")
    assert a == fused_block_candidates(8, 4096, 160, rf=3,
                                       kernel="fused_downtime_roster")
    assert a
    for bt, bp in a:
        assert 8 % bt == 0 and 4096 % bp == 0
        assert fused_vmem_bytes(bt, bp, 160, rf=3,
                                kernel="fused_downtime_roster") \
            <= 8 * 2 ** 20
    # a tighter budget prunes the fat tiles down to the smallest legal
    # one; below that there is no legal tiling, and that is an error
    floor = fused_vmem_bytes(8, 128, 160, rf=3, kernel="fused_pac")
    small = fused_block_candidates(8, 4096, 160, rf=3,
                                   vmem_limit_bytes=floor)
    assert small == ((8, 128),)
    with pytest.raises(ValueError, match="no legal fused tile"):
        fused_block_candidates(8, 4096, 160, rf=3,
                               vmem_limit_bytes=floor - 1)


def test_fused_autotune_ties_break_toward_the_smaller_tile():
    fake = lambda B, P, n, bt, bp: {(1, 16): 4.0, (2, 16): 4.0,
                                    (2, 32): 4.0, (4, 32): 9.0}[(bt, bp)]
    kw = dict(rf=3, voters=5, n_real=63,
              candidates=((1, 16), (2, 16), (2, 32), (4, 32)),
              measure=fake)
    r1 = autotune_fused_blocks(4, 64, 64, **kw)
    r2 = autotune_fused_blocks(4, 64, 64, **kw)
    assert (r1.block_t, r1.block_p) == (r2.block_t, r2.block_p) == (1, 16)
    assert r1.source == "measured"
    assert r1.timings_us[(4, 32)] == 9.0


def test_fused_autotune_rejects_bad_candidates_and_kernels():
    with pytest.raises(ValueError, match="does not tile"):
        autotune_fused_blocks(4, 64, 64, rf=2, voters=3, n_real=63,
                              candidates=((3, 16),),
                              measure=lambda *a: 1.0)
    with pytest.raises(ValueError, match="fused autotune kernel"):
        autotune_fused_blocks(4, 64, 64, rf=2, voters=3, n_real=63,
                              kernel="mystery")


def test_fused_autotune_cpu_fallback_is_deterministic_heuristic():
    kw = dict(rf=2, voters=3, n_real=63)
    r1 = autotune_fused_blocks(2048, 64, 64, **kw)
    r2 = autotune_fused_blocks(2048, 64, 64, **kw)
    assert r1.source == "heuristic-fallback"
    assert (r1.block_t, r1.block_p) == (r2.block_t, r2.block_p)
    assert 2048 % r1.block_t == 0 and 64 % r1.block_p == 0
    assert r1.timings_us == {}


def test_fused_cache_key_cannot_alias_a_block_p_entry():
    """The 2-D tuner's cache entries are tagged "fused" + kernel kind +
    full geometry; identical numeric prefixes from the 1-D tuner land on
    distinct keys, so the wrong-kernel cache race can't come back."""
    autotune_block_p(512, 64, rf=2, voters=3, n_real=63)
    autotune_fused_blocks(512, 64, 64, rf=2, voters=3, n_real=63)
    tags = {k[0] for k in _AUTOTUNE_CACHE}
    assert {"block_p", "fused"} <= tags
    for k in _AUTOTUNE_CACHE:
        assert k[0] in ("block_p", "fused")


@pytest.mark.slow
@pytest.mark.parametrize("kernel", ["fused_pac", "fused_downtime",
                                    "fused_downtime_roster"])
def test_forced_fused_measurement_races_every_kernel_kind(kernel):
    r = autotune_fused_blocks(4, 32, 64, rf=3, voters=5, n_real=63,
                              candidates=((1, 16), (2, 32)), iters=1,
                              force=True, kernel=kernel)
    assert r.source == "measured"
    assert (r.block_t, r.block_p) in ((1, 16), (2, 32))
    assert set(r.timings_us) == {(1, 16), (2, 32)}


def test_block_size_does_not_change_kernel_results():
    R, n = 512, 64
    up = jnp.asarray(RNG.random((R, n)) < 0.9)
    full = jnp.asarray(RNG.random((R, n)) < 0.3)
    outs = [tuple(np.asarray(o) for o in pac_eval_batch(
        up, full, rf=3, voters=5, n_real=63, backend="pallas", block_p=bp))
        for bp in (32, 128, 512)]
    for o in outs[1:]:
        for a, b in zip(outs[0], o):
            assert np.array_equal(a, b)


def test_pac_eval_rejects_non_tiling_block():
    up = jnp.zeros((96, 128), dtype=bool)
    with pytest.raises(ValueError, match="tile"):
        pac_eval(up, up, rf=2, voters=3, n_real=63, block_p=64,
                 interpret=True)
