"""The Monte Carlo Pallas kernels compile for a TPU v5e.

Every kernel that step_eval and client_latency_step can reach on
backend="pallas" — fused (packed) and unpacked alike — is lowered with
interpret=False for one chip of a described v5e:2x2 topology at the
paper's widths (n=155 nodes, P=4096 partitions) and must come out of
the TPU compiler holding a Mosaic kernel (tpu_custom_call).  Nothing
runs: this catches what interpret mode cannot (tile rules, Mosaic
lowering gaps) at no chip time.  The topology is described inside a
fixture, never at import, and the tests skip where it cannot be.

The pure tests below it pin the tile rule itself: both autotuners'
candidate sets and both default tilings obey it, and a compiled call
with an illegal tile raises before it reaches the compiler.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import bitpack, fused_step, pac_eval as pk
from repro.kernels.ops import (_pallas_block_p, block_p_candidates,
                               fused_block_candidates, fused_default_blocks)
from repro.kernels.pac_eval import tile_rule_error

N, P, B, RF = 155, 4096, 8, 2
W = bitpack.n_words(N)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


WORDS = ((B, W, P), jnp.uint32)
ROWS_I32 = ((B, P), jnp.int32)
ROWS_BOOL = ((B, P), jnp.bool_)


@pytest.mark.parametrize("block_t,block_p", [(8, 256), (8, 512)])
def test_fused_pac_eval_compiles(one_chip, block_t, block_p):
    _compile(one_chip, functools.partial(
        fused_step.fused_pac_eval, rf=RF, voters=3, n_real=N,
        block_t=block_t, block_p=block_p, interpret=False), WORDS, WORDS)


def test_fused_downtime_eval_fixed_compiles(one_chip):
    _compile(one_chip, functools.partial(
        fused_step.fused_downtime_eval, rf=RF, n_real=N, block_t=8,
        block_p=256, interpret=False), WORDS, WORDS)


def test_fused_downtime_eval_roster_counts_zoo_compiles(one_chip):
    def fn(u, f, roster, recruit, active):
        return fused_step.fused_downtime_eval(
            u, f, rf=RF, n_real=N, block_t=8, block_p=256,
            interpret=False, roster=roster, recruit=recruit,
            active=active, want_repmask=True, want_rleader=True)
    _compile(one_chip, fn, WORDS, WORDS, ((B, RF, P), jnp.int32),
             ROWS_I32, ROWS_BOOL)


def test_latency_charge_compiles(one_chip):
    NB, nbins = 4, 16

    def fn(dirty, decay, avail, qok, rem, dt, lamw, kf):
        return pk.latency_charge(dirty, decay, avail, qok, rem, dt, lamw,
                                 kf, nbins=nbins, slo_ticks=8,
                                 interpret=False)
    _compile(one_chip, fn, ((B, NB, P), jnp.float32),
             ((B, NB, P), jnp.float32), ROWS_BOOL, ROWS_BOOL, ROWS_I32,
             ((B,), jnp.int32), ((P,), jnp.float32), ((NB,), jnp.float32))


def test_pac_eval_compiles(one_chip):
    tile = ((B * P, 256), jnp.bool_)
    _compile(one_chip, functools.partial(
        pk.pac_eval, rf=RF, voters=3, n_real=N, block_p=256,
        interpret=False), tile, tile)


def test_downtime_eval_with_roster_compiles(one_chip):
    tile = ((B * P, 256), jnp.bool_)

    def fn(up, full, roster):
        return pk.downtime_eval(up, full, rf=RF, n_real=N, block_p=256,
                                interpret=False, roster=roster,
                                want_repmask=True, want_rleader=True)
    _compile(one_chip, fn, tile, tile, ((B * P, 128), jnp.int32))


def test_node_count_compiles(one_chip):
    _compile(one_chip, functools.partial(pk.node_count, n_real=N,
                                         interpret=False),
             ROWS_I32, ROWS_BOOL)


# ---------------------------------------------------------------------------
# the tile rule, without a topology
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,p", [(8, 4096), (64, 4096), (12, 4096),
                                 (4, 64), (2048, 64), (16, 96), (24, 384),
                                 (1000, 1000)])
def test_every_autotune_candidate_obeys_the_tile_rule(b, p):
    """Both tuners' candidate sets and both default tilings satisfy the
    Mosaic rule: each of the last two block dims is a multiple of
    (8, 128) or the whole axis — for the fused (trials, partitions)
    tiles and the unpacked (rows, lanes) tiles alike."""
    for kernel in ("fused_pac", "fused_downtime", "fused_downtime_roster"):
        tiles = fused_block_candidates(b, p, N, rf=RF, kernel=kernel) \
            + (fused_default_blocks(b, p, N, rf=RF, kernel=kernel),)
        for bt, bp in tiles:
            assert b % bt == 0 and p % bp == 0
            assert tile_rule_error((b, p), (bt, bp), what=kernel) is None
    r = b * p
    for bp in block_p_candidates(r, 256) + (_pallas_block_p(r, 256),):
        assert r % bp == 0
        assert tile_rule_error((r, 256), (bp, 256), what="rows") is None


def test_compiled_call_with_an_illegal_tile_raises():
    upw = jnp.zeros((B, W, P), dtype=jnp.uint32)
    with pytest.raises(ValueError, match="tile rule"):
        fused_step.fused_pac_eval(upw, upw, rf=RF, voters=3, n_real=N,
                                  block_t=4, block_p=256, interpret=False)
    up = jnp.zeros((96, 256), dtype=bool)
    with pytest.raises(ValueError, match="tile rule"):
        pk.pac_eval(up, up, rf=RF, voters=3, n_real=N, block_p=4,
                    interpret=False)
