"""Fused Pallas step megakernel over bit-packed cluster state.

One ``pallas_call`` per Monte Carlo step evaluates a 2-D
(block_t x block_p) grid of (trials, partitions) tiles directly on packed
uint32 words — where the unfused path launches separate PAC/downtime,
roster-gather and node-count kernels over boolean (R, n) tiles, this
kernel reads each packed word once and emits every per-step output in a
single pass:

  * PAC (SimpleMajority) / majority-baseline / quorum-log predicates as
    mask-AND + SWAR-popcount over the word planes (kernels/bitpack.py —
    the same functions the numpy and jnp backends run, so bit-identity
    is by construction, not by parallel implementation);
  * the reconfiguring baseline's roster membership via one-hot word
    select + shift (no gather);
  * the acting-leader rank + latest-copy bit via a lowest-set-bit scan;
  * the refreshed cluster-replica words via rf rounds of lowest-set-bit
    extraction;
  * optionally, the per-(trial, node) in-flight rebuild counts for the
    bandwidth-contended rebuild model, accumulated *across the partition
    grid axis* into a (block_t, n_lanes) output block that is revisited
    by every partition tile of the same trial block (initialized at
    partition-grid index 0, per the standard Pallas accumulation
    pattern) — the reduction that previously cost its own kernel launch
    and an extra HBM round trip.

Array layout: packed state is (B, W, P) uint32 — partitions on the minor
(lane) axis, words on the sublane axis — so a (block_t, W, block_p) tile
is VPU-shaped with block_p a lane multiple, and the packed node axis
never occupies lanes (the boolean kernels pad n to 128 lanes; here five
words replace 256 bool lanes).  Rosters arrive as (B, rf, P) int32 and
recruit/active as (B, P).  Validity masking uses compile-time prefix-mask
constants, so there is no `valid` input tensor at all.

ops.step_eval dispatches here for StepSpec(packed=True) on the pallas
backend; block sizes come from ops.autotune_fused_blocks (2-D fused
autotuner with fused-kernel VMEM accounting).  Interpret mode runs the
same kernel on CPU for the CI smoke rows and the bit-identity matrix.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import bitpack
from .pac_eval import node_count_tile, tile_rule_error


def _check_tiles(B: int, P: int, block_t: int, block_p: int, *,
                 interpret: bool):
    if B % block_t:
        raise ValueError(
            f"block_t={block_t} must tile the trial count B={B} exactly — "
            "pick a candidate from ops.fused_block_candidates")
    if P % block_p:
        raise ValueError(
            f"block_p={block_p} must tile the partition count P={P} "
            "exactly — pick a candidate from ops.fused_block_candidates")
    err = None if interpret else tile_rule_error(
        (B, P), (block_t, block_p), what="fused step")
    if err:
        raise ValueError(err)


def _fused_pac_kernel(upw_ref, fullw_ref, lark_ref, maj_ref, crepsw_ref, *,
                      rf: int, voters: int, n_real: int, W: int):
    upw = upw_ref[...]                         # (bt, W, bp) uint32
    fullw = fullw_ref[...]
    u = [upw[:, k, :] for k in range(W)]
    f = [fullw[:, k, :] for k in range(W)]
    lark, maj, creps = bitpack.pac_eval_packed(
        u, f, rf=rf, voters=voters, n_real=n_real, xp=jnp)
    lark_ref[...] = lark
    maj_ref[...] = maj
    crepsw_ref[...] = jnp.stack(creps, axis=1)


def fused_pac_eval(upw, fullw, *, rf: int, voters: int, n_real: int,
                   block_t: int, block_p: int, interpret: bool = False):
    """upw/fullw: (B, W, P) uint32 packed rank-space state.  Returns
    (lark (B, P) bool, maj (B, P) bool, crepsw (B, W, P) uint32) — the
    packed image of kernels/pac_eval.pac_eval, bit for bit."""
    B, W, P = upw.shape
    block_t = min(block_t, B)
    block_p = min(block_p, P)
    _check_tiles(B, P, block_t, block_p, interpret=interpret)
    kernel = functools.partial(_fused_pac_kernel, rf=rf, voters=voters,
                               n_real=n_real, W=W)
    word_spec = pl.BlockSpec((block_t, W, block_p), lambda i, j: (i, 0, j))
    row_spec = pl.BlockSpec((block_t, block_p), lambda i, j: (i, j))
    return pl.pallas_call(
        kernel,
        grid=(B // block_t, P // block_p),
        in_specs=[word_spec, word_spec],
        out_specs=[row_spec, row_spec, word_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, P), jnp.bool_),
            jax.ShapeDtypeStruct((B, P), jnp.bool_),
            jax.ShapeDtypeStruct((B, W, P), jnp.uint32),
        ],
        interpret=interpret,
    )(upw, fullw)


def _fused_downtime_kernel(refs, *, rf: int, n_real: int, W: int,
                           with_roster: bool, with_counts: bool,
                           with_repmask: bool, with_rleader: bool):
    it = iter(refs)
    upw_ref, fullw_ref = next(it), next(it)
    roster_ref = next(it) if with_roster else None
    rec_ref, act_ref = (next(it), next(it)) if with_counts else (None, None)
    lark_ref, qmaj_ref, ldr_ref, lfull_ref, nrep_ref = \
        (next(it) for _ in range(5))
    repmask_ref = next(it) if with_repmask else None
    rleader_ref = next(it) if with_rleader else None
    crepsw_ref = next(it)
    cnt_ref = next(it) if with_counts else None

    upw = upw_ref[...]                         # (bt, W, bp) uint32
    fullw = fullw_ref[...]
    u = [upw[:, k, :] for k in range(W)]
    f = [fullw[:, k, :] for k in range(W)]
    roster = None
    if with_roster:
        rost = roster_ref[...]                 # (bt, rf, bp) int32
        roster = [rost[:, j, :] for j in range(rf)]
    outs = bitpack.downtime_eval_packed(
        u, f, rf=rf, n_real=n_real, roster=roster,
        want_repmask=with_repmask, want_rleader=with_rleader, xp=jnp)
    lark, qmaj, leader, lfull, nrep = outs[:5]
    creps = outs[-1]
    lark_ref[...] = lark
    qmaj_ref[...] = qmaj
    ldr_ref[...] = leader
    lfull_ref[...] = lfull
    nrep_ref[...] = nrep
    k = 5
    if with_repmask:
        repmask_ref[...] = outs[k]
        k += 1
    if with_rleader:
        rleader_ref[...] = outs[k]
    crepsw_ref[...] = jnp.stack(creps, axis=1)

    if with_counts:
        # counts accumulate across the (innermost, sequential) partition
        # grid axis: initialize at the first partition tile of each trial
        # block, then add this tile's one-hot contribution
        j_id = pl.program_id(1)

        @pl.when(j_id == 0)
        def _init():
            cnt_ref[...] = jnp.zeros(cnt_ref.shape, dtype=jnp.int32)

        cnt_ref[...] = cnt_ref[...] + node_count_tile(
            rec_ref[...], act_ref[...], cnt_ref.shape[1])


def fused_downtime_eval(upw, fullw, *, rf: int, n_real: int, block_t: int,
                        block_p: int, interpret: bool = False, roster=None,
                        recruit=None, active=None,
                        want_repmask: bool = False,
                        want_rleader: bool = False):
    """upw/fullw: (B, W, P) uint32.  Returns (lark, qmaj, leader,
    leader_full, nrep (all (B, P)), *extras, crepsw (B, W, P)[, counts
    (B, n_lanes)]) — the packed image of kernels/pac_eval.downtime_eval
    (+ node_count when recruit/active are given), in one pallas_call.

    roster (B, rf, P) int32, optional: the reconfiguring baseline's
    carried replica-set ranks, words-on-sublanes like the state.
    recruit (B, P) int32 + active (B, P) bool, optional (together): also
    emit the per-(trial, node) in-flight rebuild counts, accumulated
    across partition tiles; counts columns >= n_real are padding for the
    caller to slice (ops.step_eval does).
    want_repmask / want_rleader: protocol-zoo int32 (B, P) extras between
    nrep and crepsw (Hermes membership bitmask; Spinnaker electable
    roster leader — requires roster)."""
    if want_rleader and roster is None:
        raise ValueError("rleader needs a roster (it elects among "
                         "roster members)")
    B, W, P = upw.shape
    block_t = min(block_t, B)
    block_p = min(block_p, P)
    _check_tiles(B, P, block_t, block_p, interpret=interpret)
    with_roster = roster is not None
    with_counts = recruit is not None
    if with_counts and active is None:
        raise ValueError("recruit and active must be passed together")
    n_lanes = n_real + (-n_real % 128)

    word_spec = pl.BlockSpec((block_t, W, block_p), lambda i, j: (i, 0, j))
    row_spec = pl.BlockSpec((block_t, block_p), lambda i, j: (i, j))
    in_specs = [word_spec, word_spec]
    operands = [upw, fullw]
    if with_roster:
        in_specs.append(pl.BlockSpec((block_t, rf, block_p),
                                     lambda i, j: (i, 0, j)))
        operands.append(roster.astype(jnp.int32))
    if with_counts:
        in_specs += [row_spec, row_spec]
        operands += [recruit.astype(jnp.int32), active.astype(jnp.int32)]
    n_extra = int(want_repmask) + int(want_rleader)
    out_specs = [row_spec] * (5 + n_extra) + [word_spec]
    out_shape = [
        jax.ShapeDtypeStruct((B, P), jnp.bool_),
        jax.ShapeDtypeStruct((B, P), jnp.bool_),
        jax.ShapeDtypeStruct((B, P), jnp.int32),
        jax.ShapeDtypeStruct((B, P), jnp.bool_),
        jax.ShapeDtypeStruct((B, P), jnp.int32),
    ] + [jax.ShapeDtypeStruct((B, P), jnp.int32)] * n_extra + [
        jax.ShapeDtypeStruct((B, W, P), jnp.uint32),
    ]
    if with_counts:
        # revisited across the partition grid axis (index map pins j -> 0)
        out_specs.append(pl.BlockSpec((block_t, n_lanes),
                                      lambda i, j: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, n_lanes), jnp.int32))

    kernel = functools.partial(
        _fused_downtime_kernel, rf=rf, n_real=n_real, W=W,
        with_roster=with_roster, with_counts=with_counts,
        with_repmask=want_repmask, with_rleader=want_rleader)

    def kernel_splat(*refs):
        kernel(refs)

    return pl.pallas_call(
        kernel_splat,
        grid=(B // block_t, P // block_p),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
