"""Pallas TPU PAC-evaluation kernel — the §5.1 availability hot loop.

Evaluates, for a block of partitions at a time (succession lists resident in
VMEM), LARK availability (SimpleMajority et al.), the majority baseline, and
the refreshed full-holder masks.  Pure VPU integer/boolean work on
(block_p, n) tiles; the node axis is padded to a lane multiple by ops.py.

Inputs are in succession-rank space: up_succ[p, i] = up[succ[p, i]],
full_succ likewise — the same layout the vectorized numpy engine uses, so
the Monte Carlo can call either implementation interchangeably.

Every kernel here compiles for the TPU (Mosaic) as well as running in
interpret mode: per-row outputs are (rows, 1) columns written straight
from keepdims lane reductions (no in-body reshape), lane prefix counts
and one-hot node counts are MXU products of 0/1 operands (exact in
float32, no cumsum or dynamic lane slice), and tiles follow the (8, 128)
block rule that tile_rule_error states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import latency as _lat


def tile_rule_error(shape, block, *, what: str):
    """None if `block` is a legal Mosaic block of an array of `shape`,
    else the reason: each of the last two block dims must be a multiple
    of (8, 128) respectively or the whole axis (leading dims are free)."""
    for dim, b, align in ((shape[-2], block[-2], 8),
                          (shape[-1], block[-1], 128)):
        if b != dim and b % align:
            return (f"{what}: block {tuple(block)} of array "
                    f"{tuple(shape)} breaks the TPU tile rule (each of the "
                    "last two block dims must be a multiple of (8, 128) "
                    "or the whole axis)")
    return None


def _check_compiled_block(shape, block, *, interpret: bool, what: str):
    """Raise on a block the TPU compiler would refuse.  Interpret mode
    has no tile rule, so the CPU tests keep their freedom of tiling."""
    err = None if interpret else tile_rule_error(shape, block, what=what)
    if err:
        raise ValueError(err)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _rowsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _load_tiles(up_ref, full_ref, n_real: int):
    """(bp, L) int32 0/1 up / full tiles with lanes >= n_real zeroed, plus
    the lane iota (the validity mask is a compile-time compare, not an
    input tensor)."""
    lanes = _iota(up_ref.shape, 1)
    valid = (lanes < n_real).astype(jnp.int32)
    up = up_ref[...].astype(jnp.int32) * valid
    full = full_ref[...].astype(jnp.int32) * valid
    return up, full, lanes


def _lark(up, full, lanes, *, rf: int, n_real: int):
    """(bp, 1) SimpleMajority PAC: majority up, a roster replica up, and
    an up node holding the latest copy."""
    majority = 2 * _rowsum(up) > n_real
    any_roster = _rowsum(jnp.where(lanes < rf, up, 0)) > 0
    full_up = _rowsum(full * up) > 0
    return majority & any_roster & full_up


def _first_up(up, rf: int):
    """up & (inclusive lane prefix count <= rf): the first rf up lanes.
    The prefix count is up @ upper-triangular ones — 0/1 operands and
    sums <= L, so the float32 product is exact on any backend."""
    L = up.shape[1]
    tri = (_iota((L, L), 0) <= _iota((L, L), 1)).astype(jnp.float32)
    rank = jnp.dot(up.astype(jnp.float32), tri,
                   preferred_element_type=jnp.float32)
    return (up > 0) & (rank <= rf)


def _pac_kernel(up_ref, full_ref, lark_ref, maj_ref, creps_ref, *,
                rf: int, voters: int, n_real: int):
    up, full, lanes = _load_tiles(up_ref, full_ref, n_real)
    lark_ref[...] = _lark(up, full, lanes, rf=rf, n_real=n_real)
    voter_up = _rowsum(jnp.where(lanes < voters, up, 0))
    maj_ref[...] = 2 * voter_up > voters
    creps_ref[...] = _first_up(up, rf)


def _row_blocks(P: int, n_pad: int, block_p: int, *, interpret: bool,
                what: str):
    block_p = min(block_p, P)
    if P % block_p:
        raise ValueError(
            f"block_p={block_p} must tile the row count P={P} exactly — "
            "pick a candidate from ops.block_p_candidates(P, n_pad)")
    _check_compiled_block((P, n_pad), (block_p, n_pad),
                          interpret=interpret, what=what)
    return block_p


def pac_eval(up_succ, full_succ, *, rf: int, voters: int, n_real: int,
             block_p: int = 256, interpret: bool = False):
    """up_succ/full_succ: (P, n_pad) bool.  Returns (lark, maj, creps)."""
    P, n_pad = up_succ.shape
    block_p = _row_blocks(P, n_pad, block_p, interpret=interpret,
                          what="pac_eval")
    kernel = functools.partial(_pac_kernel, rf=rf, voters=voters,
                               n_real=n_real)
    col_spec = pl.BlockSpec((block_p, 1), lambda i: (i, 0))
    tile_spec = pl.BlockSpec((block_p, n_pad), lambda i: (i, 0))
    lark, maj, creps = pl.pallas_call(
        kernel,
        grid=(P // block_p,),
        in_specs=[tile_spec, tile_spec],
        out_specs=[col_spec, col_spec, tile_spec],
        out_shape=[
            jax.ShapeDtypeStruct((P, 1), jnp.bool_),
            jax.ShapeDtypeStruct((P, 1), jnp.bool_),
            jax.ShapeDtypeStruct((P, n_pad), jnp.bool_),
        ],
        interpret=interpret,
    )(up_succ, full_succ)
    return lark[:, 0], maj[:, 0], creps


def _downtime_kernel(*refs, rf: int, n_real: int, with_roster: bool,
                     want_repmask: bool, want_rleader: bool):
    """PAC + quorum-log replica set + acting leader for one (bp, n) block —
    the §6 downtime engine's per-step evaluation (downtime_eval_rank_np is
    the contract; everything is integer/boolean VPU work plus exact 0/1
    MXU products, so outputs are bit-identical to the numpy and jnp
    implementations).

    with_roster: the §6 reconfiguring quorum-log baseline — the replica
    set is the given per-row roster of succession ranks (a (bp, rf_pad)
    tile; columns >= rf are lane padding, never read) rather than the
    implicit first rf lanes.  The gather up[roster[j]] is a one-hot
    compare-and-sum per roster slot (rf is small and static).
    want_repmask / want_rleader add the protocol-zoo extras (Hermes
    first-rf membership bitmask; Spinnaker electable leader = minimum up
    roster rank, n_real sentinel) as int32 columns between nrep and
    creps."""
    up_ref, full_ref = refs[:2]
    k = 2
    roster_ref = None
    if with_roster:
        roster_ref = refs[k]
        k += 1
    lark_ref, qmaj_ref, leader_ref, lfull_ref, nrep_ref = refs[k:k + 5]
    k += 5
    repmask_ref = rleader_ref = None
    if want_repmask:
        repmask_ref = refs[k]
        k += 1
    if want_rleader:
        rleader_ref = refs[k]
    creps_ref = refs[-1]

    up, full, lanes = _load_tiles(up_ref, full_ref, n_real)
    lark_ref[...] = _lark(up, full, lanes, rf=rf, n_real=n_real)

    if roster_ref is None:
        nrep = _rowsum(jnp.where(lanes < rf, up, 0))
    else:
        roster = roster_ref[...]
        rlanes = _iota(roster.shape, 1)
        nrep = jnp.zeros((up.shape[0], 1), dtype=jnp.int32)
        rlead = jnp.full((up.shape[0], 1), n_real, dtype=jnp.int32)
        for j in range(rf):
            member = _rowsum(jnp.where(rlanes == j, roster, 0))   # (bp, 1)
            mem_up = _rowsum(jnp.where(lanes == member, up, 0))
            nrep = nrep + mem_up
            if want_rleader:
                rlead = jnp.minimum(rlead, jnp.where(mem_up > 0, member,
                                                     n_real))
        if want_rleader:
            rleader_ref[...] = rlead
    qmaj_ref[...] = 2 * nrep > rf
    nrep_ref[...] = nrep

    leader = jnp.min(jnp.where(up > 0, lanes, up.shape[1]), axis=1,
                     keepdims=True)
    leader = jnp.minimum(leader, n_real)
    leader_ref[...] = leader
    lfull_ref[...] = _rowsum(jnp.where(lanes == leader, full * up, 0)) > 0

    if want_repmask:
        # the shift is clamped so the dead branch of the where never
        # shifts past the int32 width (rf <= 30 by StepSpec validation)
        shift = jnp.minimum(lanes, rf)
        repmask_ref[...] = _rowsum(jnp.where(lanes < rf, up << shift, 0))

    creps_ref[...] = _first_up(up, rf)


def node_count_tile(rec, act, n_lanes: int):
    """(bt, bp) int32 recruit ids + (bt, bp) int32 0/1 active mask ->
    (bt, n_lanes) int32 per-node counts over this tile's columns:
    cnt[b, node] = #{p : act[b, p] and rec[b, p] == node}.

    One MXU product per trial row, act @ onehot(rec[b])^T — 0/1 operands
    and sums <= bp, so the float32 result is exact and bit-identical to
    the numpy/jnp scatter-add.  No lane is sliced dynamically.  Ids
    outside [0, n_lanes) match no lane; ids in [n_real, n_lanes) land in
    padding columns the wrapper slices off."""
    bt, bp = rec.shape
    nodes = _iota((n_lanes, bp), 0)
    rows = _iota((bt, n_lanes), 0)
    actf = act.astype(jnp.float32)
    cnt = jnp.zeros((bt, n_lanes), dtype=jnp.int32)
    for b in range(bt):
        onehot = (nodes == rec[b:b + 1, :]).astype(jnp.float32)
        per = jax.lax.dot_general(
            actf, onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bt, n_lanes)
        cnt = jnp.where(rows == b, per.astype(jnp.int32), cnt)
    return cnt


def _node_count_kernel(rec_ref, act_ref, cnt_ref):
    """Per-node in-flight rebuild counts — the §6 bandwidth-contended
    rebuild reduction — accumulated across the (innermost, sequential)
    partition grid axis into a (block_b, n_lanes) block that every
    partition tile of the same trial block revisits."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        cnt_ref[...] = jnp.zeros(cnt_ref.shape, dtype=jnp.int32)

    cnt_ref[...] = cnt_ref[...] + node_count_tile(
        rec_ref[...], act_ref[...], cnt_ref.shape[1])


def legal_blocks(dim: int, align: int, max_block: int) -> list:
    """Block sizes the TPU tile rule accepts along a minor axis of length
    `dim` that a tiling may pick: power-of-two multiples of `align`
    (8 sublanes / 128 lanes) dividing dim, up to max_block, plus the
    whole axis when it is no larger or nothing else divides."""
    out = []
    b = align
    while b <= min(dim, max_block):
        if dim % b == 0:
            out.append(b)
        b *= 2
    if dim not in out and (dim <= max_block or not out):
        out.append(dim)
    return out


def lane_block(P: int, max_block: int = 512) -> int:
    """The largest legal lane block <= max_block (or all P)."""
    return max(legal_blocks(P, 128, max_block))


def trial_block(B: int) -> int:
    """8 trials per sublane block when B allows it, else the whole axis
    (both legal as the second-minor block dim)."""
    return 8 if B % 8 == 0 else B


def node_count(recruit, active, *, n_real: int, interpret: bool = False,
               block_b: int = 0):
    """recruit (B, P) int32 node ids, active (B, P) bool ->
    (B, n_lanes) int32 per-node counts (columns >= n_real are padding the
    caller slices off; see ops.rebuild_node_counts)."""
    B, P = recruit.shape
    n_lanes = n_real + (-n_real % 128)
    ppad = -P % 128                    # partition axis to a lane multiple
    if ppad:
        # pad columns carry an id no lane matches and are inactive anyway
        recruit = jnp.pad(recruit, ((0, 0), (0, ppad)),
                          constant_values=n_lanes)
        active = jnp.pad(active, ((0, 0), (0, ppad)))
    Pp = P + ppad
    block_b = block_b or trial_block(B)
    if B % block_b:
        raise ValueError(f"block_b={block_b} must tile the trial count "
                         f"B={B} exactly")
    _check_compiled_block((B, Pp), (block_b, 128), interpret=interpret,
                          what="node_count")
    bp = lane_block(Pp)
    row_spec = pl.BlockSpec((block_b, bp), lambda i, j: (i, j))
    return pl.pallas_call(
        _node_count_kernel,
        grid=(B // block_b, Pp // bp),
        in_specs=[row_spec, row_spec],
        out_specs=pl.BlockSpec((block_b, n_lanes), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n_lanes), jnp.int32),
        interpret=interpret,
    )(recruit.astype(jnp.int32), active.astype(jnp.int32))


def _latency_kernel(dirty_ref, decay_ref, kf_ref, avail_ref, qok_ref,
                    rem_ref, dt_ref, lamw_ref, ndirty_ref, dup_ref,
                    qhist_ref, qslo_ref, qsum_ref, *, nbins: int,
                    slo_ticks: int):
    """Client-latency interval charges for one (block_t, ., block_p) tile
    — the §6 per-key request layer's post-step op.  Buckets ride the
    sublanes and partitions the lanes; the (block_t, 1, block_p) row
    operands broadcast over the bucket axis, so the body never reshapes.
    Purely elementwise float32/int32 work via the shared
    kernels/latency.py math (the decay factors arrive precomputed), so
    the outputs are bit-identical to the numpy/jnp reference — see that
    module's bit-identity contract."""
    nd, dup = _lat.dirty_step(dirty_ref[...], decay_ref[...],
                              avail_ref[...] != 0, kf_ref[...], jnp)
    ndirty_ref[...] = nd
    dup_ref[...] = dup
    lanes = _iota(qhist_ref.shape, 1)             # bucket index
    qh, qs, qq = _lat.quorum_step(rem_ref[...], dt_ref[...],
                                  qok_ref[...] != 0, lamw_ref[...], lanes,
                                  nbins=nbins, slo_ticks=slo_ticks, xp=jnp)
    qhist_ref[...] = qh
    qslo_ref[...] = qs
    qsum_ref[...] = qq


def latency_charge(dirty, decay, avail, qok, rem, dt, lamw, kf, *,
                   nbins: int, slo_ticks: int, interpret: bool = False):
    """Bucket-major layout: dirty/decay (B, NB, P) f32; avail/qok (B, P)
    bool; rem (B, P) i32; dt (B,) i32; lamw (P,) f32; kf (NB,) f32 ->
    (new_dirty (B, NB, P), dup (B, NB, P), qhist (B, nbins, P),
    qslo (B, P), qsum (B, P)).  No reduction crosses a partition, so the
    tiling never changes a result."""
    B, NB, P = dirty.shape
    bt = math.gcd(B, 8)                # leading dim: any block is legal
    bp = lane_block(P)

    def rows(x, dtype):                                # (B, P) -> (B, 1, P)
        return jnp.broadcast_to(x, (B, P)).astype(dtype)[:, None, :]

    kernel = functools.partial(_latency_kernel, nbins=nbins,
                               slo_ticks=slo_ticks)
    nb_spec = pl.BlockSpec((bt, NB, bp), lambda i, j: (i, 0, j))
    row_spec = pl.BlockSpec((bt, 1, bp), lambda i, j: (i, 0, j))
    nd, dup, qh, qs, qq = pl.pallas_call(
        kernel,
        grid=(B // bt, P // bp),
        in_specs=[
            nb_spec, nb_spec,
            pl.BlockSpec((1, NB, bp), lambda i, j: (0, 0, j)),
            row_spec, row_spec, row_spec, row_spec,
            pl.BlockSpec((1, 1, bp), lambda i, j: (0, 0, j)),
        ],
        out_specs=[
            nb_spec, nb_spec,
            pl.BlockSpec((bt, nbins, bp), lambda i, j: (i, 0, j)),
            row_spec, row_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, NB, P), jnp.float32),
            jax.ShapeDtypeStruct((B, NB, P), jnp.float32),
            jax.ShapeDtypeStruct((B, nbins, P), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, P), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, P), jnp.float32),
        ],
        interpret=interpret,
    )(dirty, decay,
      jnp.broadcast_to(kf.astype(jnp.float32)[None, :, None], (1, NB, P)),
      rows(avail, jnp.int32), rows(qok, jnp.int32), rows(rem, jnp.int32),
      rows(dt[:, None], jnp.int32),
      lamw.astype(jnp.float32)[None, None, :])
    return nd, dup, qh, qs[:, 0, :], qq[:, 0, :]


def downtime_eval(up_succ, full_succ, *, rf: int, n_real: int,
                  block_p: int = 256, interpret: bool = False,
                  roster=None, want_repmask: bool = False,
                  want_rleader: bool = False):
    """up_succ/full_succ: (P, n_pad) bool.  Returns (lark, qmaj, leader,
    leader_full, nrep, *extras, creps) — see pac_np.downtime_eval_rank_np.

    roster (P, rf_pad) int32, optional: per-row replica-set ranks for the
    reconfiguring baseline (columns >= rf are lane padding).  qmaj/nrep
    are then evaluated over those ranks instead of the first rf lanes.

    want_repmask / want_rleader add the protocol-zoo int32 row outputs
    (Hermes membership bitmask; Spinnaker electable roster leader —
    requires roster) between nrep and creps, matching the numpy/jnp
    contracts bit-for-bit."""
    if want_rleader and roster is None:
        raise ValueError("rleader needs a roster (it elects among "
                         "roster members)")
    P, n_pad = up_succ.shape
    block_p = _row_blocks(P, n_pad, block_p, interpret=interpret,
                          what="downtime_eval")
    col_spec = pl.BlockSpec((block_p, 1), lambda i: (i, 0))
    tile_spec = pl.BlockSpec((block_p, n_pad), lambda i: (i, 0))
    in_specs = [tile_spec, tile_spec]
    operands = [up_succ, full_succ]
    if roster is not None:
        in_specs.append(pl.BlockSpec((block_p, roster.shape[1]),
                                     lambda i: (i, 0)))
        operands.append(roster)
    kernel = functools.partial(_downtime_kernel, rf=rf, n_real=n_real,
                               with_roster=roster is not None,
                               want_repmask=want_repmask,
                               want_rleader=want_rleader)
    n_extra = int(want_repmask) + int(want_rleader)
    col = functools.partial(jax.ShapeDtypeStruct, (P, 1))
    outs = pl.pallas_call(
        kernel,
        grid=(P // block_p,),
        in_specs=in_specs,
        out_specs=[col_spec] * (5 + n_extra) + [tile_spec],
        out_shape=[col(jnp.bool_), col(jnp.bool_), col(jnp.int32),
                   col(jnp.bool_), col(jnp.int32)]
        + [col(jnp.int32)] * n_extra
        + [jax.ShapeDtypeStruct((P, n_pad), jnp.bool_)],
        interpret=interpret,
    )(*operands)
    return tuple(o[:, 0] for o in outs[:-1]) + (outs[-1],)
