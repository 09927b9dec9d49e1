"""Bit-packed cluster-state layout + packed PAC/downtime evaluation math.

The Monte Carlo engines' dominant state is boolean rank-space tiles —
up/full masks over n nodes per (trial, partition) lane.  This module packs
the node axis into uint32 words (n=155 -> five words per lane) and
re-states every per-step protocol predicate as mask-AND + popcount /
lowest-set-bit arithmetic over those words.  Packing is *layout only*: all
outputs are bit-identical to the boolean implementations in pac_np.py /
ref.py / pac_eval.py — the invariant docs/ARCHITECTURE.md states and
tests/test_bitpack.py pins property-style.

Written once over an ``xp`` array namespace (numpy or jax.numpy) and —
deliberately — over *lists of word planes* rather than a stacked word
axis, so the exact same functions run

  * host-side numpy (backend="numpy" engines),
  * inside jit/lax.scan (backend="jax"),
  * inside the fused Pallas megakernel body (kernels/fused_step.py), where
    each plane is a (block_t, block_p) tile slice and every constant below
    folds into the kernel as an immediate.

Everything is integer/bit math (shifts, ANDs, SWAR popcount, two's-
complement lowest-set-bit), so cross-backend equality is exact, never
approximate.  This module never imports jax: the numpy event engine and
pac_np.py stay jax-import-free.
"""
from __future__ import annotations

WORD_BITS = 32

_M1 = 0x55555555
_M2 = 0x33333333
_M4 = 0x0F0F0F0F
_H01 = 0x01010101


def n_words(n_bits: int) -> int:
    """Words needed to hold n_bits lanes (ceil division)."""
    return -(-n_bits // WORD_BITS)


def popcount32(v, xp):
    """SWAR popcount of a uint32 array -> int32 counts.

    Three masked shift-adds + one multiply-shift — no lookup tables, no
    dtype casts beyond the final int32, safe inside a Pallas kernel body.
    (numpy 2.x has bitwise_count and jax has lax.population_count, but a
    single shared implementation is what keeps all call sites provably
    identical.)  Array arithmetic wraps mod 2^32 silently in both
    namespaces, which is exactly what the final multiply wants.
    """
    v = v - ((v >> xp.uint32(1)) & xp.uint32(_M1))
    v = (v & xp.uint32(_M2)) + ((v >> xp.uint32(2)) & xp.uint32(_M2))
    v = (v + (v >> xp.uint32(4))) & xp.uint32(_M4)
    return ((v * xp.uint32(_H01)) >> xp.uint32(24)).astype(xp.int32)


def prefix_masks(count: int, n_bits: int):
    """Per-word uint32 masks selecting the first `count` of n_bits lanes.

    Returned as a tuple of python ints so they weave into any context —
    numpy, jnp, or a Pallas kernel body — as compile-time constants (the
    packed kernels need no `valid` input tensor, unlike the boolean ones).
    """
    W = n_words(n_bits)
    full, rem = divmod(min(count, n_bits), WORD_BITS)
    masks = [0xFFFFFFFF] * full + [0] * (W - full)
    if full < W and rem:
        masks[full] = (1 << rem) - 1
    return tuple(masks)


def pack_words(bools, xp):
    """(..., n) bool -> (..., W) uint32, bit b of word k = lane 32k+b.

    Lanes beyond n (the top word's padding bits) are zero.  Vectorized —
    one reshape + shift + sum — so the per-step pack in the engines is a
    single fused XLA op under jit.
    """
    n = bools.shape[-1]
    W = n_words(n)
    pad = W * WORD_BITS - n
    b = bools.astype(xp.uint32)
    if pad:
        b = xp.concatenate(
            [b, xp.zeros(b.shape[:-1] + (pad,), dtype=xp.uint32)], axis=-1)
    b = b.reshape(b.shape[:-1] + (W, WORD_BITS))
    shifts = xp.arange(WORD_BITS, dtype=xp.uint32)
    return xp.sum(b << shifts, axis=-1, dtype=xp.uint32)


def unpack_words(words, n_bits: int, xp):
    """(..., W) uint32 -> (..., n_bits) bool — pack_words' exact inverse."""
    shifts = xp.arange(WORD_BITS, dtype=xp.uint32)
    bits = (words[..., None] >> shifts) & xp.uint32(1)
    flat = bits.reshape(bits.shape[:-2] + (-1,))
    return flat[..., :n_bits] != 0


def _mask_planes(planes, masks, xp):
    return [w & xp.uint32(m) for w, m in zip(planes, masks)]


def _popcount_sum(planes, xp):
    total = popcount32(planes[0], xp)
    for w in planes[1:]:
        total = total + popcount32(w, xp)
    return total


def _any_bit(planes, xp):
    acc = planes[0]
    for w in planes[1:]:
        acc = acc | w
    return acc != xp.uint32(0)


def lowest_set_bits(planes, k: int, xp):
    """Keep the k lowest set bits across a word-plane list (lane order).

    This is the packed form of ``up & (cumsum(up) <= rf)`` — the
    cluster-replica mask of the first rf *up* nodes in succession order.
    k rounds of two's-complement lowest-set-bit extraction (lsb =
    v & (~v + 1), clear via v & (v - 1)), each round walking the words in
    order and taking from the first non-empty one.  k and the word count
    are small static ints, so this unrolls to pure elementwise VPU work.
    """
    v = list(planes)
    taken = [xp.zeros_like(w) for w in v]
    for _ in range(k):
        done = None
        for i, w in enumerate(v):
            nz = w != xp.uint32(0)
            pick = nz if done is None else (nz & ~done)
            lsb = w & ((~w) + xp.uint32(1))
            taken[i] = xp.where(pick, taken[i] | lsb, taken[i])
            v[i] = xp.where(pick, w & (w - xp.uint32(1)), w)
            done = nz if done is None else (done | nz)
    return taken


def select_bit(planes, rank, xp):
    """Bit `rank` across a word-plane list -> int32 0/1 per element.

    rank: int32 array (any shape matching the planes).  The word is picked
    by a one-hot compare-sum over the (static, small) word list — no
    gather — then shifted down by rank mod 32.  Out-of-range ranks (>=
    32*W) select no word and return 0, matching how the boolean
    implementations' masked tiles read padding lanes as False.
    """
    widx = rank // WORD_BITS
    word = xp.zeros_like(planes[0])
    for kk, w in enumerate(planes):
        word = xp.where(widx == kk, w, word)
    bit = (rank % WORD_BITS).astype(xp.uint32)
    return ((word >> bit) & xp.uint32(1)).astype(xp.int32)


def pac_eval_packed(up_words, full_words, *, rf: int, voters: int,
                    n_real: int, xp):
    """Packed-word PAC — bit-identical to pac_np.pac_eval_rank_np.

    up_words/full_words: length-W lists of identically-shaped uint32
    arrays (word k, bit b = succession rank 32k+b).  Lanes >= n_real are
    masked by compile-time prefix masks.  Returns (lark, maj,
    creps_words) with lark/maj bool of the plane shape and creps_words a
    length-W list of uint32 planes.
    """
    W = len(up_words)
    n_pad = W * WORD_BITS
    u = _mask_planes(up_words, prefix_masks(n_real, n_pad), xp)
    f = _mask_planes(full_words, prefix_masks(n_real, n_pad), xp)
    n_up = _popcount_sum(u, xp)
    majority = 2 * n_up > n_real
    any_roster = _any_bit(_mask_planes(u, prefix_masks(rf, n_pad), xp), xp)
    full_up = _any_bit([a & b for a, b in zip(u, f)], xp)
    lark = majority & any_roster & full_up
    nv = _popcount_sum(_mask_planes(u, prefix_masks(voters, n_pad), xp), xp)
    maj = 2 * nv > voters
    creps = lowest_set_bits(u, rf, xp)
    return lark, maj, creps


def downtime_eval_packed(up_words, full_words, *, rf: int, n_real: int,
                         roster=None, want_repmask: bool = False,
                         want_rleader: bool = False, xp):
    """Packed-word §6 per-step eval — bit-identical to
    pac_np.downtime_eval_rank_np.

    Same word-plane contract as pac_eval_packed.  roster, optional: a
    length-rf list of int32 rank arrays (plane-shaped) — the
    reconfiguring baseline's carried replica-set ranks; qmaj/nrep are
    then evaluated over those ranks (select_bit per slot) instead of the
    first-rf prefix mask.  Returns (lark, qmaj, leader, leader_full,
    nrep, *extras, creps_words).

    The protocol-zoo extras land between nrep and creps:
      want_repmask  int32 bitmask of the first-rf lanes' up bits — the
                    Hermes membership view; free in the packed layout
                    (the mask is word 0 under the rf prefix mask, rf <=
                    30 < 32 by StepSpec validation).
      want_rleader  int32 minimum up roster rank (n_real sentinel) — the
                    Spinnaker electable leader; requires roster and rides
                    the same select_bit pass as nrep.

    The leader scan folds three boolean-tile reductions into one pass:
    the first non-empty word's lowest set bit gives the leader's rank
    (32k + popcount(lsb - 1)) and, tested against the full word, the
    leader-holds-latest-copy bit — no lane iota, no (.., n) broadcast.
    """
    if want_rleader and roster is None:
        raise ValueError("rleader needs a roster (it elects among "
                         "roster members)")
    W = len(up_words)
    n_pad = W * WORD_BITS
    u = _mask_planes(up_words, prefix_masks(n_real, n_pad), xp)
    f = _mask_planes(full_words, prefix_masks(n_real, n_pad), xp)
    n_up = _popcount_sum(u, xp)
    majority = 2 * n_up > n_real
    any_roster = _any_bit(_mask_planes(u, prefix_masks(rf, n_pad), xp), xp)
    full_up = _any_bit([a & b for a, b in zip(u, f)], xp)
    lark = majority & any_roster & full_up

    rleader = None
    if roster is None:
        nrep = _popcount_sum(
            _mask_planes(u, prefix_masks(rf, n_pad), xp), xp)
    else:
        if want_rleader:
            rleader = xp.full(u[0].shape, n_real, dtype=xp.int32)
        nrep = xp.zeros(u[0].shape, dtype=xp.int32)
        for r in roster:
            bit = select_bit(u, r, xp)
            nrep = nrep + bit
            if want_rleader:
                rleader = xp.minimum(
                    rleader, xp.where(bit > 0, r.astype(xp.int32),
                                      xp.int32(n_real)))
    qmaj = 2 * nrep > rf

    # word 0 seeds the scan (an empty word has lsb 0, so its latest-copy
    # test is already False).  The bool select is spelled as and/or:
    # Mosaic has no lowering for a where() whose operands are bool
    leader = leader_full = done = None
    for k in range(W):
        w = u[k]
        nz = w != xp.uint32(0)
        lsb = w & ((~w) + xp.uint32(1))
        rank = xp.int32(WORD_BITS * k) + popcount32(lsb - xp.uint32(1), xp)
        holds = (f[k] & lsb) != xp.uint32(0)
        if done is None:
            leader = xp.where(nz, rank, xp.int32(n_pad))
            leader_full, done = holds, nz
            continue
        pick = nz & ~done
        leader = xp.where(pick, rank, leader)
        leader_full = (pick & holds) | (leader_full & ~pick)
        done = done | nz
    leader = xp.minimum(leader, xp.int32(n_real))

    extras = ()
    if want_repmask:
        repmask = (u[0] & xp.uint32((1 << rf) - 1)).astype(xp.int32)
        extras = extras + (repmask,)
    if want_rleader:
        extras = extras + (rleader,)

    creps = lowest_set_bits(u, rf, xp)
    return (lark, qmaj, leader, leader_full, nrep) + extras + (creps,)


def packed_state_bytes(B: int, P: int, n_pad: int) -> int:
    """Carried holder-mask bytes at (B, W, P) uint32 vs (B, P, n_pad) bool —
    the memory-capacity half of the megakernel story (ROADMAP's
    million-trial grids per device): n=155 packs 5 words against 155+
    bool bytes, a ~7.8x reduction of the engine's dominant carry."""
    return B * n_words(n_pad) * P * 4
