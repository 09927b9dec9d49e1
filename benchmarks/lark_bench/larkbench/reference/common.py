"""Pieces every plain reference shares: the counter-hash RNG, the
succession lists, geometric gap draws and the node up/down advance.

Written from the semantics the engines document (paper §5.1 failure
model; ARCHITECTURE invariants 1-3: every variate is a pure function of
seed, step and global (trial, node) lane), with no import of the
program.  Arrays are plain booleans in (trials, partitions, nodes)
rank space: column j of partition p is the node of succession rank j.
"""
from __future__ import annotations

import copy
import math

import numpy as np

GEO_SALT = 0x9E3779B9
PAIR_SALT = 0x85EBCA6B
SIZE_SALT = 0x94D049BB
SEED_SALT = 0x6A09E667


def mix32(x, xp):
    """lowbias32-style avalanche on uint32 arrays (wrapping arithmetic)."""
    x = x ^ (x >> 16)
    x = x * xp.uint32(0x21F0AAAD)
    x = x ^ (x >> 15)
    x = x * xp.uint32(0xD35A2D97)
    x = x ^ (x >> 15)
    return x


def seed_mix(seed: int) -> np.ndarray:
    """(1,) uint32 hash of the low 32 bits of the seed."""
    return mix32(np.asarray([(seed & 0xFFFFFFFF) ^ SEED_SALT],
                            dtype=np.uint32), np)


def uniforms(smix, step, salt: int, lane0, n: int, xp):
    """(len(lane0), n) float32 uniforms in [0, 1): one 24-bit draw per
    (step, global lane), where lane0[b] is trial b's first global lane."""
    step = xp.reshape(xp.asarray(step), (1,)).astype(xp.uint32)
    key = mix32(step ^ smix ^ xp.uint32(salt), xp)
    lanes = (lane0[:, None] + xp.arange(n, dtype=xp.uint32)[None, :]) \
        * xp.uint32(0x9E3779B9)
    h = mix32(mix32(lanes ^ key, xp) ^ smix, xp)
    return (h >> 8).astype(xp.float32) * xp.float32(1.0 / (1 << 24))


def geometric_cdf(p: float, cap: int) -> np.ndarray:
    """float32 CDF 1-(1-p)^k, k = 1.., of Geom(p) on {1, 2, ...}, long
    enough for every 24-bit uniform (or `cap` entries, past the horizon)."""
    k_max = min(int(math.ceil(math.log(2.0 ** -25) / math.log1p(-p))) + 2,
                cap)
    k = np.arange(1, k_max + 1, dtype=np.float64)
    return (-np.expm1(k * math.log1p(-p))).astype(np.float32)


def geometric(u, cdf, xp):
    """Inverse-CDF draw: the number of CDF entries <= u, plus one."""
    return (xp.sum(cdf <= u[..., None], axis=-1) + 1).astype(xp.int32)


def succession(partitions: int, n: int, seed: int) -> np.ndarray:
    """(P, n) int32 node ids in rendezvous order per partition: nodes
    sorted (stably) by a splitmix64 hash of (partition, node, seed)."""
    node = np.arange(n, dtype=np.uint64)
    part = np.arange(partitions, dtype=np.uint64)[:, None]
    x = (part << np.uint64(32)) ^ node[None, :] \
        ^ np.uint64((seed * 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    x = x ^ (x >> np.uint64(31))
    return np.argsort(x, axis=1, kind="stable").astype(np.int32)


def pair_partner(n: int) -> np.ndarray:
    """Node 2i's rack partner is 2i+1 and back; an odd last node has none."""
    perm = np.arange(n)
    perm[:n - n % 2] ^= 1
    return perm


class Cluster:
    """Per-run constants and the node advance of one deployment."""

    def __init__(self, *, n: int, partitions: int, p: float, downtime: int,
                 seed: int, horizon: int, restart_period: int = 0,
                 wave_width: int = 1, pair_fail_prob: float = 0.0):
        import jax.numpy as jnp
        self.n, self.P, self.horizon = n, partitions, horizon
        self.restart_period, self.wave_width = restart_period, wave_width
        self.pair_fail_prob = pair_fail_prob
        self.smix = jnp.asarray(seed_mix(seed))
        self.cdf = jnp.asarray(geometric_cdf(p, horizon + downtime + 2))
        self.down_ticks = jnp.full((n,), downtime, dtype=jnp.int32)
        self.succ_np = succession(partitions, n, seed)
        self.succ = jnp.asarray(self.succ_np)
        self.partner = pair_partner(n)

    def arrays(self) -> dict:
        """The seed's constants, passed to a jitted chunk as arguments so
        that one compiled program serves every seed."""
        return {"smix": self.smix, "cdf": self.cdf, "succ": self.succ}

    def bind(self, arrays: dict) -> "Cluster":
        """This cluster with its constants taken from `arrays`."""
        c = copy.copy(self)
        c.smix, c.cdf, c.succ = arrays["smix"], arrays["cdf"], arrays["succ"]
        return c

    def initial(self, trials):
        """(lane0, up, next event tick, next restart tick, restart index)
        for the given global trial indices: every node up, first failures
        at gaps drawn at step 0."""
        import jax.numpy as jnp
        S = len(trials)
        lane0 = jnp.asarray(np.asarray(trials, dtype=np.uint32)
                            * np.uint32(self.n))
        up = jnp.ones((S, self.n), dtype=bool)
        ev = geometric(uniforms(self.smix, 0, GEO_SALT, lane0, self.n, jnp),
                       self.cdf, jnp)
        rr_t = jnp.full((S,), self.restart_period or self.horizon + 1,
                        dtype=jnp.int32)
        return lane0, up, ev, rr_t, jnp.zeros((S,), dtype=jnp.int32)

    def advance(self, now, up, ev, rr_t, rr_idx, lane0, s):
        """Jump every trial to its next event (node failure or recovery,
        or the next rolling restart) and apply it.  Returns (t, dt as
        float32, up, ev, rr_t, rr_idx); a trial past the horizon stays
        there with nothing applied."""
        import jax.numpy as jnp
        n = self.n
        t_next = jnp.min(ev, axis=1)
        if self.restart_period:
            t_next = jnp.minimum(t_next, rr_t)
        live = t_next < self.horizon
        t = jnp.minimum(t_next, self.horizon)
        dt = (t - now).astype(jnp.float32)
        due = (ev == t_next[:, None]) & live[:, None]
        fails = due & up
        recovers = due & ~up
        if self.restart_period:
            restart = live & (rr_t == t_next)
            wave = ((jnp.arange(n, dtype=jnp.int32)[None, :]
                     - rr_idx[:, None]) % n) < self.wave_width
            fails = fails | (wave & up & restart[:, None])
            rr_idx = jnp.where(restart, (rr_idx + self.wave_width) % n,
                               rr_idx)
            rr_t = jnp.where(restart, rr_t + self.restart_period, rr_t)
        if self.pair_fail_prob > 0.0:
            coin = uniforms(self.smix, s, PAIR_SALT, lane0, n, jnp)
            dragged = fails[:, self.partner] & up & ~fails & ~recovers \
                & (coin < self.pair_fail_prob)
            fails = fails | dragged
        up = (up & ~fails) | recovers
        gap = geometric(uniforms(self.smix, s, GEO_SALT, lane0, n, jnp),
                        self.cdf, jnp)
        ev = jnp.where(fails, t[:, None] + self.down_ticks[None, :],
                       jnp.where(recovers, t[:, None] + gap, ev))
        return t, dt, up, ev, rr_t, rr_idx

    def waves(self, rr_t) -> np.ndarray:
        """Rolling-restart waves fired per trial, from the final next-wave
        tick (the first wave is due at `restart_period`, each one after
        another period); 0 without a rolling restart."""
        rr_t = np.asarray(rr_t, dtype=np.int64)
        if not self.restart_period:
            return np.zeros(rr_t.shape, dtype=np.int64)
        return (rr_t - self.restart_period) // self.restart_period

    def rank_space(self, up):
        """(S, n) node mask -> (S, P, n) mask in succession-rank order."""
        return up[:, self.succ]


def pac(up_r, full_r, *, n: int, rf: int, voters: int, acks: int):
    """PAC SimpleMajority over rank-space masks: a partition is available
    iff a majority of the whole cluster is up, one of its rf roster
    replicas is up, and an up node holds the latest copy.  Also the
    majority of the first `voters` ranks (the quorum baseline) and the
    cluster replicas that take a commit: the first `acks` up nodes in
    succession order (acks = rf is the configured guarantee)."""
    import jax.numpy as jnp
    n_up = jnp.sum(up_r[:, :1, :], axis=2)          # same for every row
    lark = (2 * n_up > n) & jnp.any(up_r[:, :, :rf], axis=2) \
        & jnp.any(full_r & up_r, axis=2)
    maj = 2 * jnp.sum(up_r[:, :, :voters], axis=2) > voters
    creps = up_r & (jnp.cumsum(up_r.astype(jnp.int32), axis=2) <= acks)
    return lark, maj, creps
