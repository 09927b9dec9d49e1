"""Plain reference of the availability engine (paper §5.1): LARK's PAC
against a majority quorum, on a sample of trials.

Per step every sampled trial jumps to its next node event; the interval
before it is charged at the interval-start state (unavailable partitions
x interval length, in float32, drained into float64 per chunk, which is
how the engine reports), then both protocols are re-evaluated and the
partitions that stay available refresh their holders to the cluster
replicas that take the commit.
"""
from __future__ import annotations

import numpy as np

from .common import Cluster, pac


def simulate(cell: dict, *, seed: int, trials, chunks: int,
             chunk_steps: int, acks: int = None) -> dict:
    """Run `chunks` chunks of `chunk_steps` steps for the global trial
    indices `trials`.  `acks` (default rf) is how many cluster replicas a
    commit reaches.  Returns per-trial results and per-step trajectories
    as numpy arrays, trials on the last axis of each trajectory."""
    import jax
    import jax.numpy as jnp
    c = cell
    n, P, rf = c["n"], c["partitions"], c["rf"]
    voters = 2 * (rf - 1) + 1
    acks = rf if acks is None else acks
    cl = Cluster(n=n, partitions=P, p=c["p"], downtime=c["downtime"],
                 seed=seed, horizon=c["horizon"], **c["scenario_knobs"])
    S = len(trials)

    lane0, up, ev, rr_t, rr_idx = cl.initial(trials)
    full = jnp.broadcast_to(jnp.arange(n) < rf, (S, P, n))
    lark, maj, creps = pac(cl.rank_space(up), full, n=n, rf=rf,
                           voters=voters, acks=acks)
    full = jnp.where(lark[:, :, None], creps, full)
    zf = jnp.zeros((S,), jnp.float32)
    zi = jnp.zeros((S,), jnp.int32)
    carry = (jnp.zeros((S,), jnp.int32), up, ev, rr_t, rr_idx, full,
             ~lark, ~maj, zf, zf, zi, zi)

    def step(k, carry, s):
        now, up, ev, rr_t, rr_idx, full, lark_dn, maj_dn, lpt, mpt, lev, \
            mev = carry
        t, dt, up, ev, rr_t, rr_idx = k.advance(now, up, ev, rr_t, rr_idx,
                                                k.lane0, s)
        lpt = lpt + jnp.sum(lark_dn, axis=1).astype(jnp.float32) * dt
        mpt = mpt + jnp.sum(maj_dn, axis=1).astype(jnp.float32) * dt
        lark, maj, creps = pac(k.rank_space(up), full, n=n, rf=rf,
                               voters=voters, acks=acks)
        full = jnp.where(lark[:, :, None], creps, full)
        # an outage event is a partition going down this step
        lev = lev + jnp.sum(~lark_dn & ~lark, axis=1).astype(jnp.int32)
        mev = mev + jnp.sum(~maj_dn & ~maj, axis=1).astype(jnp.int32)
        out = (t, jnp.sum(~lark, axis=1).astype(jnp.int32),
               jnp.sum(~maj, axis=1).astype(jnp.int32),
               jnp.sum(up, axis=1).astype(jnp.int32))
        return (t, up, ev, rr_t, rr_idx, full, ~lark, ~maj, lpt, mpt,
                lev, mev), out

    def chunk(arrays, carry, s0):
        k = cl.bind(arrays)
        k.lane0 = arrays["lane0"]
        return jax.lax.scan(lambda c, s: step(k, c, s), carry,
                            s0 + jnp.arange(chunk_steps, dtype=jnp.int32))

    run = jax.jit(chunk)
    arrays = dict(cl.arrays(), lane0=lane0)
    lpt_tot = np.zeros(S)
    mpt_tot = np.zeros(S)
    lev_tot = np.zeros(S, np.int64)
    mev_tot = np.zeros(S, np.int64)
    traj = []
    for k in range(chunks):
        carry, ys = run(arrays, carry, jnp.int32(1 + k * chunk_steps))
        traj.append([np.asarray(y) for y in ys])
        lpt_tot += np.asarray(carry[8], dtype=np.float64)
        mpt_tot += np.asarray(carry[9], dtype=np.float64)
        lev_tot += np.asarray(carry[10])
        mev_tot += np.asarray(carry[11])
        carry = carry[:8] + (zf, zf, zi, zi)

    now = np.maximum(np.asarray(carry[0], dtype=np.int64), 1)
    pt = P * now.astype(np.float64)
    names = ("times", "unavail_lark", "unavail_maj", "nodes_up")
    return {
        "now": now, "partitions": P, "restarts": cl.waves(carry[3]),
        "fractions": {"u_lark_trials": lpt_tot / pt,
                      "u_maj_trials": mpt_tot / pt},
        "sums": {"u_lark": lpt_tot, "u_maj": mpt_tot},
        "events": {"lark_events": lev_tot, "maj_events": mev_tot},
        "hists": {},
        "trajectory": {nm: np.concatenate([c[i] for c in traj])
                       for i, nm in enumerate(names)},
    }
