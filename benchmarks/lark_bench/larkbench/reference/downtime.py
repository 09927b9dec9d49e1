"""Plain reference of the commit-pause engine (paper §6) with the
protocol zoo, on a sample of trials.

Protocols, each a per-partition pause state machine on the same node
trajectory:

  lark       paused while PAC fails; a leader change onto a node without
             the latest copy costs `dupres_ticks` of paused commits.
  quorum     a majority of the partition's replica-set roster (rf seats)
             must be up and no catch-up may be in flight.  After a seat's
             node goes down the seat is given to the first up node in
             succession order not already seated, and a catch-up of the
             partition's data size starts; catch-ups that ingest on the
             same node share its bandwidth evenly, in 1/256-tick quanta.
  hermes     paused while PAC fails, and for `lease_ticks` after any of
             the first rf replicas goes down (writes wait for the
             membership lease).
  spinnaker  the quorum protocol, plus `view_change_ticks` of log
             reconciliation whenever the elected leader (lowest-ranked up
             seat) is lost.

Each interval before an event is charged at the interval-start state;
countdowns that expire inside it end their pause run there.  Completed
pause runs are binned by duration in power-of-two buckets.
"""
from __future__ import annotations

import math

import numpy as np

from .common import SIZE_SALT, Cluster, pac, seed_mix, uniforms

SCALE = 256                    # catch-up work units per full-speed tick
NEVER = 2 ** 30                # remaining ticks of a starved catch-up


def rebuild_units(seed: int, partitions: int, *, ticks_per_gib: int,
                  size_skew: float, cap: int) -> np.ndarray:
    """(P,) int32 catch-up work per partition: floor(ticks/GiB x size),
    at least 1 tick, at most `cap`, times SCALE.  Sizes are bounded-Pareto
    (zipf) draws (1-u)^-skew rescaled to a 1.5 GiB mean."""
    u = uniforms(seed_mix(seed), np.asarray(0, dtype=np.uint32), SIZE_SALT,
                 np.zeros(1, dtype=np.uint32), partitions, np)[0] \
        .astype(np.float64)
    raw = (1.0 - u) ** (-size_skew)
    size = raw * (1.5 / raw.mean())
    t = np.floor(ticks_per_gib * size)
    if ticks_per_gib > 0:
        t = np.maximum(t, 1.0)
    t = np.minimum(t, float(cap))
    return t.astype(np.int32) * np.int32(SCALE)


def simulate(cell: dict, *, seed: int, trials, chunks: int,
             chunk_steps: int, acks: int = None) -> dict:
    import jax
    import jax.numpy as jnp
    c = cell
    n, P, rf = c["n"], c["partitions"], c["rf"]
    acks = rf if acks is None else acks
    bins = c["hist_bins"]
    dupres = c["dupres_ticks"]
    protocols = tuple(c["protocols"])
    hermes, spinnaker = "hermes" in protocols, "spinnaker" in protocols
    lease, view_change = c["lease_ticks"], c["view_change_ticks"]
    if c["rebuild_model"] != "reconfig":
        raise ValueError("the reference covers the reconfiguring baseline")
    cl = Cluster(n=n, partitions=P, p=c["p"], downtime=c["downtime"],
                 seed=seed, horizon=c["horizon"], **c["scenario_knobs"])
    units = jnp.asarray(rebuild_units(
        seed, P, ticks_per_gib=c["rebuild_ticks_per_gib"],
        size_skew=c["size_skew"], cap=c["horizon"] + 1))
    bw = math.floor(SCALE * c["node_bandwidth_gibps"]) \
        if math.isfinite(c["node_bandwidth_gibps"]) else None
    lanes = jnp.arange(n, dtype=jnp.int32)
    S = len(trials)
    pidx = jnp.arange(P, dtype=jnp.int32)[None, :]

    def evaluate(up_r, full, roster):
        lark, _, creps = pac(up_r, full, n=n, rf=rf, voters=rf, acks=acks)
        seat_up = jnp.take_along_axis(up_r, roster, axis=2)   # (S, P, rf)
        qmaj = 2 * jnp.sum(seat_up, axis=2) > rf
        leader = jnp.min(jnp.where(up_r, lanes, n), axis=2)
        leader_full = jnp.any(full & up_r & (lanes == leader[:, :, None]),
                              axis=2)
        repmask = jnp.sum(up_r[:, :, :rf].astype(jnp.int32)
                          << jnp.arange(rf, dtype=jnp.int32), axis=2)
        elected = jnp.min(jnp.where(seat_up, roster, n), axis=2)
        return lark, qmaj, leader, leader_full, repmask, elected, creps

    def hist_add(hist, mask, d):
        mask = mask & (d > 0)
        b = sum((d >= (1 << k)).astype(jnp.int32) for k in range(1, bins))
        onehot = (b[:, :, None] == jnp.arange(bins)) & mask[:, :, None]
        return hist + jnp.sum(onehot, axis=1).astype(jnp.int32)

    def close_open(t, pause, dn, t0, ev, hist):
        hist = hist_add(hist, dn & ~pause, t[:, None] - t0)
        go = ~dn & pause
        return pause, jnp.where(go, t[:, None], t0), \
            ev + jnp.sum(go, axis=1).astype(jnp.int32), hist

    def charge(pt, base_dn, rem, dt, dt_i):
        """Full dt where the base condition held, else the countdown's
        remaining ticks within the interval."""
        pt = pt + jnp.sum(base_dn, axis=1).astype(jnp.float32) * dt
        return pt + jnp.sum(jnp.where(~base_dn, jnp.minimum(rem, dt_i[:, None]),
                                      0).astype(jnp.float32), axis=1)

    def recruit(up_r, seat_up, roster):
        seated = jnp.any(lanes[None, None, :, None] == roster[:, :, None, :],
                         axis=3)
        newest = jnp.full((S, P), n, jnp.int32)
        took = jnp.zeros((S, P), bool)
        for j in range(rf):
            cand = jnp.min(jnp.where(up_r & ~seated, lanes, n), axis=2)
            take = ~seat_up[:, :, j] & (cand < n)
            old = roster[:, :, j]
            new = jnp.where(take, cand, old)
            seated = (seated & ~(take[:, :, None]
                                 & (lanes == old[:, :, None]))) \
                | (take[:, :, None] & (lanes == new[:, :, None]))
            roster = roster.at[:, :, j].set(new)
            newest = jnp.where(take, cand, newest)
            took = took | take
        return roster, newest, took

    # t = 0: everyone up, the roster is ranks 0..rf-1, one evaluation
    lane0, up, ev_t, rr_t, rr_idx = cl.initial(trials)
    up_r = cl.rank_space(up)
    roster = jnp.broadcast_to(jnp.arange(rf, dtype=jnp.int32), (S, P, rf))
    full = jnp.broadcast_to(lanes < rf, (S, P, n))
    lark, qmaj, leader, _, repmask, _, creps = evaluate(up_r, full, roster)
    full = jnp.where(lark[:, :, None], creps, full)
    zbp = jnp.zeros((S, P), jnp.int32)
    zf = jnp.zeros((S,), jnp.float32)
    zi = jnp.zeros((S,), jnp.int32)
    zh = jnp.zeros((S, bins), jnp.int32)
    state = dict(
        now=zi, up=up, ev_t=ev_t, rr_t=rr_t, rr_idx=rr_idx, full=full,
        roster=roster, seat_up=jnp.ones((S, P, rf), bool),
        ingest=jnp.full((S, P), n, jnp.int32), work=zbp, leader=leader,
        l_dn=~lark, l_t0=zbp, q_dn=~qmaj, q_t0=zbp,
        h_dn=~lark, h_t0=zbp, h_mask=repmask, h_lease=zbp,
        s_dn=~qmaj, s_t0=zbp, s_lead=zbp, s_vc=zbp)
    acc0 = {f"{x}_{k}": v for x in "lqhs"
            for k, v in (("pt", zf), ("ev", zi), ("hist", zh))}

    def step(k, carry, s):
        st, acc = carry
        st, acc = dict(st), dict(acc)
        now = st["now"]
        t, dt, up, st["ev_t"], st["rr_t"], st["rr_idx"] = k.advance(
            now, st["up"], st["ev_t"], st["rr_t"], st["rr_idx"], k.lane0, s)
        dt_i = t - now
        # catch-up progress rate over the interval: full speed, or an
        # even share of the ingesting node's bandwidth
        work, ingest = st["work"], st["ingest"]
        if bw is None:
            rate = jnp.full((S, P), SCALE, jnp.int32)
        else:
            busy = (work > 0) & (ingest < n)
            per_node = jnp.sum((ingest[:, :, None] == lanes) & busy[:, :, None],
                               axis=1).astype(jnp.int32)
            share = jnp.take_along_axis(per_node,
                                        jnp.clip(ingest, 0, n - 1), axis=1)
            share = jnp.where(ingest < n, jnp.maximum(share, 1), 1)
            rate = jnp.minimum(SCALE, bw // share)
        # interval charges at interval-start state
        acc["l_pt"] = acc["l_pt"] \
            + jnp.sum(st["l_dn"], axis=1).astype(jnp.float32) * dt
        qmaj0 = 2 * jnp.sum(st["seat_up"], axis=2) > rf
        rem = jnp.where(work > 0, jnp.where(
            rate > 0, (work + jnp.maximum(rate, 1) - 1)
            // jnp.maximum(rate, 1), NEVER), 0)
        acc["q_pt"] = charge(acc["q_pt"], ~qmaj0, rem, dt, dt_i)
        done = st["q_dn"] & qmaj0 & (work > 0) & (dt_i[:, None] * rate >= work)
        acc["q_hist"] = hist_add(acc["q_hist"], done,
                                 now[:, None] + rem - st["q_t0"])
        st["q_dn"] = st["q_dn"] & ~done
        work = jnp.maximum(work - dt_i[:, None] * rate, 0)
        if hermes:
            hl = st["h_lease"]
            acc["h_pt"] = charge(acc["h_pt"], st["l_dn"], hl, dt, dt_i)
            end = st["h_dn"] & ~st["l_dn"] & (hl > 0) & (dt_i[:, None] >= hl)
            acc["h_hist"] = hist_add(acc["h_hist"], end,
                                     now[:, None] + hl - st["h_t0"])
            st["h_dn"] = st["h_dn"] & ~end
            st["h_lease"] = jnp.maximum(hl - dt_i[:, None], 0)
        if spinnaker:
            wait = jnp.maximum(rem, st["s_vc"])
            acc["s_pt"] = charge(acc["s_pt"], ~qmaj0, wait, dt, dt_i)
            end = st["s_dn"] & qmaj0 & (wait > 0) & (dt_i[:, None] >= wait)
            acc["s_hist"] = hist_add(acc["s_hist"], end,
                                     now[:, None] + wait - st["s_t0"])
            st["s_dn"] = st["s_dn"] & ~end
            st["s_vc"] = jnp.maximum(st["s_vc"] - dt_i[:, None], 0)

        # the event: reseat lost seats, restart catch-ups, re-evaluate
        up_r = k.rank_space(up)
        seat_up = jnp.take_along_axis(up_r, st["roster"], axis=2)
        lost = jnp.any(st["seat_up"] & ~seat_up, axis=2)
        roster, newest, took = recruit(up_r, seat_up, st["roster"])
        work = jnp.where(lost, k.units[None, :], work)
        node = k.succ[pidx, jnp.clip(newest, 0, n - 1)]
        ingest = jnp.where(took, node, jnp.where(lost, n, ingest))
        lark, qmaj, ldr, ldr_full, repmask, elected, creps = evaluate(
            up_r, st["full"], roster)
        st["full"] = jnp.where(lark[:, :, None], creps, st["full"])

        acc["l_hist"] = hist_add(acc["l_hist"], st["l_dn"] & lark,
                                 t[:, None] - st["l_t0"])
        go = ~st["l_dn"] & ~lark
        st["l_t0"] = jnp.where(go, t[:, None], st["l_t0"])
        acc["l_ev"] = acc["l_ev"] + jnp.sum(go, axis=1).astype(jnp.int32)
        st["l_dn"] = ~lark
        if dupres > 0:
            stale = (ldr != st["leader"]) & lark & ~ldr_full
            nst = jnp.sum(stale, axis=1).astype(jnp.int32)
            acc["l_pt"] = acc["l_pt"] + nst.astype(jnp.float32) \
                * jnp.float32(dupres)
            acc["l_ev"] = acc["l_ev"] + nst
            acc["l_hist"] = hist_add(acc["l_hist"], stale,
                                     jnp.full((S, P), dupres, jnp.int32))
        st["leader"] = jnp.where(lark, ldr, st["leader"])

        st["q_dn"], st["q_t0"], acc["q_ev"], acc["q_hist"] = close_open(
            t, ~qmaj | (work > 0), st["q_dn"], st["q_t0"], acc["q_ev"],
            acc["q_hist"])
        seat_up = jnp.take_along_axis(up_r, roster, axis=2)
        if hermes:
            suspect = (st["h_mask"] & ~repmask) != 0
            if lease > 0:
                st["h_lease"] = jnp.where(suspect, lease, st["h_lease"])
            st["h_mask"] = repmask
            st["h_dn"], st["h_t0"], acc["h_ev"], acc["h_hist"] = close_open(
                t, ~lark | (st["h_lease"] > 0), st["h_dn"], st["h_t0"],
                acc["h_ev"], acc["h_hist"])
        if spinnaker:
            lead = st["s_lead"]
            kept = jnp.any((roster == lead[:, :, None]) & seat_up, axis=2)
            new_lead = jnp.where(kept, lead, elected)
            change = ~kept & (lead < n) & (new_lead < n) & (new_lead != lead)
            if view_change > 0:
                st["s_vc"] = jnp.where(change, view_change, st["s_vc"])
            st["s_lead"] = new_lead
            st["s_dn"], st["s_t0"], acc["s_ev"], acc["s_hist"] = close_open(
                t, ~qmaj | (work > 0) | (st["s_vc"] > 0), st["s_dn"],
                st["s_t0"], acc["s_ev"], acc["s_hist"])
        st.update(now=t, up=up, roster=roster, seat_up=seat_up, work=work,
                  ingest=ingest)
        out = [t, jnp.sum(st["l_dn"], axis=1), jnp.sum(st["q_dn"], axis=1),
               jnp.sum(up, axis=1)]
        if hermes:
            out.append(jnp.sum(st["h_dn"], axis=1))
        if spinnaker:
            out.append(jnp.sum(st["s_dn"], axis=1))
        return (st, acc), tuple(o.astype(jnp.int32) for o in out)

    def chunk(arrays, carry, s0):
        k = cl.bind(arrays)
        k.lane0, k.units = arrays["lane0"], arrays["units"]
        return jax.lax.scan(lambda c, s: step(k, c, s), carry,
                            s0 + jnp.arange(chunk_steps, dtype=jnp.int32))

    run = jax.jit(chunk)
    arrays = dict(cl.arrays(), lane0=lane0, units=units)
    names = ["lark", "quorum"] + [x for x in ("hermes", "spinnaker")
                                  if x in protocols]
    key = {"lark": "l", "quorum": "q", "hermes": "h", "spinnaker": "s"}
    pt_tot = {x: np.zeros(S) for x in names}
    ev_tot = {x: np.zeros(S, np.int64) for x in names}
    hist_tot = {x: np.zeros((S, bins), np.int64) for x in names}
    traj = []
    carry = (state, acc0)
    for c in range(chunks):
        carry, ys = run(arrays, carry, jnp.int32(1 + c * chunk_steps))
        traj.append([np.asarray(y) for y in ys])
        acc = carry[1]
        for x in names:
            pt_tot[x] += np.asarray(acc[key[x] + "_pt"], dtype=np.float64)
            ev_tot[x] += np.asarray(acc[key[x] + "_ev"])
            hist_tot[x] += np.asarray(acc[key[x] + "_hist"])
        carry = (carry[0], acc0)

    now = np.maximum(np.asarray(carry[0]["now"], dtype=np.int64), 1)
    pt = P * now.astype(np.float64)
    cols = ["times"] + [f"paused_{x}" for x in ("lark", "quorum")] \
        + ["nodes_up"] + [f"paused_{x}" for x in names[2:]]
    return {
        "now": now, "partitions": P,
        "restarts": cl.waves(carry[0]["rr_t"]),
        "fractions": {f"pause_{x}_trials": np.minimum(pt_tot[x] / pt, 1.0)
                      for x in names},
        "sums": {f"pause_{x}": pt_tot[x] for x in names},
        "events": {f"{x}_events": ev_tot[x] for x in names},
        "hists": {f"hist_{x}": hist_tot[x] for x in names},
        "trajectory": {nm: np.concatenate([c[i] for c in traj])
                       for i, nm in enumerate(cols)},
    }
