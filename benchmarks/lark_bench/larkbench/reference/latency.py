"""Plain reference of the client-latency engine (paper §6, per-key
requests over the commit-pause engine) with fixed rebuilds, on a sample
of trials.

Protocols on the node trajectory:

  lark    paused while PAC fails; a leader change onto a node without
          the latest copy costs `dupres_ticks` and makes every key of the
          partition dirty: the first touch of a dirty key pays one
          duplicate-resolution round.
  quorum  a majority of the first rf succession ranks must be up, and
          after one of them goes down a catch-up of `rebuild_steps` ticks
          replays onto the lowest-ranked lost replica's node, sharing that
          node's bandwidth evenly with its other catch-ups (1/256-tick
          quanta).  A write that arrives during a catch-up waits for the
          rest of it.

Client traffic: `requests_per_tick` over the cluster, zipf key
popularity (KEYS_PER_PARTITION keys a partition hashed onto partitions),
a write share of 1 - read_frac.  Per (trial, partition) the engine
carries the dirty fraction of each of N_KEY_BUCKETS popularity bands;
over an interval of dt ticks a band's dirty keys survive untouched with
probability rho^dt, and the touched ones are charged.  Quorum waits are
closed forms in the remaining catch-up and dt.  Charges are float32 per
(trial, partition), summed over partitions in float64 at each chunk.
"""
from __future__ import annotations

import math

import numpy as np

from .common import Cluster, pac, seed_mix, uniforms
from .downtime import NEVER, SCALE

KEY_SALT = 0xC2B2AE35
KEYS_PER_PARTITION = 1024
N_KEY_BUCKETS = 4
TINY = np.float32(1e-30)       # float32 values below it are flushed to 0


def partition_weights(seed: int, partitions: int, key_zipf: float):
    """(P,) share of the requests that land on each partition: key rank r
    of P x KEYS_PER_PARTITION has popularity r^-key_zipf and lands on the
    partition its hash under KEY_SALT picks."""
    if key_zipf == 0:
        return np.full(partitions, 1.0 / partitions)
    nk = partitions * KEYS_PER_PARTITION
    pop = np.arange(1, nk + 1, dtype=np.float64) ** (-float(key_zipf))
    u = uniforms(seed_mix(seed), np.asarray(0, dtype=np.uint32), KEY_SALT,
                 np.zeros(1, dtype=np.uint32), nk, np)[0].astype(np.float64)
    part = np.minimum((u * partitions).astype(np.int64), partitions - 1)
    w = np.bincount(part, weights=pop, minlength=partitions)
    return w / w.sum()


def bucket_shares(key_zipf: float):
    """(key share, traffic share) of each popularity band of a partition:
    bands split the key ranks at K^(b/N) (geometric edges)."""
    K, N = KEYS_PER_PARTITION, N_KEY_BUCKETS
    edges = [0]
    for b in range(1, N):
        e = int(round(K ** (b / N)))
        edges.append(min(max(e, edges[-1] + 1), K - (N - b)))
    edges.append(K)
    pop = np.arange(1, K + 1, dtype=np.float64) ** (-float(key_zipf))
    f = np.asarray([(edges[b + 1] - edges[b]) / K for b in range(N)])
    g = np.asarray([pop[edges[b]:edges[b + 1]].sum() / pop.sum()
                    for b in range(N)])
    return f, g


def workload(seed: int, partitions: int, cell: dict):
    """float32 tables: keys per band (NB,), write requests per tick per
    partition (P,), and rho^(2^i) per (bit, partition, band)."""
    w = partition_weights(seed, partitions, cell["key_zipf"])
    f, g = bucket_shares(cell["key_zipf"])
    lam = cell["requests_per_tick"] * w
    lamw = (lam * (1.0 - cell["read_frac"])).astype(np.float32)
    lamw[lamw < TINY] = 0.0
    mu = lam[:, None] * g[None, :] / (KEYS_PER_PARTITION * f[None, :])
    rho = np.exp(-mu).astype(np.float32)
    nbits = max(1, int(cell["horizon"]).bit_length())
    pows = np.empty((nbits,) + rho.shape, dtype=np.float32)
    t = np.where(rho >= TINY, rho, np.float32(0.0))
    for i in range(nbits):
        pows[i] = t
        t = t * t
        t = np.where(t >= TINY, t, np.float32(0.0))
    return (KEYS_PER_PARTITION * f).astype(np.float32), lamw, pows


def charges(dirty, dt, avail, qok, rem, *, kf, lamw, pows, nbins, slo):
    """One interval of client charges from interval-start state:
    (new dirty, first-touch charges, quorum wait histogram, requests over
    the SLO, total wait ticks)."""
    import jax.numpy as jnp
    one, zero = jnp.float32(1.0), jnp.float32(0.0)
    decay = None
    for i in range(pows.shape[0]):           # rho^dt, bit by bit of dt
        f = jnp.where((((dt >> i) & 1) > 0)[:, None, None], pows[i][None],
                      one)
        decay = f if decay is None else decay * f
    new = dirty * jnp.where(avail[:, :, None], decay, one)
    new = jnp.where(new >= TINY, new, zero)
    dup = jnp.maximum(kf * (dirty - new), zero)
    # a write tau ticks into the interval waits max(rem - tau, 0)
    rem3, dt3 = rem[:, :, None], dt[:, None, None]
    qok3, lamw3 = qok[:, :, None], lamw[None, :, None]
    pay = jnp.maximum(jnp.minimum(dt3, rem3), 0)
    k = jnp.arange(nbins, dtype=jnp.int32)
    lo = jnp.left_shift(jnp.int32(1), k)
    hi = jnp.where(k == nbins - 1, jnp.int32(2 ** 31 - 1), 2 * lo - 1)
    cnt = jnp.minimum(rem3, hi) - jnp.maximum(rem3 - pay + 1, lo) + 1
    cnt = jnp.where(qok3, jnp.maximum(cnt, 0), 0)
    qhist = jnp.maximum(lamw3 * cnt.astype(jnp.float32), zero)
    payf, remf = pay.astype(jnp.float32), rem3.astype(jnp.float32)
    qsum = jnp.maximum(jnp.where(qok3, lamw3 * (
        payf * remf - jnp.float32(0.5) * payf * (payf - one)), zero), zero)
    over = jnp.maximum(jnp.minimum(dt3, rem3 - slo), 0)
    qslo = jnp.maximum(jnp.where(qok3, lamw3 * over.astype(jnp.float32),
                                 zero), zero)
    return new, dup, qhist, qslo[:, :, 0], qsum[:, :, 0]


def _pool(acc):
    """float64 sum over partitions (axis 1), partitions in order."""
    return np.ascontiguousarray(acc, dtype=np.float64).sum(axis=1)


def simulate(cell: dict, *, seed: int, trials, chunks: int,
             chunk_steps: int, acks: int = None) -> dict:
    import jax
    import jax.numpy as jnp
    c = cell
    n, P, rf = c["n"], c["partitions"], c["rf"]
    acks = rf if acks is None else acks
    bins, dupres = c["hist_bins"], c["dupres_ticks"]
    if c["rebuild_model"] != "fixed" \
            or not math.isfinite(c["node_bandwidth_gibps"]):
        raise ValueError("the reference covers fixed rebuilds that share "
                         "a finite node bandwidth")
    cl = Cluster(n=n, partitions=P, p=c["p"], downtime=c["downtime"],
                 seed=seed, horizon=c["horizon"], **c["scenario_knobs"])
    bw = min(math.floor(SCALE * c["node_bandwidth_gibps"]), NEVER)
    restart = min(c["rebuild_steps"], c["horizon"] + 1) * SCALE
    kf, lamw, pows = workload(seed, P, c)
    NB = kf.shape[0]
    lanes = jnp.arange(n, dtype=jnp.int32)
    ranks = jnp.arange(rf, dtype=jnp.int32)
    pidx = jnp.arange(P, dtype=jnp.int32)[None, :]
    S = len(trials)

    def hist_add(hist, mask, d):
        mask = mask & (d > 0)
        b = sum((d >= (1 << k)).astype(jnp.int32) for k in range(1, bins))
        onehot = (b[:, :, None] == jnp.arange(bins)) & mask[:, :, None]
        return hist + jnp.sum(onehot, axis=1).astype(jnp.int32)

    def evaluate(up_r, full):
        lark, qmaj, creps = pac(up_r, full, n=n, rf=rf, voters=rf,
                                acks=acks)
        leader = jnp.min(jnp.where(up_r, lanes, n), axis=2)
        leader_full = jnp.any(full & up_r & (lanes == leader[:, :, None]),
                              axis=2)
        return lark, qmaj, leader, leader_full, creps

    def step(k, carry, s):
        st, acc = carry
        st, acc = dict(st), dict(acc)
        now = st["now"]
        t, dt, up, st["ev_t"], st["rr_t"], st["rr_idx"] = k.advance(
            now, st["up"], st["ev_t"], st["rr_t"], st["rr_idx"], k.lane0, s)
        dt_i = t - now
        work, ingest = st["work"], st["ingest"]
        busy = (work > 0) & (ingest < n)
        per_node = jnp.sum((ingest[:, :, None] == lanes) & busy[:, :, None],
                           axis=1).astype(jnp.int32)
        share = jnp.take_along_axis(per_node, jnp.clip(ingest, 0, n - 1),
                                    axis=1)
        share = jnp.where(ingest < n, jnp.maximum(share, 1), 1)
        rate = jnp.minimum(SCALE, bw // share)
        # interval charges at interval-start state
        acc["l_pt"] = acc["l_pt"] \
            + jnp.sum(st["l_dn"], axis=1).astype(jnp.float32) * dt
        qmaj0 = 2 * jnp.sum(st["rep_up"], axis=2) > rf
        acc["q_pt"] = acc["q_pt"] \
            + jnp.sum(~qmaj0, axis=1).astype(jnp.float32) * dt
        rem = jnp.where(work > 0, jnp.where(
            rate > 0, (work + jnp.maximum(rate, 1) - 1)
            // jnp.maximum(rate, 1), NEVER), 0)
        acc["q_pt"] = acc["q_pt"] + jnp.sum(jnp.where(
            qmaj0, jnp.minimum(rem, dt_i[:, None]), 0).astype(jnp.float32),
            axis=1)
        done = st["q_dn"] & qmaj0 & (work > 0) \
            & (dt_i[:, None] * rate >= work)
        acc["q_hist"] = hist_add(acc["q_hist"], done,
                                 now[:, None] + rem - st["q_t0"])
        st["q_dn"] = st["q_dn"] & ~done
        work = jnp.maximum(work - dt_i[:, None] * rate, 0)
        dirty, dup, qh, qs, qq = charges(
            st["dirty"], dt_i, ~st["l_dn"], qmaj0, rem, kf=k.kf,
            lamw=k.lamw, pows=k.pows, nbins=bins, slo=c["slo_ticks"])
        acc["dup"] = acc["dup"] + dup
        acc["qhist_req"] = acc["qhist_req"] + qh
        acc["qslo"] = acc["qslo"] + qs
        acc["qsum"] = acc["qsum"] + qq

        # the event: re-evaluate, refresh holders, move leaders
        up_r = k.rank_space(up)
        lark, qmaj, ldr, ldr_full, creps = evaluate(up_r, st["full"])
        st["full"] = jnp.where(lark[:, :, None], creps, st["full"])
        acc["l_hist"] = hist_add(acc["l_hist"], st["l_dn"] & lark,
                                 t[:, None] - st["l_t0"])
        go = ~st["l_dn"] & ~lark
        st["l_t0"] = jnp.where(go, t[:, None], st["l_t0"])
        acc["l_ev"] = acc["l_ev"] + jnp.sum(go, axis=1).astype(jnp.int32)
        st["l_dn"] = ~lark
        stale = (ldr != st["leader"]) & lark & ~ldr_full
        if dupres > 0:
            nst = jnp.sum(stale, axis=1).astype(jnp.int32)
            acc["l_pt"] = acc["l_pt"] + nst.astype(jnp.float32) \
                * jnp.float32(dupres)
            acc["l_ev"] = acc["l_ev"] + nst
            acc["l_hist"] = hist_add(acc["l_hist"], stale,
                                     jnp.full((S, P), dupres, jnp.int32))
            dirty = jnp.where(stale[:, :, None], jnp.float32(1.0), dirty)
        st["leader"] = jnp.where(lark, ldr, st["leader"])

        # a lost replica restarts the catch-up onto its own node
        rep_up = up_r[:, :, :rf]
        lost = st["rep_up"] & ~rep_up
        if restart > 0:
            loss = jnp.any(lost, axis=2)
            work = jnp.where(loss, restart, work)
            first = jnp.min(jnp.where(lost, ranks, rf), axis=2)
            node = k.succ[pidx, jnp.clip(first, 0, rf - 1)]
            ingest = jnp.where(loss, node, ingest)
        pause = ~qmaj | (work > 0)
        acc["q_hist"] = hist_add(acc["q_hist"], st["q_dn"] & ~pause,
                                 t[:, None] - st["q_t0"])
        go = ~st["q_dn"] & pause
        st["q_t0"] = jnp.where(go, t[:, None], st["q_t0"])
        acc["q_ev"] = acc["q_ev"] + jnp.sum(go, axis=1).astype(jnp.int32)
        st.update(now=t, up=up, rep_up=rep_up, work=work, ingest=ingest,
                  dirty=dirty, q_dn=pause)
        out = (t, jnp.sum(st["l_dn"], axis=1), jnp.sum(pause, axis=1),
               jnp.sum(up, axis=1))
        return (st, acc), tuple(o.astype(jnp.int32) for o in out)

    # t = 0
    lane0, up, ev_t, rr_t, rr_idx = cl.initial(trials)
    up_r = cl.rank_space(up)
    full = jnp.broadcast_to(lanes < rf, (S, P, n))
    lark, qmaj, leader, _, creps = evaluate(up_r, full)
    full = jnp.where(lark[:, :, None], creps, full)
    zbp = jnp.zeros((S, P), jnp.int32)
    zf = jnp.zeros((S,), jnp.float32)
    zi = jnp.zeros((S,), jnp.int32)
    zh = jnp.zeros((S, bins), jnp.int32)
    state = dict(
        now=zi, up=up, ev_t=ev_t, rr_t=rr_t, rr_idx=rr_idx, full=full,
        rep_up=jnp.ones((S, P, rf), bool), ingest=jnp.full((S, P), n,
                                                           jnp.int32),
        work=zbp, leader=leader, l_dn=~lark, l_t0=zbp, q_dn=~qmaj, q_t0=zbp,
        dirty=jnp.zeros((S, P, NB), jnp.float32))
    acc0 = {"l_pt": zf, "q_pt": zf, "l_ev": zi, "q_ev": zi, "l_hist": zh,
            "q_hist": zh, "dup": jnp.zeros((S, P, NB), jnp.float32),
            "qhist_req": jnp.zeros((S, P, bins), jnp.float32),
            "qslo": jnp.zeros((S, P), jnp.float32),
            "qsum": jnp.zeros((S, P), jnp.float32)}

    def chunk(arrays, carry, s0):
        k = cl.bind(arrays)
        k.lane0, k.kf, k.lamw, k.pows = (arrays["lane0"], arrays["kf"],
                                         arrays["lamw"], arrays["pows"])
        return jax.lax.scan(lambda cr, s: step(k, cr, s), carry,
                            s0 + jnp.arange(chunk_steps, dtype=jnp.int32))

    run = jax.jit(chunk)
    arrays = dict(cl.arrays(), lane0=lane0, kf=jnp.asarray(kf),
                  lamw=jnp.asarray(lamw), pows=jnp.asarray(pows))
    pt_tot = {x: np.zeros(S) for x in ("lark", "quorum")}
    ev_tot = {x: np.zeros(S, np.int64) for x in ("lark", "quorum")}
    hist_tot = {x: np.zeros((S, bins), np.int64) for x in ("lark", "quorum")}
    raw = {"dup": np.zeros((S, NB)), "qhist": np.zeros((S, bins)),
           "qslo": np.zeros(S), "qsum": np.zeros(S)}
    traj = []
    carry = (state, acc0)
    for ci in range(chunks):
        carry, ys = run(arrays, carry, jnp.int32(1 + ci * chunk_steps))
        traj.append([np.asarray(y) for y in ys])
        acc = carry[1]
        for x, key in (("lark", "l"), ("quorum", "q")):
            pt_tot[x] += np.asarray(acc[key + "_pt"], dtype=np.float64)
            ev_tot[x] += np.asarray(acc[key + "_ev"])
            hist_tot[x] += np.asarray(acc[key + "_hist"])
        raw["dup"] += _pool(acc["dup"])
        raw["qhist"] += _pool(acc["qhist_req"])
        raw["qslo"] += _pool(acc["qslo"])
        raw["qsum"] += _pool(acc["qsum"])
        carry = (carry[0], acc0)

    now = np.maximum(np.asarray(carry[0]["now"], dtype=np.int64), 1)
    pt = P * now.astype(np.float64)
    cols = ("times", "paused_lark", "paused_quorum", "nodes_up")
    return {
        "now": now, "partitions": P,
        "restarts": cl.waves(carry[0]["rr_t"]),
        "fractions": {f"pause_{x}_trials": np.minimum(pt_tot[x] / pt, 1.0)
                      for x in ("lark", "quorum")},
        "per_trial": {f"latency_{k}": v for k, v in raw.items()},
        "sums": {f"pause_{x}": pt_tot[x] for x in ("lark", "quorum")},
        "events": {f"{x}_events": ev_tot[x] for x in ("lark", "quorum")},
        "hists": {f"hist_{x}": hist_tot[x] for x in ("lark", "quorum")},
        "trajectory": {nm: np.concatenate([ch[i] for ch in traj])
                       for i, nm in enumerate(cols)},
    }
