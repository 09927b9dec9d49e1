"""The comparison that decides `correct`.

Every number compared is printed beside its limit.  What is compared,
for the window's first call (every later call of the window must equal
it bit for bit, since they repeat it with the same seed):

  traj_mismatch    per-step entries of the sampled trials' trajectories
                   (event tick, paused/unavailable partitions per
                   protocol, nodes up) that differ from the reference
  frac_rel_gap     widest relative gap of a sampled trial's pause or
                   unavailability fraction per protocol, and of its client
                   latency charges (float64 pooling of float32 chunk sums)
  pooled_mismatch  events and histogram counts that differ, and
  pooled_rel_gap   the widest relative gap of the pooled fractions —
                   compared where the sample is every trial, as in
                   every cell (a test may sample fewer)
  restart_short    sampled trials whose window held fewer rolling-restart
                   waves than the mix's `min_restart_waves` (counted by
                   the reference; only for mixes that set it)
  calls_differ     later window calls whose result differs from the first
  horizon_trials   trials that reached the horizon (the window must not)
  failed           trials with a non-finite result or that stopped
                   advancing before the window ended
"""
from __future__ import annotations

import dataclasses

import numpy as np


# Copied from chip_smoke.py (_diff) so that later changes to the program
# cannot move the yardstick.
def diff(a, b, path="result"):
    """Paths where a and b differ, comparing arrays and floats by their
    bytes (bitwise: -0.0 != 0.0, and a NaN equals only the same NaN)."""
    if dataclasses.is_dataclass(a):
        out = []
        for f in dataclasses.fields(a):
            if f.name not in ("backend", "devices"):
                out += diff(getattr(a, f.name), getattr(b, f.name),
                            f"{path}.{f.name}")
        return out
    if isinstance(a, dict):
        if set(a) != set(b):
            return [f"{path} (keys)"]
        out = []
        for k in a:
            out += diff(a[k], b[k], f"{path}[{k!r}]")
        return out
    if isinstance(a, (list, tuple)) and not isinstance(a, str):
        if len(a) != len(b):
            return [f"{path} (length)"]
        out = []
        for i, (x, y) in enumerate(zip(a, b)):
            out += diff(x, y, f"{path}[{i}]")
        return out
    if a is None or isinstance(a, (str, bool)):
        return [] if a == b else [path]
    x, y = np.asarray(a), np.asarray(b)
    same = x.shape == y.shape and x.dtype == y.dtype \
        and x.tobytes() == y.tobytes()
    return [] if same else [path]


def sample_trials(seed: int, trials: int, count: int) -> np.ndarray:
    """`count` trial indices drawn from the seed, one from each of
    `count` equal strata of the batch (so both halves, and every
    device's shard, are always in the sample)."""
    count = min(count, trials)
    edges = np.linspace(0, trials, count + 1).astype(np.int64)
    rng = np.random.default_rng(seed % (2 ** 63))
    return np.asarray([rng.integers(lo, hi) for lo, hi in
                       zip(edges[:-1], edges[1:])], dtype=np.int64)


def as_view(ref: dict) -> dict:
    """A reference's output in the shape of a program result's view, its
    trials being the sampled ones (for the control, a reference put in
    the program's place)."""
    pt = float(np.sum(ref["now"])) * ref["partitions"]
    pooled = {}
    for name, s in ref["sums"].items():
        v = float(s.sum()) / pt
        pooled[name] = min(v, 1.0) if name.startswith("pause_") else v
    return {"now": ref["now"], "fractions": ref["fractions"],
            "per_trial": ref.get("per_trial", {}),
            "events": {k: int(v.sum()) for k, v in ref["events"].items()},
            "hists": {k: v.sum(axis=0) for k, v in ref["hists"].items()},
            "pooled": pooled, "trajectory": ref["trajectory"]}


def join(parts: list) -> dict:
    """One reference output from outputs on consecutive blocks of the
    sample: per-trial arrays joined along the trials axis (the
    trajectories' second), everything else from the first block."""
    def cat(key, xs):
        if isinstance(xs[0], dict):
            return {k: cat(key, [x[k] for x in xs]) for k in xs[0]}
        if isinstance(xs[0], np.ndarray):
            return np.concatenate(xs, axis=1 if key == "trajectory" else 0)
        return xs[0]
    return {k: cat(k, [p[k] for p in parts]) for k in parts[0]}


def _rel_gap(got, want) -> float:
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    scale = np.maximum(np.abs(want), 1e-12)
    return float(np.max(np.abs(got - want) / scale, initial=0.0))


def failed_trials(view: dict, *, chunk_steps: int) -> int:
    """Trials whose fractions are not finite, or whose elapsed ticks did
    not move over the last chunk."""
    bad = np.zeros(len(view["now"]), dtype=bool)
    for v in view["fractions"].values():
        bad |= ~np.isfinite(np.asarray(v, dtype=np.float64))
    times = view["trajectory"]["times"]
    if len(times) > chunk_steps:
        bad |= times[-1] <= times[-1 - chunk_steps]
    return int(bad.sum())


def readings(view: dict, ref: dict, sample, *, partitions: int,
             horizon: int, calls_differ: int, failed: int,
             min_waves: int = 0) -> dict:
    """The numbers compared, by name."""
    sample = np.asarray(sample)
    traj_bad = 0
    for col, want in ref["trajectory"].items():
        got = np.asarray(view["trajectory"].get(col))
        if got.ndim != 2 or got.shape[0] != want.shape[0]:
            traj_bad += want.size
            continue
        traj_bad += int(np.sum(got[:, sample] != want))
    per_trial = dict(ref["fractions"], **ref.get("per_trial", {}))
    got = dict(view["fractions"], **view.get("per_trial", {}))
    frac = max(_rel_gap(np.asarray(got[k])[sample], want)
               for k, want in per_trial.items())
    out = {"traj_mismatch": traj_bad, "frac_rel_gap": frac}
    if len(sample) == len(view["now"]):
        order = np.argsort(sample)
        mism = 0
        for k, want in ref["events"].items():
            mism += abs(int(view["events"][k]) - int(want[order].sum()))
        for k, want in ref["hists"].items():
            mism += int(np.abs(np.asarray(view["hists"][k])
                               - want[order].sum(axis=0)).sum())
        pt = float(partitions) * float(ref["now"].sum())
        gap = 0.0
        for name, s in ref["sums"].items():
            want = float(s.sum()) / pt
            if name.startswith("pause_"):        # pause fractions clip at 1
                want = min(want, 1.0)
            gap = max(gap, _rel_gap(view["pooled"][name], want))
        out["pooled_mismatch"] = mism
        out["pooled_rel_gap"] = gap
    if min_waves:
        out["restart_short"] = int(np.sum(np.asarray(ref["restarts"])
                                          < min_waves))
    out["calls_differ"] = calls_differ
    out["horizon_trials"] = int(np.sum(view["now"] >= horizon))
    out["failed"] = failed
    return out


def judge(values: dict, limits: dict) -> tuple:
    """(correct, [[name, value, limit], ...]); a number with no limit is
    an error in the cell's limits file, never a pass."""
    rows = []
    ok = True
    for name, v in values.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the cell's limits")
        lim = limits[name]
        rows.append([name, v, lim])
        ok = ok and (v <= lim)
    return ok, rows
