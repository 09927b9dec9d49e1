"""Paper §5.1 / Figure 6 / Table 2: availability vs node-failure probability,
and (--metric downtime) the §6 commit-pause comparison.

This is a thin CLI over the declarative experiment layer
(src/repro/experiments/): every flag below maps 1:1 onto an
``ExperimentSpec`` field, the validation lives in the spec (gating) and
``DowntimeParams`` (values), and the sweep itself runs through
``ExperimentRunner``.  The same run is therefore expressible three
equivalent ways — flags, ``--config benchmarks/configs/<name>.toml``, or
``ExperimentSpec.create(...)`` — and all three produce byte-identical
rows (pinned per committed baseline by tests/test_experiments.py and
CI's reproducibility lane).

Reduced grid by default (CPU budget); --full sweeps the paper's p range
with n=155, P=4096 and CI early-stopping; --smoke shrinks everything for
the CI pallas-interpret lane.  Emits CSV rows:
  availability,<rf>,<p>,u_lark,u_maj,ratio,analytic_ratio,ticks

--metric downtime swaps the instantaneous engine for the batched
commit-pause engine (core/downtime_batched.py): rows carry the mean
commit-pause fraction of LARK vs the equal-storage quorum-log baseline,
the pause-duration histograms, and the dup-res / rebuild knobs
(--dupres-ticks / --rebuild-steps).  --rebuild-model picks the baseline:
"fixed" (static first-rf replica set, constant rebuild pause) or
"reconfig" (replica-set reconfiguration onto live nodes with a
data-sized catch-up, --rebuild-ticks-per-gib per GiB of per-partition
data; --size-dist/--size-skew shape the per-partition sizes — uniform,
zipf, lognormal at a pinned 1.5 GiB mean — and --node-bandwidth-gibps
makes concurrent catch-ups share each recruit node's ingest bandwidth).
Downtime rows are batched-only ("event" maps to "numpy").
--engines grows the comparison into the protocol zoo: beyond the
lark/quorum pair every downtime row carries, "hermes" (broadcast
replication under membership leases, --lease-ticks write-block window)
and "spinnaker" (Paxos with reconfiguration, --view-change-ticks
log-reconciliation pause on leader loss; reconfig model only) each add
one "downtime_engine" row per grid point, keyed by engine name.

--metric latency layers the client-traffic request engine
(core/client_latency.py) over the same trajectories: zipf key popularity
(--key-zipf) mapped onto partitions, a --read-frac read/write mix at
--requests-per-tick offered cluster load, per-key dup-res first-touch
charges for LARK vs full rebuild-wait charges for the quorum-log
baseline (and the Hermes-style read-local contrast).  Rows carry
p50/p99/p999 added commit latency, the --slo-ticks violation fraction
(strict >; --slo-curve-bins adds the full violation curve over the
2^j - 1 threshold sweep), and the quorum wait histogram.  --write-skew
draws each partition's write fraction around 1 - read_frac (mean-pinned,
independent of key popularity), and --node-bandwidth-gibps makes
fixed-model rebuilds share node ingest bandwidth just like reconfig
catch-ups.  Latency rows accept every downtime knob
(the protocol under the workload is the same) and are batched-only.

Backends (--backend):
  event    scalar heapq event engine (core/availability.py); --trials N runs
           N sequential seeds and averages — the seed repo's behavior
  numpy    batched engine (core/availability_batched.py), vectorized numpy
           PAC, python chunk loop
  jax      batched engine, jit + lax.scan, pure-jnp PAC oracle
  pallas   batched engine, PAC through kernels/pac_eval.py (compiled on
           TPU, interpret mode on CPU — slow there; use for validation)

For the batched backends --trials N advances N independent trajectories in
one device program; --devices D shards them over a 1-D "trials" mesh
(bit-identical to --devices 1 for the same seed; on CPU set
XLA_FLAGS=--xla_force_host_platform_device_count=D).  --autotune (pallas)
races kernel block_p candidates before the sweep and runs the grid at the
winner.

Failure models come from the scenario registry (core/scenarios.py):
--scenario NAME appends that scenario's (rf, p) grid on top of the i.i.d.
rows ('all' = every registered name; repeatable / comma-separated).
--scenarios is the legacy alias for --scenario all; --scenarios-only skips
the i.i.d. grid.  Scenario rows always use the batched engine ("event"
maps to "numpy" — the scalar engine has no correlated/scheduled failure
model).

Artifacts: --json PATH dumps all rows with CI half-widths plus a
provenance-stamped meta (schema version, the full canonical spec, spec
content hash, config path + file hash, git SHA, seed/RNG salts,
backend/device geometry, wall-clock) — the schema
benchmarks/check_regression.py consumes.  --events PATH streams one
JSONL progress record per row with real wall-clock deltas, the input to
tools/perf_baseline.py / tools/perf_delta.py.  --config PATH replaces
the sweep flags with a committed experiment config (TOML or JSON; see
benchmarks/configs/ and docs/BENCHMARKS.md) and is mutually exclusive
with them — only --json/--events/--seed-independent output flags ride
along.
"""
from __future__ import annotations

import argparse
import sys

from repro.core.downtime_batched import ENGINES, SIZE_DISTS
from repro.experiments.runner import (FULL_GRID,  # noqa: F401 — re-exports
                                      REDUCED_GRID, SMOKE_GRID,
                                      ExperimentRunner, _autotune_row,
                                      _batched_backend, _downtime_engine_rows,
                                      _downtime_row, _grid_scale, _iid_grid,
                                      _json_safe, _latency_row, _run_scale,
                                      run, run_downtime,
                                      run_downtime_scenarios, run_latency,
                                      run_latency_scenarios, run_scenarios)
from repro.experiments.spec import ExperimentSpec, SpecError

#: argparse dest → ExperimentSpec field for every sweep flag (the 1:1
#: flag/spec mapping; output flags --json/--events/--config are not
#: spec fields and are absent on purpose)
SPEC_FLAGS = {
    "full": "full", "smoke": "smoke", "backend": "backend",
    "metric": "metric", "trials": "trials", "devices": "devices",
    "seed": "seed", "dupres_ticks": "dupres_ticks",
    "rebuild_steps": "rebuild_steps", "rebuild_model": "rebuild_model",
    "rebuild_ticks_per_gib": "rebuild_ticks_per_gib",
    "size_dist": "size_dist", "size_skew": "size_skew",
    "node_bandwidth_gibps": "node_bandwidth_gibps", "engines": "engines",
    "lease_ticks": "lease_ticks", "view_change_ticks": "view_change_ticks",
    "key_zipf": "key_zipf", "read_frac": "read_frac",
    "requests_per_tick": "requests_per_tick", "slo_ticks": "slo_ticks",
    "write_skew": "write_skew", "slo_curve_bins": "slo_curve_bins",
    "scenario": "scenarios", "scenarios": "scenarios",
    "scenarios_only": "scenarios_only", "packed": "packed",
    "autotune": "autotune",
}


def build_parser() -> argparse.ArgumentParser:
    # allow_abbrev off: a prefix typo like --ful must fail loudly, not
    # silently launch the hours-long paper-scale grid
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0],
                                 allow_abbrev=False)
    ap.add_argument("--config", metavar="PATH",
                    help="run a committed experiment config (TOML/JSON "
                         "spec; benchmarks/configs/) instead of sweep "
                         "flags — mutually exclusive with them")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid/scale (CI pallas-interpret lane)")
    ap.add_argument("--backend", default=None,
                    choices=("event", "numpy", "jax", "pallas"))
    ap.add_argument("--metric", default=None,
                    choices=("availability", "downtime", "latency"),
                    help="instantaneous availability (§5.1), commit-pause "
                         "durations (§6), or client-visible commit "
                         "latency under a keyed request workload")
    ap.add_argument("--dupres-ticks", type=int, default=None,
                    help="LARK dup-res round-trip cost in ticks "
                         "(downtime metric only; default 1)")
    ap.add_argument("--rebuild-steps", type=int, default=None,
                    help="quorum-log rebuild pause in ticks after a "
                         "replica loss (--rebuild-model fixed only; "
                         "default 100)")
    ap.add_argument("--rebuild-model", default=None,
                    choices=("fixed", "reconfig"),
                    help="quorum-log baseline: static replica set with a "
                         "constant rebuild pause (fixed, default) or "
                         "reconfiguration onto live nodes with a "
                         "data-sized catch-up (reconfig); downtime "
                         "metric only")
    ap.add_argument("--rebuild-ticks-per-gib", type=int, default=None,
                    help="reconfig catch-up cost per GiB of partition "
                         "data (--rebuild-model reconfig only; "
                         "default 100)")
    ap.add_argument("--size-dist", default=None, choices=SIZE_DISTS,
                    help="per-partition data-size distribution for the "
                         "reconfig catch-ups (default uniform [1, 2) "
                         "GiB; zipf/lognormal skew hot partitions while "
                         "pinning the same 1.5 GiB mean; "
                         "--rebuild-model reconfig only)")
    ap.add_argument("--size-skew", type=float, default=None,
                    help="skew shape of --size-dist zipf/lognormal "
                         "(Pareto exponent / log-sigma; 0 = constant "
                         "sizes; default 1)")
    ap.add_argument("--node-bandwidth-gibps", type=float, default=None,
                    help="per-node catch-up ingest bandwidth in "
                         "full-speed streams; concurrent rebuilds on one "
                         "node share it ('inf' disables sharing, the "
                         "default; applies to both rebuild models — "
                         "fixed-model rebuilds replay onto the lost "
                         "replica's own node)")
    ap.add_argument("--engines", default=None, metavar="LIST",
                    help="comma-separated protocol engines to report "
                         f"(subset of {','.join(ENGINES)}; default "
                         "lark,quorum; --metric downtime only)")
    ap.add_argument("--lease-ticks", type=int, default=None,
                    help="Hermes membership-lease expiry window: writes "
                         "block this many ticks after a replica is "
                         "suspected (--engines hermes; default 0)")
    ap.add_argument("--view-change-ticks", type=int, default=None,
                    help="Spinnaker log-reconciliation pause after a "
                         "leader loss (--engines spinnaker, "
                         "--rebuild-model reconfig; default 0)")
    ap.add_argument("--key-zipf", type=float, default=None,
                    help="zipf exponent of the key-popularity workload "
                         "(0 = exactly uniform traffic; --metric latency "
                         "only; default 1)")
    ap.add_argument("--read-frac", type=float, default=None,
                    help="fraction of requests that are reads (the rest "
                         "are writes; --metric latency only; default 0.8)")
    ap.add_argument("--requests-per-tick", type=float, default=None,
                    help="offered cluster-wide request rate "
                         "(--metric latency only; default 32)")
    ap.add_argument("--slo-ticks", type=int, default=None,
                    help="SLO threshold: rows report the fraction of "
                         "requests whose added commit latency STRICTLY "
                         "exceeds this (0 counts any added latency; "
                         "--metric latency only; default 8)")
    ap.add_argument("--write-skew", type=float, default=None,
                    help="skew the per-partition write fraction around "
                         "1 - read_frac (mean-pinned Pareto shape, own "
                         "RNG salt, independent of key popularity; 0 = "
                         "exactly uniform mix; --metric latency only; "
                         "default 0)")
    ap.add_argument("--slo-curve-bins", type=int, default=None,
                    help="report the SLO-violation curve over the "
                         "power-of-two thresholds 2^j - 1, j < BINS, "
                         "next to the --slo-ticks scalar (0 = scalar "
                         "only; --metric latency only; default 0)")
    ap.add_argument("--trials", type=int, default=None,
                    help="seeds (event) or batch size (batched backends)")
    ap.add_argument("--devices", type=int, default=None,
                    help="shard trials over this many devices (jax/pallas)")
    ap.add_argument("--seed", type=int, default=None,
                    help="base RNG seed (default 0; event backend runs "
                         "seeds seed..seed+trials-1)")
    ap.add_argument("--scenario", action="append", metavar="NAME",
                    help="append a registered scenario's grid (repeatable, "
                         "comma-separated, or 'all')")
    ap.add_argument("--scenarios", action="store_true",
                    help="legacy alias for --scenario all")
    ap.add_argument("--scenarios-only", action="store_true",
                    help="skip the i.i.d. grid (scenario rows only)")
    ap.add_argument("--packed", action="store_true",
                    help="carry cluster state as bit-packed uint32 words; "
                         "on --backend pallas every step runs the fused "
                         "megakernel (bit-identical to unpacked)")
    ap.add_argument("--autotune", action="store_true",
                    help="race pallas kernel block candidates before the "
                         "sweep (block_p; with --packed the 2-D fused "
                         "block_t x block_p race)")
    ap.add_argument("--json", metavar="PATH",
                    help="dump rows + CI half-widths + provenance-stamped "
                         "meta as JSON")
    ap.add_argument("--events", metavar="PATH",
                    help="append one JSONL progress record per row "
                         "(run_start/row/run_end with wall-clock deltas)")
    return ap


def cli_options() -> tuple:
    """Every option string this suite's parser accepts — the suite-level
    contract benchmarks/run.py uses to flag typo'd flags that no suite
    recognizes."""
    opts = []
    for action in build_parser()._actions:
        opts.extend(action.option_strings)
    return tuple(opts)


def _provided_spec_flags(args: argparse.Namespace) -> dict:
    """The spec kwargs the user explicitly set on the command line:
    store_true flags only when true, everything else only when not None
    — so the spec's metric/engine gating fires exactly on what was
    typed, never on a filled default."""
    provided = {}
    for dest, key in SPEC_FLAGS.items():
        v = getattr(args, dest)
        if v is None or v is False:
            continue
        if dest == "scenario":
            provided["scenarios"] = tuple(v)
        elif dest == "scenarios":
            # legacy alias: --scenarios alone means --scenario all
            provided.setdefault("scenarios", ("all",))
        else:
            provided[key] = v
    return provided


def build_spec(argv=None, *, strict: bool = True):
    """Parse sweep flags into (spec, args).  The seam the equivalence
    tests pin: for every committed config, build_spec() over the
    documented flag line equals ExperimentSpec.from_file(config)."""
    ap = build_parser()
    args, extra = ap.parse_known_args(argv if argv is not None
                                      else sys.argv[1:])
    if strict and extra:
        ap.error(f"unrecognized arguments: {' '.join(extra)}")
    provided = _provided_spec_flags(args)
    try:
        if args.config:
            if provided:
                flags = ", ".join("--" + k.replace("_", "-")
                                  for k in sorted(provided))
                ap.error(f"--config is mutually exclusive with sweep "
                         f"flags (got {flags}); edit the config or drop "
                         "--config")
            spec = ExperimentSpec.from_file(args.config)
        else:
            spec = ExperimentSpec.create(**provided)
    except SpecError as e:
        ap.error(str(e))
    return spec, args


def main(argv=None, *, strict: bool = True) -> int:
    spec, args = build_spec(argv, strict=strict)
    runner = ExperimentRunner(spec, config_path=args.config,
                              events_path=args.events)
    runner.run()
    if args.json:
        runner.write_summary(args.json)
    return 0


if __name__ == "__main__":
    import os

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main()
