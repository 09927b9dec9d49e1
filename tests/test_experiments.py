"""Declarative experiment layer: spec gating, config loading, the
flag↔config↔programmatic equivalence contract, and the runner's
events/provenance stamping (engines stubbed — orchestration only)."""
import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from repro.core.downtime_batched import ENGINES
from repro.experiments import runner as runner_mod
from repro.experiments import schema
from repro.experiments.runner import ExperimentRunner, run_batch
from repro.experiments.spec import (ExperimentSpec, SpecError,
                                    _loads_flat_toml)

REPO = Path(__file__).resolve().parents[1]
CONFIGS = REPO / "benchmarks" / "configs"

_spec = importlib.util.spec_from_file_location(
    "availability_sweep", REPO / "benchmarks" / "availability_sweep.py")
sweep = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(sweep)


# -- spec construction & gating ------------------------------------------

def test_unknown_key_rejected_with_nearest_match():
    with pytest.raises(SpecError, match=r"did you mean 'metric'"):
        ExperimentSpec.create(metrc="downtime")
    with pytest.raises(SpecError, match="unknown spec key"):
        ExperimentSpec.create(zzz_not_a_knob=1)
    with pytest.raises(SpecError, match=r"did you mean 'trials'"):
        ExperimentSpec.create(trails=8)


@pytest.mark.parametrize("kwargs, match", [
    (dict(metric="availability", dupres_ticks=3), "metric 'downtime'"),
    (dict(metric="latency", engines="lark,quorum,hermes"), "protocol zoo"),
    (dict(key_zipf=2.0), "request workload"),
    (dict(metric="downtime", rebuild_ticks_per_gib=5),
     "reconfig-model knob"),
    (dict(metric="downtime", rebuild_model="reconfig", rebuild_steps=7),
     "fixed-model knob"),
    (dict(metric="downtime", size_dist="zipf"), "rebuild_model 'reconfig'"),
    (dict(metric="downtime", rebuild_model="reconfig", size_skew=2.0),
     "zipf/lognormal"),
    (dict(backend="event", metric="latency"), "batched engines"),
    (dict(backend="event", packed=True), "batched engines"),
    (dict(backend="jax", autotune=True), "pallas"),
    (dict(backend="numpy", trials=4, devices=2), "jax"),
    (dict(backend="jax", trials=5, devices=2), "multiple"),
    (dict(scenarios=["rolling-restrt"]), "rolling-restart"),
    (dict(metric="downtime", engines="lark,quorum,raft"), "raft"),
    (dict(metric="downtime", lease_ticks=40), "hermes"),
], ids=lambda v: str(sorted(v))[:40] if isinstance(v, dict) else v)
def test_gated_and_invalid_knobs_rejected(kwargs, match):
    with pytest.raises(SpecError, match=match):
        ExperimentSpec.create(**kwargs)


def test_canonical_round_trip_is_lossless():
    specs = [
        ExperimentSpec.create(),
        ExperimentSpec.create(metric="latency", backend="jax", trials=8,
                              devices=8, smoke=True, scenarios=["all"]),
        ExperimentSpec.create(metric="downtime", backend="jax", trials=8,
                              devices=8, smoke=True,
                              rebuild_model="reconfig", size_dist="zipf",
                              size_skew=1.0, node_bandwidth_gibps=1.0,
                              scenarios=["all"]),
        ExperimentSpec.create(metric="downtime", backend="pallas",
                              trials=2, smoke=True, packed=True,
                              autotune=True),
    ]
    for s in specs:
        rt = ExperimentSpec.create(**s.canonical())
        assert rt == s
        assert rt.content_hash() == s.content_hash()
        # the canonical form itself survives a JSON round trip
        again = ExperimentSpec.create(**json.loads(
            json.dumps(s.canonical())))
        assert again == s


def test_scenarios_resolve_and_dedupe():
    s = ExperimentSpec.create(scenarios=["all"])
    from repro.core.scenarios import scenario_names
    assert s.scenarios == tuple(scenario_names())
    s2 = ExperimentSpec.create(scenarios=["rack-pairs,flapping"])
    assert s2.scenarios == ("rack-pairs", "flapping")
    # scenarios_only with no selection = every registered scenario
    s3 = ExperimentSpec.create(scenarios_only=True, backend="jax",
                               trials=4)
    assert s3.scenarios == tuple(scenario_names()) and s3.scenarios_only


def test_schema_constants_pin_the_engine_stack():
    # the stdlib-only schema must never drift from the engine registry
    assert schema.KNOWN_ENGINES == ENGINES
    assert schema.SCHEMA_VERSION in schema.KNOWN_SCHEMA_VERSIONS
    # every declared row kind has key fields and gated columns
    for kind, (key_fam, col_fam) in schema.KIND_FAMILIES.items():
        assert key_fam in schema.ROW_KEY_FIELDS, kind
        assert col_fam in schema.GATED_COLS, kind


# -- committed configs ---------------------------------------------------

#: the flag spelling documented in each config header — the CLI/config
#: equivalence contract, pinned for every committed baseline
FLAG_LINES = {
    "sweep.toml": ["--backend", "jax", "--trials", "8", "--devices", "8",
                   "--scenario", "all"],
    "downtime.toml": ["--backend", "jax", "--trials", "8", "--devices",
                      "8", "--metric", "downtime", "--smoke",
                      "--scenario", "all"],
    "downtime_reconfig.toml": ["--backend", "jax", "--trials", "8",
                               "--devices", "8", "--metric", "downtime",
                               "--smoke", "--rebuild-model", "reconfig",
                               "--scenario", "all"],
    "downtime_skew.toml": ["--backend", "jax", "--trials", "8",
                           "--devices", "8", "--metric", "downtime",
                           "--smoke", "--rebuild-model", "reconfig",
                           "--size-dist", "zipf", "--size-skew", "1",
                           "--node-bandwidth-gibps", "1",
                           "--scenario", "all"],
    "latency.toml": ["--backend", "jax", "--trials", "8", "--devices",
                     "8", "--metric", "latency", "--smoke",
                     "--scenario", "all"],
    "shootout.toml": ["--backend", "jax", "--trials", "8", "--devices",
                      "8", "--metric", "downtime", "--smoke",
                      "--rebuild-model", "reconfig", "--engines",
                      "lark,quorum,hermes,spinnaker", "--lease-ticks",
                      "40", "--view-change-ticks", "200",
                      "--scenario", "rolling-restart"],
}


def test_every_committed_config_has_a_pinned_flag_line():
    tomls = sorted(p.name for p in CONFIGS.glob("*.toml"))
    assert tomls == sorted(FLAG_LINES), (
        "add the new config's flag spelling to FLAG_LINES")


@pytest.mark.parametrize("name", sorted(FLAG_LINES))
def test_cli_built_spec_equals_config_built_spec(name):
    cfg = ExperimentSpec.from_file(str(CONFIGS / name))
    cli, _ = sweep.build_spec(FLAG_LINES[name])
    assert cli == cfg
    assert cli.content_hash() == cfg.content_hash()
    assert cfg.name == Path(name).stem


@pytest.mark.parametrize("name", sorted(FLAG_LINES))
def test_fallback_toml_parser_agrees_with_from_file(name):
    # on 3.11+ from_file goes through tomllib; the flat fallback (the
    # 3.10 container path) must parse the committed configs identically
    flat = _loads_flat_toml((CONFIGS / name).read_text())
    assert ExperimentSpec.create(**flat) == \
        ExperimentSpec.from_file(str(CONFIGS / name))


@pytest.mark.parametrize("name", sorted(FLAG_LINES))
def test_config_meta_matches_committed_baseline_meta(name):
    """legacy_meta() of each config reproduces its committed BENCH
    meta key for key — the byte-compat contract for summary dumps
    (provenance-stamped dumps only ever add keys on top of these)."""
    bench = REPO / "benchmarks" / f"BENCH_{Path(name).stem}.json"
    committed = json.loads(bench.read_text())["meta"]
    spec = ExperimentSpec.from_file(str(CONFIGS / name))
    meta = spec.legacy_meta()
    legacy_keys = {k: v for k, v in meta.items()}
    assert legacy_keys == committed


def test_config_flag_conflict_is_an_error():
    with pytest.raises(SystemExit):
        sweep.build_spec(["--config", str(CONFIGS / "shootout.toml"),
                          "--trials", "4"])


def test_toml_fallback_parser_rejects_what_it_cannot_parse(tmp_path):
    with pytest.raises(SpecError, match="tables are not supported"):
        _loads_flat_toml("[section]\nkey = 1")
    with pytest.raises(SpecError, match="key = value"):
        _loads_flat_toml("just words")
    with pytest.raises(SpecError, match="cannot parse"):
        _loads_flat_toml("x = {a = 1}")
    # inline comments, quoted '#', inf, arrays all survive
    data = _loads_flat_toml(
        'a = "with # hash"  # comment\nb = inf\nc = [1, "two", true]\n')
    assert data == {"a": "with # hash", "b": float("inf"),
                    "c": [1, "two", True]}
    bad = tmp_path / "bad.toml"
    bad.write_text('name = "x"\nmetrc = "downtime"\n')
    with pytest.raises(SpecError, match="did you mean 'metric'"):
        ExperimentSpec.from_file(str(bad))


# -- runner orchestration (engines stubbed) ------------------------------

def _fake_avail(**kw):
    return SimpleNamespace(u_lark=1e-4, u_maj=2e-4, ci_lark=1e-5,
                           ci_maj=1e-5, ticks=1000)


def test_runner_streams_events_and_stamps_provenance(tmp_path, monkeypatch):
    monkeypatch.setattr(runner_mod, "simulate_availability_batched",
                        _fake_avail)
    spec = ExperimentSpec.create(backend="numpy", smoke=True, trials=2,
                                 scenarios=["rack-pairs"])
    lines = []
    ev = tmp_path / "events.jsonl"
    runner = ExperimentRunner(spec, events_path=str(ev),
                              emit=lines.append)
    rows = runner.run()
    assert [r["kind"] for r in rows[:2]] == ["iid", "iid"]  # smoke grid
    assert {r["kind"] for r in rows[2:]} == {"scenario"}
    assert len(lines) == len(rows)
    assert lines[0].startswith("availability,rf2_p")
    assert lines[-1].startswith("availability_scenario,rack-pairs_")

    events = [json.loads(x) for x in ev.read_text().splitlines()]
    assert events[0]["event"] == "run_start"
    assert events[0]["spec_sha256"] == spec.content_hash()
    assert events[-1]["event"] == "run_end"
    assert events[-1]["rows"] == len(rows)
    row_events = [e for e in events if e["event"] == "row"]
    assert len(row_events) == len(rows)
    assert all(e["dt_s"] >= 0 and e["t_s"] >= e["dt_s"] - 1e-9
               for e in row_events)
    assert row_events[0]["label"].startswith("iid_2_")

    doc = runner.summary()
    meta = doc["meta"]
    assert meta["schema_version"] == schema.SCHEMA_VERSION
    assert meta["backend"] == "numpy" and meta["smoke"] is True
    prov = meta["provenance"]
    assert prov["spec_sha256"] == spec.content_hash()
    assert prov["rng_salts"] == {"size": 0x94D049BB, "key": 0xC2B2AE35}
    assert prov["requested"] == {"backend": "numpy", "devices": 1,
                                 "trials": 2}
    assert prov["wall_s"] is not None and prov["started_unix"] > 0
    assert set(prov["observed"]) == {"platform", "device_kind",
                                     "device_count"}
    # the embedded spec reproduces the spec exactly (lossless meta)
    assert ExperimentSpec.create(**meta["spec"]) == spec
    # rows in the document are json-safe (no non-finite floats)
    json.dumps(doc, allow_nan=False)


def test_run_batch_returns_one_summary_per_spec(tmp_path, monkeypatch):
    monkeypatch.setattr(runner_mod, "simulate_availability_batched",
                        _fake_avail)
    a = ExperimentSpec.create(backend="numpy", smoke=True, trials=1)
    b = ExperimentSpec.create(backend="numpy", smoke=True, trials=2,
                              scenarios=["flapping"], scenarios_only=True)
    ev = tmp_path / "batch.jsonl"
    docs = run_batch([a, b], events_path=str(ev), emit=lambda _: None)
    assert len(docs) == 2
    assert docs[0]["meta"]["trials"] == 1
    assert docs[1]["meta"]["scenarios"] == ["flapping"]
    starts = [json.loads(x) for x in ev.read_text().splitlines()
              if json.loads(x)["event"] == "run_start"]
    assert len(starts) == 2


def test_write_summary_round_trips_through_the_gate(tmp_path, monkeypatch):
    """End to end: a provenance-stamped dump written by the runner loads
    clean through check_regression's strict loader and gates green
    against itself."""
    monkeypatch.setattr(runner_mod, "simulate_availability_batched",
                        _fake_avail)
    spec = ExperimentSpec.create(backend="numpy", smoke=True, trials=2)
    out = tmp_path / "dump.json"
    ExperimentRunner(spec, emit=None).write_summary(str(out))

    cr_spec = importlib.util.spec_from_file_location(
        "check_regression",
        REPO / "benchmarks" / "check_regression.py")
    check_regression = importlib.util.module_from_spec(cr_spec)
    cr_spec.loader.exec_module(check_regression)
    notes = []
    doc = check_regression.load_rows(str(out), notes)
    assert not notes                     # provenance-stamped: no nag
    assert doc["meta"]["schema_version"] == schema.SCHEMA_VERSION
    rc = check_regression.main([str(out), str(out), "--identical"])
    assert rc == 0


# -- run.py unknown-flag contract ----------------------------------------

def test_run_py_flags_unknown_flags_with_suggestion():
    run_spec = importlib.util.spec_from_file_location(
        "bench_run", REPO / "benchmarks" / "run.py")
    bench_run = importlib.util.module_from_spec(run_spec)
    run_spec.loader.exec_module(bench_run)
    suite = SimpleNamespace(cli_options=lambda: ("--trials", "--backend"))
    assert bench_run._unknown_flags(["--trials", "8"], [suite]) == []
    unknown = bench_run._unknown_flags(["--trails=8"], [suite])
    assert unknown == [("--trails", "--trials")]
    # every real suite publishes cli_options, and the sweep's surface
    # covers the flags run.py forwards in CI
    opts = sweep.cli_options()
    assert "--config" in opts and "--metric" in opts


def test_sweep_main_still_accepts_loose_parsing_for_run_py():
    # run.py passes every suite the same argv with strict=False; a flag
    # the sweep doesn't know must not kill it there
    spec, _ = sweep.build_spec(["--backend", "numpy", "--smoke",
                                "--some-other-suites-flag"], strict=False)
    assert spec.backend == "numpy" and spec.smoke


def test_every_benchmark_module_is_suite_or_standalone_tool():
    """run.py's suite tuple plus STANDALONE_TOOLS must cover every
    benchmarks/*.py module, with no overlap — a new tool can never be
    silently neither (run under the shared argv it would crash or drop
    flags; left off both lists it would never run at all)."""
    run_spec = importlib.util.spec_from_file_location(
        "bench_run_cov", REPO / "benchmarks" / "run.py")
    bench_run = importlib.util.module_from_spec(run_spec)
    run_spec.loader.exec_module(bench_run)
    suites = set(bench_run.SUITE_NAMES)
    tools = set(bench_run.STANDALONE_TOOLS)
    modules = {p.stem for p in (REPO / "benchmarks").glob("*.py")
               if p.stem != "run"}
    assert suites | tools == modules, \
        (suites | tools) ^ modules
    assert not suites & tools


# -- latency rows: sharpening knobs add columns only when set ------------

def test_latency_row_degenerate_knobs_add_no_columns():
    """write_skew=0 / bw=inf / slo_curve_bins=0 rows must carry exactly
    the pre-knob key set — that is what keeps regenerated baselines
    byte-identical to the committed ones row for row."""
    import math

    import numpy as np
    base = dict(
        rf=2, p=1e-4, lat_lark=0.1, lat_quorum=0.2, lat_hermes=0.02,
        ci_lat_lark=0.01, ci_lat_quorum=0.01,
        p50_lark=0.0, p99_lark=1.0, p999_lark=4.0,
        p50_quorum=0.0, p99_quorum=1.0, p999_quorum=4.0,
        p50_hermes=0.0, p99_hermes=1.0, p999_hermes=4.0,
        slo_lark=0.0, slo_quorum=0.0, slo_hermes=0.0, req_total=100.0,
        hist_edges=np.arange(3), hist_quorum_req=np.zeros(3),
        dupres_ticks=1, rebuild_model="fixed", key_zipf=1.0,
        read_frac=0.8, requests_per_tick=32.0, slo_ticks=8, ticks=1000,
        write_skew=0.0, node_bandwidth_gibps=math.inf, slo_curve_bins=0,
        slo_curve_edges=None, slo_curve_lark=None,
        slo_curve_quorum=None, slo_curve_hermes=None)
    deg = runner_mod._latency_row(SimpleNamespace(**base),
                                  kind="latency", scenario="iid")
    for key in ("write_skew", "node_bandwidth_gibps", "slo_curve_bins",
                "slo_curve_edges", "slo_curve_lark", "slo_curve_quorum",
                "slo_curve_hermes"):
        assert key not in deg, key
    curves = np.zeros(4)
    knobbed = runner_mod._latency_row(
        SimpleNamespace(**{**base, "write_skew": 1.0,
                           "node_bandwidth_gibps": 0.5,
                           "slo_curve_bins": 4, "slo_curve_edges": curves,
                           "slo_curve_lark": curves,
                           "slo_curve_quorum": curves,
                           "slo_curve_hermes": curves}),
        kind="latency", scenario="iid")
    assert knobbed["write_skew"] == 1.0
    assert knobbed["node_bandwidth_gibps"] == 0.5
    assert knobbed["slo_curve_bins"] == 4
    assert knobbed["slo_curve_quorum"] == [0.0] * 4
    # and the two rows key differently under the schema (the knobs are
    # part of the row identity, so a knobbed rerun can't shadow a
    # baseline row)
    assert schema.row_key(deg) != schema.row_key(knobbed)
