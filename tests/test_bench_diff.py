"""tools/bench_diff.py wired as a tier-1 gate (ISSUE 9 satellite): the
bench-summary trajectory becomes machine-checkable — a synthetic summary
pair round-trips through the CLI with the right exit codes, regression
classification, and thresholds."""

import copy
import importlib
import json

import pytest

bench_diff = importlib.import_module("tools.bench_diff")


BASE = {
    "platform": "cpu",
    "metric": "ivf_flat_qps_200k",
    "value": 40.0,
    "unit": "qps",
    "recall_at_10": 0.96,
    "cpu_baseline_qps": 10.0,
    "steady_state_recompiles": 0,
    "hbm_high_watermark_bytes": 1_000_000,
    "precision_sweep": {
        "fp32": {"qps": 100.0, "recall_at_10": 0.96,
                 "hbm_peak_bytes": 500_000},
        "sq8": {"qps": 120.0, "recall_at_10": 0.95,
                "live_vs_measured_delta": -0.001,
                "hbm_peak_bytes": 200_000},
    },
    "mesh_scaling": {
        "points": [
            {"n_devices": 1, "flat": {"qps": 900.0,
                                      "steady_state_recompiles": 0}},
            {"n_devices": 2, "flat": {"qps": 700.0,
                                      "steady_state_recompiles": 0}},
        ],
    },
}


def _write(tmp_path, name, obj):
    p = tmp_path / name
    p.write_text(json.dumps(obj))
    return str(p)


def test_identical_summaries_pass(tmp_path, capsys):
    a = _write(tmp_path, "a.json", BASE)
    b = _write(tmp_path, "b.json", BASE)
    assert bench_diff.main([a, b]) == 0
    assert "0 regression(s)" in capsys.readouterr().out


def test_qps_regression_fails_and_names_the_path(tmp_path, capsys):
    worse = copy.deepcopy(BASE)
    worse["precision_sweep"]["fp32"]["qps"] = 60.0     # -40%
    a = _write(tmp_path, "a.json", BASE)
    b = _write(tmp_path, "b.json", worse)
    assert bench_diff.main([a, b]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out
    assert "precision_sweep.fp32.qps" in out
    # within-threshold drift passes
    mild = copy.deepcopy(BASE)
    mild["precision_sweep"]["fp32"]["qps"] = 95.0      # -5%
    c = _write(tmp_path, "c.json", mild)
    assert bench_diff.main([a, c]) == 0


def test_recall_and_hbm_and_recompile_kinds(tmp_path, capsys):
    worse = copy.deepcopy(BASE)
    worse["recall_at_10"] = 0.91                       # -0.05 absolute
    worse["hbm_high_watermark_bytes"] = 2_000_000      # +100%
    worse["steady_state_recompiles"] = 3               # invariant broken
    a = _write(tmp_path, "a.json", BASE)
    b = _write(tmp_path, "b.json", worse)
    assert bench_diff.main([a, b]) == 1
    out = capsys.readouterr().out
    assert "recall_at_10" in out
    assert "hbm_high_watermark_bytes" in out
    assert "steady_state_recompiles" in out
    # each threshold is CLI-tunable: loosened gates pass (recompile
    # growth stays a regression at any threshold — it is an invariant)
    assert bench_diff.main(
        [a, b, "--recall-drop", "0.1", "--bytes-grow", "2.0"]) == 1
    result = bench_diff.compare(BASE, worse, recall_drop=0.1,
                                bytes_grow=2.0)
    kinds = {r["kind"] for r in result["regressions"]}
    assert kinds == {"recompiles"}


def test_classifier_scope():
    # diagnostics/deltas/baselines never threshold
    assert bench_diff.classify("precision_sweep.sq8.live_vs_measured_delta") \
        is None
    assert bench_diff.classify("cpu_baseline_qps") is None
    assert bench_diff.classify("recall_slo.estimate_vs_measured_delta") \
        is None
    # recall_slo's per-tick convergence trail intentionally starts
    # mistuned: trajectory values are diagnostics, never regressions
    assert bench_diff.classify(
        "recall_slo.trajectory[0].recall_estimate") is None
    assert bench_diff.compare(
        {"recall_slo": {"trajectory": [{"recall_estimate": 0.41}]}},
        {"recall_slo": {"trajectory": [{"recall_estimate": 0.38}]}},
    )["regressions"] == []
    # magnitudes do
    assert bench_diff.classify("mesh_scaling.points[0].flat.qps") == "qps"
    assert bench_diff.classify("hnsw_sweep.device.recall_at_10") == "recall"
    assert bench_diff.classify("mixed_rw.hbm_peak_bytes") == "bytes"
    assert bench_diff.classify(
        "recall_slo.steady_state_recompiles") == "recompiles"
    # top-level bench value classifies through its sibling unit
    assert bench_diff.classify("value", {"unit": "qps"}) == "qps"
    assert bench_diff.classify("value", {"unit": "ms"}) is None


def test_new_and_dropped_coverage_reported_not_regressed(tmp_path, capsys):
    grown = copy.deepcopy(BASE)
    grown["recall_slo"] = {"live_recall_estimate": 0.96,
                           "steady_state_recompiles": 0}
    del grown["mesh_scaling"]
    a = _write(tmp_path, "a.json", BASE)
    b = _write(tmp_path, "b.json", grown)
    assert bench_diff.main([a, b]) == 0
    out = capsys.readouterr().out
    assert "new coverage" in out
    assert "dropped from new" in out


def test_json_output_machine_readable(tmp_path, capsys):
    worse = copy.deepcopy(BASE)
    worse["value"] = 10.0
    a = _write(tmp_path, "a.json", BASE)
    b = _write(tmp_path, "b.json", worse)
    assert bench_diff.main([a, b, "--json"]) == 1
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["regressions"][0]["path"] == "value"
    assert parsed["regressions"][0]["kind"] == "qps"


def test_bad_file_is_usage_error(tmp_path):
    a = _write(tmp_path, "a.json", BASE)
    assert bench_diff.main([a, str(tmp_path / "missing.json")]) == 2
    notjson = tmp_path / "x.json"
    notjson.write_text("{nope")
    assert bench_diff.main([a, str(notjson)]) == 2


def test_live_quality_recall_estimates_are_gated(tmp_path):
    """The new quality plane figures participate in the diff: a live
    recall estimate that collapses between rounds is a regression."""
    old = {"recall_slo": {"live_recall_estimate": 0.96},
           "precision_sweep": {"sq8": {"live_recall_estimate": 0.95}}}
    new = copy.deepcopy(old)
    new["recall_slo"]["live_recall_estimate"] = 0.80
    result = bench_diff.compare(old, new)
    assert [r["path"] for r in result["regressions"]] == [
        "recall_slo.live_recall_estimate"]


def test_overload_goodput_classification():
    """ISSUE 10: the overload scenario's goodput figures regress like QPS
    — but only on the SHAPED arm. The qos_off arm is the intentional
    collapse demonstration (noisy by design), and the raw load
    accounting (shed/expired/offered counts) tracks the offered rate,
    not code quality."""
    assert bench_diff.classify("overload.qos_on.goodput_qps") == "qps"
    assert bench_diff.classify("overload.goodput_ratio_on_vs_off") == "qps"
    assert bench_diff.classify("overload.capacity_qps") == "qps"
    assert bench_diff.classify(
        "overload.qos_on.steady_state_recompiles") == "recompiles"
    # never regression signals:
    assert bench_diff.classify("overload.qos_off.goodput_qps") is None
    assert bench_diff.classify("overload.qos_off.served_p99_ms") is None
    assert bench_diff.classify("overload.qos_on.shed") is None
    assert bench_diff.classify("overload.qos_on.expired") is None
    assert bench_diff.classify("overload.qos_on.offered") is None
    assert bench_diff.classify("overload.deadline_ms") is None


def test_overload_goodput_drop_is_a_regression(tmp_path):
    old = {"overload": {
        "capacity_qps": 1800.0, "deadline_ms": 250.0,
        "qos_on": {"goodput_qps": 1200.0, "shed": 2400, "expired": 10},
        "qos_off": {"goodput_qps": 120.0},
        "goodput_ratio_on_vs_off": 10.0,
    }}
    new = copy.deepcopy(old)
    new["overload"]["qos_on"]["goodput_qps"] = 600.0   # halved: regression
    new["overload"]["qos_off"]["goodput_qps"] = 30.0   # noisy arm: ignored
    new["overload"]["qos_on"]["shed"] = 3100           # load figure: ignored
    new["overload"]["goodput_ratio_on_vs_off"] = 20.0  # improved
    result = bench_diff.compare(old, new)
    assert [r["path"] for r in result["regressions"]] == [
        "overload.qos_on.goodput_qps"]


def test_integrity_overhead_classification():
    """ISSUE 11: the integrity_scrub scenario's p99_overhead_pct is an
    instrumentation-cost figure — a percentage compared in absolute
    points, not a latency magnitude; the raw per-arm p99_ms stays
    unclassified (CPU latency noise must not gate rounds)."""
    assert bench_diff.classify(
        "integrity_scrub.p99_overhead_pct") == "overhead"
    assert bench_diff.classify(
        "integrity_scrub.steady_state_recompiles_on") == "recompiles"
    assert bench_diff.classify("integrity_scrub.p99_ms_on") is None
    assert bench_diff.classify("integrity_scrub.p99_ms_off") is None
    assert bench_diff.classify("integrity_scrub.scrub_passes") is None


def test_integrity_overhead_growth_is_a_regression():
    old = {"integrity_scrub": {
        "p99_overhead_pct": 1.5, "p99_ms_on": 10.0, "p99_ms_off": 9.9,
        "steady_state_recompiles_on": 0,
    }}
    new = copy.deepcopy(old)
    new["integrity_scrub"]["p99_overhead_pct"] = 3.0   # +1.5pt: in band
    result = bench_diff.compare(old, new)
    assert result["regressions"] == []
    new["integrity_scrub"]["p99_overhead_pct"] = 9.0   # +7.5pt: regression
    result = bench_diff.compare(old, new)
    assert [r["path"] for r in result["regressions"]] == [
        "integrity_scrub.p99_overhead_pct"]


def test_pipeline_sweep_classification():
    """ISSUE 15: the pipeline_sweep scenario rides the key-name rules —
    saturation_qps is a throughput figure, dispatch_overhead_pct an
    absolute-points overhead figure, steady_state_recompiles the zero
    invariant; stage fractions and sha strings are diagnostics."""
    assert bench_diff.classify(
        "pipeline_sweep.depths.2.saturation_qps") == "qps"
    assert bench_diff.classify(
        "pipeline_sweep.depths.2.dispatch_overhead_pct") == "overhead"
    assert bench_diff.classify(
        "pipeline_sweep.depths.2.steady_state_recompiles") == "recompiles"
    assert bench_diff.classify(
        "pipeline_sweep.depths.2.stage_fractions.dispatch") is None


def test_pipeline_sweep_regressions(tmp_path):
    old = {"pipeline_sweep": {
        "serial": {"saturation_qps": 4000.0,
                   "steady_state_recompiles": 0},
        "depths": {"2": {"saturation_qps": 5000.0,
                         "dispatch_overhead_pct": 4.0,
                         "steady_state_recompiles": 0}},
    }}
    new = copy.deepcopy(old)
    new["pipeline_sweep"]["depths"]["2"]["saturation_qps"] = 2000.0
    new["pipeline_sweep"]["depths"]["2"]["dispatch_overhead_pct"] = 12.0
    new["pipeline_sweep"]["depths"]["2"]["steady_state_recompiles"] = 1
    result = bench_diff.compare(old, new)
    assert sorted(r["path"] for r in result["regressions"]) == [
        "pipeline_sweep.depths.2.dispatch_overhead_pct",
        "pipeline_sweep.depths.2.saturation_qps",
        "pipeline_sweep.depths.2.steady_state_recompiles",
    ]


def test_event_overhead_classification():
    """ISSUE 20: the flight recorder's bench keys — per-scenario
    decision counts (events_emitted, tuner_events, tier_events) are
    cadence accounting, never a regression signal; the overhead_pct
    keys ride the absolute-points rule; the added-recompiles count
    rides the zero invariant."""
    assert bench_diff.classify("recall_slo.events_emitted") is None
    assert bench_diff.classify("recall_slo.tuner_events") is None
    assert bench_diff.classify("memory_pressure.tier_events") is None
    assert bench_diff.classify("event_overhead.events_emitted") is None
    assert bench_diff.classify(
        "event_overhead.p50_overhead_pct") == "overhead"
    assert bench_diff.classify(
        "mixed_rw.event_overhead_pct") == "overhead"
    assert bench_diff.classify(
        "event_overhead.events_added_recompiles") == "recompiles"
    assert bench_diff.classify("event_overhead.p50_ms_on") is None
    assert bench_diff.classify("event_overhead.p50_ms_off") is None
    # the end-to-end arm comparison is informational — CI-host noise
    # swamps a ~20us emit — and must never gate a round
    assert bench_diff.classify("event_overhead.arm_delta_pct") is None
    assert bench_diff.classify("event_overhead.emit_us_per_event") is None


def test_event_overhead_growth_is_a_regression():
    old = {"event_overhead": {
        "p50_overhead_pct": 0.3, "p50_ms_on": 5.0, "p50_ms_off": 4.99,
        "events_emitted": 240, "events_added_recompiles": 0,
    }}
    new = copy.deepcopy(old)
    new["event_overhead"]["p50_overhead_pct"] = 1.5    # +1.2pt: in band
    new["event_overhead"]["events_emitted"] = 480      # cadence, not perf
    result = bench_diff.compare(old, new)
    assert result["regressions"] == []
    new["event_overhead"]["p50_overhead_pct"] = 9.0    # +8.7pt: regression
    new["event_overhead"]["events_added_recompiles"] = 2
    result = bench_diff.compare(old, new)
    assert sorted(r["path"] for r in result["regressions"]) == [
        "event_overhead.events_added_recompiles",
        "event_overhead.p50_overhead_pct",
    ]
