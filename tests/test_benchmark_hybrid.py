"""The benchmark's hybrid configuration (`benchmark/configs/hybrid768.json`,
cell `hybrid768.batch64_label10`): what the harness checks before a run
accepts it, and the filtered scan's work function against a hand count."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import readers  # noqa: E402
import run as harness  # noqa: E402

CELL = "hybrid768.batch64_label10"


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cell():
    return (_load("configs", "hybrid768.json"),
            _load("traffic", "batch64_label10.json"))


def test_configuration_and_traffic_pass_check_config(cell):
    config, traffic = cell
    harness.check_config(config, traffic)
    windows = harness.region_windows(config)
    assert [w[:2] for w in windows] == [
        (0, 53_248), (53_248, 106_496), (106_496, 1 << 40)]
    assert sum(w[2] for w in windows) == config["rows"] == 160_000
    assert config["index_parameter"]["scalar_speedup_keys"] == [
        s["field"] for s in config["assumed"]["corpus"]["scalars"]]


@pytest.mark.parametrize("broken", ["no_unmatched_limit", "unknown_field"])
def test_check_config_refuses_what_it_cannot_judge(cell, broken):
    config, traffic = json.loads(json.dumps(cell[0])), \
        json.loads(json.dumps(cell[1]))
    if broken == "no_unmatched_limit":
        del config["limits"]["unmatched_rows"]
    else:
        traffic["search_args"]["predicates"][0]["field"] = "colour"
    with pytest.raises(harness.RunFailure):
        harness.check_config(config, traffic)


def test_filtered_ivf_scan_work_by_hand(cell):
    """Per client request, three regions of 53,333 rows: the union of 64
    queries' 32 probes of 1,024 lists is 889.8 lists of 52.08 rows (46,342 rows); a
    tenth of them match label eq; each match is read at 3,072 B, each
    query scores the matching rows of its 32 lists (a multiply-add per
    dimension), and every probed row's label is read at 8 B."""
    config, traffic = cell
    w = readers.load_work("filtered_ivf_scan")(config, traffic)
    lists = 1024 * (1 - (1 - 32 / 1024) ** 64)
    probed = lists * (160_000 / 3 / 1024)
    assert abs(lists - 889.8) < 0.1 and abs(probed - 46_342) < 1
    assert w["bytes"] == pytest.approx(3 * probed * (0.1 * 3072 + 8))
    assert w["bytes"] == pytest.approx(43.82e6, rel=1e-3)
    assert w["flops"] == pytest.approx(
        3 * 2 * 768 * 64 * 32 * (160_000 / 3 / 1024) * 0.1)
    peaks = readers.load_peaks("TPU v5 lite")
    assert readers.least_seconds(w, peaks) == pytest.approx(
        w["bytes"] / peaks["hbm_bytes_per_s"])


@pytest.mark.parametrize("predicates,share", [
    ([{"field": "label", "op": "eq", "value": 3}], 0.1),
    ([{"field": "label", "op": "ne", "value": 3}], 0.9),
    ([{"field": "label", "op": "in", "value": [1, 2, 2]}], 0.2),
    ([{"field": "num", "op": "lt", "value": 250_000}], 0.25),
    ([{"field": "num", "op": "ge", "value": 250_000}], 0.75),
    ([{"field": "num", "op": "le", "value": {"draw": "uniform_int"}}], 0.5),
    ([{"field": "label", "op": "eq", "value": {"draw": "categorical"}},
      {"field": "num", "op": "gt", "value": 899_999}], 0.01),
])
def test_match_share(cell, predicates, share):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "filtered_ivf_scan", os.path.join(BENCH, "work",
                                          "filtered_ivf_scan.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.match_share(predicates, cell[0]["assumed"]["corpus"][
        "scalars"]) == pytest.approx(share)


def test_benchmark_lists_the_cell():
    bench = _load("..", "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["config"] == "hybrid768"
    assert cells[CELL]["traffic"] == "batch64_label10" and \
        cells[CELL]["chips"] == 1
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"search_qps", "search_p50_ms", "filter_mask_ms",
            "filter_matched_fraction", "post_filter_rows.search",
            "filtered_ivf_scan_roofline", "recall_at_10"} <= listed
    # read one RPC a search or one unmasked region: not this cell's
    assert not listed & {"client_wire_ms", "epoch_stamped_requests.search",
                         "ivf_scan_roofline"}
    for name in listed:
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".json"))
