"""Diff two JSON summaries of the retired bench.py's schema and flag
performance regressions. Nothing in the tree writes that schema since
PR 33; the tool and its tests wait for their own issue (ROADMAP C11).

    python tools/bench_diff.py OLD.json NEW.json \
        [--qps-drop 0.15] [--recall-drop 0.02] [--bytes-grow 0.25] [--json]

Both files are flattened to dotted numeric paths; a metric is compared
only when BOTH summaries carry it (new scenarios / removed scenarios are
reported as coverage changes, never as regressions). Classification is by
key name, so the tool keeps working as bench grows scenarios:

  qps        — any key named/suffixed `qps` or a top-level `value` whose
               sibling `unit` is qps: regression when it drops by more
               than --qps-drop (relative).
  recall     — keys containing `recall` (excluding deltas/booleans):
               regression when it drops by more than --recall-drop
               (absolute — recall is already a fraction).
  bytes      — `hbm`/`bytes` keys: regression when they GROW by more
               than --bytes-grow (relative).
  recompiles — `recompiles` keys: regression when a steady-state counter
               that was meeting the invariant (0) becomes nonzero, or
               grows at all.
  recovery   — chaos-scenario `recovery_ms` keys: regression when the
               figure more than doubles AND crosses 1s absolute (coarse
               on purpose — recovery is bounded, not benchmarked).
               Chaos `goodput` keys ride the qps rule.

build_throughput (ISSUE 18) names its per-arm rates `host_rows_qps` /
`device_rows_qps` deliberately: build rows/s ride the qps rule, its
recall_*_built keys the recall rule, and steady_state_recompiles the
recompiles rule — no bespoke classifier needed.

memory_pressure (ISSUE 19) rides the same rules per curve point
(p50_qps / recall_at_10 / steady_recompiles), while its curve AXES —
`budget_frac` and `resident_fraction` — are excluded: they describe the
synthetic pressure schedule and the tier placement it forces, which are
scenario design, not code under test.

Exit status: 0 = no regressions, 1 = regressions found (CI-gateable),
2 = usage/file errors. All human output goes to stdout; --json emits the
machine-readable comparison instead.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional


def flatten(obj: Any, prefix: str = "") -> Dict[str, float]:
    """Numeric leaves only, dotted paths; bools excluded (gates, not
    magnitudes); list elements index into the path."""
    out: Dict[str, float] = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            out.update(flatten(v, f"{prefix}.{k}" if prefix else str(k)))
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            out.update(flatten(v, f"{prefix}[{i}]"))
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        out[prefix] = float(obj)
    return out


def classify(path: str, summary: Optional[dict] = None) -> Optional[str]:
    """Metric kind for a flattened path, or None (not perf-compared)."""
    if "trajectory" in path.lower():
        # recall_slo's per-tick convergence trail: it INTENTIONALLY
        # starts mistuned (~0.4 recall at tick 1) and mid-walk estimates
        # vary run to run — diagnostics, never a regression signal
        return None
    leaf = path.rsplit(".", 1)[-1]
    low = leaf.lower()
    if "baseline" in low:
        # the CPU reference measurement drifts with the host, not with
        # the code under test — never a regression signal
        return None
    if "qos_off" in path.lower():
        # the overload scenario's UNSHAPED arm exists to demonstrate the
        # collapse — its goodput is intentionally terrible and noisy
        # (whatever survived before the backlog crossed the deadline);
        # only the shaped arm and the on/off ratio are the signal
        return None
    if "goodput" in low:
        # goodput (replies within deadline) regresses like a QPS figure:
        # covers goodput_ratio_* and any future non-_qps-suffixed key
        return "qps"
    if low in ("shed", "expired", "offered", "served", "dispatched_rows",
               "deadline_ms"):
        # overload-scenario load accounting: magnitudes track the offered
        # rate (2x measured capacity), not code quality — the goodput and
        # gate keys carry the regression signal
        return None
    if low in ("events_emitted", "tuner_events", "tier_events"):
        # flight-recorder decision counts (ISSUE 20): how often the
        # controllers chose to act under a scenario's traffic — cadence
        # accounting, not a perf signal; the *overhead_pct keys carry
        # the ledger's cost gate
        return None
    if low == "value" and summary is not None and (
        summary.get("unit") == "qps"
    ):
        return "qps"
    if low == "qps" or low.endswith("_qps") or low.startswith("qps_"):
        return "qps"
    if low.endswith("overhead_pct"):
        # instrumentation-overhead ratios (e.g. integrity_scrub's mixed
        # p99 with the digest ledger + scrub on vs off): already a
        # percentage, so the threshold is absolute points, not relative
        return "overhead"
    if "recall" in low:
        # deltas/differences around recall are signed diagnostics, not
        # magnitudes to threshold
        if "delta" in low or "vs" in low:
            return None
        return "recall"
    if "recompile" in low:
        return "recompiles"
    if low.endswith("recovery_ms"):
        # chaos-scenario recovery times (kill/restart, failover, remat):
        # wall-clock on a shared CI host, so the gate is coarse — only a
        # large relative blow-up signals a real recovery-path regression
        return "recovery"
    if "working_set" in low:
        # heat_skew's working-set estimate measures the PLANTED traffic
        # pattern (bytes the skewed stream needed resident), not code
        # quality — the bytes-suffix rule below would false-flag it
        return None
    if "resident_fraction" in low or low == "budget_frac":
        # memory_pressure's curve axes: the synthetic budget step and
        # the device-resident share it forces are scenario DESIGN, not
        # code quality — the per-point p50_qps / recall_at_10 /
        # steady_recompiles keys carry the regression signal
        return None
    if "hbm" in low or low.endswith("bytes") or low.endswith(
            "bytes_per_vector"):
        return "bytes"
    return None


def compare(old: dict, new: dict, qps_drop: float = 0.15,
            recall_drop: float = 0.02, bytes_grow: float = 0.25
            ) -> Dict[str, Any]:
    """Full comparison record: per-metric rows + regression list +
    coverage changes."""
    fo, fn = flatten(old), flatten(new)
    rows: List[Dict[str, Any]] = []
    regressions: List[Dict[str, Any]] = []
    for path in sorted(set(fo) & set(fn)):
        kind = classify(path, new if "." not in path else None)
        if kind is None:
            continue
        ov, nv = fo[path], fn[path]
        row = {"path": path, "kind": kind, "old": ov, "new": nv}
        bad = False
        if kind == "qps":
            change = (nv - ov) / ov if ov else 0.0
            row["change"] = round(change, 4)
            bad = ov > 0 and change < -qps_drop
        elif kind == "recall":
            row["change"] = round(nv - ov, 4)
            bad = (ov - nv) > recall_drop
        elif kind == "bytes":
            change = (nv - ov) / ov if ov else 0.0
            row["change"] = round(change, 4)
            bad = ov > 0 and change > bytes_grow
        elif kind == "recompiles":
            row["change"] = round(nv - ov, 4)
            # the steady-state invariant: any growth is a regression
            bad = nv > ov
        elif kind == "recovery":
            # recovery is bounded, not benchmarked: flag only when a
            # recovery that used to be fast blows past double its old
            # figure AND crosses a 1s absolute floor (sub-second jitter
            # on shared hosts is machine weather, not a regression)
            change = (nv - ov) / ov if ov else 0.0
            row["change"] = round(change, 4)
            bad = ov > 0 and change > 1.0 and nv > 1000.0
        elif kind == "overhead":
            # overhead percentages regress when they grow by more than
            # 5 points (the integrity_scrub acceptance bound); shrinking
            # or noise inside the band is fine
            row["change"] = round(nv - ov, 4)
            bad = (nv - ov) > 5.0
        row["regression"] = bad
        rows.append(row)
        if bad:
            regressions.append(row)
    return {
        "compared": len(rows),
        "rows": rows,
        "regressions": regressions,
        "only_old": sorted(p for p in set(fo) - set(fn) if classify(p)),
        "only_new": sorted(p for p in set(fn) - set(fo) if classify(p)),
    }


def _fmt(v: float) -> str:
    return f"{v:g}"


def render(result: Dict[str, Any]) -> str:
    out: List[str] = []
    regs = result["regressions"]
    out.append(
        f"compared {result['compared']} metrics: "
        f"{len(regs)} regression(s)"
    )
    if regs:
        w = max(len(r["path"]) for r in regs)
        for r in regs:
            out.append(
                f"  REGRESSION {r['path'].ljust(w)}  {r['kind']:<10} "
                f"{_fmt(r['old'])} -> {_fmt(r['new'])} "
                f"(change {r['change']:+g})"
            )
    for key, label in (("only_old", "dropped from new"),
                       ("only_new", "new coverage")):
        if result[key]:
            out.append(f"  {label}: {len(result[key])} metric path(s)")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("old", help="baseline bench JSON summary")
    ap.add_argument("new", help="candidate bench JSON summary")
    ap.add_argument("--qps-drop", type=float, default=0.15,
                    help="max tolerated relative QPS drop (default 0.15)")
    ap.add_argument("--recall-drop", type=float, default=0.02,
                    help="max tolerated absolute recall drop "
                         "(default 0.02)")
    ap.add_argument("--bytes-grow", type=float, default=0.25,
                    help="max tolerated relative HBM/bytes growth "
                         "(default 0.25)")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable comparison")
    args = ap.parse_args(argv)
    try:
        with open(args.old) as f:
            old = json.load(f)
        with open(args.new) as f:
            new = json.load(f)
    except (OSError, ValueError) as e:
        print(f"bench_diff: {e}", file=sys.stderr)
        return 2
    result = compare(old, new, qps_drop=args.qps_drop,
                     recall_drop=args.recall_drop,
                     bytes_grow=args.bytes_grow)
    if args.json:
        json.dump(result, sys.stdout, indent=1, sort_keys=True)
        print()
    else:
        print(render(result))
    return 1 if result["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
