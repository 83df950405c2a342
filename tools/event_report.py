"""Render a control-plane event dump as a per-region decision timeline.

Input is JSON from any of the ledger's faces:

- the ``events`` section of a flight bundle (``tools/flight_report.py
  BUNDLE --json | jq .events``), or
- an ``EventDumpResponse`` dumped as a JSON list of event objects.

    python tools/event_report.py EVENTS_FILE [--region N] [--actor A] [--json]

The report groups events per region, renders each as TIME NODE ACTOR
KNOB old->new (trigger) evidence, and summarizes per-actor decision
counts — the offline twin of ``cluster events`` for post-incident work
on an exported bundle.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import zlib
from typing import Any, Dict, List


def load_events(path: str) -> List[Dict[str, Any]]:
    """Accepts a JSON list of events, a flight bundle (raw zlib or JSON —
    the ``events`` section is extracted), or an EventDumpResponse-shaped
    object ({"events": [...]})."""
    with open(path, "rb") as f:
        raw = f.read()
    try:
        raw = zlib.decompress(raw)
    except zlib.error:
        pass            # plain JSON already
    doc = json.loads(raw.decode("utf-8"))
    if isinstance(doc, list):
        return doc
    if isinstance(doc, dict) and isinstance(doc.get("events"), list):
        return doc["events"]
    raise SystemExit(f"{path}: no event list found")


def _fmt_time(ts_ms: int) -> str:
    if not ts_ms:
        return "-"
    return time.strftime("%H:%M:%S", time.localtime(ts_ms / 1000.0)) + (
        ".%03d" % (int(ts_ms) % 1000))


def _table(headers: List[str], rows: List[List[str]]) -> List[str]:
    widths = [
        max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
        for i, h in enumerate(headers)
    ]
    out = ["  ".join(h.ljust(w) for h, w in zip(headers, widths)),
           "  ".join("-" * w for w in widths)]
    for r in rows:
        out.append("  ".join(c.ljust(w) for c, w in zip(r, widths)))
    return out


def render(events: List[Dict[str, Any]], region_id: int = 0,
           actor: str = "") -> str:
    """Pure render (tests drive this directly): per-region timelines +
    a per-actor decision tally."""
    events = [
        e for e in events
        if (not region_id or int(e.get("region_id", 0)) == region_id)
        and (not actor or e.get("actor") == actor)
    ]
    if not events:
        return "no matching control-plane events"
    events.sort(key=lambda e: (int(e.get("ts_ms", 0)),
                               str(e.get("node_id", "")),
                               int(e.get("actor_seq", 0))))
    out: List[str] = []
    by_region: Dict[int, List[Dict[str, Any]]] = {}
    for e in events:
        by_region.setdefault(int(e.get("region_id", 0)), []).append(e)
    for rid in sorted(by_region):
        evs = by_region[rid]
        out.append(f"region {rid} — {len(evs)} decision(s)")
        rows = []
        for e in evs:
            rows.append([
                _fmt_time(int(e.get("ts_ms", 0))),
                str(e.get("node_id", "") or "-"),
                str(e.get("actor", "")),
                str(e.get("knob", "")),
                f"{e.get('old') or '-'} -> {e.get('new') or '-'}",
                str(e.get("trigger", "")),
                str(e.get("evidence", "") or "-"),
            ])
        out += _table(
            ["TIME", "NODE", "ACTOR", "KNOB", "CHANGE", "TRIGGER",
             "EVIDENCE"], rows)
        out.append("")
    tally: Dict[str, int] = {}
    for e in events:
        tally[str(e.get("actor", ""))] = tally.get(
            str(e.get("actor", "")), 0) + 1
    out.append("decisions by actor: " + ", ".join(
        f"{a}={n}" for a, n in sorted(tally.items())))
    return "\n".join(out)


def main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(
        description="render a control-plane event dump")
    ap.add_argument("path")
    ap.add_argument("--region", type=int, default=0)
    ap.add_argument("--actor", default="")
    ap.add_argument("--json", action="store_true",
                    help="dump the filtered events as JSON (for jq)")
    args = ap.parse_args(argv)
    events = load_events(args.path)
    if args.json:
        events = [
            e for e in events
            if (not args.region
                or int(e.get("region_id", 0)) == args.region)
            and (not args.actor or e.get("actor") == args.actor)
        ]
        print(json.dumps(events, indent=2, default=str))
        return 0
    print(render(events, region_id=args.region, actor=args.actor))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
