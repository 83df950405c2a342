"""Operator CLI.

Reference: src/client_v2/ (25K LoC CLI11-based interactive CLI with
subcommand groups coordinator/meta/kv/store/vector_index/document_index/
dump/restore/tools) + src/client/ (legacy). This covers the operator
surface over the grpc services: cluster introspection, region ops, vector
and kv exercisers, debug (metrics, failpoints), with an interactive REPL.

Usage:
    python -m dingo_tpu.client.cli --coordinator HOST:PORT \
        --store s0=HOST:PORT [--store s1=...] <group> <command> [args]
"""

from __future__ import annotations

import argparse
import json
import shlex
import sys
from typing import Dict, List

import numpy as np

from dingo_tpu.client.client import DingoClient
from dingo_tpu.server import pb

_ITYPES = {
    "flat": pb.VECTOR_INDEX_TYPE_FLAT,
    "ivf_flat": pb.VECTOR_INDEX_TYPE_IVF_FLAT,
    "ivf_pq": pb.VECTOR_INDEX_TYPE_IVF_PQ,
    "hnsw": pb.VECTOR_INDEX_TYPE_HNSW,
    "binary_flat": pb.VECTOR_INDEX_TYPE_BINARY_FLAT,
    "binary_ivf_flat": pb.VECTOR_INDEX_TYPE_BINARY_IVF_FLAT,
    "bruteforce": pb.VECTOR_INDEX_TYPE_BRUTEFORCE,
    "diskann": pb.VECTOR_INDEX_TYPE_DISKANN,
}


def _fmt_bytes(n: int) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024 or unit == "TB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024.0
    return f"{n}B"


def _render_table(headers: List[str], rows: List[List[str]]) -> str:
    widths = [
        max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
        for i, h in enumerate(headers)
    ]
    line = "  ".join(h.ljust(w) for h, w in zip(headers, widths))
    out = [line, "  ".join("-" * w for w in widths)]
    for r in rows:
        out.append("  ".join(c.ljust(w) for c, w in zip(r, widths)))
    return "\n".join(out)


def format_cluster_top(resp, region_id: int = 0) -> str:
    """`cluster top`: per-store summary + per-region detail tables from a
    GetStoreMetricsResponse (pure render — tests drive it directly)."""
    store_rows = []
    region_rows = []
    diverged = set(getattr(resp, "diverged_region_ids", ()))

    def _recall_cell(recall: float, samples: int) -> str:
        # 0 scored queries = no evidence (sampling off / idle region):
        # '-' beats a misleading 0.000
        return f"{recall:.3f}" if samples else "-"

    def _cache_cell(hits: int, misses: int) -> str:
        # serving-edge cache hit rate; no lookups yet (cache off or no
        # plain-search traffic) renders '-', not a misleading 0%
        total = hits + misses
        return f"{100.0 * hits / total:.0f}%" if total else "-"

    def _heat_cell(hot: float, touches: int) -> str:
        # traffic concentration (mass on the hottest 10% of heat units);
        # no sketch touches = no evidence (heat off / idle) renders '-'
        return f"{hot:.2f}" if touches else "-"

    def _wset_cell(ws: int, touches: int) -> str:
        # bytes to serve 99% of measured traffic at the region's tier
        return _fmt_bytes(int(ws)) if touches else "-"

    for entry in resp.stores:
        m = entry.metrics
        # store-level recall: sample-weighted mean over leader regions
        # with evidence (the quality plane scores on the serving leader)
        q_samples = sum(r.quality_samples for r in m.regions if r.is_leader)
        q_recall = (
            sum(r.quality_recall * r.quality_samples
                for r in m.regions if r.is_leader) / q_samples
            if q_samples else 0.0
        )
        store_rows.append([
            entry.store_id,
            "STALE" if entry.stale else "ok",
            str(len(m.regions)),
            str(sum(1 for r in m.regions if r.is_leader)),
            str(sum(r.key_count for r in m.regions)),
            str(sum(r.vector_count for r in m.regions)),
            _fmt_bytes(sum(r.vector_memory_bytes for r in m.regions)),
            _fmt_bytes(sum(r.device_memory_bytes for r in m.regions)),
            _fmt_bytes(sum(r.device_peak_bytes for r in m.regions)),
            _fmt_bytes(m.device_bytes_in_use),
            f"{sum(r.search_qps for r in m.regions if r.is_leader):.1f}",
            _recall_cell(q_recall, q_samples),
            _wset_cell(sum(r.heat_working_set_p99 for r in m.regions),
                       sum(r.heat_touches for r in m.regions)),
            str(sum(r.qos_queue_depth for r in m.regions)),
            # PRESSURE: worst recent queue-wait watermark across hosted
            # regions (ms) — the figure the shed ladder defends
            "%.0fms" % max(
                (r.qos_queue_wait_ms for r in m.regions), default=0.0
            ),
            str(sum(r.qos_shed_total for r in m.regions)),
            _cache_cell(sum(r.cache_hits for r in m.regions),
                        sum(r.cache_misses for r in m.regions)),
        ])
        for r in m.regions:
            if region_id and r.region_id != region_id:
                continue
            flags = []
            if r.index_building:
                flags.append("building")
            if r.index_build_error:
                flags.append("build-error")
            if not r.index_ready and r.vector_count:
                flags.append("not-ready")
            if r.qos_degrade_level:
                flags.append(f"degraded-l{r.qos_degrade_level}")
            if r.region_id in diverged:
                # replica digest comparison at equal applied indices
                # disagreed (state-integrity plane)
                flags.append("DIVERGED")
            if getattr(r, "integrity_mismatch", False):
                # this replica's own scrub caught its device state
                # disagreeing with the incremental ledger
                flags.append("CORRUPT")
            if getattr(r, "device_degraded", False):
                # device index lost to OOM: serving host-exact until the
                # background re-materialization lands (index/recovery.py)
                flags.append("DEV-DEGRADED")
            region_rows.append([
                str(r.region_id),
                entry.store_id,
                "L" if r.is_leader else "F",
                str(r.key_count),
                str(r.vector_count),
                _fmt_bytes(r.vector_memory_bytes),
                _fmt_bytes(r.device_memory_bytes),
                _fmt_bytes(r.device_peak_bytes),
                str(r.apply_lag),
                f"{r.search_qps:.1f}",
                _recall_cell(r.quality_recall, r.quality_samples),
                # memory-tier ladder rung serving this region's reads
                # ("" from pre-tiering stores renders as '-')
                getattr(r, "serving_tier", "") or "-",
                _heat_cell(r.heat_hot_fraction, r.heat_touches),
                _wset_cell(r.heat_working_set_p99, r.heat_touches),
                str(r.qos_queue_depth),
                f"{r.qos_queue_wait_ms:.0f}ms",
                str(r.qos_shed_total),
                _cache_cell(r.cache_hits, r.cache_misses),
                ",".join(flags) or "-",
            ])
    region_rows.sort(key=lambda r: (int(r[0]), r[1]))
    out = [
        _render_table(
            ["STORE", "METRICS", "REGIONS", "LEADERS", "KEYS", "VECTORS",
             "MEM", "DEVMEM", "DEVPEAK", "DEV-IN-USE", "QPS", "RECALL",
             "WSET", "QDEPTH", "PRESS", "SHED", "CACHE"],
            store_rows,
        ),
        "",
        _render_table(
            ["REGION", "STORE", "ROLE", "KEYS", "VECTORS", "MEM", "DEVMEM",
             "DEVPEAK", "LAG", "QPS", "RECALL", "TIER", "HEAT", "WSET",
             "QDEPTH", "PRESS", "SHED", "CACHE", "FLAGS"],
            region_rows,
        ),
    ]
    return "\n".join(out)


def format_cluster_capacity(resp, store_id: str = "") -> str:
    """`cluster capacity`: per-store headroom-vs-demand table plus the
    advisory list, rendered from a GetStoreMetricsResponse. The plan is
    recomputed client-side with the SAME pure functions the coordinator
    heartbeat hook runs (coordinator/capacity.plan_store, duck-typed
    over pb messages) — no second RPC, no divergent math. Demote
    advisories actuate through the coordinator's TIER_DEMOTE handshake
    when the store runs with tier.enabled (index/tiering.py); this
    rendering path itself never actuates."""
    from dingo_tpu.coordinator import capacity as cap

    store_rows = []
    advice_rows = []
    for entry in resp.stores:
        if store_id and entry.store_id != store_id:
            continue
        plan = cap.plan_store(entry.metrics)
        sid = plan["store_id"] or entry.store_id
        touches = plan["touches"]
        store_rows.append([
            sid,
            "STALE" if entry.stale else "ok",
            _fmt_bytes(plan["limit_bytes"]),
            _fmt_bytes(plan["in_use_bytes"]),
            _fmt_bytes(plan["headroom_bytes"]),
            f"{plan['headroom_frac']:.0%}",
            # demand/cold columns need sketch evidence to mean anything
            _fmt_bytes(plan["demand_p99_bytes"]) if touches else "-",
            _fmt_bytes(plan["resident_bytes"]),
            str(touches),
            str(len(plan["advice"])),
        ])
        for a in plan["advice"]:
            advice_rows.append([
                sid,
                str(a.region_id),
                a.kind,
                _fmt_bytes(a.bytes_at_stake),
                a.reason,
            ])
    out = [
        _render_table(
            ["STORE", "METRICS", "LIMIT", "IN-USE", "HEADROOM", "FREE%",
             "DEMAND-P99", "RESIDENT", "TOUCHES", "ADVICE"],
            store_rows,
        ),
    ]
    if advice_rows:
        out += [
            "",
            _render_table(
                ["STORE", "REGION", "KIND", "AT-STAKE", "WHY"],
                advice_rows,
            ),
        ]
    else:
        out += ["", "no capacity advisories"]
    return "\n".join(out)


def format_cluster_consistency(resp, region_id: int = 0) -> str:
    """`cluster consistency`: per-(region, store) per-artifact digest
    table from a GetRegionMetricsResponse, with a replica-comparison
    verdict per region (pure render — tests drive it directly).

    Verdict semantics: replicas are comparable only at EQUAL applied
    indices; 'ok' = every comparable pair agrees on every shared
    artifact, 'DIVERGED' = some comparable pair disagrees (or the
    coordinator flagged it), 'lagging' = no two replicas sit at the same
    applied index yet, '-' = no digest evidence."""
    import json as _json

    per_region: Dict[int, List] = {}
    for entry in resp.regions:
        m = entry.metrics
        if region_id and m.region_id != region_id:
            continue
        per_region.setdefault(m.region_id, []).append(
            (entry.store_id, entry.stale, m)
        )
    diverged = set(getattr(resp, "diverged_region_ids", ()))
    rows = []
    verdicts = []
    for rid in sorted(per_region):
        replicas = per_region[rid]
        vectors = []          # (store, applied, {artifact: digest})
        for sid, stale, m in replicas:
            digests = {}
            if m.integrity_digests:
                try:
                    digests = _json.loads(m.integrity_digests)
                except ValueError:
                    digests = {}
            vectors.append((sid, stale, m, digests))
            arts = sorted(digests) or ["-"]
            for art in arts:
                d = digests.get(art, "")
                rows.append([
                    str(rid),
                    sid,
                    str(m.integrity_applied_index),
                    art,
                    # digest hex is count-s0-s1; show count + a short
                    # prefix (full vectors via --json / GetRegionMetrics)
                    d.split("-")[0] if d else "-",
                    (d.split("-")[1][:12] if d else "-"),
                    ("STALE" if stale else
                     ("CORRUPT" if m.integrity_mismatch else "ok")),
                ])
        # replica comparison at equal applied indices
        verdict = "-"
        compared = False
        bad = rid in diverged
        for i in range(len(vectors)):
            for j in range(i + 1, len(vectors)):
                _si, _st, mi, di = vectors[i]
                _sj, _stj, mj, dj = vectors[j]
                if not di or not dj:
                    continue
                if mi.integrity_applied_index != mj.integrity_applied_index:
                    continue
                compared = True
                if any(di[a] != dj[a] for a in set(di) & set(dj)):
                    bad = True
        if bad:
            verdict = "DIVERGED"
        elif compared:
            verdict = "ok"
        elif any(v[3] for v in vectors):
            verdict = "lagging" if len(vectors) > 1 else "single"
        verdicts.append([str(rid), str(len(replicas)), verdict])
    out = [
        _render_table(
            ["REGION", "STORE", "APPLIED", "ARTIFACT", "COUNT", "DIGEST",
             "STATUS"],
            rows,
        ),
        "",
        _render_table(["REGION", "REPLICAS", "VERDICT"], verdicts),
    ]
    return "\n".join(out)


def _fmt_event_time(ts_ms: int) -> str:
    import datetime

    if not ts_ms:
        return "-"
    return datetime.datetime.fromtimestamp(
        ts_ms / 1000.0).strftime("%H:%M:%S.%f")[:-3]


def format_cluster_events(resp, limit: int = 0) -> str:
    """`cluster events`: the merged control-plane decision timeline from
    an EventDumpResponse, oldest first (pure render — tests drive it
    directly). Evidence stays compact JSON: it IS the exact inputs the
    controller read, abbreviating it would defeat the ledger."""
    rows = []
    events = list(resp.events)
    if limit and len(events) > limit:
        events = events[-limit:]
    for e in events:
        rows.append([
            _fmt_event_time(e.ts_ms),
            e.node_id or "-",
            e.actor,
            str(e.region_id),
            e.knob,
            f"{e.old or '-'} -> {e.new or '-'}",
            e.trigger,
            e.evidence or "-",
        ])
    out = [_render_table(
        ["TIME", "NODE", "ACTOR", "REGION", "KNOB", "CHANGE", "TRIGGER",
         "EVIDENCE"],
        rows,
    )]
    if not rows:
        out = ["no control-plane events recorded"]
    dropped = int(getattr(resp, "dropped", 0))
    if dropped:
        out.append(f"({dropped} events dropped to the ring bound — "
                   "raise events.max_entries for longer memory)")
    return "\n".join(out)


def format_cluster_explain(report) -> str:
    """`cluster explain <region>`: every live override accounted for as
    its decision chain, orphans called out (pure render over the
    obs/events.explain_region report — tests drive it directly)."""
    rid = report["region_id"]
    out = [f"region {rid}: {len(report['live'])} live override(s)"]
    if not report["live"]:
        out.append("  serving at configured defaults — nothing to explain")
    for entry in report["entries"]:
        knob, value = entry["knob"], entry["value"]
        if entry["explained"]:
            out.append(f"  {knob} = {value}")
        else:
            out.append(f"  {knob} = {value}   ** ORPHAN: no explaining "
                       "event (ring forgot, or a writer bypassed the "
                       "ledger) **")
        for e in entry["chain"]:
            out.append(
                f"    {_fmt_event_time(e.ts_ms)} [{e.node_id or '-'}] "
                f"{e.actor}: {e.knob} {e.old or '-'} -> {e.new or '-'} "
                f"({e.trigger}) {e.evidence or ''}".rstrip()
            )
    if report["orphans"]:
        out.append(f"  orphan knobs: {', '.join(report['orphans'])}")
    return "\n".join(out)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="dingo-cli")
    p.add_argument("--coordinator", default="127.0.0.1:20001",
                   help="coordinator endpoint, or comma-separated list of "
                        "the replicated group (client rotates on failover)")
    p.add_argument("--store", action="append", default=[],
                   help="store_id=host:port (repeatable)")
    sub = p.add_subparsers(dest="group")

    coord = sub.add_parser("coordinator").add_subparsers(dest="cmd")
    coord.add_parser("hello")
    coord.add_parser("region-map")
    tso = coord.add_parser("tso")
    tso.add_argument("--count", type=int, default=1)

    region = sub.add_parser("region").add_subparsers(dest="cmd")
    create = region.add_parser("create-index")
    create.add_argument("--partition", type=int, default=0)
    create.add_argument("--id-lo", type=int, default=0)
    create.add_argument("--id-hi", type=int, default=1 << 40)
    create.add_argument("--type", choices=sorted(_ITYPES), default="flat")
    create.add_argument("--dim", type=int, required=True)
    merge = region.add_parser("merge")
    merge.add_argument("--target", type=int, required=True)
    merge.add_argument("--source", type=int, required=True)
    cpeers = region.add_parser("change-peers")
    cpeers.add_argument("--region", type=int, required=True)
    cpeers.add_argument("--peers", required=True,
                        help="comma-separated store ids")
    tleader = region.add_parser("transfer-leader")
    tleader.add_argument("--region", type=int, required=True)
    tleader.add_argument("--store", required=True)
    split = region.add_parser("split")
    split.add_argument("--region", type=int, required=True)
    split.add_argument("--at", type=int, required=True)
    split.add_argument("--partition", type=int, default=0)

    vec = sub.add_parser("vector").add_subparsers(dest="cmd")
    vadd = vec.add_parser("add-random")
    vadd.add_argument("--partition", type=int, default=0)
    vadd.add_argument("--count", type=int, default=100)
    vadd.add_argument("--dim", type=int, required=True)
    vadd.add_argument("--start-id", type=int, default=0)
    vsearch = vec.add_parser("search-random")
    vsearch.add_argument("--partition", type=int, default=0)
    vsearch.add_argument("--dim", type=int, required=True)
    vsearch.add_argument("--topk", type=int, default=5)
    vsearch.add_argument("--deadline-ms", type=float, default=0.0,
                         help="per-request time budget propagated to the "
                              "store (0 = none); expired work is rejected "
                              "at admission when qos.enabled")
    vsearch.add_argument("--tenant", default="",
                         help="tenant id for per-tenant QoS accounting")
    vsearch.add_argument("--priority", type=int, default=None,
                         help="0 = batch (shed first), 1 = default, "
                              ">= 2 = interactive (never pressure-shed); "
                              "unset = no QoS budget attached unless "
                              "--deadline-ms/--tenant is given")
    vcount = vec.add_parser("count")
    vcount.add_argument("--partition", type=int, default=0)

    kv = sub.add_parser("kv").add_subparsers(dest="cmd")
    kput = kv.add_parser("put")
    kput.add_argument("key")
    kput.add_argument("value")
    kget = kv.add_parser("get")
    kget.add_argument("key")

    doc = sub.add_parser("document").add_subparsers(dest="cmd")
    dcreate = doc.add_parser("create-region")
    dcreate.add_argument("--partition", type=int, default=0)
    dcreate.add_argument("--id-lo", type=int, default=0)
    dcreate.add_argument("--id-hi", type=int, default=1 << 40)
    dcreate.add_argument("--schema", default="",
                         help="name:type,... (types: text/i64/f64/bytes/"
                              "bool); empty = schemaless")
    dadd = doc.add_parser("add")
    dadd.add_argument("--region", type=int, required=True)
    dadd.add_argument("--id", type=int, required=True)
    dadd.add_argument("fields", nargs="+",
                      help="name=value pairs (value parsed as JSON when "
                           "possible, else string)")
    dsearch = doc.add_parser("search")
    dsearch.add_argument("--region", type=int, required=True)
    dsearch.add_argument("--topk", type=int, default=10)
    dsearch.add_argument("--mode", default="query",
                         choices=("query", "or", "and", "phrase"))
    dsearch.add_argument("query")
    dcount = doc.add_parser("count")
    dcount.add_argument("--region", type=int, required=True)

    txn = sub.add_parser("txn").add_subparsers(dest="cmd")
    tput = txn.add_parser("put")          # one-shot transactional put
    tput.add_argument("key")
    tput.add_argument("value")
    tput.add_argument("--pessimistic", action="store_true")
    tget = txn.add_parser("get")
    tget.add_argument("key")
    tlocks = txn.add_parser("scan-locks")
    tlocks.add_argument("--max-ts", type=int, default=0)
    tlocks.add_argument("--limit", type=int, default=100)
    tres = txn.add_parser("resolve")
    tres.add_argument("--start-ts", type=int, required=True)
    tres.add_argument("--commit-ts", type=int, default=0)
    tgc = txn.add_parser("gc")
    tgc.add_argument("--safe-ts", type=int, required=True)
    tdump = txn.add_parser("dump")
    tdump.add_argument("--region", type=int, required=True)
    tdump.add_argument("--limit", type=int, default=100)

    dbg = sub.add_parser("debug").add_subparsers(dest="cmd")
    met = dbg.add_parser("metrics")
    met.add_argument("--store", dest="target_store", required=True)
    tr = dbg.add_parser("trace")
    tr.add_argument("--store", dest="target_store", required=True)
    tr.add_argument("--chrome", action="store_true",
                    help="Chrome trace_event form (chrome://tracing / "
                         "Perfetto / tools/trace_report.py) instead of "
                         "the grouped-by-trace JSON")
    prof = dbg.add_parser("profile")
    prof.add_argument("--store", dest="target_store", required=True)
    prof.add_argument("--seconds", type=float, default=5.0)
    prof.add_argument("--dir", default="",
                      help="directory on the STORE's machine for the "
                           ".xplane.pb and spans.json (default: a fresh "
                           "temporary one, named in the reply)")
    fp = dbg.add_parser("failpoint")
    fp.add_argument("--store", dest="target_store", required=True)
    fp.add_argument("name")
    fp.add_argument("config", nargs="?", default="")
    fp.add_argument("--remove", action="store_true")

    node = sub.add_parser("node").add_subparsers(dest="cmd")
    ninfo = node.add_parser("info")
    ninfo.add_argument("--store", dest="target_store", required=True)
    nlog = node.add_parser("log-level")
    nlog.add_argument("--store", dest="target_store", required=True)
    nlog.add_argument("--module", default="")
    nlog.add_argument("level", nargs="?", default="",
                      help="DEBUG/INFO/WARNING/ERROR; omit to list levels")

    meta = sub.add_parser("meta").add_subparsers(dest="cmd")
    meta.add_parser("schemas")
    cs = meta.add_parser("create-schema")
    cs.add_argument("name")
    ct = meta.add_parser("create-table")
    ct.add_argument("--schema", default="dingo")
    ct.add_argument("name")
    ct.add_argument("--type", choices=sorted(_ITYPES), default="flat")
    ct.add_argument("--dim", type=int, required=True)
    ct.add_argument("--partitions", type=int, default=1)
    ct.add_argument("--rows-per-partition", type=int, default=1 << 30)
    ct.add_argument("--partition-base", type=int, default=None,
                    help="first partition id (default: after the highest "
                         "in use, so tables never collide)")
    lt = meta.add_parser("tables")
    lt.add_argument("--schema", default="dingo")
    gt = meta.add_parser("table")
    gt.add_argument("--schema", default="dingo")
    gt.add_argument("name")
    dt = meta.add_parser("drop-table")
    dt.add_argument("--schema", default="dingo")
    dt.add_argument("name")

    cluster = sub.add_parser("cluster").add_subparsers(dest="cmd")
    cluster.add_parser("stat")
    top = cluster.add_parser("top")   # per-store/per-region metrics table
    top.add_argument("--store", dest="target_store", default="",
                     help="limit to one store id")
    top.add_argument("--region", type=int, default=0,
                     help="limit the region table to one region id")
    capacity = cluster.add_parser("capacity")  # headroom vs heat demand
    capacity.add_argument("--store", dest="target_store", default="",
                          help="limit to one store id")
    consistency = cluster.add_parser("consistency")
    consistency.add_argument("--region", type=int, default=0,
                             help="limit to one region id")
    events = cluster.add_parser("events")  # merged decision timeline
    events.add_argument("--region", type=int, default=0,
                        help="limit to one region id")
    events.add_argument("--actor", default="",
                        help="limit to one controller (tuner/shed/tier/"
                             "recovery/planner/capacity/cache)")
    events.add_argument("--limit", type=int, default=50,
                        help="newest N events (0 = everything merged)")
    explain = cluster.add_parser("explain")  # live overrides -> chains
    explain.add_argument("region", type=int,
                         help="region id to explain")
    jobs = cluster.add_parser("jobs")
    jobs.add_argument("--include-done", action="store_true")
    detail = cluster.add_parser("region-detail")
    detail.add_argument("--store", dest="target_store", required=True)
    detail.add_argument("--region", type=int, required=True)
    rbi = cluster.add_parser("rebuild-index")
    rbi.add_argument("--store", dest="target_store", required=True)
    rbi.add_argument("--region", type=int, required=True)
    snap = cluster.add_parser("snapshot-index")
    snap.add_argument("--store", dest="target_store", required=True)
    snap.add_argument("--region", type=int, required=True)

    sdbg = sub.add_parser("search-debug")
    sdbg.add_argument("--partition", type=int, default=0)
    sdbg.add_argument("--dim", type=int, required=True)
    sdbg.add_argument("--topk", type=int, default=5)

    # dump/restore tooling (client_v2 dump/restore, main.cc:225-237)
    dump = sub.add_parser("dump").add_subparsers(dest="cmd")
    dr = dump.add_parser("region")
    dr.add_argument("--region", type=int, required=True)
    dr.add_argument("--out", required=True)
    di = dump.add_parser("inspect")
    di.add_argument("--file", required=True)
    di.add_argument("--keys", type=int, default=0,
                    help="also print the first N keys per CF")
    ds = dump.add_parser("index-snapshot")
    ds.add_argument("--store", dest="target_store", required=True)
    ds.add_argument("--region", type=int, required=True)

    br = sub.add_parser("br").add_subparsers(dest="cmd")
    bb = br.add_parser("backup")
    bb.add_argument("--dir", required=True)
    bb.add_argument("--no-resume", action="store_true",
                    help="ignore progress.json and redo every region")
    rr = br.add_parser("restore")
    rr.add_argument("--dir", required=True)

    sub.add_parser("repl")
    return p


def _document_region(client: DingoClient, region_id: int):
    client.refresh_region_map()
    d = next((r for r in client._regions if r.region_id == region_id), None)
    if d is None:
        print(f"region {region_id} not found", file=sys.stderr)
    return d


def run_command(client: DingoClient, args) -> int:
    g, c = args.group, getattr(args, "cmd", None)
    if g == "coordinator" and c == "hello":
        r = client.coordinator.Hello(pb.HelloRequest())
        print(json.dumps({"stores": r.store_count, "regions": r.region_count}))
    elif g == "coordinator" and c == "region-map":
        client.refresh_region_map()
        for d in client._regions:
            print(json.dumps({
                "region_id": d.region_id,
                "partition": d.partition_id,
                "peers": d.peers,
                "epoch": d.epoch.as_tuple(),
                "index": d.index_parameter.index_type.value
                if d.index_parameter else None,
            }))
    elif g == "coordinator" and c == "tso":
        print(client.tso(args.count))
    elif g == "region" and c == "create-index":
        param = pb.VectorIndexParameter(
            index_type=_ITYPES[args.type], dimension=args.dim,
            metric_type=pb.METRIC_TYPE_L2,
        )
        d = client.create_index_region(args.partition, args.id_lo,
                                       args.id_hi, param)
        print(json.dumps({"region_id": d.region_id, "peers": d.peers}))
    elif g == "region" and c == "split":
        child = client.split_region(args.region, args.at, args.partition)
        print(json.dumps({"child_region_id": child}))
    elif g == "region" and c == "merge":
        client.merge_region(args.target, args.source)
        print(json.dumps({"merged_into": args.target}))
    elif g == "region" and c == "change-peers":
        peers = [p.strip() for p in args.peers.split(",") if p.strip()]
        client.change_peer_region(args.region, peers)
        print(json.dumps({"region": args.region, "peers": peers}))
    elif g == "region" and c == "transfer-leader":
        client.transfer_leader_region(args.region, args.store)
        print(json.dumps({"region": args.region, "leader": args.store}))
    elif g == "vector" and c == "add-random":
        rng = np.random.default_rng(0)
        x = rng.standard_normal((args.count, args.dim)).astype(np.float32)
        ids = list(range(args.start_id, args.start_id + args.count))
        client.vector_add(args.partition, ids, x)
        print(json.dumps({"added": args.count}))
    elif g == "vector" and c == "search-random":
        rng = np.random.default_rng(1)
        q = rng.standard_normal((1, args.dim)).astype(np.float32)
        res = client.vector_search(
            args.partition, q, topk=args.topk,
            deadline_ms=args.deadline_ms or None,
            tenant=args.tenant, priority=args.priority,
        )
        print(json.dumps([[int(i), float(d)] for i, d in res[0]]))
    elif g == "vector" and c == "count":
        print(client.vector_count(args.partition))
    elif g == "kv" and c == "put":
        client.kv_put(args.key.encode(), args.value.encode())
        print("OK")
    elif g == "kv" and c == "get":
        v = client.kv_get(args.key.encode())
        print(v.decode() if v is not None else "(nil)")
    elif g == "document" and c == "create-region":
        schema = None
        if args.schema:
            schema = {}
            for part in args.schema.split(","):
                name, _, ftype = part.strip().partition(":")
                schema[name] = ftype or "text"
        d = client.create_document_region(
            args.partition, args.id_lo, args.id_hi, schema=schema)
        print(json.dumps({"region_id": d.region_id, "peers": d.peers,
                          "schema": schema}))
    elif g == "document" and c == "add":
        from dingo_tpu.server.convert import scalar_to_pb

        doc_fields = {}
        for pair in args.fields:
            name, _, raw = pair.partition("=")
            try:
                doc_fields[name] = json.loads(raw)
            except ValueError:
                doc_fields[name] = raw
        d = _document_region(client, args.region)
        if d is None:
            return 1
        req = pb.DocumentAddRequest()
        req.context.region_id = args.region
        e = req.documents.add()
        e.id = args.id
        scalar_to_pb(e.fields, doc_fields)
        resp = client._call_leader(d, "DocumentService", "DocumentAdd", req)
        print(json.dumps({"added": 1, "ts": resp.ts}))
    elif g == "document" and c == "search":
        d = _document_region(client, args.region)
        if d is None:
            return 1
        req = pb.DocumentSearchRequest()
        req.context.region_id = args.region
        req.query = args.query
        req.mode = args.mode
        req.top_n = args.topk
        resp = client._call_leader(
            d, "DocumentService", "DocumentSearch", req)
        print(json.dumps([[doc.id, round(doc.score, 4)]
                          for doc in resp.documents]))
    elif g == "document" and c == "count":
        d = _document_region(client, args.region)
        if d is None:
            return 1
        resp = client._call_leader(
            d, "DocumentService", "DocumentCount",
            pb.DocumentCountRequest(
                context=pb.Context(region_id=args.region)))
        print(json.dumps({"count": resp.count}))
    elif g == "txn" and c == "put":
        t = client.begin_txn(pessimistic=args.pessimistic)
        key = args.key.encode()
        if args.pessimistic:
            t.lock([key])
        t.put(key, args.value.encode())
        commit_ts = t.commit()
        print(json.dumps({"start_ts": t.start_ts, "commit_ts": commit_ts}))
    elif g == "txn" and c == "get":
        t = client.begin_txn()
        v = t.get(args.key.encode())
        print(v.decode() if v is not None else "(nil)")
    elif g == "txn" and c == "scan-locks":
        locks = client.txn_scan_lock(max_ts=args.max_ts, limit=args.limit)
        for li in locks:
            print(json.dumps({
                "key": li.key.hex(), "lock_ts": li.lock_ts,
                "primary": li.primary_lock.hex(), "op": li.op,
                "ttl_ms": li.ttl_ms,
            }))
        print(json.dumps({"locks": len(locks)}))
    elif g == "txn" and c == "resolve":
        n = client.txn_resolve_lock(args.start_ts, args.commit_ts)
        print(json.dumps({"resolved": n}))
    elif g == "txn" and c == "gc":
        n = client.txn_gc(args.safe_ts)
        print(json.dumps({"deleted": n}))
    elif g == "txn" and c == "dump":
        d = client.txn_dump(args.region, limit=args.limit)
        print(json.dumps({
            "locks": len(d.locks), "writes": len(d.writes),
            "datas": len(d.datas),
        }))
    elif g == "debug" and c == "metrics":
        stub = client._stub(args.target_store, "DebugService")
        print(stub.MetricsDump(pb.MetricsDumpRequest()).json)
    elif g == "debug" and c == "trace":
        stub = client._stub(args.target_store, "DebugService")
        if args.chrome:
            print(stub.TraceChromeDump(pb.MetricsDumpRequest()).json)
        else:
            print(stub.TraceDump(pb.MetricsDumpRequest()).json)
    elif g == "debug" and c == "profile":
        stub = client._stub(args.target_store, "DebugService")
        r = stub.DeviceProfile(pb.MetricsDumpRequest(format=json.dumps(
            {"seconds": args.seconds, "dir": args.dir})))
        print(r.json if r.error.errcode == 0 else r.error.errmsg)
    elif g == "debug" and c == "failpoint":
        stub = client._stub(args.target_store, "DebugService")
        r = stub.FailPoint(pb.FailPointRequest(
            name=args.name, config=args.config, remove=args.remove))
        print("OK" if r.error.errcode == 0 else r.error.errmsg)
    elif g == "node" and c == "info":
        stub = client._stub(args.target_store, "NodeService")
        r = stub.NodeInfo(pb.NodeInfoRequest())
        print(json.dumps({
            "store_id": r.store_id,
            "regions": list(r.region_ids),
            "leader_regions": list(r.leader_region_ids),
        }))
    elif g == "node" and c == "log-level":
        stub = client._stub(args.target_store, "NodeService")
        if args.level:
            r = stub.SetLogLevel(pb.SetLogLevelRequest(
                level=args.level, module=args.module))
            if r.error.errcode:
                print(json.dumps({"error": r.error.errmsg}))
                return 1
            print(json.dumps({"level": args.level.upper(),
                              "module": args.module or "<all>"}))
        else:
            r = stub.GetLogLevel(pb.GetLogLevelRequest())
            if r.error.errcode:
                print(json.dumps({"error": r.error.errmsg}))
                return 1
            print(json.dumps({e.module: e.level for e in r.levels}))
    elif g == "meta" and c == "schemas":
        print(json.dumps(client.get_schemas()))
    elif g == "meta" and c == "create-schema":
        client.create_schema(args.name)
        print("OK")
    elif g == "meta" and c == "create-table":
        param = pb.VectorIndexParameter(
            index_type=_ITYPES[args.type], dimension=args.dim,
            metric_type=(
                pb.METRIC_TYPE_HAMMING if args.type.startswith("binary")
                else pb.METRIC_TYPE_L2
            ),
        )
        base = args.partition_base
        if base is None:
            taken = [
                p.partition_id
                for schema in client.get_schemas()
                for t in client.list_tables(schema)
                for p in t.partitions
            ]
            base = max(taken, default=0) + 1
        parts = [
            (base + i, i * args.rows_per_partition,
             (i + 1) * args.rows_per_partition)
            for i in range(args.partitions)
        ]
        t = client.create_vector_table(args.schema, args.name, param,
                                       partitions=parts)
        print(json.dumps({
            "table_id": t.table_id,
            "regions": [p.region_id for p in t.partitions],
        }))
    elif g == "meta" and c == "tables":
        for t in client.list_tables(args.schema):
            print(json.dumps({"name": t.name, "table_id": t.table_id,
                              "partitions": len(t.partitions)}))
    elif g == "meta" and c == "table":
        t = client.get_table(args.schema, args.name)
        if t is None:
            print("(not found)", file=sys.stderr)
            return 1
        print(json.dumps({
            "name": t.name, "table_id": t.table_id,
            "partitions": [
                {"partition_id": p.partition_id, "id_lo": p.id_lo,
                 "id_hi": p.id_hi, "region_id": p.region_id}
                for p in t.partitions
            ],
        }))
    elif g == "meta" and c == "drop-table":
        client.drop_table(args.schema, args.name)
        print("OK")
    elif g == "cluster" and c == "stat":
        stub = client.coordinator_service("ClusterStatService")
        r = stub.GetClusterStat(pb.GetClusterStatRequest())
        print(json.dumps({
            "stores": r.store_count, "alive": r.alive_store_count,
            "regions": r.region_count, "pending_jobs": r.pending_job_count,
            "per_store": [
                {"id": st.store_id, "state": st.state,
                 "regions": st.region_count, "leaders": st.leader_count}
                for st in r.stores
            ],
        }))
    elif g == "cluster" and c == "top":
        stub = client.coordinator_service("ClusterStatService")
        r = stub.GetStoreMetrics(
            pb.GetStoreMetricsRequest(store_id=args.target_store)
        )
        print(format_cluster_top(r, region_id=args.region))
    elif g == "cluster" and c == "capacity":
        stub = client.coordinator_service("ClusterStatService")
        r = stub.GetStoreMetrics(
            pb.GetStoreMetricsRequest(store_id=args.target_store)
        )
        print(format_cluster_capacity(r, store_id=args.target_store))
    elif g == "cluster" and c == "consistency":
        stub = client.coordinator_service("ClusterStatService")
        r = stub.GetRegionMetrics(
            pb.GetRegionMetricsRequest(region_id=args.region)
        )
        print(format_cluster_consistency(r, region_id=args.region))
    elif g == "cluster" and c == "events":
        stub = client.coordinator_service("ClusterStatService")
        r = stub.EventDump(pb.EventDumpRequest(
            region_id=args.region, actor=args.actor, limit=args.limit,
        ))
        print(format_cluster_events(r, limit=args.limit))
    elif g == "cluster" and c == "explain":
        # live overrides from the freshest replica rows + the merged
        # timeline, reconciled with the SAME pure function the
        # coordinator runs (obs/events.explain_region — no divergent
        # logic between the RPC face and the CLI)
        from dingo_tpu.obs.events import explain_region, live_overrides
        from dingo_tpu.server import convert as _convert

        stub = client.coordinator_service("ClusterStatService")
        rmet = stub.GetRegionMetrics(
            pb.GetRegionMetricsRequest(region_id=args.region)
        )
        live = {}
        for entry in rmet.regions:
            if entry.stale:
                continue
            if entry.metrics.is_leader or not live:
                live = live_overrides(entry.metrics)
        edump = stub.EventDump(pb.EventDumpRequest(region_id=args.region))
        events = [_convert.control_event_from_pb(e) for e in edump.events]
        print(format_cluster_explain(
            explain_region(args.region, live, events)))
    elif g == "cluster" and c == "jobs":
        stub = client.coordinator_service("JobService")
        r = stub.ListJobs(pb.ListJobsRequest(include_done=args.include_done))
        for j in r.jobs:
            print(json.dumps({
                "cmd_id": j.cmd_id, "region": j.region_id,
                "type": j.cmd_type, "status": j.status, "store": j.store_id,
            }))
    elif g == "cluster" and c == "region-detail":
        stub = client._stub(args.target_store, "RegionControlService")
        r = stub.RegionDetail(pb.RegionDetailRequest(region_id=args.region))
        if r.error.errcode:
            print(r.error.errmsg, file=sys.stderr)
            return 1
        print(json.dumps({
            "region_id": r.definition.region_id, "state": r.state,
            "is_leader": r.is_leader, "raft_term": r.raft_term,
            "commit_index": r.raft_commit_index,
            "last_applied": r.raft_last_applied,
            "index_count": r.index_count,
            "index_apply_log_id": r.index_apply_log_id,
        }))
    elif g == "cluster" and c == "rebuild-index":
        stub = client._stub(args.target_store, "RegionControlService")
        r = stub.RegionRebuildIndex(
            pb.RegionRebuildIndexRequest(region_id=args.region))
        print("OK" if r.error.errcode == 0 else r.error.errmsg)
    elif g == "cluster" and c == "snapshot-index":
        stub = client._stub(args.target_store, "RegionControlService")
        r = stub.RegionSnapshot(
            pb.RegionSnapshotRequest(region_id=args.region))
        print(r.path if r.error.errcode == 0 else r.error.errmsg)
    elif g == "search-debug":
        rng = np.random.default_rng(1)
        q = rng.standard_normal(args.dim).astype(np.float32)
        regions = client._regions_for_vector_ids(args.partition)
        if not regions:
            print(f"no indexed region in partition {args.partition}",
                  file=sys.stderr)
            return 1
        d = regions[0]
        req = pb.VectorSearchDebugRequest()
        req.context.region_id = d.region_id
        req.vectors.add().values.extend(q.tolist())
        req.parameter.top_n = args.topk
        r = client._call_leader(d, "IndexService", "VectorSearchDebug", req)
        print(json.dumps({
            "results": [
                [i.vector.id, round(i.distance, 4)]
                for i in r.batch_results[0].results
            ],
            "stage_us": {
                "prefilter": r.prefilter_us, "search": r.search_us,
                "postfilter": r.postfilter_us, "backfill": r.backfill_us,
                "total": r.total_us,
            },
        }))
    elif g == "dump" and c == "region":
        from dingo_tpu.br.remote import RemoteBr

        client.refresh_region_map()
        d = next((r for r in client._regions
                  if r.region_id == args.region), None)
        if d is None:
            print(f"region {args.region} not in the map", file=sys.stderr)
            return 1
        blob = RemoteBr(client, ".")._pull_region(d)
        with open(args.out, "wb") as f:
            f.write(blob)
        print(json.dumps({"region_id": args.region, "bytes": len(blob),
                          "file": args.out}))
    elif g == "dump" and c == "inspect":
        from dingo_tpu.raft import wire

        with open(args.file, "rb") as f:
            state = wire.decode(f.read())
        # blob shape: {cf: [(key, value), ...]} (engine/raft_engine.py
        # region_snapshot — the raft snapshot install representation)
        out = {}
        for cf, rows in sorted(state.items()):
            entry = {"keys": len(rows),
                     "bytes": sum(len(k) + len(v) for k, v in rows)}
            if args.keys:
                entry["first_keys"] = [k.hex() for k, _ in rows[:args.keys]]
            out[cf] = entry
        print(json.dumps(out, indent=1))
    elif g == "dump" and c == "index-snapshot":
        stub = client._stub(args.target_store, "RegionControlService")
        r = stub.RegionSnapshot(
            pb.RegionSnapshotRequest(region_id=args.region))
        if r.error.errcode:
            print(r.error.errmsg, file=sys.stderr)
            return 1
        nstub = client._stub(args.target_store, "NodeService")
        meta = nstub.GetVectorIndexSnapshotMeta(
            pb.VectorIndexSnapshotMetaRequest(region_id=args.region))
        print(json.dumps({
            "path": r.path,
            "snapshot_log_id": meta.snapshot_log_id,
            "files": [{"name": f.name, "size": f.size}
                      for f in meta.files],
        }))
    elif g == "br" and c == "backup":
        from dingo_tpu.br.remote import RemoteBr

        manifest = RemoteBr(client, args.dir).backup(
            resume=not args.no_resume)
        print(json.dumps({
            "regions": len(manifest["regions"]),
            "tables": len(manifest.get("tables", [])),
            "dir": args.dir,
        }))
    elif g == "br" and c == "restore":
        from dingo_tpu.br.remote import RemoteBr

        n = RemoteBr(client, args.dir).restore()
        print(json.dumps({"restored_regions": n}))
    elif g == "repl":
        return run_repl(client)
    else:
        print("unknown command", file=sys.stderr)
        return 2
    return 0


def run_repl(client: DingoClient) -> int:
    """Interactive mode (client_v2 REPL analog)."""
    parser = build_parser()
    print("dingo-cli repl — 'exit' to quit")
    while True:
        try:
            line = input("dingo> ").strip()
        except EOFError:
            return 0
        if line in ("exit", "quit"):
            return 0
        if not line:
            continue
        try:
            args = parser.parse_args(shlex.split(line))
            run_command(client, args)
        except SystemExit:
            pass
        except Exception as e:  # noqa: BLE001
            print(f"error: {e}")


def main(argv: List[str] = None) -> int:
    args = build_parser().parse_args(argv)
    stores: Dict[str, str] = {}
    for spec in args.store:
        sid, _, addr = spec.partition("=")
        stores[sid] = addr
    client = DingoClient(args.coordinator, stores)
    try:
        return run_command(client, args)
    finally:
        client.close()


if __name__ == "__main__":
    sys.exit(main())
