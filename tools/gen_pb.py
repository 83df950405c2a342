"""Regenerate dingo_tpu/server/dingo_pb2.py without protoc.

The image ships neither protoc nor grpcio-tools, so schema evolution works
by descriptor surgery: load the serialized FileDescriptorProto embedded in
the current dingo_pb2.py, apply the declarative ADDITIONS below (new
messages + new fields on existing messages), and re-emit the module in the
standard `_builder` generated-code shape. protobuf wire compatibility is
preserved because existing field numbers are never touched — only appended.

proto/dingo.proto stays the human-readable source of truth: edit it AND
mirror the change here, then run

    python tools/gen_pb.py

The tool is idempotent — messages/fields that already exist are skipped —
so it can re-run safely after partial edits.
"""

from __future__ import annotations

import os
import sys

from google.protobuf import descriptor_pb2

T = descriptor_pb2.FieldDescriptorProto

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PB2_PATH = os.path.join(REPO, "dingo_tpu", "server", "dingo_pb2.py")

# ---------------------------------------------------------------------------
# Declarative schema additions. Field spec:
#   (name, number, type, type_name_or_None, repeated)
# type_name is the fully qualified message type (".dingo_tpu.X") for
# TYPE_MESSAGE / TYPE_ENUM fields.
# ---------------------------------------------------------------------------

#: new messages appended to the file (store-metrics plane, PR 2)
NEW_MESSAGES = {
    # per-region snapshot collected by StoreMetricsCollector
    "RegionMetrics": [
        ("region_id", 1, T.TYPE_INT64, None, False),
        ("key_count", 2, T.TYPE_INT64, None, False),
        ("approximate_bytes", 3, T.TYPE_INT64, None, False),
        ("vector_count", 4, T.TYPE_INT64, None, False),
        ("vector_memory_bytes", 5, T.TYPE_INT64, None, False),
        ("device_memory_bytes", 6, T.TYPE_INT64, None, False),
        ("index_ready", 7, T.TYPE_BOOL, None, False),
        ("index_building", 8, T.TYPE_BOOL, None, False),
        ("index_build_error", 9, T.TYPE_BOOL, None, False),
        ("index_apply_log_id", 10, T.TYPE_INT64, None, False),
        ("index_snapshot_log_id", 11, T.TYPE_INT64, None, False),
        ("apply_lag", 12, T.TYPE_INT64, None, False),
        ("is_leader", 13, T.TYPE_BOOL, None, False),
        ("search_qps", 14, T.TYPE_DOUBLE, None, False),
        ("document_count", 15, T.TYPE_INT64, None, False),
        # HBM high-watermark for the region total (obs hbm ledger, PR 5)
        ("device_peak_bytes", 16, T.TYPE_INT64, None, False),
        # quality plane (obs/quality.py, PR 9): windowed live recall@k
        # estimate with its Wilson CI; quality_samples = scored queries
        # in the window (0 = no evidence, renderers show '-')
        ("quality_recall", 17, T.TYPE_DOUBLE, None, False),
        ("quality_recall_ci_low", 18, T.TYPE_DOUBLE, None, False),
        ("quality_recall_ci_high", 19, T.TYPE_DOUBLE, None, False),
        ("quality_samples", 20, T.TYPE_INT64, None, False),
        # serving-pressure plane (obs/pressure.py, PR 10): coalescer
        # queue depth (rows), recent queue-wait watermark (ms),
        # cumulative shed+expired requests, shed-ladder degrade level
        ("qos_queue_depth", 21, T.TYPE_INT64, None, False),
        ("qos_queue_wait_ms", 22, T.TYPE_DOUBLE, None, False),
        ("qos_shed_total", 23, T.TYPE_INT64, None, False),
        ("qos_degrade_level", 24, T.TYPE_INT64, None, False),
        # state-integrity plane (obs/integrity.py, PR 11): the raft
        # applied index the digest vector corresponds to, the compact
        # JSON {artifact: digest} vector, and the store-local scrub
        # verdict (a full-state recompute disagreed with the ledger)
        ("integrity_applied_index", 25, T.TYPE_INT64, None, False),
        ("integrity_digests", 26, T.TYPE_STRING, None, False),
        ("integrity_mismatch", 27, T.TYPE_BOOL, None, False),
        # fault-domain hardening (index/recovery.py): region's device
        # index OOMed past the recovery ladder — served by the host
        # exact path until the background re-materialization completes
        ("device_degraded", 28, T.TYPE_BOOL, None, False),
        # serving-edge cache (dingo_tpu/cache/): cumulative hit/miss
        # counts and live cached entries — the cluster top CACHE column
        # renders hit rate ('-' while hits+misses == 0)
        ("cache_hits", 29, T.TYPE_INT64, None, False),
        ("cache_misses", 30, T.TYPE_INT64, None, False),
        ("cache_entries", 31, T.TYPE_INT64, None, False),
        # workload-heat plane (obs/heat.py): traffic concentration
        # (hot_fraction / gini over heat units) and bytes to serve
        # {50,90,99}% of traffic at the region's own precision tier;
        # heat_touches = cumulative sketch touches (0 = no evidence).
        # The coordinator's capacity plane rolls these against the HBM
        # ledger for advisory tier/split recommendations
        ("heat_hot_fraction", 32, T.TYPE_DOUBLE, None, False),
        ("heat_gini", 33, T.TYPE_DOUBLE, None, False),
        ("heat_working_set_p50", 34, T.TYPE_INT64, None, False),
        ("heat_working_set_p90", 35, T.TYPE_INT64, None, False),
        ("heat_working_set_p99", 36, T.TYPE_INT64, None, False),
        ("heat_touches", 37, T.TYPE_INT64, None, False),
        # per-shape cost model (obs/cost.py): EWMA per-row dispatch µs
        ("cost_row_us", 38, T.TYPE_DOUBLE, None, False),
        # memory-tier ladder (index/tiering.py): serving rung name
        ("serving_tier", 39, T.TYPE_STRING, None, False),
        # control-plane flight recorder (obs/events.py): compact JSON of
        # the live overrides in force on this region at collect time —
        # {"tuning": {...}, "advisory_precision": ..., "tier": ...,
        #  "tier_base": ...}. `cluster explain` reconciles these against
        # the event ledger (a live knob with no event = orphan)
        ("live_knobs", 40, T.TYPE_STRING, None, False),
    ],
    # control-plane decision event (obs/events.py): one controller
    # actuation with the metric evidence read at decision time. Rides
    # heartbeats (StoreMetrics.events) to the coordinator's merged
    # cluster timeline
    "ControlEvent": [
        ("actor", 1, T.TYPE_STRING, None, False),
        ("region_id", 2, T.TYPE_INT64, None, False),
        ("knob", 3, T.TYPE_STRING, None, False),
        ("old", 4, T.TYPE_STRING, None, False),
        ("new", 5, T.TYPE_STRING, None, False),
        ("trigger", 6, T.TYPE_STRING, None, False),
        ("evidence", 7, T.TYPE_STRING, None, False),  # compact JSON
        ("ts_ms", 8, T.TYPE_INT64, None, False),
        ("actor_seq", 9, T.TYPE_INT64, None, False),
        ("node_id", 10, T.TYPE_STRING, None, False),
        ("trace_id", 11, T.TYPE_STRING, None, False),
        ("flight_bundle_id", 12, T.TYPE_STRING, None, False),
    ],
    # whole-store snapshot (process device gauges + per-region list)
    "StoreMetrics": [
        ("store_id", 1, T.TYPE_STRING, None, False),
        ("collected_at_ms", 2, T.TYPE_INT64, None, False),
        ("device_bytes_in_use", 3, T.TYPE_INT64, None, False),
        ("device_bytes_limit", 4, T.TYPE_INT64, None, False),
        ("device_peak_bytes", 5, T.TYPE_INT64, None, False),
        ("engine_key_count", 6, T.TYPE_INT64, None, False),
        ("regions", 7, T.TYPE_MESSAGE, ".dingo_tpu.RegionMetrics", True),
        # control-plane events harvested since the last beat (bounded by
        # events.heartbeat_batch; each event ships exactly once)
        ("events", 8, T.TYPE_MESSAGE, ".dingo_tpu.ControlEvent", True),
    ],
    "GetStoreMetricsRequest": [
        ("info", 1, T.TYPE_MESSAGE, ".dingo_tpu.RequestInfo", False),
        ("store_id", 2, T.TYPE_STRING, None, False),  # empty = every store
    ],
    "StoreMetricsEntry": [
        ("store_id", 1, T.TYPE_STRING, None, False),
        ("last_update_ms", 2, T.TYPE_INT64, None, False),
        ("stale", 3, T.TYPE_BOOL, None, False),
        ("metrics", 4, T.TYPE_MESSAGE, ".dingo_tpu.StoreMetrics", False),
    ],
    "GetStoreMetricsResponse": [
        ("info", 1, T.TYPE_MESSAGE, ".dingo_tpu.ResponseInfo", False),
        ("error", 2, T.TYPE_MESSAGE, ".dingo_tpu.Error", False),
        ("stores", 3, T.TYPE_MESSAGE, ".dingo_tpu.StoreMetricsEntry", True),
        # regions the coordinator's replica-digest comparison currently
        # flags as DIVERGED (state-integrity plane; cluster top renders)
        ("diverged_region_ids", 4, T.TYPE_INT64, None, True),
    ],
    "GetRegionMetricsRequest": [
        ("info", 1, T.TYPE_MESSAGE, ".dingo_tpu.RequestInfo", False),
        ("region_id", 2, T.TYPE_INT64, None, False),  # 0 = every region
    ],
    "RegionMetricsEntry": [
        ("store_id", 1, T.TYPE_STRING, None, False),
        ("stale", 2, T.TYPE_BOOL, None, False),
        ("metrics", 3, T.TYPE_MESSAGE, ".dingo_tpu.RegionMetrics", False),
    ],
    "GetRegionMetricsResponse": [
        ("info", 1, T.TYPE_MESSAGE, ".dingo_tpu.ResponseInfo", False),
        ("error", 2, T.TYPE_MESSAGE, ".dingo_tpu.Error", False),
        ("regions", 3, T.TYPE_MESSAGE, ".dingo_tpu.RegionMetricsEntry", True),
        ("diverged_region_ids", 4, T.TYPE_INT64, None, True),
    ],
    # flight-recorder bundle export (device-runtime observability, PR 5)
    "FlightBundleMeta": [
        ("id", 1, T.TYPE_STRING, None, False),
        ("reason", 2, T.TYPE_STRING, None, False),
        ("name", 3, T.TYPE_STRING, None, False),
        ("trace_id", 4, T.TYPE_STRING, None, False),
        ("region_id", 5, T.TYPE_INT64, None, False),
        ("created_ms", 6, T.TYPE_INT64, None, False),
        ("payload_bytes", 7, T.TYPE_INT64, None, False),
    ],
    "FlightDumpRequest": [
        ("info", 1, T.TYPE_MESSAGE, ".dingo_tpu.RequestInfo", False),
        ("bundle_id", 2, T.TYPE_STRING, None, False),  # "" = newest
        ("include_payload", 3, T.TYPE_BOOL, None, False),
    ],
    "FlightDumpResponse": [
        ("info", 1, T.TYPE_MESSAGE, ".dingo_tpu.ResponseInfo", False),
        ("error", 2, T.TYPE_MESSAGE, ".dingo_tpu.Error", False),
        ("bundles", 3, T.TYPE_MESSAGE, ".dingo_tpu.FlightBundleMeta", True),
        ("payload", 4, T.TYPE_BYTES, None, False),  # zlib(JSON) bundle
        ("payload_bundle_id", 5, T.TYPE_STRING, None, False),
    ],
    # event-ledger dump (DebugService on stores: process-local ring;
    # ClusterStatService on the coordinator: merged cluster timeline)
    "EventDumpRequest": [
        ("info", 1, T.TYPE_MESSAGE, ".dingo_tpu.RequestInfo", False),
        ("region_id", 2, T.TYPE_INT64, None, False),  # 0 = every region
        ("actor", 3, T.TYPE_STRING, None, False),     # "" = every actor
        ("limit", 4, T.TYPE_INT64, None, False),      # 0 = default bound
    ],
    "EventDumpResponse": [
        ("info", 1, T.TYPE_MESSAGE, ".dingo_tpu.ResponseInfo", False),
        ("error", 2, T.TYPE_MESSAGE, ".dingo_tpu.Error", False),
        ("events", 3, T.TYPE_MESSAGE, ".dingo_tpu.ControlEvent", True),
        ("dropped", 4, T.TYPE_INT64, None, False),
    ],
}

#: fields appended to existing messages
NEW_FIELDS = {
    # precision tier for float FLAT/IVF_FLAT storage+compute (ISSUE 4):
    # "" (conf default) / "fp32" / "bf16" / "sq8"
    "VectorIndexParameter": [
        ("precision", 13, T.TYPE_STRING, None, False),
        # HNSW: rows the region is created for (the upstream's hnsw
        # max_elements): slot store + device adjacency sized at creation
        ("max_elements", 14, T.TYPE_INT64, None, False),
    ],
    # heartbeat transport for the metrics payload
    "StoreHeartbeatRequest": [
        ("metrics", 11, T.TYPE_MESSAGE, ".dingo_tpu.StoreMetrics", False),
    ],
    # cluster-stat rollups (aggregated from the freshest store snapshots)
    "StoreStat": [
        ("key_count", 6, T.TYPE_INT64, None, False),
        ("vector_count", 7, T.TYPE_INT64, None, False),
        ("memory_bytes", 8, T.TYPE_INT64, None, False),
        ("device_memory_bytes", 9, T.TYPE_INT64, None, False),
        ("metrics_stale", 10, T.TYPE_BOOL, None, False),
        ("leader_qps", 11, T.TYPE_DOUBLE, None, False),
    ],
    "GetClusterStatResponse": [
        ("total_key_count", 8, T.TYPE_INT64, None, False),
        ("total_vector_count", 9, T.TYPE_INT64, None, False),
        ("total_memory_bytes", 10, T.TYPE_INT64, None, False),
        ("total_device_memory_bytes", 11, T.TYPE_INT64, None, False),
    ],
    # exposition selector: "" / "json" (default) or "prometheus"
    "MetricsDumpRequest": [
        ("format", 2, T.TYPE_STRING, None, False),
    ],
}

_HEADER = '''# -*- coding: utf-8 -*-
# Generated by tools/gen_pb.py (descriptor surgery; protoc is not in the
# image).  DO NOT EDIT BY HAND — edit proto/dingo.proto + tools/gen_pb.py
# and re-run `python tools/gen_pb.py`.
# source: dingo.proto
"""Generated protocol buffer code."""
from google.protobuf.internal import builder as _builder
from google.protobuf import descriptor as _descriptor
from google.protobuf import descriptor_pool as _descriptor_pool
from google.protobuf import symbol_database as _symbol_database
# @@protoc_insertion_point(imports)

_sym_db = _symbol_database.Default()


DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile({blob!r})

_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())
_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, 'dingo_pb2', globals())
# @@protoc_insertion_point(module_scope)
'''


def _load_current_fdp() -> descriptor_pb2.FileDescriptorProto:
    """Extract the serialized FileDescriptorProto from the current module
    WITHOUT importing it (importing would register the old schema in this
    interpreter's default descriptor pool and block re-registration)."""
    import ast

    with open(PB2_PATH) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and getattr(node.func, "attr", "") == "AddSerializedFile"
            and node.args
            and isinstance(node.args[0], ast.Constant)
        ):
            fdp = descriptor_pb2.FileDescriptorProto()
            fdp.ParseFromString(node.args[0].value)
            return fdp
    raise SystemExit(f"no AddSerializedFile(<bytes>) literal in {PB2_PATH}")


def _add_field(msg, spec) -> bool:
    name, number, ftype, type_name, repeated = spec
    if any(f.name == name for f in msg.field):
        return False
    taken = {f.number for f in msg.field}
    if number in taken:
        raise SystemExit(
            f"{msg.name}.{name}: field number {number} already in use"
        )
    f = msg.field.add()
    f.name = name
    f.number = number
    f.type = ftype
    f.label = T.LABEL_REPEATED if repeated else T.LABEL_OPTIONAL
    if type_name:
        f.type_name = type_name
    return True


def extend(fdp: descriptor_pb2.FileDescriptorProto) -> int:
    changed = 0
    have = {m.name: m for m in fdp.message_type}
    for name, fields in NEW_MESSAGES.items():
        msg = have.get(name)
        if msg is None:
            msg = fdp.message_type.add()
            msg.name = name
            have[name] = msg
            changed += 1
        for spec in fields:
            changed += _add_field(msg, spec)
    for name, fields in NEW_FIELDS.items():
        msg = have.get(name)
        if msg is None:
            raise SystemExit(f"NEW_FIELDS target {name} not in schema")
        for spec in fields:
            changed += _add_field(msg, spec)
    return changed


def verify(blob: bytes) -> None:
    """Round-trip the new schema in an isolated pool before writing."""
    from google.protobuf import descriptor_pool, message_factory

    fdp = descriptor_pb2.FileDescriptorProto()
    fdp.ParseFromString(blob)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    hb = message_factory.GetMessageClass(
        pool.FindMessageTypeByName("dingo_tpu.StoreHeartbeatRequest")
    )()
    rm = hb.metrics.regions.add()
    rm.region_id = 7
    rm.device_memory_bytes = 123
    again = type(hb).FromString(hb.SerializeToString())
    assert again.metrics.regions[0].device_memory_bytes == 123


def main() -> int:
    fdp = _load_current_fdp()
    changed = extend(fdp)
    blob = fdp.SerializeToString()
    verify(blob)
    with open(PB2_PATH, "w") as f:
        f.write(_HEADER.format(blob=blob))
    print(f"{PB2_PATH}: {changed} schema additions, "
          f"{len(fdp.message_type)} messages, {len(blob)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
