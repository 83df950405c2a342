"""grpc service implementations.

Reference service registry (src/server/main.cc:681-1360, per role):
  INDEX/STORE roles — IndexServiceImpl (index_service.h), StoreServiceImpl,
      NodeService, DebugService, UtilService
  COORDINATOR role — CoordinatorServiceImpl, MetaService, VersionService

Handlers are hand-written over the protoc-generated messages (no grpc
codegen plugin in this image); registration uses generic method handlers.
"""

from __future__ import annotations

import json
import tempfile
import threading
import time
from concurrent.futures import TimeoutError as FuturesTimeoutError
from typing import Dict, Optional

import grpc
import numpy as np

from dingo_tpu.common.failpoint import FAILPOINTS
from dingo_tpu.common.metrics import METRICS
from dingo_tpu.coordinator.control import CoordinatorControl, RegionCmd, RegionCmdType
from dingo_tpu.coordinator.kv_control import (
    CompactedError,
    FutureRevError,
    KvControl,
)
from dingo_tpu.coordinator.tso import TsoControl
from dingo_tpu.engine.txn import Mutation, Op, TxnEngine, TxnError
from dingo_tpu.index.base import VectorIndexError
from dingo_tpu.ops.distance import Metric
from dingo_tpu.raft import wire
from dingo_tpu.raft.core import NotLeader
from dingo_tpu.server import convert, pb
from dingo_tpu.store.node import StoreNode
from dingo_tpu.store.region import Region, RegionType
from dingo_tpu.trace import TRACER


def _err(resp, code: int, msg: str):
    resp.error.errcode = code
    resp.error.errmsg = msg
    return resp


#: server-side ceiling on a single long-poll: a blocked watch holds a
#: semaphore slot AND a grpc pool thread, so the duration must not be
#: client-chosen-unbounded
_MAX_WATCH_TIMEOUT_MS = 30_000


def _long_poll_watch(register_fn, cancel_fn, slots, timeout_ms):
    """Shared one-shot watch harness (VKvWatch + MetaWatch): register a
    callback that may fire immediately (replay), else block up to the
    clamped timeout while holding a bounded slot.

    Returns (event_args tuple | None, "busy" | None). register_fn may
    raise (e.g. CompactedError) — callers map that to their error code."""
    fired = threading.Event()
    holder = {}

    def cb(*args):
        holder["args"] = args
        fired.set()

    register_fn(cb)
    timeout_ms = min(int(timeout_ms or 0), _MAX_WATCH_TIMEOUT_MS)
    if not fired.is_set() and timeout_ms:
        if not slots.acquire(blocking=False):
            cancel_fn(cb)
            return None, "busy"
        try:
            fired.wait(timeout_ms / 1000.0)
        finally:
            slots.release()
    if fired.is_set():
        return holder["args"], None
    cancel_fn(cb)
    return None, None


def _rebuild_region(node: StoreNode, region: Region) -> None:
    """Forced rebuild through the atomic-swap path, WITH the raft log so
    catch-up happens in open rounds and the old index serves throughout
    (blocking-scan rebuild is reserved for regions with no raft node)."""
    raft = node.engine.get_node(region.id)
    node.index_manager.rebuild(region, raft_log=raft.log if raft else None)


def _clamp_range_or_err(region: Region, start: bytes, end: bytes, resp):
    """Validate a KV request range against the region bounds
    (ServiceHelper::ValidateRange analog): a store hosts many regions in
    ONE shared engine, so an unclamped range reads or deletes ANOTHER
    region's keys. Returns (start, end) or None with the error set."""
    if end and start >= end:
        _err(resp, 60003, "illegal range: start >= end")
        return None
    r_start, r_end = region.range
    if start < r_start or (r_end and (not end or end > r_end)):
        _err(resp, 60004,
             f"range outside region {region.id} bounds")
        return None
    return start, end


def _keys_in_region_or_err(region: Region, keys, resp) -> bool:
    for k in keys:
        if not region.contains_key(k):
            _err(resp, 60004,
                 f"key outside region {region.id} bounds")
            return False
    return True


def _region_or_err(node: StoreNode, context_pb, resp) -> Optional[Region]:
    stamped = context_pb.region_epoch.version
    if stamped:
        # routed from an SDK's cached region map (client.py "Routing")
        METRICS.counter("service.epoch_stamped").add(1)
    region = node.get_region(context_pb.region_id)
    if region is None:
        _err(resp, 10001, f"region {context_pb.region_id} not found")
        return None
    # epoch check (reference validates region epoch on every request): the
    # refusal is what holds an SDK's cached route true — it fetches the map
    # and routes the call again
    if stamped and stamped != region.epoch.version:
        METRICS.counter("service.epoch_refusals").add(1)
        _err(resp, 10002,
             f"epoch mismatch {stamped} != {region.epoch.version}")
        return None
    return region


class IndexService:
    """Vector RPCs (index_service.h:92+)."""

    def __init__(self, node: StoreNode):
        self.node = node
        self._coalescer = None
        self._coalescer_lock = threading.Lock()
        # rows by the path that decoded them (convert.float_rows_from_pb),
        # and requests by what `_region_or_err` saw of their epoch, from the
        # start: a path that never ran reads 0, not "no such series"
        for name in ("decode_wire_rows", "decode_boxed_rows",
                     "epoch_stamped", "epoch_refusals"):
            METRICS.counter("service." + name).add(0)
        # rows the reader's host post-filter looked up (index/vector_reader)
        METRICS.counter("filter.post_rows").add(0)

    def _get_coalescer(self):
        from dingo_tpu.common.coalescer import SearchCoalescer
        from dingo_tpu.common.config import FLAGS

        window = float(FLAGS.get("search_coalescing_window_ms"))
        with self._coalescer_lock:
            # rebuild when the (hot-changeable) window flag moves, so
            # operators tuning it actually change behavior
            if self._coalescer is not None and \
                    self._coalescer.window_s != window / 1000.0:
                self._coalescer.stop()
                self._coalescer = None
            if self._coalescer is None:
                def run(key, stacked, stage_us=None):
                    region_id, topn, kw_items = key
                    region = self.node.get_region(region_id)
                    if region is None:
                        raise VectorIndexError(f"region {region_id} gone")
                    # stage_us (reader stage timings) feeds the QoS
                    # per-stage budget accounting when qos is on; the
                    # coalescer only passes it when it wants the split
                    return self.node.storage.vector_batch_search(
                        region, stacked, topn, stage_us=stage_us,
                        **dict(kw_items)
                    )

                def dispatch(key, stacked, staged=None, stage_us=None):
                    # pipelined arm (pipeline.enabled): enqueue kernels
                    # now, return the resolve thunk — the coalescer's
                    # completion lane performs the one host sync
                    region_id, topn, kw_items = key
                    region = self.node.get_region(region_id)
                    if region is None:
                        raise VectorIndexError(f"region {region_id} gone")
                    return self.node.storage.vector_batch_search_async(
                        region, stacked, topn, staged=staged,
                        stage_us=stage_us, **dict(kw_items)
                    )

                self._coalescer = SearchCoalescer(
                    run, window_ms=window, dispatch_fn=dispatch
                )
            return self._coalescer

    def close(self) -> None:
        with self._coalescer_lock:
            if self._coalescer is not None:
                self._coalescer.stop()
                self._coalescer = None

    def _do_search(self, req, resp, stage_us=None):
        """Shared VectorSearch/VectorSearchDebug body: build kwargs (incl.
        the radius range-search arm), run the reader, fill batch_results
        (binary-aware vector payloads + scalar backfill)."""
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp, None
        # fault-injection point for the search path (flight-recorder tests
        # panic here; a panic propagates to the generic rpc handler which
        # black-boxes it and answers in-band)
        FAILPOINTS.apply("before_vector_search")
        from dingo_tpu.obs import pressure as qos
        from dingo_tpu.trace import current_span

        budget = qos.current_budget() if qos.qos_enabled() else None
        if budget is not None and budget.expired():
            # deadline-aware admission: a request that arrives already
            # dead is rejected before ANY index work — no kernel is
            # dispatched for it (sentinel-verified in tests/test_qos.py)
            qos.PRESSURE.on_expired("admission", region.id, budget)
            return _err(resp, 30002, "deadline exceeded at admission"), None
        ingress = current_span()
        if ingress is not None and ingress.sampled:
            ingress.set_attr("region_id", region.id)
            ingress.set_attr("batch", len(req.vectors))
            ingress.set_attr("topn", req.parameter.top_n or 10)
        lat = METRICS.latency("vector_search", region.id)
        t0 = time.perf_counter_ns()
        try:
            # service.decode: the request's float rows to one array
            with TRACER.start_child("service.decode"):
                binary = convert.is_binary_parameter(
                    region.definition.index_parameter
                )
                queries = convert.queries_from_pb(req.vectors, binary=binary)
                kw = convert.search_kwargs_from_pb(req.parameter)
            if req.parameter.nprobe:
                kw["nprobe"] = req.parameter.nprobe
            if req.parameter.ef_search:
                kw["ef"] = req.parameter.ef_search
            topn = req.parameter.top_n or 10
            if req.parameter.radius > 0:
                # VectorRangeSearch path: over-fetch to the cap, reader cuts
                kw["radius"] = req.parameter.radius
                from dingo_tpu.index.vector_reader import RANGE_SEARCH_CAP

                topn = min(max(topn, 128), RANGE_SEARCH_CAP)
            from dingo_tpu.common.config import FLAGS

            window = FLAGS.get("search_coalescing_window_ms")
            # coalesce only parameter-identical, filter-free searches
            from dingo_tpu.index.vector_reader import VectorFilterMode

            plain = (
                window > 0
                and stage_us is None
                and req.parameter.radius <= 0
                and not kw.get("with_vector_data")
                and not kw.get("with_scalar_data")
                and kw.get("filter_mode") in (None, VectorFilterMode.NONE)
                and not kw.get("vector_ids")
                and kw.get("scalar_filter") is None
            )
            if plain:
                from dingo_tpu.cache import edge as cache_edge
                from dingo_tpu.engine.storage import (
                    MAX_TOPN_BATCH_PRODUCT,
                    VECTOR_MAX_BATCH_COUNT,
                )

                key = (
                    region.id, topn,
                    tuple(sorted(
                        (k, v) for k, v in kw.items()
                        if isinstance(v, (int, float, str, bool, type(None)))
                    )),
                )
                # a merged batch must respect the same guards each request
                # passes alone (4096 rows; topn*rows product)
                cap = min(
                    VECTOR_MAX_BATCH_COUNT,
                    MAX_TOPN_BATCH_PRODUCT // max(1, topn),
                )
                # serving-edge cache consult BEFORE QoS queuing: a hit
                # costs no queue slot, no admission estimate, no kernel;
                # a partial hit dispatches only its miss rows
                looked = None
                if cache_edge.active():
                    w = getattr(region, "vector_index_wrapper", None)
                    looked = cache_edge.lookup(
                        region.id, queries, topn, key[2],
                        cache_edge.region_version(region),
                        index=getattr(w, "own_index", None),
                    )
                if looked is not None and looked.complete:
                    results = looked.rows
                else:
                    submit_q = (queries if looked is None
                                else queries[looked.miss_idx])
                    try:
                        results = self._get_coalescer().submit(
                            key, submit_q, max_batch=cap,
                            region_id=region.id
                        ).result(timeout=30)
                    except qos.QosRejected as e:
                        # an admission/expiry decision is FINAL — falling
                        # back to a direct search would serve exactly the
                        # work the QoS layer decided the store cannot
                        # afford
                        return _err(
                            resp,
                            30002 if isinstance(e, qos.DeadlineExceeded)
                            else 30003,
                            str(e),
                        ), None
                    except (RuntimeError, FuturesTimeoutError):
                        # coalescer stopped mid-flight (flag hot-change) or
                        # the batch stalled: serve this request directly
                        results = self.node.storage.vector_batch_search(
                            region, submit_q, topn, **kw
                        )
                    if looked is not None:
                        # fill only if the store version didn't move while
                        # the kernel ran (edge.fill re-checks), then stitch
                        # cached + fresh rows back into request order
                        cache_edge.fill(
                            region.id, looked, results,
                            cache_edge.region_version(region), queries,
                            tenant=(budget.tenant if budget is not None
                                    else "default"),
                        )
                        results = looked.merge(results)
            else:
                results = self.node.storage.vector_batch_search(
                    region, queries, topn, stage_us=stage_us, **kw
                )
        except (VectorIndexError, ValueError) as e:
            # in-band search failures never reach the generic rpc handler,
            # so they black-box here (device OOMs included)
            from dingo_tpu.obs.flight import black_box_error

            black_box_error("rpc.IndexService.VectorSearch", e, ingress,
                            region_id=region.id)
            return _err(resp, 30001, str(e)), None
        # service.encode: the results into the reply message
        with TRACER.start_child("service.encode"):
            for row in results:
                r = resp.batch_results.add()
                for v in row:
                    item = r.results.add()
                    item.vector.id = v.id
                    item.distance = v.distance
                    if v.vector is not None:
                        convert.fill_vector_pb(item.vector, v.vector)
                    if v.scalar:
                        convert.scalar_to_pb(item.scalar_data, v.scalar)
        lat.observe_us((time.perf_counter_ns() - t0) / 1000.0)
        if qos.qos_enabled():
            # throughput vs goodput: every reply counts served; only the
            # ones inside their budget count toward goodput (a late reply
            # additionally black-boxes a deadline_exceeded flight bundle)
            qos.PRESSURE.on_served(region.id, budget)
        return resp, region

    def VectorSearch(self, req: pb.VectorSearchRequest) -> pb.VectorSearchResponse:
        resp, _ = self._do_search(req, pb.VectorSearchResponse())
        return resp

    def VectorSearchDebug(self, req: pb.VectorSearchDebugRequest):
        """VectorSearch + per-stage timings (the reference's SearchDebug
        RPC, vector_reader.h:85-88 / index_service.h SearchDebug)."""
        stage_us: Dict[str, int] = {}
        resp, _ = self._do_search(
            req, pb.VectorSearchDebugResponse(), stage_us=stage_us
        )
        for field in ("prefilter_us", "search_us", "postfilter_us",
                      "backfill_us", "total_us"):
            setattr(resp, field, stage_us.get(field, 0))
        return resp

    @staticmethod
    def _vector_batch_from_pb(region, req_vectors):
        """Decode a repeated VectorWithScalar into the storage call shape:
        (ids, vectors, scalars, table_values) — shared by VectorAdd and
        VectorImport so the two RPCs cannot diverge."""
        ids = np.asarray([v.vector.id for v in req_vectors], np.int64)
        if convert.is_binary_parameter(region.definition.index_parameter):
            vectors = np.stack([
                np.frombuffer(v.vector.binary_values, np.uint8)
                for v in req_vectors
            ])
        else:
            vectors = convert.float_rows_from_pb(
                [v.vector for v in req_vectors]
            )
        scalars = [convert.scalar_from_pb(v.scalar_data) for v in req_vectors]
        table_values = None
        if any(v.HasField("table_data") for v in req_vectors):
            table_values = [
                v.table_data if v.HasField("table_data") else None
                for v in req_vectors
            ]
        return ids, vectors, scalars, table_values

    def VectorAdd(self, req: pb.VectorAddRequest) -> pb.VectorAddResponse:
        resp = pb.VectorAddResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        try:
            # service.decode: the rows' floats, ids and scalars to arrays
            with TRACER.start_child("service.decode"):
                ids, vectors, scalars, table_values = \
                    self._vector_batch_from_pb(region, req.vectors)
            ts = self.node.storage.vector_add(
                region, ids, vectors, scalars,
                is_update=req.is_update, ttl_ms=req.ttl_ms,
                table_values=table_values,
            )
        except NotLeader as e:
            return _err(resp, 20001, f"not leader: {e.leader_hint}")
        except (VectorIndexError, ValueError) as e:
            return _err(resp, 30001, str(e))
        with TRACER.start_child("service.encode"):
            resp.ts = ts
            resp.key_states.extend([True] * len(req.vectors))
        METRICS.counter("vector_add", region.id).add(len(req.vectors))
        return resp

    def VectorImport(self, req: pb.VectorImportRequest):
        """Bulk import (index_service.h:57 VectorImport): upserts + deletes
        in one call, sharing VectorAdd's validation and write path."""
        resp = pb.VectorImportResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        try:
            ts = 0
            if req.vectors:
                ids, vectors, scalars, table_values = (
                    self._vector_batch_from_pb(region, req.vectors))
                ts = self.node.storage.vector_add(
                    region, ids, vectors, scalars,
                    is_update=True, ttl_ms=req.ttl_ms,
                    table_values=table_values,
                )
                resp.added = len(req.vectors)
            if req.delete_ids:
                ts = self.node.storage.vector_delete(
                    region, list(req.delete_ids))
                resp.deleted = len(req.delete_ids)
        except NotLeader as e:
            return _err(resp, 20001, f"not leader: {e.leader_hint}")
        except (VectorIndexError, ValueError) as e:
            return _err(resp, 30001, str(e))
        resp.ts = ts
        METRICS.counter("vector_import", region.id).add(
            len(req.vectors) + len(req.delete_ids))
        return resp

    def VectorDelete(self, req: pb.VectorDeleteRequest) -> pb.VectorDeleteResponse:
        resp = pb.VectorDeleteResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        try:
            self.node.storage.vector_delete(region, list(req.ids))
        except NotLeader as e:
            return _err(resp, 20001, f"not leader: {e.leader_hint}")
        resp.key_states.extend([True] * len(req.ids))
        return resp

    def VectorBatchQuery(self, req: pb.VectorBatchQueryRequest):
        resp = pb.VectorBatchQueryResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        rows = self.node.storage.vector_batch_query(
            region, list(req.vector_ids),
            with_vector_data=req.with_vector_data,
            with_scalar_data=req.with_scalar_data,
        )
        for row in rows:
            out = resp.vectors.add()
            if row is None:
                out.vector.id = -1
                continue
            out.vector.id = row.id
            if row.vector is not None:
                convert.fill_vector_pb(out.vector, row.vector)
            if row.scalar:
                convert.scalar_to_pb(out.scalar_data, row.scalar)
        return resp

    def VectorGetBorderId(self, req: pb.VectorGetBorderIdRequest):
        resp = pb.VectorGetBorderIdResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        border = self.node.storage.vector_get_border_id(region, req.get_min)
        resp.id = border if border is not None else -1
        return resp

    def VectorScanQuery(self, req: pb.VectorScanQueryRequest):
        resp = pb.VectorScanQueryResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        rows = self.node.storage.vector_scan_query(
            region,
            start_id=req.vector_id_start,
            end_id=req.vector_id_end or None,
            limit=req.max_scan_count or 1000,
            is_reverse=req.is_reverse,
            with_vector_data=req.with_vector_data,
            with_scalar_data=req.with_scalar_data,
        )
        for row in rows:
            out = resp.vectors.add()
            out.vector.id = row.id
            if row.vector is not None:
                convert.fill_vector_pb(out.vector, row.vector)
            if row.scalar:
                convert.scalar_to_pb(out.scalar_data, row.scalar)
        return resp

    def VectorBuild(self, req: pb.VectorBuildRequest):
        """Trigger a full rebuild (LaunchRebuildVectorIndex analog)."""
        resp = pb.VectorBuildResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        if region.vector_index_wrapper is None:
            return _err(resp, 70001, "region has no vector index")
        try:
            _rebuild_region(self.node, region)
        except Exception as e:  # noqa: BLE001
            return _err(resp, 70002, f"rebuild failed: {e}")
        return resp

    def VectorLoad(self, req: pb.VectorLoadRequest):
        """Load the index from its snapshot (+ WAL catch-up)."""
        resp = pb.VectorLoadResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        if region.vector_index_wrapper is None:
            return _err(resp, 70001, "region has no vector index")
        from dingo_tpu.index.manager import StaleSnapshot

        try:
            raft = self.node.engine.get_node(region.id)
            ok = self.node.index_manager.load_index(
                region, raft_log=raft.log if raft else None,
                path=req.path or None,
            )
        except StaleSnapshot as e:
            return _err(resp, 70004, f"stale snapshot refused: {e}")
        except (OSError, ValueError, VectorIndexError) as e:
            return _err(resp, 70003, f"load failed: {e}")
        if not ok:
            return _err(resp, 70003,
                        "snapshot missing or unreadable (nothing loaded)")
        return resp

    def VectorStatus(self, req: pb.VectorStatusRequest):
        resp = pb.VectorStatusResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        w = region.vector_index_wrapper
        if w is None:
            return _err(resp, 70001, "region has no vector index")
        resp.ready = w.ready
        resp.build_error = w.build_error
        resp.is_switching = w.is_switching
        resp.apply_log_id = w.apply_log_id
        resp.snapshot_log_id = w.snapshot_log_id
        idx = w.own_index
        if idx is not None:
            resp.count = idx.get_count()
            resp.trained = idx.is_trained()
            resp.index_type = idx.index_type.value
        return resp

    def VectorReset(self, req: pb.VectorResetRequest):
        """Drop the in-memory index and rebuild from the engine (the
        engine is the source of truth; the index is a view)."""
        resp = pb.VectorResetResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        w = region.vector_index_wrapper
        if w is None:
            return _err(resp, 70001, "region has no vector index")
        try:
            # rebuild() swaps atomically under the wrapper lock — the old
            # index keeps serving (and absorbing raft applies) until the
            # fresh one is ready; never pre-mark not-ready here
            _rebuild_region(self.node, region)
        except Exception as e:  # noqa: BLE001
            return _err(resp, 70002, f"reset rebuild failed: {e}")
        return resp

    def VectorDump(self, req: pb.VectorDumpRequest):
        resp = pb.VectorDumpResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        w = region.vector_index_wrapper
        if w is None:
            return _err(resp, 70001, "region has no vector index")
        idx = w.own_index
        dump = {
            "region_id": region.id,
            "ready": w.ready,
            "apply_log_id": w.apply_log_id,
            "snapshot_log_id": w.snapshot_log_id,
            "write_count_since_save": getattr(
                idx, "write_count_since_save", 0
            ) if idx else 0,
        }
        if idx is not None:
            dump.update(
                index_type=idx.index_type.value,
                count=idx.get_count(),
                memory_bytes=idx.get_memory_size(),
                trained=idx.is_trained(),
            )
        resp.json = json.dumps(dump)
        return resp

    def VectorCountMemory(self, req: pb.VectorCountMemoryRequest):
        resp = pb.VectorCountMemoryResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        w = region.vector_index_wrapper
        idx = w.own_index if w else None
        if idx is None:
            return _err(resp, 70001, "region has no vector index")
        resp.bytes = idx.get_memory_size()
        return resp

    def VectorGetRegionMetrics(self, req: pb.VectorGetRegionMetricsRequest):
        resp = pb.VectorGetRegionMetricsResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        w = region.vector_index_wrapper
        idx = w.own_index if w else None
        if idx is not None:
            resp.vector_count = idx.get_count()
            resp.memory_bytes = idx.get_memory_size()
        reader = self.node.engine.new_vector_reader(region)
        mn, mx = reader.vector_border_ids()   # one region scan, both ends
        resp.min_id = mn if mn is not None else -1
        resp.max_id = mx if mx is not None else -1
        resp.region_state = region.state.value
        return resp

    def VectorCount(self, req: pb.VectorCountRequest):
        resp = pb.VectorCountResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        resp.count = self.node.storage.vector_count(region)
        return resp


class UtilService:
    """VectorCalcDistance (util service exposure of CalcDistanceEntry,
    vector_index_utils.h:43-160)."""

    def VectorCalcDistance(self, req: pb.VectorCalcDistanceRequest):
        from dingo_tpu.ops.distance import (
            pairwise_cosine,
            pairwise_inner_product,
            pairwise_l2sqr,
        )
        import jax.numpy as jnp

        resp = pb.VectorCalcDistanceResponse()
        left = convert.queries_from_pb(req.op_left_vectors)
        right = convert.queries_from_pb(req.op_right_vectors)
        if left.size == 0 or right.size == 0:
            return _err(resp, 30001, "empty operands")
        metric = {
            pb.METRIC_TYPE_L2: pairwise_l2sqr,
            pb.METRIC_TYPE_INNER_PRODUCT: pairwise_inner_product,
            pb.METRIC_TYPE_COSINE: pairwise_cosine,
        }.get(req.metric_type, pairwise_l2sqr)
        d = np.asarray(metric(jnp.asarray(left), jnp.asarray(right)))
        for row in d:
            resp.distances.add().values.extend(row.tolist())
        return resp


class StoreService:
    """KV + txn RPCs (store_service.h)."""

    def __init__(self, node: StoreNode):
        self.node = node
        # one TxnEngine per region, NOT per request: the engine's
        # ConcurrencyManager (per-key latches) only serializes concurrent
        # check-then-write sections if every request for a region shares it
        # — a per-request manager would let two pessimistic locks for
        # different txns both "win" the same key
        self._txn_engines: Dict[int, TxnEngine] = {}
        self._txn_engines_lock = threading.Lock()

    def _txn(self, region: Region) -> TxnEngine:
        with self._txn_engines_lock:
            eng = self._txn_engines.get(region.id)
            if eng is None or eng.region is not region:
                # new region object (create/epoch change): fresh engine
                eng = TxnEngine(self.node.engine, region)
                self._txn_engines[region.id] = eng
            return eng

    def KvGet(self, req: pb.KvGetRequest) -> pb.KvGetResponse:
        resp = pb.KvGetResponse()
        region = self._leader_region_or_err(req.context, resp)
        if region is None:
            return resp
        value = self.node.storage.kv_get(region, req.key)
        resp.found = value is not None
        resp.value = value or b""
        return resp

    def KvBatchPut(self, req: pb.KvBatchPutRequest) -> pb.KvBatchPutResponse:
        resp = pb.KvBatchPutResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        if not _keys_in_region_or_err(
            region, [kv.key for kv in req.kvs], resp
        ):
            return resp
        try:
            resp.ts = self.node.storage.kv_put(
                region, [(kv.key, kv.value) for kv in req.kvs],
                ttl_ms=req.ttl_ms,
            )
        except NotLeader as e:
            return _err(resp, 20001, f"not leader: {e.leader_hint}")
        return resp

    def KvBatchGet(self, req: pb.KvBatchGetRequest):
        resp = pb.KvBatchGetResponse()
        region = self._leader_region_or_err(req.context, resp)
        if region is None:
            return resp
        if not _keys_in_region_or_err(region, list(req.keys), resp):
            return resp
        values = self.node.storage.kv_batch_get(region, list(req.keys))
        for key, value in zip(req.keys, values):
            kv = resp.kvs.add()
            kv.key = key
            kv.value = value or b""
            resp.found.append(value is not None)
        return resp

    def KvDeleteRange(self, req: pb.KvDeleteRangeRequest):
        resp = pb.KvDeleteRangeResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        clamped = _clamp_range_or_err(
            region, req.range.start_key, req.range.end_key, resp
        )
        if clamped is None:
            return resp
        try:
            # count comes from the applied write itself (exact under
            # concurrent writes; also no follower-side scan before the
            # NotLeader rejection)
            resp.delete_count = self.node.storage.kv_delete_range(
                region, [clamped]
            )
        except NotLeader as e:
            return _err(resp, 20001, f"not leader: {e.leader_hint}")
        return resp

    def KvPutIfAbsent(self, req: pb.KvPutIfAbsentRequest):
        """KvPutIfAbsent / KvBatchPutIfAbsent (store_service.cc KV set)."""
        resp = pb.KvPutIfAbsentResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        if not _keys_in_region_or_err(
            region, [kv.key for kv in req.kvs], resp
        ):
            return resp
        try:
            states = self.node.storage.kv_put_if_absent(
                region, [(kv.key, kv.value) for kv in req.kvs],
                is_atomic=req.is_atomic,
            )
        except NotLeader as e:
            return _err(resp, 20001, f"not leader: {e.leader_hint}")
        resp.key_states.extend(states)
        return resp

    def KvCompareAndSet(self, req: pb.KvCompareAndSetRequest):
        """KvCompareAndSet (store_service.cc): expect_value b'' means
        'expect absent' (the reference's empty-value convention)."""
        resp = pb.KvCompareAndSetResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        if not _keys_in_region_or_err(region, [req.kv.key], resp):
            return resp
        expect = req.expect_value if req.expect_value else None
        try:
            resp.key_state = self.node.storage.kv_compare_and_set(
                region, req.kv.key, expect, req.kv.value
            )
        except NotLeader as e:
            return _err(resp, 20001, f"not leader: {e.leader_hint}")
        return resp

    def KvBatchDelete(self, req: pb.KvBatchDeleteRequest):
        resp = pb.KvBatchDeleteResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        if not _keys_in_region_or_err(region, list(req.keys), resp):
            return resp
        try:
            self.node.storage.kv_batch_delete(region, list(req.keys))
        except NotLeader as e:
            return _err(resp, 20001, f"not leader: {e.leader_hint}")
        return resp

    def KvScan(self, req: pb.KvScanRequest) -> pb.KvScanResponse:
        resp = pb.KvScanResponse()
        region = self._leader_region_or_err(req.context, resp)
        if region is None:
            return resp
        try:
            cop = convert.coprocessor_from_pb(req.coprocessor)
        except ValueError as e:
            return _err(resp, 60001, f"bad coprocessor: {e}")
        clamped = _clamp_range_or_err(
            region, req.range.start_key, req.range.end_key, resp
        )
        if clamped is None:
            return resp
        pairs = self.node.storage.kv_scan(
            region, clamped[0], clamped[1],
            # coprocessor filtering happens after the scan; a pre-filter
            # limit would truncate the candidate set
            limit=0 if cop is not None else req.limit,
            keys_only=req.keys_only and cop is None,
        )
        if cop is not None:
            try:
                pairs = cop.execute(pairs)
            except ValueError as e:
                return _err(resp, 60002, f"coprocessor execute: {e}")
            if req.limit:
                pairs = pairs[: req.limit]
        for k, v in pairs:
            kv = resp.kvs.add()
            kv.key = k
            kv.value = v
        return resp

    # ---- scan sessions (ScanManager v1/v2 + Stream paging) ----
    def KvScanBegin(self, req: pb.KvScanBeginRequest) -> pb.KvScanBeginResponse:
        resp = pb.KvScanBeginResponse()
        region = self._leader_region_or_err(req.context, resp)
        if region is None:
            return resp
        from dingo_tpu.engine.raw_engine import CF_DEFAULT
        from dingo_tpu.mvcc.codec import MAX_TS
        from dingo_tpu.mvcc.reader import Reader as MvccReader

        clamped = _clamp_range_or_err(
            region, req.range.start_key, req.range.end_key, resp)
        if clamped is None:
            return resp
        reader = MvccReader(self.node.raw, CF_DEFAULT)
        # materialize at open: the session must be a stable snapshot —
        # paging a live iterator would skip/repeat keys under concurrent
        # writes (the reference ScanManager pins a snapshot the same way)
        snapshot = tuple(reader.iter_visible(
            clamped[0], clamped[1], req.context.read_ts or MAX_TS,
        ))
        stream = _SCAN_SESSIONS.streams.open(iter(snapshot),
                                             limit=req.page_size or 100)
        items, more = stream.next_page()
        resp.scan_id = stream.id
        resp.has_more = more
        for k, v in items:
            kv = resp.kvs.add()
            kv.key = k
            kv.value = v
        if not more:
            _SCAN_SESSIONS.streams.release(stream.id)
        return resp

    def KvScanContinue(self, req: pb.KvScanContinueRequest):
        resp = pb.KvScanContinueResponse()
        stream = _SCAN_SESSIONS.streams.get(req.scan_id)
        if stream is None:
            return _err(resp, 10010, f"unknown scan {req.scan_id}")
        items, more = stream.next_page(req.page_size or None)
        resp.has_more = more
        for k, v in items:
            kv = resp.kvs.add()
            kv.key = k
            kv.value = v
        if not more:
            _SCAN_SESSIONS.streams.release(req.scan_id)
        return resp

    def KvScanRelease(self, req: pb.KvScanReleaseRequest):
        resp = pb.KvScanReleaseResponse()
        _SCAN_SESSIONS.streams.release(req.scan_id)
        return resp

    # ---- txn ----
    def _leader_region_or_err(self, context_pb, resp):
        """KV and txn RPCs are leader-gated — reads included: a follower
        lagging raft apply would serve state missing already-committed
        writes (the reference serves reads through the raft leader; write
        RPCs would fail at propose anyway, this just fails them earlier
        with the routing hint). Caveat: this is a ROLE check, not a
        read-index/leader-lease pass — a deposed leader that has not yet
        seen the new term can still serve a bounded-stale read during a
        partition (closing that window needs read-index or check-quorum
        in raft/core.py; tracked, matches the coordinator's documented
        stale-read stance in coordinator/raft_meta.py)."""
        region = _region_or_err(self.node, context_pb, resp)
        if region is None:
            return None
        raft = self.node.engine.get_node(region.id)
        if raft is not None and not raft.is_leader():
            hint = getattr(raft, "leader_id", None) or ""
            _err(resp, 20001, f"not leader: {hint}")
            return None
        return region

    def TxnPrewrite(self, req: pb.TxnPrewriteRequest):
        resp = pb.TxnPrewriteResponse()
        region = self._leader_region_or_err(req.context, resp)
        if region is None:
            return resp
        muts = [
            Mutation(Op(m.op), m.key, m.value) for m in req.mutations
        ]
        try:
            self._txn(region).prewrite(
                muts, req.primary_lock, req.start_ts,
                lock_ttl_ms=req.lock_ttl_ms or 3000,
                for_update_ts=req.for_update_ts,
            )
        except TxnError as e:
            return _err(resp, 40001, str(e))
        return resp

    def TxnCommit(self, req: pb.TxnCommitRequest):
        resp = pb.TxnCommitResponse()
        region = self._leader_region_or_err(req.context, resp)
        if region is None:
            return resp
        try:
            self._txn(region).commit(list(req.keys), req.start_ts, req.commit_ts)
        except TxnError as e:
            return _err(resp, 40001, str(e))
        return resp

    def TxnGet(self, req: pb.TxnGetRequest):
        resp = pb.TxnGetResponse()
        region = self._leader_region_or_err(req.context, resp)
        if region is None:
            return resp
        try:
            value = self._txn(region).get(req.key, req.start_ts)
        except TxnError as e:
            return _err(resp, 40001, str(e))
        resp.found = value is not None
        resp.value = value or b""
        return resp

    def TxnScan(self, req: pb.TxnScanRequest):
        resp = pb.TxnScanResponse()
        region = self._leader_region_or_err(req.context, resp)
        if region is None:
            return resp
        try:
            cop = convert.coprocessor_from_pb(req.coprocessor)
        except ValueError as e:
            return _err(resp, 60001, f"bad coprocessor: {e}")
        try:
            pairs = self._txn(region).scan(
                req.range.start_key, req.range.end_key, req.start_ts,
                limit=0 if cop is not None else req.limit,
            )
        except TxnError as e:
            return _err(resp, 40001, str(e))
        if cop is not None:
            import struct as _struct

            try:
                pairs = cop.execute(pairs, limit=req.limit)
            except (ValueError, IndexError, _struct.error) as e:
                return _err(resp, 60002, f"coprocessor execute: {e}")
        for k, v in pairs:
            kv = resp.kvs.add()
            kv.key = k
            kv.value = v
        return resp

    def TxnBatchRollback(self, req: pb.TxnBatchRollbackRequest):
        resp = pb.TxnBatchRollbackResponse()
        region = self._leader_region_or_err(req.context, resp)
        if region is None:
            return resp
        try:
            self._txn(region).batch_rollback(list(req.keys), req.start_ts)
        except TxnError as e:
            return _err(resp, 40001, str(e))
        return resp

    def TxnCheckStatus(self, req: pb.TxnCheckStatusRequest):
        resp = pb.TxnCheckStatusResponse()
        region = self._leader_region_or_err(req.context, resp)
        if region is None:
            return resp
        st = self._txn(region).check_txn_status(
            req.primary_key, req.lock_ts, req.caller_start_ts
        )
        resp.action = st["action"]
        resp.commit_ts = st["commit_ts"]
        return resp

    # -- pessimistic / maintenance txn surface (store_service.h exposes 16
    # Txn RPCs; engine semantics live in engine/txn.py) ----------------------
    def TxnPessimisticLock(self, req: pb.TxnPessimisticLockRequest):
        resp = pb.TxnPessimisticLockResponse()
        region = self._leader_region_or_err(req.context, resp)
        if region is None:
            return resp
        try:
            self._txn(region).pessimistic_lock(
                list(req.keys), req.primary_lock, req.start_ts,
                req.for_update_ts, ttl_ms=req.lock_ttl_ms or 3000,
            )
        except TxnError as e:
            return _err(resp, 40001, str(e))
        return resp

    def TxnPessimisticRollback(self, req: pb.TxnPessimisticRollbackRequest):
        resp = pb.TxnPessimisticRollbackResponse()
        region = self._leader_region_or_err(req.context, resp)
        if region is None:
            return resp
        try:
            self._txn(region).pessimistic_rollback(
                list(req.keys), req.start_ts)
        except TxnError as e:
            return _err(resp, 40001, str(e))
        return resp

    def TxnResolveLock(self, req: pb.TxnResolveLockRequest):
        resp = pb.TxnResolveLockResponse()
        region = self._leader_region_or_err(req.context, resp)
        if region is None:
            return resp
        try:
            resp.resolved = self._txn(region).resolve_lock(
                req.start_ts, req.commit_ts,
                keys=list(req.keys) or None,
            )
        except TxnError as e:
            return _err(resp, 40001, str(e))
        return resp

    def TxnHeartBeat(self, req: pb.TxnHeartBeatRequest):
        resp = pb.TxnHeartBeatResponse()
        region = self._leader_region_or_err(req.context, resp)
        if region is None:
            return resp
        try:
            resp.lock_ttl_ms = self._txn(region).heart_beat(
                req.primary_lock, req.start_ts, req.advise_lock_ttl_ms)
        except TxnError as e:
            return _err(resp, 40001, str(e))
        return resp

    def TxnGc(self, req: pb.TxnGcRequest):
        resp = pb.TxnGcResponse()
        region = self._leader_region_or_err(req.context, resp)
        if region is None:
            return resp
        try:
            resp.deleted = self._txn(region).gc(req.safe_point_ts)
        except TxnError as e:
            return _err(resp, 40001, str(e))
        return resp

    @staticmethod
    def _lock_to_pb(dst, key: bytes, lock) -> None:
        dst.key = key
        dst.lock_ts = lock.lock_ts
        dst.primary_lock = lock.primary
        dst.op = lock.op.value
        dst.ttl_ms = lock.ttl_ms
        dst.for_update_ts = lock.for_update_ts

    def TxnScanLock(self, req: pb.TxnScanLockRequest):
        resp = pb.TxnScanLockResponse()
        region = self._leader_region_or_err(req.context, resp)
        if region is None:
            return resp
        from dingo_tpu.mvcc.codec import MAX_TS as _MAX_TS

        locks = self._txn(region).scan_lock(
            req.range.start_key, req.range.end_key,
            max_ts=req.max_ts or _MAX_TS, limit=req.limit,
        )
        for key, lock in locks:
            self._lock_to_pb(resp.locks.add(), key, lock)
        return resp

    def TxnBatchGet(self, req: pb.TxnBatchGetRequest):
        resp = pb.TxnBatchGetResponse()
        region = self._leader_region_or_err(req.context, resp)
        if region is None:
            return resp
        try:
            pairs = self._txn(region).batch_get(list(req.keys), req.start_ts)
        except TxnError as e:
            return _err(resp, 40001, str(e))
        for key, value in pairs:
            if value is None:
                continue
            kv = resp.kvs.add()
            kv.key = key
            kv.value = value
        return resp

    def TxnCheckSecondaryLocks(self, req: pb.TxnCheckSecondaryLocksRequest):
        resp = pb.TxnCheckSecondaryLocksResponse()
        region = self._leader_region_or_err(req.context, resp)
        if region is None:
            return resp
        st = self._txn(region).check_secondary_locks(
            list(req.keys), req.start_ts)
        for key, lock in st["locks"]:
            self._lock_to_pb(resp.locks.add(), key, lock)
        resp.commit_ts = st["commit_ts"]
        resp.missing_keys.extend(st["missing"])
        return resp

    def TxnDeleteRange(self, req: pb.TxnDeleteRangeRequest):
        resp = pb.TxnDeleteRangeResponse()
        region = self._leader_region_or_err(req.context, resp)
        if region is None:
            return resp
        try:
            self._txn(region).delete_range(
                req.range.start_key, req.range.end_key)
        except TxnError as e:
            return _err(resp, 40001, str(e))
        return resp

    def TxnDump(self, req: pb.TxnDumpRequest):
        resp = pb.TxnDumpResponse()
        region = self._leader_region_or_err(req.context, resp)
        if region is None:
            return resp
        d = self._txn(region).dump(
            req.range.start_key, req.range.end_key, limit=req.limit)
        for e in d["locks"]:
            li = resp.locks.add()
            li.key, li.lock_ts, li.primary_lock = (
                e["key"], e["lock_ts"], e["primary"])
            li.op, li.ttl_ms, li.for_update_ts = (
                e["op"], e["ttl_ms"], e["for_update_ts"])
        for e in d["writes"]:
            wi = resp.writes.add()
            wi.key, wi.commit_ts = e["key"], e["commit_ts"]
            wi.start_ts, wi.op = e["start_ts"], e["op"]
        for e in d["datas"]:
            di = resp.datas.add()
            di.key, di.start_ts, di.value = (
                e["key"], e["start_ts"], e["value"])
        return resp


class DocumentService:
    """Full-text RPCs (reference DocumentService, server/main.cc:1176)."""

    def __init__(self, node: StoreNode):
        self.node = node

    def DocumentAdd(self, req: pb.DocumentAddRequest) -> pb.DocumentAddResponse:
        from dingo_tpu.engine import write_data as wd

        resp = pb.DocumentAddResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        if region.document_index is None:
            return _err(resp, 80001, "not a DOCUMENT region")
        ids = [d.id for d in req.documents]
        docs = [convert.scalar_from_pb(d.fields) for d in req.documents]
        # typed-schema validation BEFORE the raft propose: a doc that can
        # never apply must not enter the log (apply-time failures would
        # have to fail identically on every replica forever)
        from dingo_tpu.document.index import SchemaError

        try:
            for doc in docs:
                region.document_index.check_doc(doc)
        except SchemaError as e:
            return _err(resp, 80002, str(e))
        try:
            ts = self.node.storage.ts_provider.get_ts()
            self.node.engine.write(region, wd.DocumentAddData(
                ts=ts, ids=ids, documents=docs, is_update=req.is_update,
            ))
        except NotLeader as e:
            return _err(resp, 20001, f"not leader: {e.leader_hint}")
        resp.ts = ts
        return resp

    def DocumentDelete(self, req: pb.DocumentDeleteRequest):
        from dingo_tpu.engine import write_data as wd

        resp = pb.DocumentDeleteResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        if region.document_index is None:
            return _err(resp, 80001, "not a DOCUMENT region")
        try:
            ts = self.node.storage.ts_provider.get_ts()
            self.node.engine.write(region, wd.DocumentDeleteData(
                ts=ts, ids=list(req.ids),
            ))
        except NotLeader as e:
            return _err(resp, 20001, f"not leader: {e.leader_hint}")
        return resp

    def DocumentSearch(self, req: pb.DocumentSearchRequest):
        resp = pb.DocumentSearchResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        if region.document_index is None:
            return _err(resp, 80001, "not a DOCUMENT region")
        hits = region.document_index.search(
            req.query,
            topk=req.top_n or 10,
            mode=req.mode or "or",
            column_filter=convert.scalar_from_pb(req.column_filter) or None,
        )
        for did, score in hits:
            d = resp.documents.add()
            d.id = did
            d.score = score
            if req.with_fields:
                doc = region.document_index.get(did)
                if doc:
                    convert.scalar_to_pb(d.fields, doc)
        return resp

    def DocumentCount(self, req: pb.DocumentCountRequest):
        resp = pb.DocumentCountResponse()
        region = _region_or_err(self.node, req.context, resp)
        if region is None:
            return resp
        if region.document_index is None:
            return _err(resp, 80001, "not a DOCUMENT region")
        resp.count = region.document_index.count()
        return resp


class _ScanSessions:
    """Shared StreamManager for KvScan sessions (ScanManager v2 role)."""

    def __init__(self):
        from dingo_tpu.common.stream import StreamManager

        self.streams = StreamManager(idle_timeout_s=60.0)


_SCAN_SESSIONS = _ScanSessions()


class PushService:
    """Coordinator -> store push of store operations (push_service.h — the
    inverse of the heartbeat pull)."""

    def __init__(self, node: StoreNode):
        self.node = node

    def PushStoreOperation(self, req: pb.PushStoreOperationRequest):
        resp = pb.PushStoreOperationResponse()
        for c in req.commands:
            # per-command isolation: a malformed or failing command must not
            # abort the batch or lose acks for commands that DID execute
            try:
                cmd = convert.region_cmd_from_pb(c)
                self.node.execute_region_cmd(cmd)
                resp.done_cmd_ids.append(c.cmd_id)
            except NotLeader as e:
                if self.node.coordinator is not None and e.leader_hint:
                    self.node.coordinator.requeue_cmd(
                        cmd, e.leader_hint.split("/")[0],
                        from_store=self.node.store_id,
                    )
            except Exception:  # noqa: BLE001
                pass
        return resp


class NodeService:
    def __init__(self, node: StoreNode):
        self.node = node

    def GetVectorIndexSnapshotMeta(
        self, req: pb.VectorIndexSnapshotMetaRequest
    ) -> pb.VectorIndexSnapshotMetaResponse:
        """Snapshot manifest for peer pull (node_service.h:45-52 flow)."""
        import os

        resp = pb.VectorIndexSnapshotMetaResponse()
        mgr = self.node.index_manager
        if not mgr.snapshot_root:
            return _err(resp, 90001, "store has no snapshot root")
        path = mgr.snapshot_path(req.region_id)
        if not os.path.isdir(path):
            return _err(resp, 90002, f"no snapshot for region {req.region_id}")
        region = self.node.get_region(req.region_id)
        if region is not None and region.vector_index_wrapper is not None:
            resp.snapshot_log_id = region.vector_index_wrapper.snapshot_log_id
        for name in sorted(os.listdir(path)):
            full = os.path.join(path, name)
            if os.path.isfile(full):
                f = resp.files.add()
                f.name = name
                f.size = os.path.getsize(full)
        return resp

    def NodeInfo(self, req: pb.NodeInfoRequest) -> pb.NodeInfoResponse:
        resp = pb.NodeInfoResponse()
        resp.store_id = self.node.store_id
        regions = self.node.meta.get_all_regions()
        resp.region_ids.extend(r.id for r in regions)
        resp.leader_region_ids.extend(
            r.id for r in regions
            if (n := self.node.engine.get_node(r.id)) is not None
            and n.is_leader()
        )
        return resp

    def SetLogLevel(self, req: pb.SetLogLevelRequest):
        """Runtime log-level flip (node_service.h log-level RPC)."""
        from dingo_tpu.common import log as dlog

        resp = pb.SetLogLevelResponse()
        try:
            dlog.set_level(req.level, module=req.module or None)
        except ValueError as e:
            return _err(resp, 90003, str(e))
        dlog.get_logger("node").info(
            "log level set to %s (module=%s)", req.level.upper(),
            req.module or "<all>")
        return resp

    def GetLogLevel(self, req: pb.GetLogLevelRequest):
        from dingo_tpu.common import log as dlog

        resp = pb.GetLogLevelResponse()
        for module, level in sorted(dlog.get_levels().items()):
            e = resp.levels.add()
            e.module = module
            e.level = level
        return resp


class FileService:
    """Chunked snapshot file download (reference file_service.{h,cc}: the
    vector-index snapshot transfer's data plane)."""

    CHUNK = 1 << 20

    def __init__(self, node: StoreNode):
        self.node = node

    def ReadFileChunk(self, req: pb.FileChunkRequest) -> pb.FileChunkResponse:
        import os

        resp = pb.FileChunkResponse()
        mgr = self.node.index_manager
        if not mgr.snapshot_root:
            return _err(resp, 90001, "store has no snapshot root")
        base = os.path.realpath(mgr.snapshot_path(req.region_id))
        full = os.path.realpath(os.path.join(base, req.name))
        # no path escape: serve only files inside the region's snapshot dir
        if not full.startswith(base + os.sep):
            return _err(resp, 90003, "invalid file name")
        if not os.path.isfile(full):
            return _err(resp, 90002, f"no such file {req.name}")
        size = min(req.size or self.CHUNK, self.CHUNK)
        with open(full, "rb") as f:
            f.seek(req.offset)
            resp.data = f.read(size)
        resp.eof = req.offset + len(resp.data) >= os.path.getsize(full)
        return resp


class DebugService:
    def __init__(self, device: bool = False):
        #: whether this role holds the device (store, diskann): only such
        #: a process may profile it (a coordinator must never touch one)
        self._device = device

    def MetricsDump(self, req: pb.MetricsDumpRequest) -> pb.MetricsDumpResponse:
        resp = pb.MetricsDumpResponse()
        fmt = req.format or "json"
        if fmt == "prometheus":
            # the payload field stays `json` (wire compatibility); the
            # content is Prometheus text exposition format
            resp.json = METRICS.render_prometheus()
        elif fmt == "json":
            resp.json = json.dumps(METRICS.dump())
        else:
            return _err(resp, 50002, f"unknown metrics format {fmt!r}")
        return resp

    def TraceDump(self, req: pb.MetricsDumpRequest) -> pb.MetricsDumpResponse:
        """Sampled span buffer + slow-query log as JSON (spans grouped by
        trace id) — the RPC face of dingo_tpu/trace."""
        from dingo_tpu.trace import to_json

        resp = pb.MetricsDumpResponse()
        resp.json = json.dumps(to_json())
        return resp

    def TraceChromeDump(self, req: pb.MetricsDumpRequest):
        """Same buffer in Chrome trace_event form: save the payload to a
        file and open it in chrome://tracing / Perfetto, or feed it to
        tools/trace_report.py for a per-stage latency table."""
        from dingo_tpu.trace import to_chrome_trace

        resp = pb.MetricsDumpResponse()
        resp.json = json.dumps(to_chrome_trace())
        return resp

    def DeviceProfile(self, req: pb.MetricsDumpRequest):
        """Profile the device for a few seconds of whatever this store is
        serving (trace/profile.py): `format` carries the parameters as
        JSON, {"seconds": 5, "dir": "<where>"}; both optional (5 s, a
        fresh temporary directory). Blocks for the interval; the reply's
        JSON names the `.xplane.pb`, the spans file written beside it and
        both clock pairs."""
        resp = pb.MetricsDumpResponse()
        if not self._device:
            return _err(resp, 50004, "this role holds no device to profile")
        from dingo_tpu.trace import profile

        try:
            params = json.loads(req.format) if req.format else {}
            if not isinstance(params, dict):
                raise ValueError("format must be a JSON object")
            out_dir = params.get("dir") or tempfile.mkdtemp(
                prefix="dingo_profile_")
            resp.json = json.dumps(
                profile.capture(out_dir, params.get("seconds", 5.0)))
        except (ValueError, RuntimeError, OSError) as e:
            return _err(resp, 50004, f"{type(e).__name__}: {e}")
        return resp

    def FailPoint(self, req: pb.FailPointRequest) -> pb.FailPointResponse:
        resp = pb.FailPointResponse()
        try:
            if req.remove:
                FAILPOINTS.remove(req.name)
            else:
                FAILPOINTS.configure(req.name, req.config)
        except ValueError as e:
            return _err(resp, 50001, str(e))
        return resp

    def FlightDump(self, req: pb.FlightDumpRequest) -> pb.FlightDumpResponse:
        """Flight-recorder export: bundle catalog always; one compressed
        payload (zlib JSON — tools/flight_report.py renders it) when
        include_payload is set (bundle_id empty = newest)."""
        from dingo_tpu.obs.flight import FLIGHT

        resp = pb.FlightDumpResponse()
        metas = FLIGHT.bundles_meta()
        for m in metas:
            out = resp.bundles.add()
            for field in ("id", "reason", "name", "trace_id", "region_id",
                          "created_ms", "payload_bytes"):
                setattr(out, field, m[field])
        if req.include_payload:
            found = FLIGHT.get_with_id(req.bundle_id)
            if found is None:
                return _err(
                    resp, 50003,
                    f"no flight bundle {req.bundle_id!r}" if req.bundle_id
                    else "no flight bundles captured",
                )
            # id + payload resolved atomically: a bundle captured between
            # the catalog read above and here can't mislabel the blob
            resp.payload_bundle_id, resp.payload = found
        return resp

    def EventDump(self, req: pb.EventDumpRequest) -> pb.EventDumpResponse:
        """This process's control-plane decision ring (obs/events.py),
        oldest first — harvested-but-unevicted events included, so the
        local view overlaps the coordinator's merged timeline."""
        from dingo_tpu.obs.events import EVENTS

        resp = pb.EventDumpResponse()
        for ev in EVENTS.recent(
            limit=int(req.limit) or 0,
            region_id=req.region_id or None,
            actor=req.actor,
        ):
            convert.control_event_to_pb(ev, resp.events.add())
        resp.dropped = EVENTS.dropped
        return resp


class CoordinatorService:
    def __init__(self, control: CoordinatorControl, tso: TsoControl):
        self.control = control
        self.tso = tso

    def Hello(self, req: pb.HelloRequest) -> pb.HelloResponse:
        resp = pb.HelloResponse()
        resp.store_count = len(self.control.stores)
        resp.region_count = len(self.control.regions)
        return resp

    def StoreHeartbeat(self, req: pb.StoreHeartbeatRequest):
        resp = pb.StoreHeartbeatResponse()
        cmds = self.control.store_heartbeat(
            req.store_id,
            region_ids=list(req.region_ids),
            leader_region_ids=list(req.leader_region_ids),
            capacity_bytes=req.capacity_bytes,
            used_bytes=req.used_bytes,
            region_defs=[
                convert.region_def_from_pb(d) for d in req.region_definitions
            ],
            done_cmd_ids=list(req.done_cmd_ids),
            failed_cmd_ids=list(req.failed_cmd_ids),
            stalled_cmd_ids=list(req.stalled_cmd_ids),
            metrics=(
                convert.store_metrics_from_pb(req.metrics)
                if req.HasField("metrics") else None
            ),
        )
        for c in cmds:
            out = resp.commands.add()
            out.cmd_id = c.cmd_id
            out.region_id = c.region_id
            out.cmd_type = c.cmd_type.value
            out.split_key = c.split_key
            out.child_region_id = c.child_region_id
            out.target_store_id = c.target_store_id
            if c.definition is not None:
                out.definition.CopyFrom(convert.region_def_to_pb(c.definition))
        return resp

    def CreateRegion(self, req: pb.CreateRegionRequest):
        resp = pb.CreateRegionResponse()
        try:
            d = self.control.create_region(
                start_key=req.range.start_key,
                end_key=req.range.end_key,
                partition_id=req.partition_id,
                region_type=[RegionType.STORE, RegionType.INDEX,
                             RegionType.DOCUMENT][req.region_type],
                index_parameter=convert.index_parameter_from_pb(
                    req.index_parameter
                ),
                replication=req.replication or None,
                document_schema=(
                    {c.name: c.sql_type for c in req.document_schema}
                    if req.document_schema else None
                ),
            )
        except RuntimeError as e:
            return _err(resp, 60001, str(e))
        resp.definition.CopyFrom(convert.region_def_to_pb(d))
        return resp

    def SplitRegion(self, req: pb.SplitRegionRequest):
        resp = pb.SplitRegionResponse()
        try:
            resp.child_region_id = self.control.split_region(
                req.region_id, req.split_key
            )
        except (KeyError, ValueError) as e:
            return _err(resp, 60002, str(e))
        return resp

    def MergeRegion(self, req: pb.MergeRegionRequest):
        """Operator region op (coordinator_service.cc MergeRegion): queue
        MERGE to the target's leader; adjacency/co-location validated."""
        resp = pb.MergeRegionResponse()
        try:
            self.control.merge_region(
                req.target_region_id, req.source_region_id)
        except (KeyError, ValueError) as e:
            return _err(resp, 60002, str(e))
        return resp

    def ChangePeerRegion(self, req: pb.ChangePeerRegionRequest):
        """Operator region op: replace the region's peer set (additions
        get CREATE, survivors CHANGE_PEER, removals DELETE)."""
        resp = pb.ChangePeerRegionResponse()
        if not req.new_peers:
            return _err(resp, 60002, "empty peer set")
        try:
            self.control.change_peer(req.region_id, list(req.new_peers))
        except (KeyError, ValueError) as e:
            return _err(resp, 60002, str(e))
        return resp

    def TransferLeaderRegion(self, req: pb.TransferLeaderRegionRequest):
        """Operator region op: ask the current leader to hand off."""
        resp = pb.TransferLeaderRegionResponse()
        try:
            self.control.transfer_leader(req.region_id, req.target_store)
        except (KeyError, ValueError) as e:
            return _err(resp, 60002, str(e))
        return resp

    def GetRegionMap(self, req: pb.GetRegionMapRequest):
        resp = pb.GetRegionMapResponse()
        for d in self.control.regions.values():
            resp.regions.add().CopyFrom(convert.region_def_to_pb(d))
        return resp

    def RequeueRegionCmd(self, req: pb.RequeueRegionCmdRequest):
        resp = pb.RequeueRegionCmdResponse()
        cmd = convert.region_cmd_from_pb(req.cmd)
        self.control.requeue_cmd(cmd, req.target_store_id,
                                 from_store=req.from_store_id or None)
        return resp

    def GetGCSafePoint(self, req: pb.GetGCSafePointRequest):
        """GC safe point = now - retention (tso-format). Stores poll this
        and run MVCC GC below it (gc_safe_point push/pull flow)."""
        resp = pb.GetGCSafePointResponse()
        resp.safe_ts = self.control.gc_safe_ts(self.tso)
        return resp

    def Tso(self, req: pb.TsoRequest) -> pb.TsoResponse:
        resp = pb.TsoResponse()
        first, count = self.tso.gen_ts(req.count or 1)
        resp.first_ts = first
        resp.count = count
        return resp

    def TsoAdvance(self, req: pb.TsoAdvanceRequest) -> pb.TsoAdvanceResponse:
        """Restore path: future timestamps must stay above the restored
        cluster's watermark or MVCC versions would collide."""
        resp = pb.TsoAdvanceResponse()
        self.tso.advance_to(req.ts)
        return resp


class VersionService:
    """etcd-like KV (version_service.cc analog over KvControl)."""

    def __init__(self, kv: KvControl):
        self.kv = kv
        self._watch_slots = threading.Semaphore(self._MAX_BLOCKED_WATCHES)

    def VKvPut(self, req: pb.VKvPutRequest) -> pb.VKvPutResponse:
        resp = pb.VKvPutResponse()
        try:
            resp.revision = self.kv.kv_put(req.key, req.value, req.lease_id)
        except KeyError as e:
            return _err(resp, 70001, str(e))
        return resp

    @staticmethod
    def _item_to_pb(it, o) -> None:
        o.key = it.key
        o.value = it.value
        o.create_revision = it.create_revision
        o.mod_revision = it.mod_revision
        o.version = it.version

    def VKvRange(self, req: pb.VKvRangeRequest) -> pb.VKvRangeResponse:
        resp = pb.VKvRangeResponse()
        try:
            items, rev = self.kv.kv_range(
                req.start, req.end or None, limit=req.limit,
                revision=req.revision,
            )
        except CompactedError as e:
            return _err(resp, 70002, str(e))
        except FutureRevError as e:
            return _err(resp, 70003, str(e))
        resp.revision = rev
        for it in items:
            self._item_to_pb(it, resp.items.add())
        return resp

    def VKvDeleteRange(self, req: pb.VKvDeleteRangeRequest):
        resp = pb.VKvDeleteRangeResponse()
        resp.deleted = self.kv.kv_delete_range(req.start, req.end or None)
        return resp

    def VKvCompaction(self, req: pb.VKvCompactionRequest):
        """KvCompaction RPC (kv_control.h:287)."""
        resp = pb.VKvCompactionResponse()
        resp.removed_versions = self.kv.kv_compaction(req.revision)
        resp.compact_revision = self.kv._compact_revision
        return resp

    #: cap on concurrently BLOCKED watch polls: the grpc pool is shared
    #: with the puts that would wake the watchers, so unbounded long-polls
    #: could starve the writers and wedge the server
    _MAX_BLOCKED_WATCHES = 8

    def VKvWatch(self, req: pb.VKvWatchRequest) -> pb.VKvWatchResponse:
        """One-time watch with history replay (kv_control.h:47-113):
        events at/after start_revision fire immediately from the revision
        chain; otherwise long-poll up to timeout_ms. Unset start_revision
        means "from now" (etcd watch semantics), NOT from history."""
        resp = pb.VKvWatchResponse()
        start = req.start_revision or (self.kv._revision + 1)
        # pin the window even on timeout: the server clamps long polls
        # (_MAX_WATCH_TIMEOUT_MS), so a client that re-polled "from now"
        # would drop any event landing in the turnaround gap — re-polling
        # from revision + 1 replays it from the revision chain instead
        resp.revision = start - 1
        try:
            args, busy = _long_poll_watch(
                lambda cb: self.kv.watch(req.key, start, cb),
                lambda cb: self.kv.cancel_watch(req.key, cb),
                self._watch_slots, req.timeout_ms,
            )
        except CompactedError as e:
            return _err(resp, 70002, str(e))
        if busy:
            return _err(resp, 70004, "too many blocked watchers")
        if args is not None:
            event, item = args
            resp.fired = True
            resp.event = event
            resp.revision = item.mod_revision
            self._item_to_pb(item, resp.item)
        return resp

    def LeaseGrant(self, req: pb.LeaseGrantRequest) -> pb.LeaseGrantResponse:
        resp = pb.LeaseGrantResponse()
        resp.lease_id = self.kv.lease_grant(req.ttl_s).lease_id
        return resp

    def LeaseRenew(self, req: pb.LeaseRenewRequest):
        resp = pb.LeaseRenewResponse()
        try:
            resp.ttl_s = self.kv.lease_renew(req.lease_id).ttl_s
        except KeyError as e:
            return _err(resp, 70001, str(e))
        return resp

    def LeaseRevoke(self, req: pb.LeaseRevokeRequest):
        resp = pb.LeaseRevokeResponse()
        resp.deleted = self.kv.lease_revoke(req.lease_id)
        return resp


class MetaService:
    """Schema/table meta RPCs (reference src/server/meta_service.cc)."""

    #: same rationale as VersionService: blocked long-polls must not be
    #: able to occupy the whole shared grpc pool
    _MAX_BLOCKED_WATCHES = 8

    def __init__(self, meta):
        from dingo_tpu.coordinator.meta import MetaControl

        self.meta: MetaControl = meta
        self._watch_slots = threading.Semaphore(self._MAX_BLOCKED_WATCHES)

    @staticmethod
    def _table_to_pb(t, out) -> None:
        from dingo_tpu.store.region import RegionType

        out.table_id = t.table_id
        out.schema_name = t.schema_name
        out.name = t.name
        out.table_type = [RegionType.STORE, RegionType.INDEX,
                          RegionType.DOCUMENT].index(t.table_type)
        out.replication = t.replication
        for c in t.columns:
            col = out.columns.add()
            col.name, col.sql_type = c.name, c.sql_type
            col.nullable, col.primary = c.nullable, c.primary
        for p in t.partitions:
            pp = out.partitions.add()
            pp.partition_id = p.partition_id
            pp.id_lo, pp.id_hi = p.id_lo, p.id_hi
            pp.start_key, pp.end_key = p.start_key, p.end_key
            pp.region_id = p.region_id
        if t.index_parameter is not None:
            out.index_parameter.CopyFrom(
                convert.index_parameter_to_pb(t.index_parameter)
            )

    def CreateSchema(self, req: pb.CreateSchemaRequest):
        from dingo_tpu.coordinator.meta import MetaError, MetaExistsError

        resp = pb.CreateSchemaResponse()
        try:
            self.meta.create_schema(req.schema_name)
        except MetaExistsError as e:
            return _err(resp, 40002, str(e))
        except MetaError as e:
            return _err(resp, 40001, str(e))
        return resp

    def DropSchema(self, req: pb.DropSchemaRequest):
        from dingo_tpu.coordinator.meta import MetaError

        resp = pb.DropSchemaResponse()
        try:
            self.meta.drop_schema(req.schema_name)
        except MetaError as e:
            return _err(resp, 40001, str(e))
        return resp

    def GetSchemas(self, req: pb.GetSchemasRequest):
        resp = pb.GetSchemasResponse()
        resp.schema_names.extend(self.meta.get_schemas())
        return resp

    def CreateTable(self, req: pb.CreateTableRequest):
        from dingo_tpu.coordinator.meta import (
            ColumnDefinition,
            MetaError,
            PartitionDefinition,
        )
        from dingo_tpu.store.region import RegionType

        resp = pb.CreateTableResponse()
        d = req.definition
        columns = [
            ColumnDefinition(c.name, c.sql_type or "VARCHAR",
                             c.nullable, c.primary)
            for c in d.columns
        ]
        partitions = [
            PartitionDefinition(
                partition_id=p.partition_id, id_lo=p.id_lo, id_hi=p.id_hi,
                start_key=p.start_key, end_key=p.end_key,
            )
            for p in d.partitions
        ]
        param = (
            convert.index_parameter_from_pb(d.index_parameter)
            if d.HasField("index_parameter") else None
        )
        table_type = [RegionType.STORE, RegionType.INDEX,
                      RegionType.DOCUMENT][d.table_type]
        try:
            t = self.meta.create_table(
                d.schema_name, d.name, partitions,
                columns=columns, index_parameter=param,
                table_type=table_type, replication=d.replication,
            )
        except (MetaError, RuntimeError) as e:
            return _err(resp, 40001, str(e))
        self._table_to_pb(t, resp.definition)
        return resp

    def ImportTable(self, req: pb.ImportTableRequest):
        """Restore-path registration: partitions must already point at
        live regions (no region creation — reference br restore)."""
        from dingo_tpu.coordinator.meta import (
            ColumnDefinition,
            MetaError,
            MetaExistsError,
            PartitionDefinition,
            TableDefinition,
        )
        from dingo_tpu.store.region import RegionType

        resp = pb.ImportTableResponse()
        d = req.definition
        t = TableDefinition(
            table_id=0,
            schema_name=d.schema_name,
            name=d.name,
            table_type=[RegionType.STORE, RegionType.INDEX,
                        RegionType.DOCUMENT][d.table_type],
            columns=[
                ColumnDefinition(c.name, c.sql_type or "VARCHAR",
                                 c.nullable, c.primary)
                for c in d.columns
            ],
            partitions=[
                PartitionDefinition(
                    partition_id=p.partition_id, id_lo=p.id_lo,
                    id_hi=p.id_hi, start_key=p.start_key,
                    end_key=p.end_key, region_id=p.region_id,
                )
                for p in d.partitions
            ],
            index_parameter=(
                convert.index_parameter_from_pb(d.index_parameter)
                if d.HasField("index_parameter") else None
            ),
        )
        try:
            registered = self.meta.import_table(t)
        except MetaExistsError as e:
            return _err(resp, 40002, str(e))
        except (MetaError, RuntimeError) as e:
            return _err(resp, 40001, str(e))
        self._table_to_pb(registered, resp.definition)
        return resp

    def DropTable(self, req: pb.DropTableRequest):
        from dingo_tpu.coordinator.meta import MetaError

        resp = pb.DropTableResponse()
        try:
            self.meta.drop_table(req.schema_name, req.table_name)
        except MetaError as e:
            return _err(resp, 40001, str(e))
        return resp

    def GetTable(self, req: pb.GetTableRequest):
        resp = pb.GetTableResponse()
        t = self.meta.get_table(req.schema_name, req.table_name)
        resp.found = t is not None
        if t is not None:
            self._table_to_pb(t, resp.definition)
        return resp

    def GetTables(self, req: pb.GetTablesRequest):
        resp = pb.GetTablesResponse()
        for t in self.meta.get_tables(req.schema_name):
            self._table_to_pb(t, resp.definitions.add())
        return resp

    def MetaWatch(self, req: pb.MetaWatchRequest) -> pb.MetaWatchResponse:
        """Meta-watch RPC (meta_service.cc analog): one-shot schema/table
        change event with replay, or long-poll up to timeout_ms. Unset
        start_revision = from now. A timed-out response still carries the
        current revision so the next poll can pin its window (events
        between polls must not be lost)."""
        resp = pb.MetaWatchResponse()
        start = req.start_revision or (self.meta.meta_revision + 1)
        args, busy = _long_poll_watch(
            lambda cb: self.meta.watch(start, cb),
            lambda cb: self.meta.cancel_watch(cb),
            self._watch_slots, req.timeout_ms,
        )
        if busy:
            return _err(resp, 70004, "too many blocked watchers")
        if args is not None:
            (ev,) = args
            resp.fired = True
            resp.event = ev["event"]
            resp.schema_name = ev["schema"]
            resp.table_name = ev["table"]
            resp.table_id = ev["table_id"]
            resp.revision = ev["revision"]
        else:
            # not fired: report where the watch window started so the
            # client resumes from revision+1 without a gap
            resp.revision = start - 1
        return resp


class JobService:
    """Job introspection (reference JobService, main.cc registry): lists
    the coordinator's queued/active region commands."""

    def __init__(self, control: CoordinatorControl):
        self.control = control

    def ListJobs(self, req: pb.ListJobsRequest):
        resp = pb.ListJobsResponse()
        with self.control._lock:
            # jobs is the retained history — store_ops queues are pruned
            # once the store acks execution
            for cmd in self.control.jobs:
                if cmd.status == "done" and not req.include_done:
                    continue
                j = resp.jobs.add()
                j.cmd_id = cmd.cmd_id
                j.region_id = cmd.region_id
                j.cmd_type = cmd.cmd_type.value
                j.status = cmd.status
                j.store_id = cmd.store_id
                j.retries = cmd.retries
        return resp


class ClusterStatService:
    """Cluster-level stats (reference ClusterStatService)."""

    def __init__(self, control: CoordinatorControl):
        self.control = control

    def GetClusterStat(self, req: pb.GetClusterStatRequest):
        from dingo_tpu.coordinator.control import StoreState

        resp = pb.GetClusterStatResponse()
        with self.control._lock:
            stores = list(self.control.stores.values())
            resp.store_count = len(stores)
            resp.alive_store_count = sum(
                1 for s in stores if s.state is StoreState.NORMAL
            )
            resp.region_count = len(self.control.regions)
            resp.pending_job_count = sum(
                1 for cmds in self.control.store_ops.values()
                for c in cmds if c.status != "done"
            )
            for s in stores:
                st = resp.stores.add()
                st.store_id = s.store_id
                st.state = s.state.value
                st.region_count = len(s.region_ids)
                st.leader_count = len(s.leader_region_ids)
                st.last_heartbeat_ms = s.last_heartbeat_ms
                summary = self.control.store_metrics_summary(s.store_id)
                st.key_count = summary["key_count"]
                st.vector_count = summary["vector_count"]
                st.memory_bytes = summary["memory_bytes"]
                st.device_memory_bytes = summary["device_memory_bytes"]
                st.metrics_stale = summary["stale"]
                st.leader_qps = summary["leader_qps"]
            rollup = self.control.cluster_metrics_rollup()
        resp.total_key_count = rollup["key_count"]
        resp.total_vector_count = rollup["vector_count"]
        resp.total_memory_bytes = rollup["memory_bytes"]
        resp.total_device_memory_bytes = rollup["device_memory_bytes"]
        return resp

    def GetStoreMetrics(self, req: pb.GetStoreMetricsRequest):
        """Freshest per-store metrics snapshots with staleness flags (the
        query face of the heartbeat metrics plane; `cluster top` renders
        this)."""
        resp = pb.GetStoreMetricsResponse()
        for sid, snap, at_ms, stale in self.control.get_store_metrics(
            req.store_id
        ):
            entry = resp.stores.add()
            entry.store_id = sid
            entry.last_update_ms = at_ms
            entry.stale = stale
            convert.store_metrics_to_pb(snap, entry.metrics)
        resp.diverged_region_ids.extend(self.control.diverged_regions())
        return resp

    def GetRegionMetrics(self, req: pb.GetRegionMetricsRequest):
        """Per-replica rows for one region (or all, region_id=0) across
        stores — leader/follower lag and per-replica HBM side by side."""
        resp = pb.GetRegionMetricsResponse()
        for sid, stale, rm in self.control.get_region_metrics(req.region_id):
            entry = resp.regions.add()
            entry.store_id = sid
            entry.stale = stale
            convert.region_metrics_to_pb(rm, entry.metrics)
        resp.diverged_region_ids.extend(self.control.diverged_regions())
        return resp

    def EventDump(self, req: pb.EventDumpRequest) -> pb.EventDumpResponse:
        """The merged cross-node control-plane timeline (heartbeat-
        harvested store events + the coordinator's own planner/capacity
        decisions), causally ordered — `cluster events` / `cluster
        explain` render this."""
        resp = pb.EventDumpResponse()
        for ev in self.control.cluster_events(
            region_id=int(req.region_id),
            actor=req.actor,
            limit=int(req.limit) or 0,
        ):
            convert.control_event_to_pb(ev, resp.events.add())
        from dingo_tpu.obs.events import EVENTS

        resp.dropped = EVENTS.dropped
        return resp


class RegionControlService:
    """Store-side forced region operations (reference RegionControlService):
    snapshot / index rebuild / detailed state dump, plus the BR transport
    (chunked region export/import — reference src/br/ backup RPCs)."""

    _EXPORT_CHUNK = 1 << 20
    _TRANSFER_TTL_S = 300.0   # abandoned transfer sessions die after this
    #: once the final chunk was served, the (multi-MB) export blob is only
    #: kept long enough for a lost-response re-pull — not the full TTL
    _EOF_GRACE_S = 20.0

    def __init__(self, node: StoreNode):
        self.node = node
        # Transfer sessions, guarded by one lock (the grpc pool is
        # 16-threaded; two br runs against the same region must not
        # corrupt each other's stream):
        #   exports: export_id -> (blob, last_access)   server-assigned id
        #   imports: (region_id, import_id) -> (bytearray, last_access)
        self._transfer_lock = threading.Lock()
        self._exports: Dict[int, list] = {}
        self._imports: Dict[tuple, list] = {}
        self._next_export_id = 1

    def _gc_transfers_locked(self) -> None:
        now = time.monotonic()
        for d in (self._exports, self._imports):
            dead = []
            for k, v in d.items():
                eof_served = len(v) > 2 and v[2]
                ttl = self._EOF_GRACE_S if eof_served else self._TRANSFER_TTL_S
                if now - v[1] > ttl:
                    dead.append(k)
            for k in dead:   # crashed/finished client: drop the buffer
                del d[k]

    def RegionExport(self, req: pb.RegionExportRequest):
        from dingo_tpu.engine.raft_engine import region_snapshot

        resp = pb.RegionExportResponse()
        region = self.node.get_region(req.region_id)
        if region is None:
            return _err(resp, 10001, f"region {req.region_id} not found")
        # leader-gated: a follower can lag raft apply, and a backup that
        # silently exports a stale replica is a data-losing backup. 20001
        # routes the client's retry to the leader (reference br backs up
        # through the leader too).
        raft = self.node.engine.get_node(req.region_id)
        if raft is not None and not raft.is_leader():
            hint = getattr(raft, "leader_id", None) or ""
            return _err(resp, 20001, f"not leader: {hint}")
        if req.export_id == 0 and req.offset != 0:
            return _err(resp, 70004, "offset > 0 requires an export_id")
        blob = None
        if req.export_id == 0:
            # build the (multi-MB) snapshot OUTSIDE the transfer lock: a
            # slow export must not block unrelated concurrent transfers
            try:
                blob = wire.encode(region_snapshot(self.node.raw, region))
            except OSError as e:
                return _err(resp, 70003, f"export snapshot failed: {e}")
        with self._transfer_lock:
            self._gc_transfers_locked()
            if req.export_id == 0:
                export_id = self._next_export_id
                self._next_export_id += 1
                # [blob, last_access, eof_served]
                self._exports[export_id] = [blob, time.monotonic(), False]
            else:
                export_id = int(req.export_id)
                ses = self._exports.get(export_id)
                if ses is None:
                    return _err(resp, 70004,
                                f"unknown/expired export {export_id}")
                ses[1] = time.monotonic()
                blob = ses[0]
            limit = (int(req.max_bytes) if req.max_bytes > 0
                     else self._EXPORT_CHUNK)
            if not 0 <= req.offset <= len(blob):
                return _err(resp, 70004, f"bad export offset {req.offset}")
            resp.data = blob[req.offset:req.offset + limit]
            resp.total_bytes = len(blob)
            resp.export_id = export_id
            resp.eof = req.offset + len(resp.data) >= len(blob)
            if resp.eof:
                # keep the session briefly (eof-grace TTL): if this
                # response is lost in transit the client can re-pull the
                # final chunk, without pinning the blob for the full TTL
                resp.checksum = wire.blob_checksum(blob)
                self._exports[export_id][2] = True
        return resp

    def RegionImport(self, req: pb.RegionImportRequest):
        from dingo_tpu.engine.raft_engine import region_install

        resp = pb.RegionImportResponse()
        region = self.node.get_region(req.region_id)
        if region is None:
            return _err(resp, 10001, f"region {req.region_id} not found")
        # raft-hosted region: reject on the FIRST chunk if this store
        # isn't the leader — the client would otherwise upload the whole
        # multi-MB blob to a peer that can only refuse it at commit time
        raft = self.node.engine.get_node(req.region_id)
        if raft is not None and not raft.is_leader():
            hint = getattr(raft, "leader_id", None) or ""
            return _err(resp, 20001, f"not leader: {hint}")
        key = (int(req.region_id), int(req.import_id))
        with self._transfer_lock:
            self._gc_transfers_locked()
            ses = self._imports.setdefault(key, [bytearray(), 0.0])
            buf = ses[0]
            if req.offset != len(buf):
                if req.offset == 0:
                    buf.clear()   # restarted push: drop the stale prefix
                else:
                    self._imports.pop(key, None)
                    return _err(resp, 70005,
                                f"import offset {req.offset} != {len(buf)}")
            buf.extend(req.data)
            ses[1] = time.monotonic()
            if not req.commit:
                return resp
            blob = bytes(self._imports.pop(key)[0])
        if (req.total_bytes != len(blob)
                or wire.blob_checksum(blob) != req.checksum):
            return _err(resp, 70006,
                        "import blob size/checksum mismatch (torn upload)")
        try:
            state = wire.decode(blob)
        except (ValueError, wire.WireError) as e:
            return _err(resp, 70007, f"install failed: {e}")
        if raft is not None:
            # raft-replicated region: the install MUST ride the log — a
            # direct engine write on one replica would fork it from peers
            # applying concurrent raft traffic (the apply handler also
            # rebuilds derived indexes on every replica)
            from dingo_tpu.engine import write_data as wd

            install = wd.RegionInstallData(
                cfs=[(cf, list(pairs)) for cf, pairs in state.items()])
            try:
                self.node.engine.write(region, install, timeout=60.0)
            except NotLeader as e:
                # election raced the upload: 20001 so the client rotates
                # to the new leader instead of aborting the restore
                return _err(resp, 20001, f"not leader: {e}")
            except (TimeoutError, RuntimeError) as e:
                return _err(resp, 70007, f"install propose failed: {e}")
            return resp
        try:
            region_install(self.node.raw, region, state)
        except (ValueError, OSError) as e:
            return _err(resp, 70007, f"install failed: {e}")
        self.node.after_region_install(region)
        return resp

    def RegionSnapshot(self, req: pb.RegionSnapshotRequest):
        resp = pb.RegionSnapshotResponse()
        region = self.node.get_region(req.region_id)
        if region is None:
            return _err(resp, 10001, f"region {req.region_id} not found")
        if region.vector_index_wrapper is None:
            return _err(resp, 70001, "region has no vector index")
        try:
            resp.path = self.node.index_manager.save_index(region)
        except (AssertionError, OSError) as e:
            return _err(resp, 70002, f"snapshot failed: {e}")
        return resp

    def RegionRebuildIndex(self, req: pb.RegionRebuildIndexRequest):
        resp = pb.RegionRebuildIndexResponse()
        region = self.node.get_region(req.region_id)
        if region is None:
            return _err(resp, 10001, f"region {req.region_id} not found")
        if region.vector_index_wrapper is not None:
            _rebuild_region(self.node, region)
        elif region.document_index is not None:
            self.node.rebuild_document_index(region)
        else:
            return _err(resp, 70001, "region has no index")
        return resp

    def RegionDetail(self, req: pb.RegionDetailRequest):
        resp = pb.RegionDetailResponse()
        region = self.node.get_region(req.region_id)
        if region is None:
            return _err(resp, 10001, f"region {req.region_id} not found")
        resp.definition.CopyFrom(convert.region_def_to_pb(region.definition))
        resp.state = region.state.value
        raft = self.node.engine.get_node(region.id)
        if raft is not None:
            resp.is_leader = raft.is_leader()
            resp.raft_term = raft.current_term
            resp.raft_commit_index = raft.commit_index
            resp.raft_last_applied = raft.last_applied
        wrapper = region.vector_index_wrapper
        if wrapper is not None and wrapper.own_index is not None:
            resp.index_count = wrapper.own_index.get_count()
            resp.index_apply_log_id = wrapper.apply_log_id
        resp.change_log.extend(
            f"{ts:.3f} {msg}" for ts, msg in region.change_log[-20:]
        )
        return resp
