"""DingoClient: cluster-aware SDK over the grpc services.

Plays the role of the reference's Java SDK (java/dingo-sdk — "C++ provides
distributed storage and computing, Java layer provides basic API interfaces",
README.md:41): keeps a region map from the coordinator, routes requests to
region leaders, retries on NotLeader errors, and scatter-gathers multi-region
vector searches client-side (the server returns per-region results only —
SURVEY.md §5).

Routing. Searches (``vector_search``, ``table_vector_search``) route from
the region map as it was last fetched and stamp every request with the
epoch of the definition it was routed from; the store refuses a stale one
(10002) and a region that is gone answers 10001 from every peer, and
either makes the SDK fetch the map and route the whole call again. So a
split, merge, move or drop made by anyone is seen by the next search. What
the cache cannot see: a region another client ADDS to a partition without
changing any region this client knows (a hand-made ``create_index_region``
over a new id range) is found at the next refresh, not at the next search
— the upstream's SDK is the same — and a dropped table is served until its
stores have deleted its regions (a heartbeat), unless the meta watch runs.
Writes, counts, builds, the ``txn_*`` and ``kv_*`` calls fetch the map on
every call. ``client.region_map_refreshes{cause}`` counts the fetches.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import grpc
import numpy as np

from dingo_tpu.client import retry as retry_mod
from dingo_tpu.common.config import grpc_options
from dingo_tpu.common.coord_channel import RotatingCoordinatorChannel
from dingo_tpu.common.metrics import METRICS
from dingo_tpu.index import codec as vcodec
from dingo_tpu.server import pb
from dingo_tpu.server.convert import region_def_from_pb, scalar_from_pb
from dingo_tpu.server.rpc import ServiceStub
from dingo_tpu.raft import wire


class ClientError(RuntimeError):
    pass


class _HedgeMiss(ClientError):
    """Internal: the hedged fast path didn't settle the call (stale
    leader hint, follower rejected) — fall back to the rotation loop."""


class _StaleRoute(ClientError):
    """Internal: the cluster refused a request routed from the cached
    region map (epoch mismatch, or every peer lost the region) — fetch the
    map and route the whole call again. `cause` labels the refresh."""

    def __init__(self, cause: str, msg: str):
        super().__init__(msg)
        self.cause = cause


class _CoordServiceFacade:
    """Duck-types ServiceStub for one coordinator-side service over the
    failover-aware group channel (common/coord_channel.py)."""

    def __init__(self, chan: "RotatingCoordinatorChannel", service: str):
        self._chan = chan
        self._service = service

    def __getattr__(self, method: str):
        return lambda req: self._chan.call(self._service, method, req)


class DingoClient:
    def __init__(self, coordinator_addr: str,
                 store_addrs: Dict[str, str]):
        """store_addrs: store_id -> grpc address. `coordinator_addr` may
        be a comma-separated list of the replicated coordinator group's
        endpoints; the client rotates on NotLeader/connect failure."""
        self._coordinator_addr = coordinator_addr
        self._coord_channel = RotatingCoordinatorChannel(
            coordinator_addr, ClientError)
        self.coordinator = _CoordServiceFacade(
            self._coord_channel, "CoordinatorService")
        self.version = _CoordServiceFacade(
            self._coord_channel, "VersionService")
        self.meta = _CoordServiceFacade(self._coord_channel, "MetaService")
        self._store_addrs = dict(store_addrs)
        self._retry = retry_mod.RetryPolicy.from_flags(rounds=4)
        self._channels: Dict[str, grpc.Channel] = {}
        self._stubs: Dict[Tuple[str, str], ServiceStub] = {}
        self._regions: List = []           # RegionDefinition list
        self._refresh_cause = threading.local()
        self._leader_hint: Dict[int, str] = {}
        self._table_cache: Dict[str, object] = {}
        self._cache_gen = 0   # bumped by every watcher invalidation
        self._meta_watch_thread = None
        self._meta_watch_stop = None

    def coordinator_service(self, service: str) -> "_CoordServiceFacade":
        """Failover-aware stub for any coordinator-side service (used by
        the CLI for JobService / ClusterStatService)."""
        return _CoordServiceFacade(self._coord_channel, service)

    # ---------------- plumbing ----------------
    def _stub(self, store_id: str, service: str) -> ServiceStub:
        chan = self._channels.get(store_id)
        if chan is None:
            chan = grpc.insecure_channel(
                self._store_addrs[store_id], options=grpc_options())
            self._channels[store_id] = chan
        stub = self._stubs.get((store_id, service))
        if stub is None:
            # one multicallable per method of the service: built once
            stub = self._stubs[(store_id, service)] = ServiceStub(
                chan, service)
        return stub

    def refresh_region_map(self) -> None:
        """Fetch the region map from the coordinator. Every fetch, the
        SDK's own too (`_refresh`), goes through this name."""
        cause = getattr(self._refresh_cause, "value", None) or "explicit"
        self._refresh_cause.value = None
        METRICS.counter("client.region_map_refreshes",
                        labels={"cause": cause}).add(1)
        resp = self.coordinator.GetRegionMap(pb.GetRegionMapRequest())
        self._regions = [region_def_from_pb(d) for d in resp.regions]

    def _refresh(self, cause: str) -> None:
        """The SDK's own fetch, counted under `cause` (one of empty,
        stale_epoch, region_not_found, region_op)."""
        self._refresh_cause.value = cause
        self.refresh_region_map()

    def _regions_for_vector_ids(self, partition_id: int, refresh: bool = True):
        """The partition's index regions: from a fresh map, or with
        refresh=False from the cached one, fetched only when it holds
        nothing for the partition."""
        if refresh:
            self.refresh_region_map()
        found = self._index_regions(partition_id)
        if not found and not refresh:
            self._refresh("empty")
            found = self._index_regions(partition_id)
        return found

    def _index_regions(self, partition_id: int):
        return [
            d for d in self._regions
            if d.partition_id == partition_id and d.index_parameter is not None
        ]

    def _region_for_id(self, partition_id: int, vector_id: int,
                       regions=None):
        key = vcodec.encode_vector_key(partition_id, vector_id)
        for d in (regions if regions is not None
                  else self._regions_for_vector_ids(partition_id)):
            if d.start_key <= key < d.end_key:
                return d
        raise ClientError(f"no region covers vector id {vector_id}")

    def _leader_order(self, definition) -> List[str]:
        order = [self._leader_hint.get(definition.region_id)] if \
            self._leader_hint.get(definition.region_id) else []
        order += [p for p in definition.peers if p not in order]
        return order

    def _call_leader(self, definition, service: str, method: str, req,
                     retries: int = 4, hedge: bool = False,
                     cached_route: bool = False):
        """Leader routing with NotLeader retry (SDK behavior), through the
        shared RetryPolicy: grpc never-served failures rotate with
        equal-jitter backoff + per-store circuit breaker, in-band NotLeader
        (20001, updating the leader hint from the errmsg) and region-busy
        (10001) rotate, any other application error fails fast — the node
        that actually served the request answered (lock conflict,
        validation, ...) and rotating peers can't change the answer.

        ``hedge=True`` (idempotent reads only) additionally races a
        second attempt at the next peer after a p99-derived delay when
        retry.hedge_enabled — falling back to the plain rotation loop if
        the hedged pair can't settle it (stale hint, follower rejects).

        ``cached_route=True``: `definition` comes from the cached region
        map, so the request carries its epoch for the store to check, and
        a refusal (10002), or 10001 from every peer, raises `_StaleRoute`
        at once instead of rotating; a call that reaches no leader at all
        drops the cached map, so the next call routes from a fresh one."""
        order = self._leader_order(definition)
        last_store = {}
        not_found = set()
        if cached_route:
            req.context.region_epoch.version = definition.epoch.version
            req.context.region_epoch.conf_version = \
                definition.epoch.conf_version

        def _attempt(store_id, attempt):
            last_store["id"] = store_id
            stub = self._stub(store_id, service)
            return getattr(stub, method)(
                req, metadata=retry_mod.attempt_metadata(attempt))

        def _classify(resp):
            code = resp.error.errcode
            if code == 0:
                self._leader_hint[definition.region_id] = \
                    last_store.get("id")
                return retry_mod.OK
            if code == 20001 and ":" in resp.error.errmsg:
                hint = resp.error.errmsg.split(":")[-1].strip()
                if "/" in hint:
                    self._leader_hint[definition.region_id] = \
                        hint.split("/")[0]
            if cached_route:
                if code == 10001:
                    not_found.add(last_store.get("id"))
                if code == 10002 or len(not_found) == len(order):
                    raise _StaleRoute(
                        "stale_epoch" if code == 10002
                        else "region_not_found", resp.error.errmsg)
            if code in (20001, 10001):
                return (retry_mod.ROTATE, resp.error.errmsg)
            last_store["fatal"] = True
            return (retry_mod.FATAL, resp.error.errmsg)

        if hedge and len(order) >= 2 and self._hedge_enabled():
            try:
                return self._retry.call_hedged(
                    order, _attempt, classify=_classify, op=method,
                    error_cls=_HedgeMiss)
            except _HedgeMiss:
                pass   # stale hint / slow pair: the rotation loop decides
        # NotLeader rotation waits on raft elections (O(100ms)), not on
        # transport blips — scale the round gap to the election, matching
        # the reference SDK's fixed 100ms inter-round sleep
        try:
            return self._retry.call(
                order, _attempt, classify=_classify, op=method,
                error_cls=ClientError, idempotent=True, rounds=retries,
                base_backoff_ms=100.0)
        except ClientError as e:
            if cached_route and not isinstance(e, _StaleRoute) \
                    and not last_store.get("fatal"):
                self._regions = []   # the route reached no leader
            raise

    @staticmethod
    def _hedge_enabled() -> bool:
        from dingo_tpu.common.config import FLAGS

        return bool(FLAGS.get("retry_hedge_enabled"))

    # ---------------- admin ----------------
    def create_index_region(self, partition_id: int, id_lo: int, id_hi: int,
                            index_parameter: pb.VectorIndexParameter,
                            replication: int = 0):
        req = pb.CreateRegionRequest()
        req.range.start_key = vcodec.encode_vector_key(partition_id, id_lo)
        req.range.end_key = vcodec.encode_vector_key(partition_id, id_hi)
        req.partition_id = partition_id
        req.region_type = 1
        req.index_parameter.CopyFrom(index_parameter)
        req.replication = replication
        resp = self.coordinator.CreateRegion(req)
        if resp.error.errcode:
            raise ClientError(resp.error.errmsg)
        self._refresh("region_op")
        return region_def_from_pb(resp.definition)

    def split_region(self, region_id: int, split_vector_id: int,
                     partition_id: int = 0) -> int:
        req = pb.SplitRegionRequest()
        req.region_id = region_id
        req.split_key = vcodec.encode_vector_key(partition_id, split_vector_id)
        resp = self.coordinator.SplitRegion(req)
        if resp.error.errcode:
            raise ClientError(resp.error.errmsg)
        self._refresh("region_op")
        return resp.child_region_id

    def create_document_region(self, partition_id: int, id_lo: int,
                               id_hi: int,
                               schema: Optional[Dict[str, str]] = None,
                               replication: int = 0):
        """DOCUMENT region with an optional typed column schema
        (name -> text/i64/f64/bytes/bool — validated on add, backs
        range/eq predicates in query syntax)."""
        req = pb.CreateRegionRequest()
        req.range.start_key = vcodec.encode_vector_key(partition_id, id_lo)
        req.range.end_key = vcodec.encode_vector_key(partition_id, id_hi)
        req.partition_id = partition_id
        req.region_type = 2
        req.replication = replication
        for name, ftype in (schema or {}).items():
            col = req.document_schema.add()
            col.name = name
            col.sql_type = ftype
        resp = self.coordinator.CreateRegion(req)
        if resp.error.errcode:
            raise ClientError(resp.error.errmsg)
        self._refresh("region_op")
        return region_def_from_pb(resp.definition)

    def merge_region(self, target_region_id: int,
                     source_region_id: int) -> None:
        """Operator region op: target absorbs the adjacent source."""
        resp = self.coordinator.MergeRegion(pb.MergeRegionRequest(
            target_region_id=target_region_id,
            source_region_id=source_region_id,
        ))
        if resp.error.errcode:
            raise ClientError(resp.error.errmsg)
        self._refresh("region_op")

    def change_peer_region(self, region_id: int,
                           new_peers: Sequence[str]) -> None:
        """Operator region op: replace the region's peer set."""
        req = pb.ChangePeerRegionRequest(region_id=region_id)
        req.new_peers.extend(new_peers)
        resp = self.coordinator.ChangePeerRegion(req)
        if resp.error.errcode:
            raise ClientError(resp.error.errmsg)
        self._refresh("region_op")

    def transfer_leader_region(self, region_id: int,
                               target_store: str) -> None:
        """Operator region op: hand region leadership to target_store."""
        resp = self.coordinator.TransferLeaderRegion(
            pb.TransferLeaderRegionRequest(
                region_id=region_id, target_store=target_store,
            ))
        if resp.error.errcode:
            raise ClientError(resp.error.errmsg)

    def vector_import(self, partition_id: int,
                      ids: Optional[Sequence[int]] = None,
                      vectors: Optional[np.ndarray] = None,
                      scalars: Optional[List[Dict[str, Any]]] = None,
                      delete_ids: Optional[Sequence[int]] = None,
                      ttl_ms: int = 0) -> dict:
        """Bulk import (VectorImport RPC): upserts and/or deletes routed
        per owning region. Returns {"added": n, "deleted": n}."""
        if ids is not None and vectors is None:
            raise ClientError("vector_import: ids given without vectors")
        regions = self._regions_for_vector_ids(partition_id)
        added = deleted = 0
        groups: Dict[int, dict] = {}
        for i, vid in enumerate(ids if ids is not None else []):
            d = self._region_for_id(partition_id, int(vid), regions)
            groups.setdefault(d.region_id, {"add": [], "del": []})[
                "add"].append(i)
        for vid in (delete_ids if delete_ids is not None else []):
            d = self._region_for_id(partition_id, int(vid), regions)
            groups.setdefault(d.region_id, {"add": [], "del": []})[
                "del"].append(int(vid))
        by_region = {d.region_id: d for d in self._regions}
        for rid, g in groups.items():
            req = pb.VectorImportRequest()
            req.context.region_id = rid
            for i in g["add"]:
                v = req.vectors.add()
                v.vector.id = int(ids[i])
                v.vector.values.extend(
                    np.asarray(vectors[i], np.float32).tolist())
                if scalars is not None:
                    for k, val in scalars[i].items():
                        e = v.scalar_data.add()
                        e.key = k
                        e.value = wire.encode_obj(val)
            req.delete_ids.extend(g["del"])
            req.ttl_ms = ttl_ms
            resp = self._call_leader(
                by_region[rid], "IndexService", "VectorImport", req)
            added += resp.added
            deleted += resp.deleted
        return {"added": added, "deleted": deleted}

    # ---------------- table meta API (reference Java SDK table ops) -------
    def create_schema(self, name: str) -> None:
        resp = self.meta.CreateSchema(pb.CreateSchemaRequest(schema_name=name))
        if resp.error.errcode:
            raise ClientError(resp.error.errmsg)

    def get_schemas(self) -> List[str]:
        return list(self.meta.GetSchemas(pb.GetSchemasRequest()).schema_names)

    def create_vector_table(
        self, schema: str, name: str,
        index_parameter: "pb.VectorIndexParameter",
        partitions: Sequence[Tuple[int, int, int]] = ((0, 0, 1 << 40),),
        replication: int = 0,
    ):
        """Create an index table: partitions = [(partition_id, id_lo, id_hi)].
        Returns the TableDef pb (with region ids filled in)."""
        req = pb.CreateTableRequest()
        d = req.definition
        d.schema_name, d.name = schema, name
        d.table_type = 1
        d.replication = replication
        d.index_parameter.CopyFrom(index_parameter)
        for pid, lo, hi in partitions:
            p = d.partitions.add()
            p.partition_id, p.id_lo, p.id_hi = pid, lo, hi
        resp = self.meta.CreateTable(req)
        if resp.error.errcode:
            raise ClientError(resp.error.errmsg)
        self._refresh("region_op")
        return resp.definition

    def get_table(self, schema: str, name: str, cached: bool = False):
        """cached=True serves from the SDK table cache (filled on miss).
        Start the meta watcher (start_meta_watch) to have the cache
        invalidate on coordinator-pushed change events instead of
        serving stale definitions forever."""
        if cached:
            key = f"{schema}.{name}"
            hit = self._table_cache.get(key)
            if hit is not None:
                return hit
        gen = self._cache_gen
        resp = self.meta.GetTable(pb.GetTableRequest(
            schema_name=schema, table_name=name))
        t = resp.definition if resp.found else None
        # only cache if no invalidation raced the RPC: a drop event
        # processed mid-flight must not be overwritten by the stale reply
        if cached and t is not None and gen == self._cache_gen:
            self._table_cache[f"{schema}.{name}"] = t
        return t

    def start_meta_watch(self, poll_timeout_ms: int = 2000) -> None:
        """Background long-poll on MetaWatch: each schema/table change
        event invalidates the SDK table cache (and the region map on
        table create/drop) — the reference SDK's meta-watch cache story
        without client polling of table definitions."""
        if self._meta_watch_thread is not None:
            return
        self._meta_watch_stop = threading.Event()

        def loop():
            start = 0   # 0 = from now (server fills current+1)
            registered = False
            while not self._meta_watch_stop.is_set():
                try:
                    resp = self.meta.MetaWatch(pb.MetaWatchRequest(
                        start_revision=start,
                        timeout_ms=poll_timeout_ms,
                    ))
                except Exception:
                    self._meta_watch_stop.wait(0.5)
                    continue
                if resp.error.errcode:
                    # e.g. watcher slots exhausted — back off, don't hammer
                    self._meta_watch_stop.wait(0.5)
                    continue
                # ALWAYS pin the window: a timed-out poll reports where it
                # watched up to, so events landing between polls replay on
                # the next call instead of being skipped by "from now"
                start = resp.revision + 1
                if not registered:
                    # entries cached between start_meta_watch() and this
                    # first pinned window may predate events the watch
                    # never saw (the first poll starts "from now") —
                    # drop them so nothing stale survives the gap. The
                    # region map is as stale as the cache (a missed
                    # create/drop moved regions), so refresh it too,
                    # exactly like the resync branch.
                    registered = True
                    self._cache_gen += 1
                    self._table_cache.clear()
                    try:
                        self.refresh_region_map()
                    except Exception:
                        pass
                if not resp.fired:
                    continue
                self._cache_gen += 1
                if resp.event == "resync":
                    self._table_cache.clear()
                    # the lost events may include table create/drop
                    try:
                        self.refresh_region_map()
                    except Exception:
                        pass
                    continue
                key = f"{resp.schema_name}.{resp.table_name}"
                self._table_cache.pop(key, None)
                if resp.event in ("create_table", "drop_table"):
                    try:
                        self.refresh_region_map()
                    except Exception:
                        pass

        self._meta_watch_thread = threading.Thread(
            target=loop, daemon=True, name="meta-watch"
        )
        self._meta_watch_thread.start()

    def stop_meta_watch(self) -> None:
        if self._meta_watch_thread is None:
            return
        self._meta_watch_stop.set()
        self._meta_watch_thread.join(timeout=5)
        self._meta_watch_thread = None

    def list_tables(self, schema: str):
        return list(self.meta.GetTables(
            pb.GetTablesRequest(schema_name=schema)).definitions)

    def drop_table(self, schema: str, name: str) -> None:
        resp = self.meta.DropTable(pb.DropTableRequest(
            schema_name=schema, table_name=name))
        if resp.error.errcode:
            raise ClientError(resp.error.errmsg)
        self._refresh("region_op")

    def table_vector_add(self, table, ids, vectors, scalars=None) -> None:
        """Route rows to the owning partition by id window; ids outside
        every partition's window are an error, not a silent drop."""
        import numpy as _np

        ids = _np.asarray(ids, _np.int64)
        routing = []
        routed = _np.zeros(len(ids), bool)
        for p in table.partitions:
            sel = [i for i, vid in enumerate(ids)
                   if p.id_lo <= vid < p.id_hi]
            if sel:
                routed[sel] = True
                routing.append((p, sel))
        # validate the whole batch BEFORE the first write so a routing
        # error cannot leave a partial batch behind
        if not routed.all():
            orphans = ids[~routed][:5].tolist()
            raise ClientError(
                f"ids outside every partition window: {orphans}"
            )
        for p, sel in routing:
            self.vector_add(
                p.partition_id, ids[sel].tolist(),
                _np.asarray(vectors)[sel],
                [scalars[i] for i in sel] if scalars is not None else None,
            )

    def table_vector_search(self, table, queries, topk: int = 10, **params):
        """Scatter over every partition, merge top-k client-side
        (metric-aware: IP/COSINE similarity descends)."""
        asc = table.index_parameter.metric_type in (
            pb.METRIC_TYPE_L2, pb.METRIC_TYPE_HAMMING
        )
        per_part = [
            self.vector_search(p.partition_id, queries, topk, **params)
            for p in table.partitions
        ]
        out = []
        for qi in range(len(per_part[0])):
            allhits = [h for part in per_part for h in part[qi]]
            allhits.sort(key=lambda t: t[1], reverse=not asc)
            out.append(allhits[:topk])
        return out

    def tso(self, count: int = 1) -> int:
        resp = self.coordinator.Tso(pb.TsoRequest(count=count))
        return resp.first_ts

    # ---------------- vectors ----------------
    def vector_add(self, partition_id: int, ids: Sequence[int],
                   vectors: np.ndarray,
                   scalars: Optional[List[Dict[str, Any]]] = None,
                   table_values: Optional[Sequence[bytes]] = None) -> None:
        """Batch add routed per owning region. `table_values[i]` is an
        optional serial-encoded table row per vector (the TABLE
        coprocessor filter's data source)."""
        groups: Dict[int, List[int]] = {}
        regions = self._regions_for_vector_ids(partition_id)  # ONE refresh
        for i, vid in enumerate(ids):
            d = self._region_for_id(partition_id, int(vid), regions)
            groups.setdefault(d.region_id, []).append(i)
        by_region = {d.region_id: d for d in self._regions}
        for rid, idxs in groups.items():
            d = by_region[rid]
            req = pb.VectorAddRequest()
            req.context.region_id = rid
            for i in idxs:
                v = req.vectors.add()
                v.vector.id = int(ids[i])
                v.vector.values.extend(np.asarray(vectors[i], np.float32).tolist())
                if scalars is not None:
                    for k, val in scalars[i].items():
                        e = v.scalar_data.add()
                        e.key = k
                        e.value = wire.encode_obj(val)
                if table_values is not None and table_values[i] is not None:
                    # explicit b"" clears the row (optional-field presence)
                    v.table_data = table_values[i]
            self._call_leader(d, "IndexService", "VectorAdd", req)

    def vector_search(
        self, partition_id: int, queries: np.ndarray, topk: int = 10,
        with_scalar_data: bool = False, deadline_ms: float = None,
        tenant: str = "", priority: int = None, **params,
    ) -> List[List[Tuple[int, float]]]:
        """Scatter to every region of the partition, gather + merge top-k
        client-side (the reference SDK's cross-region story).

        ``deadline_ms``/``tenant``/``priority`` attach a QoS budget to the
        calls: the stub injects it as gRPC metadata (remaining-ms form)
        next to the trace context, so a qos.enabled store can admit,
        prioritize, or shed the request against ITS clock."""
        if deadline_ms or tenant or priority is not None:
            from dingo_tpu.obs.pressure import (
                DEFAULT_PRIORITY,
                budget_scope,
            )

            with budget_scope(
                # no deadline given: a full day — effectively "account
                # tenant/priority, never expire"
                deadline_ms if deadline_ms else 86_400_000.0,
                tenant=tenant or "default",
                priority=DEFAULT_PRIORITY if priority is None else priority,
            ):
                return self._vector_search_budgeted(
                    partition_id, queries, topk, with_scalar_data, params
                )
        return self._vector_search_budgeted(
            partition_id, queries, topk, with_scalar_data, params
        )

    def _vector_search_budgeted(self, partition_id, queries, topk,
                                with_scalar_data, params):
        """Route from the cached map; a stale route (`_StaleRoute`) drops
        what the round gathered, fetches the map and routes the WHOLE call
        again, so no row of a region that split or merged meanwhile is
        merged twice or missed. Bounded by the retry policy's rounds."""
        queries = np.asarray(queries, np.float32)
        regions = self._regions_for_vector_ids(partition_id, refresh=False)
        rounds = self._retry.rounds
        stale = None
        for round_i in range(rounds):
            if stale is not None:
                if round_i > 1:
                    # the first refresh did not cure it: the coordinator
                    # has not heard of the change yet (a split between the
                    # store's bump and its report) — wait as a leader
                    # rotation would
                    self._retry.backoff(round_i - 1, "VectorSearch",
                                        ClientError, round_i, base_ms=100.0)
                self._refresh(stale.cause)
                regions = self._index_regions(partition_id)
            if not regions:
                raise ClientError("no index regions")
            try:
                return self._search_regions(regions, queries, topk,
                                            with_scalar_data, params)
            except _StaleRoute as e:
                stale = e
        raise ClientError(
            f"VectorSearch: route still stale after {rounds} rounds: {stale}")

    def _search_regions(self, regions, queries, topk, with_scalar_data,
                        params):
        merged: List[List[Tuple[int, float]]] = [[] for _ in queries]
        # wire convention: L2/HAMMING distances ascend, IP/COSINE similarity
        # descends (ops/distance.py metric_ascending) — merge accordingly
        from dingo_tpu.ops.distance import Metric, metric_ascending

        metric = (regions[0].index_parameter.metric
                  if regions[0].index_parameter else Metric.L2)
        ascending = metric_ascending(metric)
        for d in regions:
            req = pb.VectorSearchRequest()
            req.context.region_id = d.region_id
            for q in queries:
                v = req.vectors.add()
                v.values.extend(q.tolist())
            req.parameter.top_n = topk
            req.parameter.with_scalar_data = with_scalar_data
            if "nprobe" in params:
                req.parameter.nprobe = params["nprobe"]
            if "ef_search" in params:
                req.parameter.ef_search = params["ef_search"]
            if "filter" in params:
                req.parameter.filter = params["filter"]
            if "filter_type" in params:
                req.parameter.filter_type = params["filter_type"]
            if "coprocessor" in params:   # pb.Coprocessor (TABLE filter)
                req.parameter.coprocessor.CopyFrom(params["coprocessor"])
            resp = self._call_leader(d, "IndexService", "VectorSearch", req,
                                     hedge=True, cached_route=True)
            for qi, row in enumerate(resp.batch_results):
                for item in row.results:
                    merged[qi].append((item.vector.id, item.distance))
        out = []
        for row in merged:
            row.sort(key=lambda t: t[1], reverse=not ascending)
            out.append(row[:topk])
        return out

    def vector_count(self, partition_id: int) -> int:
        total = 0
        for d in self._regions_for_vector_ids(partition_id):
            req = pb.VectorCountRequest()
            req.context.region_id = d.region_id
            resp = self._call_leader(d, "IndexService", "VectorCount", req)
            total += resp.count
        return total

    def vector_build(self, partition_id: int) -> None:
        """VectorBuild on every region of the partition: full rebuild from
        the engine (+ train for IVF types); returns when each is done."""
        for d in self._regions_for_vector_ids(partition_id):
            req = pb.VectorBuildRequest()
            req.context.region_id = d.region_id
            self._call_leader(d, "IndexService", "VectorBuild", req)

    def vector_status(self, partition_id: int) -> List[Dict[str, Any]]:
        """VectorStatus per region of the partition."""
        out = []
        for d in self._regions_for_vector_ids(partition_id):
            req = pb.VectorStatusRequest()
            req.context.region_id = d.region_id
            resp = self._call_leader(d, "IndexService", "VectorStatus", req)
            out.append({
                "region_id": d.region_id,
                "ready": resp.ready,
                "trained": resp.trained,
                "build_error": resp.build_error,
                "count": resp.count,
            })
        return out

    # ---------------- kv ----------------
    def _region_for_key(self, key: bytes):
        self.refresh_region_map()
        for d in self._regions:
            if d.start_key <= key < d.end_key:
                return d
        raise ClientError(f"no region covers key {key!r}")

    def _group_keys_by_region(self, keys):
        """[(region_definition, [keys])] — one group per hosting region."""
        groups = {}
        for key in keys:
            d = self._region_for_key(key)
            groups.setdefault(d.region_id, (d, []))[1].append(key)
        return list(groups.values())

    # ---------------- transactions (reference Java SDK txn API) ----------
    def begin_txn(self, pessimistic: bool = False,
                  lock_ttl_ms: int = 3000):
        """Start a Percolator transaction (client/txn.py drives the 2PC)."""
        from dingo_tpu.client.txn import Transaction

        return Transaction(self, self.tso(1), pessimistic=pessimistic,
                           lock_ttl_ms=lock_ttl_ms)

    def txn_scan_lock(self, start_key: bytes = b"", end_key: bytes = b"",
                      max_ts: int = 0, limit: int = 0):
        """Leftover locks across every region intersecting the range."""
        self.refresh_region_map()
        out = []
        for d in self._regions:
            req = pb.TxnScanLockRequest()
            req.context.region_id = d.region_id
            req.range.start_key = start_key
            req.range.end_key = end_key
            req.max_ts = max_ts
            req.limit = limit
            resp = self._call_leader(d, "StoreService", "TxnScanLock", req)
            out.extend(resp.locks)
            if limit and len(out) >= limit:
                return out[:limit]
        return out

    def txn_check_status(self, primary: bytes, lock_ts: int) -> dict:
        d = self._region_for_key(primary)
        req = pb.TxnCheckStatusRequest()
        req.context.region_id = d.region_id
        req.primary_key = primary
        req.lock_ts = lock_ts
        req.caller_start_ts = self.tso(1)
        resp = self._call_leader(d, "StoreService", "TxnCheckStatus", req)
        return {"action": resp.action, "commit_ts": resp.commit_ts}

    def txn_resolve_lock(self, start_ts: int, commit_ts: int = 0,
                         keys: Optional[Sequence[bytes]] = None) -> int:
        """Commit (commit_ts > 0) or roll back leftover locks of a txn on
        every region (or just the regions hosting `keys`)."""
        resolved = 0
        if keys:
            groups = self._group_keys_by_region(keys)
        else:
            self.refresh_region_map()
            groups = [(d, []) for d in self._regions]
        for d, group in groups:
            req = pb.TxnResolveLockRequest()
            req.context.region_id = d.region_id
            req.start_ts = start_ts
            req.commit_ts = commit_ts
            req.keys.extend(group)
            resp = self._call_leader(d, "StoreService", "TxnResolveLock", req)
            resolved += resp.resolved
        return resolved

    def txn_resolve_leftovers(self, lock) -> int:
        """Crash recovery around one leftover lock (pb.TxnLockInfo): ask
        the primary's region for the txn's fate, then resolve accordingly
        on every region. Returns locks resolved."""
        st = self.txn_check_status(lock.primary_lock, lock.lock_ts)
        commit_ts = st["commit_ts"] if st["action"] == "committed" else 0
        if st["action"] == "locked":
            return 0   # still alive — nothing to resolve
        return self.txn_resolve_lock(lock.lock_ts, commit_ts)

    def txn_gc(self, safe_point_ts: int) -> int:
        """MVCC garbage collection below the safe point, all regions."""
        self.refresh_region_map()
        deleted = 0
        for d in self._regions:
            req = pb.TxnGcRequest()
            req.context.region_id = d.region_id
            req.safe_point_ts = safe_point_ts
            resp = self._call_leader(d, "StoreService", "TxnGc", req)
            deleted += resp.deleted
        return deleted

    def txn_dump(self, region_id: int, limit: int = 0):
        """Debug dump of a region's txn CFs (TxnDump)."""
        self.refresh_region_map()
        d = next((r for r in self._regions if r.region_id == region_id),
                 None)
        if d is None:
            raise ClientError(f"region {region_id} not found")
        req = pb.TxnDumpRequest()
        req.context.region_id = region_id
        req.limit = limit
        return self._call_leader(d, "StoreService", "TxnDump", req)

    def kv_put(self, key: bytes, value: bytes) -> None:
        d = self._region_for_key(key)
        req = pb.KvBatchPutRequest()
        req.context.region_id = d.region_id
        kv = req.kvs.add()
        kv.key = key
        kv.value = value
        self._call_leader(d, "StoreService", "KvBatchPut", req)

    def kv_get(self, key: bytes) -> Optional[bytes]:
        d = self._region_for_key(key)
        req = pb.KvGetRequest()
        req.context.region_id = d.region_id
        req.key = key
        resp = self._call_leader(d, "StoreService", "KvGet", req)
        return resp.value if resp.found else None

    def close(self) -> None:
        self.stop_meta_watch()
        self._coord_channel.close()
        self._stubs.clear()
        for chan in self._channels.values():
            chan.close()
