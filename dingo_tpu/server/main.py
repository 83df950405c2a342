"""Role-based server binary (the `dingodb_server --role=...` analog,
reference src/server/main.cc:526-541).

    python -m dingo_tpu.server.main --role coordinator --port 20001 \
        --data-dir /tmp/dingo/coord
    python -m dingo_tpu.server.main --role store --id s0 --port 20011 \
        --coordinator 127.0.0.1:20001 --data-dir /tmp/dingo/s0

Startup order mirrors §3.3: config -> engine -> (coordinator: controls |
store: meta recovery -> index manager -> storage -> controllers) ->
services -> crontab schedule.

Raft traffic between processes rides the grpc raft transport
(raft/grpc_transport.py, wired below for --coor-peers deployments); the
in-process LocalTransport serves single-process multi-role runs.
"""

from __future__ import annotations

import argparse
import signal
import threading
import sys
import time

from dingo_tpu.common.config import (
    FLAGS,
    Config,
    auto_arms,
    enable_compile_cache,
    require_device,
)
from dingo_tpu.common.crontab import CrontabManager
from dingo_tpu.common.metrics import METRICS
from dingo_tpu.common.stream import StreamManager
from dingo_tpu.coordinator.balance import (
    BalanceLeaderScheduler,
    BalanceRegionScheduler,
    ReplicaPlanScheduler,
)
from dingo_tpu.coordinator.control import CoordinatorControl
from dingo_tpu.coordinator.kv_control import KvControl
from dingo_tpu.coordinator.tso import TsoControl
from dingo_tpu.engine.gc import GCSafePointManager
from dingo_tpu.engine.raw_engine import MemEngine, WalEngine
from dingo_tpu.raft import LocalTransport
from dingo_tpu.server.rpc import DingoServer
from dingo_tpu.store.checker import PreMergeChecker, PreSplitChecker
from dingo_tpu.store.node import StoreNode
from dingo_tpu.trace import TRACER

_TRANSPORT = LocalTransport()   # in-process multi-role transport


def _make_engine(args):
    """Raw engine per --engine/--data-dir (an explicit durable engine
    without --data-dir is rejected in main() before reaching here)."""
    engine = getattr(args, "engine", "wal")
    if not args.data_dir:
        return MemEngine()
    if engine == "lsm":
        from dingo_tpu.engine.lsm_engine import LsmRawEngine

        return LsmRawEngine(args.data_dir)
    if engine == "mem":
        return MemEngine()
    return WalEngine(args.data_dir)


def serve_coordinator(args) -> None:
    engine = _make_engine(args)
    raft_coordinator = None
    if args.coor_peers:
        # replicated coordinator: every control mutation rides a raft group
        # (coordinator_control.h:218 SubmitMetaIncrementSync analog)
        import os

        from dingo_tpu.coordinator.raft_meta import RaftMetaCoordinator
        from dingo_tpu.raft.grpc_transport import GrpcRaftTransport
        from dingo_tpu.raft.log import RaftLog

        transport = GrpcRaftTransport(args.id,
                                      cluster_token=args.cluster_token)
        peer_ids = []
        for spec in args.coor_peers.split(","):
            cid, eq, addr = spec.strip().partition("=")
            if not eq or not cid or not addr:
                raise SystemExit(
                    f"--coor-peers: malformed entry {spec!r} "
                    "(want coor_id=host:port)"
                )
            transport.set_peer(cid.strip(), addr.strip())
            peer_ids.append(cid.strip())
        log = RaftLog(os.path.join(args.data_dir, "meta_raft.log")) \
            if args.data_dir else None
        raft_coordinator = RaftMetaCoordinator(
            args.id, peer_ids, transport, engine,
            replication=args.replication, log=log,
        )
        raft_coordinator.start()
        control = raft_coordinator.control
        tso = raft_coordinator.tso
        kv_control = raft_coordinator.kv
        meta = raft_coordinator.meta
        is_leader = raft_coordinator.is_leader
    else:
        control = CoordinatorControl(engine, replication=args.replication)
        tso = TsoControl(engine)
        kv_control = KvControl(engine)
        meta = None
        is_leader = lambda: True  # noqa: E731 — single coordinator

    server = DingoServer(args.port)
    server.host_coordinator_role(
        control, tso, kv_control, meta=meta,
        raft_transport=(raft_coordinator and transport) or None,
    )
    port = server.start()

    def when_leader(fn):
        """Crontab mutations run only on the raft leader — a follower
        proposing would just bounce with NotLeader."""
        return lambda: fn() if is_leader() else None

    crontab = CrontabManager()
    crontab.add("update_store_state", 5.0,
                when_leader(control.update_store_states))
    crontab.add("lease_gc", 10.0, when_leader(kv_control.lease_gc))
    balance_leader = BalanceLeaderScheduler(control)

    def dispatch_balance_leader():
        # balance_mode is hot-changeable — re-read per tick so an operator
        # can flip count <-> load without a restart
        balance_leader.mode = str(FLAGS.get("balance_mode"))
        return balance_leader.dispatch()

    crontab.add(
        "balance_leader", 30.0,
        when_leader(dispatch_balance_leader),
    )
    crontab.add(
        "balance_region", 60.0,
        when_leader(BalanceRegionScheduler(control).dispatch),
    )
    # replica planner reads balance_replica_mode/qps_target from FLAGS on
    # every tick (hot-changeable, no-ops while mode != auto or metrics
    # are stale), so it can always ride the crontab
    crontab.add(
        "replica_plan", 30.0,
        when_leader(ReplicaPlanScheduler(control).dispatch),
    )
    metrics_http = _maybe_metrics_http()
    TRACER.watch_gc()     # full collections as gc.gen2 background spans
    crontab.start()
    print(f"coordinator {args.id} listening on 127.0.0.1:{port}"
          + (" (raft group)" if raft_coordinator else ""), flush=True)
    try:
        _wait(server, crontab)
    finally:
        if metrics_http is not None:
            metrics_http.stop()
        if raft_coordinator is not None:
            raft_coordinator.stop()


def _claim_device() -> None:
    """Start-up of a role that serves from the chip (store/index,
    diskann): refuse any backend but a TPU before anything is built on
    it, turn the persistent compile cache on, and publish what was found
    — `device.count` and the backend-resolved `device.auto_arm`s — so a
    client can tell from MetricsDump what the process really serves
    from."""
    cache_dir = enable_compile_cache()
    dev = require_device()
    METRICS.gauge("device.count", labels={
        "platform": dev["platform"], "kind": dev["kind"],
    }).set(dev["count"])
    for flag, on in auto_arms().items():
        METRICS.gauge("device.auto_arm", labels={"flag": flag}).set(int(on))
    print(f"device: platform: {dev['platform']} kind: {dev['kind']} "
          f"count: {dev['count']} compile cache: {cache_dir}", flush=True)


def serve_store(args) -> None:
    _claim_device()
    engine = _make_engine(args)
    if args.raft_peers:
        # multi-process replication: raft RPCs ride grpc between stores
        from dingo_tpu.raft.grpc_transport import GrpcRaftTransport

        transport = GrpcRaftTransport(args.id,
                                      cluster_token=args.cluster_token)
        for spec in args.raft_peers.split(","):
            sid, eq, addr = spec.strip().partition("=")
            if not eq or not sid or not addr:
                raise SystemExit(
                    f"--raft-peers: malformed entry {spec!r} "
                    "(want store_id=host:port)"
                )
            transport.set_peer(sid.strip(), addr.strip())
    else:
        transport = _TRANSPORT
    # single-process deployments reach the coordinator object directly; a
    # remote coordinator is reached through the grpc heartbeat below
    node = StoreNode(
        args.id, transport, coordinator=None, raw_engine=engine,
        snapshot_root=args.data_dir,
    )
    node.recover()
    gc = GCSafePointManager()
    streams = StreamManager()

    server = DingoServer(args.port)
    server.host_store_role(node)
    port = server.start()
    if args.raft_peers:
        transport.set_peer(args.id, f"127.0.0.1:{port}")

    crontab = CrontabManager()
    hb_interval = FLAGS.get("server_heartbeat_interval_s")
    if args.coordinator:
        from dingo_tpu.server.remote_heartbeat import RemoteHeartbeat

        hb = RemoteHeartbeat(node, args.coordinator)
        crontab.add("heartbeat", float(hb_interval), hb.beat, immediately=True)
    def scan_gc():
        from dingo_tpu.server.services import _SCAN_SESSIONS

        return streams.recycle_idle() + _SCAN_SESSIONS.streams.recycle_idle()

    crontab.add("scan_gc", 30.0, scan_gc)

    def run_gc():
        # advance the safe point (coordinator pull when configured, local
        # now-minus-retention otherwise), then prune MVCC versions below it
        from dingo_tpu.mvcc.ts_provider import compose_ts

        if args.coordinator:
            try:
                resp = hb._stub.GetGCSafePoint(pb_mod.GetGCSafePointRequest())
                gc.update(resp.safe_ts)
            except Exception:
                pass
        else:
            gc.update(compose_ts(
                int(time.time() * 1000) - FLAGS.get("gc_retention_ms"), 0
            ))
        return gc.gc_non_txn(node.raw)

    from dingo_tpu.server import pb as pb_mod

    crontab.add("mvcc_gc", 60.0, run_gc)
    crontab.add("split_check", 60.0,
                lambda: PreSplitChecker(node).run() if node.coordinator else None)
    scrub_worker = {"thread": None}

    def scrub_all():
        # rebuilds/saves can take minutes; run them OFF the shared crontab
        # thread so mvcc_gc/split_check keep ticking, one worker at a time
        t = scrub_worker["thread"]
        if t is not None and t.is_alive():
            return

        def work():
            for r in node.meta.get_all_regions():
                raft = node.engine.get_node(r.id)
                actions = node.index_manager.scrub(
                    r, act=True, raft_log=raft.log if raft else None
                )
                if actions.get("error"):
                    print(
                        f"scrub region {r.id}: {actions['error']}",
                        file=sys.stderr, flush=True,
                    )

        t = threading.Thread(target=work, name="scrub", daemon=True)
        scrub_worker["thread"] = t
        t.start()

    crontab.add("scrub_vector_index", 60.0, scrub_all)
    # IVF view compaction: restores the dense bucket layout once the
    # incrementally-maintained view accumulates tombstone/spill garbage —
    # off the search path (index/manager.py compact_views)
    crontab.add(
        "ivf_compact",
        float(FLAGS.get("ivf_compact_interval_s")),
        lambda: node.index_manager.compact_views(
            node.meta.get_all_regions()
        ),
    )
    # metrics collection rides its own crontab so heartbeats reuse the
    # cached snapshot instead of paying a full region sweep per beat
    crontab.add(
        "store_metrics",
        float(FLAGS.get("metrics_collect_interval_s")),
        node.metrics.collect,
        immediately=True,
    )
    # closed-loop SLO parameter controller (obs/tuner.py): one
    # cheap-to-expensive ladder step per region per tick against the live
    # recall CI from the quality plane. Hot-gated on tuner.enabled per
    # tick (the replica-planner wiring pattern), so it always rides the
    # crontab and no-ops while disabled or while estimates are stale
    from dingo_tpu.obs import QualityTunerRunner

    crontab.add(
        "quality_tuner",
        float(FLAGS.get("tuner_interval_s")),
        QualityTunerRunner(node, crontab=crontab).tick,
    )
    # graduated load shedding (obs/pressure.py): one degrade-ladder level
    # per tick per over-pressure region (drop rerank -> lower nprobe/ef ->
    # advisory sq8), one level back per calm tick. Hot-gated per tick on
    # qos.enabled + a 'degrade' shed policy (the tuner/replica-planner
    # wiring pattern), so it always rides the crontab and no-ops off
    from dingo_tpu.obs import ShedController

    crontab.add(
        "qos_shed",
        float(FLAGS.get("qos_shed_interval_s")),
        ShedController(node, crontab=crontab).tick,
    )
    # state-integrity corruption scrub (obs/integrity.py): recompute full
    # per-artifact digests from device state (chunked under the store
    # device lock) and check them against the incremental write-path
    # ledger. Hot-gated on integrity.enabled per tick; runs on its own
    # worker (the scrub_vector_index pattern) so a long chunked pass
    # never stalls the shared crontab thread
    from dingo_tpu.obs import IntegrityScrubRunner

    crontab.add(
        "consistency_scrub",
        float(FLAGS.get("integrity_scrub_interval_s")),
        IntegrityScrubRunner(node, crontab=crontab).tick,
    )
    # memory-tier ladder (index/tiering.py): one policy pass per tick —
    # demote the coldest region under HBM pressure / coordinator
    # advisory, promote a sustained-hot demoted one. Hot-gated on
    # tier.enabled per tick; transitions are full-region copies, so the
    # tick body runs on its own worker (the consistency_scrub pattern)
    # and never stalls the shared crontab thread
    from dingo_tpu.index.tiering import TierRunner

    crontab.add(
        "memory_tier",
        float(FLAGS.get("tier_interval_s")),
        TierRunner(node, crontab=crontab).tick,
    )
    # device-runtime observability: process HBM watermark poll (per-region
    # owner ledgers refresh with each store_metrics pass) + region/index
    # config snapshots for flight-recorder bundles
    from dingo_tpu.obs import FLIGHT, HBM

    crontab.add(
        "hbm_watermark",
        float(FLAGS.get("hbm_watermark_interval_s")),
        HBM.poll_process,
        immediately=True,
    )

    def _flight_node_config():
        return {
            "store_id": node.store_id,
            "regions": {
                r.id: {
                    "type": r.definition.region_type.name,
                    "index": (
                        r.definition.index_parameter.index_type.name
                        if r.definition.index_parameter else None
                    ),
                    "leader": (
                        node.engine.get_node(r.id).is_leader()
                        if node.engine.get_node(r.id) else False
                    ),
                }
                for r in node.meta.get_all_regions()
            },
        }

    FLIGHT.config_provider = _flight_node_config
    metrics_http = _maybe_metrics_http()
    TRACER.watch_gc()     # full collections as gc.gen2 background spans
    crontab.start()
    print(f"store {args.id} listening on 127.0.0.1:{port}", flush=True)
    try:
        _wait(server, crontab, node)
    finally:
        if metrics_http is not None:
            metrics_http.stop()


def _maybe_metrics_http():
    """Bind the plain-HTTP /metrics sidecar when metrics.http_port is set
    (Prometheus scrapers can't speak the grpc DebugService)."""
    port = int(FLAGS.get("metrics_http_port"))
    if not port:
        return None
    from dingo_tpu.metrics.http import MetricsHttpServer

    srv = MetricsHttpServer(port)
    bound = srv.start()
    print(f"metrics http on 127.0.0.1:{bound}/metrics", flush=True)
    return srv


def _wait(server, crontab, node=None) -> None:
    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    try:
        while not stop:
            time.sleep(0.2)
    finally:
        if crontab is not None:
            crontab.stop()
        server.stop()
        if node is not None:
            node.stop()


def serve_diskann(args) -> None:
    """--role=diskann: the separate build/search server (main.cc:1340)."""
    import tempfile

    from dingo_tpu.diskann.item import DiskAnnItemManager

    _claim_device()
    root = args.data_dir or tempfile.mkdtemp(prefix="dingo-diskann-")
    manager = DiskAnnItemManager(root)
    server = DingoServer(args.port)
    server.host_diskann_role(manager)
    port = server.start()
    print(f"diskann server on 127.0.0.1:{port} data={root}", flush=True)
    try:
        _wait(server, None)
    finally:
        manager.stop()
        server.stop()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="dingo-server")
    p.add_argument("--role",
                   choices=["coordinator", "store", "index", "diskann"],
                   required=True)
    p.add_argument("--id", default="s0")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--coordinator", default="")
    p.add_argument("--data-dir", default="")
    p.add_argument("--engine", choices=["mem", "wal", "lsm"], default=None,
                   help="raw KV engine when --data-dir is set (default wal; "
                        "lsm = native C++ LSM, the RocksRawEngine analog)")
    p.add_argument("--replication", type=int, default=3)
    p.add_argument("--config", default="")
    p.add_argument("--cluster-token", default="",
                   help="shared secret gating the raft transport")
    p.add_argument("--raft-peers", default="",
                   help="store raft endpoints: s0=host:port,s1=host:port,...")
    p.add_argument("--coor-peers", default="",
                   help="coordinator raft group endpoints: "
                        "coor0=host:port,... (replicated coordinator; this "
                        "process's --id must be one of the ids)")
    args = p.parse_args(argv)
    if args.engine in ("lsm", "wal") and not args.data_dir \
            and args.role != "diskann":
        # an explicitly requested durable engine must not silently
        # downgrade to memory (None = flag not passed, default applies)
        p.error(f"--engine {args.engine} requires --data-dir")
    if args.engine is None:
        args.engine = "wal"
    if args.config:
        Config.load(args.config).apply_flag_overrides(FLAGS)
    if args.role == "coordinator":
        serve_coordinator(args)
    elif args.role == "diskann":
        serve_diskann(args)
    else:
        serve_store(args)   # store and index are one binary role-wise here
    return 0


if __name__ == "__main__":
    sys.exit(main())
