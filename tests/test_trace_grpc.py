"""Trace propagation over real gRPC: metadata carries the context
client -> server, and a full VectorSearch through the coalescer produces
one connected multi-span trace, exported via the debug RPCs and as a
valid Chrome trace_event file."""

import json

import grpc
import numpy as np
import pytest

from dingo_tpu.common.config import FLAGS
from dingo_tpu.common.metrics import METRICS
from dingo_tpu.coordinator.control import CoordinatorControl
from dingo_tpu.coordinator.kv_control import KvControl
from dingo_tpu.coordinator.tso import TsoControl
from dingo_tpu.engine.raw_engine import MemEngine
from dingo_tpu.raft import LocalTransport
from dingo_tpu.server import pb
from dingo_tpu.server.rpc import DingoServer, ServiceStub, _register
from dingo_tpu.server.services import DebugService
from dingo_tpu.store.node import StoreNode
from dingo_tpu.trace import TRACE_BUFFER, TRACER, to_chrome_trace


@pytest.fixture()
def sampled():
    TRACE_BUFFER.clear()
    FLAGS.set("trace_sampling_rate", 1.0)
    try:
        yield
    finally:
        FLAGS.set("trace_sampling_rate", 0.0)
        TRACE_BUFFER.clear()


def test_grpc_metadata_propagation_roundtrip(sampled):
    """Client span context rides gRPC metadata; the server ingress span
    joins the SAME trace with the client span as parent."""
    server = DingoServer()
    _register(server._server, "DebugService", DebugService())
    port = server.start()
    chan = grpc.insecure_channel(f"127.0.0.1:{port}")
    try:
        stub = ServiceStub(chan, "DebugService")
        with TRACER.start_span("test.client_root") as root:
            stub.MetricsDump(pb.MetricsDumpRequest())
            trace_id = f"{root.trace_id:016x}"
        spans = {r["name"]: r
                 for r in TRACE_BUFFER.snapshot(trace_id=trace_id)}
        assert "client.DebugService.MetricsDump" in spans
        assert "rpc.DebugService.MetricsDump" in spans
        # cross-process link: server parent == client egress span id
        assert spans["rpc.DebugService.MetricsDump"]["parent_id"] == \
            spans["client.DebugService.MetricsDump"]["span_id"]
        assert spans["client.DebugService.MetricsDump"]["parent_id"] == \
            spans["test.client_root"]["span_id"]
    finally:
        chan.close()
        server.stop()


def test_grpc_unsampled_sends_no_metadata():
    """With sampling off the stub must not add metadata (and the server
    must not record)."""
    FLAGS.set("trace_sampling_rate", 0.0)
    TRACE_BUFFER.clear()
    server = DingoServer()
    _register(server._server, "DebugService", DebugService())
    port = server.start()
    chan = grpc.insecure_channel(f"127.0.0.1:{port}")
    try:
        stub = ServiceStub(chan, "DebugService")
        stub.MetricsDump(pb.MetricsDumpRequest())
        assert TRACE_BUFFER.snapshot() == []
    finally:
        chan.close()
        server.stop()


def test_grpc_propagates_unsampled_decision(sampled):
    """At 0 < rate < 1 an unsampled root's decision rides the metadata as
    '0-0-0' so downstream servers do NOT re-roll and mint fragment roots
    mid-request."""
    FLAGS.set("trace_sampling_rate", 0.5)
    server = DingoServer()
    _register(server._server, "DebugService", DebugService())
    port = server.start()
    chan = grpc.insecure_channel(f"127.0.0.1:{port}")
    try:
        stub = ServiceStub(chan, "DebugService")
        for _ in range(40):
            stub.MetricsDump(pb.MetricsDumpRequest())
        # every recorded server span must be linked to a client span of
        # the same trace — no server-side roots (fragments) at all
        recs = TRACE_BUFFER.snapshot()
        server_spans = [r for r in recs if r["name"].startswith("rpc.")]
        client_ids = {
            (r["trace_id"], r["span_id"])
            for r in recs if r["name"].startswith("client.")
        }
        assert server_spans, "rate 0.5 over 40 calls: expected samples"
        for s in server_spans:
            assert (s["trace_id"], s["parent_id"]) in client_ids, s
    finally:
        chan.close()
        server.stop()


def test_tracing_off_ingress_leaves_context_clean():
    """A rate-0 server with no incoming header must not attach a noop
    context: its nested outbound calls would otherwise send '0-0-0' for
    a decision nobody made, suppressing sampling on downstream servers."""
    from dingo_tpu.trace import current_span

    FLAGS.set("trace_sampling_rate", 0.0)
    seen = {}

    class Probe(DebugService):
        def MetricsDump(self, req):
            seen["ctx"] = current_span()
            seen["onward_md"] = __import__(
                "dingo_tpu.trace", fromlist=["inject_metadata"]
            ).inject_metadata(None)
            return super().MetricsDump(req)

    server = DingoServer()
    _register(server._server, "DebugService", Probe())
    port = server.start()
    chan = grpc.insecure_channel(f"127.0.0.1:{port}")
    try:
        ServiceStub(chan, "DebugService").MetricsDump(pb.MetricsDumpRequest())
        assert seen["ctx"] is None
        assert seen["onward_md"] is None
    finally:
        chan.close()
        server.stop()


def test_slow_query_logged_even_when_unsampled(sampled):
    """Always-sample-slow: a request that loses the head-sampling roll
    still lands in the slow-query log (synthesized record, no span tree)."""
    FLAGS.set("trace_sampling_rate", 1e-9)   # armed, but never samples
    FLAGS.set("slow_query_ms", 0.0001)       # every RPC counts as slow
    server = DingoServer()
    _register(server._server, "DebugService", DebugService())
    port = server.start()
    chan = grpc.insecure_channel(f"127.0.0.1:{port}")
    try:
        stub = ServiceStub(chan, "DebugService")
        stub.MetricsDump(pb.MetricsDumpRequest())
        slow = TRACE_BUFFER.slow_queries()
        mine = [s for s in slow if s["name"] == "rpc.DebugService.MetricsDump"]
        assert mine and mine[-1]["attrs"] == {"unsampled": True}
        assert mine[-1]["dur_us"] > 0
        # no span tree was recorded for the unsampled request
        assert all(r["name"] != "rpc.DebugService.MetricsDump"
                   for r in TRACE_BUFFER.snapshot())
    finally:
        FLAGS.set("slow_query_ms", 500.0)
        chan.close()
        server.stop()


def test_slow_log_excludes_background_roots(sampled):
    """Slow-QUERY log: only rpc./client. roots qualify — a slow sampled
    background root (rebuild, raft-apply write) is buffered and bridged
    but never buries query evidence in the slow log."""
    import time as _time

    FLAGS.set("slow_query_ms", 0.001)
    try:
        with TRACER.start_span("index.rebuild"):
            _time.sleep(0.005)
        assert all(s["name"] != "index.rebuild"
                   for s in TRACE_BUFFER.slow_queries())
        assert any(r["name"] == "index.rebuild"
                   for r in TRACE_BUFFER.snapshot())
    finally:
        FLAGS.set("slow_query_ms", 500.0)


def test_vector_search_trace_end_to_end(sampled):
    """Acceptance: at sampling 1.0 one VectorSearch RPC through the
    coalescer yields >= 5 nested spans (rpc -> coalesce.wait ->
    coalesce.run -> index scan -> device kernel) sharing one trace id,
    visible through TraceDump JSON and a valid Chrome trace file."""
    from dingo_tpu.client import DingoClient

    me = MemEngine()
    control = CoordinatorControl(me, replication=1)
    cs = DingoServer()
    cs.host_coordinator_role(control, TsoControl(me), KvControl(me))
    cport = cs.start()
    node = StoreNode("s0", LocalTransport(), control, raft_kw={"seed": 0})
    srv = DingoServer()
    srv.host_store_role(node)
    port = srv.start()
    node.start_heartbeat(0.1)
    client = DingoClient(f"127.0.0.1:{cport}", {"s0": f"127.0.0.1:{port}"})
    FLAGS.set("search_coalescing_window_ms", 10.0)
    try:
        param = pb.VectorIndexParameter(
            index_type=pb.VECTOR_INDEX_TYPE_FLAT, dimension=8,
            metric_type=pb.METRIC_TYPE_L2,
        )
        client.create_index_region(0, 0, 1 << 30, param)
        import time
        time.sleep(1.0)
        rng = np.random.default_rng(0)
        x = rng.standard_normal((50, 8)).astype(np.float32)
        client.vector_add(0, list(range(50)), x)

        TRACE_BUFFER.clear()
        with TRACER.start_span("test.ingress") as root:
            res = client.vector_search(0, x[[3]], topk=3)
            trace_id = f"{root.trace_id:016x}"
        assert res[0][0][0] == 3

        spans = TRACE_BUFFER.snapshot(trace_id=trace_id)
        names = {s["name"] for s in spans}
        assert len(spans) >= 5, names
        assert "rpc.IndexService.VectorSearch" in names
        assert "coalesce.wait" in names
        assert "coalesce.run" in names
        assert "index.search" in names
        assert any(n.startswith("ops.") for n in names), names
        # single trace id and a CONNECTED tree: every non-root parent id
        # is another span of the same trace
        ids = {s["span_id"] for s in spans}
        roots = [s for s in spans if not s["parent_id"]]
        assert [r["name"] for r in roots] == ["test.ingress"]
        for s in spans:
            assert s["trace_id"] == trace_id
            if s["parent_id"]:
                assert s["parent_id"] in ids, s
        # ingress carries the profiling attributes
        rpc_span = next(s for s in spans
                        if s["name"] == "rpc.IndexService.VectorSearch")
        assert rpc_span["attrs"]["region_id"] >= 1
        assert rpc_span["attrs"]["batch"] == 1

        # exported via the DebugService JSON RPC
        dbg = client._stub("s0", "DebugService")
        payload = json.loads(dbg.TraceDump(pb.MetricsDumpRequest()).json)
        assert trace_id in payload["traces"]
        assert {s["name"] for s in payload["traces"][trace_id]} >= {
            "rpc.IndexService.VectorSearch", "coalesce.run"}

        # and as a Chrome trace_event payload (RPC + in-process exporter)
        chrome = json.loads(
            dbg.TraceChromeDump(pb.MetricsDumpRequest()).json)
        assert chrome["traceEvents"]
        local = to_chrome_trace(spans)
        assert {e["name"] for e in local["traceEvents"]} == names
        for ev in local["traceEvents"]:
            assert ev["ph"] == "X"
            assert isinstance(ev["ts"], int) and ev["dur"] >= 1
    finally:
        FLAGS.set("search_coalescing_window_ms", 0.0)
        client.close()
        srv.stop()
        cs.stop()
        node.stop()


# ---------------- span trees of the served paths ----------------

@pytest.fixture()
def cluster():
    """Coordinator + one store (replication 1) behind real gRPC, and a
    client; yields (client, node)."""
    from dingo_tpu.client import DingoClient

    me = MemEngine()
    control = CoordinatorControl(me, replication=1)
    cs = DingoServer()
    cs.host_coordinator_role(control, TsoControl(me), KvControl(me))
    cport = cs.start()
    node = StoreNode("s0", LocalTransport(), control, raft_kw={"seed": 0})
    srv = DingoServer()
    srv.host_store_role(node)
    port = srv.start()
    node.start_heartbeat(0.1)
    client = DingoClient(f"127.0.0.1:{cport}", {"s0": f"127.0.0.1:{port}"})
    try:
        yield client, node
    finally:
        client.close()
        srv.stop()
        cs.stop()
        node.stop()


_INDEX_PARAMS = {
    "flat": dict(index_type=pb.VECTOR_INDEX_TYPE_FLAT),
    "ivf_flat": dict(index_type=pb.VECTOR_INDEX_TYPE_IVF_FLAT, ncentroids=4),
}


def _make_region(client, kind, rows=300, dim=8):
    import time

    client.create_index_region(0, 0, 1 << 30, pb.VectorIndexParameter(
        dimension=dim, metric_type=pb.METRIC_TYPE_L2, **_INDEX_PARAMS[kind]))
    time.sleep(1.0)
    x = np.random.default_rng(0).standard_normal((rows, dim)).astype(
        np.float32)
    client.vector_add(0, list(range(rows)), x)
    if kind == "ivf_flat":
        client.vector_build(0)
        deadline = time.monotonic() + 30.0
        while not client.vector_status(0)[0]["trained"]:
            assert time.monotonic() < deadline, "index never trained"
            time.sleep(0.1)
    client.vector_search(0, x[:2], topk=3)      # compile outside the trace
    return x


def _request_trace(rpc_name):
    """The spans of the one request with an ingress span `rpc_name`."""
    roots = [r for r in TRACE_BUFFER.snapshot() if r["name"] == rpc_name]
    assert len(roots) == 1, [r["name"] for r in TRACE_BUFFER.snapshot()]
    return TRACE_BUFFER.snapshot(trace_id=roots[0]["trace_id"])


def _assert_connected_and_nested(spans, root):
    """One trace id; one root (the in-process client's egress span);
    every other parent id present; every child's interval inside its
    parent's (microsecond records, both ends floored)."""
    by_id = {s["span_id"]: s for s in spans}
    assert len({s["trace_id"] for s in spans}) == 1
    assert [s["name"] for s in spans if not s["parent_id"]] == [root]
    for s in spans:
        if not s["parent_id"]:
            continue
        assert s["parent_id"] in by_id, s
        parent = by_id[s["parent_id"]]
        assert parent["start_us"] <= s["start_us"], (s, parent)
        assert s["start_us"] + s["dur_us"] <= \
            parent["start_us"] + parent["dur_us"] + 1, (s, parent)


@pytest.mark.parametrize("kind", ["flat", "ivf_flat"])
def test_search_span_tree_at_every_boundary(cluster, kind):
    """One served VectorSearch, sampled by the store: decode, dispatch
    (with its lock wait), device wait, resolve, encode — once each, in
    one trace, nested, and adding up inside index.search."""
    client, _node = cluster
    x = _make_region(client, kind)
    TRACE_BUFFER.clear()
    FLAGS.set("trace_sampling_rate", 1.0)
    try:
        res = client.vector_search(0, x[[3, 4]], topk=3)
    finally:
        FLAGS.set("trace_sampling_rate", 0.0)
    assert [r[0][0] for r in res] == [3, 4]
    ingress = "rpc.IndexService.VectorSearch"
    spans = _request_trace(ingress)
    names = [s["name"] for s in spans]
    ops = [n for n in names if n.startswith("ops.")]
    assert len(ops) == 1, names            # one device wait per request
    assert ops[0] == {"flat": "ops.flat_scan", "ivf_flat": "ops.ivf_scan"}[kind]
    want = {ingress, "service.decode", "index.search", "index.dispatch",
            "index.lock_wait", ops[0], "index.resolve", "service.encode"}
    assert want <= set(names), names
    for once in want:                      # the four old metrics' meaning
        assert names.count(once) == 1, (once, names)
    assert [n for n in names if n.startswith("rpc.")] == [ingress]
    _assert_connected_and_nested(spans, "client.IndexService.VectorSearch")
    by_name = {s["name"]: s for s in spans}
    by_id = {s["span_id"]: s for s in spans}

    def parent_of(name):
        return by_id[by_name[name]["parent_id"]]["name"]

    assert parent_of("service.decode") == ingress
    assert parent_of("service.encode") == ingress
    assert parent_of("index.search") == ingress
    assert parent_of("index.dispatch") == "index.search"
    assert parent_of("index.lock_wait") == "index.dispatch"
    assert parent_of(ops[0]) == "index.search"
    assert parent_of("index.resolve") == "index.search"
    # the stages follow each other and fit the span that holds them
    d, w, r = (by_name[n] for n in ("index.dispatch", ops[0],
                                    "index.resolve"))
    assert d["start_us"] + d["dur_us"] <= w["start_us"] + 1
    assert w["start_us"] + w["dur_us"] <= r["start_us"] + 1
    assert d["dur_us"] + w["dur_us"] + r["dur_us"] <= \
        by_name["index.search"]["dur_us"] + 3
    assert by_name["service.decode"]["dur_us"] \
        + by_name["service.encode"]["dur_us"] \
        + by_name["index.search"]["dur_us"] <= by_name[ingress]["dur_us"] + 3
    if kind == "ivf_flat":
        # the probed-bucket ids rode the same fetch: rows in the probed
        # buckets per query, from the view's fill counts
        got = [v for k, v in METRICS.dump().items()
               if k.startswith("ivf.probed_rows_per_query")]
        assert got and 0 < got[0] <= 300


def test_vector_add_span_tree_through_raft(cluster):
    client, _node = cluster
    x = _make_region(client, "flat")
    TRACE_BUFFER.clear()
    FLAGS.set("trace_sampling_rate", 1.0)
    try:
        client.vector_add(0, [1000, 1001], x[:2])
    finally:
        FLAGS.set("trace_sampling_rate", 0.0)
    ingress = "rpc.IndexService.VectorAdd"
    spans = _request_trace(ingress)
    names = [s["name"] for s in spans]
    want = {ingress, "service.decode", "raft.propose", "raft.apply",
            "engine.write", "index.upsert", "service.encode"}
    assert want <= set(names), names
    for once in want:
        assert names.count(once) == 1, (once, names)
    _assert_connected_and_nested(spans, "client.IndexService.VectorAdd")
    by_name = {s["name"]: s for s in spans}
    by_id = {s["span_id"]: s for s in spans}
    chain = ["index.upsert", "raft.apply", "raft.propose", ingress]
    for child, parent in zip(chain, chain[1:]):
        assert by_id[by_name[child]["parent_id"]]["name"] == parent
    assert by_id[by_name["engine.write"]["parent_id"]]["name"] == "raft.apply"
    # the written rows are searchable: the traced write was a real one
    assert client.vector_search(0, x[[0]], topk=2)[0][0][1] == 0.0


@pytest.mark.parametrize("kind", ["flat", "ivf_flat"])
def test_sampled_search_never_blocks_and_frees_the_device_lock(
        cluster, kind, monkeypatch):
    """A sampled request makes the device calls an unsampled one makes:
    no block_until_ready anywhere, one device_get, and while it waits for
    its result another thread can take the store's device lock."""
    import threading

    import jax

    client, node = cluster
    x = _make_region(client, kind)
    index = node.get_region(
        client.vector_status(0)[0]["region_id"]).vector_index_wrapper.active()
    blocked, gets, lock_free = [], [], []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda v: blocked.append(1) or v)
    real_get = jax.device_get

    def watched_get(tree):
        # the request is about to wait for the device: the lock is free
        def probe():
            got = index.store.device_lock.acquire(timeout=5.0)
            lock_free.append(got)
            if got:
                index.store.device_lock.release()

        t = threading.Thread(target=probe)
        t.start()
        t.join(timeout=10.0)
        gets.append(1)
        return real_get(tree)

    monkeypatch.setattr(jax, "device_get", watched_get)
    TRACE_BUFFER.clear()
    FLAGS.set("trace_sampling_rate", 1.0)
    try:
        res = client.vector_search(0, x[[7]], topk=3)
    finally:
        FLAGS.set("trace_sampling_rate", 0.0)
    assert res[0][0][0] == 7
    assert blocked == []
    assert gets == [1] and lock_free == [True]
    assert any(s["name"].startswith("ops.")
               for s in _request_trace("rpc.IndexService.VectorSearch"))
