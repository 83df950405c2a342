"""The SDK's routing state outlives a request: searches route from the cached
region map under the store's epoch check, a stub is built once per (store,
service), and a stale route is fetched again and the whole call routed anew.
Coordinator + 3 stores + two SDK clients over real sockets (the fixture of
test_grpc_server.py, with the table layer for drops)."""

import copy
import itertools
import time

import numpy as np
import pytest

from dingo_tpu.client import DingoClient
from dingo_tpu.client import client as client_mod
from dingo_tpu.client.client import ClientError
from dingo_tpu.common.metrics import METRICS
from dingo_tpu.coordinator.control import CoordinatorControl
from dingo_tpu.coordinator.kv_control import KvControl
from dingo_tpu.coordinator.meta import MetaControl
from dingo_tpu.coordinator.tso import TsoControl
from dingo_tpu.engine.raw_engine import MemEngine
from dingo_tpu.raft import LocalTransport
from dingo_tpu.server import pb, services
from dingo_tpu.server.rpc import DingoServer
from dingo_tpu.store.node import StoreNode

DIM = 16
ROWS = 200
FRESH_IDS = itertools.count(ROWS)
CAUSES = ("empty", "stale_epoch", "region_not_found", "region_op", "explicit")


class Cluster:
    def __init__(self, coord_addr, addrs, control, nodes, map_calls):
        self.coord_addr, self.addrs = coord_addr, addrs
        self.control, self.nodes = control, nodes
        self._map_calls = map_calls
        self._clients = []
        self._partition = 100

    def client(self) -> DingoClient:
        c = DingoClient(self.coord_addr, self.addrs)
        self._clients.append(c)
        return c

    def map_calls(self) -> int:
        """GetRegionMap calls the coordinator has served."""
        return self._map_calls["n"]

    def partition(self) -> int:
        self._partition += 1
        return self._partition

    def leader(self, region_id, timeout=5.0):
        return wait_for(lambda: next(
            (n for n in self.nodes.values()
             if (rn := n.engine.get_node(region_id)) is not None
             and rn.is_leader()), None), timeout)

    def loaded_partition(self, client):
        """A FLAT region over a partition of its own with ROWS rows; the
        rows' ids are 0..ROWS-1 and row i is the nearest to X[i]."""
        pid = self.partition()
        d = client.create_index_region(pid, 0, 1 << 40, PARAM)
        self.leader(d.region_id)
        self.load(client, pid, d.region_id)
        return pid, d

    def load(self, client, pid, region_id):
        wait_for(lambda: _try(lambda: client.vector_add(
            pid, list(range(ROWS)), X)))
        # a search may be answered by a follower: every replica has applied
        wait_for(lambda: all(
            n.get_region(region_id).vector_index_wrapper.own_index.get_count()
            == ROWS for n in self.nodes.values()))


def wait_for(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while True:
        got = cond()
        if got:
            return got
        if time.monotonic() > deadline:
            raise AssertionError("condition not met in time")
        time.sleep(0.02)


def _try(fn):
    try:
        fn()
        return True
    except ClientError:
        return False


def refreshes(cause: str) -> int:
    return METRICS.counter("client.region_map_refreshes",
                           labels={"cause": cause}).get()


def service_count(name: str) -> int:
    return METRICS.counter("service." + name).get()


PARAM = pb.VectorIndexParameter(
    index_type=pb.VECTOR_INDEX_TYPE_FLAT, dimension=DIM,
    metric_type=pb.METRIC_TYPE_L2)
X = np.random.default_rng(0).standard_normal((ROWS, DIM)).astype(np.float32)


@pytest.fixture(scope="module")
def cluster():
    map_calls = {"n": 0}
    served = services.CoordinatorService.GetRegionMap

    def counted(self, req):
        map_calls["n"] += 1
        return served(self, req)

    mp = pytest.MonkeyPatch()
    # before the coordinator registers its handlers: they bind at start
    mp.setattr(services.CoordinatorService, "GetRegionMap", counted)
    transport = LocalTransport()
    meta_engine = MemEngine()
    control = CoordinatorControl(meta_engine, replication=3)
    coord_server = DingoServer()
    coord_server.host_coordinator_role(
        control, TsoControl(meta_engine), KvControl(meta_engine),
        meta=MetaControl(meta_engine, control))
    coord_port = coord_server.start()
    nodes, servers, addrs = {}, [], {}
    for i, sid in enumerate(["s0", "s1", "s2"]):
        node = StoreNode(sid, transport, control, raft_kw={"seed": i})
        server = DingoServer()
        server.host_store_role(node)
        addrs[sid] = f"127.0.0.1:{server.start()}"
        node.start_heartbeat(0.1)
        nodes[sid] = node
        servers.append(server)
    c = Cluster(f"127.0.0.1:{coord_port}", addrs, control, nodes, map_calls)
    yield c
    for client in c._clients:
        client.close()
    for s in servers:
        s.stop()
    coord_server.stop()
    for n in nodes.values():
        n.stop()
    mp.undo()


@pytest.mark.parametrize("n", [1, 8])
def test_n_searches_make_one_region_map_call_and_one_stub_each(
        cluster, monkeypatch, n):
    pid, _ = cluster.loaded_partition(cluster.client())
    built = []

    class CountedStub(client_mod.ServiceStub):
        def __init__(self, channel, service):
            built.append(service)
            super().__init__(channel, service)

    monkeypatch.setattr(client_mod, "ServiceStub", CountedStub)
    client = cluster.client()
    calls, empty = cluster.map_calls(), refreshes("empty")
    for i in range(n):
        res = client.vector_search(pid, X[i:i + 2], topk=3)
        assert [row[0][0] for row in res] == [i, i + 1]
    assert cluster.map_calls() - calls == 1
    assert refreshes("empty") - empty == 1
    # one stub per (store, service), however many requests went through it
    assert len(built) == len(client._stubs) <= len(cluster.addrs)
    assert set(built) == {"IndexService"}
    for key, stub in client._stubs.items():
        assert client._stub(*key) is stub


@pytest.mark.parametrize("call", [
    lambda c, pid: c.vector_add(pid, [next(FRESH_IDS)], X[:1]),
    lambda c, pid: c.vector_import(pid, ids=[next(FRESH_IDS)], vectors=X[:1]),
    lambda c, pid: c.vector_count(pid),
    lambda c, pid: c.vector_status(pid),
    lambda c, pid: c.vector_build(pid),
], ids=["vector_add", "vector_import", "vector_count", "vector_status",
        "vector_build"])
def test_other_calls_still_fetch_the_map_every_time(cluster, call):
    client = cluster.client()
    pid, _ = cluster.loaded_partition(client)
    calls, explicit = cluster.map_calls(), refreshes("explicit")
    for _ in range(3):
        call(client, pid)
    assert cluster.map_calls() - calls == 3
    assert refreshes("explicit") - explicit == 3
    # and they go through the one stub
    assert len({k for k in client._stubs if k[1] == "IndexService"}) \
        <= len(cluster.addrs)


@pytest.mark.parametrize("stamp,errcode,stamped,refused", [
    (0, 0, 0, 0),          # an unstamped request is not checked
    (1, 0, 1, 0),          # the region's own version
    (7, 10002, 1, 1),      # another version: refused, and counted
], ids=["unstamped", "current", "stale"])
def test_store_checks_a_stamped_epoch(cluster, stamp, errcode, stamped,
                                      refused):
    client = cluster.client()
    pid, d = cluster.loaded_partition(client)
    assert d.epoch.version == 1
    leader = cluster.leader(d.region_id)
    req = pb.VectorSearchRequest()
    req.context.region_id = d.region_id
    req.context.region_epoch.version = stamp
    req.vectors.add().values.extend(X[0].tolist())
    req.parameter.top_n = 1
    before = service_count("epoch_stamped"), service_count("epoch_refusals")
    resp = client._stub(leader.store_id, "IndexService").VectorSearch(req)
    assert resp.error.errcode == errcode
    assert (not errcode) == (len(resp.batch_results) == 1)
    assert service_count("epoch_stamped") - before[0] == stamped
    assert service_count("epoch_refusals") - before[1] == refused


def test_searches_are_stamped_with_the_cached_epoch(cluster):
    client = cluster.client()
    pid, _ = cluster.loaded_partition(client)
    stamped, refused = service_count("epoch_stamped"), \
        service_count("epoch_refusals")
    for _ in range(4):
        client.vector_search(pid, X[:1], topk=1)
    assert service_count("epoch_stamped") - stamped >= 4
    assert service_count("epoch_refusals") == refused


def _ids(rows):
    return [vid for vid, _dist in rows]


def _split(cluster, operator, pid, d, at):
    child = operator.split_region(d.region_id, at, partition_id=pid)
    wait_for(lambda: all(n.get_region(child) is not None
                         for n in cluster.nodes.values()))
    wait_for(lambda: child in cluster.control.regions)
    cluster.leader(child)
    return child


def test_split_by_another_client_is_seen_by_the_next_search(cluster):
    first, operator = cluster.client(), cluster.client()
    pid, d = cluster.loaded_partition(first)
    assert _ids(first.vector_search(pid, X[:1], topk=3)[0])[0] == 0
    _split(cluster, operator, pid, d, ROWS // 2)
    calls = cluster.map_calls()
    before = {c: refreshes(c) for c in CAUSES}
    # no refresh_region_map(): the store's refusal is what tells `first`
    res = first.vector_search(pid, X[[10, ROWS - 10]], topk=ROWS)
    assert res[0][0][0] == 10 and res[1][0][0] == ROWS - 10
    for row in res:
        ids = _ids(row)
        assert len(ids) == len(set(ids)) == ROWS      # both halves, once
        assert set(ids) == set(range(ROWS))
    moved = {c: refreshes(c) - before[c] for c in CAUSES}
    assert moved == dict.fromkeys(CAUSES, 0) | {"stale_epoch": 1}
    assert cluster.map_calls() - calls == 1
    # and the new route is cached in its turn
    first.vector_search(pid, X[:1], topk=3)
    assert cluster.map_calls() - calls == 1


def test_merge_by_another_client_is_seen_by_the_next_search(cluster):
    first, operator = cluster.client(), cluster.client()
    pid, d = cluster.loaded_partition(first)
    child = _split(cluster, operator, pid, d, ROWS // 2)
    assert len(_ids(first.vector_search(pid, X[:1], topk=ROWS)[0])) == ROWS
    assert len(first._index_regions(pid)) == 2
    # the child's own index, then the merge back
    cluster.leader(child).finish_child_index(child)
    operator.merge_region(d.region_id, child)
    wait_for(lambda: all(n.get_region(child) is None
                         for n in cluster.nodes.values()))
    wait_for(lambda: child not in cluster.control.regions)
    # the target answers the absorbed range from the source's index beside
    # its own, which still holds those rows from before the split: its
    # rebuild leaves one index, so that a row twice would be the SDK's doing
    for n in cluster.nodes.values():
        n.finish_merge_index(d.region_id)
    before = {c: refreshes(c) for c in CAUSES}
    res = first.vector_search(pid, X[[10, ROWS - 10]], topk=ROWS)
    assert res[0][0][0] == 10 and res[1][0][0] == ROWS - 10
    for row in res:
        ids = _ids(row)
        assert len(ids) == len(set(ids)) == ROWS
    moved = {c: refreshes(c) - before[c] for c in CAUSES}
    # the target's bumped epoch or the source's absence, whichever region
    # the route met first
    assert moved["stale_epoch"] + moved["region_not_found"] == 1
    assert sum(moved.values()) == 1
    assert len(first._index_regions(pid)) == 1


def test_search_of_a_dropped_partition_fails_in_bounded_time(cluster):
    first, operator = cluster.client(), cluster.client()
    pid = cluster.partition()
    table = operator.create_vector_table(
        "dingo", f"t{pid}", PARAM, partitions=[(pid, 0, 1 << 40)])
    rid = table.partitions[0].region_id
    cluster.leader(rid)
    cluster.load(operator, pid, rid)
    assert first.vector_search(pid, X[:1], topk=1)[0][0][0] == 0
    operator.drop_table("dingo", f"t{pid}")
    wait_for(lambda: all(n.get_region(rid) is None
                         for n in cluster.nodes.values()))
    t0 = time.monotonic()
    with pytest.raises(ClientError):
        first.vector_search(pid, X[:1], topk=1)
    assert time.monotonic() - t0 < 5.0
    # and again, from whatever the failure left cached
    with pytest.raises(ClientError):
        first.vector_search(pid, X[:1], topk=1)


def test_persistently_stale_route_gives_up(cluster):
    """A map that stays at odds with the stores (a split the coordinator
    never hears of): bounded rounds, then ClientError."""
    client = cluster.client()
    pid, d = cluster.loaded_partition(client)
    ahead = cluster.control.regions[d.region_id].epoch
    ahead.version = 9           # no heartbeat brings it back: 1 is not newer
    try:
        client.refresh_region_map()
        calls, stale = cluster.map_calls(), refreshes("stale_epoch")
        t0 = time.monotonic()
        with pytest.raises(ClientError, match="stale"):
            client.vector_search(pid, X[:1], topk=1)
        assert time.monotonic() - t0 < 5.0
        rounds = client._retry.rounds
        assert cluster.map_calls() - calls == rounds - 1
        assert refreshes("stale_epoch") - stale == rounds - 1
    finally:
        ahead.version = 1
    assert client.vector_search(pid, X[:1], topk=1)[0][0][0] == 0


def test_region_ops_refresh_the_map_when_they_return(cluster):
    client = cluster.client()
    before = refreshes("region_op")
    pid, d = cluster.loaded_partition(client)          # create_index_region
    assert refreshes("region_op") - before == 1
    assert [r.region_id for r in client._index_regions(pid)] == [d.region_id]
    client.change_peer_region(d.region_id, list(d.peers))
    assert refreshes("region_op") - before == 2
    assert client._index_regions(pid)[0].epoch.conf_version == \
        d.epoch.conf_version + 1


def test_close_drops_the_stubs_with_the_channels(cluster):
    client = DingoClient(cluster.coord_addr, cluster.addrs)
    pid, _ = cluster.loaded_partition(client)
    client.vector_search(pid, X[:1], topk=1)
    assert client._stubs
    client.close()
    assert not client._stubs


@pytest.mark.parametrize("heartbeat_first", [False, True],
                         ids=["report_first", "heartbeat_first"])
def test_split_report_leaves_the_map_at_the_stores_version(heartbeat_first):
    """The leader's heartbeat may bring the shrunk parent before its split
    report does: the coordinator's parent must not end a version ahead of
    the stores, or every stamped request would be refused for ever."""
    control = CoordinatorControl(MemEngine(), replication=1)
    control.register_store("s0")
    d = control.create_region(start_key=b"a", end_key=b"z")
    on_store = copy.deepcopy(d)
    child = copy.deepcopy(d)
    child.region_id, child.start_key = d.region_id + 1, b"m"
    child.epoch.version = on_store.epoch.version + 1     # node.handle_split
    on_store.end_key = b"m"
    on_store.epoch.version += 1
    if heartbeat_first:
        control.store_heartbeat("s0", region_ids=[d.region_id],
                                leader_region_ids=[d.region_id],
                                region_defs=[copy.deepcopy(on_store)])
    control.on_region_split_done(d.region_id, child)
    known = control.regions[d.region_id]
    assert known.end_key == b"m"
    assert known.epoch.version == on_store.epoch.version
    assert control.regions[child.region_id].start_key == b"m"


def test_heartbeat_does_not_bring_a_dropped_region_back():
    """The leader reports a region until it has executed the DELETE; the
    map, which the SDK routes from, keeps it dropped."""
    control = CoordinatorControl(MemEngine(), replication=1)
    control.register_store("s0")
    d = control.create_region(start_key=b"a", end_key=b"z")
    beat = dict(region_ids=[d.region_id], leader_region_ids=[d.region_id],
                region_defs=[copy.deepcopy(d)])
    control.store_heartbeat("s0", **beat)
    control.drop_region(d.region_id)
    cmds = control.store_heartbeat("s0", **beat)
    assert [c.cmd_type.value for c in cmds
            if c.region_id == d.region_id][-1] == "delete"
    assert d.region_id not in control.regions
    control.store_heartbeat("s0", **beat)          # a beat in flight
    assert d.region_id not in control.regions
