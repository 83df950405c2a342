"""HNSW keeps ONE graph, the device adjacency (ISSUE 31; on every backend
since ISSUE 33).

Writes go into the live adjacency through ops/graph_build.insert_batch,
nothing else is fed, exported or searched, save/load persist the adjacency
itself, and the device walk + exact rerank agree with the plain numpy
reference (tests/ref_graph_walk.py) on the same adjacency.
"""

import os
import sys
import time

import numpy as np
import pytest

from dingo_tpu.common.metrics import METRICS
from dingo_tpu.index import IndexParameter, IndexType, new_index

sys.path.insert(0, os.path.dirname(__file__))
import ref_graph_walk as ref  # noqa: E402

M, EF, K = 32, 200, 10
#: the limit benchmark/reference.py holds `dist_err` to
DIST_TOL = 5e-6


def clustered(n, d, seed, queries=32):
    """chip_smoke.make_corpus's mixture at a small size: standard-normal
    centres, 0.35 x noise within a cluster; queries are rows + 0.05 noise."""
    rng = np.random.default_rng(seed)
    centres = rng.standard_normal((max(8, n // 256), d)).astype(np.float32)
    x = centres[rng.integers(0, len(centres), n)] \
        + 0.35 * rng.standard_normal((n, d)).astype(np.float32)
    q = x[:queries] + 0.05 * rng.standard_normal(
        (queries, d)).astype(np.float32)
    return x.astype(np.float32), q.astype(np.float32)


def hnsw(rid, d):
    return new_index(rid, IndexParameter(
        index_type=IndexType.HNSW, dimension=d, nlinks=M,
        efconstruction=EF))


def counters(rid):
    return {name: METRICS.counter("hnsw." + name, region_id=rid).get()
            for name in ("native_adds", "host_searches",
                         "adjacency_rebuilds", "device_searches")}


def built_by_upserts(rid, x, batch=1000):
    idx = hnsw(rid, x.shape[1])
    ids = np.arange(len(x), dtype=np.int64)
    for s in range(0, len(x), batch):
        idx.upsert(ids[s:s + batch], x[s:s + batch])
    return idx


@pytest.fixture(scope="module")
def small():
    """4,096 x 64-d built by upsert batches."""
    x, q = clustered(4096, 64, seed=31)
    return built_by_upserts(310, x), x, q


def slot_space(idx, x):
    """(adjacency, rows by slot, validity) of the index, on the host."""
    store = idx.store
    rows = np.zeros((store.capacity, x.shape[1]), np.float32)
    live = store.ids_by_slot >= 0
    rows[live] = x[store.ids_by_slot[live]]
    return np.asarray(store.adj), rows, store.valid_h.copy()


def assert_matches_reference(idx, x, q):
    adj, rows, valid = slot_space(idx, x)
    got = idx.search(q, K, ef=EF)
    for qi, res in zip(q, got):
        slots, dists, _ = ref.search(adj, rows, qi, idx._entry_slot, EF, K,
                                     valid)
        want_ids = idx.store.ids_by_slot[slots]
        np.testing.assert_array_equal(res.ids, want_ids)
        scale = float(qi.astype(np.float64) @ qi) + np.einsum(
            "nd,nd->n", rows[slots].astype(np.float64), rows[slots])
        assert np.all(np.abs(res.distances - dists) <= DIST_TOL * scale)


@pytest.mark.parametrize("n,d,rid", [(4096, 64, 311), (2048, 768, 312)])
def test_device_walk_and_rerank_match_reference(n, d, rid, small):
    """Same adjacency, same entry, same ef: the device walk + exact rerank
    return the reference's ids in its order, distances within the
    benchmark's `dist_err` limit."""
    if (n, d) == (4096, 64):
        idx, x, q = small
    else:
        x, q = clustered(n, d, seed=32, queries=8)
        idx = built_by_upserts(rid, x, batch=512)
    assert_matches_reference(idx, x, q[:8])


def test_bf16_rerank_fails_the_distance_tolerance(small):
    """The control: the same candidates reranked in bfloat16 leave the
    tolerance, so the comparison would catch a precision below the
    configuration's fp32."""
    import jax.numpy as jnp

    idx, x, q = small
    adj, rows, valid = slot_space(idx, x)
    worst = 0.0
    for qi in q[:8]:
        slots, dists, _ = ref.search(adj, rows, qi, idx._entry_slot, EF, K,
                                     valid)
        qb = jnp.asarray(qi, jnp.bfloat16)
        rb = jnp.asarray(rows[slots], jnp.bfloat16)
        low = np.asarray(jnp.sum((rb - qb[None, :]) ** 2, axis=1),
                         np.float64)
        scale = float(qi.astype(np.float64) @ qi) + np.einsum(
            "nd,nd->n", rows[slots].astype(np.float64), rows[slots])
        worst = max(worst, float(np.max(np.abs(low - dists) / scale)))
    assert worst > DIST_TOL


def test_read_your_writes_without_rebuild(small):
    """64 fresh rows, then each is its own nearest neighbour in the next
    search; the counters of the retired native arm stay 0."""
    idx, x, _ = small
    before = counters(idx.id)
    rng = np.random.default_rng(5)
    fresh = x[rng.integers(0, len(x), 64)] + 0.2 * rng.standard_normal(
        (64, x.shape[1])).astype(np.float32)
    fids = np.arange(10**6, 10**6 + 64, dtype=np.int64)
    idx.upsert(fids, fresh)
    got = idx.search(fresh, 1, ef=EF)
    assert [int(r.ids[0]) for r in got] == fids.tolist()
    after = counters(idx.id)
    assert after["adjacency_rebuilds"] == before["adjacency_rebuilds"]
    assert after["native_adds"] == before["native_adds"] == 0
    assert after["host_searches"] == before["host_searches"] == 0
    # a delete tombstones: the rows are gone from the next search
    idx.delete(fids)
    for r in idx.search(fresh[:8], 3, ef=EF):
        assert not set(r.ids.tolist()) & set(fids.tolist())
    assert counters(idx.id)["adjacency_rebuilds"] \
        == before["adjacency_rebuilds"]


def test_save_load_serves_the_device_graph(tmp_path, small):
    """save -> new index object -> load: the same replies, the adjacency
    itself on disk and no other graph file."""
    idx, x, q = small
    want = idx.search(q, K, ef=EF)
    idx.save(str(tmp_path))
    assert not os.path.exists(tmp_path / "hnsw_graph.bin")
    again = hnsw(idx.id, x.shape[1])
    again.load(str(tmp_path))
    before = counters(idx.id)
    got = again.search(q, K, ef=EF)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a.ids, b.ids)
        np.testing.assert_allclose(a.distances, b.distances, rtol=1e-6)
    assert counters(idx.id) == dict(
        before, device_searches=before["device_searches"] + 1)
    # and it takes writes into the loaded graph
    row = x[:1] + 0.3
    again.upsert(np.asarray([10**7], np.int64), row)
    assert int(again.search(row, 1, ef=EF)[0].ids[0]) == 10**7


def test_visited_fraction_is_over_live_rows(small):
    """The gauge does not halve when the slot store doubles."""
    idx, x, q = small
    idx.search(q[:4], K, ef=EF)
    g = METRICS.gauge("hnsw.visited_fraction", region_id=idx.id)
    first = g.get()
    idx.store.reserve(idx.store.capacity * 2)
    idx.search(q[:4], K, ef=EF)
    assert g.get() == pytest.approx(first, rel=1e-6)
    assert 0.0 < first <= 1.0
    gathered = METRICS.gauge(
        "hnsw.gathered_rows_per_query", region_id=idx.id).get()
    assert gathered >= first * len(idx.store)


def test_max_elements_sizes_the_graph_at_creation(tmp_path, small):
    """The recipe's `max_elements` (the upstream's hnsw parameter, over
    the wire as pb field 14): slot store and adjacency hold the region's
    rows from the first write, so a load never re-shapes them; a loaded
    index is sized the same."""
    from dingo_tpu.server import pb
    from dingo_tpu.server.convert import (index_parameter_from_pb,
                                          index_parameter_to_pb)

    _, x, q = small
    param = index_parameter_from_pb(pb.VectorIndexParameter(
        index_type="VECTOR_INDEX_TYPE_HNSW", dimension=64,
        metric_type="METRIC_TYPE_L2", nlinks=M, efconstruction=EF,
        max_elements=10000))
    assert param.max_elements == 10000
    assert index_parameter_to_pb(param).max_elements == 10000
    idx = new_index(314, param)
    assert idx.store.capacity == 16384
    ids = np.arange(3000, dtype=np.int64)
    for s in range(0, 3000, 500):
        idx.upsert(ids[s:s + 500], x[s:s + 500])
        assert idx.store.capacity == 16384
        assert idx.store.adj.shape == (16384, 2 * M)
    assert counters(314)["native_adds"] == 0
    got = idx.search(x[:16], 1, ef=EF)
    assert [int(r.ids[0]) for r in got] == list(range(16))
    idx.save(str(tmp_path))
    again = new_index(314, param)
    again.load(str(tmp_path))
    assert again.store.capacity == 16384
    # without it the store grows from its smallest size, as before
    assert hnsw(315, 64).store.capacity < 16384


# -- what ISSUE 33 took away, and what it has to keep loading -----------------

def _as_native_arm_snapshot(path, rng):
    """Rewrite a snapshot into the form the native arm wrote before PR 33:
    node space in the native graph's own order (not slot order) with a
    tombstoned node whose row is gone, meta without `device_graph` /
    `entry_slot`, and the native blob beside them."""
    import json

    snap = np.load(os.path.join(path, "hnsw_adj.npz"))
    labels, adj = snap["labels"], snap["adj"]
    n = len(labels)
    perm = rng.permutation(n)                 # new node i = old node perm[i]
    inv = np.empty(n, np.int64)
    inv[perm] = np.arange(n)
    adj = np.where(adj >= 0, inv[np.maximum(adj, 0)], -1)[perm]
    # a node the native graph still held for a deleted row: it has edges,
    # and a live node points at it
    adj = np.concatenate([adj, adj[:1]]).astype(np.int32)
    adj[0, -1] = n
    labels = np.concatenate([labels[perm], [10**9]]).astype(np.int64)
    np.savez(os.path.join(path, "hnsw_adj.npz"), labels=labels, adj=adj)
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    graph = meta["hnsw_graph"]
    meta["hnsw_graph"] = {"deg": graph["deg"], "nodes": n + 1,
                          "entry_label": graph["entry_label"]}
    with open(os.path.join(path, "meta.json"), "w") as f:
        json.dump(meta, f)
    with open(os.path.join(path, "hnsw_graph.bin"), "wb") as f:
        f.write(b"not a graph")


def test_native_arm_snapshot_loads_and_serves(tmp_path, small):
    """A snapshot the native arm wrote carries the same labels +
    level-0 adjacency: load installs it, ignores the blob, and the index
    answers at the source's recall against numpy."""
    idx, x, q = small
    idx.save(str(tmp_path))
    _as_native_arm_snapshot(str(tmp_path), np.random.default_rng(7))
    again = hnsw(idx.id, x.shape[1])
    again.load(str(tmp_path))
    assert again.get_count() == len(x) and again.adjacency_in_sync()
    got = again.search(q, K, ef=EF)
    hits = sum(len(set(r.ids.tolist())
                   & set(ref.exact_topk(qi, x, K)[0].tolist()))
               for qi, r in zip(q, got))
    assert hits / (K * len(q)) >= 0.95
    # the next save leaves the form every backend writes now
    again.save(str(tmp_path))
    assert not os.path.exists(tmp_path / "hnsw_graph.bin")


def test_snapshot_without_adjacency_is_refused(tmp_path, small):
    """No usable adjacency (absent, or of another degree): load raises
    and the manager's rebuild from the engine takes over, as for a
    device snapshot without one before."""
    from dingo_tpu.index.base import InvalidParameter

    idx, x, _ = small
    idx.save(str(tmp_path))
    other = new_index(idx.id, IndexParameter(
        index_type=IndexType.HNSW, dimension=x.shape[1], nlinks=M // 2,
        efconstruction=EF))
    with pytest.raises(InvalidParameter):
        other.load(str(tmp_path))
    os.remove(tmp_path / "hnsw_adj.npz")
    with pytest.raises(InvalidParameter):
        hnsw(idx.id, x.shape[1]).load(str(tmp_path))


def test_graph_index_builds_and_opens_no_shared_object(tmp_path, small,
                                                       monkeypatch):
    """`dingo_tpu.native` serves the LSM engine only: an HNSW round trip
    (write, search, save, load, search) never asks it for a library."""
    import dingo_tpu.native as native

    assert [n for n in dir(native) if n.startswith("load_")] == ["load_lsm"]

    def refuse(*a, **kw):
        raise AssertionError("a graph index asked for a native library")

    monkeypatch.setattr(native, "_build", refuse)
    _, x, q = small
    idx = built_by_upserts(318, x[:512], batch=256)
    want = idx.search(q[:4], K, ef=EF)
    idx.delete(np.arange(8, dtype=np.int64))
    idx.save(str(tmp_path))
    again = hnsw(318, x.shape[1])
    again.load(str(tmp_path))
    got = again.search(q[:4], K, ef=EF)
    assert all(len(r.ids) == K for r in want + got)
    assert not any(name.startswith("libdingohnsw")
                   for name in os.listdir(os.path.dirname(native.__file__)))


def test_no_arm_flag_and_the_kept_counters_read_zero(small):
    """No flag chooses a graph: FLAGS and auto_arms() name none, and the
    three counters the benchmark still reads stay 0 through a write and
    a search."""
    from dingo_tpu.common.config import FLAGS, auto_arms

    assert not [n for n in FLAGS.all() if n.startswith("hnsw_device")]
    assert not [n for n in auto_arms() if n.startswith("hnsw")]
    idx, x, q = small
    searches = counters(idx.id)["device_searches"]
    idx.upsert(np.asarray([10**7 + 1], np.int64), x[:1] + 0.3)
    idx.search(q[:4], K, ef=EF)
    c = counters(idx.id)
    assert c["native_adds"] == c["host_searches"] \
        == c["adjacency_rebuilds"] == 0
    assert c["device_searches"] == searches + 1


# -- the served path: coordinator + store + SDK ------------------------------

@pytest.fixture(scope="module")
def served():
    from dingo_tpu.client import DingoClient
    from dingo_tpu.coordinator.control import CoordinatorControl
    from dingo_tpu.coordinator.kv_control import KvControl
    from dingo_tpu.coordinator.tso import TsoControl
    from dingo_tpu.engine.raw_engine import MemEngine
    from dingo_tpu.raft import LocalTransport
    from dingo_tpu.server import pb
    from dingo_tpu.server.rpc import DingoServer
    from dingo_tpu.store.node import StoreNode

    meta = MemEngine()
    control = CoordinatorControl(meta, replication=1)
    coord = DingoServer()
    coord.host_coordinator_role(control, TsoControl(meta), KvControl(meta))
    coord_port = coord.start()
    node = StoreNode("s0", LocalTransport(), control, raft_kw={"seed": 0})
    server = DingoServer()
    server.host_store_role(node)
    port = server.start()
    node.start_heartbeat(0.1)
    client = DingoClient(f"127.0.0.1:{coord_port}",
                         {"s0": f"127.0.0.1:{port}"})
    x, q = clustered(3072, 64, seed=33)
    param = pb.VectorIndexParameter(
        index_type=pb.VECTOR_INDEX_TYPE_HNSW, dimension=64,
        metric_type=pb.METRIC_TYPE_L2, nlinks=M, efconstruction=EF)
    region = client.create_index_region(0, 0, 1 << 40, param, replication=1)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        try:
            if client.vector_status(0):
                break
        except Exception:  # noqa: BLE001 — placement rides a heartbeat
            pass
        time.sleep(0.2)
    # METRICS is the process's: another test file on this worker may have
    # had a region of the same id, so the arm counters are read from here
    base = dict(counters(region.region_id), device_builds=METRICS.counter(
        "build.device_builds", region_id=region.region_id).get())
    for s in range(0, len(x), 512):
        client.vector_add(0, list(range(s, s + 512)), x[s:s + 512])
    yield client, region.region_id, x, q, base
    client.close()
    server.stop()
    coord.stop()
    node.stop()


def test_served_graph_built_by_upserts_only(served):
    """No bulk session, no VectorBuild: the load's own vector_add batches
    build the graph, and the SDK's searches at ef 200 reach the source's
    recall."""
    client, rid, x, q, base = served
    got = client.vector_search(0, q, topk=K, ef_search=EF)
    hits = 0
    for qi, row in zip(q, got):
        want, _ = ref.exact_topk(qi, x, K)
        hits += len({vid for vid, _ in row} & set(want.tolist()))
    assert hits / (K * len(q)) >= 0.95
    c = {name: n - base[name] for name, n in counters(rid).items()}
    bulk = METRICS.counter("build.device_builds", region_id=rid).get()
    assert c["host_searches"] == 0 and c["native_adds"] == 0
    assert c["adjacency_rebuilds"] == 0 and c["device_searches"] >= 1
    assert bulk == base["device_builds"]


def test_served_read_your_writes(served):
    client, rid, x, _, base = served
    before = counters(rid)
    rng = np.random.default_rng(6)
    fresh = x[rng.integers(0, len(x), 64)] + 0.2 * rng.standard_normal(
        (64, 64)).astype(np.float32)
    fids = list(range(5 * 10**5, 5 * 10**5 + 64))
    client.vector_add(0, fids, fresh)
    got = client.vector_search(0, fresh, topk=1, ef_search=EF)
    assert [row[0][0] for row in got] == fids
    after = counters(rid)
    assert after["adjacency_rebuilds"] == before["adjacency_rebuilds"] \
        == base["adjacency_rebuilds"]
    assert after["native_adds"] == base["native_adds"]
    assert after["host_searches"] == base["host_searches"]


# -- the benchmark's data for the cell ----------------------------------------

def _bench(*parts):
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def test_beam_walk_work_counts_visited_rows_and_rerank():
    """benchmark/work/beam_walk.py: per query the visited rows, each with
    its adjacency row, plus the ef rows of the rerank; never what the
    implementation gathers."""
    import importlib.util

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "work_beam_walk",
        os.path.join(root, "benchmark", "work", "beam_walk.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    config = _bench("benchmark", "configs", "hnsw768.json")
    traffic = _bench("benchmark", "traffic", "single_closed4_ef200.json")
    visited = config["assumed"]["walk"]["visited_rows_per_query"]
    w = mod.work(config, traffic)
    assert w["bytes"] == visited * (768 * 4 + 64 * 4) + 200 * 768 * 4
    assert w["flops"] == 2.0 * 768 * (visited + 200)
    assert 0 < visited < config["rows"]


def test_cell_states_the_sources_spec():
    """hnsw768 keeps every width, M, ef and k of the source; only rows are
    reduced, and the recipe is what the store's IndexParameter takes."""
    from dingo_tpu.server import pb

    config = _bench("benchmark", "configs", "hnsw768.json")
    traffic = _bench("benchmark", "traffic", "single_closed4_ef200.json")
    bench = _bench("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "hnsw768")
    assert entry["reduced"] == ["rows"] == list(config["reduced"])
    recipe = config["index_parameter"]
    assert (recipe["dimension"], recipe["nlinks"],
            recipe["efconstruction"]) == (768, M, EF)
    assert traffic["search_args"] == {"topk": K, "ef_search": EF}
    assert traffic["callers"] == 4 and traffic["batch"] == 1
    param = pb.VectorIndexParameter(**recipe)
    assert param.index_type == pb.VECTOR_INDEX_TYPE_HNSW
    # sized for the rows the cell loads, reduced with them
    assert param.max_elements == config["rows"]
    cell = next(w for w in bench["workloads"]
                if w["name"] == "hnsw768.conc4")
    assert cell["chips"] == 1 and cell["config"] == "hnsw768"
    assert config["conf_overrides"] == {}
