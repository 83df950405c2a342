"""State-integrity plane (ISSUE 11): incremental device-state digests,
the corruption scrub, snapshot restore verification, coordinator replica
divergence detection, and the ReplicaGroup post-fanout monitor."""

import json
import os
import time

import numpy as np
import pytest

import jax.numpy as jnp

from dingo_tpu.common.config import FLAGS
from dingo_tpu.common.metrics import METRICS
from dingo_tpu.index.base import (
    IndexParameter,
    IndexType,
    SnapshotCorruption,
)
from dingo_tpu.index.factory import new_index
from dingo_tpu.obs.flight import FLIGHT
from dingo_tpu.obs.integrity import INTEGRITY, diverged_artifacts
from dingo_tpu.ops.digest import SetDigest, row_fingerprints

D = 32
N = 400


@pytest.fixture(autouse=True)
def _integrity_on():
    """Plane on + a clean flight recorder/status per test."""
    was = FLAGS.get("integrity_enabled")
    FLAGS.set("integrity_enabled", True)
    FLIGHT.clear()
    INTEGRITY.clear()
    yield
    FLAGS.set("integrity_enabled", was)
    INTEGRITY.clear()


def _wait_region_leader(node, region_id, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        rn = node.engine.get_node(region_id)
        if rn is not None and rn.is_leader():
            return
        node.heartbeat_once()
        time.sleep(0.05)
    raise AssertionError(f"no leader for region {region_id}")


def _corpus(seed=0, n=N, d=D):
    rng = np.random.default_rng(seed)
    return (np.arange(n, dtype=np.int64),
            rng.standard_normal((n, d)).astype(np.float32))


def _param(kind, d=D, **kw):
    defaults = dict(index_type=kind, dimension=d)
    if kind in (IndexType.IVF_FLAT, IndexType.IVF_PQ,
                IndexType.BINARY_IVF_FLAT):
        defaults.update(ncentroids=8, default_nprobe=8)
    if kind is IndexType.IVF_PQ:
        defaults.update(nsubvector=8)
    defaults.update(kw)
    return IndexParameter(**defaults)


# ---------------- digest primitive ----------------

def test_digest_order_invariant_and_homomorphic():
    ids, x = _corpus()
    fps = row_fingerprints("rows", ids, x)
    perm = np.random.default_rng(1).permutation(len(ids))
    assert SetDigest.of(fps) == SetDigest.of(
        row_fingerprints("rows", ids[perm], x[perm])
    )
    d = SetDigest.of(fps)
    d.remove(fps[:50])
    d.add(fps[:50])
    assert d == SetDigest.of(fps)
    assert d.count == len(ids)


def test_digest_detects_flip_swap_and_separates_tags():
    ids, x = _corpus()
    base = SetDigest.of(row_fingerprints("rows", ids, x))
    flipped = x.copy()
    flipped.view(np.uint8)[7, 13] ^= 1            # one byte, one row
    assert SetDigest.of(row_fingerprints("rows", ids, flipped)) != base
    swapped = x.copy()
    swapped[[3, 4]] = swapped[[4, 3]]             # payloads trade owners
    assert SetDigest.of(row_fingerprints("rows", ids, swapped)) != base
    assert SetDigest.of(row_fingerprints("blocked", ids, x)) != base
    assert SetDigest.from_hex(base.hex()) == base


def test_diverged_artifacts_helper():
    a = json.dumps({"rows": "1-a-b", "blocked": "1-c-d"})
    b = json.dumps({"rows": "1-a-b", "blocked": "1-x-y", "extra": "1-e-f"})
    # only artifacts BOTH sides report can diverge
    assert diverged_artifacts(a, b) == ["blocked"]
    assert diverged_artifacts(a, a) == []
    assert diverged_artifacts("", a) == []


# ---------------- incremental ledger vs full-state scrub ----------------

@pytest.mark.parametrize("kind,precision", [
    (IndexType.FLAT, "fp32"),
    (IndexType.FLAT, "bf16"),
    (IndexType.FLAT, "sq8"),
    (IndexType.IVF_FLAT, "fp32"),
    (IndexType.IVF_FLAT, "sq8"),
    (IndexType.HNSW, "fp32"),
    (IndexType.IVF_PQ, "fp32"),
])
def test_incremental_ledger_matches_scrub(kind, precision):
    """Writes + deletes + overwrites maintained incrementally must agree
    with a from-scratch device-state recompute for every index kind and
    precision tier."""
    ids, x = _corpus(seed=3)
    idx = new_index(11, _param(kind, precision=precision))
    idx.upsert(ids, x)
    if idx.need_train():
        idx.train()
        idx.search(x[:4], 5)           # builds the IVF view
    idx.delete(ids[10:40])
    idx.upsert(ids[20:30], x[20:30] + 1.0)   # re-add + fresh values
    idx.upsert(ids[:5], x[:5] * 2.0)          # overwrite in place
    if kind is IndexType.IVF_FLAT:
        idx.search(x[:4], 5)           # re-sync the view post-writes
    res = INTEGRITY.scrub_index(idx)
    assert res, "no artifacts scrubbed"
    for artifact, r in res.items():
        assert r["status"] == "ok", (artifact, r)
    assert "rows" in res
    if kind in (IndexType.IVF_FLAT, IndexType.IVF_PQ):
        assert "ivf_buckets" in res
    if kind is IndexType.IVF_PQ:
        assert "pq_codes" in res


def test_binary_flat_ledger_matches_scrub():
    rng = np.random.default_rng(5)
    packed = rng.integers(0, 256, size=(N, D // 8), dtype=np.uint8)
    ids = np.arange(N, dtype=np.int64)
    idx = new_index(12, _param(IndexType.BINARY_FLAT))
    idx.upsert(ids, packed)
    idx.delete(ids[:17])
    res = INTEGRITY.scrub_index(idx)
    assert res["rows"]["status"] == "ok"


def test_disabled_plane_is_inert():
    FLAGS.set("integrity_enabled", False)
    ids, x = _corpus()
    idx = new_index(13, _param(IndexType.FLAT))
    idx.upsert(ids, x)
    assert INTEGRITY.peek(idx) is None
    applied, digests, mismatch = INTEGRITY.region_report(idx)
    assert digests == "" and not mismatch


# ---------------- fault injection: one flipped byte per artifact --------

def _corrupt_device_array(store, attr, mutate):
    """Simulate silent HBM/restore corruption: read the device array back,
    flip state host-side, re-upload wholesale."""
    arr = np.asarray(getattr(store, attr)).copy()
    mutate(arr)
    with store.device_lock:
        setattr(store, attr, jnp.asarray(arr))


def _assert_detected(idx, artifact, results):
    assert results[artifact]["status"] == "mismatch", results
    mm = METRICS.counter("consistency.scrub_mismatches", region_id=idx.id,
                         labels={"artifact": artifact})
    assert mm.get() >= 1
    metas = FLIGHT.bundles_meta()
    assert any(m["reason"] == "corruption" for m in metas), metas


def test_scrub_detects_flipped_row_byte_and_renders_flight_report():
    ids, x = _corpus()
    idx = new_index(21, _param(IndexType.FLAT))
    idx.upsert(ids, x)
    slot = int(idx.store.slots_of(ids[:1])[0])
    _corrupt_device_array(
        idx.store, "vecs", lambda a: a.view(np.uint8).__setitem__(
            (slot, 3), a.view(np.uint8)[slot, 3] ^ 1)
    )
    res = INTEGRITY.scrub_index(idx)
    _assert_detected(idx, "rows", res)
    # the bundle carries the digest vectors and flight_report renders them
    import tools.flight_report as fr

    bundle = FLIGHT.get_json()
    assert bundle["reason"] == "corruption"
    assert bundle["trigger"]["artifacts"]["rows"]["expected"] != \
        bundle["trigger"]["artifacts"]["rows"]["actual"]
    text = fr.render(bundle)
    assert "state integrity" in text
    assert "MISMATCH" in text or "mismatch" in text


def test_scrub_detects_flipped_sq8_code():
    ids, x = _corpus(seed=7)
    idx = new_index(22, _param(IndexType.FLAT, precision="sq8"))
    idx.upsert(ids, x)
    slot = int(idx.store.slots_of(ids[5:6])[0])
    _corrupt_device_array(
        idx.store, "vecs",
        lambda a: a.__setitem__((slot, 2), a[slot, 2] ^ 1)
    )
    res = INTEGRITY.scrub_index(idx)
    assert res["rows"]["status"] == "mismatch"


def test_scrub_detects_flipped_blocked_mirror_entry():
    was = FLAGS.get("vector_blocked_layout")
    FLAGS.set("vector_blocked_layout", "True")
    try:
        ids, x = _corpus(seed=8, d=256)   # >= 2 x ivf_dim_block blocks
        idx = new_index(23, _param(IndexType.FLAT, d=256))
        assert idx.store.vecs_blk is not None
        idx.upsert(ids, x)
        res = INTEGRITY.scrub_index(idx)
        assert res["blocked"]["status"] == "ok"
        slot = int(idx.store.slots_of(ids[3:4])[0])
        _corrupt_device_array(
            idx.store, "vecs_blk", lambda a: a.view(np.uint8).__setitem__(
                (1, slot, 5), a.view(np.uint8)[1, slot, 5] ^ 1)
        )
        res = INTEGRITY.scrub_index(idx)
        # the rows copy is intact; only the mirror rotted
        assert res["rows"]["status"] == "ok"
        _assert_detected(idx, "blocked", res)
    finally:
        FLAGS.set("vector_blocked_layout", was)


def test_scrub_detects_flipped_adjacency_entry(tmp_path):
    ids, x = _corpus(seed=9)
    idx = new_index(24, _param(IndexType.HNSW))
    idx.upsert(ids, x)
    # the adjacency ledger is stale from a write to the next save, and
    # the scrub leaves the artifact alone meanwhile
    assert not idx.adjacency_in_sync()
    assert "adjacency" not in INTEGRITY.scrub_index(idx)
    idx.save(str(tmp_path))           # re-seeds the ledger
    assert idx.adjacency_in_sync()
    res = INTEGRITY.scrub_index(idx)
    assert res["adjacency"]["status"] == "ok"
    # rewire one neighbor entry to a DIFFERENT live slot
    slots = idx.store.slots_of(ids[:2])
    _corrupt_device_array(
        idx.store, "adj",
        lambda a: a.__setitem__((int(slots[0]), 0), int(slots[1]))
    )
    res = INTEGRITY.scrub_index(idx)
    _assert_detected(idx, "adjacency", res)


def test_scrub_detects_flipped_ivf_bucket_entry():
    ids, x = _corpus(seed=10)
    idx = new_index(25, _param(IndexType.IVF_FLAT))
    idx.upsert(ids, x)
    idx.train()
    idx.search(x[:2], 5)
    res = INTEGRITY.scrub_index(idx)
    assert res["ivf_buckets"]["status"] == "ok"
    view = idx._view
    bs = np.asarray(view.bucket_slot).copy()
    valid = np.argwhere(bs >= 0)
    b, r = valid[0]
    other = bs[tuple(valid[-1])]
    bs[b, r] = other              # a row claims a slot from another bucket
    with idx.store.device_lock:
        view.bucket_slot = jnp.asarray(bs)
    res = INTEGRITY.scrub_index(idx)
    _assert_detected(idx, "ivf_buckets", res)


def test_scrub_detects_flipped_pq_code():
    ids, x = _corpus(seed=11)
    idx = new_index(26, _param(IndexType.IVF_PQ))
    idx.upsert(ids, x)
    idx.train()
    res = INTEGRITY.scrub_index(idx)
    assert res["pq_codes"]["status"] == "ok"
    slot = int(idx.store.slots_of(ids[:1])[0])
    codes = np.asarray(idx._codes).copy()
    codes[slot, 0] ^= 1
    with idx.store.device_lock:
        idx._codes = jnp.asarray(codes)
    res = INTEGRITY.scrub_index(idx)
    _assert_detected(idx, "pq_codes", res)


def test_scrub_detection_within_one_interval_and_recovery():
    """A flip is caught by the NEXT scrub pass; a rebuilt (healed) state
    clears the region's mismatch flag on the following clean pass."""
    ids, x = _corpus(seed=12)
    idx = new_index(27, _param(IndexType.FLAT))
    idx.upsert(ids, x)
    INTEGRITY.scrub_index(idx)
    _applied, _digests, mismatch = INTEGRITY.region_report(idx)
    assert not mismatch
    slot = int(idx.store.slots_of(ids[:1])[0])
    _corrupt_device_array(
        idx.store, "vecs", lambda a: a.view(np.uint8).__setitem__(
            (slot, 0), a.view(np.uint8)[slot, 0] ^ 1)
    )
    INTEGRITY.scrub_index(idx)
    assert INTEGRITY.region_report(idx)[2] is True
    # heal: re-write the row through the front door
    idx.upsert(ids[:1], x[:1])
    INTEGRITY.scrub_index(idx)
    assert INTEGRITY.region_report(idx)[2] is False


# ---------------- snapshot round-trips ----------------

@pytest.mark.parametrize("kind,precision", [
    (IndexType.FLAT, "fp32"),
    (IndexType.FLAT, "bf16"),
    (IndexType.FLAT, "sq8"),
    (IndexType.IVF_FLAT, "fp32"),
    (IndexType.IVF_FLAT, "bf16"),
    (IndexType.IVF_FLAT, "sq8"),
    (IndexType.HNSW, "fp32"),
    (IndexType.HNSW, "sq8"),
    (IndexType.IVF_PQ, "fp32"),
])
def test_snapshot_digest_round_trip(tmp_path, kind, precision):
    ids, x = _corpus(seed=13)
    idx = new_index(31, _param(kind, precision=precision))
    idx.upsert(ids, x)
    if idx.need_train():
        idx.train()
    path = str(tmp_path / "snap")
    idx.save(path)
    meta = json.load(open(os.path.join(path, "meta.json")))
    assert meta.get("integrity", {}).get("rows"), meta
    fresh = new_index(31, _param(kind, precision=precision))
    fresh.load(path)                        # restore verification passes
    assert fresh.get_count() == len(ids)


@pytest.mark.parametrize("kind,precision,npz,field", [
    (IndexType.FLAT, "fp32", "flat.npz", "vectors"),
    (IndexType.FLAT, "sq8", "flat.npz", "codes"),
    (IndexType.IVF_FLAT, "fp32", "ivf_flat.npz", "vectors"),
    (IndexType.IVF_PQ, "fp32", "ivf_pq.npz", "vectors"),
    (IndexType.HNSW, "fp32", "hnsw_vectors.npz", "vectors"),
])
def test_tampered_snapshot_refused(tmp_path, kind, precision, npz, field):
    ids, x = _corpus(seed=14)
    idx = new_index(32, _param(kind, precision=precision))
    idx.upsert(ids, x)
    if idx.need_train():
        idx.train()
    path = str(tmp_path / "snap")
    idx.save(path)
    data = dict(np.load(os.path.join(path, npz)))
    data[field].view(np.uint8)[1, 0] ^= 1   # one flipped byte at rest
    np.savez(os.path.join(path, npz), **data)
    fresh = new_index(32, _param(kind, precision=precision))
    with pytest.raises(SnapshotCorruption):
        fresh.load(path)
    assert METRICS.counter("consistency.restore_mismatches",
                           region_id=32).get() >= 1


def test_tampered_hnsw_adjacency_snapshot_refused(tmp_path):
    """The PR 8 hnsw_adj.npz arm: the persisted adjacency is digest-gated
    too."""
    ids, x = _corpus(seed=15)
    idx = new_index(33, _param(IndexType.HNSW))
    idx.upsert(ids, x)
    path = str(tmp_path / "snap")
    idx.save(path)
    meta = json.load(open(os.path.join(path, "meta.json")))
    assert "adjacency" in meta["integrity"]
    data = dict(np.load(os.path.join(path, "hnsw_adj.npz")))
    adj = data["adj"]
    r, c = np.argwhere(adj >= 0)[0]
    adj[r, c] = int(data["labels"][-1])   # rewire to another node
    np.savez(os.path.join(path, "hnsw_adj.npz"), **data)
    fresh = new_index(33, _param(IndexType.HNSW))
    with pytest.raises(SnapshotCorruption):
        fresh.load(path)


def test_manager_falls_back_to_rebuild_on_corrupt_snapshot(tmp_path):
    """load_index returns False on SnapshotCorruption (any load failure),
    which is the rebuild-from-engine recovery path."""
    from dingo_tpu.engine.raw_engine import MemEngine
    from dingo_tpu.index.manager import VectorIndexManager
    from dingo_tpu.index.wrapper import VectorIndexWrapper
    from dingo_tpu.store.region import (
        Region,
        RegionDefinition,
        RegionType,
    )

    param = _param(IndexType.FLAT)
    ids, x = _corpus(seed=16)
    idx = new_index(34, param)
    idx.upsert(ids, x)
    mgr = VectorIndexManager(MemEngine(), snapshot_root=str(tmp_path))
    path = mgr.snapshot_path(34)
    idx.save(path)
    data = dict(np.load(os.path.join(path, "flat.npz")))
    data["vectors"].view(np.uint8)[0, 0] ^= 1
    np.savez(os.path.join(path, "flat.npz"), **data)
    definition = RegionDefinition(
        region_id=34, start_key=b"", end_key=b"",
        region_type=RegionType.INDEX, index_parameter=param,
    )
    region = Region(definition)
    region.vector_index_wrapper = VectorIndexWrapper(34, param)
    assert mgr.load_index(region) is False


# ---------------- br backup/restore verification ----------------

def test_br_backup_manifest_checksum_and_corrupt_restore(tmp_path):
    from dingo_tpu.br import backup_cluster, restore_cluster
    from dingo_tpu.coordinator.control import CoordinatorControl
    from dingo_tpu.engine.raw_engine import MemEngine
    from dingo_tpu.index import codec as vcodec
    from dingo_tpu.raft import LocalTransport
    from dingo_tpu.store.node import StoreNode
    from dingo_tpu.store.region import RegionType

    transport = LocalTransport()
    coord = CoordinatorControl(MemEngine(), replication=1)
    node = StoreNode("s0", transport, coord, raft_kw={"seed": 0})
    try:
        d = coord.create_region(
            start_key=vcodec.encode_vector_key(0, 0),
            end_key=vcodec.encode_vector_key(0, 1 << 30),
            region_type=RegionType.INDEX,
            index_parameter=_param(IndexType.FLAT, d=8),
        )
        for _ in range(3):
            node.heartbeat_once()
            time.sleep(0.05)
        _wait_region_leader(node, d.region_id)
        region = node.get_region(d.region_id)
        rng = np.random.default_rng(0)
        node.storage.vector_add(
            region, np.arange(20, dtype=np.int64),
            rng.standard_normal((20, 8)).astype(np.float32),
            [{} for _ in range(20)],
        )
        time.sleep(0.2)
        bak = str(tmp_path / "bak")
        manifest = backup_cluster(coord, {"s0": node}, bak)
        entry = manifest["regions"][0]
        assert len(entry["sha256"]) == 64
        # flip one byte at rest -> restore must refuse the artifact
        fpath = os.path.join(bak, entry["data_file"])
        blob = bytearray(open(fpath, "rb").read())
        blob[len(blob) // 2] ^= 1
        open(fpath, "wb").write(bytes(blob))
        transport2 = LocalTransport()
        coord2 = CoordinatorControl(MemEngine(), replication=1)
        node2 = StoreNode("s0", transport2, coord2, raft_kw={"seed": 0})
        try:
            with pytest.raises(ValueError, match="corrupt"):
                restore_cluster(coord2, {"s0": node2}, bak)
        finally:
            node2.stop()
    finally:
        node.stop()


# ---------------- heartbeat + coordinator divergence ----------------

def _region_snapshot(rid, applied, digests, mismatch=False):
    from dingo_tpu.metrics.snapshot import RegionMetricsSnapshot

    return RegionMetricsSnapshot(
        region_id=rid, is_leader=True,
        integrity_applied_index=applied,
        integrity_digests=digests,
        integrity_mismatch=mismatch,
    )


def _store_snapshot(sid, regions):
    from dingo_tpu.metrics.snapshot import StoreMetricsSnapshot

    return StoreMetricsSnapshot(store_id=sid, regions=regions)


def test_region_metrics_pb_round_trip():
    from dingo_tpu.server import convert, pb

    rm = _region_snapshot(7, 42, json.dumps({"rows": "1-a-b"}), True)
    m = convert.region_metrics_to_pb(rm)
    back = convert.region_metrics_from_pb(
        pb.RegionMetrics.FromString(m.SerializeToString())
    )
    assert back.integrity_applied_index == 42
    assert back.integrity_digests == rm.integrity_digests
    assert back.integrity_mismatch is True


def test_coordinator_divergence_detect_flag_and_clear():
    from dingo_tpu.coordinator.control import CoordinatorControl
    from dingo_tpu.engine.raw_engine import MemEngine

    coord = CoordinatorControl(MemEngine(), replication=2)
    coord.register_store("s0")
    coord.register_store("s1")
    good = json.dumps({"rows": "64-aaaa-bbbb", "blocked": "64-cc-dd"})
    bad = json.dumps({"rows": "64-aaaa-bbbb", "blocked": "64-ee-ff"})
    div0 = METRICS.counter("consistency.divergence", region_id=9).get()
    coord.store_heartbeat(
        "s0", metrics=_store_snapshot("s0", [_region_snapshot(9, 5, good)])
    )
    assert coord.diverged_regions() == []     # only one replica reporting
    # equal applied index, differing blocked digest -> DIVERGED
    FLIGHT.clear()
    coord.store_heartbeat(
        "s1", metrics=_store_snapshot("s1", [_region_snapshot(9, 5, bad)])
    )
    assert coord.diverged_regions() == [9]
    assert METRICS.counter(
        "consistency.divergence", region_id=9).get() == div0 + 1
    assert METRICS.gauge("consistency.diverged_regions").get() == 1.0
    metas = FLIGHT.bundles_meta()
    assert any(m["reason"] == "divergence" for m in metas)
    bundle = FLIGHT.get_json()
    assert bundle["trigger"]["peers"][0]["artifacts"] == ["blocked"]
    assert bundle["trigger"]["digests"] == bad
    # a replica merely LAGGING (different applied index) never diverges
    coord.store_heartbeat(
        "s1", metrics=_store_snapshot("s1", [_region_snapshot(9, 6, bad)])
    )
    # healed replica re-converges at the same applied index -> cleared
    coord.store_heartbeat(
        "s1", metrics=_store_snapshot("s1", [_region_snapshot(9, 5, good)])
    )
    coord.store_heartbeat(
        "s0", metrics=_store_snapshot("s0", [_region_snapshot(9, 5, good)])
    )
    assert coord.diverged_regions() == []
    assert METRICS.gauge("consistency.diverged_regions").get() == 0.0


def test_cluster_top_and_consistency_render():
    from dingo_tpu.client.cli import (
        format_cluster_consistency,
        format_cluster_top,
    )
    from dingo_tpu.server import convert, pb

    good = json.dumps({"rows": "64-aaaa-bbbb"})
    bad = json.dumps({"rows": "64-cccc-dddd"})
    top = pb.GetStoreMetricsResponse()
    for sid, digests in (("s0", good), ("s1", bad)):
        entry = top.stores.add()
        entry.store_id = sid
        convert.store_metrics_to_pb(
            _store_snapshot(sid, [_region_snapshot(9, 5, digests)]),
            entry.metrics,
        )
    top.diverged_region_ids.append(9)
    text = format_cluster_top(top)
    assert "DIVERGED" in text

    resp = pb.GetRegionMetricsResponse()
    for sid, digests in (("s0", good), ("s1", bad)):
        entry = resp.regions.add()
        entry.store_id = sid
        convert.region_metrics_to_pb(
            _region_snapshot(9, 5, digests), entry.metrics
        )
    resp.diverged_region_ids.append(9)
    text = format_cluster_consistency(resp)
    assert "DIVERGED" in text and "rows" in text
    # agreeing replicas render ok
    resp2 = pb.GetRegionMetricsResponse()
    for sid in ("s0", "s1"):
        entry = resp2.regions.add()
        entry.store_id = sid
        convert.region_metrics_to_pb(
            _region_snapshot(9, 5, good), entry.metrics
        )
    text = format_cluster_consistency(resp2)
    assert "ok" in text and "DIVERGED" not in text


def test_wrapper_tags_applied_index():
    from dingo_tpu.index.wrapper import VectorIndexWrapper

    param = _param(IndexType.FLAT)
    w = VectorIndexWrapper(41, param)
    w.build_own()
    w.ready = True
    ids, x = _corpus(seed=20, n=32)
    w.add(ids, x, log_id=17)
    led = INTEGRITY.peek(w.own_index)
    assert led is not None and led.applied_index == 17
    w.delete(ids[:4], log_id=18)
    assert led.applied_index == 18
    rep = led.report()
    assert rep["artifacts"]["rows"].startswith(f"{32 - 4:x}-")


def test_collector_fills_integrity_fields():
    """The heartbeat snapshot carries (applied index, digest vector,
    scrub verdict) — via a real StoreNode region."""
    from dingo_tpu.coordinator.control import CoordinatorControl
    from dingo_tpu.engine.raw_engine import MemEngine
    from dingo_tpu.index import codec as vcodec
    from dingo_tpu.raft import LocalTransport
    from dingo_tpu.store.node import StoreNode
    from dingo_tpu.store.region import RegionType

    transport = LocalTransport()
    coord = CoordinatorControl(MemEngine(), replication=1)
    node = StoreNode("s0", transport, coord, raft_kw={"seed": 0})
    try:
        d = coord.create_region(
            start_key=vcodec.encode_vector_key(0, 0),
            end_key=vcodec.encode_vector_key(0, 1 << 30),
            region_type=RegionType.INDEX,
            index_parameter=_param(IndexType.FLAT, d=8),
        )
        for _ in range(3):
            node.heartbeat_once()
            time.sleep(0.05)
        _wait_region_leader(node, d.region_id)
        region = node.get_region(d.region_id)
        rng = np.random.default_rng(1)
        node.storage.vector_add(
            region, np.arange(10, dtype=np.int64),
            rng.standard_normal((10, 8)).astype(np.float32),
            [{} for _ in range(10)],
        )
        time.sleep(0.2)
        snap = node.metrics.collect()
        rm = snap.region(d.region_id)
        assert rm.integrity_digests, "digest vector missing from heartbeat"
        digests = json.loads(rm.integrity_digests)
        assert digests["rows"].startswith("a-")      # 10 rows
        assert rm.integrity_applied_index > 0
        assert rm.integrity_mismatch is False
    finally:
        node.stop()


# ---------------- ReplicaGroup post-fanout monitor ----------------

def test_replica_group_fanout_divergence_detected():
    from dingo_tpu.parallel.replica_group import ReplicaGroup

    param = _param(IndexType.FLAT, d=16)

    def builder(index_id, parameter, devices):
        return new_index(index_id, parameter)

    group = ReplicaGroup(51, param, replicas=2,
                         devices=list(range(4)), member_builder=builder)
    ids, x = _corpus(seed=21, n=64, d=16)
    group.upsert(ids, x)
    assert group.verify_fanout(force=True) is True
    mm0 = METRICS.counter(
        "consistency.replica_mismatch", region_id=51).get()
    # one member silently loses a row OUTSIDE the next write batch (the
    # failure the bit-identity claim used to just assume away)
    group.members[1].delete(ids[10:11])
    FLIGHT.clear()
    rng = np.random.default_rng(2)
    group.upsert(ids[:4], rng.standard_normal((4, 16)).astype(np.float32))
    assert METRICS.counter(
        "consistency.replica_mismatch", region_id=51).get() == mm0 + 1
    assert any(m["reason"] == "divergence"
               for m in FLIGHT.bundles_meta())
    # healing the member clears the verdict
    group.members[1].upsert(ids[10:11], x[10:11])
    assert group.verify_fanout(force=True) is True


def test_scrub_runner_hot_gates_and_sweeps():
    from dingo_tpu.obs.integrity import IntegrityScrubRunner

    class _Meta:
        def __init__(self, regions):
            self._regions = regions

        def get_all_regions(self):
            return self._regions

    class _Region:
        def __init__(self, rid, idx):
            self.id = rid
            self.vector_index_wrapper = type(
                "W", (), {"own_index": idx})()

    ids, x = _corpus(seed=22, n=64)
    idx = new_index(61, _param(IndexType.FLAT))
    idx.upsert(ids, x)
    node = type("N", (), {"meta": _Meta([_Region(61, idx)])})()
    runner = IntegrityScrubRunner(node)
    runner.tick()
    for _ in range(100):
        t = runner._worker
        if t is None or not t.is_alive():
            break
        time.sleep(0.02)
    assert runner.sweeps == 1
    assert METRICS.gauge("consistency.scrub_ok", region_id=61).get() == 1.0
    # disabled -> no new sweep
    FLAGS.set("integrity_enabled", False)
    runner.tick()
    assert runner.sweeps == 1


# ---------------- review-fix regressions ----------------

def test_scrub_marks_inflight_write_as_raced(monkeypatch):
    """A write that mutated device state but hasn't folded into the
    ledger yet must read as 'raced' (retried next pass), never as a
    phantom 'mismatch' — write paths bump the region mutation counter
    BEFORE touching the device, and the scrub checks it."""
    from dingo_tpu.obs import integrity as integ_mod

    ids, x = _corpus(seed=30)
    idx = new_index(71, _param(IndexType.FLAT))
    idx.upsert(ids, x)
    orig = integ_mod._iter_rows

    def hijacked(index, chunk):
        for ids_, payload in orig(index, chunk):
            # simulate the window: the writer announced its mutation and
            # changed device bytes, but its ledger fold hasn't landed
            INTEGRITY.note_mutation_begin(index)
            bad = payload.copy()
            bad.view(np.uint8)[0, 0] ^= 1
            yield ids_, bad

    monkeypatch.setattr(integ_mod, "_iter_rows", hijacked)
    res = INTEGRITY.scrub_index(idx)
    assert res["rows"]["status"] == "raced", res
    assert INTEGRITY.region_report(idx)[2] is False  # no CORRUPT verdict


def test_ledger_survives_enabled_toggle():
    """integrity.enabled gates ledger CREATION only: an existing ledger
    keeps folding writes made while the flag is momentarily off, so
    re-enabling never yields false corruption verdicts or restore
    vetoes (the PR 9 quality-mirror toggle discipline)."""
    ids, x = _corpus(seed=31)
    idx = new_index(72, _param(IndexType.FLAT))
    idx.upsert(ids[:200], x[:200])
    FLAGS.set("integrity_enabled", False)
    idx.upsert(ids[200:300], x[200:300])       # tracked despite the flag
    idx.delete(ids[:10])
    FLAGS.set("integrity_enabled", True)
    res = INTEGRITY.scrub_index(idx)
    assert res["rows"]["status"] == "ok", res
    # a NEVER-tracked index stays zero-cost while disabled
    FLAGS.set("integrity_enabled", False)
    fresh = new_index(73, _param(IndexType.FLAT))
    fresh.upsert(ids[:50], x[:50])
    assert INTEGRITY.peek(fresh) is None
    FLAGS.set("integrity_enabled", True)


def test_adjacency_excluded_from_heartbeat_vector(tmp_path):
    """The adjacency ledger is re-seeded by each replica's own save
    (crontab timing), not in raft order — it must not ride the
    replica-compared heartbeat vector, while snapshot meta still carries
    it."""
    ids, x = _corpus(seed=32)
    idx = new_index(74, _param(IndexType.HNSW))
    idx.upsert(ids, x)
    idx.save(str(tmp_path))              # seeds the adjacency ledger
    led = INTEGRITY.peek(idx)
    assert "adjacency" in led.report()["artifacts"]
    digests = json.loads(led.heartbeat_view()[1])
    assert "adjacency" not in digests
    assert "rows" in digests
    assert "adjacency" in INTEGRITY.snapshot_artifacts(idx)


def test_heartbeat_withheld_while_write_in_flight():
    """The (applied, digest) heartbeat pair can be torn between a ledger
    fold and its applied-index tag — while any bracketed write is in
    flight the ledger withholds the digest vector for the beat instead
    of letting the coordinator compare a torn pair."""
    ids, x = _corpus(seed=33, n=64)
    idx = new_index(75, _param(IndexType.FLAT))
    idx.upsert(ids, x)
    led = INTEGRITY.peek(idx)
    applied, digests, _ = INTEGRITY.region_report(idx)
    assert digests != ""
    INTEGRITY.note_mutation_begin(idx)      # a write opened its bracket
    try:
        applied2, digests2, _ = INTEGRITY.region_report(idx)
        assert digests2 == ""               # no evidence this beat
    finally:
        INTEGRITY.note_mutation_end(idx)
    assert INTEGRITY.region_report(idx)[1] == digests
    assert led.pending == 0                  # brackets balanced


def test_scrub_raced_when_write_began_before_pass():
    """A write that opened its bracket BEFORE the scrub pass started and
    folds after it must also read as raced (the pending counter at the
    capture endpoint)."""
    ids, x = _corpus(seed=34)
    idx = new_index(76, _param(IndexType.FLAT))
    idx.upsert(ids, x)
    INTEGRITY.note_mutation_begin(idx)      # in-flight before the pass
    try:
        res = INTEGRITY.scrub_index(idx)
        assert res["rows"]["status"] == "raced", res
    finally:
        INTEGRITY.note_mutation_end(idx)
    assert INTEGRITY.scrub_index(idx)["rows"]["status"] == "ok"


def test_scrub_ok_gauge_holds_through_raced_passes():
    """consistency.scrub_ok only moves on DECISIVE passes: a raced pass
    after a confirmed mismatch must not flip the gauge back to healthy
    while the heartbeat still reports CORRUPT."""
    ids, x = _corpus(seed=35)
    idx = new_index(77, _param(IndexType.FLAT))
    idx.upsert(ids, x)
    slot = int(idx.store.slots_of(ids[:1])[0])
    _corrupt_device_array(
        idx.store, "vecs", lambda a: a.view(np.uint8).__setitem__(
            (slot, 0), a.view(np.uint8)[slot, 0] ^ 1)
    )
    INTEGRITY.scrub_index(idx)
    g = METRICS.gauge("consistency.scrub_ok", region_id=77)
    assert g.get() == 0.0
    INTEGRITY.note_mutation_begin(idx)      # every pass now races
    try:
        res = INTEGRITY.scrub_index(idx)
        assert res["rows"]["status"] == "raced"
        assert g.get() == 0.0               # raced pass: gauge holds
        assert INTEGRITY.region_report(idx)[2] is True
    finally:
        INTEGRITY.note_mutation_end(idx)


def test_sq8_canonical_rows_reuses_put_codes():
    """The integrity hook must not re-quantize the batch the store just
    encoded: canonical_rows reuses put()'s codes for the same array
    object, and still encodes correctly for any other input."""
    from dingo_tpu.index.slot_store import SqSlotStore

    ids, x = _corpus(seed=36, n=64)
    store = SqSlotStore(D)
    store.put(ids, x)
    memo_codes = store._canonical_memo[2]
    got = store.canonical_rows(x)           # same object: memo consumed
    assert got is memo_codes
    assert store._canonical_memo is None
    again = store.canonical_rows(x)         # no memo: fresh encode
    assert np.array_equal(again, memo_codes)
