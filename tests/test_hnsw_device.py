"""Device graph tier (ISSUE 8): batched beam-search HNSW on the device.

Host C++ graph path = parity oracle: the device walk must reach at least
the host path's recall at equal ef, adjacency must stay in sync across
upserts/deletes, the ef/beam shape-bucket ladder must keep steady-state
recompiles at zero, the filter pushdown must match the host post-filter,
and the adjacency must survive a snapshot round-trip.
"""

import numpy as np
import pytest

from dingo_tpu.common.config import FLAGS
from dingo_tpu.common.metrics import METRICS
from dingo_tpu.index import FilterSpec, IndexParameter, IndexType, new_index
from dingo_tpu.ops.distance import Metric


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    FLAGS.set("hnsw_device_search", "auto")
    FLAGS.set("hnsw_device_beam", 0)


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(11)
    n, d = 2500, 32
    x = rng.standard_normal((n, d)).astype(np.float32)
    ids = np.arange(n, dtype=np.int64)
    q = x[:12] + 0.01 * rng.standard_normal((12, d)).astype(np.float32)
    return ids, x, q


def hnsw_param(**kw):
    defaults = dict(
        index_type=IndexType.HNSW, dimension=32, nlinks=16,
        efconstruction=80,
    )
    defaults.update(kw)
    return IndexParameter(**defaults)


def exact_topk(x, ids, q, k, metric):
    if metric is Metric.L2:
        score = -(((q[:, None, :] - x[None, :, :]) ** 2).sum(-1))
    elif metric is Metric.COSINE:
        xn = x / np.linalg.norm(x, axis=1, keepdims=True)
        qn = q / np.linalg.norm(q, axis=1, keepdims=True)
        score = qn @ xn.T
    else:
        score = q @ x.T
    return ids[np.argsort(-score, axis=1)[:, :k]]


def recall(res, want, k=10):
    return float(np.mean(
        [len(set(r.ids) & set(w)) / k for r, w in zip(res, want)]
    ))


@pytest.mark.parametrize("metric", [Metric.L2, Metric.INNER_PRODUCT,
                                    Metric.COSINE])
@pytest.mark.parametrize("tier", ["fp32", "bf16", "sq8"])
def test_device_recall_at_least_host(corpus, metric, tier):
    """The acceptance gate: device beam recall@10 >= host recall at equal
    ef, per metric x precision tier."""
    ids, x, q = corpus
    idx = new_index(30, hnsw_param(metric=metric, precision=tier))
    idx.add(ids, x)
    want = exact_topk(x, ids, q, 10, metric)
    FLAGS.set("hnsw_device_search", False)
    r_host = recall(idx.search(q, 10, ef=96), want)
    FLAGS.set("hnsw_device_search", True)
    r_dev = recall(idx.search(q, 10, ef=96), want)
    assert r_dev >= r_host - 1e-9
    if metric is Metric.L2:
        assert r_dev >= 0.9     # the walk actually finds neighbors


def test_device_final_order_matches_host_on_agreeing_sets(corpus):
    """Both paths end in the SAME exact device rerank: when recall is
    saturated the final id ordering is byte-identical."""
    ids, x, q = corpus
    idx = new_index(31, hnsw_param())
    idx.add(ids, x)
    FLAGS.set("hnsw_device_search", False)
    host = idx.search(q, 10, ef=128)
    FLAGS.set("hnsw_device_search", True)
    dev = idx.search(q, 10, ef=128)
    want = exact_topk(x, ids, q, 10, Metric.L2)
    if recall(host, want) == 1.0 and recall(dev, want) == 1.0:
        for a, b in zip(host, dev):
            np.testing.assert_array_equal(a.ids, b.ids)
            np.testing.assert_allclose(a.distances, b.distances,
                                       rtol=1e-6, atol=1e-5)


def test_incremental_upsert_delete_adjacency_sync(corpus):
    """Writes dirty the mirror; the next device search re-exports and the
    walk sees the new/removed rows. The CPU arm's mirror
    (`hnsw.device_build` is off here, so the native graph takes the
    writes): the TPU arm re-exports nothing
    (tests/test_hnsw_one_graph.py)."""
    ids, x, q = corpus
    idx = new_index(32, hnsw_param())
    idx.add(ids[:2000], x[:2000])
    FLAGS.set("hnsw_device_search", True)
    rb = METRICS.counter("hnsw.adjacency_rebuilds", region_id=32)
    idx.search(q, 10, ef=64)
    rb0 = rb.get()
    # repeated read-only searches must NOT re-export
    idx.search(q, 10, ef=64)
    assert rb.get() == rb0
    # new rows become findable after one search-triggered resync
    idx.upsert(ids[2000:2300], x[2000:2300])
    res = idx.search(x[2000:2300:30], 1, ef=64)
    assert rb.get() == rb0 + 1
    hit = np.mean([
        len(r.ids) and r.ids[0] == want_id
        for r, want_id in zip(res, ids[2000:2300:30])
    ])
    assert hit >= 0.9
    # deleted rows disappear from device results
    idx.delete(ids[:500])
    res = idx.search(q, 20, ef=128)
    for r in res:
        assert (r.ids >= 500).all()
    assert rb.get() == rb0 + 2


def test_steady_state_recompiles_zero_under_ladder(corpus):
    """After warmup over the (batch, beam) buckets, serving with any
    ef/batch inside those buckets never retraces (the monitored PR 3/5
    invariant extended to the beam kernel family)."""
    ids, x, q = corpus
    idx = new_index(33, hnsw_param())
    idx.add(ids, x)
    FLAGS.set("hnsw_device_search", True)
    idx.warmup(batches=(1, 8, 32), topk=10, ef=64)
    rc = METRICS.counter("xla.recompiles")
    rc0 = rc.get()
    for b, ef in ((1, 64), (5, 60), (8, 49), (27, 64), (32, 52)):
        idx.search(q[:1].repeat(b, axis=0), 10, ef=ef)
    assert rc.get() - rc0 == 0


def test_filter_pushdown_equivalence(corpus):
    """Masked candidates never enter the result beam: device results
    satisfy the filter, recall matches the host post-filter path, and the
    second identical filter hits the (fingerprint, store version) cache."""
    ids, x, q = corpus
    idx = new_index(34, hnsw_param())
    idx.add(ids, x)
    spec = FilterSpec(ranges=[(500, 1500)])
    sub = (ids >= 500) & (ids < 1500)
    want = exact_topk(x[sub], ids[sub], q, 10, Metric.L2)
    FLAGS.set("hnsw_device_search", False)
    r_host = recall(idx.search(q, 10, spec, ef=160), want)
    FLAGS.set("hnsw_device_search", True)
    hits = METRICS.counter("hnsw.filter_mask_hits", region_id=34)
    h0 = hits.get()
    res = idx.search(q, 10, spec, ef=160)
    for r in res:
        assert ((r.ids >= 500) & (r.ids < 1500)).all()
    assert recall(res, want) >= r_host - 1e-9
    idx.search(q, 10, spec, ef=160)
    assert hits.get() > h0


def test_snapshot_roundtrip_adjacency(tmp_path, corpus):
    """hnsw_adj.npz + meta restore the device mirror without a native
    re-export, and the restored index serves identical device results."""
    ids, x, q = corpus
    idx = new_index(35, hnsw_param())
    idx.add(ids[:2000], x[:2000])
    FLAGS.set("hnsw_device_search", True)
    before = idx.search(q, 10, ef=96)
    idx.save(str(tmp_path))
    idx2 = new_index(35, hnsw_param())
    idx2.load(str(tmp_path))
    assert idx2.store.adj is not None
    np.testing.assert_array_equal(
        np.asarray(idx.store.adj), np.asarray(idx2.store.adj)
    )
    rb = METRICS.counter("hnsw.adjacency_rebuilds", region_id=35)
    rb0 = rb.get()
    after = idx2.search(q, 10, ef=96)
    assert rb.get() == rb0      # mirror restored from the snapshot
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a.ids, b.ids)


def test_sq8_snapshot_keeps_codes(tmp_path, corpus):
    """sq8 persists codes + codec params (no re-encode on load), so the
    restored device walk is bit-identical to the saved one."""
    ids, x, q = corpus
    idx = new_index(36, hnsw_param(precision="sq8"))
    idx.add(ids[:1500], x[:1500])
    FLAGS.set("hnsw_device_search", True)
    before = idx.search(q, 10, ef=96)
    idx.save(str(tmp_path))
    idx2 = new_index(36, hnsw_param(precision="sq8"))
    idx2.load(str(tmp_path))
    after = idx2.search(q, 10, ef=96)
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a.ids, b.ids)


def test_entry_tombstone_falls_back(corpus):
    """Deleting most of the graph (possibly including the entry node)
    still leaves the device walk serving the remaining rows."""
    ids, x, q = corpus
    idx = new_index(37, hnsw_param())
    idx.add(ids[:300], x[:300])
    idx.delete(ids[:250])
    FLAGS.set("hnsw_device_search", True)
    res = idx.search(q, 5, ef=64)
    for r in res:
        assert len(r.ids) > 0
        assert ((r.ids >= 250) & (r.ids < 300)).all()


def test_device_empty_index(corpus):
    FLAGS.set("hnsw_device_search", True)
    idx = new_index(38, hnsw_param())
    res = idx.search(np.zeros((2, 32), np.float32), 5)
    assert all(len(r.ids) == 0 for r in res)
