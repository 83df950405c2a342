"""Device graph tier (ISSUE 8): batched beam-search HNSW on the device.

numpy's exact top-k is the oracle: the walk over the graph its own
inserts built must reach the recall the retired native graph reached at
equal ef (ISSUE 33 measured it on this corpus, per metric and tier),
upserts and deletes must show in the next search with nothing rebuilt,
the ef/beam shape-bucket ladder must keep steady-state recompiles at
zero, the filter pushdown must return only eligible rows at the
unfiltered recall, and the adjacency must survive a snapshot round-trip.
"""

import numpy as np
import pytest

from dingo_tpu.common.metrics import METRICS
from dingo_tpu.index import FilterSpec, IndexParameter, IndexType, new_index
from dingo_tpu.ops.distance import Metric


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


#: recall@10 at ef 96 of the retired native graph on `corpus` (the parent
#: of PR 33, host arm): what the one graph has to reach. The quantized
#: tiers are bounded by their codes, not by the graph
HOST_RECALL = {
    (Metric.L2, "fp32"): 1.0, (Metric.L2, "bf16"): 1.0,
    (Metric.L2, "sq8"): 0.9666,
    (Metric.INNER_PRODUCT, "fp32"): 1.0,
    (Metric.INNER_PRODUCT, "bf16"): 1.0,
    (Metric.INNER_PRODUCT, "sq8"): 0.9916,
    (Metric.COSINE, "fp32"): 1.0, (Metric.COSINE, "bf16"): 0.9916,
    (Metric.COSINE, "sq8"): 0.975,
}


@pytest.mark.parametrize("metric", [Metric.L2, Metric.INNER_PRODUCT,
                                    Metric.COSINE])
@pytest.mark.parametrize("tier", ["fp32", "bf16", "sq8"])
def test_recall_against_exact_topk(corpus, metric, tier):
    """The acceptance gate: recall@10 against numpy's exact top-k at ef
    96, per metric x precision tier, at what the native graph met."""
    ids, x, q = corpus
    idx = new_index(30, hnsw_param(metric=metric, precision=tier))
    idx.add(ids, x)
    want = exact_topk(x, ids, q, 10, metric)
    assert recall(idx.search(q, 10, ef=96), want) \
        >= HOST_RECALL[metric, tier] - 1e-4


def test_incremental_upsert_delete_show_in_next_search(corpus):
    """A write goes into the live adjacency: the next search finds the new
    rows and misses the removed ones, and nothing is rebuilt or exported
    in between."""
    ids, x, q = corpus
    idx = new_index(32, hnsw_param())
    idx.add(ids[:2000], x[:2000])
    rb = METRICS.counter("hnsw.adjacency_rebuilds", region_id=32)
    rb0 = rb.get()
    adj_before = idx.store.adj
    idx.search(q, 10, ef=64)
    assert idx.store.adj is adj_before      # a search installs nothing
    idx.upsert(ids[2000:2300], x[2000:2300])
    res = idx.search(x[2000:2300:30], 1, ef=64)
    hit = np.mean([
        len(r.ids) and r.ids[0] == want_id
        for r, want_id in zip(res, ids[2000:2300:30])
    ])
    assert hit >= 0.9
    # deleted rows disappear from device results
    idx.delete(ids[:500])
    res = idx.search(q, 20, ef=128)
    for r in res:
        assert len(r.ids) == 20 and (r.ids >= 500).all()
    assert rb.get() == rb0


def test_steady_state_recompiles_zero_under_ladder(corpus):
    """After warmup over the (batch, beam) buckets, serving with any
    ef/batch inside those buckets never retraces (the monitored PR 3/5
    invariant extended to the beam kernel family)."""
    ids, x, q = corpus
    idx = new_index(33, hnsw_param())
    idx.add(ids, x)
    idx.warmup(batches=(1, 8, 32), topk=10, ef=64)
    rc = METRICS.counter("xla.recompiles")
    rc0 = rc.get()
    for b, ef in ((1, 64), (5, 60), (8, 49), (27, 64), (32, 52)):
        idx.search(q[:1].repeat(b, axis=0), 10, ef=ef)
    assert rc.get() - rc0 == 0


def test_filter_pushdown_equivalence(corpus):
    """Masked candidates never enter the result beam: results satisfy the
    filter, recall against numpy's exact top-k over the eligible rows is
    what the native graph's post-filter met here (1.0), and the second
    identical filter hits the (fingerprint, store version) cache."""
    ids, x, q = corpus
    idx = new_index(34, hnsw_param())
    idx.add(ids, x)
    spec = FilterSpec(ranges=[(500, 1500)])
    sub = (ids >= 500) & (ids < 1500)
    want = exact_topk(x[sub], ids[sub], q, 10, Metric.L2)
    hits = METRICS.counter("hnsw.filter_mask_hits", region_id=34)
    h0 = hits.get()
    res = idx.search(q, 10, spec, ef=160)
    for r in res:
        assert ((r.ids >= 500) & (r.ids < 1500)).all()
    assert recall(res, want) >= 1.0 - 1e-9
    idx.search(q, 10, spec, ef=160)
    assert hits.get() > h0


def test_snapshot_roundtrip_adjacency(tmp_path, corpus):
    """hnsw_adj.npz + meta restore the adjacency, and the restored index
    serves identical results."""
    ids, x, q = corpus
    idx = new_index(35, hnsw_param())
    idx.add(ids[:2000], x[:2000])
    before = idx.search(q, 10, ef=96)
    idx.save(str(tmp_path))
    idx2 = new_index(35, hnsw_param())
    idx2.load(str(tmp_path))
    assert idx2.store.adj is not None
    np.testing.assert_array_equal(
        np.asarray(idx.store.adj), np.asarray(idx2.store.adj)
    )
    after = idx2.search(q, 10, ef=96)
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a.ids, b.ids)


def test_sq8_snapshot_keeps_codes(tmp_path, corpus):
    """sq8 persists codes + codec params (no re-encode on load), so the
    restored device walk is bit-identical to the saved one."""
    ids, x, q = corpus
    idx = new_index(36, hnsw_param(precision="sq8"))
    idx.add(ids[:1500], x[:1500])
    before = idx.search(q, 10, ef=96)
    idx.save(str(tmp_path))
    idx2 = new_index(36, hnsw_param(precision="sq8"))
    idx2.load(str(tmp_path))
    after = idx2.search(q, 10, ef=96)
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a.ids, b.ids)


def test_entry_tombstone_falls_back(corpus):
    """Deleting the entry row, then most of the graph, leaves the walk
    serving the remaining rows: the entry moves to a live slot (at the
    parent of PR 33 the device arm answered empty from here on). With no
    live row left the index answers empty, and serves again after the
    next write."""
    ids, x, q = corpus
    idx = new_index(37, hnsw_param())
    idx.add(ids[:300], x[:300])
    entry_id = int(idx.store.ids_by_slot[idx._entry_slot])
    idx.delete(np.asarray([entry_id], np.int64))
    assert idx._entry_slot >= 0 and idx.store.valid_h[idx._entry_slot]
    assert all(len(r.ids) == 5 for r in idx.search(q, 5, ef=64))
    idx.delete(ids[:250])
    res = idx.search(q, 5, ef=64)
    for r in res:
        assert len(r.ids) > 0
        assert ((r.ids >= 250) & (r.ids < 300)).all()
    idx.delete(ids[250:300])
    assert idx._entry_slot == -1
    assert all(len(r.ids) == 0 for r in idx.search(q, 5, ef=64))
    idx.upsert(ids[300:400], x[300:400])
    res = idx.search(x[300:304], 1, ef=64)
    assert [int(r.ids[0]) for r in res] == [300, 301, 302, 303]


def test_device_empty_index(corpus):
    idx = new_index(38, hnsw_param())
    res = idx.search(np.zeros((2, 32), np.float32), 5)
    assert all(len(r.ids) == 0 for r in res)
