"""Device-side bulk index construction (ISSUE 18): batched MXU graph
build, streaming rebuild, shared train-sample conf.

numpy's exact top-k is the oracle: a bulk-built graph must reach the
recall the retired native insert loop's graph reached at equal ef
(ISSUE 33 measured it on this corpus), build byte-identically under a
fixed seed, keep steady-state recompiles at zero across the insert
ladder, and take ordinary upserts and deletes afterwards. The manager
build must stream scan chunks — peak host memory O(chunk), not O(corpus).
"""

import numpy as np
import pytest

from dingo_tpu.common.config import FLAGS
from dingo_tpu.common.metrics import METRICS
from dingo_tpu.index import IndexParameter, IndexType, new_index
from dingo_tpu.ops.distance import Metric


@pytest.fixture(autouse=True)
def _restore_flags():
    yield
    FLAGS.set("hnsw_build_batch", 256)
    FLAGS.set("hnsw_build_alpha", 1.0)
    FLAGS.set("train_sample_rows", 65536)


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(18)
    n, d = 1200, 32
    x = rng.standard_normal((n, d)).astype(np.float32)
    ids = np.arange(n, dtype=np.int64)
    q = x[:10] + 0.01 * rng.standard_normal((10, d)).astype(np.float32)
    return ids, x, q


def hnsw_param(**kw):
    defaults = dict(
        index_type=IndexType.HNSW, dimension=32, nlinks=12,
        efconstruction=64,
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


def bulk_build(rid, ids, x, chunk=500, **param_kw):
    """Build an index through the bulk device session in scan-sized
    chunks (the manager feed pattern)."""
    idx = new_index(rid, hnsw_param(**param_kw))
    sess = idx.bulk_builder(expect_rows=len(ids))
    assert sess is not None
    for s in range(0, len(ids), chunk):
        sess.add(ids[s:s + chunk], x[s:s + chunk])
    sess.finish()
    return idx


#: recall@10 at ef 128 of the device walk over the graph the retired
#: native insert loop built on `corpus` (the parent of PR 33)
HOST_BUILT_RECALL = {
    (Metric.L2, "fp32"): 0.99, (Metric.L2, "sq8"): 0.97,
    (Metric.INNER_PRODUCT, "fp32"): 1.0, (Metric.INNER_PRODUCT, "sq8"): 1.0,
    (Metric.COSINE, "fp32"): 1.0, (Metric.COSINE, "sq8"): 0.98,
}


@pytest.mark.parametrize("metric", [Metric.L2, Metric.INNER_PRODUCT,
                                    Metric.COSINE])
@pytest.mark.parametrize("tier", ["fp32", "sq8"])
def test_bulk_built_recall_against_exact_topk(corpus, metric, tier):
    """The acceptance gate: searching a bulk-built graph reaches, against
    numpy's exact top-k, the recall the native-built graph reached at
    equal ef, per metric x precision tier."""
    ids, x, q = corpus
    dev = bulk_build(60, ids, x, metric=metric, precision=tier)
    want = exact_topk(x, ids, q, 10, metric)
    r_dev = recall(dev.search(q, 10, ef=128), want)
    # sq8 quantizes the candidate scores during construction, so two
    # builders see slightly different geometry — allow the noise floor
    tol = 1e-4 if tier == "fp32" else 0.05
    assert r_dev >= HOST_BUILT_RECALL[metric, tier] - tol


def test_adjacency_byte_stable_under_fixed_seed(corpus):
    """Same rows, same order, same conf -> bit-identical adjacency and
    entry slot (no data-dependent nondeterminism in the build kernel)."""
    ids, x, q = corpus
    a = bulk_build(62, ids, x)
    b = bulk_build(63, ids, x)
    np.testing.assert_array_equal(
        np.asarray(a.store.adj), np.asarray(b.store.adj)
    )
    assert a._entry_slot == b._entry_slot


def test_incremental_insert_after_bulk_build(corpus):
    """A bulk-built adjacency is the live graph: ordinary upserts insert
    into it, deletes tombstone in it, and the corpus it was built from
    stays served."""
    ids, x, q = corpus
    rng = np.random.default_rng(5)
    idx = bulk_build(64, ids, x)
    # a second bulk session on a non-empty index must refuse
    assert idx.bulk_builder() is None
    adj_shape = idx.store.adj.shape
    extra = rng.standard_normal((60, 32)).astype(np.float32)
    eids = np.arange(len(ids), len(ids) + 60, dtype=np.int64)
    idx.upsert(eids, extra)
    assert idx.store.adj.shape == adj_shape
    res = idx.search(extra[:10], 1, ef=64)
    hit = np.mean([len(r.ids) and r.ids[0] == w
                   for r, w in zip(res, eids[:10])])
    assert hit >= 0.9
    want = exact_topk(x, ids, q, 10, Metric.L2)
    assert recall(idx.search(q, 10, ef=128), want) >= 0.9
    idx.delete(eids)
    for r in idx.search(extra[:5], 5, ef=64):
        assert (r.ids < len(ids)).all()


def test_zero_steady_state_recompiles_across_ladder(corpus):
    """The second bulk build at identical shapes (capacity, batch, beam,
    deg) reuses every compiled program — the monitored recompile
    invariant extended to construction."""
    ids, x, q = corpus
    bulk_build(65, ids, x)        # warm the (shape, static-args) cache
    rc = METRICS.counter("xla.recompiles")
    rc0 = rc.get()
    bulk_build(66, ids, x)
    assert rc.get() - rc0 == 0


def test_save_load_after_bulk_build(tmp_path, corpus):
    """The snapshot carries the built adjacency itself and the restored
    index serves without knowing how its graph was built."""
    ids, x, q = corpus
    idx = bulk_build(67, ids[:600], x[:600])
    idx.save(str(tmp_path))
    assert sorted(p.name for p in tmp_path.iterdir()) == [
        "hnsw_adj.npz", "hnsw_vectors.npz", "meta.json"]
    idx2 = new_index(67, hnsw_param())
    idx2.load(str(tmp_path))
    want = exact_topk(x[:600], ids[:600], q, 10, Metric.L2)
    assert recall(idx2.search(q, 10, ef=128), want) >= 0.9


def test_reverse_dropped_counted(corpus):
    """Degree-clamped reverse insertion drops overflow incomers and
    counts them (silent truncation would read as full coverage)."""
    ids, x, q = corpus
    rd = METRICS.counter("build.reverse_dropped", region_id=68)
    rd0 = rd.get()
    bulk_build(68, ids, x)
    assert rd.get() >= rd0      # non-negative fold; value is data-driven


# -- manager: streaming rebuild --------------------------------------------

def _make_stack(rid, index_type=IndexType.HNSW, **param_kw):
    from dingo_tpu.engine.mono_engine import MonoStoreEngine
    from dingo_tpu.engine.raw_engine import MemEngine
    from dingo_tpu.engine.storage import Storage
    from dingo_tpu.index import codec as vcodec
    from dingo_tpu.store.region import (
        Region,
        RegionDefinition,
        RegionType,
    )

    raw = MemEngine()
    engine = MonoStoreEngine(raw)
    storage = Storage(engine)
    defaults = dict(index_type=index_type, dimension=16, ncentroids=4,
                    default_nprobe=4, nlinks=8, efconstruction=48)
    defaults.update(param_kw)
    region = Region(RegionDefinition(
        region_id=rid,
        start_key=vcodec.encode_vector_key(0, 0),
        end_key=vcodec.encode_vector_key(0, 1 << 40),
        region_type=RegionType.INDEX,
        index_parameter=IndexParameter(**defaults),
    ))
    w = region.vector_index_wrapper
    w.build_own()
    w.set_own(w.own_index)
    return raw, engine, storage, region


def test_manager_build_streams_bounded_chunks(monkeypatch):
    """The rebuild scan pages in BUILD_BATCH-row chunks — no single call
    materializes the corpus (the old path asked for 1<<62 rows at once,
    then copied them AGAIN for the train sample)."""
    from dingo_tpu.index.manager import (
        BUILD_BATCH,
        VectorIndexManager,
    )
    from dingo_tpu.index.vector_reader import VectorReader

    raw, engine, storage, region = _make_stack(70)
    rng = np.random.default_rng(2)
    n = BUILD_BATCH + 500       # forces > 1 page
    x = rng.standard_normal((n, 16)).astype(np.float32)
    all_ids = np.arange(n, dtype=np.int64)
    for s in range(0, n, 4096):   # VECTOR_MAX_BATCH_COUNT per write
        storage.vector_add(region, all_ids[s:s + 4096], x[s:s + 4096])
    limits = []
    orig = VectorReader.vector_scan_query

    def spy(self, *args, **kwargs):
        limits.append(kwargs.get("limit", args[1] if len(args) > 1 else None))
        return orig(self, *args, **kwargs)

    monkeypatch.setattr(VectorReader, "vector_scan_query", spy)
    mgr = VectorIndexManager(raw)
    index = mgr.build_index(region)
    assert index.get_count() == n
    assert len(limits) >= 2                       # actually paged
    assert max(limits) <= BUILD_BATCH             # O(chunk), not O(corpus)
    res = index.search(x[:2], 1)
    assert [r.ids[0] for r in res] == [0, 1]


def test_manager_build_uses_bulk_session():
    """manager.build_index constructs the HNSW graph through the device
    bulk session (build.device_builds) and the result serves."""
    from dingo_tpu.index.manager import VectorIndexManager

    raw, engine, storage, region = _make_stack(71)
    rng = np.random.default_rng(3)
    x = rng.standard_normal((900, 16)).astype(np.float32)
    storage.vector_add(region, np.arange(900, dtype=np.int64), x)
    db = METRICS.counter("build.device_builds", region_id=71)
    db0 = db.get()
    mgr = VectorIndexManager(raw)
    assert mgr.rebuild(region)
    assert db.get() == db0 + 1
    index = region.vector_index_wrapper.own_index
    assert index.get_count() == 900
    res = index.search(x[:2], 1)
    assert [r.ids[0] for r in res] == [0, 1]


def test_remat_override_goes_through_bulk_path():
    """PR 13 re-materialization is a rebuild with a narrowed parameter:
    the same streaming + bulk-build arm must carry it (repair time is
    degraded-serving time)."""
    from dingo_tpu.index.manager import VectorIndexManager
    from dingo_tpu.index.recovery import DeviceRecoveryPlane

    raw, engine, storage, region = _make_stack(72)
    rng = np.random.default_rng(4)
    x = rng.standard_normal((600, 16)).astype(np.float32)
    storage.vector_add(region, np.arange(600, dtype=np.int64), x)
    db = METRICS.counter("build.device_builds", region_id=72)
    db0 = db.get()
    override = DeviceRecoveryPlane.remat_parameter(
        region.definition.index_parameter
    )
    mgr = VectorIndexManager(raw)
    assert mgr.rebuild(region, param_override=override)
    assert db.get() == db0 + 1
    index = region.vector_index_wrapper.own_index
    assert index._precision == "sq8"              # narrowed tier applied
    assert region.definition.index_parameter.precision == ""
    assert index.get_count() == 600


def test_manager_train_failure_counted_not_swallowed():
    """Too little data to train: the counter + log replace the old silent
    `except Exception: pass`; the index still installs (untrained exact
    fallback)."""
    from dingo_tpu.index.manager import VectorIndexManager

    raw, engine, storage, region = _make_stack(
        73, index_type=IndexType.IVF_FLAT)
    rng = np.random.default_rng(6)
    x = rng.standard_normal((3, 16)).astype(np.float32)   # < ncentroids
    storage.vector_add(region, np.arange(3, dtype=np.int64), x)
    tf = METRICS.counter("build.train_failures", region_id=73)
    t0 = tf.get()
    mgr = VectorIndexManager(raw)
    index = mgr.build_index(region)
    assert tf.get() == t0 + 1
    assert not index.is_trained()
    assert index.get_count() == 3   # rows held; reader-level exact
    # fallback serves them (untrained IVF search itself raises NotTrained)


def test_manager_build_trains_ivf_from_stream():
    """The streamed build trains IVF AFTER ingest from the device-held
    rows — no second host copy of the corpus — and assignments cover
    every row."""
    from dingo_tpu.index.manager import VectorIndexManager

    raw, engine, storage, region = _make_stack(
        74, index_type=IndexType.IVF_FLAT)
    rng = np.random.default_rng(7)
    x = rng.standard_normal((300, 16)).astype(np.float32)
    storage.vector_add(region, np.arange(300, dtype=np.int64), x)
    mgr = VectorIndexManager(raw)
    index = mgr.build_index(region)
    assert index.is_trained()
    res = index.search(x[:3], 1)
    assert [r.ids[0] for r in res] == [0, 1, 2]


# -- shared train-sample conf ----------------------------------------------

def test_train_sample_rows_conf_caps_device_sample():
    """conf train.sample_rows bounds every implicit train gather; 0 lifts
    both the conf cap and the caller's derived cap (full corpus)."""
    idx = new_index(75, IndexParameter(
        index_type=IndexType.IVF_FLAT, dimension=8, ncentroids=4,
    ))
    rng = np.random.default_rng(8)
    n = 300
    idx.add(np.arange(n, dtype=np.int64),
            rng.standard_normal((n, 8)).astype(np.float32))
    FLAGS.set("train_sample_rows", 64)
    assert int(idx._train_rows_device(0).shape[0]) == 64
    # derived cap still binds when tighter than conf
    assert int(idx._train_rows_device(32).shape[0]) == 32
    FLAGS.set("train_sample_rows", 0)             # full corpus
    assert int(idx._train_rows_device(128).shape[0]) == n


def test_resolve_train_cap_semantics():
    from dingo_tpu.index.flat import _resolve_train_cap

    FLAGS.set("train_sample_rows", 1000)
    assert _resolve_train_cap(0) == 1000          # conf only
    assert _resolve_train_cap(500) == 500         # derived tighter
    assert _resolve_train_cap(5000) == 1000       # conf tighter
    FLAGS.set("train_sample_rows", 0)
    assert _resolve_train_cap(500) == 0           # 0 lifts BOTH caps
