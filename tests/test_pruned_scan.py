"""Early-pruning dimension-blocked scan kernels: parity vs the XLA scan
across metrics x precision tiers (interpret mode on CPU), pruning
observability, the fused Quick-ADC IVF_PQ path, and the steady-state
recompile invariant."""

import numpy as np
import jax.numpy as jnp
import pytest

from dingo_tpu.common.config import FLAGS
from dingo_tpu.common.metrics import METRICS
from dingo_tpu.index.base import IndexParameter, IndexType
from dingo_tpu.index.flat import TpuFlat
from dingo_tpu.index.ivf_flat import TpuIvfFlat
from dingo_tpu.index.ivf_pq import TpuIvfPq
from dingo_tpu.ops.distance import Metric

N, D, NLIST, K = 6000, 32, 16, 10


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(3)
    centers = rng.standard_normal((NLIST, D)).astype(np.float32)
    x = centers[rng.integers(0, NLIST, N)] + 0.2 * rng.standard_normal(
        (N, D)
    ).astype(np.float32)
    ids = np.arange(N, dtype=np.int64)
    q = x[rng.choice(N, 8, replace=False)] + 0.01
    return x, ids, q


@pytest.fixture
def small_dim_block():
    FLAGS.set("ivf_dim_block", 8)
    yield
    FLAGS.set("ivf_dim_block", 128)


def _ground_truth(x, q, metric):
    if metric is Metric.L2:
        dm = ((q[:, None, :] - x[None, :, :]) ** 2).sum(-1)
        return np.argsort(dm, 1)[:, :K]
    return np.argsort(-(q @ x.T), 1)[:, :K]


def _recall(res, truth):
    return float(np.mean(
        [len(set(r.ids) & set(t)) / K for r, t in zip(res, truth)]
    ))


@pytest.mark.parametrize("precision", ["fp32", "bf16", "sq8"])
@pytest.mark.parametrize("metric", [Metric.L2, Metric.INNER_PRODUCT])
def test_ivf_pruned_parity_vs_xla(corpus, small_dim_block, precision,
                                  metric):
    """Exact tiers must return identical ids; sq8 recall@10 within 0.995
    relative of the XLA arm (blocked partial sums reorder bf16-multiply
    rounding near ties). Four queries: under ROW_BLOCK the request takes
    the query-major pruned kernel (tests/test_pallas_ivf.py holds the
    batch-major arm to the same answers)."""
    x, ids, q = corpus
    q = q[:4]
    idx = TpuIvfFlat(1, IndexParameter(
        index_type=IndexType.IVF_FLAT, dimension=D, ncentroids=NLIST,
        metric=metric, precision=precision,
    ))
    idx.upsert(ids, x)
    idx.train()
    truth = _ground_truth(x, q, metric)
    base = idx.search(q, K, nprobe=8)
    FLAGS.set("use_pallas_ivf_search", True)
    try:
        assert idx._bucket_bsq is None   # built lazily at next rebuild
        idx._invalidate_view()
        pruned = idx.search(q, K, nprobe=8)
        assert idx._bucket_bsq is not None
    finally:
        FLAGS.set("use_pallas_ivf_search", False)
    if precision == "sq8":
        assert _recall(pruned, truth) >= 0.995 * _recall(base, truth)
    else:
        assert [list(r.ids) for r in base] == [list(r.ids) for r in pruned]
    frac = METRICS.gauge("ivf.pruned_dim_fraction", region_id=1).get()
    assert 0.0 < frac < 1.0   # pruning demonstrably engaged


@pytest.mark.parametrize("nq", [4, 8])
def test_ivf_pruned_incremental_append_parity(corpus, small_dim_block, nq):
    """In-place appends must keep the blocked norm metadata in sync (the
    scatter arm, not just the dense materialize) — for the query-major
    pruned kernel (4 queries) and the batch-major one (8)."""
    x, ids, q = corpus
    q = q[:nq]
    idx = TpuIvfFlat(1, IndexParameter(
        index_type=IndexType.IVF_FLAT, dimension=D, ncentroids=NLIST,
    ))
    idx.upsert(ids[:5000], x[:5000])
    idx.train()
    FLAGS.set("use_pallas_ivf_search", True)
    try:
        idx.search(q, K, nprobe=8)      # builds view + blocked metadata
        idx.upsert(ids[5000:], x[5000:])   # incremental append
        idx.delete(ids[:64])               # tombstones
        assert idx.view_stats()["inplace_appends"] > 0
        pruned = idx.search(q, K, nprobe=NLIST)
    finally:
        FLAGS.set("use_pallas_ivf_search", False)
    base = idx.search(q, K, nprobe=NLIST)
    assert [list(r.ids) for r in base] == [list(r.ids) for r in pruned]
    for r in pruned:
        assert all(i >= 64 for i in r.ids)


def test_pruned_small_batch_grid_clamp(corpus, small_dim_block):
    """b < ROW_BLOCK batches run a clamped query grid; results match the
    XLA path for a single-query search."""
    x, ids, q = corpus
    idx = TpuIvfFlat(1, IndexParameter(
        index_type=IndexType.IVF_FLAT, dimension=D, ncentroids=NLIST,
    ))
    idx.upsert(ids, x)
    idx.train()
    base = idx.search(q[:1], K, nprobe=8)
    FLAGS.set("use_pallas_ivf_search", True)
    try:
        pruned = idx.search(q[:1], K, nprobe=8)
    finally:
        FLAGS.set("use_pallas_ivf_search", False)
    assert [list(r.ids) for r in base] == [list(r.ids) for r in pruned]


def test_pruned_counters_and_span_names(corpus, small_dim_block):
    x, ids, q = corpus
    idx = TpuIvfFlat(7, IndexParameter(
        index_type=IndexType.IVF_FLAT, dimension=D, ncentroids=NLIST,
    ))
    idx.upsert(ids, x)
    idx.train()
    c = METRICS.counter("ivf.pruned_candidates", region_id=7)
    arms = {a: METRICS.counter("ivf.scan_arm", region_id=7,
                               labels={"arm": a}) for a in ("query", "batch")}
    frac = METRICS.gauge("ivf.pruned_dim_fraction", region_id=7)
    before = c.get()
    FLAGS.set("use_pallas_ivf_search", True)
    try:
        idx.search(q[:4], K, nprobe=8)
        assert c.get() > before
        assert 0.0 < frac.get() < 1.0
        assert (arms["query"].get(), arms["batch"].get()) == (1, 0)
        # a batch-major scan prunes nothing and says so: the b = 4 value
        # left standing would be a false reading
        idx.search(q, K, nprobe=8)
        assert frac.get() == 0.0
        assert (arms["query"].get(), arms["batch"].get()) == (1, 1)
        touched = METRICS.gauge("ivf.batch_scan_buckets", region_id=7).get()
        assert 1 <= touched <= idx.view_stats()["nbuckets"]
    finally:
        FLAGS.set("use_pallas_ivf_search", False)


@pytest.mark.parametrize("nq", [4, 8])
def test_pruned_steady_state_no_recompiles(corpus, small_dim_block, nq):
    """PR 5 sentinel invariant: repeated same-shape searches hit the jit
    cache (grid clamp + shape bucketing keep shapes stable), on both loop
    orders of the Pallas scan."""
    x, ids, q = corpus
    q = q[:nq]
    idx = TpuIvfFlat(1, IndexParameter(
        index_type=IndexType.IVF_FLAT, dimension=D, ncentroids=NLIST,
    ))
    idx.upsert(ids, x)
    idx.train()
    FLAGS.set("use_pallas_ivf_search", True)
    try:
        idx.search(q, K, nprobe=8)        # warm
        rc = METRICS.counter("xla.recompiles")
        before = rc.get()
        for _ in range(3):
            idx.search(q, K, nprobe=8)
        assert rc.get() == before
    finally:
        FLAGS.set("use_pallas_ivf_search", False)


def test_flat_pruned_parity_all_tiers(corpus, small_dim_block):
    x, ids, q = corpus
    truth = _ground_truth(x, q, Metric.L2)
    FLAGS.set("vector_blocked_layout", True)
    try:
        for precision in ("fp32", "bf16", "sq8"):
            idx = TpuFlat(2, IndexParameter(
                index_type=IndexType.FLAT, dimension=D, precision=precision,
            ))
            idx.upsert(ids, x)
            assert idx.store.vecs_blk is not None
            base = idx.search(q, K)
            FLAGS.set("use_pallas_fused_search", True)
            try:
                pruned = idx.search(q, K)
            finally:
                FLAGS.set("use_pallas_fused_search", "auto")
            if precision == "sq8":
                assert _recall(pruned, truth) >= 0.995 * _recall(
                    base, truth
                )
            else:
                assert [list(r.ids) for r in base] == [
                    list(r.ids) for r in pruned
                ]
    finally:
        FLAGS.set("vector_blocked_layout", "auto")


def test_flat_fused_auto_is_off_on_cpu(corpus):
    """Tri-state 'auto' must not route to the Pallas kernel on the CPU
    arm (interpret mode is a test vehicle, not a serving path)."""
    from dingo_tpu.common.config import pallas_fused_enabled

    assert FLAGS.get("use_pallas_fused_search") == "auto"
    assert not pallas_fused_enabled(1 << 20)


@pytest.mark.parametrize("host_vectors", [False, True])
def test_ivfpq_fused_adc_parity(corpus, host_vectors):
    """Quick-ADC fused kernel: identical post-rerank results on the
    device-store arm; identical shortlist->rerank ids on the host arm."""
    x, ids, q = corpus
    idx = TpuIvfPq(3, IndexParameter(
        index_type=IndexType.IVF_PQ, dimension=D, ncentroids=NLIST,
        nsubvector=4, host_vectors=host_vectors,
    ))
    idx.upsert(ids, x)
    idx.train()
    base = idx.search(q, 5, nprobe=8)
    FLAGS.set("use_pallas_ivf_search", True)
    try:
        fused = idx.search(q, 5, nprobe=8)
    finally:
        FLAGS.set("use_pallas_ivf_search", False)
    assert [list(r.ids) for r in base] == [list(r.ids) for r in fused]
    for rb, rf in zip(base, fused):
        np.testing.assert_allclose(
            np.asarray(rb.distances), np.asarray(rf.distances),
            rtol=1e-3, atol=1e-3,
        )


def test_ivfpq_fused_adc_respects_filters(corpus):
    from dingo_tpu.index.base import FilterSpec

    x, ids, q = corpus
    idx = TpuIvfPq(3, IndexParameter(
        index_type=IndexType.IVF_PQ, dimension=D, ncentroids=NLIST,
        nsubvector=4,
    ))
    idx.upsert(ids, x)
    idx.train()
    spec = FilterSpec(ranges=[(100, 3000)])
    FLAGS.set("use_pallas_ivf_search", True)
    try:
        res = idx.search(q, 5, filter_spec=spec, nprobe=NLIST)
    finally:
        FLAGS.set("use_pallas_ivf_search", False)
    for r in res:
        assert all(100 <= i < 3000 for i in r.ids)
