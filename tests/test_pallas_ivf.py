"""Pallas IVF list-DMA kernel: parity vs the XLA scan path (interpret mode
on CPU; same program compiles for TPU via Mosaic)."""

import numpy as np
import jax.numpy as jnp
import pytest

from dingo_tpu.common.config import FLAGS
from dingo_tpu.index.base import IndexParameter, IndexType
from dingo_tpu.index.ivf_flat import TpuIvfFlat
from dingo_tpu.ops.distance import Metric


@pytest.fixture(scope="module")
def trained_index():
    rng = np.random.default_rng(3)
    n, d, nlist = 6000, 32, 16
    centers = rng.standard_normal((nlist, d)).astype(np.float32)
    x = centers[rng.integers(0, nlist, n)] + 0.2 * rng.standard_normal(
        (n, d)
    ).astype(np.float32)
    ids = np.arange(n, dtype=np.int64)
    idx = TpuIvfFlat(1, IndexParameter(
        index_type=IndexType.IVF_FLAT, dimension=d, ncentroids=nlist,
    ))
    idx.upsert(ids, x)
    idx.train()
    q = x[rng.choice(n, 8, replace=False)] + 0.01
    return idx, x, q


def _results(idx, q, **kw):
    return [(list(r.ids), np.asarray(r.distances)) for r in idx.search(q, 10, **kw)]


def _assert_parity(base, fused):
    for (bi, bd), (fi, fd) in zip(base, fused):
        assert bi == fi
        np.testing.assert_allclose(bd, fd, rtol=1e-4, atol=1e-4)


def test_pallas_ivf_parity_with_xla_path(trained_index):
    idx, x, q = trained_index
    base = _results(idx, q, nprobe=8)
    FLAGS.set("use_pallas_ivf_search", True)
    try:
        fused = _results(idx, q, nprobe=8)
    finally:
        FLAGS.set("use_pallas_ivf_search", False)
    _assert_parity(base, fused)


def test_pallas_ivf_filter_and_full_probe(trained_index):
    idx, x, q = trained_index
    from dingo_tpu.index.base import FilterSpec

    spec = FilterSpec(ranges=[(100, 3000)])
    base = _results(idx, q, nprobe=idx.nlist, filter_spec=spec)
    FLAGS.set("use_pallas_ivf_search", True)
    try:
        fused = _results(idx, q, nprobe=idx.nlist, filter_spec=spec)
    finally:
        FLAGS.set("use_pallas_ivf_search", False)
    _assert_parity(base, fused)
    for ids, _ in fused:
        assert all(100 <= i < 3000 for i in ids)


def test_pallas_paths_accept_bf16_stores():
    """bench stores vectors in bf16; the Pallas kernels promote in VMEM so
    the flag-gated paths must route (and agree with XLA) for bf16 too."""
    import jax.numpy as jnp

    from dingo_tpu.index.flat import TpuFlat

    rng = np.random.default_rng(9)
    x = rng.standard_normal((3000, 32)).astype(np.float32)
    ids = np.arange(3000, dtype=np.int64)
    flat = TpuFlat(5, IndexParameter(index_type=IndexType.FLAT, dimension=32,
                                     dtype="bfloat16"))
    flat.upsert(ids, x)
    assert flat.store.vecs.dtype == jnp.bfloat16
    want = [list(r.ids) for r in flat.search(x[:4], 5)]
    FLAGS.set("use_pallas_fused_search", True)
    try:
        got = [list(r.ids) for r in flat.search(x[:4], 5)]
    finally:
        FLAGS.set("use_pallas_fused_search", "auto")
    assert want == got

    ivf = TpuIvfFlat(6, IndexParameter(
        index_type=IndexType.IVF_FLAT, dimension=32, ncentroids=8,
        dtype="bfloat16",
    ))
    ivf.upsert(ids, x)
    ivf.train()
    base = [list(r.ids) for r in ivf.search(x[:4], 5, nprobe=8)]
    FLAGS.set("use_pallas_ivf_search", True)
    try:
        fused = [list(r.ids) for r in ivf.search(x[:4], 5, nprobe=8)]
    finally:
        FLAGS.set("use_pallas_ivf_search", False)
    assert base == fused


# --------------------------------------------------------------------------
# The batch-major arm (ops/pallas_ivf.ivf_batch_topk): from ROW_BLOCK queries
# on a request's scan reads each probed bucket once for the whole batch. It
# has to give the query-major kernels' and the XLA rank scan's answers.

def _rel_close(got, want, scale):
    """Distances within 1e-6, relative to |q|^2 + |x|^2 (what the L2
    expansion's rounding scales with; the benchmark's dist_err)."""
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6 * scale)


def _clustered(rng, n, d, nlist, weights=None):
    centers = rng.standard_normal((nlist, d)).astype(np.float32)
    pick = rng.choice(nlist, n, p=weights)
    return centers[pick] + 0.2 * rng.standard_normal((n, d)).astype(
        np.float32)


@pytest.fixture(scope="module")
def tier_indexes():
    """One trained index per (metric, precision), built on first use."""
    rng = np.random.default_rng(11)
    n, d, nlist = 4000, 32, 16
    x = _clustered(rng, n, d, nlist)
    q = x[rng.choice(n, 64, replace=False)] + 0.01
    built = {}

    def get(metric, precision):
        key = (metric, precision)
        if key not in built:
            FLAGS.set("use_pallas_ivf_search", True)
            FLAGS.set("ivf_dim_block", 8)
            try:
                idx = TpuIvfFlat(20 + len(built), IndexParameter(
                    index_type=IndexType.IVF_FLAT, dimension=d,
                    ncentroids=nlist, metric=metric, precision=precision,
                ))
                idx.upsert(np.arange(n, dtype=np.int64), x)
                idx.train()
                idx._ensure_view()      # blocked norms built under the flags
            finally:
                FLAGS.set("use_pallas_ivf_search", False)
                FLAGS.set("ivf_dim_block", 128)
            built[key] = idx
        return built[key]

    return get, x, q


def _arm_counts(idx):
    from dingo_tpu.common.metrics import METRICS

    return {a: METRICS.counter("ivf.scan_arm", region_id=idx.id,
                               labels={"arm": a}).get()
            for a in ("batch", "query", "xla")}


@pytest.mark.parametrize("b", [8, 9, 64])
@pytest.mark.parametrize("precision", ["fp32", "bf16", "sq8"])
@pytest.mark.parametrize(
    "metric", [Metric.L2, Metric.INNER_PRODUCT, Metric.COSINE])
def test_batch_arm_matches_xla_arm(tier_indexes, metric, precision, b):
    """Exact tiers: the XLA rank scan's ids, in its order, at its
    distances. sq8 multiplies in bf16 on both arms but rounds in another
    order, so it is held to the recall the existing sq8 parity tests use.
    sq8 + COSINE has no Pallas arm (the XLA scan divides by the decoded
    norm) and has to stay on it."""
    get, x, q = tier_indexes
    idx = get(metric, precision)
    base = idx.search(q[:b], 10, nprobe=8)
    before = _arm_counts(idx)
    FLAGS.set("use_pallas_ivf_search", True)
    try:
        got = idx.search(q[:b], 10, nprobe=8)
    finally:
        FLAGS.set("use_pallas_ivf_search", False)
    ran = {a: v - before[a] for a, v in _arm_counts(idx).items() if
           v - before[a]}
    if precision == "sq8" and metric is Metric.COSINE:
        assert ran == {"xla": 1}
    else:
        assert ran == {"batch": 1}
    assert len(got) == b
    if precision == "sq8":
        hit = np.mean([len(set(g.ids) & set(w.ids)) / 10
                       for g, w in zip(got, base)])
        assert hit >= 0.99
        return
    scale = float((q[:b] ** 2).sum(1).max() + (x ** 2).sum(1).max())
    for g, w in zip(got, base):
        assert list(g.ids) == list(w.ids)
        _rel_close(np.asarray(g.distances), np.asarray(w.distances), scale)


def _synthetic_buckets(rng, nb=24, cap=64, d=128):
    import jax.numpy as jnp

    rows = rng.standard_normal((nb, cap, d)).astype(np.float32)
    return (jnp.asarray(rows), jnp.asarray((rows ** 2).sum(-1)),
            jnp.asarray(np.arange(nb * cap, dtype=np.int32).reshape(nb, cap)))


def _probe_case(case, rng, nb, cap, b, budget):
    """-> (vprobes [b, budget], valid [nb, cap]) for one corner."""
    valid = np.ones((nb, cap), bool)
    vp = np.stack([rng.choice(nb, budget, replace=False) for _ in range(b)])
    if case == "padded_ranks":          # spill expansion left -1 ranks,
        vp[:, budget - 3:] = -1         # in the middle for some queries
        vp[::2, 1] = -1
    elif case == "fewer_than_k_live":   # 7 live rows in all its probes
        valid[:] = False
        valid[vp[0, 0], :4] = True
        valid[vp[0, 1], 5:8] = True
        vp[0, 2:] = -1
    elif case == "share_every_bucket":  # two queries, one probe set
        vp[1] = vp[0]
        vp[2] = vp[0][::-1]
    elif case == "share_no_bucket":     # disjoint probe sets
        vp[0] = np.arange(budget)
        vp[1] = np.arange(budget, 2 * budget)
    elif case == "filter_mask":         # a filter's validity mask
        valid = rng.random((nb, cap)) > 0.7
    elif case == "no_probe_at_all":     # a padded query row probes nothing
        vp[3] = -1
    return vp.astype(np.int32), valid


@pytest.mark.parametrize("ascending", [True, False])
@pytest.mark.parametrize("case", [
    "padded_ranks", "fewer_than_k_live", "share_every_bucket",
    "share_no_bucket", "filter_mask", "no_probe_at_all"])
def test_batch_kernel_matches_query_major_kernel(case, ascending):
    """Kernel against kernel, on the same arrays: ids, order and scores of
    the query-major list kernel, for the corners of the probe sets; and
    the touched-bucket count is the number of distinct probed buckets."""
    import jax.numpy as jnp

    from dingo_tpu.ops import pallas_ivf

    rng = np.random.default_rng(5)
    nb, cap, d, b, budget, k = 24, 64, 128, 8, 9, 12
    buckets, sqnorm, slot = _synthetic_buckets(rng, nb, cap, d)
    vp, valid = _probe_case(case, rng, nb, cap, b, budget)
    q = jnp.asarray(rng.standard_normal((b, d)).astype(np.float32))
    args = (jnp.asarray(vp), q, buckets, sqnorm, jnp.asarray(valid), slot)
    want_v, want_i = pallas_ivf.ivf_list_topk(
        *args, k=k, ascending=ascending, interpret=True, nq=b)
    got_v, got_i, count = pallas_ivf.ivf_batch_topk(
        *args, None, None, k=k, ascending=ascending, interpret=True)
    assert int(count) == len(np.unique(vp[vp >= 0]))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    want_v, got_v = np.asarray(want_v), np.asarray(got_v)
    assert np.array_equal(np.isneginf(got_v), np.isneginf(want_v))
    live = ~np.isneginf(want_v)
    _rel_close(got_v[live], want_v[live], 2.0 * d)
    if case == "fewer_than_k_live":
        assert (np.asarray(got_i)[0] >= 0).sum() == 7
    if case == "no_probe_at_all":
        assert (np.asarray(got_i)[3] == -1).all()


@pytest.fixture(scope="module")
def spilled_index():
    """Half the rows in one list: the view spills it over several buckets
    (max_spill > 1), so expand_probes pads the other lists' ranks with -1
    and the budget exceeds nprobe."""
    rng = np.random.default_rng(17)
    n, d, nlist = 4000, 32, 16
    w = np.full(nlist, 0.5 / (nlist - 1))
    w[0] = 0.5
    x = _clustered(rng, n, d, nlist, w)
    idx = TpuIvfFlat(40, IndexParameter(
        index_type=IndexType.IVF_FLAT, dimension=d, ncentroids=nlist))
    idx.upsert(np.arange(n, dtype=np.int64), x)
    idx.train()
    idx._ensure_view()
    assert idx._view.max_spill > 1
    q = x[rng.choice(n, 16, replace=False)] + 0.01
    return idx, x, q


@pytest.mark.parametrize("filtered", [False, True])
def test_batch_arm_spilled_view(spilled_index, filtered):
    from dingo_tpu.index.base import FilterSpec

    idx, x, q = spilled_index
    spec = FilterSpec(ranges=[(500, 2500)]) if filtered else None
    base = _results(idx, q, nprobe=6, filter_spec=spec)
    FLAGS.set("use_pallas_ivf_search", True)
    try:
        got = _results(idx, q, nprobe=6, filter_spec=spec)
    finally:
        FLAGS.set("use_pallas_ivf_search", False)
    scale = float((q ** 2).sum(1).max() + (x ** 2).sum(1).max())
    for (bi, bd), (gi, gd) in zip(base, got):
        assert bi == gi
        _rel_close(gd, bd, scale)
        assert not filtered or all(500 <= i < 2500 for i in gi)


def test_batch_arm_races_inplace_appends():
    """Searches of the batch arm while another thread appends in place
    (donated scatters of the bucket arrays): the view snapshot and the
    program's launch share one device_lock hold, so no search sees a
    donated buffer or a view ahead of its arrays; afterwards the arm
    agrees with the XLA scan on the final state."""
    import threading

    rng = np.random.default_rng(23)
    n, d, nlist = 3000, 32, 8
    x = _clustered(rng, n + 600, d, nlist)
    idx = TpuIvfFlat(41, IndexParameter(
        index_type=IndexType.IVF_FLAT, dimension=d, ncentroids=nlist))
    idx.upsert(np.arange(n, dtype=np.int64), x[:n])
    idx.train()
    q = x[:8] + 0.01
    errors, seen = [], []
    FLAGS.set("use_pallas_ivf_search", True)
    try:
        idx.search(q, 10, nprobe=4)             # view built, program warm

        def writer():
            try:
                for lo in range(n, n + 600, 100):
                    idx.upsert(np.arange(lo, lo + 100, dtype=np.int64),
                               x[lo:lo + 100])
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append(e)

        def reader():
            try:
                for _ in range(6):
                    seen.append(idx.search(q, 10, nprobe=4))
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append(e)

        threads = [threading.Thread(target=writer),
                   threading.Thread(target=reader)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        assert idx.view_stats()["inplace_appends"] >= 600
        for res in seen:                        # each query's own row, first
            assert [r.ids[0] for r in res] == list(range(8))
        got = _results(idx, x[n + 500:n + 508], nprobe=nlist)
    finally:
        FLAGS.set("use_pallas_ivf_search", False)
    base = _results(idx, x[n + 500:n + 508], nprobe=nlist)
    for (bi, _), (gi, _) in zip(base, got):
        assert bi == gi
    assert [gi[0] for gi, _ in got] == list(range(n + 500, n + 508))


@pytest.mark.parametrize("b,want", [
    (1, "query"), (4, "query"), (8, "batch"), (64, "batch"),
    (512, "batch"), (1024, "query")])
def test_scan_arm_from_the_request_shape(b, want):
    """Loop order is read off the padded batch (and the VMEM the batch-
    major blocks would need), never a flag."""
    from dingo_tpu.ops.pallas_ivf import scan_arm

    assert scan_arm(b, 256, 768, 4) == want


def test_search_dispatch_is_one_program(monkeypatch):
    """A warm unfiltered search launches exactly one jitted program
    between index.dispatch's start and end, and nothing eager: the
    sentinel's per-kernel call counts grow by `index.ivf.search` alone;
    every array the program is given is the uploaded batch or an array
    the index already holds (the same objects, not results of slices or
    casts); and what the reply's one fetch is handed are the program's
    own outputs (the same objects again). An eager `jnp` call anywhere in
    between would have made a new array."""
    import jax

    from dingo_tpu.index import ivf_flat
    from dingo_tpu.obs.sentinel import SENTINEL

    rng = np.random.default_rng(29)
    n, d, nlist = 3000, 32, 8
    x = _clustered(rng, n, d, nlist)
    idx = TpuIvfFlat(42, IndexParameter(
        index_type=IndexType.IVF_FLAT, dimension=d, ncentroids=nlist))
    idx.upsert(np.arange(n, dtype=np.int64), x)
    idx.train()
    q = x[:8] + 0.01
    real_program, real_fetch = ivf_flat.ivf_search_program, \
        ivf_flat.begin_host_fetch
    seen = {}

    def program(*args, **kw):
        seen["in"] = args
        seen["out"] = real_program(*args, **kw)
        return seen["out"]

    def fetch(*arrays):
        seen["fetch"] = arrays
        return real_fetch(*arrays)

    FLAGS.set("use_pallas_ivf_search", True)
    try:
        idx.search(q, 10, nprobe=4)              # warm
        monkeypatch.setattr(ivf_flat, "ivf_search_program", program)
        monkeypatch.setattr(ivf_flat, "begin_host_fetch", fetch)
        calls0 = {k: v["calls"] for k, v in SENTINEL.state().items()}
        idx.search_async(q, 10, nprobe=4)()
        calls1 = {k: v["calls"] for k, v in SENTINEL.state().items()}
    finally:
        FLAGS.set("use_pallas_ivf_search", False)
    grew = {k: v - calls0.get(k, 0) for k, v in calls1.items()
            if v - calls0.get(k, 0)}
    assert grew == {"index.ivf.search": 1}
    view, store = idx._view, idx.store
    resident = [idx.centroids, idx._c_sqnorm, view.probe_table,
                view.bucket_valid, view.bucket_slot, idx._buckets,
                idx._bucket_sqnorm, idx._bucket_bsq]
    qpad, *held = seen["in"]
    assert isinstance(qpad, jax.Array) and qpad.shape == (8, d)
    for arr in held:
        assert arr is None or any(arr is r for r in resident)
    dists, slots, _probes, _vprobes, aux = seen["out"]
    assert len(seen["fetch"]) == 5
    assert seen["fetch"][0] is dists and seen["fetch"][1] is slots
    assert seen["fetch"][2] is aux and aux is not None
