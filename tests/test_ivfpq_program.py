"""IVF_PQ's served search against a plain numpy IVF-PQ (PR 37).

The reference below imports nothing of the search path: it takes the
index's trained coarse centroids and PQ codebooks (the model's weights, as
it were) and recomputes in float64 what faiss IndexIVFPQ with residual codes
and an exact refine does — coarse distances, residual codes, the
per-(query, list) lookup tables, ADC distances over the probed lists, the
top-k' shortlist and the exact rerank. The served path is the request's
one device program, ``index.ivfpq.search``, whose ADC scores come from the
XLA rank scan or, on the TPU, from the Pallas scoring kernel (interpreted
here where a test forces it).
"""

import jax
import numpy as np
import pytest

from dingo_tpu.common.config import FLAGS
from dingo_tpu.common.metrics import METRICS
from dingo_tpu.index.base import FilterSpec, IndexParameter, IndexType
from dingo_tpu.index.ivf_pq import TpuIvfPq

N, D, NLIST, M, K = 4096, 64, 64, 8, 10
FACTOR = 8                       # the shipped ivfpq_rerank_factor


def _ids(n=N):
    return np.arange(n, dtype=np.int64) * 3 + 5


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(37)
    centers = rng.standard_normal((32, D)).astype(np.float32) * 4
    x = (centers[rng.integers(0, 32, N)]
         + rng.standard_normal((N, D))).astype(np.float32)
    q = (x[rng.choice(N, 16, replace=False)]
         + 0.05 * rng.standard_normal((16, D))).astype(np.float32)
    return x, q


def _index(x, train=True, index_id=7):
    idx = TpuIvfPq(index_id, IndexParameter(
        index_type=IndexType.IVF_PQ, dimension=D, ncentroids=NLIST,
        nsubvector=M, nbits_per_idx=8))
    idx.upsert(_ids(len(x)), x)
    if train:
        idx.train()
    return idx


@pytest.fixture(scope="module")
def trained(data):
    return _index(data[0])


@pytest.fixture(params=["xla", "pallas"])
def arm(request):
    """The ADC arm a search takes: the XLA rank scan, or the Pallas scoring
    kernel forced on (interpret mode off the TPU)."""
    FLAGS.set("use_pallas_ivf_search", request.param == "pallas")
    try:
        yield request.param
    finally:
        FLAGS.set("use_pallas_ivf_search", "auto")


# ------------------------------------------------------------ the reference
class PlainIvfPq:
    """faiss IndexIVFPQ (by_residual) + an exact refine, in float64 numpy,
    over the index's own centroids and codebooks."""

    def __init__(self, idx, x):
        self.c = np.asarray(idx.centroids, np.float64)      # [nlist, d]
        self.cb = np.asarray(idx.codebooks, np.float64)     # [m, ksub, dsub]
        self.x = x.astype(np.float64)
        self.assign = np.argmin(self._l2(self.x, self.c), axis=1)
        resid = self.x - self.c[self.assign]
        self.dsub = D // M
        self.codes = np.stack([
            np.argmin(self._l2(self._sub(resid, j), self.cb[j]), axis=1)
            for j in range(M)], axis=1)                     # [n, m]

    @staticmethod
    def _l2(a, b):
        return ((a[:, None, :] - b[None, :, :]) ** 2).sum(-1)

    def _sub(self, a, j):
        return a[:, j * self.dsub:(j + 1) * self.dsub]

    def probes(self, q, nprobe):
        return np.argsort(self._l2(q.astype(np.float64), self.c), axis=1,
                          kind="stable")[:, :nprobe]

    def adc(self, q, nprobe, live):
        """-> per query (rows, ADC distances) over the live rows of its
        probed lists."""
        out = []
        for qi, lists in zip(q.astype(np.float64), self.probes(q, nprobe)):
            rows, dist = [], []
            for lst in lists:
                r = np.flatnonzero((self.assign == lst) & live)
                resid = (qi - self.c[lst])[None, :]
                lut = np.stack([self._l2(self._sub(resid, j), self.cb[j])[0]
                                for j in range(M)])          # [m, ksub]
                rows.append(r)
                dist.append(lut[np.arange(M)[None, :], self.codes[r]].sum(1))
            out.append((np.concatenate(rows), np.concatenate(dist)))
        return out

    def search(self, q, k, nprobe, live, kprime=None):
        """ADC top-kprime, then (if kprime) the exact rerank to k:
        -> (row indices [b, k], distances [b, k])."""
        ids, dists = [], []
        for qi, (rows, dist) in zip(q.astype(np.float64),
                                    self.adc(q, nprobe, live)):
            keep = np.argsort(dist, kind="stable")[:kprime or k]
            rows, dist = rows[keep], dist[keep]
            if kprime:
                dist = ((self.x[rows] - qi) ** 2).sum(1)
                keep = np.argsort(dist, kind="stable")[:k]
                rows, dist = rows[keep], dist[keep]
            ids.append(rows)
            dists.append(dist)
        return ids, dists


def _rows_of(results):
    """Served ids -> row indices (ids are row * 3 + 5)."""
    return [(np.asarray(r.ids) - 5) // 3 for r in results]


def _assert_no_probe_dropped(idx, q, nprobe):
    """The reference scans whole lists; the served probe expansion keeps a
    budget of buckets and drops the lowest-ranked lists' spill buckets past
    it. On this data it drops none (else the comparison would not be of
    like with like)."""
    from dingo_tpu.index.ivf_layout import expand_probes_ranked

    idx._ensure_view()
    ref = PlainIvfPq(idx, np.zeros((1, D), np.float32))
    table = np.asarray(idx._view.probe_table)
    vp, _ = expand_probes_ranked(ref.probes(q, nprobe).astype(np.int32),
                                 idx._view.probe_table, nprobe,
                                 table.shape[1])
    wanted = (table[ref.probes(q, nprobe)] >= 0).sum(axis=(1, 2))
    assert ((np.asarray(vp) >= 0).sum(axis=1) == wanted).all()


def _close(served, ref_d):
    # ADC: float32 tables in the expanded form ||r||^2 - 2 r.c + ||c||^2
    # against float64 direct differences; the rerank: float32 HIGHEST
    # against float64. Both agree to ~1e-6 of the distances' scale here;
    # 1e-4 of it leaves room and still separates neighbours (the 10th and
    # 11th distances of these queries differ by 1e-2 of it or more)
    scale = max(1.0, float(np.abs(ref_d).max()))
    np.testing.assert_allclose(np.asarray(served, np.float64), ref_d,
                               rtol=0, atol=1e-4 * scale)


@pytest.mark.parametrize("nprobe", [8, NLIST])
@pytest.mark.parametrize("factor", [1, FACTOR])
def test_served_matches_plain_ivfpq(data, trained, nprobe, factor, arm):
    """ids and distances: the ADC top-k (factor 1: no rerank, the wire
    distances are the ADC ones) and the ADC top-k' reranked exactly to k
    (the shipped factor), on either ADC arm."""
    x, q = data
    _assert_no_probe_dropped(trained, q, nprobe)
    ref = PlainIvfPq(trained, x)
    live = np.ones(N, bool)
    key = f"ivfpq.scan_arm{{region=7,arm={arm}}}"
    before = METRICS.dump().get(key, 0)
    FLAGS.set("ivfpq_rerank_factor", factor)
    try:
        got = trained.search(q, K, nprobe=nprobe)
    finally:
        FLAGS.set("ivfpq_rerank_factor", FACTOR)
    assert METRICS.dump()[key] == before + 1
    want_rows, want_d = ref.search(q, K, nprobe, live,
                                   kprime=K * factor if factor > 1 else None)
    for r, rows, dist in zip(got, want_rows, want_d):
        assert list((np.asarray(r.ids) - 5) // 3) == list(rows)
        _close(r.distances, dist)
    if factor > 1 and nprobe == NLIST:
        # every list probed: where the ADC shortlist of k' = 80 holds the
        # true top 10 the reply is the exact top-k (it does for most of
        # these queries; PQ at m 8 over 64-d misranks some 10th places)
        exact = np.argsort(
            ((x[None].astype(np.float64) - q[:, None]) ** 2).sum(-1),
            axis=1, kind="stable")[:, :K]
        short = ref.search(q, K * factor, nprobe, live)[0]
        covered = [set(e) <= set(s) for e, s in zip(exact, short)]
        assert sum(covered) >= 12, covered
        for r, e, c in zip(_rows_of(got), exact, covered):
            if c:
                assert list(r) == list(e)


@pytest.mark.parametrize("case", ["filter", "deletes", "filter_and_deletes"])
def test_filters_and_tombstones_match_plain_ivfpq(data, case):
    """A filter's bucket mask and deleted rows (tombstoned in the view) are
    honoured inside the program as the reference honours them."""
    x, q = data
    idx = _index(x, index_id=11)
    ref = PlainIvfPq(idx, x)
    live = np.ones(N, bool)
    spec = None
    if case != "deletes":
        lo, hi = 1000, 8000                 # ids: rows 332..2664
        spec = FilterSpec(ranges=[(lo, hi)])
        ids = _ids()
        live &= (ids >= lo) & (ids < hi)
    if case != "filter":
        # every query's own nearest rows among them, so the deletes bite
        near = ref.search(q, 3, NLIST, np.ones(N, bool))[0]
        dead = np.unique(np.concatenate(near + [np.arange(0, N, 5)]))
        idx.delete(_ids()[dead])
        live[dead] = False
    nprobe = 16
    _assert_no_probe_dropped(idx, q, nprobe)
    got = idx.search(q, K, filter_spec=spec, nprobe=nprobe)
    want_rows, want_d = ref.search(q, K, nprobe, live, kprime=K * FACTOR)
    for r, rows, dist in zip(got, want_rows, want_d):
        assert live[(np.asarray(r.ids) - 5) // 3].all()
        assert list((np.asarray(r.ids) - 5) // 3) == list(rows)
        _close(r.distances, dist)


@pytest.mark.parametrize("calls", [1, 3])
def test_pallas_scores_shortlist_as_the_xla_scan(data, calls, monkeypatch):
    """The Pallas scoring kernel (interpreted) and `_ivfpq_scan_kernel`,
    each through the shared top-k: a hot list's spill buckets sharing one
    table, ranks of -1, tombstoned and filtered-out slots, a batch of 5 and
    a k' past the live codes. The shortlists hold the same slots, their
    scores within 1e-6 of each other. `calls` 3: the batch's queries split
    over three kernel calls, as a batch whose probe ids overflow SMEM is."""
    import jax.numpy as jnp

    from dingo_tpu.index.ivf_flat import coarse_probes
    from dingo_tpu.index.ivf_layout import expand_probes_ranked
    from dingo_tpu.index.ivf_pq import (
        _adc_shortlist, _ivfpq_adc_lut, _ivfpq_scan_kernel)
    from dingo_tpu.ops import pallas_pq

    x, q = data
    rng = np.random.default_rng(38)
    hot = (x[:1] + 0.3 * rng.standard_normal((N // 2, D))).astype(np.float32)
    idx = _index(np.concatenate([x[:N // 2], hot]), index_id=17)
    idx.delete(_ids()[::7])
    spec = FilterSpec(ranges=[(0, 9000)])
    idx._ensure_view()
    view = idx._view
    valid = idx._bucket_valid_for_filter(spec, idx._prep_filter_mask(spec))
    qb = jnp.asarray(np.concatenate([q[:3], hot[:2] + 0.01]))
    b, nprobe = 5, 8
    probes = coarse_probes(qb, idx.centroids, idx._c_sqnorm, nprobe)
    vp, cp = expand_probes_ranked(probes, view.probe_table, nprobe,
                                  view.probe_table.shape[1])
    vp_h, cp_h = np.asarray(vp), np.asarray(cp)
    assert (vp_h < 0).any()
    shared = (cp_h[:, 1:] == cp_h[:, :-1]) & (vp_h[:, 1:] >= 0)
    assert shared.any()                 # spill buckets of one probed list
    width = -(-vp_h.shape[1] // pallas_pq.OUT_ROWS) * pallas_pq.OUT_ROWS
    monkeypatch.setattr(pallas_pq, "PREFETCH_STEPS", width * -(-b // calls))
    scores = pallas_pq.ivf_pq_adc_scores.__wrapped__(
        vp, cp, _ivfpq_adc_lut(qb, idx.centroids, probes, idx.codebooks),
        idx._code_buckets, valid, interpret=True)
    assert np.isneginf(np.asarray(scores)[vp_h < 0]).all()
    slots = jnp.take(view.bucket_slot, jnp.maximum(vp, 0), axis=0)
    live = int(np.isfinite(np.asarray(scores)).sum(axis=(1, 2)).max())
    for kscan in (40, live + 64):
        got_d, got_s = _adc_shortlist(scores.reshape(b, -1),
                                      slots.reshape(b, -1), kscan)
        want_d, want_s = _ivfpq_scan_kernel(
            idx._code_buckets, valid, view.bucket_slot, view.bucket_coarse,
            probes, vp, cp, qb, idx.centroids, idx.codebooks, kscan, True)
        got_s, want_s = np.asarray(got_s), np.asarray(want_s)
        for row in range(b):
            g = dict(zip(got_s[row], np.asarray(got_d[row])))
            w = dict(zip(want_s[row], np.asarray(want_d[row])))
            assert set(g) == set(w)
            assert (got_s[row] < 0).sum() == (want_s[row] < 0).sum()
            ok = [s for s in g if s >= 0]
            np.testing.assert_allclose([g[s] for s in ok],
                                       [w[s] for s in ok], rtol=1e-6)
        assert (got_s < 0).any() == (kscan > live)


@pytest.mark.parametrize("filtered", [False, True])
def test_untrained_hybrid_arm_is_exact(data, filtered):
    """Until trained the index serves an exact flat scan
    (vector_index_ivf_pq.h:113-115), whatever nprobe asks, and counts it
    as the `exact` arm."""
    x, q = data
    idx = _index(x[:1024], train=False, index_id=13)
    spec = FilterSpec(ranges=[(0, 2000)]) if filtered else None
    live = _ids(1024) < 2000 if filtered else np.ones(1024, bool)
    before = METRICS.dump().get("ivfpq.scan_arm{region=13,arm=exact}", 0)
    got = idx.search(q, K, filter_spec=spec, nprobe=4)
    d = ((x[None, :1024].astype(np.float64) - q[:, None]) ** 2).sum(-1)
    d[:, ~live] = np.inf
    exact = np.argsort(d, axis=1, kind="stable")[:, :K]
    for r, e, row in zip(got, exact, d):
        assert list((np.asarray(r.ids) - 5) // 3) == list(e)
        _close(r.distances, row[e])
    after = METRICS.dump().get("ivfpq.scan_arm{region=13,arm=exact}", 0)
    assert after == before + 1


def test_save_load_replies_identical(data, trained, tmp_path):
    """A restored snapshot (codebooks, centroids, rows re-encoded on load)
    answers bit for bit as the index it was saved from."""
    x, q = data
    trained.save(str(tmp_path))
    back = TpuIvfPq(7, trained.parameter)
    back.load(str(tmp_path))
    for nprobe in (8, NLIST):
        a = trained.search(q, K, nprobe=nprobe)
        b = back.search(q, K, nprobe=nprobe)
        for ra, rb in zip(a, b):
            assert np.array_equal(ra.ids, rb.ids)
            assert np.array_equal(np.asarray(ra.distances),
                                  np.asarray(rb.distances))


@pytest.mark.parametrize("batch", [1, 8, 64])
def test_one_program_a_request(data, trained, batch, monkeypatch):
    """Across the warmed ladder a search launches exactly one jitted
    program, `index.ivfpq.search`, and compiles nothing: what the program
    is given is the uploaded batch or arrays the index already holds, and
    what the reply's one fetch is handed are the program's own outputs."""
    from dingo_tpu.index import ivf_pq
    from dingo_tpu.obs.sentinel import SENTINEL
    from dingo_tpu.ops import topk as topk_mod

    x, _q = data
    q = x[:batch] + 0.01
    real_program = ivf_pq.ivfpq_search_program
    real_fetch = topk_mod.begin_host_fetch
    seen = {}

    def program(*args, **kw):
        seen["in"] = args
        seen["out"] = real_program(*args, **kw)
        return seen["out"]

    def fetch(*arrays):
        seen["fetch"] = arrays
        return real_fetch(*arrays)

    trained.search(q, K, nprobe=32)                 # warm this shape
    monkeypatch.setattr(ivf_pq, "ivfpq_search_program", program)
    monkeypatch.setattr(topk_mod, "begin_host_fetch", fetch)
    calls0 = {k: v["calls"] for k, v in SENTINEL.state().items()}
    compiles0 = SENTINEL.recompiles()
    arm0 = METRICS.dump().get("ivfpq.scan_arm{region=7,arm=xla}", 0)
    trained.search_async(q, K, nprobe=32)()
    calls1 = {k: v["calls"] for k, v in SENTINEL.state().items()}
    grew = {k: v - calls0.get(k, 0) for k, v in calls1.items()
            if v - calls0.get(k, 0)}
    assert grew == {"index.ivfpq.search": 1}
    assert SENTINEL.recompiles() == compiles0
    assert METRICS.dump()["ivfpq.scan_arm{region=7,arm=xla}"] == arm0 + 1
    view, store = trained._view, trained.store
    resident = [trained.centroids, trained._c_sqnorm, view.probe_table,
                view.bucket_valid, view.bucket_slot, view.bucket_coarse,
                trained._code_buckets, trained.codebooks, store.vecs,
                store.sqnorm]
    qpad, *held = seen["in"]
    assert isinstance(qpad, jax.Array) and qpad.shape[0] >= batch
    for arr in held:
        assert any(arr is r for r in resident)
    dists, slots, _probes, _vprobes = seen["out"]
    assert seen["fetch"][0] is dists and seen["fetch"][1] is slots
