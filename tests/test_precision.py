"""Precision tiers (ISSUE 4): bf16/sq8 recall parity vs fp32, SQ codec
persistence, device-resident rerank correctness vs the host rerank, and
the capacity win (device bytes/vector) the tiers exist for.

Scales are test-sized. The pyproject filterwarnings gate
("Some donated buffers were not usable" -> error) rides along on every
device write these tests trigger.
"""

import tempfile

import numpy as np
import pytest

import jax.numpy as jnp

from dingo_tpu.common.config import FLAGS
from dingo_tpu.index.base import (
    FilterSpec,
    IndexParameter,
    IndexType,
    InvalidParameter,
    Metric,
    resolve_precision,
)
from dingo_tpu.index.flat import TpuFlat
from dingo_tpu.index.ivf_flat import TpuIvfFlat
from dingo_tpu.index.ivf_pq import TpuIvfPq, _exact_rerank_host
from dingo_tpu.index.rerank_cache import DeviceRerankCache
from dingo_tpu.index.slot_store import HostSlotStore, SlotStore, SqSlotStore
from dingo_tpu.ops.rerank import cached_rerank_device, exact_rerank_device
from dingo_tpu.ops.sq import SqParams, params_close, sq_decode, sq_encode, sq_train

N, D, K = 6000, 64, 10


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(42)
    centers = rng.standard_normal((64, D), dtype=np.float32)
    x = centers[rng.integers(0, 64, N)] + 0.3 * rng.standard_normal(
        (N, D)
    ).astype(np.float32)
    ids = np.arange(N, dtype=np.int64)
    q = x[:16] + 0.02 * rng.standard_normal((16, D)).astype(np.float32)
    gt = np.argsort(((q[:, None, :] - x[None, :, :]) ** 2).sum(-1), 1)[:, :K]
    return ids, x, q, gt


def _recall(res, gt):
    return float(np.mean(
        [len(set(r.ids) & set(g)) / K for r, g in zip(res, gt)]
    ))


@pytest.fixture
def no_cache():
    FLAGS.set("rerank_cache_rows", 0)
    yield
    FLAGS.set("rerank_cache_rows", 0)


@pytest.fixture
def with_cache():
    FLAGS.set("rerank_cache_rows", 8192)
    FLAGS.set("rerank_cache_dtype", "float32")
    yield
    FLAGS.set("rerank_cache_rows", 0)


def _flat(precision, idx_id=1, metric=Metric.L2):
    return TpuFlat(idx_id, IndexParameter(
        index_type=IndexType.FLAT, dimension=D, metric=metric,
        precision=precision,
    ))


def _ivf(precision, idx_id=1, nlist=32):
    return TpuIvfFlat(idx_id, IndexParameter(
        index_type=IndexType.IVF_FLAT, dimension=D, ncentroids=nlist,
        default_nprobe=16, precision=precision,
    ))


# ---------------------------------------------------------------- codec --

def test_sq_codec_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2000, D)).astype(np.float32)
    params = sq_train(x)
    codes = sq_encode(x, params)
    assert codes.dtype == np.uint8
    err = np.abs(sq_decode(codes, params) - x)
    # per-dim error bound: half a quantization step
    assert (err <= params.scale[None, :] * 0.5 + 1e-6).all()


def test_sq_out_of_range_clips_not_wraps():
    params = SqParams(np.zeros(D, np.float32), np.full(D, 1 / 255, np.float32))
    hot = np.full((1, D), 9.0, np.float32)     # far above the range
    cold = np.full((1, D), -9.0, np.float32)
    assert (sq_encode(hot, params) == 255).all()
    assert (sq_encode(cold, params) == 0).all()


def test_resolve_precision_aliases_and_legacy_dtype():
    p = IndexParameter(index_type=IndexType.FLAT, dimension=D)
    assert resolve_precision(p) == "fp32"
    assert resolve_precision(
        IndexParameter(dimension=D, precision="bfloat16")) == "bf16"
    # legacy configs set dtype=bfloat16 directly (bench rounds 1-5)
    assert resolve_precision(
        IndexParameter(dimension=D, dtype="bfloat16")) == "bf16"
    with pytest.raises(InvalidParameter):
        resolve_precision(IndexParameter(dimension=D, precision="fp8"))


# ---------------------------------------------------- recall parity gates --

def test_flat_recall_parity(corpus, no_cache):
    ids, x, q, gt = corpus
    recalls = {}
    for tier in ("fp32", "bf16", "sq8"):
        idx = _flat(tier)
        idx.upsert(ids, x)
        recalls[tier] = _recall(idx.search(q, K), gt)
    assert recalls["fp32"] >= 0.999
    assert recalls["bf16"] >= recalls["fp32"] - 0.05
    assert recalls["sq8"] >= recalls["fp32"] - 0.05
    assert recalls["sq8"] >= 0.95 and recalls["bf16"] >= 0.95


def test_ivf_recall_parity(corpus, no_cache):
    ids, x, q, gt = corpus
    recalls = {}
    for tier in ("fp32", "bf16", "sq8"):
        idx = _ivf(tier)
        idx.upsert(ids, x)
        idx.train()
        recalls[tier] = _recall(idx.search(q, K), gt)
    assert recalls["bf16"] >= recalls["fp32"] - 0.05
    assert recalls["sq8"] >= recalls["fp32"] - 0.05


def test_sq8_rerank_restores_exact_recall(corpus, with_cache):
    ids, x, q, gt = corpus
    idx = _flat("sq8")
    idx.upsert(ids, x)
    assert len(idx._rerank_cache) == N      # cache covers every row
    # shortlist k*factor reranked exactly from fp32 rows -> exact top-k
    assert _recall(idx.search(q, K), gt) == 1.0


def test_cosine_tier_parity(corpus, no_cache):
    ids, x, q, gt_l2 = corpus
    res = {}
    for tier in ("fp32", "sq8"):
        idx = _flat(tier, metric=Metric.COSINE)
        idx.upsert(ids, x)
        res[tier] = idx.search(q, K)
    overlap = np.mean([
        len(set(a.ids) & set(b.ids)) / K
        for a, b in zip(res["fp32"], res["sq8"])
    ])
    assert overlap >= 0.9


# --------------------------------------------------- capacity (HBM) gates --

def test_sq8_device_bytes_at_least_3p5x_smaller(corpus, no_cache):
    ids, x, _, _ = corpus
    sizes = {}
    for tier in ("fp32", "sq8"):
        idx = _ivf(tier, idx_id=5)
        idx.upsert(ids, x)
        idx.train()
        idx.search(x[:4], K)     # materialize the bucketed view
        sizes[tier] = idx.get_device_memory_size()
    assert sizes["fp32"] / sizes["sq8"] >= 3.5, sizes


def test_bf16_device_bytes_about_half(corpus, no_cache):
    ids, x, _, _ = corpus
    sizes = {}
    for tier in ("fp32", "bf16"):
        idx = _flat(tier, idx_id=6)
        idx.upsert(ids, x)
        sizes[tier] = idx.get_device_memory_size()
    assert sizes["fp32"] / sizes["bf16"] >= 1.8, sizes


# ------------------------------------------------------------ persistence --

def test_sq_params_persist_flat(corpus, no_cache, tmp_path):
    ids, x, q, _ = corpus
    idx = _flat("sq8")
    idx.upsert(ids, x)
    idx.save(str(tmp_path))
    idx2 = _flat("sq8", idx_id=2)
    idx2.load(str(tmp_path))
    assert params_close(idx.store.sq_params, idx2.store.sq_params)
    a, b = idx.search(q, K), idx2.search(q, K)
    for ai, bi in zip(a, b):
        np.testing.assert_array_equal(ai.ids, bi.ids)
        np.testing.assert_allclose(ai.distances, bi.distances, rtol=1e-6)


def test_sq_params_persist_ivf_snapshot(corpus, no_cache, tmp_path):
    ids, x, q, _ = corpus
    idx = _ivf("sq8", idx_id=7)
    idx.upsert(ids, x)
    idx.train()
    before = idx.search(q, K)
    idx.save(str(tmp_path))
    idx2 = _ivf("sq8", idx_id=8)
    idx2.load(str(tmp_path))
    assert params_close(idx.store.sq_params, idx2.store.sq_params)
    after = idx2.search(q, K)
    for ai, bi in zip(before, after):
        np.testing.assert_array_equal(ai.ids, bi.ids)


def test_empty_untrained_sq8_saves_and_reloads(no_cache, tmp_path):
    """Snapshotting an sq8 region that never saw a write must not crash
    on the missing codec params (code-review finding: to_host decoded
    unconditionally)."""
    idx = _flat("sq8", idx_id=30)
    idx.save(str(tmp_path))
    idx2 = _flat("sq8", idx_id=31)
    idx2.load(str(tmp_path))
    assert idx2.get_count() == 0
    assert idx2.search(np.zeros((1, D), np.float32), K)[0].ids.size == 0


def test_legacy_snapshot_without_precision_key_loads(corpus, no_cache,
                                                     tmp_path):
    """Pre-tier snapshots carry no 'precision' meta; a legacy
    dtype=bfloat16 index (tier bf16) must still load them, and an
    fp32<->bf16 tier flip must load (shared f32-on-disk row format) while
    crossing into sq8 stays a hard error."""
    import json as _json
    import os as _os

    ids, x, q, _ = corpus
    idx = _flat("fp32", idx_id=32)
    idx.upsert(ids[:200], x[:200])
    idx.save(str(tmp_path))
    meta_path = _os.path.join(str(tmp_path), "meta.json")
    with open(meta_path) as f:
        meta = _json.load(f)
    del meta["precision"]                 # simulate a pre-upgrade snapshot
    with open(meta_path, "w") as f:
        _json.dump(meta, f)
    legacy = TpuFlat(33, IndexParameter(
        index_type=IndexType.FLAT, dimension=D, dtype="bfloat16",
    ))
    legacy.load(str(tmp_path))            # must not raise
    assert legacy.get_count() == 200
    # explicit fp32 meta + bf16 index: tier flip, same container — loads
    meta["precision"] = "fp32"
    with open(meta_path, "w") as f:
        _json.dump(meta, f)
    flip = _flat("bf16", idx_id=34)
    flip.load(str(tmp_path))
    assert flip.get_count() == 200
    # crossing into sq8 is a container change — still rejected
    with open(meta_path) as f:
        meta = _json.load(f)
    meta["precision"] = "sq8"
    with open(meta_path, "w") as f:
        _json.dump(meta, f)
    with pytest.raises(InvalidParameter):
        _flat("fp32", idx_id=35).load(str(tmp_path))


def test_precision_mismatch_rejected(corpus, no_cache, tmp_path):
    ids, x, _, _ = corpus
    idx = _flat("sq8")
    idx.upsert(ids[:100], x[:100])
    idx.save(str(tmp_path))
    with pytest.raises(InvalidParameter):
        _flat("fp32", idx_id=3).load(str(tmp_path))


# ----------------------------------------------------- rerank correctness --

def test_device_rerank_matches_host_rerank(corpus):
    """exact_rerank_device == _exact_rerank_host on identical rows and
    candidates (the satellite gate: the device stage may remove the host
    gather, not change the answer)."""
    ids, x, q, _ = corpus
    dev = SlotStore(D)
    host = HostSlotStore(D)
    dev.put(ids, x)
    host.put(ids, x)
    rng = np.random.default_rng(1)
    cand = rng.integers(0, N, size=(len(q), 40)).astype(np.int64)
    cand[:, -3:] = -1                      # padding must stay padding
    for metric in (Metric.L2, Metric.INNER_PRODUCT):
        d_dev, s_dev = exact_rerank_device(
            dev.vecs, dev.sqnorm, jnp.asarray(q), jnp.asarray(cand),
            k=K, metric=metric,
        )
        d_host, s_host = _exact_rerank_host(host, q, cand, K, metric)
        np.testing.assert_array_equal(
            np.asarray(s_dev), np.asarray(s_host))
        np.testing.assert_allclose(
            np.asarray(d_dev), np.asarray(d_host), rtol=1e-5, atol=1e-4)


def test_cached_rerank_full_cache_matches_exact(corpus):
    ids, x, q, _ = corpus
    store = SlotStore(D)
    slots = store.put(ids, x)
    cache = DeviceRerankCache(D, max_rows=N, device_lock=store.device_lock)
    cache.offer(slots, x)
    rng = np.random.default_rng(2)
    cand = rng.integers(0, N, size=(len(q), 40)).astype(np.int64)
    quant = rng.standard_normal((len(q), 40)).astype(np.float32)
    d_ref, s_ref = exact_rerank_device(
        store.vecs, store.sqnorm, jnp.asarray(q), jnp.asarray(cand),
        k=K, metric=Metric.L2,
    )
    d_c, s_c = cached_rerank_device(
        cache.vecs, cache.sqnorm, cache.device_map(store.capacity),
        jnp.asarray(quant), jnp.asarray(cand), jnp.asarray(q),
        k=K, metric=Metric.L2,
    )
    np.testing.assert_array_equal(np.asarray(s_ref), np.asarray(s_c))
    np.testing.assert_allclose(np.asarray(d_ref), np.asarray(d_c),
                               rtol=1e-5, atol=1e-4)


def test_cached_rerank_partial_cache_keeps_quantized_scores(corpus):
    """A candidate missing from the cache must keep its quantized score,
    never drop out of the shortlist."""
    ids, x, q, _ = corpus
    store = SlotStore(D)
    slots = store.put(ids, x)
    cache = DeviceRerankCache(D, max_rows=16, device_lock=store.device_lock)
    cache.offer(slots[:16], x[:16])
    cand = np.tile(np.arange(30, dtype=np.int64), (len(q), 1))
    # give uncached candidate #25 an unbeatable quantized (wire-L2) score
    quant = np.full((len(q), 30), 1e6, np.float32)
    quant[:, 25] = 0.0
    d_c, s_c = cached_rerank_device(
        cache.vecs, cache.sqnorm, cache.device_map(store.capacity),
        jnp.asarray(quant), jnp.asarray(cand), jnp.asarray(q),
        k=K, metric=Metric.L2,
    )
    assert (np.asarray(s_c)[:, 0] == 25).all()


def test_rerank_cache_eviction_and_overwrite(corpus):
    ids, x, _, _ = corpus
    store = SlotStore(D)
    slots = store.put(ids[:100], x[:100])
    cache = DeviceRerankCache(D, max_rows=32, device_lock=store.device_lock)
    assert cache.offer(slots, x[:100]) == 32          # bounded admit
    assert len(cache) == 32
    # overwrite of a cached slot always lands, even when full
    new_row = x[200:201]
    assert cache.offer(slots[:1], new_row) == 1
    found, row = cache.inner.gather(slots[:1])
    np.testing.assert_allclose(row[0], new_row[0], rtol=1e-6)
    # invalidation frees room
    cache.invalidate(slots[:8])
    assert len(cache) == 24
    assert cache.offer(slots[40:60], x[40:60]) > 0


def test_ivfpq_device_store_reranks_on_device(corpus, no_cache):
    """Device-resident IVF_PQ now reranks its ADC shortlist from
    store.vecs on device; recall must beat the ADC-only ranking."""
    ids, x, q, gt = corpus
    param = IndexParameter(
        index_type=IndexType.IVF_PQ, dimension=D, ncentroids=16,
        nsubvector=8, default_nprobe=16,
    )
    FLAGS.set("ivfpq_rerank_factor", 8)
    idx = TpuIvfPq(11, param)
    idx.upsert(ids, x)
    idx.train()
    r_rerank = _recall(idx.search(q, K), gt)
    FLAGS.set("ivfpq_rerank_factor", 1)
    try:
        r_adc = _recall(idx.search(q, K), gt)
    finally:
        FLAGS.set("ivfpq_rerank_factor", 8)
    assert r_rerank >= r_adc
    assert r_rerank >= 0.9


# --------------------------------------------------------------- plumbing --

def test_search_by_precision_counter(corpus, no_cache):
    from dingo_tpu.common.metrics import METRICS

    ids, x, q, _ = corpus
    idx = _flat("sq8", idx_id=77)
    idx.upsert(ids[:100], x[:100])
    c = METRICS.counter("vector.search_by_precision", region_id=77,
                        labels={"precision": "sq8"})
    before = c.get()
    idx.search(q, K)
    assert c.get() == before + 1


def test_sq8_rejected_for_ivfpq_and_sharded():
    with pytest.raises(InvalidParameter):
        TpuIvfPq(12, IndexParameter(
            index_type=IndexType.IVF_PQ, dimension=D, nsubvector=8,
            precision="sq8",
        ))


def test_conf_template_precision_keys_in_sync():
    """conf/store.template.conf carries the precision-tier keys, each maps
    to a defined flag, and the template's value equals the flag default
    (the satellite's 'kept in sync with common/config.py defaults')."""
    from dingo_tpu.common.config import Config

    cfg = Config.load("conf/store.template.conf")
    for key, want in (
        ("vector.precision", "fp32"),
        ("rerank.cache_rows", 0),
        ("rerank.cache_dtype", "float32"),
        ("quantized.rerank_factor", 4),
    ):
        assert cfg.get(key) == want, key
        flag = key.replace(".", "_")
        assert FLAGS._flags[flag].default == want, flag


def test_sharded_flat_bf16_parity(corpus):
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    from dingo_tpu.parallel.sharded_flat import TpuShardedFlat

    ids, x, q, gt = corpus
    idx = TpuShardedFlat(21, IndexParameter(
        index_type=IndexType.FLAT, dimension=D, precision="bf16",
    ))
    idx.upsert(ids, x)
    assert idx._store.vecs.dtype == jnp.bfloat16
    assert _recall(idx.search(q, K), gt) >= 0.95
