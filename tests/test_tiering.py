"""Memory-tier ladder (index/tiering.py, ISSUE 19).

Round-trip parity is the load-bearing claim: demote -> serve -> promote
must return BYTE-identical top-k to a never-demoted region at equal
state, per index family x precision. That holds because every rung move
is either a deterministic engine rebuild (same WAL order -> same slot
layout -> same kernel tie-breaks) or a byte-exact code transcription,
and the digest gate refuses any destination copy whose recomputed rows
artifact disagrees with the source ledger before the swap.

The process-kill-mid-transition story lives in tools/chaos.py
(tier_kill scenario, auto-parametrized by test_chaos.py).
"""

import numpy as np
import pytest

from dingo_tpu.index.base import IndexType
from dingo_tpu.index.tiering import (
    RUNG_HBM_SQ8,
    RUNGS,
    TIERING,
    HostSqFlat,
    TierRefused,
)
from tools.chaos import DIM, cluster


@pytest.fixture(autouse=True)
def _fresh_ladder():
    TIERING.reset()
    yield
    TIERING.reset()


def _fill(node, region, n=96, seed=5):
    rng = np.random.default_rng(seed)
    ids = np.arange(1, n + 1, dtype=np.int64)
    x = rng.standard_normal((n, DIM)).astype(np.float32)
    for lo in range(0, n, 16):
        node.storage.vector_add(region, ids[lo:lo + 16], x[lo:lo + 16])
    return ids, x


def _topk(node, region, queries, k=10):
    res = node.storage.vector_batch_search(region, queries, k)
    return ([[r.id for r in row] for row in res],
            [[r.distance for r in row] for row in res])


MATRIX = [
    (IndexType.FLAT, "fp32"),
    (IndexType.FLAT, "bf16"),
    (IndexType.FLAT, "sq8"),
    (IndexType.IVF_FLAT, "fp32"),
    (IndexType.IVF_FLAT, "bf16"),
    (IndexType.IVF_FLAT, "sq8"),
]


@pytest.mark.parametrize(
    "index_type,precision", MATRIX,
    ids=[f"{t.value}-{p}" for t, p in MATRIX])
def test_round_trip_parity(index_type, precision):
    """Walk the full ladder down and back; every rung serves all acked
    rows, and the promoted-back region answers byte-identically to the
    never-demoted baseline."""
    param_kw = {}
    if index_type == IndexType.IVF_FLAT:
        param_kw = {"ncentroids": 4, "default_nprobe": 4}
    with cluster(1, replication=1, seed=7) as c:
        rid = c.create_region(index_type=index_type, precision=precision,
                              **param_kw)
        _sid, node = c.wait_leader(rid)
        region = node.get_region(rid)
        ids, x = _fill(node, region)
        q = x[:8]
        # Normalize the baseline through ONE canonical rebuild (the same
        # shared arm every precision-crossing promotion rides): byte-
        # identity is a claim about deterministic rebuilds from the WAL,
        # not about incremental-build float-reduction order (IVF trains
        # centroids differently mid-stream vs full-corpus).
        assert node.index_manager.rebuild_at_precision(
            region, raft_log=TIERING._raft_log(node, rid), precision=None)
        base_ids, base_dists = _topk(node, region, q)
        assert [row[0] for row in base_ids] == [int(i) for i in ids[:8]]

        st = TIERING._state(region)
        base_rung = st.base
        # ---- down the ladder, serving at every rung -------------------
        while st.rung < len(RUNGS) - 1:
            rep = TIERING.demote(node, region)
            assert rep["ok"], rep
            got_ids, _ = _topk(node, region, q)
            # all acked rows searchable at every point: exact self-hit
            assert [row[0] for row in got_ids] == [int(i) for i in ids[:8]]
        assert RUNGS[st.rung] == "mmap_sq8"
        w = region.vector_index_wrapper
        assert isinstance(w.own_index, HostSqFlat)
        # retire hook: a region out of HBM has zero device residency and
        # the ledger forgot it (no ghost hbm.region.bytes / DEVPEAK)
        from dingo_tpu.obs.hbm import HBM

        assert w.get_device_memory_size() == 0
        assert rid not in HBM.state()["regions"]

        # ---- back up to the base rung ---------------------------------
        while st.rung > base_rung:
            rep = TIERING.promote(node, region)
            assert rep["ok"], rep
        rt_ids, rt_dists = _topk(node, region, q)
        assert rt_ids == base_ids
        for a, b in zip(rt_dists, base_dists):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_digest_gate_refuses_corrupted_copy():
    """Flip one destination byte between the copy and the verify: the
    swap must be refused, the OLD tier keeps serving byte-identically,
    and tier.digest_refusals ticks."""
    from dingo_tpu.common.metrics import METRICS

    with cluster(1, replication=1, seed=9) as c:
        rid = c.create_region(precision="sq8")
        _sid, node = c.wait_leader(rid)
        region = node.get_region(rid)
        ids, x = _fill(node, region, n=64)
        q = x[:4]
        before_ids, before_dists = _topk(node, region, q)
        st = TIERING._state(region)
        assert st.rung == RUNG_HBM_SQ8

        def corrupt(stage, ctx=None):
            if stage == "copied" and ctx is not None:
                ctx.store.vecs[0, 0] ^= 1   # one flipped destination byte

        TIERING.test_hook = corrupt
        try:
            rep = TIERING.demote(node, region)
        finally:
            TIERING.test_hook = None
        assert rep["ok"] is False
        assert "digest" in rep["reason"]
        # rung unchanged, old tier still serving, byte-identical
        assert st.rung == RUNG_HBM_SQ8
        assert not isinstance(region.vector_index_wrapper.own_index,
                              HostSqFlat)
        after_ids, after_dists = _topk(node, region, q)
        assert after_ids == before_ids
        for a, b in zip(after_dists, before_dists):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        refusals = METRICS.counter("tier.digest_refusals",
                                   region_id=rid).get()
        assert refusals >= 1


def test_clean_copy_passes_digest_gate_and_swaps():
    """Control for the corruption test: the same transition with no
    interference verifies and installs (the gate is exact, not noisy)."""
    with cluster(1, replication=1, seed=9) as c:
        rid = c.create_region(precision="sq8")
        _sid, node = c.wait_leader(rid)
        region = node.get_region(rid)
        _fill(node, region, n=64)
        fired = []
        TIERING.test_hook = lambda stage, ctx=None: fired.append(stage)
        try:
            rep = TIERING.demote(node, region)
        finally:
            TIERING.test_hook = None
        assert rep["ok"], rep
        assert fired == ["copied", "mid_demote"]
        assert isinstance(region.vector_index_wrapper.own_index, HostSqFlat)


def test_hamming_region_refuses_ladder():
    """Binary regions have no sq8 codec: the policy never picks them,
    the transcription arm refuses (old tier keeps serving), and the
    host index constructor rejects the metric outright."""
    from dingo_tpu.index.base import IndexParameter, InvalidParameter
    from dingo_tpu.ops.distance import Metric

    with cluster(1, replication=1, seed=13) as c:
        rid = c.create_region(index_type=IndexType.BINARY_FLAT,
                              metric=Metric.HAMMING)
        _sid, node = c.wait_leader(rid)
        region = node.get_region(rid)
        ids = np.arange(1, 17, dtype=np.int64)
        rng = np.random.default_rng(13)
        packed = rng.integers(0, 256, size=(16, DIM // 8), dtype=np.uint8)
        node.storage.vector_add(region, ids, packed)
        # the policy never even nominates a binary region
        assert TIERING._pick_demote({rid: region}, {rid: 0.0}, 5.0) is None
        st = TIERING._state(region)
        st.rung = RUNG_HBM_SQ8   # force the transcription arm anyway
        rep = TIERING.demote(node, region)
        assert rep["ok"] is False
        res = node.storage.vector_batch_search(region, packed[:2], 3)
        assert [r[0].id for r in res] == [1, 2]
    with pytest.raises(InvalidParameter):
        HostSqFlat(1, IndexParameter(
            index_type=IndexType.FLAT, dimension=DIM,
            metric=Metric.HAMMING), store=None)


def test_advisory_flags_region_and_policy_tick_demotes():
    """The coordinator handshake end state: note_advisory flags the
    region; with tiering enabled and a synthetic HBM budget that leaves
    no headroom, one policy tick demotes exactly that region one rung."""
    from dingo_tpu.common.config import FLAGS

    with cluster(1, replication=1, seed=21) as c:
        rid = c.create_region()
        _sid, node = c.wait_leader(rid)
        region = node.get_region(rid)
        _fill(node, region, n=64)
        TIERING.note_advisory(rid)
        assert TIERING.state()[rid]["advisory"]
        FLAGS.set("tier_enabled", True)
        TIERING.budget_override = 1   # 1-byte budget: zero headroom
        try:
            rep = TIERING.tick(node)
        finally:
            FLAGS.set("tier_enabled", False)
            TIERING.budget_override = None
        assert rep.get("ok"), rep
        assert rep["action"] == "demote" and rep["region"] == rid
        assert not TIERING.state()[rid]["advisory"]   # consumed


def test_tick_noop_when_disabled():
    with cluster(1, replication=1, seed=23) as c:
        rid = c.create_region()
        _sid, node = c.wait_leader(rid)
        assert TIERING.tick(node) == {}
        assert TIERING.region_tier(rid) == "hbm"


def test_region_tier_reporting_defaults():
    """Untracked regions report their resident precision's base rung;
    tracked ones report the live rung (heartbeat serving_tier source)."""
    assert TIERING.region_tier(999) == "hbm"
    assert TIERING.region_tier(999, precision="sq8") == "hbm_sq8"


def test_host_sq_flat_matches_device_sq8_ranking():
    """Demoting FLAT-sq8 one rung serves the SAME codes: the host paged
    scan decodes them exactly in f32, the device kernel accumulates the
    same decoded surrogate in bf16 compute (flat.py). So wire distances
    agree to bf16 tolerance (host is the tighter of the two) and the
    ranking agrees except across sub-bf16-resolution near-ties. Rerank
    disabled: that stage is device bookkeeping the retire hook releases,
    so the comparable surface is the pure over-codes distance."""
    from dingo_tpu.common.config import FLAGS

    old_rows = FLAGS.get("rerank_cache_rows")
    FLAGS.set("rerank_cache_rows", 0)
    try:
        _host_vs_device_sq8()
    finally:
        FLAGS.set("rerank_cache_rows", old_rows)


def _host_vs_device_sq8():
    with cluster(1, replication=1, seed=31) as c:
        rid = c.create_region(precision="sq8")
        _sid, node = c.wait_leader(rid)
        region = node.get_region(rid)
        _ids, x = _fill(node, region, n=80)
        q = x[:6]
        dev_ids, dev_dists = _topk(node, region, q, k=7)
        assert TIERING.demote(node, region)["ok"]
        host_ids, host_dists = _topk(node, region, q, k=7)
        for hi, di, hd, dd in zip(host_ids, dev_ids, host_dists,
                                  dev_dists):
            # atol scales with the ~|x|^2-magnitude terms bf16 cancels
            # on near-zero distances, not with the distance itself
            np.testing.assert_allclose(np.asarray(hd), np.asarray(dd),
                                       rtol=2e-2, atol=0.2)
            assert hi[0] == di[0]           # self-hit survives the tier
            overlap = len(set(hi) & set(di))
            assert overlap >= 6, (hi, di)   # ≥6/7 modulo bf16 near-ties


def test_snapshot_source_refuses_non_sq_store():
    class _Wrapper:
        class _Idx:
            store = object()

        own_index = _Idx()
        apply_log_id = 0

        import threading as _t

        _lock = _t.RLock()

    with pytest.raises(TierRefused):
        TIERING._snapshot_source(_Wrapper())
