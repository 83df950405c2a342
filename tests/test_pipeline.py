"""Stall-free serving pipeline (common/pipeline.py + the coalescer's
overlapped-dispatch arm).

The pipeline is only allowed to change WHEN work happens, never what
comes back: the tentpole assertions here are byte-identical results
against the serial path for every index family x precision tier, zero
steady-state recompiles across the staging-depth ladder, and the
dispatch/resolve split actually overlapping (region B dispatches before
region A resolves). The shutdown contract extends to the completion
lane: drain resolves, no-drain abandons but still runs the fetch.
"""

import threading
import time

import numpy as np
import pytest

from dingo_tpu.common.config import FLAGS
from dingo_tpu.common.coalescer import CoalescerStopped, SearchCoalescer
from dingo_tpu.common.metrics import METRICS
from dingo_tpu.common.pipeline import (
    CompletionLane,
    StagedBatch,
    StagingRing,
    _next_pow2,
)
from dingo_tpu.index.base import IndexParameter, IndexType, Metric
from dingo_tpu.index.flat import TpuFlat
from dingo_tpu.index.hnsw import TpuHnsw
from dingo_tpu.index.ivf_flat import TpuIvfFlat
from dingo_tpu.index.ivf_pq import TpuIvfPq

N, D, K = 2000, 32, 10


@pytest.fixture
def pipeline_flags():
    """Force the pipeline on (the tri-state default is TPU-only) and
    restore every knob the tests twist."""
    FLAGS.set("pipeline_enabled", "true")
    yield
    FLAGS.set("pipeline_enabled", "auto")
    FLAGS.set("pipeline_depth", 2)


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(7)
    x = rng.standard_normal((N, D)).astype(np.float32)
    ids = np.arange(N, dtype=np.int64)
    q = x[:16] + 0.01 * rng.standard_normal((16, D)).astype(np.float32)
    return ids, x, q


def _build(family, precision, corpus, idx_id=1):
    ids, x, _ = corpus
    if family == "flat":
        idx = TpuFlat(idx_id, IndexParameter(
            index_type=IndexType.FLAT, dimension=D, precision=precision))
        idx.add(ids, x)
    elif family == "ivf_flat":
        idx = TpuIvfFlat(idx_id, IndexParameter(
            index_type=IndexType.IVF_FLAT, dimension=D, ncentroids=16,
            default_nprobe=16, precision=precision))
        idx.add(ids, x)
        idx.train()
    elif family == "ivf_pq":
        idx = TpuIvfPq(idx_id, IndexParameter(
            index_type=IndexType.IVF_PQ, dimension=D, ncentroids=16,
            default_nprobe=16, nsubvector=8))
        idx.add(ids, x)
        idx.train()
    elif family == "hnsw":
        idx = TpuHnsw(idx_id, IndexParameter(
            index_type=IndexType.HNSW, dimension=D, nlinks=16,
            efconstruction=80, precision=precision))
        idx.add(ids, x)
    else:  # pragma: no cover
        raise AssertionError(family)
    return idx


def _via_coalescer(idx, q, chunks=4):
    """Submit q in `chunks`-row batches under DISTINCT keys (so batch
    composition is identical between the serial and pipelined arms) and
    return the flattened per-query rows."""
    def run(key, stacked):
        return idx.search(stacked, K)

    def dispatch(key, stacked, staged=None):
        return idx.search_async(stacked, K, staged=staged)

    co = SearchCoalescer(run, window_ms=5.0, dispatch_fn=dispatch)
    try:
        futs = [co.submit(i, q[i:i + chunks])
                for i in range(0, len(q), chunks)]
        return [r for f in futs for r in f.result(timeout=60)]
    finally:
        co.stop()


def _assert_bitwise_equal(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g.ids), np.asarray(w.ids))
        assert np.asarray(g.distances, np.float32).tobytes() == \
            np.asarray(w.distances, np.float32).tobytes()


# ---------------- byte-identical across families x tiers ----------------

_FAMILIES = [
    ("flat", "fp32"), ("flat", "bf16"), ("flat", "sq8"),
    ("ivf_flat", "fp32"), ("ivf_flat", "bf16"), ("ivf_flat", "sq8"),
    ("ivf_pq", "fp32"),
    ("hnsw", "fp32"), ("hnsw", "bf16"), ("hnsw", "sq8"),
]


@pytest.mark.parametrize("family,precision", _FAMILIES)
def test_pipelined_byte_identical(pipeline_flags, corpus, family,
                                  precision):
    """The pipelined path (overlapped dispatch + staged upload + lane
    resolve) returns bit-equal ids AND distances vs the serial coalescer
    arm and vs a direct per-chunk search."""
    _, _, q = corpus
    idx = _build(family, precision, corpus)
    direct = [r for i in range(0, len(q), 4)
              for r in idx.search(q[i:i + 4], K)]
    FLAGS.set("pipeline_enabled", "false")
    serial = _via_coalescer(idx, q)
    FLAGS.set("pipeline_enabled", "true")
    pipelined = _via_coalescer(idx, q)
    _assert_bitwise_equal(serial, direct)
    _assert_bitwise_equal(pipelined, direct)


def test_depth_ladder_no_recompiles_and_identical(pipeline_flags, corpus):
    """Once warm at depth 1, running the same shapes at depth 2 and 4
    never retraces (the staging ring pads on the same pow2 ladder as
    _pad_batch) and returns the same bytes."""
    _, _, q = corpus
    idx = _build("flat", "fp32", corpus)
    baseline = None
    rc = METRICS.counter("xla.recompiles")
    for depth in (1, 2, 4):
        FLAGS.set("pipeline_depth", depth)
        if depth > 1:
            rc0 = rc.get()
        rows = _via_coalescer(idx, q)
        if baseline is None:
            baseline = rows
        else:
            assert rc.get() - rc0 == 0, f"depth {depth} retraced"
            _assert_bitwise_equal(rows, baseline)


# ---------------- dispatch/resolve overlap ------------------------------

def test_dispatch_overlap_ordering(pipeline_flags):
    """Both due batches dispatch before EITHER resolves: region B's
    kernel is enqueued while region A's fetch is still pending on the
    completion lane."""
    events = []
    guard = threading.Lock()

    def run(key, stacked):  # pragma: no cover — pipelined arm only
        raise AssertionError("serial arm must not run")

    def dispatch(key, stacked, staged=None):
        with guard:
            events.append(("dispatch", key))

        def thunk():
            with guard:
                events.append(("resolve", key))
            return [key] * len(stacked)

        return thunk

    co = SearchCoalescer(run, window_ms=50.0, dispatch_fn=dispatch)
    try:
        fa = co.submit("a", np.zeros((2, 4), np.float32))
        fb = co.submit("b", np.zeros((2, 4), np.float32))
        assert fa.result(timeout=10) == ["a", "a"]
        assert fb.result(timeout=10) == ["b", "b"]
    finally:
        co.stop()
    order = {e: i for i, e in enumerate(events)}
    assert order[("dispatch", "a")] < order[("resolve", "a")]
    assert order[("dispatch", "b")] < order[("resolve", "a")], events
    # FIFO lane: resolves happen in dispatch order
    assert order[("resolve", "a")] < order[("resolve", "b")]


def test_stage_totals_record_pipeline_stages(pipeline_flags):
    def dispatch(key, stacked, staged=None):
        return lambda: list(range(len(stacked)))

    co = SearchCoalescer(lambda k, s: list(range(len(s))),
                         window_ms=5.0, dispatch_fn=dispatch)
    try:
        co.submit("k", np.zeros((2, 4), np.float32)).result(timeout=10)
        deadline = time.monotonic() + 5
        while "resolve" not in co.stage_totals() \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        totals = co.stage_totals()
    finally:
        co.stop()
    assert "dispatch" in totals and "resolve" in totals, totals


# ---------------- shutdown contract on the lane -------------------------

def test_stop_drain_resolves_queued_handoffs(pipeline_flags):
    """stop(drain=True) while a handoff is mid-resolve and another is
    queued: every future still gets its real results."""
    release = threading.Event()

    def dispatch(key, stacked, staged=None):
        def thunk():
            if key == "a":
                release.wait(timeout=10)
            return [key] * len(stacked)
        return thunk

    co = SearchCoalescer(lambda k, s: [k] * len(s), window_ms=5.0,
                         dispatch_fn=dispatch)
    fa = co.submit("a", np.zeros((1, 4), np.float32))
    fb = co.submit("b", np.zeros((1, 4), np.float32))
    threading.Timer(0.3, release.set).start()
    co.stop(drain=True)
    assert fa.result(timeout=10) == ["a"]
    assert fb.result(timeout=10) == ["b"]


def test_stop_nodrain_abandons_but_runs_fetch(pipeline_flags):
    """stop(drain=False): queued handoffs fail fast with
    CoalescerStopped, but their thunk still runs (device-side leases
    must release)."""
    release = threading.Event()
    ran = []

    def dispatch(key, stacked, staged=None):
        def thunk():
            if key == "a":
                release.wait(timeout=10)
            ran.append(key)
            return [key] * len(stacked)
        return thunk

    co = SearchCoalescer(lambda k, s: [k] * len(s), window_ms=5.0,
                         dispatch_fn=dispatch)
    fa = co.submit("a", np.zeros((1, 4), np.float32))
    fb = co.submit("b", np.zeros((1, 4), np.float32))
    # wait until a is mid-resolve on the lane (b queued behind it)
    deadline = time.monotonic() + 5
    while co._lane.depth() < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    threading.Timer(0.3, release.set).start()
    co.stop(drain=False)
    assert fa.result(timeout=10) == ["a"]        # mid-resolve completes
    with pytest.raises(CoalescerStopped):
        fb.result(timeout=10)
    assert "b" in ran                            # fetch ran anyway


# ---------------- staging ring primitives -------------------------------

def test_staging_ring_pads_on_ladder_and_zero_tail():
    ring = StagingRing(depth=2)
    stacked = np.arange(5 * 4, dtype=np.float32).reshape(5, 4)
    staged = ring.stage(stacked)
    assert staged.rows == 5
    qpad = staged.take(stacked)
    assert qpad is not None
    assert qpad.shape == (_next_pow2(5), 4) == (8, 4)
    host = np.asarray(qpad)
    assert np.array_equal(host[:5], stacked)
    assert not host[5:].any()
    staged.release()


def test_staged_batch_take_identity():
    ring = StagingRing(depth=1)
    stacked = np.ones((2, 4), np.float32)
    staged = ring.stage(stacked)
    # the exact staged array claims the upload; a copy (what a dtype
    # rebind in _prep_queries produces) must NOT
    assert staged.take(stacked) is not None
    assert staged.take(stacked.copy()) is None
    assert staged.take(np.asarray(stacked, np.float64)) is None
    staged.release()
    staged.release()  # idempotent


def test_staging_ring_depth_backpressure():
    ring = StagingRing(depth=2)
    a = ring.stage(np.zeros((1, 4), np.float32))
    b = ring.stage(np.zeros((1, 4), np.float32))
    third_in = threading.Event()

    def third():
        s = ring.stage(np.zeros((1, 4), np.float32))
        third_in.set()
        s.release()

    t = threading.Thread(target=third, daemon=True)
    t.start()
    assert not third_in.wait(timeout=0.3)   # both slots leased: blocked
    a.release()
    assert third_in.wait(timeout=5)         # release unblocks the ring
    b.release()
    t.join(timeout=5)


def test_staging_ring_closed_raises():
    ring = StagingRing(depth=1)
    ring.close()
    with pytest.raises(RuntimeError, match="closed"):
        ring.stage(np.zeros((1, 4), np.float32))


def test_completion_lane_fifo_and_stop_idempotent():
    done = []

    class H:
        def __init__(self, tag):
            self.tag = tag

        def resolve(self):
            done.append(self.tag)

        def abandon(self):  # pragma: no cover
            done.append(("abandon", self.tag))

    lane = CompletionLane(name="test-lane")
    for i in range(5):
        assert lane.submit(H(i))
    lane.stop(drain=True)
    assert done == [0, 1, 2, 3, 4]
    assert not lane.submit(H(9))    # stopped lane refuses new handoffs
    lane.stop(drain=True)           # idempotent
