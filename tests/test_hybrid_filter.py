"""Filtered searches over regions that mirror their flagged scalar fields,
held to the plain reference (tests/filter_reference.py).

The served path end to end, in one process over real sockets: SDK ->
three index regions of one store -> reader -> the FilterSpec's predicates
as a slot mask inside the index's one search program. Rows carry two
columns, `label` (10 values) and `num` (uniform below 1,000), flagged as the
regions' scalar_speedup_keys.

"Exact" compares with the reference's float64 squared L2: a served distance
is its row's exact distance within float32 rounding of the scan's
|q|^2 + |x|^2 - 2 q.x (TOL times |q|^2 + |x|^2, the benchmark's dist_err
rule), and the served ids are the reference's, except where two rows at the
k-th distance lie within that rounding of each other (float32 cannot order
them).
"""

import time

import numpy as np
import pytest

from dingo_tpu.client import DingoClient
from dingo_tpu.common.metrics import METRICS
from dingo_tpu.coordinator.control import CoordinatorControl
from dingo_tpu.coordinator.kv_control import KvControl
from dingo_tpu.coordinator.tso import TsoControl
from dingo_tpu.coprocessor.scalar_filter import CmpOp, ScalarPredicate
from dingo_tpu.engine.raw_engine import MemEngine
from dingo_tpu.index.columns import ColumnsUnavailable, ScalarColumns
from dingo_tpu.raft import LocalTransport, wire
from dingo_tpu.server import pb
from dingo_tpu.server.convert import predicates_from_pb
from dingo_tpu.server.rpc import DingoServer
from dingo_tpu.store.node import StoreNode

from filter_reference import distances, filtered_topk, matching

DIM, ROWS, K, NQ = 16, 3000, 10, 8
NLIST = 64
WINDOWS = [(0, 1000), (1000, 2000), (2000, 1 << 40)]
KEYS = ["label", "num"]
TOL = 5e-6
FLAT_P, IVF_P, PLAIN_P = 1, 2, 3


def _param(index_type, keys=KEYS, **kw):
    return pb.VectorIndexParameter(
        index_type=index_type, dimension=DIM,
        metric_type=pb.METRIC_TYPE_L2, scalar_speedup_keys=keys, **kw)


PARTITIONS = {
    FLAT_P: _param(pb.VECTOR_INDEX_TYPE_FLAT),
    IVF_P: _param(pb.VECTOR_INDEX_TYPE_IVF_FLAT, ncentroids=NLIST,
                  default_nprobe=NLIST),
    PLAIN_P: _param(pb.VECTOR_INDEX_TYPE_FLAT, keys=[]),
}


def _corpus(seed=0):
    """A clustered mixture (the benchmark's shape at 16-d) with columns."""
    rng = np.random.default_rng(seed)
    centres = rng.standard_normal((30, DIM))
    x = (centres[rng.integers(0, 30, ROWS)]
         + 0.35 * rng.standard_normal((ROWS, DIM))).astype(np.float32)
    cols = {"label": rng.integers(0, 10, ROWS),
            "num": rng.integers(0, 1000, ROWS)}
    q = (x[rng.choice(ROWS, NQ, replace=False)]
         + 0.05 * rng.standard_normal((NQ, DIM))).astype(np.float32)
    return x, cols, q


def _rows(cols, sel):
    return [{f: int(cols[f][i]) for f in cols
             if not np.ma.is_masked(cols[f][i])} for i in sel]


def _wait(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.05)
    raise AssertionError("condition not met in time")


@pytest.fixture(scope="module")
def hybrid():
    transport, me = LocalTransport(), MemEngine()
    control = CoordinatorControl(me, replication=1)
    coord = DingoServer()
    coord.host_coordinator_role(control, TsoControl(me), KvControl(me))
    coord_port = coord.start()
    node = StoreNode("s0", transport, control, raft_kw={"seed": 0})
    server = DingoServer()
    server.host_store_role(node)
    port = server.start()
    node.start_heartbeat(0.1)
    client = DingoClient(f"127.0.0.1:{coord_port}",
                         {"s0": f"127.0.0.1:{port}"})
    x, cols, q = _corpus()
    ids = np.arange(ROWS, dtype=np.int64)
    for pid, param in PARTITIONS.items():
        for lo, hi in WINDOWS:
            client.create_index_region(pid, lo, hi, param, replication=1)
        _wait(lambda: len(client.vector_status(pid)) == len(WINDOWS))
        for lo in range(0, ROWS, 500):
            sel = range(lo, lo + 500)
            client.vector_add(pid, list(sel), x[lo:lo + 500],
                              _rows(cols, sel))
    client.vector_build(IVF_P)
    _wait(lambda: all(st["ready"] and st["trained"]
                      for st in client.vector_status(IVF_P)))
    yield {"client": client, "node": node, "x": x, "cols": cols, "q": q,
           "ids": ids}
    client.close()
    server.stop()
    coord.stop()
    node.stop()


def _probe_all(pid):
    return {"nprobe": NLIST} if pid == IVF_P else {}


def _post_rows():
    return METRICS.counter("filter.post_rows").get()


def assert_exact(served, ref, x, ids, query, keep_ids):
    """The served row of one query against the reference's."""
    s_ids = np.asarray([i for i, _ in served], np.int64)
    s_d = np.asarray([d for _, d in served], np.float64)
    r_ids, r_d = ref
    assert len(s_ids) == len(r_ids), (s_ids, r_ids)
    if not len(r_ids):
        return
    assert set(s_ids) <= keep_ids, "a served row fails the predicates"
    truth = distances(x, ids, query, s_ids)
    rows = x[[int(i) for i in s_ids]].astype(np.float64)
    scale = float(np.dot(query, query)) + (rows ** 2).sum(axis=1)
    assert np.all(np.abs(s_d - truth) <= TOL * scale), (s_d, truth)
    assert np.all(np.diff(s_d) >= -TOL * scale[1:]), "not nearest first"
    edge = r_d[-1] + TOL * scale.max()
    assert np.all(truth <= edge), "a served row outside the exact top-k"
    for i, d in zip(r_ids, r_d):
        if int(i) not in set(s_ids.tolist()):
            assert d >= r_d[-1] - TOL * scale.max(), \
                f"row {i} at {d} missing, not a tie at the k-th distance"


def _fewer_than_k(cols):
    """An `eq` on num that 1 to 9 rows match."""
    vals, counts = np.unique(cols["num"], return_counts=True)
    return int(vals[(counts >= 1) & (counts < K)][0])


#: (case, predicates); `few` is filled from the data
CASES = [
    ("eq", [{"field": "label", "op": "eq", "value": 3}]),
    ("ne", [{"field": "label", "op": "ne", "value": 3}]),
    ("lt", [{"field": "num", "op": "lt", "value": 300}]),
    ("le", [{"field": "num", "op": "le", "value": 300}]),
    ("gt", [{"field": "num", "op": "gt", "value": 700}]),
    ("ge", [{"field": "num", "op": "ge", "value": 700}]),
    ("in", [{"field": "label", "op": "in", "value": [1, 4, 7]}]),
    ("none_match", [{"field": "label", "op": "eq", "value": 99}]),
    ("fewer_than_k", None),
    ("one_percent", [{"field": "num", "op": "lt", "value": 10}]),
    ("half", [{"field": "num", "op": "lt", "value": 500}]),
    ("both_fields", [{"field": "label", "op": "in", "value": [2, 5]},
                     {"field": "num", "op": "ge", "value": 250}]),
]


@pytest.mark.parametrize("filter_type", ["QUERY_POST", "QUERY_PRE"])
@pytest.mark.parametrize("case,predicates", CASES, ids=[c[0] for c in CASES])
@pytest.mark.parametrize("pid", [FLAT_P, IVF_P], ids=["flat", "ivf_exact"])
def test_filtered_search_is_the_reference(hybrid, pid, case, predicates,
                                          filter_type):
    """FLAT, and IVF_FLAT probing every list, return the reference's exact
    filtered top-k for every op and both filter types; no engine read and
    no host post-filter serve it (`filter.post_rows` does not move)."""
    h = hybrid
    if predicates is None:
        predicates = [{"field": "num", "op": "eq",
                       "value": _fewer_than_k(h["cols"])}]
    before = _post_rows()
    got = h["client"].vector_search(
        pid, h["q"], topk=K, filter=pb.SCALAR_FILTER,
        filter_type=getattr(pb, filter_type), predicates=predicates,
        **_probe_all(pid))
    assert _post_rows() == before
    keep = matching(h["cols"], predicates, ROWS)
    keep_ids = set(h["ids"][keep].tolist())
    ref = filtered_topk(h["x"], h["ids"], h["cols"], h["q"], predicates, K)
    for qi in range(NQ):
        assert_exact(got[qi], ref[qi], h["x"], h["ids"], h["q"][qi],
                     keep_ids)


def test_ivf_nprobe32_recall(hybrid):
    """IVF_FLAT at nprobe 32 of 64 lists: every served row matches, and
    recall@10 against the exact filtered top-10 is the source's 0.95."""
    h = hybrid
    hit = total = 0
    for label in range(10):
        predicates = [{"field": "label", "op": "eq", "value": label}]
        got = h["client"].vector_search(
            IVF_P, h["q"], topk=K, nprobe=32, filter=pb.SCALAR_FILTER,
            filter_type=pb.QUERY_POST, predicates=predicates)
        ref = filtered_topk(h["x"], h["ids"], h["cols"], h["q"],
                            predicates, K)
        for row, (r_ids, _) in zip(got, ref):
            assert all(h["cols"]["label"][i] == label for i, _ in row)
            hit += len({i for i, _ in row} & set(r_ids.tolist()))
            total += len(r_ids)
    assert hit / total >= 0.95


@pytest.mark.parametrize("pid", [FLAT_P, IVF_P], ids=["flat", "ivf"])
def test_writes_filter_at_once(hybrid, pid):
    """Rows written after the build, rows re-labelled and rows deleted are
    what the very next filtered search sees."""
    h = hybrid
    client, rng = h["client"], np.random.default_rng(pid)
    new_ids = list(range(5000 + 100 * pid, 5000 + 100 * pid + 12))
    fresh = (h["q"][0] + 0.01 * rng.standard_normal((12, DIM))).astype(
        np.float32)
    client.vector_add(pid, new_ids, fresh,
                      [{"label": 42, "num": i} for i in range(12)])

    def served(value):
        rows = client.vector_search(
            pid, h["q"][:1], topk=20, filter=pb.SCALAR_FILTER,
            filter_type=pb.QUERY_PRE,
            predicates=[{"field": "label", "op": "eq", "value": value}],
            **_probe_all(pid))
        return sorted(i for i, _ in rows[0])

    assert served(42) == new_ids
    _upsert(client, pid, new_ids[:3], fresh[:3],
            [{"label": 43, "num": 0}] * 3)
    assert served(42) == new_ids[3:] and served(43) == new_ids[:3]
    _delete(client, pid, new_ids[3:6])
    assert served(42) == new_ids[6:]
    # a row without scalars takes a freed slot: it carries no label
    spare = new_ids[-1] + 1
    client.vector_add(pid, [spare], fresh[3:4])
    assert served(42) == new_ids[6:]
    _delete(client, pid, new_ids[:3] + new_ids[6:] + [spare])
    assert served(42) == [] and served(43) == []


def _leader_request(client, pid, ids, req):
    """`req` to the leader of the region of `ids` (all in one region)."""
    d = client._region_for_id(pid, ids[0],
                              client._regions_for_vector_ids(pid))
    req.context.region_id = d.region_id
    method = type(req).__name__[:-len("Request")]
    client._call_leader(d, "IndexService", method, req)


def _upsert(client, pid, ids, vectors, scalars):
    """VectorAdd with is_update (the SDK's add refuses a held id)."""
    req = pb.VectorAddRequest(is_update=True)
    for vid, v, sc in zip(ids, vectors, scalars):
        row = req.vectors.add()
        row.vector.id = vid
        row.vector.values.extend(v.tolist())
        for key, val in sc.items():
            e = row.scalar_data.add()
            e.key, e.value = key, wire.encode_obj(val)
    _leader_request(client, pid, ids, req)


def _delete(client, pid, ids):
    """VectorDelete (the SDK has no call)."""
    _leader_request(client, pid, ids, pb.VectorDeleteRequest(ids=ids))


@pytest.mark.parametrize("pid", [FLAT_P, IVF_P], ids=["flat", "ivf"])
def test_filter_span_and_matched_fraction(hybrid, pid):
    """A sampled filtered request carries span `index.filter` inside its
    `index.dispatch`, built or found in the IVF cache alike, and the gauge
    `filter.matched_fraction` of every region reads the matching share."""
    from dingo_tpu.common.config import FLAGS
    from dingo_tpu.index import codec as vcodec
    from dingo_tpu.trace import TRACE_BUFFER

    h = hybrid
    predicates = [{"field": "label", "op": "in", "value": [0, 1]}]
    TRACE_BUFFER.clear()
    FLAGS.set("trace_sampling_rate", 1.0)
    try:
        for _ in range(2):       # the second finds the IVF masks cached
            h["client"].vector_search(
                pid, h["q"][:2], topk=K, filter=pb.SCALAR_FILTER,
                filter_type=pb.QUERY_POST, predicates=predicates,
                **_probe_all(pid))
        spans = TRACE_BUFFER.snapshot()
    finally:
        FLAGS.set("trace_sampling_rate", 0.0)
        TRACE_BUFFER.clear()
    by_id = {r["span_id"]: r for r in spans}
    filters = [r for r in spans if r["name"] == "index.filter"]
    assert len(filters) == 2 * len(WINDOWS)
    assert all(by_id[r["parent_id"]]["name"] == "index.dispatch"
               for r in filters)
    keep = matching(h["cols"], predicates, ROWS)
    for r in h["node"].meta.get_all_regions():
        if r.definition.partition_id != pid:
            continue
        lo, hi = vcodec.range_to_vector_ids(r.definition.start_key,
                                            r.definition.end_key)
        share = keep[lo:min(hi, ROWS)].mean()
        got = METRICS.gauge("filter.matched_fraction", region_id=r.id).get()
        assert abs(got - share) < 1e-9, (r.id, got, share)


def test_unflagged_region_keeps_the_host_paths(hybrid):
    """A region with no flagged field keeps no columns and serves a SCALAR
    filter as before: QUERY_PRE exactly (a candidate scan), QUERY_POST
    from a 10-fold over-fetch whose candidates the host post-filter looks
    up, each row's in one multi-get, counted by `filter.post_rows`."""
    h = hybrid
    node = h["node"]
    for r in node.meta.get_all_regions():
        if r.definition.partition_id == PLAIN_P:
            assert r.vector_index_wrapper.own_index.store.columns is None
    predicates = [{"field": "label", "op": "eq", "value": 3}]
    keep = matching(h["cols"], predicates, ROWS)
    ref = filtered_topk(h["x"], h["ids"], h["cols"], h["q"], predicates, K)
    pre = h["client"].vector_search(
        PLAIN_P, h["q"], topk=K, filter=pb.SCALAR_FILTER,
        filter_type=pb.QUERY_PRE, predicates=predicates)
    for qi in range(NQ):
        assert_exact(pre[qi], ref[qi], h["x"], h["ids"], h["q"][qi],
                     set(h["ids"][keep].tolist()))
    before = _post_rows()
    post = h["client"].vector_search(
        PLAIN_P, h["q"], topk=K, filter=pb.SCALAR_FILTER,
        filter_type=pb.QUERY_POST, predicates=predicates)
    # three regions x NQ queries x a top-100 over-fetch, every candidate
    assert _post_rows() - before == len(WINDOWS) * NQ * K * 10
    for row in post:
        assert 0 < len(row) <= K
        assert all(h["cols"]["label"][i] == 3 for i, _ in row)


def test_split_keeps_columns(hybrid):
    """A split region's child serves its rows' columns from the shared
    parent index, and from its own once rebuilt from the speed-up CF."""
    h = hybrid
    client, node = h["client"], h["node"]
    pid = 4
    client.create_index_region(pid, 0, 1 << 40, PARTITIONS[FLAT_P],
                               replication=1)
    _wait(lambda: len(client.vector_status(pid)) == 1)
    x, cols, ids = h["x"][:600], {f: v[:600] for f, v in h["cols"].items()}, \
        h["ids"][:600]
    client.vector_add(pid, ids.tolist(), x, _rows(cols, range(600)))
    parent = client.vector_status(pid)[0]["region_id"]
    child = client.split_region(parent, 300, partition_id=pid)
    _wait(lambda: len(client.vector_status(pid)) == 2)
    predicates = [{"field": "label", "op": "in", "value": [0, 9]}]
    keep_ids = set(ids[matching(cols, predicates, 600)].tolist())
    ref = filtered_topk(x, ids, cols, h["q"], predicates, K)

    def check():
        got = client.vector_search(
            pid, h["q"], topk=K, filter=pb.SCALAR_FILTER,
            filter_type=pb.QUERY_POST, predicates=predicates)
        for qi in range(NQ):
            assert_exact(got[qi], ref[qi], x, ids, h["q"][qi], keep_ids)

    before = _post_rows()
    check()
    region = node.get_region(child)
    wrapper = region.vector_index_wrapper
    node.index_manager.rebuild(region)
    _wait(lambda: wrapper.share_index is None
          and wrapper.own_index is not None)
    own = wrapper.own_index
    assert own.store.columns.complete() and own.get_count() == 300
    check()
    assert _post_rows() == before


def test_sdk_sends_one_request_with_every_parameter(hybrid, monkeypatch):
    """The SDK boxes the queries once and copies every search parameter
    once, then asks each region with only the context changed; predicates
    reach the wire as the store decodes them."""
    client = hybrid["client"]
    sent = []

    def record(d, service, method, req, **kw):
        sent.append((d.region_id, req.SerializeToString(),
                     req.context.region_id, id(req)))
        return pb.VectorSearchResponse()

    monkeypatch.setattr(client, "_call_leader", record)
    cop = pb.Coprocessor()
    cop.filter_expr = b"x"
    preds = [{"field": "label", "op": "in", "value": [1, 2]},
             {"field": "num", "op": CmpOp.GE, "value": 7}]
    client.vector_search(
        FLAT_P, hybrid["q"][:2], topk=7, with_scalar_data=True, nprobe=5,
        ef_search=9, filter=pb.SCALAR_FILTER, filter_type=pb.QUERY_PRE,
        radius=2.5, with_vector_data=True, vector_ids=[3, 4],
        predicates=preds, coprocessor=cop)
    assert [s[0] for s in sent] == [s[2] for s in sent]
    assert len({s[0] for s in sent}) == len(WINDOWS)
    assert len({s[3] for s in sent}) == 1, "one request object a call"
    reqs = [pb.VectorSearchRequest.FromString(s[1]) for s in sent]
    for r in reqs:
        r.ClearField("context")
        assert r == reqs[0]
    p = reqs[0].parameter
    assert (p.top_n, p.nprobe, p.ef_search, p.radius) == (7, 5, 9, 2.5)
    assert p.with_scalar_data and p.with_vector_data
    assert (p.filter, p.filter_type) == (pb.SCALAR_FILTER, pb.QUERY_PRE)
    assert list(p.vector_ids) == [3, 4]
    assert p.coprocessor.filter_expr == b"x"
    assert predicates_from_pb(p.predicates).predicates == [
        ScalarPredicate("label", CmpOp.IN, [1, 2]),
        ScalarPredicate("num", CmpOp.GE, 7)]
    assert wire.decode_obj(p.predicates[0].value) == [1, 2]
    np.testing.assert_array_equal(
        [list(v.values) for v in reqs[0].vectors], hybrid["q"][:2])
    with pytest.raises(TypeError, match="no search parameter"):
        client.vector_search(FLAT_P, hybrid["q"][:1], topk=1, nprob=3)


# ---------------------------------------------------- the columns themselves
def _python_truth(rows, p):
    return np.asarray([p.matches(r) for r in rows], bool)


VALUE_KINDS = {
    "int": [5, -3, 0, 7, 5, 2 ** 40],
    "float": [0.5, -1.25, float("nan"), 3.0, 0.5, 1e300],
    "str": ["a", "b", "", "ab", "a", "z"],
    "mixed": [1, "1", 1.0, None, True, [1]],
    "bool": [True, False, True, True, False, False],
    "big_int": [2 ** 70, 1, -(2 ** 70), 3, 2 ** 70, 0],
}
OPERANDS = [0, 1, 5, 0.5, 3.0, "a", 2 ** 70, True, float("nan")]


@pytest.mark.parametrize("kind", sorted(VALUE_KINDS))
@pytest.mark.parametrize("op", list(CmpOp))
def test_column_mask_is_scalar_predicate(kind, op):
    """The column mask equals ScalarPredicate.matches row by row, for
    every op and operand, over whole-number, float, string, mixed, bool
    and beyond-int64 columns, with rows that lack the field."""
    vals = VALUE_KINDS[kind]
    rows = [{"f": v} for v in vals] + [{}, {"g": 1}]
    cols = ScalarColumns(["f"], 16)
    slots = np.arange(len(rows))
    cols.assign(slots)
    cols.write(slots, rows)
    assert cols.complete()
    operands = [[1, 5], ("a", 0.5), [], [2 ** 70, True]] \
        if op is CmpOp.IN else OPERANDS + ["x"]
    for v in operands:
        p = ScalarPredicate("f", op, v)
        got = cols.match([p], 16)[:len(rows)]
        np.testing.assert_array_equal(got, _python_truth(rows, p),
                                      err_msg=f"{kind} {op} {v!r}")
        assert not cols.match([p], 16)[len(rows):].any()


def test_column_lifecycle():
    """Unknown slots make the mirror incomplete until written; release
    clears a slot; growth keeps values; `rows` None keeps a known slot's
    values and marks a new one as having none."""
    cols = ScalarColumns(["label"], 4)
    cols.assign(np.array([0, 1]))
    with pytest.raises(ColumnsUnavailable):
        cols.match([ScalarPredicate("label", CmpOp.EQ, 1)], 4)
    cols.write(np.array([0, 1]), [{"label": 1}, {"label": 2}])
    cols.grow(8)
    cols.assign(np.array([5]))
    cols.write(np.array([5, -1]), None)
    p = ScalarPredicate("label", CmpOp.EQ, 1)
    assert cols.match([p], 8).tolist() == [True] + [False] * 7
    cols.write(np.array([0]), None)
    assert cols.match([p], 8)[0]
    cols.release(np.array([0, -1]))
    assert not cols.match([p], 8).any()
    cols.write(np.array([1]), [{"other": 1}])
    assert not cols.match([ScalarPredicate("label", CmpOp.NE, 9)], 8).any()
    with pytest.raises(ColumnsUnavailable):
        cols.match([ScalarPredicate("num", CmpOp.EQ, 1)], 8)


# ------------------------------------------------ save, load, WAL replay
@pytest.mark.parametrize("index_type", ["FLAT", "IVF_FLAT"])
def test_save_load_restores_columns(tmp_path, index_type):
    """A snapshot holds no columns: load fills them from the speed-up CF
    and the WAL replay writes those of the entries after the snapshot. A
    freed slot that a row without scalars takes carries no value."""
    from dingo_tpu.engine import write_data as wd
    from dingo_tpu.engine.mono_engine import MonoStoreEngine
    from dingo_tpu.engine.storage import Storage
    from dingo_tpu.index import codec as vcodec
    from dingo_tpu.index.base import IndexParameter, IndexType
    from dingo_tpu.index.manager import VectorIndexManager
    from dingo_tpu.index.vector_reader import (
        VectorFilterMode,
        VectorFilterType,
    )
    from dingo_tpu.coprocessor.scalar_filter import ScalarFilter
    from dingo_tpu.raft.log import RaftLog
    from dingo_tpu.store.region import Region, RegionDefinition, RegionType

    raw = MemEngine()
    storage = Storage(MonoStoreEngine(raw))
    region = Region(RegionDefinition(
        region_id=9, start_key=vcodec.encode_vector_key(0, 0),
        end_key=vcodec.encode_vector_key(0, 1 << 40),
        region_type=RegionType.INDEX,
        index_parameter=IndexParameter(
            index_type=IndexType[index_type], dimension=DIM, ncentroids=16,
            default_nprobe=16, scalar_speedup_keys=tuple(KEYS))))
    w = region.vector_index_wrapper
    w.build_own()
    w.set_own(w.own_index)
    x, cols, q = _corpus(1)
    n = 800
    x = np.concatenate([x[:n], x[5:6]])
    q = np.concatenate([q, x[5:6]])      # nearest: row 5, then row 5000
    ids = np.append(np.arange(n, dtype=np.int64), 5000)
    cols = {f: np.ma.masked_array(np.append(v[:n], 0),
                                  mask=np.arange(n + 1) == n)
            for f, v in cols.items()}
    probe = {"nprobe": 16} if index_type == "IVF_FLAT" else {}

    def check(reg):
        for predicates in (
                [{"field": "label", "op": "eq",
                  "value": int(cols["label"][ids == 5][0]) if 5 in ids
                  else int(cols["label"][ids == 7][0])}],
                [{"field": "num", "op": "lt", "value": 100}],
                [{"field": "label", "op": "ne", "value": 4}]):
            got = storage.vector_batch_search(
                reg, q, K, filter_mode=VectorFilterMode.SCALAR,
                filter_type=VectorFilterType.QUERY_POST,
                scalar_filter=ScalarFilter([ScalarPredicate(
                    p["field"], CmpOp(p["op"]), p["value"])
                    for p in predicates]),
                **probe)
            ref = filtered_topk(x, ids, cols, q, predicates, K)
            keep = set(ids[matching(cols, predicates, len(ids))].tolist())
            for qi in range(len(q)):
                assert_exact([(v.id, v.distance) for v in got[qi]],
                             ref[qi], x, ids, q[qi], keep)

    storage.vector_add(region, ids[:n], x[:n], _rows(cols, range(n)))
    mgr = VectorIndexManager(raw, str(tmp_path))
    mgr.rebuild(region)
    check(region)
    # row 5 deleted; row 5000, without scalars, takes its slot
    storage.vector_delete(region, [5])
    storage.vector_add(region, ids[n:], x[n:])
    label5 = int(cols["label"][5])
    ids, x = np.delete(ids, 5), np.delete(x, 5, axis=0)
    cols = {f: np.ma.masked_array(np.delete(v.data, 5),
                                  mask=np.delete(v.mask, 5))
            for f, v in cols.items()}
    cols["label"][ids == 7] = label5     # row 7 takes row 5's label
    relabel = wd.VectorAddData(ts=9, ids=np.asarray([7], np.int64),
                               vectors=x[ids == 7],
                               scalars=[{"label": label5, "num": 999}])
    cols["num"][ids == 7] = 999
    w.apply_log_id = w.own_index.apply_log_id = 3
    mgr.save_index(region)
    # after the snapshot: row 7 re-labelled, in the engine and the log
    storage.vector_add(region, relabel.ids, relabel.vectors,
                       relabel.scalars)
    check(region)
    log = RaftLog()
    for _ in range(3):
        log.append(1, wd.encode_write(wd.KvPutData(cf="default", ts=1,
                                                   kvs=[])))
    log.append(1, wd.encode_write(relabel))
    restarted = Region(region.definition)
    restarted.vector_index_wrapper.apply_log_id = 4
    assert mgr.load_index(restarted, raft_log=log)
    own = restarted.vector_index_wrapper.own_index
    assert own.store.columns.complete() and own.get_count() == n
    check(restarted)


def test_hnsw_filters_on_the_mask():
    """HNSW mirrors flagged fields as the other families do: a filtered
    walk serves only matching rows, reaches the reference's top-k, and
    reads no engine row."""
    from dingo_tpu.coprocessor.scalar_filter import ScalarFilter
    from dingo_tpu.engine.mono_engine import MonoStoreEngine
    from dingo_tpu.engine.storage import Storage
    from dingo_tpu.index import codec as vcodec
    from dingo_tpu.index.base import IndexParameter, IndexType
    from dingo_tpu.index.vector_reader import (
        VectorFilterMode,
        VectorFilterType,
    )
    from dingo_tpu.store.region import Region, RegionDefinition, RegionType

    region = Region(RegionDefinition(
        region_id=11, start_key=vcodec.encode_vector_key(0, 0),
        end_key=vcodec.encode_vector_key(0, 1 << 40),
        region_type=RegionType.INDEX,
        index_parameter=IndexParameter(
            index_type=IndexType.HNSW, dimension=DIM, nlinks=16,
            efconstruction=80, scalar_speedup_keys=tuple(KEYS))))
    w = region.vector_index_wrapper
    w.build_own()
    w.set_own(w.own_index)
    storage = Storage(MonoStoreEngine(MemEngine()))
    x, cols, q = _corpus(2)
    n = 1000
    x, cols, ids = x[:n], {f: v[:n] for f, v in cols.items()}, \
        np.arange(n, dtype=np.int64)
    storage.vector_add(region, ids, x, _rows(cols, range(n)))
    predicates = [{"field": "label", "op": "in", "value": [2, 3, 4]}]
    before = _post_rows()
    got = storage.vector_batch_search(
        region, q, K, filter_mode=VectorFilterMode.SCALAR,
        filter_type=VectorFilterType.QUERY_POST, ef=128,
        scalar_filter=ScalarFilter([ScalarPredicate("label", CmpOp.IN,
                                                    [2, 3, 4])]))
    assert _post_rows() == before
    ref = filtered_topk(x, ids, cols, q, predicates, K)
    hit = 0
    for row, (r_ids, _) in zip(got, ref):
        assert len(row) == K
        assert all(cols["label"][v.id] in (2, 3, 4) for v in row)
        hit += len({v.id for v in row} & set(r_ids.tolist()))
    assert hit / (K * NQ) >= 0.95
