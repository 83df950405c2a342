"""End-to-end single-node slice: Storage facade -> MonoStoreEngine -> apply
handlers -> raw engine + vector index wrapper -> VectorReader.

Mirrors the reference's §3.1/§3.2 call stacks without RPC/raft: the
dual-write invariant (engine is source of truth, index is an apply-log-
tracked view), filter modes, brute-force fallback, and recovery-by-rebuild.
"""

import numpy as np
import pytest

from dingo_tpu.coprocessor import ScalarFilter
from dingo_tpu.engine.mono_engine import MonoStoreEngine
from dingo_tpu.engine.raw_engine import MemEngine, WalEngine
from dingo_tpu.engine.storage import Storage
from dingo_tpu.index import codec as vcodec
from dingo_tpu.index.base import IndexParameter, IndexType, InvalidParameter
from dingo_tpu.index.vector_reader import VectorFilterMode, VectorFilterType
from dingo_tpu.store.region import (
    Region,
    RegionDefinition,
    RegionType,
    StoreMetaManager,
)

DIM = 16


def make_region(region_id=77, id_lo=0, id_hi=1 << 40, index_type=IndexType.FLAT):
    definition = RegionDefinition(
        region_id=region_id,
        start_key=vcodec.encode_vector_key(1, id_lo),
        end_key=vcodec.encode_vector_key(1, id_hi),
        partition_id=1,
        region_type=RegionType.INDEX,
        index_parameter=IndexParameter(index_type=index_type, dimension=DIM,
                                       ncentroids=8, default_nprobe=8),
    )
    region = Region(definition)
    w = region.vector_index_wrapper
    w.build_own()
    w.set_own(w.own_index)
    return region


@pytest.fixture()
def stack():
    raw = MemEngine()
    engine = MonoStoreEngine(raw)
    storage = Storage(engine)
    region = make_region()
    return raw, engine, storage, region


def rand(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, DIM)).astype(np.float32)


def test_vector_add_search_roundtrip(stack):
    raw, engine, storage, region = stack
    x = rand(100)
    ids = np.arange(100, dtype=np.int64)
    scalars = [{"color": "red" if i % 2 == 0 else "blue", "n": i} for i in range(100)]
    storage.vector_add(region, ids, x, scalars)
    res = storage.vector_batch_search(region, x[:3], 5)
    assert [r[0].id for r in res] == [0, 1, 2]
    assert res[0][0].distance == pytest.approx(0.0, abs=1e-3)
    # engine holds the data (source of truth)
    got = storage.vector_batch_query(region, [5, 99, 12345],
                                     with_scalar_data=True)
    assert got[0].scalar["n"] == 5
    assert np.allclose(got[1].vector, x[99], atol=1e-5)
    assert got[2] is None


def test_vector_delete_hides_everywhere(stack):
    raw, engine, storage, region = stack
    x = rand(50)
    storage.vector_add(region, np.arange(50, dtype=np.int64), x)
    storage.vector_delete(region, [0, 1, 2])
    res = storage.vector_batch_search(region, x[:1], 3)
    assert all(v.id >= 3 for v in res[0])
    assert storage.vector_batch_query(region, [1])[0] is None
    assert storage.vector_count(region) == 47


def test_scalar_post_filter(stack):
    raw, engine, storage, region = stack
    x = rand(200)
    ids = np.arange(200, dtype=np.int64)
    scalars = [{"color": "red" if i % 4 == 0 else "blue"} for i in range(200)]
    storage.vector_add(region, ids, x, scalars)
    res = storage.vector_batch_search(
        region, x[:2], 5,
        filter_mode=VectorFilterMode.SCALAR,
        filter_type=VectorFilterType.QUERY_POST,
        scalar_filter=ScalarFilter.equals({"color": "red"}),
        with_scalar_data=True,
    )
    for row in res:
        assert len(row) == 5
        assert all(v.id % 4 == 0 for v in row)
        assert all(v.scalar == {"color": "red"} for v in row)


def test_scalar_pre_filter(stack):
    raw, engine, storage, region = stack
    x = rand(200)
    ids = np.arange(200, dtype=np.int64)
    scalars = [{"bucket": i % 10} for i in range(200)]
    storage.vector_add(region, ids, x, scalars)
    res = storage.vector_batch_search(
        region, x[:2], 50,
        filter_mode=VectorFilterMode.SCALAR,
        filter_type=VectorFilterType.QUERY_PRE,
        scalar_filter=ScalarFilter.equals({"bucket": 3}),
    )
    for row in res:
        assert len(row) == 20  # only 20 vectors have bucket==3
        assert all(v.id % 10 == 3 for v in row)


def test_vector_id_pre_filter(stack):
    raw, engine, storage, region = stack
    x = rand(100)
    storage.vector_add(region, np.arange(100, dtype=np.int64), x)
    res = storage.vector_batch_search(
        region, x[:1], 10,
        filter_mode=VectorFilterMode.VECTOR_ID,
        vector_ids=[7, 13, 21],
    )
    assert sorted(v.id for v in res[0]) == [7, 13, 21]


def test_bruteforce_fallback_from_untrained_ivf(stack):
    """EVECTOR_NOT_SUPPORT contract (vector_reader.cc:1814-1833): untrained
    IVF search falls back to scanning the engine."""
    raw = MemEngine()
    engine = MonoStoreEngine(raw)
    storage = Storage(engine)
    region = make_region(index_type=IndexType.IVF_FLAT)
    x = rand(120)
    storage.vector_add(region, np.arange(120, dtype=np.int64), x)
    res = storage.vector_batch_search(region, x[:2], 5)
    assert [r[0].id for r in res] == [0, 1]


def test_bruteforce_type_scans_engine(stack):
    region = make_region(index_type=IndexType.BRUTEFORCE)
    raw = MemEngine()
    engine = MonoStoreEngine(raw)
    storage = Storage(engine)
    x = rand(30)
    storage.vector_add(region, np.arange(30, dtype=np.int64), x)
    res = storage.vector_batch_search(region, x[:1], 3)
    assert res[0][0].id == 0


def test_rebuild_from_engine_after_restart(tmp_path):
    """Recovery invariant: the index is a materialized view rebuildable from
    the engine (§3.2/§3.4)."""
    path = str(tmp_path / "wal")
    raw = WalEngine(path)
    engine = MonoStoreEngine(raw)
    storage = Storage(engine)
    region = make_region()
    x = rand(60)
    storage.vector_add(region, np.arange(60, dtype=np.int64), x)
    storage.vector_delete(region, [10, 11])
    raw.close()

    # restart: fresh engine + empty index; rebuild from the data CF
    raw2 = WalEngine(path)
    engine2 = MonoStoreEngine(raw2)
    storage2 = Storage(engine2)
    region2 = make_region()
    reader = engine2.new_vector_reader(region2)
    rows = reader.vector_scan_query(0, limit=10_000, with_vector_data=True)
    assert len(rows) == 58
    w = region2.vector_index_wrapper
    w.add(
        np.asarray([r.id for r in rows], np.int64),
        np.stack([r.vector for r in rows]),
        log_id=1,
    )
    res = storage2.vector_batch_search(region2, x[:1], 3)
    assert res[0][0].id == 0
    assert storage2.vector_count(region2) == 58
    raw2.close()


def test_validation_guards(stack):
    raw, engine, storage, region = stack
    x = rand(10)
    with pytest.raises(InvalidParameter):
        storage.vector_add(region, np.arange(9, dtype=np.int64), x)
    with pytest.raises(InvalidParameter):
        storage.vector_add(
            region, np.arange(5000, dtype=np.int64), rand(5000)
        )
    storage.vector_add(region, np.arange(10, dtype=np.int64), x)
    with pytest.raises(InvalidParameter):
        storage.vector_batch_search(region, x, 100000)


def test_border_ids_and_scan(stack):
    raw, engine, storage, region = stack
    x = rand(20)
    ids = (np.arange(20, dtype=np.int64) + 1) * 5
    storage.vector_add(region, ids, x)
    assert storage.vector_get_border_id(region, get_min=True) == 5
    assert storage.vector_get_border_id(region, get_min=False) == 100
    rows = storage.vector_scan_query(region, start_id=50, limit=3)
    assert [r.id for r in rows] == [50, 55, 60]


def test_kv_surface(stack):
    raw, engine, storage, region = stack
    storage.kv_put(region, [(b"a", b"1"), (b"b", b"2")])
    assert storage.kv_get(region, b"a") == b"1"
    assert storage.kv_put_if_absent(region, [(b"a", b"X"), (b"c", b"3")]) == [
        False,
        True,
    ]
    assert storage.kv_get(region, b"a") == b"1"
    assert storage.kv_compare_and_set(region, b"b", b"2", b"20")
    assert not storage.kv_compare_and_set(region, b"b", b"2", b"30")
    assert storage.kv_get(region, b"b") == b"20"
    storage.kv_batch_delete(region, [b"a"])
    assert storage.kv_get(region, b"a") is None
    got = storage.kv_scan(region, b"a", b"z")
    assert [k for k, _ in got] == [b"b", b"c"]
    assert storage.kv_delete_range(region, [(b"a", b"z")]) == 2  # b, c live
    assert storage.kv_scan(region, b"a", b"z") == []


def test_kv_delete_range_unbounded_end():
    """Empty end key = delete to the end (a region with unbounded end_key):
    the count and the delete must agree — regression for the encoded-b""-
    sorts-below-everything bug."""
    raw = MemEngine()
    engine = MonoStoreEngine(raw)
    storage = Storage(engine)
    definition = RegionDefinition(
        region_id=88, start_key=b"a", end_key=b"",  # unbounded
        partition_id=1, region_type=RegionType.STORE,
    )
    region = Region(definition)
    storage.kv_put(region, [(b"a", b"1"), (b"m", b"2"), (b"\xffzz", b"3")])
    assert storage.kv_delete_range(region, [(b"b", b"")]) == 2
    assert storage.kv_get(region, b"a") == b"1"
    assert storage.kv_get(region, b"m") is None
    assert storage.kv_get(region, b"\xffzz") is None


def test_meta_manager_recovery(tmp_path):
    raw = WalEngine(str(tmp_path / "meta"))
    mm = StoreMetaManager(raw)
    region = make_region()
    mm.add_region(region)
    raw.close()
    raw2 = WalEngine(str(tmp_path / "meta"))
    mm2 = StoreMetaManager(raw2)
    assert mm2.recover() == 1
    r = mm2.get_region(77)
    assert r is not None and r.definition.partition_id == 1
    raw2.close()


class _CfSpyEngine:
    """RawEngine proxy recording which CFs reads touch."""

    def __init__(self, inner):
        self._inner = inner
        self.read_cfs = []

    def get(self, cf, key):
        self.read_cfs.append(cf)
        return self._inner.get(cf, key)

    def scan(self, cf, start=b"", end=None):
        self.read_cfs.append(cf)
        return self._inner.scan(cf, start, end)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_scalar_speedup_cf_pre_filter():
    """Scalar speed-up CF end-to-end (raft_apply_handler.cc:1115 via
    SplitVectorScalarData + constant.h kVectorScalarKeySpeedUpCF): with
    scalar_speedup_keys flagged, apply writes the flagged subset to the
    narrow CF; a covered pre-filter search reads no scalar CF at all
    while the index mirrors the flagged fields per slot, and ONLY the
    narrow CF on the host path (no serving index); results are identical
    to the wide-CF path."""
    from dingo_tpu.engine.raw_engine import (
        CF_VECTOR_SCALAR,
        CF_VECTOR_SCALAR_SPEEDUP,
    )
    from dingo_tpu.index.vector_reader import deserialize_scalar

    def build(speedup_keys):
        raw = MemEngine()
        engine = MonoStoreEngine(raw)
        storage = Storage(engine)
        definition = RegionDefinition(
            region_id=88,
            start_key=vcodec.encode_vector_key(1, 0),
            end_key=vcodec.encode_vector_key(1, 1 << 40),
            partition_id=1,
            region_type=RegionType.INDEX,
            index_parameter=IndexParameter(
                index_type=IndexType.FLAT, dimension=DIM,
                scalar_speedup_keys=speedup_keys,
            ),
        )
        region = Region(definition)
        w = region.vector_index_wrapper
        w.build_own()
        w.set_own(w.own_index)
        x = rand(200, seed=7)
        ids = np.arange(200, dtype=np.int64)
        # WIDE scalars: many fields, only "color" is flagged
        scalars = [
            {"color": "red" if i % 5 == 0 else "blue", "size": i,
             "shape": "s" + str(i % 7), "w0": i * 2, "w1": i * 3,
             "w2": "x" * 50}
            for i in range(200)
        ]
        storage.vector_add(region, ids, x, scalars)
        return raw, engine, storage, region, x

    # flagged region: narrow CF holds ONLY the flagged subset
    raw, engine, storage, region, x = build(("color",))
    narrow_rows = list(raw.scan(CF_VECTOR_SCALAR_SPEEDUP, b"", None))
    assert len(narrow_rows) == 200
    from dingo_tpu.mvcc.codec import Codec as _C

    _flag, payload, _ttl = _C.unpackage_value(narrow_rows[0][1])
    assert set(deserialize_scalar(payload)) == {"color"}

    # covered pre-filter search reads only the narrow CF
    spy = _CfSpyEngine(raw)
    reader = engine.new_vector_reader(region)
    reader.ctx = dataclasses_replace_engine(reader.ctx, spy)
    reader._scalar.engine = spy
    reader._speedup.engine = spy
    reader._data.engine = spy
    res_narrow = reader.vector_batch_search(
        x[:8], 10, filter_mode=VectorFilterMode.SCALAR,
        filter_type=VectorFilterType.QUERY_PRE,
        scalar_filter=ScalarFilter.equals({"color": "red"}),
    )
    assert CF_VECTOR_SCALAR_SPEEDUP not in spy.read_cfs
    assert CF_VECTOR_SCALAR not in spy.read_cfs, (
        "covered pre-filter touched the wide scalar CF")
    # no serving index, no columns: the host path reads the narrow CF
    host = engine.new_vector_reader(region)
    host.ctx = dataclasses_replace_engine(host.ctx, spy)
    host.ctx.index_wrapper = None
    for cf_reader in (host._scalar, host._speedup, host._data):
        cf_reader.engine = spy
    res_host = host.vector_batch_search(
        x[:8], 10, filter_mode=VectorFilterMode.SCALAR,
        filter_type=VectorFilterType.QUERY_PRE,
        scalar_filter=ScalarFilter.equals({"color": "red"}),
    )
    assert CF_VECTOR_SCALAR_SPEEDUP in spy.read_cfs
    assert CF_VECTOR_SCALAR not in spy.read_cfs, (
        "covered pre-filter touched the wide scalar CF")
    for a, b in zip(res_narrow, res_host):
        assert [v.id for v in a] == [v.id for v in b]

    # identical results to a region WITHOUT the speed-up CF
    raw2, engine2, storage2, region2, x2 = build(())
    res_wide = storage2.vector_batch_search(
        region2, x2[:8], 10, filter_mode=VectorFilterMode.SCALAR,
        filter_type=VectorFilterType.QUERY_PRE,
        scalar_filter=ScalarFilter.equals({"color": "red"}),
    )
    for a, b in zip(res_narrow, res_wide):
        assert [v.id for v in a] == [v.id for v in b]

    # an UNCOVERED filter (field not flagged) falls back to the wide CF
    spy.read_cfs.clear()
    reader.vector_batch_search(
        x[:2], 5, filter_mode=VectorFilterMode.SCALAR,
        filter_type=VectorFilterType.QUERY_PRE,
        scalar_filter=ScalarFilter.equals({"size": 5}),
    )
    assert CF_VECTOR_SCALAR in spy.read_cfs

    # deletes tombstone the narrow CF too
    storage.vector_delete(region, [0, 5])
    reader2 = engine.new_vector_reader(region)
    res_after = reader2.vector_batch_search(
        x[:1], 5, filter_mode=VectorFilterMode.SCALAR,
        filter_type=VectorFilterType.QUERY_PRE,
        scalar_filter=ScalarFilter.equals({"color": "red"}),
    )
    got_after = [v.id for v in res_after[0]]
    assert 0 not in got_after and 5 not in got_after


def dataclasses_replace_engine(ctx, engine):
    import dataclasses as _dc

    return _dc.replace(ctx, engine=engine)


def test_table_coprocessor_filter_pre_and_post():
    """VECTOR_FILTER=TABLE (vector_reader.cc:169-232): table rows ride
    VectorAdd into the vector_table CF; search dispatches the coprocessor
    filter over them — pre variant scans the table CF into a candidate id
    set, post variant over-fetches x10 then filters candidates' rows."""
    from dingo_tpu.coprocessor.coprocessor_v2 import (
        CoprocessorDef,
        CoprocessorV2,
        SchemaColumn,
        encode_row,
    )

    raw = MemEngine()
    engine = MonoStoreEngine(raw)
    storage = Storage(engine)
    region = make_region(region_id=99)
    x = rand(200, seed=3)
    ids = np.arange(200, dtype=np.int64)
    schema = [
        SchemaColumn("dept", "VARCHAR", 0),
        SchemaColumn("salary", "DOUBLE", 1),
    ]
    rows = [
        ["eng" if i % 3 == 0 else "ops", float(50 + i)] for i in range(200)
    ]
    storage.vector_add(region, ids, x,
                       table_values=[encode_row(r) for r in rows])

    cop = CoprocessorV2(CoprocessorDef(
        original_schema=schema,
        filter_expr=["and", ["eq", ["field", "dept"], ["const", "eng"]],
                     ["ge", ["field", "salary"], ["const", 100.0]]],
    ))
    want = {i for i in range(200) if i % 3 == 0 and 50 + i >= 100}

    reader = engine.new_vector_reader(region)
    res_pre = reader.vector_batch_search(
        x[:4], 20, filter_mode=VectorFilterMode.TABLE,
        filter_type=VectorFilterType.QUERY_PRE, coprocessor=cop,
    )
    for qi, row in enumerate(res_pre):
        assert row, "pre-filter returned nothing"
        assert all(v.id in want for v in row), [v.id for v in row]

    res_post = reader.vector_batch_search(
        x[60:62], 5, filter_mode=VectorFilterMode.TABLE,
        filter_type=VectorFilterType.QUERY_POST, coprocessor=cop,
    )
    for row in res_post:
        assert all(v.id in want for v in row)
    # query 60: 60 % 3 == 0 and salary 110 -> its own id must lead
    assert res_post[0][0].id == 60

    # missing coprocessor is a hard error, not a silent no-filter
    with pytest.raises(ValueError):
        reader.vector_batch_search(
            x[:1], 5, filter_mode=VectorFilterMode.TABLE,
            filter_type=VectorFilterType.QUERY_PRE,
        )

    # deletes tombstone the table CF: deleted ids drop out of pre-filter
    storage.vector_delete(region, [60])
    reader2 = engine.new_vector_reader(region)
    res2 = reader2.vector_batch_search(
        x[60:61], 10, filter_mode=VectorFilterMode.TABLE,
        filter_type=VectorFilterType.QUERY_PRE, coprocessor=cop,
    )
    assert all(v.id != 60 for v in res2[0])


def test_speedup_cf_upsert_drops_flagged_field():
    """Regression: an upsert that drops every flagged field must tombstone
    the narrow CF — otherwise the stale narrow row stays visible and a
    covered filter diverges from the wide path."""
    raw = MemEngine()
    engine = MonoStoreEngine(raw)
    storage = Storage(engine)
    definition = RegionDefinition(
        region_id=91,
        start_key=vcodec.encode_vector_key(1, 0),
        end_key=vcodec.encode_vector_key(1, 1 << 40),
        partition_id=1,
        region_type=RegionType.INDEX,
        index_parameter=IndexParameter(
            index_type=IndexType.FLAT, dimension=DIM,
            scalar_speedup_keys=("color",),
        ),
    )
    region = Region(definition)
    w = region.vector_index_wrapper
    w.build_own()
    w.set_own(w.own_index)
    x = rand(4, seed=9)
    ids = np.arange(4, dtype=np.int64)
    storage.vector_add(region, ids, x,
                       [{"color": "red", "n": int(i)} for i in ids])
    # upsert id 0 WITHOUT the flagged field
    storage.vector_add(region, ids[:1], x[:1], [{"n": 100}])
    reader = engine.new_vector_reader(region)
    res = reader.vector_batch_search(
        x[:1], 4, filter_mode=VectorFilterMode.SCALAR,
        filter_type=VectorFilterType.QUERY_PRE,
        scalar_filter=ScalarFilter.equals({"color": "red"}),
    )
    got = [v.id for v in res[0]]
    assert 0 not in got, (
        "stale narrow-CF row survived an upsert that dropped the field")
    assert set(got) == {1, 2, 3}


def test_table_row_clear_with_empty_bytes():
    """Per-entry table semantics: None leaves the row, b'' clears it."""
    from dingo_tpu.coprocessor.coprocessor_v2 import (
        CoprocessorDef,
        CoprocessorV2,
        SchemaColumn,
        encode_row,
    )

    raw = MemEngine()
    engine = MonoStoreEngine(raw)
    storage = Storage(engine)
    region = make_region(region_id=92)
    x = rand(3, seed=4)
    ids = np.arange(3, dtype=np.int64)
    storage.vector_add(
        region, ids, x,
        table_values=[encode_row(["eng"]) for _ in range(3)])
    cop = CoprocessorV2(CoprocessorDef(
        original_schema=[SchemaColumn("dept", "VARCHAR", 0)],
        filter_expr=["eq", ["field", "dept"], ["const", "eng"]],
    ))
    reader = engine.new_vector_reader(region)
    res = reader.vector_batch_search(
        x[:1], 3, filter_mode=VectorFilterMode.TABLE,
        filter_type=VectorFilterType.QUERY_PRE, coprocessor=cop)
    assert {v.id for v in res[0]} == {0, 1, 2}

    # upsert id 1 clearing its table row, id 2 untouched (None)
    storage.vector_add(region, ids[1:3], x[1:3],
                       table_values=[b"", None])
    reader = engine.new_vector_reader(region)
    res = reader.vector_batch_search(
        x[:1], 3, filter_mode=VectorFilterMode.TABLE,
        filter_type=VectorFilterType.QUERY_PRE, coprocessor=cop)
    assert {v.id for v in res[0]} == {0, 2}
