"""convert.float_rows_from_pb: a request's float rows go from the packed
bytes of ``Vector.values`` to one float32 array, bit for bit what the
boxed line (a Python float per element) gives, and whatever is not one
packed run goes through the boxed line and is counted."""

import struct
import time

import numpy as np
import pytest

from dingo_tpu.common.metrics import METRICS
from dingo_tpu.server import convert, pb
from dingo_tpu.server.convert import float_rows_from_pb, packed_float_run

WIRE = "service.decode_wire_rows"
BOXED = "service.decode_boxed_rows"

#: quiet NaNs with payloads, infinities, signed zeros, denormals and the
#: ends of the normal range, as float32 bit patterns
SPECIAL_BITS = np.array([
    0x7FC00000, 0xFFC00000, 0x7FC00001, 0xFFD12345, 0x7FFFFFFF,
    0x7F800000, 0xFF800000, 0x00000000, 0x80000000,
    0x00000001, 0x80000001, 0x007FFFFF, 0x00800000, 0x7F7FFFFF,
], np.uint32)


def boxed(vectors) -> np.ndarray:
    """The line float_rows_from_pb replaced, kept here as the reference."""
    return np.asarray([list(v.values) for v in vectors], np.float32)


def rows(n: int, d: int, seed: int = 0) -> np.ndarray:
    """[n, d] float32 with every special pattern in it (as far as n * d
    has room), the rest normal draws."""
    x = np.random.default_rng(seed).standard_normal((n, d)).astype(np.float32)
    flat = x.reshape(-1)
    k = min(len(SPECIAL_BITS), flat.size)
    at = np.random.default_rng(seed + 1).choice(flat.size, k, replace=False)
    flat.view(np.uint32)[at] = SPECIAL_BITS[:k]
    return x


def vector_pbs(x: np.ndarray, ids=None, binary=None):
    """Parsed ``pb.Vector`` messages as a store receives them: built the
    SDK's way (``values.extend(row.tolist())``), serialised, parsed."""
    out = []
    for i, row in enumerate(x):
        v = pb.Vector()
        if ids is not None:
            v.id = int(ids[i])
        v.values.extend(row.tolist())
        if binary is not None:
            v.binary_values = binary
        out.append(pb.Vector.FromString(v.SerializeToString()))
    return out


def counts():
    return METRICS.counter(WIRE).get(), METRICS.counter(BOXED).get()


def same_bits(a: np.ndarray, b: np.ndarray) -> bool:
    return a.shape == b.shape and a.dtype == b.dtype == np.float32 \
        and np.array_equal(a.view(np.uint32), b.view(np.uint32))


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def run_bytes(row) -> bytes:
    """One packed run of field 2."""
    payload = np.asarray(row, "<f4").tobytes()
    return b"\x12" + varint(len(payload)) + payload


class AsArrived:
    """A Vector whose serialiser gives back the bytes it was parsed from.
    (upb, the runtime installed here, re-serialises ``values`` as one
    packed run whatever form arrived, so only such a stand-in shows the
    helper a form a parser accepts and a serialiser never writes.)"""

    def __init__(self, wire: bytes):
        self._wire = wire
        self.values = pb.Vector.FromString(wire).values

    def SerializeToString(self) -> bytes:
        return self._wire


@pytest.mark.parametrize("d", [1, 768, 1536])
@pytest.mark.parametrize("n", [1, 64, 4096])
def test_bit_identical_with_the_boxed_line(n, d):
    x = rows(n, d, seed=n + d)
    vectors = vector_pbs(x)
    wire0, boxed0 = counts()
    got = float_rows_from_pb(vectors)
    assert counts() == (wire0 + n, boxed0)
    assert same_bits(got, x)
    assert same_bits(got, boxed(vectors))


def test_signalling_nan_keeps_the_wire_bits():
    """The one pattern the boxed line alters: float -> double -> float
    sets a signalling NaN's quiet bit. The wire path copies the bytes."""
    x = np.array([[0x7F800001, 0xFFA00000, 0x3F800000]], np.uint32).view(
        np.float32)
    v = pb.Vector.FromString(run_bytes(x[0]))
    assert same_bits(float_rows_from_pb([v]), x)


@pytest.mark.parametrize("ids", [None, "small", "ten_byte_varints"])
@pytest.mark.parametrize("binary", [None, b"", b"\x12\x04abcd" * 40])
def test_id_and_binary_values_around_the_run(ids, binary):
    """``id`` set or unset (1 to 10 bytes of varint before the run) and
    ``binary_values`` after it (bytes that look like a run of field 2
    among them): the reader walks the fields, it does not count back
    from the end."""
    x = rows(5, 24, seed=3)
    id_values = {None: None, "small": [0, 1, 127, 128, 300],
                 "ten_byte_varints": [-1, -2, 2**62, 2**63 - 1, -2**63]}[ids]
    vectors = vector_pbs(x, ids=id_values, binary=binary)
    wire0, boxed0 = counts()
    got = float_rows_from_pb(vectors)
    assert counts() == (wire0 + 5, boxed0)
    assert same_bits(got, x)


def test_unknown_fields_are_stepped_over():
    row = rows(1, 8, seed=5)[0]
    unknown = (b"\xa0\x06\x96\x01"             # field 100, varint 150
               + b"\xa9\x06" + b"\x01" * 8      # field 101, fixed64
               + b"\xb2\x06\x03xyz"             # field 102, 3 bytes
               + b"\xbd\x06" + b"\x02" * 4)     # field 103, fixed32
    wire = unknown + b"\x08\x07" + run_bytes(row) + unknown
    offset, size = packed_float_run(wire)
    assert wire[offset:offset + size] == row.tobytes()
    # the runtime keeps unknown fields and writes them back
    v = pb.Vector.FromString(wire)
    assert len(v.SerializeToString()) == len(wire)
    wire0, boxed0 = counts()
    assert same_bits(float_rows_from_pb([v]), row[None])
    assert counts() == (wire0 + 1, boxed0)


FORMS = {
    "unpacked": lambda row: b"".join(
        b"\x15" + struct.pack("<f", f) for f in row),
    "two_packed_runs": lambda row: run_bytes(row[:3]) + run_bytes(row[3:]),
    "packed_then_unpacked": lambda row: run_bytes(row[:-1])
    + b"\x15" + struct.pack("<f", row[-1]),
}


@pytest.mark.parametrize("form", sorted(FORMS))
def test_forms_a_parser_accepts_take_the_boxed_line(form):
    """``values`` unpacked or in several runs is legal input: the parser
    accepts it, the reader says "not one packed run", and the whole
    request is decoded by the boxed line to the same array."""
    x = rows(6, 7, seed=11)
    x.view(np.uint32)[x != x] = 0x7FC00000   # the boxed line's own NaN
    wires = [b"\x08" + varint(i + 1) + FORMS[form](row)
             for i, row in enumerate(x)]
    for i, w in enumerate(wires):
        assert packed_float_run(w) is None
        assert pb.Vector.FromString(w).id == i + 1
    wire0, boxed0 = counts()
    got = float_rows_from_pb([AsArrived(w) for w in wires])
    assert counts() == (wire0, boxed0 + 6)
    assert same_bits(got, x)
    # parsed and re-serialised by the installed runtime the same rows are
    # one packed run each
    wire0, boxed0 = counts()
    again = float_rows_from_pb([pb.Vector.FromString(w) for w in wires])
    assert counts() == (wire0 + 6, boxed0)
    assert same_bits(again, x)


def test_one_odd_row_sends_the_whole_request_to_the_boxed_line():
    x = rows(4, 5, seed=13)
    x.view(np.uint32)[x != x] = 0x7FC00000
    vectors = vector_pbs(x)
    vectors[2] = AsArrived(FORMS["unpacked"](x[2]))
    wire0, boxed0 = counts()
    assert same_bits(float_rows_from_pb(vectors), x)
    assert counts() == (wire0, boxed0 + 4)


@pytest.mark.parametrize("wire", [
    b"\x12\x05" + b"\x00" * 5,        # a run that is no whole floats
    b"\x12\x08" + b"\x00" * 4,        # a run longer than the message
    b"\x12",                          # a tag and nothing
    b"\x12\xff",                      # a length that never ends
    b"\x13",                          # a group: not walked
    b"\x08",                          # a varint that never starts
], ids=["odd_size", "overrun", "bare_tag", "open_varint", "group",
        "open_id"])
def test_bytes_the_reader_cannot_walk(wire):
    assert packed_float_run(wire) is None


def test_reader_on_a_message_without_values():
    assert packed_float_run(b"") == (0, 0)
    assert packed_float_run(b"\x08\x05\x1a\x02ab") == (0, 0)


@pytest.mark.parametrize("lengths", [(3, 2), (2, 3), (3, 0), (0, 3),
                                     (4, 4, 5)])
def test_ragged_rows_refused_as_today(lengths):
    vectors = [pb.Vector(values=[1.0] * k) for k in lengths]
    with pytest.raises(ValueError) as today:
        boxed(vectors)
    before = counts()
    with pytest.raises(ValueError) as now:
        float_rows_from_pb(vectors)
    assert str(now.value) == str(today.value)
    assert counts() == before


def test_empty_request_as_today():
    before = counts()
    got = float_rows_from_pb([])
    want = boxed([])
    assert got.shape == want.shape == (0,) and got.dtype == want.dtype
    assert counts() == before


@pytest.mark.parametrize("n", [1, 3])
def test_rows_without_values_as_today(n):
    vectors = [pb.Vector(id=i) for i in range(n)]
    got = float_rows_from_pb(vectors)
    want = boxed(vectors)
    assert got.shape == want.shape == (n, 0) and got.dtype == want.dtype


@pytest.mark.parametrize("n,d", [(1, 1), (1, 768), (64, 768)])
def test_result_owned_writable_contiguous(n, d):
    x = rows(n, d, seed=17)
    got = float_rows_from_pb(vector_pbs(x))
    assert got.flags.owndata and got.flags.writeable
    assert got.flags.c_contiguous and got.flags.aligned
    assert got.dtype == np.float32 and got.dtype.isnative
    got += 1.0                                   # the index may write to it
    assert same_bits(got, x + np.float32(1.0))


def test_queries_from_pb_binary_arm_unchanged():
    packed = [bytes([i, 255 - i, 7, 0]) for i in range(3)]
    vectors = [pb.Vector(binary_values=b, values=[1.0, 2.0]) for b in packed]
    before = counts()
    got = convert.queries_from_pb(vectors, binary=True)
    assert got.dtype == np.uint8 and got.tolist() == [list(b) for b in packed]
    assert counts() == before
    assert same_bits(convert.queries_from_pb(vectors),
                     np.array([[1.0, 2.0]] * 3, np.float32))


# ------------------------------------------------- through the served path
@pytest.fixture(scope="module")
def cluster():
    """Coordinator + one store (replication 1) behind real gRPC, one FLAT
    region of 24-d rows; yields (client, node, rows, region id)."""
    from dingo_tpu.client import DingoClient
    from dingo_tpu.coordinator.control import CoordinatorControl
    from dingo_tpu.coordinator.kv_control import KvControl
    from dingo_tpu.coordinator.tso import TsoControl
    from dingo_tpu.engine.raw_engine import MemEngine
    from dingo_tpu.raft import LocalTransport
    from dingo_tpu.server.rpc import DingoServer
    from dingo_tpu.store.node import StoreNode

    me = MemEngine()
    control = CoordinatorControl(me, replication=1)
    cs = DingoServer()
    cs.host_coordinator_role(control, TsoControl(me), KvControl(me))
    cport = cs.start()
    node = StoreNode("s0", LocalTransport(), control, raft_kw={"seed": 0})
    srv = DingoServer()
    srv.host_store_role(node)
    port = srv.start()
    node.start_heartbeat(0.1)
    client = DingoClient(f"127.0.0.1:{cport}", {"s0": f"127.0.0.1:{port}"})
    try:
        definition = client.create_index_region(0, 0, 1 << 30, pb.VectorIndexParameter(
            dimension=24, metric_type=pb.METRIC_TYPE_L2,
            index_type=pb.VECTOR_INDEX_TYPE_FLAT))
        time.sleep(1.0)
        x = np.random.default_rng(23).standard_normal((96, 24)).astype(
            np.float32)
        x.view(np.uint32)[0, :3] = [0x80000000, 0x00000001, 0x807FFFFF]
        yield client, node, x, definition.region_id
    finally:
        client.close()
        srv.stop()
        cs.stop()
        node.stop()


def test_counters_exist_at_zero_from_the_services_creation(monkeypatch):
    from dingo_tpu.common.metrics import MetricsRegistry
    from dingo_tpu.server import services

    fresh = MetricsRegistry()
    monkeypatch.setattr(services, "METRICS", fresh)
    services.IndexService(node=None)
    dump = fresh.dump()
    assert dump[WIRE] == 0 and dump[BOXED] == 0


def test_sdk_built_requests_round_trip(cluster, monkeypatch):
    """What the SDK's own builders serialise (``vector_add`` and
    ``vector_search``, unchanged by this helper) decodes to the arrays
    they were given, bit for bit, on the wire path; and the store, fed
    over gRPC, answers each row as its own nearest neighbour."""
    client, _node, x, _region_id = cluster
    sent = []
    call = client._call_leader

    def recording(definition, service, method, req, *a, **kw):
        sent.append((method, req.SerializeToString()))
        return call(definition, service, method, req, *a, **kw)

    monkeypatch.setattr(client, "_call_leader", recording)
    wire0, boxed0 = counts()
    client.vector_add(0, list(range(64)), x[:64])
    res = client.vector_search(0, x[:64], topk=1)
    assert [r[0][0] for r in res] == list(range(64))
    assert counts() == (wire0 + 128, boxed0)
    (add,) = [b for m, b in sent if m == "VectorAdd"]
    (search,) = [b for m, b in sent if m == "VectorSearch"]
    added = pb.VectorAddRequest.FromString(add).vectors
    assert [v.vector.id for v in added] == list(range(64))
    assert same_bits(float_rows_from_pb([v.vector for v in added]), x[:64])
    assert same_bits(float_rows_from_pb(
        pb.VectorSearchRequest.FromString(search).vectors), x[:64])


def test_add_then_search_through_the_in_process_services(cluster):
    """VectorAdd then VectorSearch on the service objects themselves: the
    row is its own nearest neighbour at distance 0, every row went the
    wire path and none the boxed one."""
    from dingo_tpu.server.services import IndexService

    _client, node, x, region_id = cluster
    svc = IndexService(node)
    fresh = x[64:]
    add = pb.VectorAddRequest()
    add.context.region_id = region_id
    for i, row in enumerate(fresh):
        v = add.vectors.add()
        v.vector.id = 1000 + i
        v.vector.values.extend(row.tolist())
    wire0, boxed0 = counts()
    resp = svc.VectorAdd(pb.VectorAddRequest.FromString(
        add.SerializeToString()))
    assert resp.error.errcode == 0, resp.error.errmsg
    search = pb.VectorSearchRequest()
    search.context.region_id = add.context.region_id
    for row in fresh:
        search.vectors.add().values.extend(row.tolist())
    search.parameter.top_n = 1
    resp = svc.VectorSearch(pb.VectorSearchRequest.FromString(
        search.SerializeToString()))
    assert resp.error.errcode == 0, resp.error.errmsg
    hits = [r.results[0] for r in resp.batch_results]
    assert [h.vector.id for h in hits] == \
        [1000 + i for i in range(len(fresh))]
    assert all(h.distance < 1e-5 for h in hits)
    wire1, boxed1 = counts()
    assert wire1 - wire0 == 2 * len(fresh) and boxed1 - boxed0 == 0


def test_ragged_request_refused_with_30001_through_the_service(cluster):
    from dingo_tpu.server.services import IndexService

    _client, node, _x, region_id = cluster
    svc = IndexService(node)
    req = pb.VectorAddRequest()
    req.context.region_id = region_id
    for i, k in enumerate((24, 23)):
        v = req.vectors.add()
        v.vector.id = 5000 + i
        v.vector.values.extend([0.5] * k)
    resp = svc.VectorAdd(req)
    assert resp.error.errcode == 30001
    assert "inhomogeneous" in resp.error.errmsg
