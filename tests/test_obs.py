"""Device-runtime observability (dingo_tpu/obs): recompile sentinel, HBM
watermark ledger, and the flight recorder.

Acceptance (ISSUE 5): the sentinel proves the steady-state no-recompile
invariant end-to-end (warmup + mixed upsert/search leaves xla.recompiles
unchanged; a novel shape increments it and records an xla.compile span);
a slow-query fault yields a FlightDump bundle tools/flight_report.py
renders with the triggering trace's spans, metric deltas, and kernel
cache state; and the Prometheus exposition carries a matching exemplar
trace id.
"""

import importlib
import itertools
import json
import logging
import time
import zlib

import grpc
import numpy as np
import pytest

import jax.numpy as jnp

from dingo_tpu.common.config import FLAGS
from dingo_tpu.common.failpoint import FAILPOINTS
from dingo_tpu.common.metrics import METRICS, MetricsRegistry
from dingo_tpu.obs import FLIGHT, HBM, SENTINEL, looks_like_oom, sentinel_jit
from dingo_tpu.obs import flight as flight_mod
from dingo_tpu.trace import TRACE_BUFFER, TRACER

flight_report = importlib.import_module("tools.flight_report")

_seq = itertools.count()


def _kname():
    """Unique kernel name per test (the sentinel registry is process-global)."""
    return f"test.kernel_{next(_seq)}"


@pytest.fixture()
def obs_env():
    """Clean flight/trace state + restored observability flags."""
    saved = {k: FLAGS.get(k) for k in (
        "trace_sampling_rate", "slow_query_ms", "obs_flight_max_bundles",
        "obs_flight_buffer_s", "obs_exemplars",
    )}
    FLIGHT.clear()
    TRACE_BUFFER.clear()
    try:
        yield
    finally:
        for k, v in saved.items():
            FLAGS.set(k, v)
        FLIGHT.clear()
        TRACE_BUFFER.clear()


# ---------------------------------------------------------------------------
# recompile sentinel
# ---------------------------------------------------------------------------

def test_sentinel_counts_traces_and_hits(obs_env):
    # the background rule: compile spans are recorded at ANY rate > 0,
    # whatever the head roll says (at 0 tracing is off altogether)
    FLAGS.set("trace_sampling_rate", 1e-9)
    name = _kname()

    @sentinel_jit(name, static_argnames=("k",))
    def scaled_sum(x, k):
        return jnp.sum(x) * k

    total0 = METRICS.counter("xla.recompiles").get()
    kern_c = METRICS.counter("xla.recompiles_by_kernel",
                             labels={"kernel": name})
    hits_c = METRICS.counter("xla.cache_hits", labels={"kernel": name})

    scaled_sum(jnp.ones(8), 2)          # trace 1 (static k positional)
    scaled_sum(jnp.ones(8), 2)          # hit
    scaled_sum(jnp.ones(8), 2)          # hit
    scaled_sum(jnp.ones(16), 2)         # trace 2: new shape
    scaled_sum(jnp.ones(8), 3)          # trace 3: new static value

    assert kern_c.get() == 3
    assert hits_c.get() == 2
    assert METRICS.counter("xla.recompiles").get() - total0 == 3
    st = SENTINEL.state()[name]
    assert st["calls"] == 5 and st["traces"] == 3 and st["cache_hits"] == 2
    assert st["compile_ms_total"] > 0
    # signature labels carry dtype + shape of the novel call
    assert any("float32[16]" in s for s in st["signatures"])
    # each compile recorded an xla.compile span (whatever the head roll)
    compiles = [s for s in TRACE_BUFFER.snapshot()
                if s["name"] == "xla.compile"
                and s["attrs"].get("kernel") == name]
    assert len(compiles) == 3
    assert all(s["attrs"]["ms"] > 0 for s in compiles)


def test_sentinel_compile_span_joins_sampled_trace(obs_env):
    FLAGS.set("trace_sampling_rate", 1.0)
    name = _kname()

    @sentinel_jit(name)
    def double(x):
        return x * 2

    with TRACER.start_span("test.compile_parent") as root:
        double(jnp.ones(4))
        trace_id = f"{root.trace_id:016x}"
    spans = TRACE_BUFFER.snapshot(trace_id=trace_id)
    compile_spans = [s for s in spans if s["name"] == "xla.compile"]
    assert len(compile_spans) == 1
    # parented under the victim request, not a fragment root
    assert compile_spans[0]["parent_id"] == \
        next(s for s in spans if s["name"] == "test.compile_parent")["span_id"]


def test_sentinel_donation_still_works(obs_env):
    name = _kname()

    @sentinel_jit(name, donate_argnums=(0,))
    def bump(v, delta):
        return v + delta

    v = jnp.ones(4)
    out = bump(v, jnp.ones(4))
    assert float(out[0]) == 2.0
    assert SENTINEL.state()[name]["traces"] == 1


def test_steady_state_invariant_end_to_end(obs_env):
    """THE acceptance invariant: after warmup (searches AND one write
    round), a mixed upsert/delete/search workload never touches the XLA
    compile cache; a deliberately novel shape does, and records the
    compile as an xla.compile span."""
    from dingo_tpu.index import IndexParameter, IndexType, new_index

    rng = np.random.default_rng(5)
    n, d = 2048, 24
    x = rng.standard_normal((n, d), dtype=np.float32)
    ids = np.arange(n, dtype=np.int64)
    idx = new_index(950, IndexParameter(
        index_type=IndexType.IVF_FLAT, dimension=d,
        ncentroids=8, default_nprobe=4,
    ))
    idx.store.reserve(n + 512)
    idx.upsert(ids, x)
    idx.train()
    idx.warmup(batches=(8,), topk=10, nprobe=4)
    # force every list onto its spill chain NOW: the dense build packs
    # each bucket full, so the first writes allocate spill buckets and
    # step the alloc ladder — that step must happen during warmup, not
    # mid-measurement
    extra = np.arange(n, n + 400, dtype=np.int64)
    idx.upsert(extra, rng.standard_normal((400, d)).astype(np.float32))

    def mixed_round():
        sel = rng.choice(n, 48, replace=False)
        idx.delete(ids[sel[:24]])
        idx.upsert(ids[sel], x[sel])
        res = idx.search(x[:8], 10, nprobe=4)
        assert len(res) == 8

    # write-path warmup: search warmup can't reach the scatter/tombstone
    # buckets (and the per-round append sizes land in a couple of pow2
    # pads). Steady state is reached when two consecutive rounds leave
    # the jit cache untouched; 12 rounds is the failure bound.
    c = METRICS.counter("xla.recompiles")
    clean = 0
    for _ in range(12):
        before = c.get()
        mixed_round()
        clean = clean + 1 if c.get() == before else 0
        if clean >= 2:
            break
    else:
        pytest.fail(
            "mixed workload never reached trace-free rounds:"
            f" {dict((k, v) for k, v in SENTINEL.state().items() if v['traces'])}"
        )

    # THE invariant: once steady, sustained mixed traffic stays trace-free
    before = c.get()
    for _ in range(4):
        mixed_round()
    assert c.get() - before == 0, (
        "steady-state mixed workload recompiled:"
        f" {dict((k, v) for k, v in SENTINEL.state().items() if v['traces'])}"
    )

    # novel batch shape (beyond every warmed bucket) must recompile and
    # leave compile evidence (a background span: any sampling rate > 0)
    FLAGS.set("trace_sampling_rate", 1e-9)
    TRACE_BUFFER.clear()
    idx.search(x[:200], 10, nprobe=4)
    assert c.get() - before >= 1
    compiles = [s for s in TRACE_BUFFER.snapshot()
                if s["name"] == "xla.compile"]
    assert compiles and all(s["attrs"]["kernel"] for s in compiles)


# ---------------------------------------------------------------------------
# hbm ledger
# ---------------------------------------------------------------------------

def test_hbm_ledger_owner_attribution_and_watermark(obs_env):
    from dingo_tpu.index import IndexParameter, IndexType, new_index

    rid = 960
    HBM.forget_region(rid)
    idx = new_index(rid, IndexParameter(
        index_type=IndexType.FLAT, dimension=16,
    ))
    idx.upsert(np.arange(64, dtype=np.int64),
               np.ones((64, 16), np.float32))
    idx.search(np.ones((2, 16), np.float32), 4)
    owners = HBM.account_index(rid, idx)
    assert owners.get("slot_store", 0) > 0
    total = sum(owners.values())
    assert HBM.region_peak(rid) == total
    # shrink the region: current gauges drop, the watermark holds
    HBM.update_region(rid, {"slot_store": 10})
    assert HBM.region_peak(rid) == total
    g = METRICS.gauge("hbm.region.bytes", rid, labels={"owner": "slot_store"})
    assert g.get() == 10
    assert METRICS.gauge("hbm.region.total_peak_bytes", rid).get() == total
    st = HBM.state()
    assert st["regions"][rid]["total_peak_bytes"] == total
    HBM.forget_region(rid)
    assert HBM.region_peak(rid) == 0


def test_hbm_owner_attribution_dedupes_shared_arrays(obs_env):
    from types import SimpleNamespace

    arr = jnp.ones((32, 8))
    # the walker recurses plain containers and dingo_tpu objects; the
    # SAME buffer reachable from both owners must be charged exactly once
    fake = SimpleNamespace(store=[arr], _view=[arr])
    owners = HBM.account_index(961, fake)
    # charged once: view walks first (most-specific), store sees the dup
    assert owners.get("ivf_view", 0) == arr.nbytes
    assert owners.get("slot_store", 0) == 0
    HBM.forget_region(961)


def test_hbm_alloc_failure_hook(obs_env):
    FLIGHT.clear()
    c0 = METRICS.counter("hbm.alloc_failures").get()
    assert HBM.on_alloc_failure(ValueError("bad nprobe")) is None
    assert METRICS.counter("hbm.alloc_failures").get() == c0
    bid = HBM.on_alloc_failure(
        RuntimeError("RESOURCE_EXHAUSTED: Out of memory while trying to "
                     "allocate 137438953472 bytes"),
        context="VectorSearch", region_id=7,
    )
    assert bid
    assert METRICS.counter("hbm.alloc_failures").get() == c0 + 1
    metas = FLIGHT.bundles_meta()
    assert metas[-1]["reason"] == "device_oom"
    assert metas[-1]["region_id"] == 7
    bundle = FLIGHT.get_json(bid)
    assert "RESOURCE_EXHAUSTED" in bundle["trigger"]["error"]
    assert "hbm" in bundle and "kernel_cache" in bundle


def test_oom_rpc_path_keeps_trace_linked_bundle(obs_env):
    """rpc error arm ordering: the trace-linked device_oom bundle wins;
    the ledger hook only counts (capture=False) instead of burning the
    per-reason rate limit on a trace-less bundle."""
    FLAGS.set("trace_sampling_rate", 1.0)
    oom = RuntimeError("RESOURCE_EXHAUSTED: Out of memory")
    c0 = METRICS.counter("hbm.alloc_failures").get()
    with TRACER.start_span("rpc.IndexService.VectorSearch") as span:
        trace_id = f"{span.trace_id:016x}"
        bid = FLIGHT.on_rpc_error("rpc.IndexService.VectorSearch", oom, span)
        assert HBM.on_alloc_failure(oom, capture=False) is None
    assert bid
    meta = FLIGHT.bundles_meta()[-1]
    assert meta["reason"] == "device_oom"
    assert meta["trace_id"] == trace_id
    assert METRICS.counter("hbm.alloc_failures").get() == c0 + 1


def test_prometheus_exemplars_stripped_for_classic_scrape(obs_env):
    m = MetricsRegistry()
    lr = m.latency("span.rpc.classic_probe")
    lr.observe_us(5000.0, trace_id="abcdef0123456789")
    assert "trace_id=" in m.render_prometheus()            # in-band default
    assert "trace_id=" not in m.render_prometheus(exemplars=False)


def test_metrics_http_exemplars_opt_in(obs_env):
    import urllib.request

    from dingo_tpu.metrics.http import MetricsHttpServer

    m = MetricsRegistry()
    m.latency("span.rpc.scrape_probe").observe_us(
        7000.0, trace_id="feed0123feed0123")
    srv = MetricsHttpServer(registry=m)
    port = srv.start()
    try:
        # a plain Prometheus scrape (even one whose Accept header offers
        # OpenMetrics) gets clean classic text — no exemplar suffix
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/metrics",
            headers={"Accept": "application/openmetrics-text;version=1.0.0;"
                               "q=0.75,text/plain;version=0.0.4;q=0.5"},
        )
        classic = urllib.request.urlopen(req, timeout=5)
        body = classic.read().decode()
        assert "version=0.0.4" in classic.headers["Content-Type"]
        assert "trace_id=" not in body          # classic parser survives
        assert "span_rpc_scrape_probe" in body
        # explicit opt-in serves the nonstandard exemplar suffix
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics?exemplars=1", timeout=5,
        ).read().decode()
        assert 'trace_id="feed0123feed0123"' in body
    finally:
        srv.stop()


def test_looks_like_oom():
    assert looks_like_oom(RuntimeError("RESOURCE_EXHAUSTED: ..."))
    assert looks_like_oom(RuntimeError("Failed to allocate 1GB"))
    assert not looks_like_oom(ValueError("dimension mismatch"))


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_slow_query_trigger_and_exemplar(obs_env, monkeypatch):
    FLAGS.set("trace_sampling_rate", 1.0)
    FLAGS.set("slow_query_ms", 0.001)
    lines = []
    monkeypatch.setattr(
        "dingo_tpu.trace.span._log",
        type("L", (), {"warning": lambda self, msg, *a: lines.append(msg % a)})(),
    )
    FLIGHT.tick()
    # a bigger earlier sample (a warmup compile, say) must NOT keep the
    # exemplar: the slow path pins its own (bundled) sample
    METRICS.latency("span.rpc.TestService.Slow").observe_us(
        10_000_000.0, trace_id="feedfacefeedface")
    with TRACER.start_span("rpc.TestService.Slow") as span:
        time.sleep(0.004)
        trace_id = f"{span.trace_id:016x}"
    metas = FLIGHT.bundles_meta()
    assert metas and metas[-1]["reason"] == "slow_query"
    assert metas[-1]["trace_id"] == trace_id
    # satellite: the slow-query log line carries trace id AND bundle id
    assert lines and trace_id in lines[-1]
    assert metas[-1]["id"] in lines[-1]
    # bundle carries the triggering trace's spans
    bundle = FLIGHT.get_json(metas[-1]["id"])
    assert any(s["name"] == "rpc.TestService.Slow" for s in bundle["spans"])
    # the Prometheus exposition carries a matching exemplar trace id on
    # the span's p99 series
    text = METRICS.render_prometheus()
    assert f'# {{trace_id="{trace_id}"}}' in text
    line = next(l for l in text.splitlines()
                if l.startswith("span_rpc_TestService_Slow")
                and 'quantile="0.99"' in l)
    assert f'trace_id="{trace_id}"' in line


def test_flight_unsampled_slow_query_still_bundles(obs_env, monkeypatch):
    FLAGS.set("trace_sampling_rate", 1e-12)   # armed, never samples
    FLAGS.set("slow_query_ms", 0.001)
    lines = []
    monkeypatch.setattr(
        "dingo_tpu.trace.span._log",
        type("L", (), {"warning": lambda self, msg, *a: lines.append(msg % a)})(),
    )
    t0 = TRACER.slow_watch_start()
    assert t0
    time.sleep(0.004)
    TRACER.slow_watch_end("rpc.TestService.Unsampled", t0)
    metas = FLIGHT.bundles_meta()
    assert metas and metas[-1]["reason"] == "slow_query"
    assert metas[-1]["trace_id"] == ""
    assert metas[-1]["name"] == "rpc.TestService.Unsampled"
    assert lines and metas[-1]["id"] in lines[-1]


def test_error_bundle_contains_inflight_root_span(obs_env):
    """The failing ingress span hasn't ended when the error trigger
    fires; its in-flight record must still appear in the bundle even when
    child spans of the trace already ended (no ring-tail fallback)."""
    FLAGS.set("trace_sampling_rate", 1.0)
    with TRACER.start_span("rpc.TestService.Fails") as root:
        with TRACER.start_span("child.work"):
            pass                      # child ENDS before the failure
        bid = FLIGHT.on_rpc_error("rpc.TestService.Fails",
                                  ValueError("boom"), root)
    assert bid
    bundle = FLIGHT.get_json(bid)
    names = {s["name"]: s for s in bundle["spans"]}
    assert "child.work" in names
    root_rec = names["rpc.TestService.Fails"]
    assert root_rec["attrs"]["in_flight"] is True
    assert root_rec["status"].startswith("error")
    assert not bundle["spans_fallback"]


def test_flight_metrics_delta_window(obs_env):
    FLIGHT.tick()
    METRICS.counter("flighttest.delta_probe").add(7)
    bid = FLIGHT.trigger("manual", name="delta-test")
    bundle = FLIGHT.get_json(bid)
    assert bundle["metrics"]["deltas"]["flighttest.delta_probe"] == 7
    assert bundle["metrics"]["window_s"] >= 0.0


def test_flight_rate_limit_and_retention(obs_env):
    bid1 = FLIGHT.trigger("stormy")
    bid2 = FLIGHT.trigger("stormy")            # < 1s later: suppressed
    assert bid1 and bid2 == ""
    assert METRICS.counter(
        "flight.suppressed", labels={"reason": "stormy"}).get() >= 1
    # retention honors obs.flight_max_bundles
    FLAGS.set("obs_flight_max_bundles", 2)
    for i, reason in enumerate(("r_a", "r_b", "r_c")):
        FLIGHT.trigger(reason)
    metas = FLIGHT.bundles_meta()
    assert len(metas) == 2
    assert [m["reason"] for m in metas] == ["r_b", "r_c"]
    # 0 disables capturing entirely
    FLAGS.set("obs_flight_max_bundles", 0)
    assert FLIGHT.trigger("r_d") == ""


def test_flight_eviction_preserves_singleton_reasons(obs_env, monkeypatch):
    """A storm of one reason evicts its own duplicates, never the lone
    device_oom/slow_query bundle an operator came for."""
    monkeypatch.setattr(flight_mod, "MIN_TRIGGER_INTERVAL_S", 0.0)
    FLAGS.set("obs_flight_max_bundles", 3)
    oom_id = FLIGHT.trigger("device_oom")
    for _ in range(5):
        FLIGHT.trigger("error")
    metas = FLIGHT.bundles_meta()
    assert len(metas) == 3
    assert metas[0]["id"] == oom_id          # survived the storm
    assert [m["reason"] for m in metas[1:]] == ["error", "error"]
    # pin-on-capture only: a rate-limited slow query must not move the
    # exemplar to a bundle-less trace
    monkeypatch.setattr(flight_mod, "MIN_TRIGGER_INTERVAL_S", 60.0)
    FLAGS.set("trace_sampling_rate", 1.0)
    FLAGS.set("slow_query_ms", 0.001)
    with TRACER.start_span("rpc.TestService.Pinned") as s1:
        time.sleep(0.003)
        t1 = f"{s1.trace_id:016x}"
    with TRACER.start_span("rpc.TestService.Pinned") as s2:
        time.sleep(0.02)                     # slower, but rate-limited
    ex = METRICS.latency("span.rpc.TestService.Pinned").exemplar()
    assert ex is not None and ex[1] == t1


def test_flight_report_roundtrip(obs_env, tmp_path):
    name = _kname()

    @sentinel_jit(name)
    def triple(x):
        return x * 3

    triple(jnp.ones(4))
    FLIGHT.tick()
    METRICS.counter("flighttest.report_probe").add(3)
    HBM.update_region(962, {"slot_store": 4096, "ivf_view": 1024})
    bid = FLIGHT.trigger("manual", name="report-test", region_id=962)
    path = tmp_path / "bundle.bin"
    path.write_bytes(FLIGHT.get(bid))
    bundle = flight_report.parse_bundle(str(path))
    assert bundle["id"] == bid
    text = flight_report.render(bundle)
    assert "-- metric deltas" in text
    assert "flighttest.report_probe" in text
    assert "-- kernel cache state" in text and name in text
    assert "-- hbm ledger" in text and "slot_store" in text
    # uncompressed JSON parses too
    jpath = tmp_path / "bundle.json"
    jpath.write_text(json.dumps(bundle))
    assert flight_report.parse_bundle(str(jpath))["id"] == bid
    HBM.forget_region(962)


# ---------------------------------------------------------------------------
# heartbeat / cluster-top plumbing for the hbm watermark
# ---------------------------------------------------------------------------

def test_region_metrics_pb_roundtrip_device_peak():
    from dingo_tpu.metrics.snapshot import RegionMetricsSnapshot
    from dingo_tpu.server import convert

    rm = RegionMetricsSnapshot(region_id=4, device_peak_bytes=123456)
    again = convert.region_metrics_from_pb(convert.region_metrics_to_pb(rm))
    assert again.device_peak_bytes == 123456


def test_cluster_top_shows_devpeak():
    from dingo_tpu.client.cli import format_cluster_top
    from dingo_tpu.server import pb

    resp = pb.GetStoreMetricsResponse()
    entry = resp.stores.add()
    entry.store_id = "s0"
    rm = entry.metrics.regions.add()
    rm.region_id = 1
    rm.vector_count = 10
    rm.device_memory_bytes = 1024
    rm.device_peak_bytes = 4096
    out = format_cluster_top(resp)
    assert "DEVPEAK" in out
    assert "4.0KB" in out


# ---------------------------------------------------------------------------
# grpc end-to-end: fault injection -> FlightDump -> flight_report
# ---------------------------------------------------------------------------

def test_flight_grpc_end_to_end(obs_env, tmp_path, monkeypatch):
    """Full acceptance chain: a slow search captures a bundle with the
    trace's spans; an injected failpoint error captures another; both
    export through FlightDump; tools/flight_report.py renders the slow
    bundle; the Prometheus exposition (MetricsDump) carries the matching
    exemplar trace id."""
    from dingo_tpu.client import DingoClient
    from dingo_tpu.coordinator.control import CoordinatorControl
    from dingo_tpu.coordinator.kv_control import KvControl
    from dingo_tpu.coordinator.tso import TsoControl
    from dingo_tpu.engine.raw_engine import MemEngine
    from dingo_tpu.raft import LocalTransport
    from dingo_tpu.server import pb
    from dingo_tpu.server.rpc import DingoServer
    from dingo_tpu.store.node import StoreNode

    FLAGS.set("trace_sampling_rate", 1.0)
    # at a micro slow_query_ms EVERY rpc is "slow" (region-map refreshes
    # included); disable the per-reason rate limit so the search's own
    # bundle is captured rather than suppressed behind a neighbor's
    monkeypatch.setattr(flight_mod, "MIN_TRIGGER_INTERVAL_S", 0.0)
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
        param = pb.VectorIndexParameter(
            index_type=pb.VECTOR_INDEX_TYPE_FLAT, dimension=8,
            metric_type=pb.METRIC_TYPE_L2,
        )
        client.create_index_region(0, 0, 1 << 30, param)
        time.sleep(1.0)
        rng = np.random.default_rng(0)
        x = rng.standard_normal((40, 8)).astype(np.float32)
        client.vector_add(0, list(range(40)), x)

        FLIGHT.clear()
        FLIGHT.tick()
        # --- slow query: every search now crosses the threshold ---
        FLAGS.set("slow_query_ms", 0.0001)
        res = client.vector_search(0, x[[3]], topk=3)
        assert res[0][0][0] == 3
        FLAGS.set("slow_query_ms", 500.0)
        slow_metas = [m for m in FLIGHT.bundles_meta()
                      if m["reason"] == "slow_query"
                      and m["name"] == "rpc.IndexService.VectorSearch"]
        assert slow_metas, FLIGHT.bundles_meta()
        slow = slow_metas[-1]
        assert slow["trace_id"]

        # --- injected search error via the failpoint ---
        FAILPOINTS.configure("before_vector_search", "1*panic")
        try:
            with pytest.raises(Exception):
                client.vector_search(0, x[[3]], topk=3)
        finally:
            FAILPOINTS.remove("before_vector_search")
        err_metas = [m for m in FLIGHT.bundles_meta()
                     if m["reason"] == "error"]
        assert err_metas
        assert "VectorSearch" in err_metas[-1]["name"]

        # --- FlightDump RPC round-trip ---
        dbg = client._stub("s0", "DebugService")
        resp = dbg.FlightDump(pb.FlightDumpRequest())
        assert {m.reason for m in resp.bundles} >= {"slow_query", "error"}
        resp = dbg.FlightDump(pb.FlightDumpRequest(
            bundle_id=slow["id"], include_payload=True,
        ))
        assert resp.payload_bundle_id == slow["id"]
        assert resp.payload
        path = tmp_path / "slow_bundle.bin"
        path.write_bytes(resp.payload)

        # --- flight_report parse-back + render ---
        bundle = flight_report.parse_bundle(str(path))
        assert bundle["id"] == slow["id"]
        assert bundle["trace_id"] == slow["trace_id"]
        span_names = {s["name"] for s in bundle["spans"]}
        assert "rpc.IndexService.VectorSearch" in span_names
        text = flight_report.render(bundle)
        assert "rpc.IndexService.VectorSearch" in text
        assert "-- metric deltas" in text
        assert "-- kernel cache state" in text
        assert "index.flat.search" in text

        # --- exemplar: scrape links the bad bucket to the same trace ---
        prom = dbg.MetricsDump(
            pb.MetricsDumpRequest(format="prometheus")).json
        assert f'trace_id="{slow["trace_id"]}"' in prom

        # unknown bundle id answers in-band
        resp = dbg.FlightDump(pb.FlightDumpRequest(
            bundle_id="fb-nope", include_payload=True))
        assert resp.error.errcode == 50003
    finally:
        client.close()
        srv.stop()
        cs.stop()
        node.stop()
