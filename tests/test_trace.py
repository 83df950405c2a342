"""Distributed tracing subsystem (dingo_tpu/trace): span API, sampling,
cross-thread propagation through the coalescer, gRPC metadata propagation,
exporters, and the zero-overhead-when-off contract."""

import json
import sys
import threading
import time

import numpy as np
import pytest

from dingo_tpu.common.coalescer import CoalescerStopped, SearchCoalescer
from dingo_tpu.common.config import FLAGS
from dingo_tpu.common.metrics import METRICS
from dingo_tpu.trace import (
    NOOP_SPAN,
    TRACE_BUFFER,
    TRACE_METADATA_KEY,
    TRACER,
    TraceBuffer,
    current_span,
    dump_chrome_trace,
    extract_metadata,
    inject_metadata,
    to_chrome_trace,
    to_json,
)


@pytest.fixture()
def sampled():
    """Sampling on, clean buffer; restores the off state after."""
    TRACE_BUFFER.clear()
    FLAGS.set("trace_sampling_rate", 1.0)
    try:
        yield
    finally:
        FLAGS.set("trace_sampling_rate", 0.0)
        TRACE_BUFFER.clear()


# ---------------- span core ----------------

def test_unsampled_returns_shared_noop():
    FLAGS.set("trace_sampling_rate", 0.0)
    s1 = TRACER.start_span("a")
    s2 = TRACER.start_span("b")
    assert s1 is NOOP_SPAN and s2 is NOOP_SPAN
    # noop is inert: attrs, end, context manager all no-ops
    with s1 as s:
        s.set_attr("k", 1).end()
    assert s1.duration_us() == 0.0


def test_span_tree_and_buffer(sampled):
    with TRACER.start_span("root") as root:
        root.set_attr("who", "me")
        with TRACER.start_span("child") as child:
            assert current_span() is child
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
        assert current_span() is root
    recs = TRACE_BUFFER.snapshot()
    assert [r["name"] for r in recs] == ["child", "root"]  # end order
    assert recs[0]["trace_id"] == recs[1]["trace_id"]
    assert recs[1]["attrs"] == {"who": "me"}
    assert recs[1]["parent_id"] == ""


def test_span_error_status(sampled):
    with pytest.raises(ValueError):
        with TRACER.start_span("boom"):
            raise ValueError("x")
    rec = TRACE_BUFFER.snapshot()[-1]
    assert rec["status"] == "error: ValueError"


def test_metrics_bridge(sampled):
    before = METRICS.latency("span.bridged").stats()["count"]
    with TRACER.start_span("bridged"):
        pass
    assert METRICS.latency("span.bridged").stats()["count"] == before + 1


def test_slow_query_log(sampled):
    FLAGS.set("slow_query_ms", 0.001)
    try:
        # request roots (rpc./client. prefix) qualify for the slow log
        with TRACER.start_span("rpc.test.Slow"):
            time.sleep(0.005)
        slow = TRACE_BUFFER.slow_queries()
        assert slow and slow[-1]["name"] == "rpc.test.Slow"
        # interior (non-ingress) spans never enter the slow log
        with TRACER.start_span("rpc.test.Outer"):
            with TRACER.start_span("index.search"):
                time.sleep(0.005)
        assert all(s["name"] != "index.search"
                   for s in TRACE_BUFFER.slow_queries())
    finally:
        FLAGS.set("slow_query_ms", 500.0)


def test_slow_log_covers_adopted_ingress_and_excludes_raft(sampled):
    """A sampled rpc ingress span adopted from a REMOTE parent still
    slow-logs on the serving store; raft/push replication-plane spans
    never do (a down peer would churn out query evidence)."""
    from dingo_tpu.trace import SpanContext

    FLAGS.set("slow_query_ms", 0.001)
    try:
        remote = SpanContext(0xabc, 0xdef, sampled=True)
        with TRACER.start_span("rpc.StoreService.KvScan", parent=remote):
            time.sleep(0.005)
        assert any(s["name"] == "rpc.StoreService.KvScan"
                   for s in TRACE_BUFFER.slow_queries())
        with TRACER.start_span("client.RaftService.RaftMessage"):
            time.sleep(0.005)
        assert all(s["name"] != "client.RaftService.RaftMessage"
                   for s in TRACE_BUFFER.slow_queries())
    finally:
        FLAGS.set("slow_query_ms", 500.0)


def test_buffer_ring_bounded():
    buf = TraceBuffer(capacity=4)
    for i in range(10):
        buf.add({"name": f"s{i}", "trace_id": "t"})
    snap = buf.snapshot()
    assert len(snap) == 4
    assert [r["name"] for r in snap] == ["s6", "s7", "s8", "s9"]
    assert buf.stats()["dropped"] == 6


def test_sampling_rate_fraction(sampled):
    FLAGS.set("trace_sampling_rate", 0.5)
    hits = sum(TRACER.start_span("p").sampled or 0 for _ in range(400))
    assert 100 < hits < 300   # ~200 expected; generous bounds


# ---------------- metadata propagation ----------------

def test_metadata_inject_extract_roundtrip(sampled):
    with TRACER.start_span("client") as sp:
        md = inject_metadata([("other", "1")])
        assert ("other", "1") in md
        ctx = extract_metadata(md)
        assert ctx.trace_id == sp.trace_id
        assert ctx.span_id == sp.span_id
        assert ctx.sampled
    # no current span -> passthrough
    assert inject_metadata(None) is None
    assert extract_metadata(None) is None
    assert extract_metadata([("x", "y")]) is None
    assert extract_metadata([(TRACE_METADATA_KEY, "garbage")]) is None


def test_remote_parent_links_span(sampled):
    md = [(TRACE_METADATA_KEY, f"{0xabc:016x}-{0xdef:016x}-1")]
    with TRACER.start_span("server", parent=extract_metadata(md)) as sp:
        assert sp.trace_id == 0xabc
        assert sp.parent_id == 0xdef
    # unsampled remote parent suppresses recording entirely
    md0 = [(TRACE_METADATA_KEY, f"{0xabc:016x}-{0xdef:016x}-0")]
    assert TRACER.start_span("s", parent=extract_metadata(md0)) is NOOP_SPAN


# ---------------- coalescer propagation (tentpole contract) ----------------

def test_coalescer_span_tree_single_trace(sampled):
    """A search through SearchCoalescer.submit yields a connected tree
    ingress -> coalesce.wait -> coalesce.run -> index.search with ONE
    trace id even though the batch runs on the timer thread."""
    def run(key, stacked):
        with TRACER.start_span("index.search") as sp:
            sp.set_attr("batch", len(stacked))
        return list(range(len(stacked)))

    co = SearchCoalescer(run, window_ms=5.0)
    try:
        with TRACER.start_span("rpc.test.Search") as ingress:
            fut = co.submit("k", np.zeros((2, 4), np.float32))
            assert fut.result(timeout=5) == [0, 1]
            trace_id = f"{ingress.trace_id:016x}"
    finally:
        co.stop()
    spans = {r["name"]: r for r in TRACE_BUFFER.snapshot(trace_id=trace_id)}
    assert {"rpc.test.Search", "coalesce.wait", "coalesce.run",
            "index.search"} <= set(spans)
    # connected parent/child chain, all on one trace id
    assert spans["coalesce.wait"]["parent_id"] == \
        spans["rpc.test.Search"]["span_id"]
    assert spans["coalesce.run"]["parent_id"] == \
        spans["coalesce.wait"]["span_id"]
    assert spans["index.search"]["parent_id"] == \
        spans["coalesce.run"]["span_id"]
    assert spans["coalesce.run"]["attrs"]["batch_size"] == 2
    # batch ran on the coalescer timer thread, not the submitter's
    assert spans["coalesce.run"]["thread"] != \
        spans["rpc.test.Search"]["thread"]


def test_coalescer_batch_links_cobatched_traces(sampled):
    """Two sampled submitters merged into one batch: the run span lands in
    the first trace and records the other trace id as a link."""
    def run(key, stacked):
        return list(range(len(stacked)))

    co = SearchCoalescer(run, window_ms=200.0)
    traces = []

    def one():
        with TRACER.start_span("rpc.r") as sp:
            traces.append(f"{sp.trace_id:016x}")
            co.submit("k", np.zeros((1, 4), np.float32)).result(timeout=5)

    try:
        t1 = threading.Thread(target=one)
        t2 = threading.Thread(target=one)
        t1.start(); t2.start(); t1.join(); t2.join()
    finally:
        co.stop()
    runs = [r for r in TRACE_BUFFER.snapshot() if r["name"] == "coalesce.run"]
    assert len(runs) == 1
    assert runs[0]["attrs"]["requests"] == 2
    linked = runs[0]["attrs"]["cobatched_traces"]
    assert set(linked) == set(traces) - {runs[0]["trace_id"]}


# ---------------- coalescer stop(drain=) satellite ----------------

def test_coalescer_stop_drain_runs_pending():
    ran = []

    def run(key, stacked):
        ran.append(len(stacked))
        return list(range(len(stacked)))

    co = SearchCoalescer(run, window_ms=10_000.0)   # never expires alone
    fut = co.submit("k", np.zeros((3, 2), np.float32))
    co.stop(drain=True)
    assert fut.result(timeout=1) == [0, 1, 2]
    assert ran == [3]


def test_coalescer_stop_no_drain_fails_futures_deterministically():
    def run(key, stacked):
        raise AssertionError("must not run")

    co = SearchCoalescer(run, window_ms=10_000.0)
    fut = co.submit("k", np.zeros((3, 2), np.float32))
    co.stop(drain=False)
    with pytest.raises(CoalescerStopped):
        fut.result(timeout=1)
    # post-stop submits are refused with the same typed error — on the
    # FUTURE, not by raising: since the QoS layer (ISSUE 10) the submit
    # contract is "never raises, never hangs; every returned future
    # resolves deterministically"
    late = co.submit("k", np.zeros((1, 2), np.float32))
    with pytest.raises(CoalescerStopped):
        late.result(timeout=1)


# ---------------- exporters ----------------

def test_json_and_chrome_export(sampled, tmp_path):
    with TRACER.start_span("outer"):
        with TRACER.start_span("inner"):
            pass
    payload = to_json()
    assert len(payload["traces"]) == 1
    (spans,) = payload["traces"].values()
    assert {s["name"] for s in spans} == {"outer", "inner"}
    assert payload["stats"]["buffered"] == 2

    chrome = to_chrome_trace()
    assert {e["name"] for e in chrome["traceEvents"]} == {"outer", "inner"}
    for ev in chrome["traceEvents"]:
        assert ev["ph"] == "X" and ev["dur"] >= 1
        assert ev["args"]["trace_id"]
    path = dump_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        assert json.load(f) == json.loads(json.dumps(chrome))


def test_trace_report_tool(sampled, tmp_path, capsys):
    sys.path.insert(0, "tools")
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    with TRACER.start_span("rpc.IndexService.VectorSearch"):
        with TRACER.start_span("index.search"):
            time.sleep(0.001)
    path = dump_chrome_trace(str(tmp_path / "t.json"))
    rc = trace_report.main([path, str(tmp_path / "out")])
    assert rc == 0
    out = capsys.readouterr().out
    assert "index.search" in out and "p99_us" in out
    report = json.loads((tmp_path / "out" / "trace_report.json").read_text())
    assert {r["stage"] for r in report["stages"]} == {
        "rpc.IndexService.VectorSearch", "index.search"}
    assert (tmp_path / "out" / "trace_report.html").exists()
    # empty trace -> rc 1, not a stacktrace
    empty = tmp_path / "empty.json"
    empty.write_text('{"traceEvents": []}')
    assert trace_report.main([str(empty)]) == 1


# ---------------- config knobs ----------------

def test_trace_flags_defined_and_conf_parsed(tmp_path):
    from dingo_tpu.common.config import Config

    assert FLAGS.get("trace_sampling_rate") == 0.0
    assert FLAGS.get("slow_query_ms") == 500.0
    conf = tmp_path / "store.conf"
    conf.write_text("trace.sampling_rate = 0.25\nslow_query_ms = 123\n")
    cfg = Config.load(str(conf))
    n = cfg.apply_flag_overrides()
    try:
        assert n == 2
        assert FLAGS.get("trace_sampling_rate") == 0.25
        assert FLAGS.get("slow_query_ms") == 123.0
    finally:
        FLAGS.set("trace_sampling_rate", 0.0)
        FLAGS.set("slow_query_ms", 500.0)


def test_conf_templates_carry_trace_keys():
    for path in ("conf/store.template.conf", "conf/coordinator.template.conf"):
        with open(path) as f:
            text = f.read()
        assert "trace.sampling_rate" in text
        assert "slow_query_ms" in text


# ---------------- layer-boundary and background spans ----------------

def _busy_ms(job):
    return METRICS.counter("background.busy_ms", labels={"job": job}).get()


def _lose_the_roll(monkeypatch):
    """Every head-sampling roll loses from here on (rate < 1)."""
    import random

    monkeypatch.setattr(random, "random", lambda: 0.999999)


def test_rate_zero_new_sites_are_noop_and_buffer_stays_empty(tmp_path):
    """trace_sampling_rate 0: every site this round added returns the
    shared NOOP_SPAN (no allocation, no clock) and records nothing."""
    import gc

    from dingo_tpu.common.crontab import CrontabManager
    from dingo_tpu.engine.raw_engine import CF_DEFAULT, WalEngine, WriteBatch
    from dingo_tpu.ops.distance import device_wait_begin

    FLAGS.set("trace_sampling_rate", 0.0)
    TRACE_BUFFER.clear()
    assert TRACER.start_child("index.dispatch") is NOOP_SPAN
    assert TRACER.start_background("cron.x") is NOOP_SPAN
    assert device_wait_begin("flat_scan") is NOOP_SPAN
    # ... also inside an (unsampled) request context
    token = NOOP_SPAN.attach()
    try:
        assert TRACER.start_child("raft.propose") is NOOP_SPAN
        assert device_wait_begin("pruned_scan") is NOOP_SPAN
    finally:
        NOOP_SPAN.detach(token)
    # the background sites themselves: a crontab job, a WAL checkpoint,
    # a full collection
    before = _busy_ms("cron.rate0_job")
    cron = CrontabManager()
    ran = []
    cron.add("rate0_job", 0.0, lambda: ran.append(1), immediately=True)
    assert cron.run_pending() == 1 and ran == [1]
    eng = WalEngine(str(tmp_path), checkpoint_threshold_bytes=1 << 30)
    eng.write(WriteBatch().put(CF_DEFAULT, b"k", b"v"))
    eng.checkpoint()
    eng.close()
    TRACER.watch_gc()
    try:
        gc.collect()
    finally:
        gc.callbacks.remove(TRACER._on_gc)
    assert TRACE_BUFFER.snapshot() == []
    assert _busy_ms("cron.rate0_job") == before


def test_start_child_never_mints_a_root(sampled):
    # rate 1.0 but no request around: a boundary site records nothing
    assert TRACER.start_child("index.dispatch") is NOOP_SPAN
    with TRACER.start_span("rpc.X") as root:
        with TRACER.start_child("index.dispatch") as child:
            assert child.sampled and child.parent_id == root.span_id
    assert [r["name"] for r in TRACE_BUFFER.snapshot()] == \
        ["index.dispatch", "rpc.X"]


def test_background_recorded_whatever_the_head_roll(sampled, monkeypatch):
    """Rate 0.05 and a losing roll: a request is not traced, a
    background job still is (the one save of a minute must not be lost
    to the request sampler)."""
    FLAGS.set("trace_sampling_rate", 0.05)
    _lose_the_roll(monkeypatch)
    assert TRACER.start_span("rpc.Lost") is NOOP_SPAN
    with TRACER.start_background("cron.kept") as bg:
        assert bg.sampled
    recs = TRACE_BUFFER.snapshot()
    assert [r["name"] for r in recs] == ["cron.kept"]
    assert recs[0]["parent_id"] == ""           # a root of its own


def test_background_child_of_sampled_request_else_root(sampled, monkeypatch):
    with TRACER.start_span("rpc.Writer") as req:
        with TRACER.start_background("engine.wal_checkpoint") as ck:
            assert ck.trace_id == req.trace_id
            assert ck.parent_id == req.span_id
    # inside an UNSAMPLED request: a root of its own
    FLAGS.set("trace_sampling_rate", 0.05)
    _lose_the_roll(monkeypatch)
    token = NOOP_SPAN.attach()
    try:
        with TRACER.start_background("engine.wal_checkpoint") as ck2:
            assert ck2.sampled and ck2.parent_id == 0
            assert ck2.trace_id != req.trace_id
    finally:
        NOOP_SPAN.detach(token)


def test_background_busy_ms_counts_the_outermost_job_once(sampled):
    outer0, inner0 = _busy_ms("cron.outer_job"), _busy_ms("index.save_t")
    with TRACER.start_background("cron.outer_job"):
        with TRACER.start_span("some.step"):          # ordinary child
            with TRACER.start_background("index.save_t"):
                time.sleep(0.02)
    grew = _busy_ms("cron.outer_job") - outer0
    assert 20.0 <= grew < 2000.0
    # the nested job's time is inside the outer's: not counted twice
    assert _busy_ms("index.save_t") == inner0
    # alone, the same job counts under its own name
    with TRACER.start_background("index.save_t"):
        time.sleep(0.005)
    assert _busy_ms("index.save_t") - inner0 >= 5.0


def test_spans_recorded_and_dropped_reach_metrics(sampled):
    rec0 = METRICS.counter("trace.spans_recorded").get()
    for i in range(3):
        TRACER.start_span(f"n{i}").end()
    assert METRICS.counter("trace.spans_recorded").get() - rec0 == 3
    drop0 = METRICS.counter("trace.spans_dropped").get()
    small = TraceBuffer(capacity=2)
    for i in range(5):
        small.add({"name": f"s{i}", "trace_id": "t"})
    assert small.stats()["dropped"] == 3
    assert METRICS.counter("trace.spans_dropped").get() - drop0 == 3


def test_span_clock_is_monotonic_ns_and_dur_never_zero(sampled):
    t0 = time.monotonic_ns()
    span = TRACER.start_span("tiny")
    span.end()
    t1 = time.monotonic_ns()
    assert t0 <= span.start_ns <= span.end_ns <= t1
    rec = TRACE_BUFFER.snapshot()[-1]
    assert rec["dur_us"] >= 1                 # readers divide by it
    assert rec["start_us"] == span.start_ns // 1000
    # an unfinished span still reads 0 (nothing to divide yet)
    assert TRACER.start_span("open").record()["dur_us"] == 0


def test_crontab_job_is_a_background_span(sampled, monkeypatch):
    from dingo_tpu.common.crontab import CrontabManager

    FLAGS.set("trace_sampling_rate", 0.05)
    _lose_the_roll(monkeypatch)
    seen = {}
    cron = CrontabManager()
    cron.add("probe_job", 0.0,
             lambda: seen.setdefault("cur", current_span()),
             immediately=True)
    before = _busy_ms("cron.probe_job")
    assert cron.run_pending() == 1
    recs = [r for r in TRACE_BUFFER.snapshot()
            if r["name"] == "cron.probe_job"]
    assert len(recs) == 1 and recs[0]["parent_id"] == ""
    # the job ran INSIDE its span (a save it starts is its child)
    assert seen["cur"].name == "cron.probe_job"
    assert _busy_ms("cron.probe_job") > before
    # a failing job still ends its span, marked as an error
    cron.add("bad_job", 0.0, lambda: 1 / 0, immediately=True)
    cron.run_pending()
    bad = [r for r in TRACE_BUFFER.snapshot() if r["name"] == "cron.bad_job"]
    assert bad and bad[0]["status"].startswith("error")


def test_wal_checkpoint_span_inside_a_sampled_write(sampled, tmp_path):
    from dingo_tpu.engine.raw_engine import CF_DEFAULT, WalEngine, WriteBatch

    eng = WalEngine(str(tmp_path), checkpoint_threshold_bytes=256)
    try:
        with TRACER.start_span("rpc.Write") as req:
            for i in range(8):
                eng.write(WriteBatch().put(
                    CF_DEFAULT, f"k{i}".encode(), b"x" * 128))
            trace_id = f"{req.trace_id:016x}"
    finally:
        eng.close()
    spans = TRACE_BUFFER.snapshot(trace_id=trace_id)
    by_id = {s["span_id"]: s for s in spans}
    cks = [s for s in spans if s["name"] == "engine.wal_checkpoint"]
    assert cks, {s["name"] for s in spans}
    for ck in cks:
        # the rewrite happens under the write that tripped the threshold
        assert by_id[ck["parent_id"]]["name"] == "engine.wal_write"
        assert ck["attrs"]["wal_bytes"] >= 256


def test_gc_gen2_is_a_background_span_with_a_pause_counter(sampled):
    import gc

    pause = METRICS.counter("gc.pause_ms", labels={"gen": "2"})
    TRACER.watch_gc()
    TRACER.watch_gc()                       # idempotent
    assert gc.callbacks.count(TRACER._on_gc) == 1
    try:
        before = pause.get()
        gc.collect(0)                       # young generations: ignored
        TRACER.start_span("flush0").end()
        assert all(r["name"] != "gc.gen2" for r in TRACE_BUFFER.snapshot())
        with TRACER.start_span("rpc.Victim") as req:
            gc.collect()                    # a full collection
        # the callback only parks the pause (it may run under any lock);
        # the next span to finish records it
        recs = [r for r in TRACE_BUFFER.snapshot() if r["name"] == "gc.gen2"]
        assert len(recs) == 1
        assert recs[0]["parent_id"] == f"{req.span_id:016x}"
        assert recs[0]["dur_us"] >= 1
        assert pause.get() > before
    finally:
        gc.callbacks.remove(TRACER._on_gc)


def test_compile_span_follows_the_background_rule():
    from dingo_tpu.obs.sentinel import SENTINEL

    TRACE_BUFFER.clear()
    try:
        FLAGS.set("trace_sampling_rate", 0.0)
        SENTINEL._emit_compile_span("k.rate0", 12.0, "f32[8]")
        assert TRACE_BUFFER.snapshot() == []
        FLAGS.set("trace_sampling_rate", 1e-9)
        t0 = time.monotonic_ns()
        SENTINEL._emit_compile_span("k.on", 12.0, "f32[8]")
        rec = TRACE_BUFFER.snapshot()[-1]
        assert rec["name"] == "xla.compile" and rec["parent_id"] == ""
        # back-dated by the compile's length through the tracer itself
        assert 12_000 <= rec["dur_us"] < 12_000 + 50_000
        assert rec["start_us"] <= t0 // 1000 - 11_000
    finally:
        FLAGS.set("trace_sampling_rate", 0.0)
        TRACE_BUFFER.clear()


def test_propose_apply_parentage_across_threads(sampled):
    """raft.apply joins its entry's raft.propose through the context kept
    per log index, also when the ticker's thread applies the entry."""
    from dingo_tpu.raft import LocalTransport, RaftNode

    applied_on = []
    node = RaftNode(
        "n0", ["n0"], LocalTransport(), seed=0,
        apply_fn=lambda i, p: applied_on.append(
            (threading.get_ident(), current_span())),
    )
    node.start()
    try:
        deadline = time.monotonic() + 5.0
        while not node.is_leader() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert node.is_leader()
        # the proposer's own broadcast does nothing: the entry commits and
        # applies on the ticker's next heartbeat, on the ticker's thread
        proposer = threading.get_ident()
        real = node._broadcast_append
        node._broadcast_append = lambda: (
            None if threading.get_ident() == proposer else real())
        with TRACER.start_span("rpc.Add") as req:
            index = node.propose(b"payload")
            trace_id = f"{req.trace_id:016x}"
        # an unsampled proposal leaves no context behind and no span
        node.propose(b"untraced")
    finally:
        node.stop()
    thread_id, cur = applied_on[0]
    assert thread_id != proposer
    assert cur is not None and cur.name == "raft.apply"
    assert applied_on[1][1] is None
    assert node._propose_ctx == {}
    spans = {s["name"]: s for s in TRACE_BUFFER.snapshot(trace_id=trace_id)}
    assert set(spans) == {"rpc.Add", "raft.propose", "raft.apply"}
    assert spans["raft.propose"]["attrs"]["index"] == index
    assert spans["raft.apply"]["parent_id"] == spans["raft.propose"]["span_id"]
    assert spans["raft.apply"]["thread"] == thread_id
    assert spans["raft.propose"]["thread"] == proposer
    # propose waits for the apply: the child's interval is inside it
    p, a = spans["raft.propose"], spans["raft.apply"]
    assert p["start_us"] <= a["start_us"]
    assert a["start_us"] + a["dur_us"] <= p["start_us"] + p["dur_us"]


def test_capture_writes_spans_and_both_clock_pairs(sampled, tmp_path):
    """trace/profile.capture on the CPU backend: the profiler's trace,
    the interval's spans beside it, a clock pair at each end; spans are
    mirrored into the profile only while it is live."""
    from dingo_tpu.trace import profile

    out = {}
    t = threading.Thread(
        target=lambda: out.update(profile.capture(str(tmp_path), 0.6)))
    assert TRACER.annotate is None
    t.start()
    deadline = time.monotonic() + 30.0
    while TRACER.annotate is None and t.is_alive() \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert TRACER.annotate is not None
    with TRACER.start_span("rpc.Profiled"):
        with TRACER.start_child("index.dispatch"):
            time.sleep(0.01)
    t.join(timeout=60.0)
    assert not t.is_alive()
    assert TRACER.annotate is None and TRACER.buffer is TRACE_BUFFER
    assert out["xplane"].endswith(".xplane.pb")
    assert out["spans_file"].startswith(out["xplane"].rsplit("/", 1)[0])
    with open(out["spans_file"]) as f:
        saved = json.load(f)
    names = [r["name"] for r in saved["spans"]]
    assert "rpc.Profiled" in names and "index.dispatch" in names
    clock = saved["clock"]
    for end in ("start", "stop"):
        mono, wall = clock[end]
        assert mono > 0 and wall > 1_600_000_000 * 10**9
    assert clock["stop"][0] - clock["start"][0] >= 0.6e9
    # every kept span lies on the same monotonic clock, inside the pairs
    for r in saved["spans"]:
        assert clock["start"][0] // 1000 <= r["start_us"] \
            <= clock["stop"][0] // 1000
    # the ring got them too (the tee forwards)
    assert any(r["name"] == "rpc.Profiled" for r in TRACE_BUFFER.snapshot())
    # one capture at a time, and bad intervals are refused
    with pytest.raises(ValueError):
        profile.capture(str(tmp_path), 0)


def test_device_profile_rpc_needs_a_role_that_holds_the_device(tmp_path):
    from dingo_tpu.server import pb
    from dingo_tpu.server.services import DebugService

    no = DebugService().DeviceProfile(pb.MetricsDumpRequest())
    assert no.error.errcode == 50004 and "no device" in no.error.errmsg
    ok = DebugService(device=True).DeviceProfile(pb.MetricsDumpRequest(
        format=json.dumps({"seconds": 0.2, "dir": str(tmp_path)})))
    assert ok.error.errcode == 0, ok.error.errmsg
    reply = json.loads(ok.json)
    assert reply["xplane"].endswith(".xplane.pb")
    assert set(reply["clock"]) == {"start", "stop"}
    bad = DebugService(device=True).DeviceProfile(
        pb.MetricsDumpRequest(format=json.dumps({"seconds": -1})))
    assert bad.error.errcode == 50004


# ---------------- overhead contract ----------------

@pytest.mark.slow
def test_unsampled_hot_path_overhead_micro_benchmark():
    """With sampling at 0.0 an instrumented site is one sampled-check:
    start_span returns the shared noop (no per-call allocations) and the
    per-call cost stays within an order of magnitude of a bare function
    call."""
    import timeit
    import tracemalloc

    FLAGS.set("trace_sampling_rate", 0.0)

    def site():
        with TRACER.start_span("hot"):
            pass

    site()  # warm
    # allocation check: the loop itself must not grow memory per span site
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(10_000):
        site()
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    growth = sum(s.size_diff for s in after.compare_to(before, "filename")
                 if "dingo_tpu" in s.traceback[0].filename)
    # no O(n) retention from 10k unsampled spans (tiny interpreter noise ok)
    assert growth < 16 * 1024, growth

    def bare():
        pass

    t_site = timeit.timeit(site, number=50_000)
    t_bare = timeit.timeit(bare, number=50_000)
    # a contextvar read + flag read + noop context manager: well under
    # 30x a bare call (typically ~5-10x); catches accidental Span allocs
    assert t_site < t_bare * 30 + 0.5, (t_site, t_bare)
