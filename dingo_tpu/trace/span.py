"""Span / SpanContext / Tracer: the in-process tracing core.

A Span is one timed operation; SpanContext is the (trace_id, span_id,
sampled) triple that links spans into a tree and rides gRPC metadata
between processes. Propagation inside a process is a contextvar, so spans
nest across the coalescer's thread handoffs as long as the handoff side
attaches the captured context (see common/coalescer.py).

Clock contract: every span timestamp is ``time.monotonic_ns()`` — the
clock of ``time.monotonic()``, which load generators and a profiler
capture's (monotonic_ns, time_ns) pairs (trace/profile.py) are read on,
so a span can be laid over a device trace or a caller's record without a
per-process offset guess.

Sampling is head-based and decided once at the root: an unsampled root
returns the shared NOOP_SPAN and every descendant site sees it via the
contextvar and short-circuits — one check, zero allocations per site.
Remote parents carry their sampled bit in the metadata, so one decision
at the first ingress governs the whole distributed trace.

Background work (crontab jobs, WAL checkpoints, index saves and rebuilds,
full garbage collections, XLA compiles) is the exception to the head
roll: ``Tracer.start_background`` records it whenever
``trace_sampling_rate > 0``. Such work runs a few times a second at most,
and the one index save of a minute must not be lost to a 5 % sampler.
"""

from __future__ import annotations

import collections
import contextvars
import random
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from dingo_tpu.common.config import FLAGS
from dingo_tpu.common.log import get_logger
from dingo_tpu.common.metrics import METRICS

#: gRPC metadata key carrying "trace_id-span_id-flags" (hex-hex-int).
TRACE_METADATA_KEY = "x-dingo-trace"

_log = get_logger("trace")

_CURRENT: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "dingo_trace_span", default=None
)


def _gen_id() -> int:
    """Non-zero 63-bit random id (0 is the 'no parent' sentinel). From
    the `random` module's generator (seeded from the OS per process and
    again after a fork): an `os.urandom` call per span was two fifths of
    a span's cost on the chip's host (6.4 of 15.9 us, PERF.md, PR 27)."""
    return random.getrandbits(63) or 1


class SpanContext:
    """The propagated identity of a span: what children and remote hops
    need to link to it. Immutable by convention."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: int, span_id: int, sampled: bool = True):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled

    def __repr__(self) -> str:
        return (f"SpanContext({self.trace_id:016x}, {self.span_id:016x}, "
                f"sampled={self.sampled})")


class Span:
    """A recording span. Use as a context manager for same-thread scopes;
    for cross-thread lifetimes create it, hand it off, and call end()."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "start_ns",
                 "end_ns", "attrs", "status", "thread_id", "background",
                 "_tracer", "_token", "_annotation")

    sampled = True

    def __init__(self, tracer: "Tracer", name: str, trace_id: int,
                 parent_id: int = 0, background: int = 0):
        self.name = name
        self.trace_id = trace_id
        self.span_id = _gen_id()
        self.parent_id = parent_id
        self.start_ns = time.monotonic_ns()
        self.end_ns = 0
        self.attrs: Dict[str, Any] = {}
        self.status = "ok"
        self.thread_id = threading.get_ident()
        #: 0 request work; 1 the outermost background span of a job
        #: (its end feeds ``background.busy_ms``); 2 anything below one
        self.background = background
        self._tracer = tracer
        self._token = None
        self._annotation = None

    @property
    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id, True)

    def set_attr(self, key: str, value: Any) -> "Span":
        self.attrs[key] = value
        return self

    def set_error(self, exc: BaseException) -> "Span":
        self.status = f"error: {type(exc).__name__}"
        return self

    # -- contextvar scope ----------------------------------------------------
    def attach(self):
        """Make this span the current one; returns a token for detach()."""
        return _CURRENT.set(self)

    def detach(self, token) -> None:
        try:
            _CURRENT.reset(token)
        except ValueError:
            # token minted in another thread/context (cross-thread handoff);
            # that context is gone with its thread, nothing to restore
            pass

    def __enter__(self) -> "Span":
        self._token = self.attach()
        if self._tracer.annotate is not None:
            # a device profile is being captured (trace/profile.py): the
            # scope also becomes a host event of the profiler's trace, so
            # an idle gap of the device can be put down to this span
            self._annotation = self._tracer.annotate("span:" + self.name)
            self._annotation.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc is not None:
            self.set_error(exc)
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        if self._token is not None:
            self.detach(self._token)
            self._token = None
        self.end()
        return False

    # -- completion ----------------------------------------------------------
    def end(self) -> None:
        if self.end_ns:
            return          # idempotent: exporter race / double-exit safe
        self.end_ns = time.monotonic_ns()
        self._tracer._finish(self)

    def duration_us(self) -> float:
        end = self.end_ns or time.monotonic_ns()
        return (end - self.start_ns) / 1000.0

    def record(self) -> Dict[str, Any]:
        """The buffered/exported form (ids as fixed-width hex)."""
        return {
            "name": self.name,
            "trace_id": f"{self.trace_id:016x}",
            "span_id": f"{self.span_id:016x}",
            "parent_id": f"{self.parent_id:016x}" if self.parent_id else "",
            "start_us": self.start_ns // 1000,
            # both ends floored to the microsecond (a child's interval
            # stays inside its parent's); a finished span never reads 0,
            # because readers divide by it
            "dur_us": max(1, self.end_ns // 1000 - self.start_ns // 1000)
            if self.end_ns else 0,
            "thread": self.thread_id,
            "status": self.status,
            "attrs": self.attrs,
        }


class _NoopSpan:
    """Shared do-nothing span. Every method is side-effect free and
    allocation free; attach() is the one exception — ingress sites attach
    it so descendants of an unsampled root short-circuit instead of
    minting fragment roots of their own."""

    __slots__ = ()

    sampled = False
    background = 0
    name = ""
    context = None
    attrs: Dict[str, Any] = {}

    def set_attr(self, key: str, value: Any) -> "_NoopSpan":
        return self

    def set_error(self, exc: BaseException) -> "_NoopSpan":
        return self

    def attach(self):
        return _CURRENT.set(self)

    def detach(self, token) -> None:
        try:
            _CURRENT.reset(token)
        except ValueError:
            pass

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    def end(self) -> None:
        pass

    def duration_us(self) -> float:
        return 0.0


NOOP_SPAN = _NoopSpan()

#: wire form of a decided-but-unsampled context: downstream hops must
#: honor the root's decision instead of re-rolling (fragment roots would
#: otherwise appear mid-request and skew the effective sampling rate)
UNSAMPLED_HEADER = "0-0-0"


def current_span():
    """The contextvar-current span (Span, NOOP_SPAN, or None)."""
    return _CURRENT.get()


class Tracer:
    """Mints spans, applies the sampling policy, feeds finished spans to
    the buffer, the slow-query log, and the MetricsRegistry bridge."""

    def __init__(self, buffer) -> None:
        self.buffer = buffer
        #: set by trace/profile.py while a device profile is captured:
        #: name -> context manager that mirrors a `with`-scoped span into
        #: the profiler's trace; None (the steady state) costs one read
        self.annotate = None
        #: span name -> its `span.<name>` recorder: a finished span looks
        #: it up here, not through the registry's lock and series-key
        #: formatting (names are code constants and RPC methods: bounded)
        self._recorders: Dict[str, Any] = {}
        self._recorded = METRICS.counter("trace.spans_recorded")
        self._gc_t0 = 0
        self._gc_parked: collections.deque = collections.deque()

    def start_span(self, name: str,
                   parent: Optional[SpanContext] = None):
        """Start a span. parent=None means 'inherit the contextvar current
        span, else make a sampling decision for a new root'; an explicit
        SpanContext (e.g. extracted from gRPC metadata or captured at a
        queue handoff) overrides inheritance."""
        if parent is None:
            cur = _CURRENT.get()
            if cur is not None:
                return self._child_of(cur, name)
            rate = FLAGS.get("trace_sampling_rate")
            if rate <= 0.0 or (rate < 1.0 and random.random() >= rate):
                return NOOP_SPAN
            return Span(self, name, _gen_id())
        if not parent.sampled:
            return NOOP_SPAN
        return Span(self, name, parent.trace_id, parent_id=parent.span_id)

    def start_child(self, name: str):
        """A span at a layer boundary INSIDE a request: recorded only as
        a child of a sampled current span, never a root of its own (the
        same code runs under warm-ups, rebuilds and shadow scoring, which
        must not mint single-span traces). One contextvar read when the
        request is unsampled or there is none."""
        cur = _CURRENT.get()
        return NOOP_SPAN if cur is None else self._child_of(cur, name)

    def _child_of(self, cur, name: str):
        if not cur.sampled:
            return NOOP_SPAN
        return Span(self, name, cur.trace_id, parent_id=cur.span_id,
                    background=2 if cur.background else 0)

    def start_background(self, name: str, backdate_ns: int = 0):
        """Start the span of a background job (crontab job, WAL checkpoint,
        index save or rebuild, full GC, XLA compile). Recorded whenever
        ``trace_sampling_rate > 0``, whatever the head roll said: a child
        of the current span when that one is sampled (a checkpoint inside
        a writer's request shows in that request's trace), else a root of
        its own. The outermost background span of a job adds its duration
        to ``background.busy_ms{job}`` when it ends. ``backdate_ns`` moves
        the start back, for work that is only known once it is over."""
        if FLAGS.get("trace_sampling_rate") <= 0.0:
            return NOOP_SPAN
        span = self._background_span(name, _CURRENT.get())
        span.start_ns -= backdate_ns
        return span

    def _background_span(self, name: str, cur) -> Span:
        if cur is not None and cur.sampled:
            return Span(self, name, cur.trace_id, parent_id=cur.span_id,
                        background=2 if cur.background else 1)
        return Span(self, name, _gen_id(), background=1)

    # -- full garbage collections (one gc.callbacks entry) -------------------
    def watch_gc(self) -> None:
        """Record every full (generation 2) collection as a `gc.gen2`
        background span and in ``gc.pause_ms{gen}``; idempotent. Server
        roles call it at start."""
        import gc

        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)
            # the series exists from the start: a window without a full
            # collection reads 0, not "no such counter"
            METRICS.counter("gc.pause_ms", labels={"gen": "2"})

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        if info["generation"] < 2:
            return
        # A collection can start at any allocation, also one made while
        # this thread holds the metrics registry's or the ring's lock: the
        # callback only reads the clock and parks the pause (lock-free
        # deque); the next span to finish records it. The interpreter
        # runs one collection at a time, so one slot holds the start.
        if phase == "start":
            self._gc_t0 = time.monotonic_ns() \
                if FLAGS.get("trace_sampling_rate") > 0.0 else 0
        elif self._gc_t0:
            self._gc_parked.append((self._gc_t0, time.monotonic_ns(),
                                    _CURRENT.get(), threading.get_ident()))
            self._gc_t0 = 0

    def _record_parked_gc(self) -> None:
        while self._gc_parked:
            try:
                t0, t1, cur, thread_id = self._gc_parked.popleft()
            except IndexError:      # another thread took the last one
                return
            span = self._background_span("gc.gen2", cur)
            span.start_ns, span.thread_id = t0, thread_id
            span.end_ns = t1
            METRICS.counter("gc.pause_ms", labels={"gen": "2"}).add(
                (t1 - t0) / 1e6)
            self._finish(span)

    def _finish(self, span: Span) -> None:
        if self._gc_parked:
            self._record_parked_gc()
        rec = span.record()
        self.buffer.add(rec)
        self._recorded.add(1)
        if span.background == 1:
            METRICS.counter(
                "background.busy_ms", labels={"job": span.name}
            ).add((span.end_ns - span.start_ns) / 1e6)
        # bridge: every span name is automatically a LatencyRecorder, so
        # aggregate percentiles come for free wherever a span exists; the
        # trace id rides along as an exemplar candidate (outlier samples
        # surface it in the Prometheus exposition)
        recorder = self._recorders.get(span.name)
        if recorder is None:
            recorder = self._recorders[span.name] = METRICS.latency(
                f"span.{span.name}")
        recorder.observe_us(rec["dur_us"], trace_id=rec["trace_id"])
        if self._slow_eligible(span.name, span.parent_id):
            slow_ms = FLAGS.get("slow_query_ms")
            if slow_ms > 0 and rec["dur_us"] >= slow_ms * 1000.0:
                self.buffer.add_slow(rec)
                bundle_id = self._capture_flight(rec)
                if bundle_id:
                    # pin the scrape exemplar to THIS sample: the p99
                    # series must link to the trace a bundle was CAPTURED
                    # for — not to a larger unbundled sample (a warmup
                    # compile), and not to a rate-limited slow query that
                    # has no bundle to link to
                    recorder.pin_exemplar(rec["dur_us"], rec["trace_id"])
                # logs -> traces -> flight bundles are one hop each: the
                # line carries the trace id and (when captured) the bundle
                _log.warning(
                    "slow query: %s took %.1f ms (trace %s%s)",
                    span.name, rec["dur_us"] / 1000.0, rec["trace_id"],
                    f", bundle {bundle_id}" if bundle_id else "",
                )

    @staticmethod
    def _capture_flight(rec: Dict[str, Any]) -> str:
        """Hand the slow-log record to the flight recorder (lazy import —
        this is the slow path only; the recorder itself rate-limits).
        Observability must never fail the request that tripped it."""
        try:
            from dingo_tpu.obs.flight import FLIGHT

            return FLIGHT.on_slow_query(rec)
        except Exception:  # noqa: BLE001
            return ""

    #: replication-plane spans: a slow/down PEER makes every one of these
    #: slow — they'd churn the user-query evidence out of the slow log
    _SLOW_LOG_EXCLUDE = ("rpc.RaftService.", "client.RaftService.",
                         "rpc.PushService.", "client.PushService.")

    @classmethod
    def _slow_eligible(cls, name: str, parent_id: int = 0) -> bool:
        """Slow-QUERY log membership: every RPC ingress span (root OR
        adopted from a remote parent — the serving store must log its own
        slow requests) and client-side request roots; never background
        roots (index.rebuild, raft-apply engine.write) or the raft/push
        replication plane."""
        if name.startswith(cls._SLOW_LOG_EXCLUDE):
            return False
        return name.startswith("rpc.") or (
            parent_id == 0 and name.startswith("client.")
        )

    # -- always-sample-slow (tail safety net) --------------------------------
    def slow_watch_start(self) -> int:
        """Non-zero t0 when a request that LOST the head-sampling roll
        should still be watched for the slow-query log. Costs two clock
        reads per request at the ingress only; returns 0 (no watching)
        when tracing is fully off so the rate-0 path stays free."""
        if FLAGS.get("trace_sampling_rate") > 0 \
                and FLAGS.get("slow_query_ms") > 0:
            return time.monotonic_ns()
        return 0

    def slow_watch_end(self, name: str, t0: int) -> None:
        if not t0 or not self._slow_eligible(name):
            return
        dur_us = (time.monotonic_ns() - t0) // 1000
        slow_ms = FLAGS.get("slow_query_ms")
        if slow_ms <= 0 or dur_us < slow_ms * 1000.0:
            return
        # synthesized single-record evidence: the request was unsampled so
        # no span tree exists, but the outlier itself must not be lost
        rec = {
            "name": name, "trace_id": "", "span_id": "", "parent_id": "",
            "start_us": t0 // 1000, "dur_us": dur_us,
            "thread": threading.get_ident(), "status": "ok",
            "attrs": {"unsampled": True},
        }
        self.buffer.add_slow(rec)
        bundle_id = self._capture_flight(rec)
        _log.warning(
            "slow query (unsampled): %s took %.1f ms%s",
            name, dur_us / 1000.0,
            f" (bundle {bundle_id})" if bundle_id else "",
        )


# -- cross-process propagation (gRPC metadata) -------------------------------

def inject_metadata(
    metadata: Optional[Sequence[Tuple[str, str]]] = None,
) -> Optional[List[Tuple[str, str]]]:
    """Metadata list carrying the current span context, merged with the
    caller's metadata. Returns the input unchanged (possibly None) when
    there is nothing to propagate — the no-trace path must not allocate."""
    cur = _CURRENT.get()
    if cur is None or not cur.sampled:
        return list(metadata) if metadata is not None else None
    entry = (
        TRACE_METADATA_KEY,
        f"{cur.trace_id:016x}-{cur.span_id:016x}-1",
    )
    return [*(metadata or ()), entry]


def extract_metadata(
    metadata: Optional[Iterable[Tuple[str, str]]],
) -> Optional[SpanContext]:
    """Parse the propagation header out of gRPC invocation metadata.
    Returns None when absent or malformed (a bad header must never fail
    the RPC it rode in on)."""
    if not metadata:
        return None
    for key, value in metadata:
        if key != TRACE_METADATA_KEY:
            continue
        try:
            trace_hex, span_hex, flags = value.split("-")
            return SpanContext(
                int(trace_hex, 16), int(span_hex, 16),
                sampled=bool(int(flags)),
            )
        except (ValueError, AttributeError):
            return None
    return None


from dingo_tpu.trace.buffer import TRACE_BUFFER  # noqa: E402  (cycle-free: buffer has no span import)

TRACER = Tracer(TRACE_BUFFER)
