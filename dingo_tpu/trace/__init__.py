"""Distributed tracing & query profiling.

The reference attributes latency with ad-hoc bvar recorders and a
per-request Tracker (src/common/tracker.h) that never leaves the process.
This package adds real causality: every RPC ingress mints (or adopts) a
trace id, spans nest through contextvars across the coalescer's thread
handoffs, gRPC metadata carries the context between processes, and a
bounded ring buffer retains sampled traces for the DebugService JSON dump
and a Chrome ``trace_event`` file (chrome://tracing / Perfetto).

Overhead contract: with ``trace_sampling_rate = 0`` every instrumented
site costs ONE sampled-check (a contextvar read + flag read) and returns
the shared no-op span — no allocations, no clock reads on the hot path.
A traced request makes exactly the device calls an untraced one makes:
no span ever synchronises with the device (ops/distance.device_wait_begin
stamps the wait at the reply's one fetch).

Layer-boundary spans (``Tracer.start_child``) exist only inside a sampled
request; background work (crontab jobs, checkpoints, saves, full GCs,
compiles: ``Tracer.start_background``) is recorded at any rate above 0.
All timestamps are ``time.monotonic_ns()``. ``trace/profile.py`` captures
a device profile with the spans mirrored into it (DebugService
DeviceProfile).
"""

from dingo_tpu.trace.buffer import TRACE_BUFFER, TraceBuffer
from dingo_tpu.trace.export import (
    dump_chrome_trace,
    to_chrome_trace,
    to_json,
)
from dingo_tpu.trace.span import (
    NOOP_SPAN,
    TRACE_METADATA_KEY,
    UNSAMPLED_HEADER,
    Span,
    SpanContext,
    TRACER,
    Tracer,
    current_span,
    extract_metadata,
    inject_metadata,
)

__all__ = [
    "NOOP_SPAN",
    "Span",
    "SpanContext",
    "TRACER",
    "TRACE_BUFFER",
    "TRACE_METADATA_KEY",
    "TraceBuffer",
    "Tracer",
    "UNSAMPLED_HEADER",
    "current_span",
    "dump_chrome_trace",
    "extract_metadata",
    "inject_metadata",
    "to_chrome_trace",
    "to_json",
]
