"""Recompile sentinel: jit-cache observability for the device kernels.

PR 3's shape-bucketing ladder and warmup() exist so that steady-state
serving never recompiles — but until now nothing OBSERVED that invariant.
A single silent retrace costs 100ms-40s of compile stall on the serving
path, and it shows up only as an inexplicable p99 outlier.

``sentinel_jit(name, ...)`` is a drop-in replacement for ``jax.jit`` used
at every persistent jitted entry point (ops/, index/, parallel/). It
detects a trace the robust way: the wrapped Python body only executes
while jax is TRACING, so a thread-local mark set inside the body tells the
caller "this call compiled". No private jit APIs, works across jax
versions, and composes with static_argnames / donate_argnums /
out_shardings (``functools.wraps`` carries the original signature so
positional static args still resolve).

Per kernel the sentinel counts calls, cache hits, and traces; per trace it
records the argument signature (dtype + shape bucket — the label that
tells you WHICH shape broke the ladder), the compile wall time (gauge
``xla.compile_ms``, counters ``xla.recompiles`` / ``xla.compile_ms_total``)
and an ``xla.compile`` background span in the tracer (recorded at any
``trace_sampling_rate`` above 0, whatever the head roll said) — parented
under the current request's trace when one is sampled, minted as a root
otherwise: a compile stall is evidence, never noise.

Cost contract: a cache-hit call pays one thread-local push/pop, two clock
reads, and one Counter.add. Signatures are only computed on a miss.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Any, Dict, Optional

from dingo_tpu.common.metrics import METRICS

__all__ = ["SENTINEL", "RecompileSentinel", "sentinel_jit"]


def _arg_sig(args, kwargs) -> str:
    """Compact (dtype, shape) signature of a call — the shape-bucket/dtype
    label a recompile is attributed to. Uses `x` as the dim separator so
    the value stays legal inside a `name{k=v,...}` metric series key
    (commas would corrupt split_series_key)."""
    parts = []
    for a in list(args) + [v for _, v in sorted(kwargs.items())]:
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is not None and dtype is not None:
            dims = "x".join(str(d) for d in shape)
            parts.append(f"{getattr(dtype, 'name', dtype)}[{dims}]")
        elif isinstance(a, (int, float, bool, str)):
            parts.append(repr(a))
        else:
            parts.append(type(a).__name__)
    return "_".join(parts)[:160]


class _Entry:
    """Per-kernel cache accounting (lock-protected on the miss path only;
    `calls` rides the hit path as a plain int — monitoring-grade)."""

    __slots__ = ("calls", "traces", "compile_ms_total", "last_compile_ms",
                 "last_trace_at", "sigs", "lock")

    def __init__(self):
        self.calls = 0
        self.traces = 0
        self.compile_ms_total = 0.0
        self.last_compile_ms = 0.0
        self.last_trace_at = 0.0
        self.sigs: Dict[str, int] = {}
        self.lock = threading.Lock()


class RecompileSentinel:
    """Registry of sentinel-wrapped kernels + the trace-detection
    thread-local. Global singleton ``SENTINEL``; state() feeds the flight
    recorder's "kernel cache state" section."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[str, _Entry] = {}
        self._tls = threading.local()

    # ---- registry ----------------------------------------------------------
    def entry(self, kernel: str) -> _Entry:
        with self._lock:
            e = self._entries.get(kernel)
            if e is None:
                e = self._entries[kernel] = _Entry()
            return e

    def recompiles(self) -> int:
        """Lifetime process total (same figure as the xla.recompiles
        counter; kept here so non-metrics callers can diff it)."""
        with self._lock:
            return sum(e.traces for e in self._entries.values())

    def state(self) -> Dict[str, Dict[str, Any]]:
        """Flight-recorder snapshot: per-kernel cache accounting."""
        with self._lock:
            entries = list(self._entries.items())
        out: Dict[str, Dict[str, Any]] = {}
        for kernel, e in entries:
            with e.lock:
                out[kernel] = {
                    "calls": e.calls,
                    "traces": e.traces,
                    "cache_hits": max(0, e.calls - e.traces),
                    "compile_ms_total": round(e.compile_ms_total, 2),
                    "last_compile_ms": round(e.last_compile_ms, 2),
                    "last_trace_age_s": (
                        round(time.monotonic() - e.last_trace_at, 1)
                        if e.last_trace_at else None
                    ),
                    "signatures": dict(e.sigs),
                }
        return out

    # ---- trace detection (thread-local frame stack) ------------------------
    def _push(self, kernel: str) -> None:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        stack.append([kernel, False])

    def _pop(self) -> bool:
        stack = getattr(self._tls, "stack", None)
        if not stack:
            return False
        return stack.pop()[1]

    def mark_trace(self, kernel: str) -> None:
        """Called from INSIDE the wrapped function body — i.e. only while
        jax is tracing it. Flags the innermost in-flight call of this
        kernel; a mark with no frame (retrace outside a wrapper call, e.g.
        jax re-tracing for a new backend) still counts the trace."""
        stack = getattr(self._tls, "stack", None)
        if stack:
            for frame in reversed(stack):
                if frame[0] == kernel:
                    frame[1] = True
                    return
            stack[-1][1] = True
            return
        self._record_trace(kernel, 0.0, "", timed=False)

    # ---- recording ---------------------------------------------------------
    def _record_trace(self, kernel: str, dur_ms: float, sig: str,
                      timed: bool = True) -> None:
        e = self.entry(kernel)
        with e.lock:
            e.calls += 1
            e.traces += 1
            e.last_trace_at = time.monotonic()
            if timed:
                e.compile_ms_total += dur_ms
                e.last_compile_ms = dur_ms
            if sig:
                e.sigs[sig] = e.sigs.get(sig, 0) + 1
        # the per-kernel breakdown rides a DISTINCT name: sharing
        # xla.recompiles would make sum(xla_recompiles) double-count
        METRICS.counter("xla.recompiles").add(1)
        METRICS.counter("xla.recompiles_by_kernel",
                        labels={"kernel": kernel}).add(1)
        if timed:
            METRICS.counter("xla.compile_ms_total").add(int(dur_ms))
            METRICS.gauge(
                "xla.compile_ms", labels={"kernel": kernel}
            ).set(dur_ms)
            self._emit_compile_span(kernel, dur_ms, sig)

    def _emit_compile_span(self, kernel: str, dur_ms: float,
                           sig: str) -> None:
        """Record the compile stall as an `xla.compile` background span
        (the tracer's rule: under the current sampled request span when
        there is one, so the stall shows up inside the victim's trace;
        else a root of its own, at any sampling rate > 0). The compile is
        only known once it is over, so the span is back-dated by it."""
        from dingo_tpu.trace import TRACER

        span = TRACER.start_background(
            "xla.compile", backdate_ns=int(dur_ms * 1e6))
        span.set_attr("kernel", kernel)
        if sig:
            span.set_attr("sig", sig)
        span.set_attr("ms", round(dur_ms, 2))
        span.end()


SENTINEL = RecompileSentinel()


def sentinel_jit(kernel: str, fn=None, **jit_kwargs):
    """``jax.jit`` with recompile accounting under `kernel`.

    Decorator or call form::

        @sentinel_jit("ops.scan", static_argnames=("k",))
        def _scan(...): ...

        self._search_jit = sentinel_jit("parallel.flat.search",
                                        search_fn, static_argnames=("k",))

    All jit kwargs pass through. The returned wrapper exposes the raw
    jitted callable as ``._jitted`` and the kernel name as ``._kernel``.
    """
    if fn is None:
        return functools.partial(sentinel_jit, kernel, **jit_kwargs)

    import jax

    def _traced(*args, **kwargs):
        SENTINEL.mark_trace(kernel)
        return fn(*args, **kwargs)

    # carries the original signature so jax resolves static_argnames for
    # positionally-passed arguments through __wrapped__
    functools.update_wrapper(_traced, fn)
    jitted = jax.jit(_traced, **jit_kwargs)
    entry = SENTINEL.entry(kernel)
    hits = METRICS.counter("xla.cache_hits", labels={"kernel": kernel})

    from dingo_tpu.ops.devfault import DEVFAULT

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        DEVFAULT.maybe_fail(kernel)
        SENTINEL._push(kernel)
        t0 = time.perf_counter_ns()
        try:
            out = jitted(*args, **kwargs)
        except BaseException:
            if SENTINEL._pop():
                # the trace happened; the failure makes its wall time
                # meaningless (it may BE a compile/OOM failure)
                SENTINEL._record_trace(kernel, 0.0,
                                       _arg_sig(args, kwargs), timed=False)
            else:
                # a warm call that failed at RUNTIME (device OOM, say) is
                # still a call + cache hit — the flight bundle debugging
                # that very failure must not show it missing from the
                # kernel's accounting
                entry.calls += 1
                hits.add(1)
            raise
        if SENTINEL._pop():
            SENTINEL._record_trace(
                kernel, (time.perf_counter_ns() - t0) / 1e6,
                _arg_sig(args, kwargs),
            )
        else:
            entry.calls += 1
            hits.add(1)
        return out

    wrapper._kernel = kernel
    wrapper._jitted = jitted
    return wrapper
