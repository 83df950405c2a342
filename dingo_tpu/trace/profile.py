"""Device profile of a running store, owned by the program.

``capture(dir, seconds)`` wraps ``jax.profiler`` around a few seconds of
whatever the process is serving and leaves, beside the profiler's
``.xplane.pb``, what is needed to put an idle gap of the device down to
the store's own work:

- while the capture is live (and only then) the tracer mirrors every
  ``with``-scoped span into the profiler's trace as a
  ``TraceAnnotation("span:<name>")`` host event;
- ``spans.json`` holds the spans that finished in the interval (the
  records the ring gets: name, ids, ``start_us``/``dur_us`` on the
  monotonic clock) and a ``(monotonic_ns, time_ns)`` pair taken at start
  and at stop, so span times, a caller's records and the trace's wall
  clock can be laid over each other.

Which requests have spans is the head sampler's business
(``trace_sampling_rate``); background spans are there at any rate > 0.
Only a process that holds the device may capture: the RPC face is
``DebugService.DeviceProfile`` of the store role.
"""

from __future__ import annotations

import glob
import json
import os
import threading
import time
from typing import Dict, List

from dingo_tpu.trace.span import TRACER

_capturing = threading.Lock()

#: an operator's typo must not hold the profiler for an hour
MAX_SECONDS = 120.0


class _Tee:
    """The tracer's buffer for the length of a capture: every record goes
    on to the buffer that was there, and is kept."""

    def __init__(self, inner) -> None:
        self.inner = inner
        self.records: List[Dict] = []

    def add(self, record: Dict) -> None:
        self.records.append(record)
        self.inner.add(record)

    def add_slow(self, record: Dict) -> None:
        self.inner.add_slow(record)


def _clock_pair() -> List[int]:
    return [time.monotonic_ns(), time.time_ns()]


def capture(out_dir: str, seconds: float) -> Dict:
    """Profile the device for `seconds` into `out_dir`; returns where the
    trace and the spans went. One capture at a time (RuntimeError
    otherwise); blocks the caller for the interval."""
    import jax

    seconds = float(seconds)
    if not 0.0 < seconds <= MAX_SECONDS:
        raise ValueError(f"seconds must be in (0, {MAX_SECONDS:g}]")
    if not _capturing.acquire(blocking=False):
        raise RuntimeError("a device profile is already being captured")
    try:
        os.makedirs(out_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the spans name the host side
        options.host_tracer_level = 2
        tee = _Tee(TRACER.buffer)
        jax.profiler.start_trace(out_dir, profiler_options=options)
        clock = {"start": _clock_pair()}
        TRACER.buffer = tee
        TRACER.annotate = jax.profiler.TraceAnnotation
        try:
            time.sleep(seconds)
        finally:
            TRACER.annotate = None
            TRACER.buffer = tee.inner
            clock["stop"] = _clock_pair()
            jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            out_dir, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        xplane = found[-1] if found else ""
        spans_file = os.path.join(
            os.path.dirname(xplane) if xplane else out_dir, "spans.json")
        with open(spans_file, "w") as f:
            json.dump({"clock": clock, "spans": tee.records}, f)
        return {"dir": out_dir, "xplane": xplane, "spans_file": spans_file,
                "spans": len(tee.records), "clock": clock}
    finally:
        _capturing.release()
