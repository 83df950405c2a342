"""Shared tracing wrapper for the mesh-sharded search fan-outs.

One context manager instead of three copies of the start/attr/error/end
boilerplate in sharded_flat / sharded_ivf / sharded_pq.
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def shard_search_span(name: str, mesh):
    """Span around a sharded search dispatch: records the mesh fan-out,
    marks errors, and always ends. It times the dispatch only; the device
    wait is the ``ops.mesh_search`` span the caller starts after it and
    ends at resolve()'s one fetch (ops/distance.device_wait_begin) — a
    sampled request never synchronises."""
    from dingo_tpu.trace import TRACER

    span = TRACER.start_span(name)
    if span.sampled:
        for axis in ("data", "dim"):
            if axis in mesh.shape:
                span.set_attr(f"{axis}_shards", mesh.shape[axis])
    try:
        yield span
    except BaseException as e:
        span.set_error(e)
        raise
    finally:
        span.end()
