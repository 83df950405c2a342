"""The one process that touches the chip: the store, through its normal entry
(`dingo_tpu.server.main.main([... --role store --engine wal ...])`), with the
benchmark's instruments put around it from outside:

- every crontab job, every `WalEngine` checkpoint, every index scrub / save /
  rebuild / compaction and every Python garbage collection is logged with
  its begin and end (`time.monotonic()`), for the run's one-second timeline;
- in a `--trace 1` run the store's own spans, of the share of the requests
  that `harness.json` names, are kept whole (not in its 2048 entry ring) and
  written into the profiler's trace as `TraceAnnotation`s, and `jax.profiler`
  wraps a few seconds of the steady window.

Nothing of the store's behaviour is changed: no job is switched off, no
interval stretched, no flag set except `trace_sampling_rate` while a traced
window is open. The store itself refuses any backend but a TPU
(`config.require_device`), unless JAX_PLATFORMS=cpu is set explicitly: the
CPU rehearsal, which `run.py` never reports as a pass.

The parent speaks JSON lines on stdin; replies leave on the original stdout,
and everything the store prints goes to its log file.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

#: calls inside the store worth a line in the timeline, besides crontab jobs:
#: (module, class, method, name in the timeline)
WATCHED = [
    ("dingo_tpu.engine.raw_engine", "WalEngine", "_checkpoint_locked",
     "wal_checkpoint"),
    ("dingo_tpu.index.manager", "VectorIndexManager", "save_index",
     "index_save"),
    ("dingo_tpu.index.manager", "VectorIndexManager", "rebuild",
     "index_rebuild"),
    ("dingo_tpu.index.manager", "VectorIndexManager", "scrub", "index_scrub"),
    ("dingo_tpu.index.manager", "VectorIndexManager", "compact_views",
     "ivf_compact_views"),
    ("dingo_tpu.metrics.collector", "StoreMetricsCollector", "collect",
     "metrics_collect"),
]


class Instruments:
    def __init__(self):
        self.events = []        # [name, t_begin, t_end]
        self.crontab = {}       # name -> {"interval_s", "added"}
        self.gc_events = []     # [generation, t_begin, seconds]
        self._gc_t0 = 0.0
        self.spans = []         # span records of a traced window
        self.keep_spans = False

    # -- wrappers -----------------------------------------------------------
    def timed(self, name, fn):
        def wrapper(*a, **kw):
            t0 = time.monotonic()
            try:
                return fn(*a, **kw)
            finally:
                self.events.append([name, t0, time.monotonic()])
        return wrapper

    def install(self, watch: bool = True) -> None:
        import importlib

        from dingo_tpu.common import crontab as crontab_mod

        inst = self
        add = crontab_mod.CrontabManager.add

        def add_logged(mgr, name, interval_s, func, immediately=False):
            inst.crontab[name] = {"interval_s": float(interval_s),
                                  "added": time.monotonic(),
                                  "immediately": bool(immediately)}
            return add(mgr, name, interval_s,
                       inst.timed("cron." + name, func), immediately)

        crontab_mod.CrontabManager.add = add_logged
        for module, cls, method, name in (WATCHED if watch else ()):
            try:
                owner = getattr(importlib.import_module(module), cls)
                setattr(owner, method, self.timed(name, getattr(owner, method)))
            except (ImportError, AttributeError) as e:
                print(f"store_host: cannot watch {module}.{cls}.{method}: {e}",
                      flush=True)
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info) -> None:
        if phase == "start":
            self._gc_t0 = time.monotonic()
        else:
            self.gc_events.append([info["generation"], self._gc_t0,
                                   time.monotonic() - self._gc_t0])

    # -- the store's spans, kept whole while a traced window is open ---------
    def add(self, record) -> None:
        if self.keep_spans:
            self.spans.append([record["name"], record["start_us"],
                               record["dur_us"], record["trace_id"],
                               record["parent_id"], record["span_id"]])

    def add_slow(self, record) -> None:
        pass

    def tap_spans(self) -> None:
        """Spans to this sink instead of the store's ring, and `with`-scoped
        spans also into the profiler's trace."""
        import jax
        from dingo_tpu.trace import span as span_mod

        span_mod.TRACER.buffer = self
        enter, leave = span_mod.Span.__enter__, span_mod.Span.__exit__

        # Span uses __slots__: the annotation rides a side table instead
        table = {}

        def enter_slots(span):
            if self.keep_spans:
                ann = jax.profiler.TraceAnnotation("span:" + span.name)
                ann.__enter__()
                table[id(span)] = ann
            return enter(span)

        def leave_slots(span, exc_type, exc, tb):
            out = leave(span, exc_type, exc, tb)
            ann = table.pop(id(span), None)
            if ann is not None:
                ann.__exit__(exc_type, exc, tb)
            return out

        span_mod.Span.__enter__ = enter_slots
        span_mod.Span.__exit__ = leave_slots


def control_loop(inst: Instruments, reply_fd: int, out_dir: str) -> None:
    import jax
    from dingo_tpu.common.config import FLAGS
    from dingo_tpu.common.metrics import METRICS

    reply = os.fdopen(reply_fd, "w")
    for line in sys.stdin:
        try:
            cmd = json.loads(line)
            op = cmd["cmd"]
            if op == "device":
                d = jax.devices()
                out = {"platform": d[0].platform, "kind": d[0].device_kind,
                       "count": len(d)}
            elif op == "events":
                out = {"events": list(inst.events), "crontab": inst.crontab,
                       "gc": list(inst.gc_events), "now": time.monotonic()}
            elif op == "memory":
                stats = [dev.memory_stats() or {} for dev in jax.devices()]
                out = {"peak_bytes": max(
                    (s.get("peak_bytes_in_use", 0) for s in stats), default=0),
                    "bytes_in_use": [s.get("bytes_in_use", 0) for s in stats],
                    "bytes_limit": [s.get("bytes_limit", 0) for s in stats]}
                try:        # what this process has written to disk so far
                    with open("/proc/self/io") as f:
                        io = dict(ln.split(": ") for ln in f.read().split("\n")
                                  if ": " in ln)
                    out["disk_write_bytes"] = int(io.get("write_bytes", 0))
                except OSError:
                    pass
            elif op == "metrics":
                out = {"metrics": METRICS.dump()}
            elif op == "spans_on":
                inst.spans.clear()
                inst.keep_spans = True
                FLAGS.set("trace_sampling_rate", float(cmd["rate"]))
                out = {}
            elif op == "spans_off":
                FLAGS.set("trace_sampling_rate", 0.0)
                inst.keep_spans = False
                path = os.path.join(out_dir, "spans.json")
                with open(path, "w") as f:
                    json.dump(inst.spans, f)
                out = {"file": path, "spans": len(inst.spans)}
            elif op == "profile_start":
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 2
                out = {"t": time.monotonic()}
                jax.profiler.start_trace(cmd["dir"], profiler_options=options)
                out["t_started"] = time.monotonic()
            elif op == "profile_stop":
                out = {"t": time.monotonic()}
                jax.profiler.stop_trace()
                out["t_stopped"] = time.monotonic()
            else:
                out = {"error": f"unknown command {op!r}"}
        except Exception as e:  # noqa: BLE001 — reported to the parent
            out = {"error": f"{type(e).__name__}: {e}"[:500]}
        reply.write(json.dumps(out) + "\n")
        reply.flush()


def main() -> int:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    if spec.get("cores"):
        os.sched_setaffinity(0, spec["cores"])
    # replies on the original stdout; the store's own prints to its log
    reply_fd = os.dup(1)
    log = os.open(spec["log"], os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
    os.dup2(log, 1)
    os.dup2(log, 2)
    sys.stdout = os.fdopen(1, "w", buffering=1, closefd=False)
    sys.stderr = os.fdopen(2, "w", buffering=1, closefd=False)

    inst = Instruments()
    inst.install(watch="coordinator" not in spec["argv"])
    if spec.get("trace"):
        inst.tap_spans()
    threading.Thread(target=control_loop, name="bench-control", daemon=True,
                     args=(inst, reply_fd, spec["out"])).start()

    from dingo_tpu.server.main import main as store_main

    return store_main(spec["argv"])


if __name__ == "__main__":
    sys.exit(main())
