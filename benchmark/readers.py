"""The small generic set of readers that per-layer metrics are made from.

A metric is a data file, `metrics/<name>.json`: {"reader": <one of READERS>,
"args": {...}, "what": "..."}. A reader takes the run's record (`Run`) and
its arguments and returns a number, or None where it finds nothing to read:
the harness then leaves the metric out of the line. No reader returns 0 for
a share it could not measure.
"""

from __future__ import annotations

import importlib.util
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def percentile(values, p: float):
    """Nearest-rank percentile of a sequence (p in 0..100), None if empty."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return None
    rank = max(1, int(np.ceil(p / 100.0 * len(v))))
    return float(v[rank - 1])


def stat(values, name: str):
    if name == "mean":
        return float(np.mean(values)) if len(values) else None
    if name == "max":
        return float(np.max(values)) if len(values) else None
    if name.startswith("p"):
        return percentile(values, float(name[1:]))
    raise ValueError(f"unknown statistic {name!r}")


class Run:
    """What a run recorded, for the readers."""

    def __init__(self, **kw):
        self.records = kw.get("records")          # [n, 4] due,start,done,ok
        self.t_open = kw.get("t_open")
        self.t_close = kw.get("t_close")
        self.spans = kw.get("spans") or []        # name,start_us,dur_us,trace,parent,id
        self.metrics_before = kw.get("metrics_before") or {}
        self.metrics_after = kw.get("metrics_after") or {}
        self.trace = kw.get("trace") or {}        # trace_reduce output
        self.profile = kw.get("profile")          # (t_started, t_stopped)
        self.setup = kw.get("setup") or {}        # phase -> seconds
        self.memory = kw.get("memory") or {}
        self.compared = kw.get("compared") or {}  # reference.py's numbers
        self.config = kw.get("config")
        self.traffic = kw.get("traffic")
        self.device_kind = kw.get("device_kind", "")
        self.readings = kw.get("readings") or {}  # the harness's own

    def latencies_ms(self, field: str = "latency"):
        """All requests of the window; a failed one counts as over any
        limit (infinite)."""
        r = self.records
        if r is None or not len(r):
            return np.zeros(0)
        if field == "late":
            return (r[:, 1] - r[:, 0]) * 1e3
        lat = (r[:, 2] - r[:, 0]) * 1e3
        return np.where(r[:, 3] > 0, lat, np.inf)


def series(dump: dict, name: str):
    """Every series of one counter or gauge in a MetricsDump."""
    return [v for k, v in dump.items()
            if (k == name or k.startswith(name + "{"))
            and isinstance(v, (int, float))]


def series_total(dump: dict, name: str):
    found = series(dump, name)
    return float(sum(found)) if found else None


def series_mean(dump: dict, name: str):
    found = series(dump, name)
    return float(np.mean(found)) if found else None


def _matches(name: str, patterns) -> bool:
    return any(name == p or (p.endswith("*") and name.startswith(p[:-1]))
               for p in patterns)


def per_trace(run: Run, spans, minus=()):
    """Per request (trace id): summed duration of the spans matching
    `spans`, less that of those matching `minus`; in ms."""
    plus, less = {}, {}
    for name, _start, dur, trace, _parent, _sid in run.spans:
        if _matches(name, spans):
            plus[trace] = plus.get(trace, 0.0) + dur / 1e3
        elif minus and _matches(name, minus):
            less[trace] = less.get(trace, 0.0) + dur / 1e3
    return [v - less.get(t, 0.0) for t, v in plus.items()]


# ------------------------------------------------------------------ readers
def request_stat(run: Run, stat_name: str, field: str = "latency"):
    v = run.latencies_ms(field)
    out = stat(v, stat_name) if len(v) else None
    return None if out is None or not np.isfinite(out) else out


def rate(run: Run, per_request: str = ""):
    """Work completed inside the window over the window's seconds: every
    request whose reply was complete by the close, times the traffic's
    `per_request` (its batch), over all the seconds of the window."""
    r = run.records
    if r is None or not len(r) or run.t_close <= run.t_open:
        return None
    done = int(((r[:, 3] > 0) & (r[:, 2] <= run.t_close)).sum())
    unit = run.traffic.get(per_request, 1) if per_request else 1
    return done * unit / (run.t_close - run.t_open)


def span_stat(run: Run, spans, stat_name: str, minus=()):
    v = per_trace(run, spans, minus)
    return stat(v, stat_name) if v else None


def difference(run: Run, of: dict, minus: dict):
    a, b = read(run, of), read(run, minus)
    return None if a is None or b is None else a - b


def stall_seconds(run: Run):
    """Seconds of the window in which nothing completed, per minute."""
    r = run.records
    if r is None or not len(r) or run.t_close <= run.t_open:
        return None
    n = int(np.ceil(run.t_close - run.t_open))
    done = np.floor(r[r[:, 3] > 0, 2] - run.t_open).astype(int)
    busy = np.zeros(n, bool)
    busy[done[(done >= 0) & (done < n)]] = True
    return float((~busy).sum()) * 60.0 / n


def counter_delta(run: Run, counter: str):
    after = series_total(run.metrics_after, counter)
    if after is None:
        return None
    return after - (series_total(run.metrics_before, counter) or 0.0)


def gauge(run: Run, gauge: str, scale: float = 1.0):  # noqa: A002
    v = series_mean(run.metrics_after, gauge)
    return None if v is None else v * scale


def compared(run: Run, key: str):
    v = run.compared.get(key)
    return None if v is None else float(v)


def harness_reading(run: Run, key: str):
    """What the harness read of itself and its machine (`host_probe.py`,
    the callers' CPU seconds)."""
    v = run.readings.get(key)
    return None if v is None else float(v)


def setup_phase(run: Run, phase: str):
    v = run.setup.get(phase)
    return None if v is None else float(v)


def memory_peak(run: Run, scale: float = 1e-9):
    v = run.memory.get("peak_bytes")
    return float(v) * scale if v else None


def _stage_seconds(run: Run, include=(), exclude=()):
    ops = run.trace.get("op_seconds")
    if not ops:
        return None
    total = 0.0
    for name, secs in ops.items():
        if include and not any(p in name for p in include):
            continue
        if any(p in name for p in exclude):
            continue
        total += secs
    return total or None


def _calls_in_profile(run: Run):
    """Requests answered inside the profiled seconds, on the generator's
    clock: one call of the stage each. (Not the store's spans: they are
    kept for a share of the requests only.)"""
    r = run.records
    if not run.profile or r is None or not len(r):
        return 0
    lo, hi = run.profile
    return int(((r[:, 3] > 0) & (r[:, 2] >= lo) & (r[:, 2] < hi)).sum())


def trace_op_time(run: Run, include=(), exclude=(), per_call: bool = False):
    """Device ms of the operations whose names hold one of `include` (all,
    if empty) and none of `exclude`; per request answered in the profiled
    seconds if `per_call`."""
    secs = _stage_seconds(run, include, exclude)
    if secs is None:
        return None
    if per_call:
        calls = _calls_in_profile(run)
        if not calls:
            return None
        secs /= calls
    return secs * 1e3


def load_work(name: str):
    path = os.path.join(HERE, "work", name + ".py")
    spec = importlib.util.spec_from_file_location("work_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.work


def least_seconds(work: dict, peaks: dict) -> float:
    return max(work["bytes"] / peaks["hbm_bytes_per_s"],
               work["flops"] / peaks["flops_per_s"])


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json")
    return table[device_kind]


def roofline(run: Run, work: str, include=(), exclude=()):
    """Share (%) of the stage's device time that the least time of its work
    explains: least = max(bytes / peak bytes/s, FLOPs / peak FLOP/s) per
    request answered in the profiled seconds, from the work function of the
    stage's shapes; device time = every traced operation of the stage."""
    secs = _stage_seconds(run, include, exclude)
    calls = _calls_in_profile(run)
    if secs is None or not calls:
        return None
    least = least_seconds(load_work(work)(run.config, run.traffic),
                          load_peaks(run.device_kind))
    return 100.0 * least * calls / secs


def idle_share(run: Run):
    busy, window = run.trace.get("busy_s"), run.trace.get("window_s")
    if not busy or not window:
        return None
    return 100.0 * (1.0 - busy / window)


READERS = {f.__name__: f for f in (
    request_stat, rate, span_stat, difference, stall_seconds, counter_delta, gauge,
    compared, harness_reading, setup_phase, memory_peak, trace_op_time, roofline,
    idle_share)}


def read(run: Run, spec: dict):
    fn = READERS.get(spec["reader"])
    if fn is None:
        raise KeyError(f"unknown reader {spec['reader']!r}")
    return fn(run, **spec.get("args", {}))


def read_metric(run: Run, name: str):
    with open(os.path.join(HERE, "metrics", name + ".json")) as f:
        return read(run, json.load(f))
