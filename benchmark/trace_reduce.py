"""From a profiler trace (`.xplane.pb`) to the numbers the benchmark reports:
device busy seconds (the union of the intervals in which an operation ran,
averaged over the chips), the time of every device operation by name, and the
idle gaps of the device attributed to what the host was doing in them.

Reads the file with `jax.profiler.ProfileData` and never touches a backend.
Runs as a process of its own once the store is gone:

    python benchmark/trace_reduce.py <dir or file> > reduced.json
"""

from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import re
import sys

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
GAPS_KEPT = 400
NOTHING = "nothing traced (Python, gRPC, or no request)"


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(
        path, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def union_seconds(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_of(intervals):
    """Gaps (start, end) between merged intervals, inside their span."""
    out, cur_e = [], None
    for s, e in sorted(intervals):
        if cur_e is not None and s > cur_e:
            out.append((cur_e, s))
        cur_e = e if cur_e is None else max(cur_e, e)
    return out


@functools.lru_cache(maxsize=None)     # a few hundred texts, millions of events
def short_op(text: str) -> str:
    """`%fusion.2 = f32[...] fusion(...), kind=...` -> `fusion.2:fusion`:
    the instruction's name and opcode, not its whole text."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text[:120]
    m = re.search(r"(?:^|[\s})])([a-z][\w\-]*)\(", rest)
    return (name.lstrip("%") + (":" + m.group(1) if m else ""))[:120]


def _module_of(stats: dict, start: float, modules, starts=None) -> str:
    """The module an operation ran in: by its own stats, else the first
    event of the device's modules line that holds its start. `starts` (the
    modules' starts, where they are in order and do not overlap) finds it by
    bisection: an hnsw trace holds 1.7 M operations under 2,300 modules."""
    for key in ("hlo_module", "module", "program"):
        if stats.get(key):
            return str(stats[key])
    if starts is not None:
        i = bisect.bisect_right(starts, start) - 1
        return modules[i][0] if i >= 0 and start < modules[i][2] else ""
    for name, s, e in modules:
        if s <= start < e:
            return name
    return ""


def _ordered_starts(modules):
    """The modules' starts if each begins at or after the one before ends
    (then at most one holds a given time, and bisection finds the one the
    scan would), else None."""
    ok = all(a[2] <= b[1] for a, b in zip(modules, modules[1:]))
    return [m[1] for m in modules] if ok else None


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except Exception:  # noqa: BLE001 — a stat the binding cannot decode
        return {}


def read_planes(path: str):
    """-> (device: {plane: [(name, start_s, end_s)]}, host: [(name, s, e)],
    structure: a short description of what the file holds)"""
    from jax.profiler import ProfileData

    found = find_xplane(path)
    if found.endswith(".gz"):           # the recorded trace of the self-test
        import gzip

        with gzip.open(found, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(found)
    device, host, structure = {}, [], []
    for plane in data.planes:
        lines = list(plane.lines)
        structure.append({"plane": plane.name, "lines": [
            [ln.name, sum(1 for _ in ln.events)] for ln in lines]})
        if DEVICE_PLANE.match(plane.name):
            modules = []
            for ln in lines:
                if ln.name == MODULES_LINE:
                    modules = [(re.sub(r"\(\d+\)$", "", ev.name),
                                ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9)
                               for ev in ln.events]
            ops, starts = [], _ordered_starts(modules)
            for ln in lines:
                if ln.name != OPS_LINE:
                    continue
                for ev in ln.events:
                    s = ev.start_ns * 1e-9
                    module = _module_of(_stats(ev), s, modules, starts)
                    op = short_op(ev.name)
                    name = f"{module}/{op}" if module else op
                    ops.append((name, s, s + ev.duration_ns * 1e-9))
            device[plane.name] = ops
        elif plane.name == HOST_PLANE:
            for ln in lines:
                for ev in ln.events:
                    if ev.duration_ns > 0:
                        s = ev.start_ns * 1e-9
                        host.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return device, host, structure


def attribute(gap, host) -> str:
    """What the host was doing in an idle gap of the device: the shortest
    host event that covers at least half of it, else the one that overlaps
    it most."""
    gs, ge = gap
    best_cover, best_overlap = None, None
    for name, s, e in host:
        ov = min(e, ge) - max(s, gs)
        if ov <= 0:
            continue
        if ov >= 0.5 * (ge - gs) and (
                best_cover is None or e - s < best_cover[1]):
            best_cover = (name, e - s)
        if best_overlap is None or ov > best_overlap[1]:
            best_overlap = (name, ov)
    pick = best_cover or best_overlap
    return ("host: " + pick[0]) if pick else NOTHING


def reduce_trace(path: str) -> dict:
    device, host, structure = read_planes(path)
    out = {"structure": structure, "devices": len(device)}
    if not device:
        return out
    busy, ops, spans = [], {}, []
    calls = {}
    for plane, events in device.items():
        iv = [(s, e) for _, s, e in events]
        busy.append(union_seconds(iv))
        if iv:
            spans.append((min(s for s, _ in iv), max(e for _, e in iv)))
        for name, s, e in events:
            ops[name] = ops.get(name, 0.0) + (e - s)
            calls[name] = calls.get(name, 0) + 1
    n = len(device)
    out["busy_s"] = sum(busy) / n
    out["op_seconds"] = {k: v / n for k, v in ops.items()}
    out["op_calls"] = {k: v / n for k, v in calls.items()}
    out["device_span_s"] = max((e - s for s, e in spans), default=0.0)
    out["device_ops"] = [[k, v / n] for k, v in sorted(
        ops.items(), key=lambda kv: -kv[1])[:10]]
    # idle gaps of the first device, named by the host's events
    first = sorted(device)[0]
    gaps = sorted(gaps_of([(s, e) for _, s, e in device[first]]),
                  key=lambda g: g[0] - g[1])
    starts = np.asarray([ev[1] for ev in host])
    ends = np.asarray([ev[2] for ev in host])
    by_name = {}
    for gap in gaps[:GAPS_KEPT]:
        # host events are many: look only at those that overlap the gap
        near = np.nonzero((starts < gap[1]) & (ends > gap[0]))[0] \
            if len(host) else []
        name = attribute(gap, [host[i] for i in near])
        by_name[name] = by_name.get(name, 0.0) + (gap[1] - gap[0])
    rest = sum(g[1] - g[0] for g in gaps[GAPS_KEPT:])
    if rest:
        by_name[f"gaps beyond the {GAPS_KEPT} longest"] = rest
    out["idle_gaps"] = [[k, v] for k, v in sorted(
        by_name.items(), key=lambda kv: -kv[1])[:10]]
    out["idle_s"] = sum(g[1] - g[0] for g in gaps)
    return out


def main() -> int:
    print(json.dumps(reduce_trace(sys.argv[1])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
