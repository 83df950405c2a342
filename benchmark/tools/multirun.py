"""Several runs of the benchmark in one call (one machine, one compile cache),
with their spreads: how the bounds in BENCHMARK.json were measured.

    python3 benchmark/tools/multirun.py --tag hunt --keep chiprun_out \
        ivf768.batch64:101:45:0 ivf768.batch64:102:45:0 ...

Each run is `workload:seed:seconds:trace[:extra run.py arguments,comma
separated]`. Prints every run's result line, then per workload and metric the
values, the median and the spread: the distance between the first and third
quartile (`statistics.quantiles(values, n=4)`) as a share of the median.
`--keep DIR` copies each run's timeline, reduced trace and logs to
DIR/<tag>/ and appends each result line to DIR/<tag>.jsonl (the chip tool
brings `chiprun_out/` back).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
KEPT = ("timeline.json", "trace_reduced.json", "store.log",
        "coordinator.log", "job.json")


def spread(values):
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else None


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--tag", default="runs")
    p.add_argument("--keep", default="")
    p.add_argument("runs", nargs="+")
    args = p.parse_args()
    keep = os.path.join(ROOT, args.keep, args.tag) if args.keep else ""
    if keep:
        os.makedirs(keep, exist_ok=True)
    rows = []
    for i, spec in enumerate(args.runs):
        parts = spec.split(":")
        workload, seed, seconds, trace = parts[:4]
        extra = [a for a in parts[4].split(",") if a] if len(parts) > 4 else []
        out = os.path.join(BENCH, "out", f"{args.tag}-{i}")
        cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
               workload, "--seed", seed, "--seconds", seconds, "--trace",
               trace, "--out", out] + extra
        t0 = time.monotonic()
        got = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        took = time.monotonic() - t0
        lines = got.stdout.strip().splitlines()
        last = lines[-1] if lines else ""
        print(f"--- run {i}: {spec} exit {got.returncode} in {took:.0f}s",
              flush=True)
        for ln in lines[:-1]:
            if not ln.startswith("timeline "):
                print("    " + ln[:400], flush=True)
        print("    " + last[:6000], flush=True)
        if got.returncode != 0:
            print("    stderr: " + got.stderr[-3000:], flush=True)
        try:
            result = json.loads(last)
        except ValueError:
            result = None
        # what the harness read of itself (`harness {...}` in the run's log)
        readings = next((json.loads(ln[8:]) for ln in lines[::-1]
                         if ln.startswith("harness {")), {})
        rows.append((workload, int(trace), seed, got.returncode, took, result,
                     readings))
        if keep:
            with open(keep + ".jsonl", "a") as f:
                f.write(json.dumps({"tag": args.tag, "spec": spec,
                                    "rc": got.returncode, "took": took,
                                    "result": result,
                                    "harness": readings}) + "\n")
            dest = os.path.join(keep, f"{i}-{workload}-s{seed}-t{trace}")
            os.makedirs(dest, exist_ok=True)
            for name in KEPT:
                if os.path.exists(os.path.join(out, name)):
                    shutil.copy(os.path.join(out, name), dest)
            with open(os.path.join(dest, "stdout.txt"), "w") as f:
                f.write(got.stdout)
            with open(os.path.join(dest, "stderr.txt"), "w") as f:
                f.write(got.stderr)
            prof = os.path.join(out, "profile")
            if os.path.isdir(prof):
                shutil.copytree(prof, os.path.join(dest, "profile"))
        shutil.rmtree(out, ignore_errors=True)
    print("=== summary", flush=True)
    groups = {}
    for workload, trace, seed, rc, took, result, readings in rows:
        if result is None:
            continue
        g = groups.setdefault((workload, trace), {})
        g.setdefault("_correct", []).append(result.get("correct"))
        g.setdefault("_seconds", []).append(round(took))
        for name, m in result.get("metrics", {}).items():
            g.setdefault(name, []).append(m["value"])
        for key in ("busy_s", "window_s", "memory_peak_bytes"):
            if key in result.get("device", {}):
                g.setdefault("device." + key, []).append(
                    result["device"][key])
        for name, value in readings.items():
            g.setdefault("harness." + name, []).append(value)
        for name, shown in result.get("compared", {}).items():
            g.setdefault("compared." + name, []).append(shown[0])
    for (workload, trace), g in groups.items():
        print(f"{workload} trace {trace}: correct {g.pop('_correct')} "
              f"run seconds {g.pop('_seconds')}")
        for name, values in g.items():
            sp = spread(values)
            print(f"  {name}: median {statistics.median(values):.6g} spread "
                  f"{'n/a' if sp is None else f'{100 * sp:.2f}%'} values "
                  f"{[float(f'{v:.5g}') for v in values]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
