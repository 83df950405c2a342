"""One run of one cell of BENCHMARK.json, on the served path.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This parent never imports JAX. It starts a coordinator, one store host
(`store_host.py`, the only process that touches the chip) and the load
generator as processes of their own (`caller.py`), loads and builds the
cell's configuration through the SDK, warms the cell's own shapes, opens the
window at a fixed offset from the store's own periodic jobs, measures for
`--seconds`, stops everything and then compares what the window's replies
said with the plain reference (`reference.py`).

Last line of stdout: one JSON object {correct, attempted, failed, metrics,
device[, breakdown], compared}. A machine without a TPU gives no result and
a non-zero exit. JAX_PLATFORMS=cpu with an explicit --rows is a rehearsal:
every line says `platform: cpu`, the last line is not a pass, exit 3.
"""

from __future__ import annotations

T_START = __import__("time").monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)
PARTITION = 1
PROBE_LEAD_S = 1.5      # host_probe.py runs this long before the window opens
FALLBACKS = ("fault.host_exact_searches", "fault.bruteforce_searches",
             "fault.oom_recoveries", "fault.degraded_regions")

_tag = ""


def say(msg: str) -> None:
    print(f"{msg}{_tag}", flush=True)


class RunFailure(Exception):
    pass


class SetupDeadline:
    """Set-up's own limit, `harness.json` `setup_deadline_s`, counted from
    the process's start. The driver stops a whole run at 360 s and says
    nothing of where it stood; a run that cannot open its window in time
    ends here instead, with the phase named: when a phase is still running
    at the deadline (an alarm in the main thread: it breaks the blocking
    `readline` on a child that never answers as well as a sleep or a gRPC
    wait), or as soon as `aligned_open` lays the window's open past it."""

    REPEAT_S = 0.5

    def __init__(self, seconds: float, t_start: float = T_START):
        self.seconds, self.t_start = float(seconds), t_start
        self.phase = "store_up"     # then region, load, build, warm, align
        self.fired = None

    def late(self, after_s: float) -> RunFailure:
        return RunFailure(f"set-up past its deadline of {self.seconds:g} s in "
                          f"phase {self.phase} after {after_s:.0f} s")

    def _on_alarm(self, signum, frame):
        # the alarm repeats every REPEAT_S until disarmed: an exception
        # raised inside a finalizer or a library's catch-all is swallowed,
        # and the next one ends the phase, as late as the first said
        self.fired = self.fired or time.monotonic()
        raise self.late(self.fired - self.t_start)

    def arm(self) -> None:
        signal.signal(signal.SIGALRM, self._on_alarm)
        signal.setitimer(signal.ITIMER_REAL, max(
            1e-3, self.t_start + self.seconds - time.monotonic()),
            self.REPEAT_S)

    def check_open(self, t_open: float) -> None:
        """The open of the window is laid: past the deadline the run ends
        now, not after the wait for it."""
        self.phase = "align"
        if t_open - self.t_start > self.seconds:
            raise self.late(t_open - self.t_start)

    def disarm(self) -> None:
        signal.setitimer(signal.ITIMER_REAL, 0)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# ---------------------------------------------------------------- processes
class Child:
    """A child process spoken to in JSON lines (or just watched)."""

    def __init__(self, name, argv, env, log_path, cores=None, pipes=True):
        self.name, self.log_path = name, log_path
        self.err = open(log_path, "a")
        self.p = subprocess.Popen(
            argv, env=env, cwd=ROOT, text=True, bufsize=1,
            stdin=subprocess.PIPE if pipes else subprocess.DEVNULL,
            stdout=subprocess.PIPE if pipes else self.err, stderr=self.err)
        if cores:
            try:
                os.sched_setaffinity(self.p.pid, cores)
            except OSError:
                pass

    def send(self, **cmd) -> None:
        self.p.stdin.write(json.dumps(cmd) + "\n")
        self.p.stdin.flush()

    def reply(self) -> dict:
        line = self.p.stdout.readline()
        if not line:
            raise RunFailure(f"{self.name} ended (code {self.p.poll()}); "
                             f"its last output:\n{self.tail()}")
        out = json.loads(line)
        if "error" in out:
            raise RunFailure(f"{self.name}: {out['error']}")
        return out

    def ask(self, **cmd) -> dict:
        self.send(**cmd)
        return self.reply()

    def tail(self, n=25) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""

    def stop(self, timeout=30.0):
        if self.p.poll() is None:
            self.p.send_signal(signal.SIGTERM)
        try:
            self.p.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.p.kill()
            self.p.wait()
        for f in (self.p.stdin, self.p.stdout, self.err):
            try:
                if f:
                    f.close()
            except OSError:
                pass
        return self.p.returncode


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_for(what, probe, timeout_s, children, every_s=0.5):
    t_end = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < t_end:
        for c in children:
            if c.p.poll() is not None:
                raise RunFailure(f"{c.name} exited early (code "
                                 f"{c.p.returncode}):\n{c.tail()}")
        try:
            got = probe()
            if got:
                return got
        except RunFailure:              # the deadline's or a signal's: not retried
            raise
        except Exception as e:  # noqa: BLE001 — peer not up yet; retried
            last = e
        time.sleep(every_s)
    raise RunFailure(f"timeout after {timeout_s:.0f}s waiting for {what}"
                     + (f" (last error: {last})" if last else ""))


def split_cores(share: float):
    cores = sorted(os.sched_getaffinity(0))
    if len(cores) < 4:
        return cores, cores
    n_gen = max(2, int(round(len(cores) * share)))
    return cores[:n_gen], cores[n_gen:]


class Cluster:
    """The processes of one run: coordinator, store host, callers; and the
    set-up every run shares (region, load, build)."""

    def __init__(self, out: str, generator_core_share: float):
        self.out = out
        self.gen_cores, self.store_cores = split_cores(generator_core_share)
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = (
            ROOT + os.pathsep + self.env.get("PYTHONPATH", ""))
        self.env.pop("BENCH_RUN", None)
        self.off_jax = dict(self.env,
                            JAX_PLATFORMS="none_this_process_is_off_jax")
        self.coord_addr = f"127.0.0.1:{free_port()}"
        self.store_addr = f"127.0.0.1:{free_port()}"
        self.children, self.callers = [], []
        self.store = self.coordinator = self.client = None

    def start(self, config: dict, conf: dict, trace: bool):
        """Coordinator and store host up; -> the store's device."""
        from dingo_tpu.client import DingoClient
        from dingo_tpu.server import pb

        py, out = sys.executable, self.out
        port = lambda addr: addr.rsplit(":", 1)[1]  # noqa: E731
        argv = ["--role", "coordinator", "--port", port(self.coord_addr),
                "--replication", str(config["guarantees"]["replication"])]
        self.coordinator = self._host("coordinator", argv, self.off_jax,
                                      self.gen_cores, False)
        store_argv = ["--role", "store", "--id", "s0",
                      "--port", port(self.store_addr),
                      "--coordinator", self.coord_addr,
                      "--engine", config["guarantees"]["engine"],
                      "--data-dir", os.path.join(out, "data", "s0")]
        if conf:
            conf_path = os.path.join(out, "store.conf")
            with open(conf_path, "w") as f:
                f.writelines(f"{k} = {v}\n" for k, v in conf.items())
            store_argv += ["--config", conf_path]
        self.store = self._host("store", store_argv, self.env,
                                self.store_cores, trace)
        self.client = DingoClient(self.coord_addr, {"s0": self.store_addr})
        wait_for("the coordinator", lambda: self.client.coordinator.Hello(
            pb.HelloRequest()) is not None, 60, self.children)
        wait_for("the store", lambda: json.loads(
            self.client._stub("s0", "DebugService").MetricsDump(
                pb.MetricsDumpRequest()).json), 300, self.children)
        return self.store.ask(cmd="device")

    def _host(self, name, argv, env, cores, trace) -> Child:
        """A role of the program under `store_host.py`'s instruments."""
        spec_path = os.path.join(self.out, f"{name}_host.json")
        with open(spec_path, "w") as f:
            json.dump({"argv": argv, "cores": cores, "out": self.out,
                       "log": os.path.join(self.out, f"{name}.log"),
                       "trace": trace}, f)
        child = Child(name, [sys.executable,
                             os.path.join(HERE, "store_host.py"), spec_path],
                      env, os.path.join(self.out, f"{name}.log"))
        self.children.append(child)
        return child

    def spawn_caller(self, i: int, config, traffic, seed, wrap=None) -> Child:
        path = os.path.join(self.out, f"caller{i}.json")
        with open(path, "w") as f:
            json.dump({"coordinator": self.coord_addr,
                       "store": self.store_addr, "config": config,
                       "traffic": traffic, "seed": seed, "caller": i,
                       "out": self.out, "cores": self.gen_cores,
                       "wrap_client": wrap}, f)
        c = Child(f"caller{i}", [sys.executable,
                                 os.path.join(HERE, "caller.py"), path],
                  self.off_jax, os.path.join(self.out, f"caller{i}.log"))
        self.callers.append(c)
        return c

    def spawn_probe(self) -> Child:
        """`host_probe.py` on the store's cores: asked once, just before the
        window opens, how long its fixed piece of host work takes today."""
        c = Child("host_probe", [sys.executable,
                                 os.path.join(HERE, "host_probe.py")],
                  self.off_jax, os.path.join(self.out, "host_probe.log"),
                  cores=self.store_cores)
        self.callers.append(c)
        return c

    def create_region(self, config: dict) -> None:
        """The regions by the configuration's own recipe, one a window of
        `region_windows`: `index_parameter` holds the fields of
        pb.VectorIndexParameter, enums by their names."""
        from dingo_tpu.server import pb

        try:
            param = pb.VectorIndexParameter(**config["index_parameter"])
        except (TypeError, ValueError) as e:
            raise RunFailure(f"index_parameter is no VectorIndexParameter: {e}")
        # the store registers on its first heartbeat, and placement rides
        # the next: both are waited for
        self.window_rows = {}
        for lo, hi, rows in region_windows(config):
            made = wait_for("region creation",
                            lambda: self.client.create_index_region(
                                PARTITION, lo, hi, param,
                                replication=config["guarantees"]["replication"]),
                            60, self.children, 1.0)
            self.window_rows[made.region_id] = rows
        wait_for("every region on the store",
                 lambda: len(self.client.vector_status(PARTITION))
                 >= len(self.window_rows), 60, self.children, 1.0)

    def build(self, config: dict) -> list:
        """VectorBuild where the index trains (once for the partition: the
        SDK builds each region); -> every region's status."""
        if config.get("build"):
            self.client.vector_build(PARTITION)

        def ready():
            sts = self.client.vector_status(PARTITION)
            ok = len(sts) == len(self.window_rows) and all(
                st["ready"] and (st["trained"] or not config.get("build"))
                for st in sts)
            return sts if ok else None

        statuses = wait_for("every region to be ready", ready, 600,
                            self.children)
        for st in statuses:
            if st["count"] != self.window_rows.get(st["region_id"]) \
                    or st["build_error"]:
                raise RunFailure(f"region status after build: {st}")
        if sum(st["count"] for st in statuses) != config["rows"]:
            raise RunFailure(f"regions hold {statuses}, not {config['rows']} "
                             "rows")
        # the operator's `cluster snapshot-index` after a bulk load: the
        # first save of a fresh index takes 1.4-1.6 s where the later ones
        # take 1.1 s (chip runs, PR 26), and the store saves at every 60-s
        # scrub tick. With the first save here, the one tick a window holds
        # is a later one whatever second of the minute set-up ends at.
        from dingo_tpu.server import pb

        for st in statuses:
            snap = self.client._stub(
                "s0", "RegionControlService").RegionSnapshot(
                pb.RegionSnapshotRequest(region_id=st["region_id"]))
            if snap.error.errcode:
                raise RunFailure(f"index snapshot failed: {snap.error.errmsg}")
        return statuses

    def stop_callers(self) -> None:
        for c in self.callers:
            if c.p.poll() is None:
                c.send(cmd="exit")
        for c in self.callers:
            c.stop()
        self.callers.clear()

    def stop(self, timeout: float = 15.0) -> dict:
        """Everything stopped and waited for; -> exit codes. The store's own
        shutdown (a last checkpoint and save) is no part of what is
        measured: it gets `timeout`, then it is killed; its data goes."""
        if self.client is not None:
            self.client.close()
            self.client = None
        for c in self.callers:
            c.stop()
        self.callers.clear()
        codes = {c.name: c.stop(timeout) for c in self.children[::-1]}
        self.children.clear()
        shutil.rmtree(os.path.join(self.out, "data"), ignore_errors=True)
        return codes


# ------------------------------------------------------------ configuration
def region_windows(config: dict) -> list:
    """The id space cut into the configuration's `regions` (default 1)
    windows of about equal rows, by whole `block_rows` blocks where there
    are as many blocks as regions: the first from 0, the last open to
    1 << 40, where a write cell's fresh ids land. -> [(lo, hi, rows)]"""
    n, rows = config.get("regions", 1), config["rows"]
    block = int(config["assumed"]["corpus"]["block_rows"])
    blocks = -(-rows // block)
    cuts = [i * blocks // n * block for i in range(n)] if blocks >= n \
        else [i * rows // n for i in range(n)]
    his = cuts[1:] + [1 << 40]
    return [(lo, hi, min(hi, rows) - lo) for lo, hi in zip(cuts, his)]


def check_config(config: dict, mix: dict = None) -> None:
    """What the configuration states has to be what its region recipe asks
    the store for and what the reference can judge: a metric, precision,
    corpus, column, region count or filter the harness would have to ignore
    is an error, not a run. `mix`: the traffic it is to be run with."""
    import reference

    try:
        distance = reference.check_supported(config)
        regions = config.get("regions", 1)
        if not isinstance(regions, int) or regions < 1:
            raise RunFailure(f"regions {regions!r}: a whole number >= 1")
        small = [w for w in region_windows(config) if w[2] < config["k"]]
        if small:
            raise RunFailure(f"region windows {small} hold fewer than k = "
                             f"{config['k']} rows")
        args = (mix or {}).get("search_args", {})
        if any(a in args for a in reference.columns.FILTER_ARGS):
            if mix["operation"] != "vector_search":
                raise RunFailure(f"a filter on {mix['operation']}: the "
                                 "reference judges filtered searches only")
            if args.get("filter") != "SCALAR_FILTER" or args.get(
                    "filter_type", "QUERY_POST") not in ("QUERY_POST",
                                                         "QUERY_PRE"):
                raise RunFailure(
                    f"filter {args.get('filter')!r} / {args.get('filter_type')!r}"
                    ": the reference judges SCALAR_FILTER, QUERY_POST or "
                    "QUERY_PRE")
            if config["limits"].get("unmatched_rows", {}).get("max") != 0:
                raise RunFailure("a filtered traffic needs the configuration's "
                                 "limits.unmatched_rows.max 0")
            reference.columns.check_predicates(args.get("predicates"), config)
    except reference.Unsupported as e:
        raise RunFailure(str(e))
    recipe = config["index_parameter"]
    stated = {"dimension": config["dimension"],
              "metric_type": distance.METRIC_TYPE,
              "precision": config["precision"]}
    for key, want in stated.items():
        if recipe.get(key) != want:
            raise RunFailure(f"index_parameter.{key} is {recipe.get(key)!r} "
                             f"where the configuration states {want!r}")


# ------------------------------------------------------------------ traffic
def arrivals(mix: dict, seed: int, seconds: float):
    """Due offsets of an open-loop window: rate x seconds exponential gaps
    drawn once from the mix's own seed, the same set in every run, put in
    this run's order by `seed`, and scaled to fill the window."""
    import numpy as np

    n = int(mix["rate_per_s"] * seconds)
    gaps = np.random.default_rng(mix["arrival_seed"]).exponential(1.0, n + 1)
    gaps = np.random.default_rng([11, seed]).permutation(gaps)
    due = np.cumsum(gaps)[:n] * (seconds / gaps.sum())
    return due


# ---------------------------------------------------------- the generator
def generator_busy_share(files, seconds: float) -> float:
    """The busiest caller's own CPU seconds over the window's: a caller is
    one process under one GIL, so it can use one core and no more (the
    generator's cores outnumber the callers or equal them). Near 1 that
    caller, not the store, sets its pace, and the run says nothing about
    the store."""
    return max(float(f["cpu_s"]) for f in files) / seconds


# ---------------------------------------------------------------- alignment
def aligned_open(ev: dict, harness: dict, earliest: float, seconds: float):
    """When to open the window so that every run holds the same periodic
    work of the store: all its crontab jobs were added at one moment and
    their intervals divide the longest (`align_interval_s`), so a window
    that opens on the `phase_interval_s` grid laid `window_lead_s` before a
    tick of the longest jobs sees the short jobs (2, 5, 10 s) at the same
    seconds in every run; and it has to hold exactly one tick of the longest
    jobs, at least `tick_margin_s` [before, after] from its ends.
    -> (t_open, the tick inside), or (earliest, None) if no such job runs."""
    period, grid = harness["align_interval_s"], harness["phase_interval_s"]
    jobs = [n for n, j in ev["crontab"].items() if j["interval_s"] == period]
    if not jobs:
        return earliest, None
    begun = [b for n, b, _e in ev["events"]
             if n.startswith("cron.") and n[5:] in jobs]
    # a tick's jobs run one after the other on the crontab's one thread and
    # all come due again `period` after the pump that took them, not after
    # their own begins: the tick is the earliest begin of the latest one (a
    # job that waits behind the load's writes holds the later ones back:
    # 6-14 s in hnsw768.conc4, whose tick then came at the window's second
    # 26-34 where the latest begin laid it at 40; chip runs, PR 34-35)
    tick = (min(b for b in begun if b > max(begun) - period / 2) if begun
            else min(ev["crontab"][n]["added"] for n in jobs)) + period
    lo, hi = harness["tick_margin_s"]
    if seconds < lo + hi:
        lo = hi = 0.0
    t_open = tick - harness["window_lead_s"]
    t_open -= grid * ((t_open - earliest) // grid)      # first grid point
    while True:
        first = tick + period * -((tick - (t_open + lo)) // period)
        if first <= t_open + seconds - hi:
            return t_open, first
        t_open += grid


# ----------------------------------------------------------------- timeline
def timeline(records, t_open, seconds, events, gc_events):
    """Per second of the window: completions, the longest request that ended
    in it, and what the store was doing (jobs, checkpoints, collections)."""
    import numpy as np

    n = int(np.ceil(seconds))
    done = np.zeros(n, int)
    longest, longest_map = np.zeros(n), np.zeros(n)
    for due, _start, end, _ok, map_s in records:
        s = int(end - t_open)
        if 0 <= s < n:
            done[s] += 1
            longest[s] = max(longest[s], (end - due) * 1e3)
            longest_map[s] = max(longest_map[s], map_s * 1e3)
    jobs = [[] for _ in range(n)]
    for name, b, e in events:
        if e - b < 0.002 and not name.startswith("cron."):
            continue                    # coordinator jobs only when long
        for s in range(max(0, int(b - t_open)), min(n, int(e - t_open) + 1)):
            if b < t_open + s + 1 and e >= t_open + s:
                jobs[s].append(f"{name}:{(e - b) * 1e3:.0f}ms")
    for gen, b, dur in gc_events:
        s = int(b - t_open)
        if 0 <= s < n and (str(gen).endswith("2") or dur >= 0.005):
            jobs[s].append(f"gc{gen}:{dur * 1e3:.0f}ms")
    return {"completions": done.tolist(),
            "longest_ms": [round(float(v), 1) for v in longest],
            "longest_region_map_ms": [round(float(v), 1) for v in longest_map],
            "store": jobs}


# ----------------------------------------------------------------- the run
def main() -> int:
    global _tag
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rows", type=int, default=None,
                   help="rows to load instead of the configuration's: only "
                        "for a JAX_PLATFORMS=cpu rehearsal")
    p.add_argument("--out", default="", help="output directory (default "
                   "benchmark/out/<workload>/seed<seed>-trace<trace>)")
    p.add_argument("--control", default="", help="'bf16': besides the run's own "
                   "comparison, put the lower-precision reference in the "
                   "program's place; it has to come out as not correct")
    p.add_argument("--keep-profile", action="store_true",
                   help="leave the profiler's files in the output directory")
    p.add_argument("--wrap-client", default="", help="test only "
                   "(selftest.py): <file under benchmark/>:<function> that "
                   "breaks each caller's client underneath the timed path; "
                   "`correct` has to come out false")
    p.add_argument("--sweep", default="", help="open-loop cells: offer these "
                   "rates (comma separated) for --seconds each on one store "
                   "and print what each sustained; gives no result line")
    p.add_argument("--no-align", action="store_true",
                   help="open the window at once (stall hunt only)")
    args = p.parse_args()

    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, cfg_entry["file"])
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
    harness = load_json(HERE, "harness.json")

    wanted = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    rehearsal = wanted == "cpu"
    if rehearsal:
        if args.rows is None:
            print("JAX_PLATFORMS=cpu: no accelerator, no result. A CPU "
                  "rehearsal needs an explicit --rows and is never a pass.",
                  file=sys.stderr)
            return 2
        _tag = "  [platform: cpu - rehearsal, not a chip result]"
        config["rows"] = args.rows
    elif args.rows is not None:
        print("--rows is for a JAX_PLATFORMS=cpu rehearsal only",
              file=sys.stderr)
        return 2
    try:
        check_config(config, mix)
    except RunFailure as e:
        print(f"configuration {cell['config']}: {e}", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(ROOT, "dingo_tpu")):
        print("benchmark/run.py needs the dingo_tpu package of the checkout "
              "it sits in", file=sys.stderr)
        return 2

    out = os.path.abspath(args.out or os.path.join(
        HERE, "out", args.workload, f"seed{args.seed}-trace{args.trace}"))
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    cluster = Cluster(out, harness["generator_core_share"])
    try:
        os.sched_setaffinity(0, cluster.gen_cores)
    except OSError:
        pass
    say(f"cell {cell['name']}: config {cell['config']} "
        f"({config['index_parameter']['index_type']}, {config['rows']} x "
        f"{config['dimension']} {config['precision']} {config['metric']}), "
        f"traffic {cell['traffic']}, seed "
        f"{args.seed}, {args.seconds:g} s, trace {args.trace}; cores: "
        f"generator+coordinator+parent {cluster.gen_cores}, store "
        f"{cluster.store_cores}")
    os.environ["JAX_PLATFORMS"] = "none_the_parent_is_off_jax"

    def on_term(signum, frame):
        raise RunFailure(f"signal {signum}")

    signal.signal(signal.SIGTERM, on_term)
    deadline = SetupDeadline(harness["setup_deadline_s"])
    deadline.arm()
    failure = None
    try:
        try:
            result = drive(args, bench, cell, config, mix, harness, cluster,
                           rehearsal, deadline)
        finally:
            # before the failure is caught: a repeat of the alarm comes
            # here at the latest, and is caught below as the first would be
            deadline.disarm()
    except RunFailure as e:
        failure = e
    finally:
        # a whole run has stopped its processes itself (`drive`); a failed
        # one has nothing to save, so its store gets 5 s and not 15
        cluster.stop(timeout=5.0)
    if failure is not None:
        print(f"RUN FAILED: {failure}{_tag}", file=sys.stderr, flush=True)
        return 1
    if args.sweep:
        return 4
    if rehearsal:
        result["correct_if_it_were_a_chip"] = result["correct"]
        result["correct"] = False
        result["rehearsal"] = True
        print(json.dumps(result) + _tag, flush=True)
        return 3
    print(json.dumps(result), flush=True)
    return 0


def drive(args, bench, cell, config, mix, harness, cluster, rehearsal,
          deadline) -> dict:
    import numpy as np

    import readers

    setup = {}
    seconds = args.seconds
    out, py = cluster.out, sys.executable

    # ---- processes --------------------------------------------------------
    conf = dict(config.get("conf_overrides") or {})
    if rehearsal:
        # walk the host code the chip will walk (as chip_smoke.py does)
        conf.update({"vector_blocked_layout": "true",
                     "pipeline_enabled": "true"})
    n_callers = mix["callers"] if mix["loop"] == "closed" else mix["processes"]
    wrap = None
    if args.wrap_client:
        name, function = args.wrap_client.split(":")
        wrap = [os.path.join(HERE, name), function]
    traffic_callers = [cluster.spawn_caller(i, config, mix, args.seed, wrap)
                       for i in range(n_callers)]
    probe = cluster.spawn_probe()
    device = cluster.start(config, conf, bool(args.trace))
    store, client = cluster.store, cluster.client
    setup["store_up"] = time.monotonic() - T_START
    say(f"store up in {setup['store_up']:.1f}s: platform: "
        f"{device['platform']} kind: {device['kind']} count: "
        f"{device['count']}")
    if not rehearsal and device["platform"] != "tpu":
        raise RunFailure(f"store platform is {device['platform']!r}")
    if not rehearsal and device["count"] < cell["chips"]:
        raise RunFailure(f"{device['count']} chips, the cell asks for "
                         f"{cell['chips']}")

    # ---- region, load, build ----------------------------------------------
    deadline.phase = "region"
    cluster.create_region(config)
    for c in cluster.callers:
        c.reply()                       # {"ready": true}; the probe's too
    t0 = time.monotonic()
    setup["region"] = t0 - T_START - setup["store_up"]
    windows = region_windows(config)
    say(f"region: {len(windows)} over ids " + ", ".join(
        f"[{lo}, {hi}) {rows} rows" for lo, hi, rows in windows)
        + f" in {setup['region']:.1f}s")
    deadline.phase = "load"
    traffic_callers[0].ask(cmd="load")
    setup["load"] = time.monotonic() - t0
    say(f"load: {config['rows']} rows through vector_add in "
        f"{setup['load']:.1f}s")
    t0 = time.monotonic()
    deadline.phase = "build"
    statuses = cluster.build(config)
    setup["build"] = time.monotonic() - t0
    say(f"build: {setup['build']:.1f}s; "
        + "; ".join(f"region {st}" for st in statuses))

    # ---- warm the cell's own shapes ---------------------------------------
    t0 = time.monotonic()
    deadline.phase = "warm"
    first = traffic_callers[0].ask(cmd="warm", n=mix["warm_requests"])
    writes = first["writes"]
    for c in traffic_callers[1:]:
        c.send(cmd="warm", n=mix["warm_requests"])
    for c in traffic_callers[1:]:
        c.reply()
    setup["warm"] = time.monotonic() - t0
    say(f"warm: {setup['warm']:.1f}s ({mix['warm_requests']} requests per "
        f"caller, first caller alone first)")
    if first["failed"]:
        say(f"{first['failed']} warm-up requests failed: {first['errors']}")

    if args.sweep:
        deadline.disarm()
        return sweep(args, mix, traffic_callers, seconds)

    # ---- open the window at a fixed offset from the store's schedule ------
    ev = store.ask(cmd="events")
    t_open = time.monotonic() + PROBE_LEAD_S + 0.5
    tick = None
    if not args.no_align:
        t_open, tick = aligned_open(ev, harness, t_open, seconds)
    deadline.check_open(t_open)
    deadline.disarm()
    t_close = t_open + seconds
    setup["align"] = t_open - time.monotonic()
    setup["total"] = t_open - T_START
    say(f"window opens in {setup['align']:.1f}s"
        + (f"; the store's {harness['align_interval_s']:g}-s jobs tick at "
           f"its second {tick - t_open:.1f}" if tick else "")
        + f"; setup_s = {setup['total']:.1f}")
    before = store.ask(cmd="metrics")["metrics"]
    if args.trace:
        # the store's own spans, for a share of the requests all through the
        # window: at every request they cost a third of the closed loop's
        # rate (chip runs, PR 26), and the traced run has to measure the
        # regime the timed one does
        store.ask(cmd="spans_on", rate=harness["span_sampling_rate"])
    if mix["loop"] == "open":
        due = arrivals(mix, args.seed, seconds)
        for i, c in enumerate(traffic_callers):
            c.send(cmd="go", t_open=t_open, t_close=t_close,
                   due=due[i::len(traffic_callers)].tolist())
    else:
        for c in traffic_callers:
            c.send(cmd="go", t_open=t_open, t_close=t_close, due=None)
    # the store is idle, between two of its 5-s jobs: how fast is the host?
    time.sleep(max(0.0, t_open - PROBE_LEAD_S - time.monotonic()))
    readings = {"host_probe_ms": probe.ask(cmd="probe")["least_ms"]}
    profile = None
    if args.trace:
        trace_dir = os.path.join(out, "profile")
        # the profiled seconds hold one 5-s metrics sweep and end before
        # the minute's tick can come (tick_margin_s): steady state
        time.sleep(max(0.0, t_open + harness["profile_at_s"]
                       - time.monotonic()))
        started = store.ask(cmd="profile_start", dir=trace_dir)
        # a mix of many small device operations states fewer seconds of its
        # own: the profiler's stop and the reduction take time by the event
        time.sleep(mix.get("profile_seconds", harness["profile_seconds"]))
        stopped = store.ask(cmd="profile_stop")
        profile = (started["t_started"], stopped["t"])
        setup["profile_stop"] = stopped["t_stopped"] - stopped["t"]
    time.sleep(max(0.0, t_close - time.monotonic()))
    files = [c.reply() for c in traffic_callers]     # after the drain
    t_drained = time.monotonic()
    # after the close: the last replies, and in a traced run what is left of
    # the profiler's stop (`profile_stop`, which begins inside the window)
    setup["drain"] = t_drained - t_close
    after = store.ask(cmd="metrics")["metrics"]
    spans = []
    if args.trace:
        with open(store.ask(cmd="spans_off")["file"]) as f:
            spans = json.load(f)
    memory = store.ask(cmd="memory")
    ev = store.ask(cmd="events")
    if tick is not None:
        # where the tick really came: `aligned_open` laid it from the
        # store's record of the ticks before
        came = [b - t_open for n, b, _e in ev["events"] if n.startswith("cron.")
                and ev["crontab"].get(n[5:], {}).get("interval_s")
                == harness["align_interval_s"] and t_open <= b < t_close]
        if came:
            readings["tick_at_s"] = min(came)
    coord_ev = cluster.coordinator.ask(cmd="events")
    ev["events"] += [["coord." + n, b, e] for n, b, e in coord_ev["events"]]
    ev["gc"] += [[f"coord.{g}", b, d] for g, b, d in coord_ev["gc"]]

    # ---- what the window said ----------------------------------------------
    parts = [np.load(f["file"]) for f in files]
    records = np.concatenate([d["records"] for d in parts])
    attempted = int(len(records))
    failed = int((records[:, 3] <= 0).sum()) if attempted else 0
    errors = [e for f in files for e in f["errors"]]
    readings["generator_busy_share"] = generator_busy_share(files, seconds)
    job = {"seed": args.seed, "config": config,
           "k": mix["search_args"]["topk"], "pool": mix["query_pool"],
           "replies": os.path.join(out, "replies.npz")}
    count_mismatch = None
    if mix["operation"] == "vector_add":
        # every acknowledged row is searchable from the leader: the count,
        # and a read-back of the last acknowledged batch first, then of a
        # sample of the others drawn from the seed
        w = traffic_callers[0]
        acked = files[0]["writes"]
        if failed:
            raise RunFailure(f"{failed} writes failed: {errors[:3]}")
        rng = np.random.default_rng([13, args.seed])
        n_check = min(acked, mix["check_requests"])
        pick = sorted(set([acked - 1, 0] + rng.choice(
            acked, n_check, replace=False).tolist()))[::-1]
        rb = np.load(w.ask(cmd="readback", requests=pick)["file"])
        count = client.vector_count(PARTITION)
        count_mismatch = abs(count - (config["rows"] + acked * mix["batch"]))
        np.savez(job["replies"], batch=mix["batch"], offsets=rb["offsets"],
                 fresh_request=rb["fresh_request"], ids=rb["ids"],
                 dists=rb["dists"])
        job["fresh"] = {"batch": mix["batch"], "acked_requests": acked}
        job["truth_rows"] = list(range(len(rb["offsets"]) * mix["batch"]))
        say(f"writes: {acked} requests acknowledged (warm-up {writes}), "
            f"count {count}, read back {len(pick)} requests")
    else:
        ids = np.concatenate([d["ids"] for d in parts])
        offsets = np.concatenate([d["offsets"] for d in parts])
        # each filtered request's predicates with their drawn values
        filtered = {"predicates": np.concatenate(
            [d["predicates"] for d in parts])} \
            if "predicates" in parts[0].files else {}
        np.savez(job["replies"], batch=mix["batch"], offsets=offsets,
                 fresh_request=np.full(len(offsets), -1, np.int64), ids=ids,
                 dists=np.concatenate([d["dists"] for d in parts]), **filtered)
        # recall against the exact top-k on a sample of requests drawn from
        # the seed, the last one in it; every reply's distances are checked
        rng = np.random.default_rng([13, args.seed])
        n = len(offsets)
        pick = set(rng.choice(n, min(n, mix["check_requests"]),
                              replace=False).tolist()) | ({n - 1} if n else set())
        job["truth_rows"] = [r * mix["batch"] + j for r in sorted(pick)
                             for j in range(mix["batch"])]
        # distances: every reply, or where those are more than
        # `check_distance_requests`, that many drawn from the seed
        more = mix.get("check_distance_requests", n)
        if n > more:
            pick |= set(rng.choice(n, more, replace=False).tolist())
            job["reply_rows"] = sorted(pick)
    with open(os.path.join(out, "job.json"), "w") as f:
        json.dump(job, f)
    statuses = client.vector_status(PARTITION)

    # ---- stop the program, then the trace reduction and the reference ------
    cluster.stop_callers()
    codes = cluster.stop()
    try:
        os.sched_setaffinity(
            0, sorted(set(cluster.gen_cores) | set(cluster.store_cores)))
    except OSError:
        pass
    trace = {}
    if args.trace:
        t0 = time.monotonic()
        got = subprocess.run(
            [py, os.path.join(HERE, "trace_reduce.py"), trace_dir],
            env=dict(cluster.env, JAX_PLATFORMS="cpu"), cwd=ROOT,
            capture_output=True, text=True, timeout=240)
        if got.returncode != 0:
            raise RunFailure(f"trace reduction failed:\n{got.stderr[-2000:]}")
        trace = json.loads(got.stdout.strip().splitlines()[-1])
        trace["window_s"] = profile[1] - profile[0]
        setup["reduce"] = time.monotonic() - t0
        with open(os.path.join(out, "trace_reduced.json"), "w") as f:
            json.dump(trace, f)
        if not args.keep_profile:
            shutil.rmtree(trace_dir, ignore_errors=True)
    def refer(control=""):
        got = subprocess.run(
            [py, os.path.join(HERE, "reference.py"), "--job",
             os.path.join(out, "job.json")]
            + (["--control", control] if control else []),
            env=cluster.off_jax, cwd=ROOT, capture_output=True, text=True,
            timeout=300)
        if got.returncode != 0:
            raise RunFailure(f"the reference failed:\n{got.stderr[-2000:]}")
        return json.loads(got.stdout.strip().splitlines()[-1])

    def judge(numbers):
        """Each number against its limit (the configuration's file).
        -> ({name: [value, op, limit]}, all inside)"""
        shown, inside = {}, True
        for name, limit in config["limits"].items():
            if name not in numbers:
                continue
            op, bound = (">=", limit["min"]) if "min" in limit \
                else ("<=", limit["max"])
            value = numbers[name]
            shown[name] = [value, op, bound]
            inside = inside and (value >= bound if op == ">=" else
                                 value <= bound)
        return shown, bool(inside)

    t0 = time.monotonic()
    numbers = refer()
    if "predicates" in mix["search_args"] and "unmatched_rows" not in numbers:
        raise RunFailure("filtered requests judged with no unmatched_rows: "
                         "the replies lost their predicates")
    say(f"reference and comparison: {time.monotonic() - t0:.1f}s over "
        f"{numbers.get('compared_queries')} queries")

    # ---- correct -----------------------------------------------------------
    numbers["fallback_events"] = sum(
        readers.series_total(after, name) or 0.0 for name in FALLBACKS)
    if count_mismatch is not None:
        numbers["count_mismatch"] = count_mismatch
    compared, inside = judge(numbers)
    correct = inside and failed == 0
    compared["failed_requests"] = [failed, "<=", 0]
    if not all(st["ready"] and not st["build_error"] for st in statuses):
        correct = False
        compared["region_ready"] = [0, ">=", 1]

    # ---- metrics -----------------------------------------------------------
    run = readers.Run(
        records=records, t_open=t_open, t_close=t_close, spans=spans,
        metrics_before=before, metrics_after=after, trace=trace,
        profile=profile, setup=setup, memory=memory, compared=numbers,
        config=config, traffic=mix, device_kind=device["kind"],
        readings=readings)
    wanted = bench["per_layer"] if args.trace else bench["end_to_end"]
    metrics = {}
    for m in wanted:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        value = readers.read_metric(run, m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    tl = timeline(records, t_open, seconds, ev["events"], ev["gc"])
    if profile:
        # does the profiler slow what it looks at? answered requests a
        # second, inside the profiled seconds and in the rest of the window
        done = records[records[:, 3] > 0, 2]
        inside = int(((done >= profile[0]) & (done < profile[1])).sum())
        rest = int(((done >= t_open) & (done < t_close)).sum()) - inside
        tl["profiled_requests_per_s"] = inside / (profile[1] - profile[0])
        tl["unprofiled_requests_per_s"] = rest / max(
            1e-9, seconds - (profile[1] - profile[0]))
        say(f"traced run: {tl['profiled_requests_per_s']:.2f} requests/s "
            f"answered inside the profiled seconds, "
            f"{tl['unprofiled_requests_per_s']:.2f} in the rest of the "
            f"window; spans kept for {len({s[3] for s in spans})} requests")
    tl.update(workload=cell["name"], seed=args.seed, t_open=t_open,
              setup=setup, harness=readings, drain_s=t_drained - t_close,
              exit_codes=codes, crontab=ev["crontab"])
    with open(os.path.join(out, "timeline.json"), "w") as f:
        json.dump(tl, f)
    say("timeline " + json.dumps({k: tl[k] for k in (
        "completions", "longest_ms", "longest_region_map_ms", "store")}))
    say("setup " + json.dumps({k: round(v, 2) for k, v in setup.items()}))
    say("harness " + json.dumps(readings))
    if memory.get("disk_write_bytes"):
        say(f"the store wrote {memory['disk_write_bytes'] / 1e9:.2f} GB to "
            "disk in this run (WAL, checkpoints, raft log, index snapshots)")
    if errors:
        say(f"request errors: {errors[:3]}")
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": int(memory["peak_bytes"])}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": dev}
    if args.trace and trace.get("busy_s"):
        dev["busy_s"], dev["window_s"] = trace["busy_s"], trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    if args.control:
        # the lower-precision reference in the program's place, through the
        # same comparison: it has to come out as not correct
        shown, inside = judge(refer(args.control))
        result["control"] = {"what": args.control, "correct": inside,
                             "compared": shown}
        say("control " + json.dumps(result["control"]))
    result["compared"] = compared
    for name, (value, op, limit) in compared.items():
        print(f"compared {name}: {value} {op} {limit}{_tag}", file=sys.stderr)
    sys.stderr.flush()
    return result


def sweep(args, mix, traffic_callers, seconds) -> dict:
    """The highest of a few fixed rates with no growing backlog: each rate
    for `seconds` on the same store, one after the other."""
    import numpy as np

    import readers

    rows = []
    for rate in [float(r) for r in args.sweep.replace("+", ",").split(",")]:
        step = dict(mix, rate_per_s=rate)
        due = arrivals(step, args.seed, seconds)
        t_open = time.monotonic() + 1.0
        for i, c in enumerate(traffic_callers):
            c.send(cmd="go", t_open=t_open, t_close=t_open + seconds,
                   due=due[i::len(traffic_callers)].tolist())
        rec = np.concatenate([np.load(c.reply()["file"])["records"]
                              for c in traffic_callers])
        rec = rec[np.argsort(rec[:, 0])]
        lat = (rec[:, 2] - rec[:, 0]) * 1e3
        q = max(1, len(lat) // 4)
        row = {"rate": rate, "sent": len(rec),
               "done_per_s": float((rec[:, 2] <= t_open + seconds).sum()
                                   / seconds),
               "p50_ms": readers.percentile(lat, 50),
               "p95_ms": readers.percentile(lat, 95),
               "first_quarter_p50_ms": readers.percentile(lat[:q], 50),
               "last_quarter_p50_ms": readers.percentile(lat[-q:], 50),
               "drain_s": float(rec[:, 2].max() - (t_open + seconds)),
               "failed": int((rec[:, 3] <= 0).sum())}
        rows.append(row)
        say("sweep " + json.dumps(row))
    return {"sweep": rows, "correct": False, "metrics": {}}


if __name__ == "__main__":
    sys.exit(main())
