"""The benchmark's own tests (no chip needed; about five minutes):

    python3 benchmark/selftest.py            # everything
    python3 benchmark/selftest.py quick      # all but the CPU rehearsals

1. the trace reducer on the small recorded trace in `testdata/`;
2. the work functions against bytes and FLOPs computed by hand at the cells'
   shapes, and the roofline arithmetic;
3. the percentile, rate, stall and open-loop timing arithmetic;
4. the comparison: a perfect program passes, the bfloat16 control does not;
5. the contract's data: every cell's traffic file states its load (the
   caller count of a closed loop, rate and threads of an open one) and why,
   every metric has its file, the bounds in BENCHMARK.json are the ones
   PERF.md section 2 gives its reasons for, and a recipe that states
   `max_elements` states the configuration's rows;
6. set-up's deadline (`harness.json` `setup_deadline_s`): a child that never
   answers ends the run at the deadline with the phase named and leaves no
   process behind, an open of the window laid past it fails in phase `align`,
   and the window's tick is laid from the earliest begin of a tick's jobs;
7. CPU rehearsals (`JAX_PLATFORMS=cpu`, explicit small `--rows`): every line
   says `platform: cpu`, the last line is never a pass, and with the timed
   path broken underneath (`--wrap-client faults.py:<fault>`) `correct`
   comes out false; a configuration whose metric or precision no arm of the
   harness honours is refused; a run whose set-up cannot end by the deadline
   (a scratch copy of the benchmark whose `harness.json` states 3 s) exits 1
   with the phase named and leaves no process behind; the fixtures of
   `testdata/cells.json` (three regions with scalar columns, unfiltered and
   filtered), laid into a scratch copy of the benchmark whose
   BENCHMARK.json lists them (`python3 benchmark/selftest.py fixture-tree
   <dir>` makes one), run, and the filtered judgement tells a client that
   drops the predicates from one that applies them;
8. regions, scalar columns and filtered searches: (a) the columns are the
   ones the load sends and the reference judges by, and `ivf768`'s vectors
   and queries are the bits they were before columns existed; (b) the
   filter arm against a brute-force loop; (c) the region windows; (d) what
   `run.check_config` refuses; (e) for every cell, the SDK calls the
   harness makes (a recording fake client) are the ones in
   `testdata/calls.json`, which the scripts made before regions and filters
   came (`python3 benchmark/selftest.py record-calls <file>` records them):
   set-up, warm-up, closed-loop requests and an open loop's threaded sends.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import readers  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402


def check(ok: bool, what: str) -> None:
    print(("ok   " if ok else "FAIL ") + what, flush=True)
    if not ok:
        raise SystemExit(1)


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


# ------------------------------------------------------------ 1. the reducer
def test_reducer() -> None:
    check(trace_reduce.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4,
          "union of overlapping intervals")
    check(trace_reduce.gaps_of([(0, 2), (1, 3), (5, 6)]) == [(3, 5)],
          "gaps between merged intervals")
    host = [("outer", 0.0, 10.0), ("inner", 3.0, 4.2), ("far", 8.0, 9.0)]
    check(trace_reduce.attribute((3.1, 4.0), host) == "host: inner",
          "a gap is named by the shortest host event covering half of it")
    check(trace_reduce.attribute((20.0, 21.0), host) == trace_reduce.NOTHING,
          "a gap no host event overlaps is named as such")
    want = load("testdata", "recorded.expect.json")
    got = trace_reduce.reduce_trace(
        os.path.join(HERE, "testdata", "recorded.xplane.pb.gz"))
    check(got["devices"] == want["devices"], "recorded trace: device planes")
    check(abs(got["busy_s"] - want["busy_s"]) < 1e-9,
          f"recorded trace: busy_s {got['busy_s']}")
    check(got["device_ops"][0][0] == want["device_ops"][0][0]
          and abs(got["device_ops"][0][1] - want["device_ops"][0][1]) < 1e-9,
          f"recorded trace: top device op {got['device_ops'][0]}")
    check(0 < got["busy_s"] <= got["device_span_s"],
          "recorded trace: busy time lies inside the device's span")
    # a witness that is not the reducer: the store's own device_wait_span
    # read 19.6 ms per call in the run the trace is from
    top = got["device_ops"][0][0]
    per_call = got["op_seconds"][top] / got["op_calls"][top] * 1e3
    check(18.5 < per_call < 20.5,
          f"recorded trace: the scan kernel takes {per_call:.2f} ms a call, "
          "as the store's device_wait_span read (19.6)")


# ------------------------------------------------------ 2. work and roofline
def test_work() -> None:
    ivf, flat = load("configs", "ivf768.json"), load("configs", "flat768.json")
    peaks = readers.load_peaks("TPU v5 lite")
    w = readers.load_work("flat_scan")(flat, {"batch": 1, "search_args": {}})
    check(w["bytes"] == 160_000 * 768 * 4 == 491_520_000,
          "flat scan reads every row once: 491.5 MB")
    check(w["flops"] == 2 * 768 * 160_000, "flat scan FLOPs at batch 1")
    check(abs(readers.least_seconds(w, peaks) - 491.52e6 / 819e9) < 1e-12,
          "flat scan is bound by bytes: 0.600 ms least")
    w = readers.load_work("ivf_scan")(
        ivf, {"batch": 1, "search_args": {"nprobe": 32}})
    check(abs(w["bytes"] - 32 * (160_000 / 1024) * 768 * 4) < 1,
          "ivf scan at batch 1: 32 lists of 156.25 rows = 15.4 MB")
    w = readers.load_work("ivf_scan")(ivf, {"batch": 64, "search_args": {}})
    lists = 1024 * (1 - (1 - 32 / 1024) ** 64)
    check(abs(lists - 889.8) < 0.1 and abs(
        w["bytes"] - lists * (160_000 / 1024) * 3072) < 1,
        "ivf scan at batch 64: the union of probes, 889.8 lists = 427.1 MB")
    check(w["flops"] == 2.0 * 768 * 64 * 32 * (160_000 / 1024),
          "ivf scan FLOPs: one multiply-add per dimension per probed row")
    try:
        readers.load_peaks("TPU v9 imaginary")
        check(False, "an unknown device kind is an error")
    except KeyError:
        check(True, "an unknown device kind is an error")
    run = readers.Run(
        trace={"op_seconds": {"jit_scan/a": 0.030, "jit_coarse_probes/b": 0.5},
               "busy_s": 0.53, "window_s": 5.3},
        records=np.array([[0.0, 0.0, 0.4, 1]] + [
            [1.0, 1.0, 1.0 + 0.1 * i, 1] for i in range(4)] + [
            [1.0, 1.0, 1.5, 0], [1.9, 1.9, 2.0, 1]]),
        profile=(0.5, 2.0), config=flat, traffic={"batch": 1},
        device_kind="TPU v5 lite")
    got = readers.roofline(run, "flat_scan", exclude=["coarse_probes"])
    check(abs(got - 100 * (491.52e6 / 819e9) * 4 / 0.030) < 1e-9,
          "roofline share: least x requests answered in the profiled "
          f"seconds / stage device time = {got:.3f} %")
    check(abs(readers.idle_share(run) - 90.0) < 1e-9, "idle share")
    check(readers.roofline(readers.Run(config=flat, traffic={}),
                           "flat_scan") is None,
          "a roofline with nothing to read is left out, not 0")


# ------------------------------------------------------------- 3. arithmetic
def test_arithmetic() -> None:
    check(readers.percentile([5, 1, 3, 2, 4], 50) == 3, "median, nearest rank")
    check(readers.percentile(range(1, 101), 95) == 95, "p95 of 1..100")
    check(readers.percentile([], 50) is None, "percentile of nothing")
    # open loop: timed from when it was due, failures count as infinite
    rec = np.array([[10.0, 10.5, 10.7, 1], [11.0, 11.0, 11.1, 1],
                    [12.0, 12.0, 12.2, 0], [13.0, 13.0, 15.5, 1]])
    run = readers.Run(records=rec, t_open=10.0, t_close=15.0,
                      traffic={"batch": 64})
    lat = run.latencies_ms()
    check(abs(lat[0] - 700) < 1e-6 and np.isinf(lat[2]),
          "latency from the due time; a failed request is infinite")
    check(abs(readers.request_stat(run, "p50") - 700) < 1e-6, "request p50")
    check(readers.request_stat(run, "p99") is None,
          "a tail that holds a failure has no finite reading")
    check(abs(readers.request_stat(run, "p99", "late") - 500) < 1e-6,
          "generator lateness")
    check(readers.rate(run, "batch") == 2 * 64 / 5.0,
          "rate: replies complete by the close, over all the window")
    check(readers.stall_seconds(run) == 3 * 60 / 5,
          "stall seconds per minute: seconds with no completion")
    import host_probe
    import run as harness

    check(harness.generator_busy_share(
        [{"cpu_s": 9.0}, {"cpu_s": 4.5}], 45.0) == 0.2,
          "generator busy share: the busiest caller's CPU seconds over the "
          "window's, one core a caller")
    spec = {"reader": "harness_reading", "args": {"key": "host_probe_ms"}}
    check(readers.read(readers.Run(readings={"host_probe_ms": 61.5}), spec)
          == 61.5 and readers.read(readers.Run(), spec) is None,
          "a harness reading is read by its key, and left out where absent")
    check(host_probe.one_pass([1.0] * 64 * 768) > 0,
          "the host probe times a pass of its fixed work")
    mix = load("traffic", "single_open_flat768.json")
    a, b = harness.arrivals(mix, 1, 45), harness.arrivals(mix, 2**31 + 5, 45)
    check(len(a) == len(b) == int(mix["rate_per_s"] * 45),
          "every seed gets the same number of arrivals")
    ga = np.sort(np.diff(np.concatenate([a, [45.0]])))
    gb = np.sort(np.diff(np.concatenate([b, [45.0]])))
    check(np.allclose(ga[1:-1], gb[1:-1], rtol=0.2),
          "the same set of gaps, up to the two at the window's ends")
    check(0 < a[0] and a[-1] < 45 and 0 < b[0] and b[-1] < 45,
          "arrivals stay inside the window")
    check(not np.array_equal(a, b), "another seed, another order")


# ------------------------------------------------------------- 4. comparison
def test_comparison() -> None:
    seed, rows, k = 2**31 + 11, 20_000, 10
    config = dict(load("configs", "ivf768.json"), rows=rows)
    dist = reference.check_supported(config)
    data = reference.Data(seed, config)
    x = data.corpus()
    check(len(x) == rows and np.array_equal(
        x[:data.block_rows], reference.Data(seed, config).block(0)),
        "the corpus is the same made whole or block by block")
    check(data.n_clusters == 64 and reference.Data(
        seed, load("configs", "ivf768.json")).n_clusters == 160,
        "clusters by the configuration's assumed.corpus")
    q = data.query_pool(256)
    ids, d = reference.exact_topk(dist, x, q, k)
    brute = np.argsort(((x[None, :, :].astype(np.float64)
                         - q[:2, None, :]) ** 2).sum(-1), axis=1)[:, :k]
    check(np.array_equal(ids[:2], brute),
          "exact_topk agrees with a brute-force float64 sort")
    truth = np.arange(len(q))
    good = reference.compare_replies(
        dist, x, q, ids, d.astype(np.float32), k, truth)
    check(good["recall_at_10"] == 1.0 and good["dist_err"] < 1e-6
          and good["malformed_items"] == 0,
          f"a perfect program passes (dist_err {good['dist_err']:.2e})")
    cid, cd = reference.control_topk(dist, x, q, k)
    ctl = reference.compare_replies(dist, x, q, cid, cd, k, truth)
    limit = load("configs", "ivf768.json")["limits"]["dist_err"]["max"]
    check(ctl["dist_err"] > 3 * limit,
          f"the bfloat16 control fails dist_err: {ctl['dist_err']:.2e} "
          f"against the limit {limit:.0e}")
    bad = ids.copy()
    bad[0, 0] = (bad[0, 0] + 1) % rows
    alt = reference.compare_replies(
        dist, x, q, bad, d.astype(np.float32), k, truth)
    check(alt["dist_err"] > limit, "an altered id fails dist_err")
    short = ids.copy()
    short[3, 5:] = -1
    check(reference.compare_replies(
        dist, x, q, short, d.astype(np.float32), k, truth
    )["malformed_items"] == 5, "a short reply is malformed")
    # a metric whose wire values descend (inner product), as a module would
    # state it: the comparison's order follows the module, not L2
    import types

    ip = types.SimpleNamespace(
        ASCENDING=False, norms=lambda x: np.zeros(len(x), np.float32),
        rank32=lambda q_, xs, _n: -(q_ @ xs.T),
        served64=lambda x_, q_, ids_: np.einsum(
            "qd,qkd->qk", q_.astype(np.float64), x_[ids_].astype(np.float64)),
        scale=lambda x_, q_, ids_: np.ones(ids_.shape))
    iid, idd = reference.exact_topk(ip, x, q[:16], k)
    brute = np.argsort(-(q[:16].astype(np.float64) @ x.T.astype(np.float64)),
                       axis=1)[:, :k]
    check(np.array_equal(iid, brute) and (np.diff(idd, axis=1) <= 0).all(),
          "a descending metric: exact_topk is the largest, largest first")
    got = reference.compare_replies(ip, x, q[:16], iid, idd, k, np.arange(16))
    check(got["recall_at_10"] == 1.0 and got["unsorted_rows"] == 0,
          "a descending metric: a perfect program passes")
    check(reference.compare_replies(
        ip, x, q[:16], iid[:, ::-1], idd[:, ::-1], k, np.arange(16)
    )["unsorted_rows"] == 16, "a descending metric: ascending replies fail")
    # what the harness cannot honour is refused, not run as fp32 L2
    import run as harness

    for key, value in (("metric", "IP"), ("precision", "sq8"),
                       ("dimension", 512)):
        try:
            harness.check_config(dict(config, **{key: value}))
            check(False, f"a configuration stating {key} {value!r} is refused")
        except harness.RunFailure:
            check(True, f"a configuration stating {key} {value!r} is refused")
    recipe = dict(config["index_parameter"], metric_type="METRIC_TYPE_COSINE")
    try:
        harness.check_config(dict(config, index_parameter=recipe))
        check(False, "a recipe that differs from the stated metric is refused")
    except harness.RunFailure:
        check(True, "a recipe that differs from the stated metric is refused")
    harness.check_config(config)


# ------------------------------------------------------------------ 5. data
def test_data() -> None:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell in bench["workloads"]:
        mix = load("traffic", cell["traffic"] + ".json")
        if mix["loop"] == "closed":
            n = mix.get("callers")
            stated = isinstance(n, int) and n >= 1
            said = stated and all(re.search(rf"\b{n} (closed-loop )?"
                                            r"(callers?|writers?)\b", why)
                                  for why in (mix.get("why", ""), cell["why"]))
        else:
            n = (mix.get("processes"), mix.get("threads_per_process"))
            stated = all(isinstance(v, int) and v >= 1 for v in n) \
                and mix.get("rate_per_s", 0) > 0
            said = stated and f"{mix['rate_per_s']:g}" in cell["why"]
        check(stated, f"{cell['name']}: traffic {cell['traffic']} states its "
              f"load ({n})")
        check(said and len(mix["why"]) > 40,
              f"{cell['name']}: the traffic's and the cell's why name that load")
    for m in bench["end_to_end"] + bench["per_layer"]:
        check(os.path.exists(os.path.join(HERE, "metrics", m["name"] + ".json")),
              f"metric {m['name']} has its file")
    on_file = {os.path.splitext(f)[0] for f in os.listdir(
        os.path.join(HERE, "metrics"))}
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    check(on_file == listed, "no metric file without an entry in "
          f"BENCHMARK.json ({sorted(on_file - listed)})")
    # PERF.md section 2's table: | `metric` | cells | what | bound | set from |
    with open(os.path.join(ROOT, "PERF.md")) as f:
        section = f.read().split("\n## 2.")[1].split("\n## 3.")[0]
    table = {}
    for line in section.splitlines():
        cols = [c.strip() for c in line.strip().strip("|").split("|")]
        if len(cols) >= 4 and re.fullmatch(r"`[\w.]+`", cols[0]):
            table[cols[0].strip("`")] = float(cols[3])
    bounds = {m["name"]: m["bound"] for m in bench["end_to_end"]}
    check(table == bounds, f"BENCHMARK.json's bounds {bounds} are PERF.md "
          f"section 2's {table}")
    for name in sorted(os.listdir(os.path.join(HERE, "configs"))):
        config = load("configs", name)
        stated = config["index_parameter"].get("max_elements")
        if stated is not None:
            check(stated == config["rows"],
                  f"configs/{name}: the recipe's max_elements ({stated}) is "
                  f"the configuration's rows ({config['rows']})")
    run_seconds = re.search(r"`run_seconds` (\d+)", section)
    check(run_seconds and int(run_seconds.group(1)) == bench["run_seconds"],
          "PERF.md section 2 states BENCHMARK.json's run_seconds")


# --------------------------------------------------------------- 6. deadline
def processes_naming(path: str):
    """Pids of live processes whose command line names `path`."""
    found = []
    for pid in os.listdir("/proc"):
        if pid.isdigit() and int(pid) != os.getpid():
            try:
                with open(f"/proc/{pid}/cmdline", "rb") as f:
                    if path.encode() in f.read():
                        found.append(int(pid))
            except OSError:
                pass
    return found


def test_deadline() -> None:
    import run as harness

    def failure_of(fn, armed=None) -> str:
        """What `fn` fails with, under the deadline `armed` if one is given
        ('' if it returns)."""
        if armed is not None:
            armed.arm()
        try:
            try:
                fn()
            finally:
                if armed is not None:
                    armed.disarm()      # as run.main does: before the catch
            return ""
        except harness.RunFailure as e:
            return str(e)

    limits = load("harness.json")
    check(0 < limits["setup_deadline_s"] + 45 + 20 <= 360 - 45,
          "harness.json: set-up's deadline, the window and the run's end "
          "leave 45 s of the driver's 360")
    # (a) a child that never answers: the blocking readline ends at the
    # deadline, the phase is named, and nothing is left running
    out = os.path.join(HERE, "out", "selftest-deadline")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    cluster = harness.Cluster(out, limits["generator_core_share"])
    stub = harness.Child("stub", [sys.executable, "-c",
                                  "import time; time.sleep(600)", out],
                         cluster.off_jax, os.path.join(out, "stub.log"))
    cluster.callers.append(stub)
    t0 = time.monotonic()
    deadline = harness.SetupDeadline(1.0, t_start=t0)
    deadline.phase = "load"
    said = failure_of(lambda: stub.ask(cmd="load"), deadline)
    took = time.monotonic() - t0
    check(said == "set-up past its deadline of 1 s in phase load after 1 s"
          and 1.0 <= took < 2.0,
          f"a child that never answers: {said!r} after {took:.2f} s")
    cluster.stop()
    check(stub.p.poll() is not None and not processes_naming(out),
          "and no child is left alive")
    shutil.rmtree(out, ignore_errors=True)
    # the deadline's failure passes through wait_for's retries
    said = failure_of(
        lambda: harness.wait_for("nothing", lambda: time.sleep(5), 5, []),
        harness.SetupDeadline(0.3, t_start=time.monotonic()))
    check("phase store_up" in said, f"wait_for does not retry past it: {said!r}")
    # (b) an open of the window laid past the deadline fails in `align`
    ev = {"crontab": {"scrub": {"interval_s": 60.0, "added": 0.0},
                      "sweep": {"interval_s": 5.0, "added": 0.0}},
          "events": [["cron.scrub", 200.0, 200.1], ["cron.sweep", 235.0, 235.5]]}
    t_open, tick = harness.aligned_open(ev, limits, 225.0, 45.0)
    check((round(t_open, 6), tick) == (280.0, 320.0),
          "a set-up that ends 5 s after a grid point waits for the next "
          f"tick: opens at {t_open}, tick at {tick}")
    deadline = harness.SetupDeadline(250.0, t_start=0.0)
    said = failure_of(lambda: deadline.check_open(t_open))
    check(said == "set-up past its deadline of 250 s in phase align after 280 s",
          f"an open laid past the deadline: {said!r}")
    t_open, tick = harness.aligned_open(ev, limits, 215.0, 45.0)
    deadline.check_open(t_open)
    check(t_open == 220.0 and deadline.phase == "align",
          "an open inside the deadline passes")
    # the tick is the earliest begin of the latest tick's jobs: one that
    # waited 14 s behind a write does not move it
    ev["crontab"]["gc"] = {"interval_s": 60.0, "added": 0.0}
    ev["events"] += [["cron.gc", 214.0, 214.1], ["cron.scrub", 140.0, 140.1]]
    check(harness.aligned_open(ev, limits, 215.0, 45.0) == (220.0, 260.0),
          "a tick's job that began late does not move the tick")


# ------------------------------------------------------------- 7. rehearsals
def rehearse(workload: str, *extra, root: str = ROOT):
    """A CPU rehearsal of the cell `workload` of the checkout at `root`."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", workload, "--seed", str(2**31 + 77), "--seconds", "4",
         "--trace", "0", "--rows", "8192", "--no-align", *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    lines = got.stdout.strip().splitlines()
    check(got.returncode == 3, f"{workload} {extra}: a rehearsal exits 3 "
          f"(got {got.returncode}) {got.stderr[-500:] if got.returncode != 3 else ''}")
    check(all("platform: cpu" in ln for ln in lines),
          f"{workload} {extra}: every line says platform: cpu")
    last = json.loads(lines[-1].split("  [platform")[0])
    check(last["correct"] is False and last["rehearsal"] is True,
          f"{workload} {extra}: the last line is not a pass")
    last["harness"] = next(json.loads(ln[8:].split("  [platform")[0])
                           for ln in lines if ln.startswith("harness {"))
    import ast

    last["regions"] = [ast.literal_eval(st) for ln in lines
                       if ln.startswith("build: ")
                       for st in re.findall(r"region (\{[^}]*\})", ln)]
    return last


def test_rehearsals() -> None:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "ivf768.batch64", "--seed", "1", "--seconds", "4", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    check(got.returncode != 0 and not got.stdout.strip(),
          "without a chip and without --rows: no result, non-zero exit")
    sound = rehearse("ivf768.batch64")
    check(sound["correct_if_it_were_a_chip"] is True,
          "ivf768.batch64: the sound path would be correct")
    check(0 < sound["harness"]["generator_busy_share"] <= 1.05
          and sound["harness"]["host_probe_ms"] > 0,
          "ivf768.batch64: the harness reads its busiest caller's share of "
          "one core and the host probe")
    check(rehearse("ivf768.batch64", "--wrap-client", "faults.py:alter_answer")[
        "correct_if_it_were_a_chip"] is False,
        "an answer altered where it is produced: not correct")
    check(rehearse("ivf768.batch64", "--wrap-client", "faults.py:half_batch")[
        "correct_if_it_were_a_chip"] is False,
        "half of the batch left out: not correct")
    check(rehearse("ivf768.insert64")["correct_if_it_were_a_chip"] is True,
          "ivf768.insert64: the sound path would be correct")
    check(rehearse("ivf768.insert64", "--wrap-client", "faults.py:drop_row")[
        "correct_if_it_were_a_chip"] is False,
        "an acknowledged row that was never written: not correct")
    check(rehearse("flat768.single", "--control", "bf16")["control"][
        "correct"] is False,
        "the bfloat16 control in the program's place: not correct")
    # the fixtures: three regions with scalar columns; filtered searches
    # judged on fake clients only, one that drops the predicates and one
    # that applies them, whatever the program does with them itself
    tree = fixture_tree()
    r3 = rehearse("regions3.batch64", root=tree)
    check(r3["correct_if_it_were_a_chip"] is True and len(r3["regions"]) == 3
          and sum(st["count"] for st in r3["regions"]) == 8192,
          "regions3.batch64: correct over three regions whose counts sum to "
          f"the rows ({[st['count'] for st in r3['regions']]})")
    drop = rehearse("regions3.batch64_label", "--wrap-client",
                    "faults.py:drop_predicates", root=tree)
    check(drop["correct_if_it_were_a_chip"] is False
          and drop["compared"]["unmatched_rows"][0] > 0,
          "a client that drops the predicates: not correct, unmatched_rows "
          f"{drop['compared']['unmatched_rows'][0]}")
    honest = rehearse("regions3.batch64_label", "--wrap-client",
                      "faults.py:apply_predicates", root=tree)
    check(honest["correct_if_it_were_a_chip"] is True
          and honest["compared"]["unmatched_rows"][0] == 0,
          "a client that applies them: correct, unmatched_rows 0, recall "
          f"{honest['compared']['recall_at_10'][0]}")
    # a whole run against a deadline it cannot keep: a scratch copy of the
    # benchmark (the program by a link) whose harness.json states 3 s
    shutil.rmtree(tree, ignore_errors=True)
    tree = scratch_tree(os.path.join(HERE, "out", "selftest-deadline-tree"))
    limits = load("harness.json")
    with open(os.path.join(tree, "benchmark", "harness.json"), "w") as f:
        json.dump(dict(limits, setup_deadline_s=3.0), f)
    t0 = time.monotonic()
    got = subprocess.run(
        [sys.executable, os.path.join(tree, "benchmark", "run.py"),
         "--workload", "hnsw768.conc4", "--seed", "5", "--seconds", "4",
         "--trace", "0", "--rows", "8192", "--no-align"],
        cwd=tree, env=env, capture_output=True, text=True, timeout=300)
    took = time.monotonic() - t0
    check(got.returncode == 1 and re.search(
        r"RUN FAILED: set-up past its deadline of 3 s in phase "
        r"(store_up|region|load) after 3 s", got.stderr) is not None
        and "{" not in got.stdout.strip().splitlines()[-1],
        f"a run that cannot keep the deadline exits 1 with the phase named "
        f"and no result ({took:.1f} s): {got.stderr.strip()[-200:]!r}")
    check(took < 3.0 + 20.0 and not processes_naming(tree),
          "and leaves no process behind")
    shutil.rmtree(tree, ignore_errors=True)


def scratch_tree(tree: str) -> str:
    """A scratch copy of the benchmark at `tree`, with the checkout's
    BENCHMARK.json and the program by a link. -> `tree`"""
    shutil.rmtree(tree, ignore_errors=True)
    shutil.copytree(HERE, os.path.join(tree, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "sets", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tree)
    os.symlink(os.path.join(ROOT, "dingo_tpu"), os.path.join(tree, "dingo_tpu"))
    return tree


def fixture_tree(tree: str = "") -> str:
    """A scratch copy of the benchmark (`out/selftest-fixtures` unless
    `tree` is given) whose BENCHMARK.json adds the fixtures of
    `testdata/cells.json`: their configurations, their cells (listed in
    every metric that lists `metrics_as`), and `testdata/traffic/`'s mixes
    in its `traffic/`. -> its root"""
    tree = scratch_tree(tree or os.path.join(HERE, "out", "selftest-fixtures"))
    fixtures = load("testdata", "cells.json")
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] += fixtures["configs"]
    bench["workloads"] += fixtures["workloads"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if fixtures["metrics_as"] in m.get("workloads", ()):
            m["workloads"] += [w["name"] for w in fixtures["workloads"]]
    with open(os.path.join(tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    mixes = os.path.join(HERE, "testdata", "traffic")
    for name in os.listdir(mixes):
        shutil.copy(os.path.join(mixes, name),
                    os.path.join(tree, "benchmark", "traffic"))
    return tree


# --------------------------------------------- 8. regions, columns, filters
#: sha256 (first 16 hex) of ivf768's rows, queries and fresh rows at seed
#: 2**31 + 11, recorded from the scripts before scalar columns existed
BEFORE_COLUMNS = {"block0": "06847ba414cfb6e0", "block38": "84d2d3fe58b7c596",
                  "pool": "1b59197e66d07ee7", "fresh3": "9af0cee5f2ce975f"}


def digest(a) -> str:
    b = a if isinstance(a, bytes) else np.ascontiguousarray(a).tobytes()
    return hashlib.sha256(b).hexdigest()[:16]


class RecordingClient:
    """A fake SDK client: records each call the harness makes (method,
    partition, id range, keyword arguments, digests of vectors and scalar
    rows) and answers in the shape a sound store would."""

    def __init__(self, config: dict, log: list):
        self.config, self.log, self.windows = config, log, {}

    def note(self, *call) -> None:
        self.log.append(json.loads(json.dumps(call)))

    def refresh_region_map(self):
        pass

    def close(self):
        pass

    def create_index_region(self, partition, lo, hi, param, **kw):
        region_id = 1000 + len(self.windows)
        self.windows[region_id] = (lo, hi)
        self.note("create_index_region", partition, lo, hi,
                  " ".join(str(param).split()), kw)
        return types.SimpleNamespace(region_id=region_id)

    def vector_status(self, partition):
        self.note("vector_status", partition)
        rows = self.config["rows"]
        return [{"region_id": r, "ready": True, "trained": True,
                 "build_error": False, "count": min(hi, rows) - min(lo, rows)}
                for r, (lo, hi) in self.windows.items()]

    def vector_build(self, partition):
        self.note("vector_build", partition)

    def vector_count(self, partition):
        self.note("vector_count", partition)
        return self.config["rows"]

    def _stub(self, store, service):
        fake = self

        class Stub:
            def __getattr__(self, method):
                def call(req):
                    fake.note(f"{service}.{method}", store,
                              " ".join(str(req).split()))
                    return types.SimpleNamespace(
                        error=types.SimpleNamespace(errcode=0, errmsg=""))
                return call
        return Stub()

    def vector_add(self, partition, ids, vectors, **kw):
        ids = [int(i) for i in ids]
        self.note("vector_add", partition, [ids[0], ids[-1] + 1, len(ids)],
                  digest(np.asarray(vectors, np.float32)),
                  {a: digest(json.dumps(v).encode()) for a, v in kw.items()})

    def vector_search(self, partition, queries, **kw):
        self.note("vector_search", partition, len(queries),
                  digest(np.asarray(queries, np.float32)), kw)
        return [[(j, float(j)) for j in range(kw.get("topk", 10))]
                for _ in queries]


def record_calls(workload: str, root: str = ROOT, rows: int = 8192) -> list:
    """The SDK calls of a cell of the checkout at `root`: its set-up
    (regions, load, build, snapshot), its first caller's warm-up, four more
    requests (the closed loop's step), for an open loop four more sent from
    its pool of threads as they fall due (in a fixed order), and for a write
    cell a read-back of two requests, against `RecordingClient`; `rows` as
    a rehearsal's `--rows`. Uses only what the scripts had before regions
    and filters, so that the same function records the older scripts."""
    import caller as callers
    import run as harness

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        listed = json.load(f)
    cell = next(w for w in listed["workloads"] if w["name"] == workload)
    entry = next(c for c in listed["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, entry["file"])) as f:
        config = dict(json.load(f), rows=rows)
    with open(os.path.join(root, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    out = os.path.join(HERE, "out", "selftest-calls")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    log = []
    fake = RecordingClient(config, log)
    cluster = harness.Cluster(out, 0.3)
    cluster.client = fake
    cluster.create_region(config)
    cluster.build(config)
    one = callers.Caller({
        "coordinator": "127.0.0.1:1", "store": "127.0.0.1:2",
        "config": config, "traffic": mix, "seed": 2**31 + 3, "caller": 0,
        "out": out, "cores": [], "wrap_client": None})
    one._plain_client = one._new_client = lambda: fake
    one.load()
    one.warm(mix["warm_requests"])
    for _ in range(4):
        one.one()
    if mix["loop"] == "open":
        start, now = len(log), time.monotonic()
        one.go(now, now, [0.0, 0.01, 0.02, 0.03])
        log[start:] = sorted(log[start:], key=json.dumps)
    if mix["operation"] == "vector_add":
        one.readback([0, 1])
    shutil.rmtree(out, ignore_errors=True)
    return log


def test_columns() -> None:
    """(a) the scalar columns, and the vectors beside them."""
    import caller as callers
    import columns

    ivf = load("configs", "ivf768.json")
    r3 = load("testdata", "regions3.json")
    seed = 2**31 + 11
    for name, config in (("ivf768", ivf), ("regions3", r3)):
        data = reference.Data(seed, config)
        got = {"block0": digest(data.block(0)), "block38": digest(data.block(38)),
               "pool": digest(data.query_pool(2048)),
               "fresh3": digest(data.fresh_rows(3, 64)[1])}
        check(got == BEFORE_COLUMNS, f"{name}: blocks 0 and 38, the query pool "
              f"and fresh rows hash as before columns existed ({got})")
    check(not reference.Data(seed, ivf).columns and not columns.specs(ivf),
          "ivf768 states no scalars: no columns")
    c = callers.Caller({
        "coordinator": "127.0.0.1:1", "store": "127.0.0.1:2", "config": r3,
        "traffic": load("traffic", "batch64_closed.json"), "seed": seed,
        "caller": 0, "out": "", "cores": [], "wrap_client": None})
    ref = reference.Data(seed, r3).columns
    same = all(np.array_equal(c.data.columns.block(b)[f], ref.block(b)[f])
               for b in (0, 38) for f in ("label", "num"))
    check(same and np.array_equal(c.data.columns.fresh(5, 64)["num"],
                                  ref.fresh(5, 64)["num"]),
          "the caller's columns are the reference's (blocks 0 and 38, fresh)")
    b0 = ref.block(0)
    check(len(b0["label"]) == 4096 and set(b0["label"].tolist()) == {0, 1}
          and 0 <= b0["num"].min() and b0["num"].max() < 1_000_000
          and b0["num"].dtype == np.int64,
          "label in [0, 2), num in [0, 1,000,000), int64, a block's rows")
    check(not np.array_equal(b0["num"], reference.Data(seed + 1, r3)
                             .columns.block(0)["num"]),
          "another seed, other columns")
    corpus = ref.corpus()
    check(len(corpus["num"]) == r3["rows"] and np.array_equal(
        corpus["num"][38 * 4096:39 * 4096], ref.block(38)["num"]),
        "the corpus's columns are its blocks' one after the other")
    # what the load sends is what the reference judges by
    tree = fixture_tree()
    log = record_calls("regions3.batch64", tree, rows=8192)
    shutil.rmtree(tree, ignore_errors=True)
    adds = [c for c in log if c[0] == "vector_add"]
    data = reference.Data(2**31 + 3, dict(r3, rows=8192))
    want = [digest(json.dumps({"scalars": columns.as_rows(
        data.columns.block(b))}["scalars"]).encode()) for b in (0, 1)]
    check([a[4].get("scalars") for a in adds] == want,
          "the load sends each block's columns, as the reference makes them")
    fills = [columns.fill([{"field": "label", "op": "eq",
                            "value": {"draw": "categorical"}}], r3, 7, 0, n)
             for n in range(64)]
    check({f[0]["value"] for f in fills} == {0, 1} and fills[5] == columns.fill(
        [{"field": "label", "op": "eq", "value": {"draw": "categorical"}}],
        r3, 7, 0, 5), "a drawn value: from the seed and the request's number")


def brute_force(x, cols, queries, predicates, k):
    """ids of the min(k, matches) nearest matching rows, a Python loop."""
    import operator

    test = {"eq": operator.eq, "ne": operator.ne, "lt": operator.lt,
            "le": operator.le, "gt": operator.gt, "ge": operator.ge,
            "in": lambda v, values: v in values}

    def ok(i, p):
        return test[p["op"]](int(cols[p["field"]][i]), p["value"])
    rows = [i for i in range(len(x)) if all(ok(i, p) for p in predicates)]
    out = []
    for q in queries.astype(np.float64):
        d = [float(((x[i].astype(np.float64) - q) ** 2).sum()) for i in rows]
        out.append([rows[j] for j in np.argsort(d, kind="stable")[:k]])
    return out, len(rows)


def test_filter_arm() -> None:
    """(b) the filter arm against a brute-force loop."""
    import columns

    k, batch, seed = 10, 8, 2**31 + 29
    config = dict(load("testdata", "regions3.json"), rows=3000)
    dist = reference.check_supported(config)
    data = reference.Data(seed, config)
    x, cols = data.corpus(), data.columns.corpus()
    pool = data.query_pool(64)
    sets = [[{"field": "label", "op": "eq", "value": 1}],
            [{"field": "num", "op": "ge", "value": 200_000},
             {"field": "num", "op": "lt", "value": 600_000}],
            [{"field": "num", "op": "in",
              "value": [int(cols["num"][i]) for i in (3, 17, 250)]
              + [1_000_001]}]]
    served = np.full((3, batch, k), -1, np.int64)
    dists = np.full((3, batch, k), np.inf, np.float32)
    n_match = []
    for r, preds in enumerate(sets):
        q = pool[r * batch:(r + 1) * batch]
        want, n = brute_force(x, cols, q, preds, k)
        n_match.append(n)
        mask = columns.matches(cols, preds)
        got, _ = reference.where_topk(reference.exact_topk, dist, x, q, k,
                                      np.zeros(batch, np.int64), [mask])
        check(int(mask.sum()) == n and all(
            got[i, :len(w)].tolist() == w and (got[i, len(w):] == -1).all()
            for i, w in enumerate(want)),
            f"{[p['op'] for p in preds]}: the reference's filtered top-k is "
            f"the brute-force loop's ({n} rows match)")
        for i, w in enumerate(want):
            served[r, i, :len(w)] = w
            dists[r, i, :len(w)] = dist.served64(x, q[i:i + 1], np.asarray([w]))[0]
    check(n_match[2] < k <= min(n_match[:2]),
          f"one predicate matches fewer than k rows ({n_match})")
    out = os.path.join(HERE, "out", "selftest-filter")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)

    def judged(ids, ds, predicates=None):
        path = os.path.join(out, "replies.npz")
        np.savez(path, batch=batch, offsets=np.arange(3) * batch,
                 fresh_request=np.full(3, -1, np.int64), ids=ids, dists=ds,
                 predicates=np.asarray(predicates or [json.dumps(p) for p in sets]))
        return reference.run_job({
            "seed": seed, "config": config, "k": k, "pool": 64,
            "replies": path, "truth_rows": list(range(3 * batch))})

    good = judged(served, dists)
    check(good["recall_at_10"] == 1.0 and good["unmatched_rows"] == 0
          and good["malformed_items"] == 0 and good["dist_err"] < 1e-6,
          f"the brute-force answers pass, short where fewer than k match ({good})")
    bad = served.copy()
    other = int(np.flatnonzero(cols["label"] == 0)[0])
    bad[0, 0, 0] = other
    planted = judged(bad, dists)
    check(planted["unmatched_rows"] == 1,
          f"a planted id whose row fails its predicates is counted: {planted}")
    short = served.copy()
    short[1, 0, 7:] = -1
    check(judged(short, dists)["malformed_items"] == 3,
          "a short reply where k rows match stays malformed")
    tops = [reference.exact_topk(dist, x, pool[r * batch:(r + 1) * batch], k)
            for r in range(3)]
    plain = np.stack([t[0] for t in tops])
    unfiltered = judged(plain, np.stack([t[1] for t in tops]).astype(np.float32))
    masks = [columns.matches(cols, p) for p in sets]
    want = sum(int((~masks[r][plain[r]]).sum()) for r in range(3))
    check(unfiltered["unmatched_rows"] == want > 0
          and unfiltered["recall_at_10"] < 0.95,
          f"the unfiltered top-k: every non-matching id counted ({want})")
    none = judged(served[:, :, :], dists, ["", "", ""])
    check("unmatched_rows" in none and none["unmatched_rows"] == 0
          and none["malformed_items"] > 0,
          "requests with no predicates are judged unfiltered")
    shutil.rmtree(out, ignore_errors=True)


def test_regions() -> None:
    """(c) the region windows."""
    import run as harness

    r3 = load("testdata", "regions3.json")
    for regions, rows in ((3, 160_000), (3, 8192), (1, 160_000), (5, 160_000),
                          (3, 12_288), (7, 4096 * 9 + 5)):
        config = dict(r3, regions=regions, rows=rows)
        w = harness.region_windows(config)
        ids = np.arange(rows)
        check(len(w) == regions and w[0][0] == 0 and w[-1][1] == 1 << 40
              and all(w[i][1] == w[i + 1][0] for i in range(regions - 1))
              and all(lo < hi for lo, hi, _ in w)
              and [n for *_, n in w] == [int(((ids >= lo) & (ids < hi)).sum())
                                         for lo, hi, _ in w]
              and sum(n for *_, n in w) == rows,
              f"{regions} regions of {rows} rows: windows from 0 to 2^40, no "
              f"gap, no overlap, each window's rows ({[n for *_, n in w]})")
        if -(-rows // 4096) >= regions:
            check(all(lo % 4096 == 0 for lo, _, _ in w),
                  "  cut by whole blocks")
    check(harness.region_windows(load("configs", "ivf768.json"))
          == [(0, 1 << 40, 160_000)], "no `regions`: one region [0, 2^40)")
    w = harness.region_windows(r3)
    check([lo for lo, _, _ in w] == [0, 53_248, 106_496],
          "regions3 at 160,000 rows: 13, 13 and 14 blocks")


def test_refusals() -> None:
    """(d) what `check_config` refuses before anything starts."""
    import run as harness

    r3 = load("testdata", "regions3.json")
    mix = load("testdata", "traffic", "batch64_label.json")
    harness.check_config(r3, mix)
    harness.check_config(r3, load("traffic", "batch64_closed.json"))
    harness.check_config(load("configs", "ivf768.json"),
                         load("traffic", "batch64_closed.json"))

    def args(**kw):
        return dict(mix, search_args=dict(mix["search_args"], **kw))

    def pred(**kw):
        return args(predicates=[dict(mix["search_args"]["predicates"][0], **kw)])

    corpus = r3["assumed"]["corpus"]
    no_limit = {n: v for n, v in r3["limits"].items() if n != "unmatched_rows"}
    cases = [
        ("a filtered traffic without limits.unmatched_rows", dict(
            r3, limits=no_limit), mix),
        ("limits.unmatched_rows.max 1", dict(r3, limits=dict(
            r3["limits"], unmatched_rows={"max": 1})), mix),
        ("a predicate on a field scalars lacks", r3, pred(field="colour")),
        ("an unknown op", r3, pred(op="like")),
        ("a draw of another kind than the field's", r3,
         pred(value={"draw": "uniform_int"})),
        ("an unknown draw kind", r3, pred(value={"draw": "zipf"})),
        ("`in` with a number", r3, pred(op="in", value=1)),
        ("`eq` with a list", r3, pred(value=[1])),
        ("no predicates", r3, args(predicates=[])),
        ("a filter on writes", r3, dict(mix, operation="vector_add")),
        ("a filter the reference cannot judge", r3, args(filter="TABLE_FILTER")),
        ("an unknown filter_type", r3, args(filter_type="QUERY_SIDEWAYS")),
        ("a filtered traffic on a configuration with no scalars",
         dict(load("configs", "ivf768.json"), limits=r3["limits"]), mix),
        ("an unknown scalar kind", dict(r3, assumed=dict(
            r3["assumed"], corpus=dict(corpus, scalars=[
                {"field": "label", "kind": "zipf", "values": 2}]))), None),
        ("a column without its number", dict(r3, assumed=dict(
            r3["assumed"], corpus=dict(corpus, scalars=[
                {"field": "label", "kind": "categorical"}]))), None),
        ("regions 0", dict(r3, regions=0), None),
        ("regions '3'", dict(r3, regions="3"), None),
        ("a window of fewer than k rows", dict(r3, rows=20), None),
    ]
    for what, config, traffic in cases:
        try:
            harness.check_config(config, traffic)
            check(False, f"refused: {what}")
        except harness.RunFailure as e:
            check(True, f"refused: {what} ({str(e)[:90]})")


def test_calls() -> None:
    """(e) the five cells that were there when regions and filters came
    make the SDK calls the scripts made before them."""
    with open(os.path.join(HERE, "testdata", "calls.json")) as f:
        before = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    # a cell added later has no record from before
    check(len(before) == 5 and set(before) <= set(cells),
          "testdata/calls.json holds the five cells, each still a cell")
    for name in before:
        got = record_calls(name)
        check(got == before[name], f"{name}: the same {len(got)} SDK calls, "
              "arguments and region creation as before regions and filters")
    tree = fixture_tree()
    log = record_calls("regions3.batch64_label", tree)
    shutil.rmtree(tree, ignore_errors=True)
    made = [c[2:4] for c in log if c[0] == "create_index_region"]
    snaps = [c for c in log if c[0] == "RegionControlService.RegionSnapshot"]
    searches = [c for c in log if c[0] == "vector_search"]
    check(made == [[0, 2730], [2730, 5461], [5461, 1 << 40]] and len(snaps) == 3
          and sum(c[0] == "vector_build" for c in log) == 1,
          "regions3: three regions created, one build, three snapshots")
    check(all(c[4]["filter"] == 1 and c[4]["filter_type"] == 0
              and c[4]["predicates"][0]["value"] in (0, 1) for c in searches),
          "a filtered search: the SDK's enum values and the drawn predicates")


def main() -> int:
    if sys.argv[1:2] == ["fixture-tree"]:
        print(fixture_tree(os.path.abspath(sys.argv[2])))
        return 0
    if sys.argv[1:2] == ["record-calls"]:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            cells = [w["name"] for w in json.load(f)["workloads"]]
        with open(sys.argv[2], "w") as f:
            json.dump({name: record_calls(name) for name in cells}, f)
        return 0
    test_work()
    test_arithmetic()
    test_comparison()
    test_reducer()
    test_data()
    test_deadline()
    test_columns()
    test_filter_arm()
    test_regions()
    test_refusals()
    test_calls()
    if "quick" not in sys.argv[1:]:
        test_rehearsals()
    print("selftest passed", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
