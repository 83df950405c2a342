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
   with the phase named and leaves no process behind.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import time

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
            fn()
            return ""
        except harness.RunFailure as e:
            return str(e)
        finally:
            if armed is not None:
                armed.disarm()

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
def rehearse(workload: str, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", workload,
         "--seed", str(2**31 + 77), "--seconds", "4", "--trace", "0",
         "--rows", "8192", "--no-align", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
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
    # a whole run against a deadline it cannot keep: a scratch copy of the
    # benchmark (the program by a link) whose harness.json states 3 s
    tree = os.path.join(HERE, "out", "selftest-deadline-tree")
    shutil.rmtree(tree, ignore_errors=True)
    shutil.copytree(HERE, os.path.join(tree, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "sets", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tree)
    os.symlink(os.path.join(ROOT, "dingo_tpu"), os.path.join(tree, "dingo_tpu"))
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


def main() -> int:
    test_work()
    test_arithmetic()
    test_comparison()
    test_reducer()
    test_data()
    test_deadline()
    if "quick" not in sys.argv[1:]:
        test_rehearsals()
    print("selftest passed", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
