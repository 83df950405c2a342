"""First-run smoke on the chip: a three-process cluster answers searches
from a TPU at the repo's documented configuration (BASELINE.json config 2:
IVF_FLAT, 768-d fp32, L2, nlist 1024, query batch 64, k 10), and says so
when it cannot.

    python chip_smoke.py                     # on a machine with a TPU
    python chip_smoke.py --chips 4           # mesh-sharded IVF on 4 chips
    JAX_PLATFORMS=cpu python chip_smoke.py --rows 20000   # CPU rehearsal

This process never initialises a JAX backend (a chip belongs to one
process): it starts a coordinator and one store as children through the
normal entry point (`python -m dingo_tpu.server.main --role ...`) and drives
the SDK: create region -> vector_add in 4096-row batches -> VectorBuild ->
8 x 64 searches, twice -> 64 fresh rows searched at once -> SIGTERM. Then,
with the chip free again, one more child compiles every Pallas kernel `auto`
can select on a TPU and compares each with its XLA arm.

It fails (non-zero exit, reason printed) unless the answers are right AND
the store says it served them from the chip without walking any fallback:
every check reads a reply or the store's own MetricsDump / VectorStatus,
never a child's log. Load, build and compile seconds are printed as set-up
observations, not as metrics. A CPU rehearsal needs JAX_PLATFORMS=cpu AND an
explicit --rows; every line it prints says `platform: cpu`, it exits 3, and
its last line is not the pass object.

Last line of stdout on a pass:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# the configuration (BASELINE.json config 2); only rows may be cut
SPEC_ROWS = 1_000_000
DIM, NLIST, BATCH, TOPK = 768, 1024, 64, 10
NPROBE = 32
N_BATCHES = 8
ADD_BATCH = 4096
FRESH_ROWS = 64
MIN_ROWS = 262_144
#: rows loaded by default. 1,000,000 x 768 does not fit the 1200 s limit
#: today: on the v5e's host (chip run, PR 22) the load alone took 818 s
#: (1223 rows/s — every float is boxed through Python on both sides of the
#: wire, ROADMAP A3, and the WAL engine re-writes its whole state every 64
#: MiB) and VectorBuild, which re-reads every row from the engine, had not
#: finished at 1150 s. 262,144 rows: 2072 rows/s, 311 s in all. Cut as
#: ISSUE 22 allows (rows only, never below 262,144).
DEFAULT_ROWS = 262_144
REDUCED_WHY = ("1,000,000 rows do not fit the 1200 s limit at today's wire "
               "and engine speed: load alone took 818 s on the v5e host and "
               "VectorBuild had not finished at 1150 s (chip run, PR 22; "
               "ROADMAP A3)")
RECALL_GATE = 0.95
DEADLINE_S = 1150
PARTITION = 1

_children = []          # (name, Popen, log path) — stopped on every exit path
_tag = ""               # " [platform: cpu]" on a rehearsal


def say(msg: str) -> None:
    print(f"{msg}{_tag}", flush=True)


class SmokeFailure(Exception):
    pass


# --------------------------------------------------------------- processes
def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn(name: str, argv, env, out_dir: str) -> subprocess.Popen:
    log = os.path.join(out_dir, f"{name}.log")
    with open(log, "w") as f:
        p = subprocess.Popen(argv, env=env, cwd=HERE, stdout=f,
                             stderr=subprocess.STDOUT)
    _children.append((name, p, log))
    return p


def _log_tail(log: str, n: int = 30) -> str:
    try:
        with open(log, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def _check_alive() -> None:
    for name, p, log in _children:
        rc = p.poll()
        if rc is not None:
            raise SmokeFailure(
                f"{name} exited early with code {rc}; its last output:\n"
                f"{_log_tail(log)}")


def _stop_children(timeout_s: float = 30.0) -> list:
    """SIGTERM every child and wait; returns [(name, returncode)] with
    None for a child that had to be killed."""
    out = []
    for name, p, _log in _children:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    for name, p, _log in _children:
        try:
            out.append((name, p.wait(timeout=timeout_s)))
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
            out.append((name, None))
    _children.clear()
    return out


def _wait_for(what: str, probe, timeout_s: float, every_s: float = 0.5):
    """Poll `probe` (returns a truthy value when ready, may raise while the
    peer is still coming up) until it answers; a dead child or the timeout
    fails the smoke."""
    t_end = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < t_end:
        _check_alive()
        try:
            got = probe()
            if got:
                return got
        except Exception as e:  # noqa: BLE001 — peer not up yet; retried
            last = e
        time.sleep(every_s)
    raise SmokeFailure(f"timeout after {timeout_s:.0f}s waiting for {what}"
                       + (f" (last error: {last})" if last else ""))


# -------------------------------------------------------------------- data
def make_corpus(rows: int, seed: int):
    """Clustered mixture: iid gaussians have near-orthogonal neighbours
    and defeat any IVF."""
    import numpy as np

    rng = np.random.default_rng(seed)
    ncl = max(64, rows // 1000)
    centers = rng.standard_normal((ncl, DIM), dtype=np.float32)
    x = centers[rng.integers(0, ncl, rows)]
    x += 0.35 * rng.standard_normal((rows, DIM), dtype=np.float32)
    nq = N_BATCHES * BATCH
    queries = x[rng.choice(rows, nq, replace=False)] + 0.05 * (
        rng.standard_normal((nq, DIM), dtype=np.float32))
    fresh = centers[rng.integers(0, ncl, FRESH_ROWS)] + 0.35 * (
        rng.standard_normal((FRESH_ROWS, DIM), dtype=np.float32))
    return x, queries.astype(np.float32), fresh.astype(np.float32)


def exact_topk(x, queries, k: int, chunk: int = 131_072):
    """Plain numpy reference: exact L2 top-k ids [nq, k] over all rows."""
    import numpy as np

    qsq = (queries ** 2).sum(1)[:, None]
    best_d = np.full((len(queries), k), np.inf, np.float32)
    best_i = np.full((len(queries), k), -1, np.int64)
    for lo in range(0, len(x), chunk):
        xs = x[lo:lo + chunk]
        d = qsq - 2.0 * (queries @ xs.T) + (xs ** 2).sum(1)[None, :]
        top = np.argpartition(d, k - 1, axis=1)[:, :k]
        cat_d = np.concatenate(
            [best_d, np.take_along_axis(d, top, axis=1)], axis=1)
        cat_i = np.concatenate([best_i, top + lo], axis=1)
        keep = np.argpartition(cat_d, k - 1, axis=1)[:, :k]
        best_d = np.take_along_axis(cat_d, keep, axis=1)
        best_i = np.take_along_axis(cat_i, keep, axis=1)
    return best_i


# ----------------------------------------------------------- store metrics
def _series(dump: dict, name: str) -> list:
    """Every series of one metric in a MetricsDump: [(labels, value)]."""
    from dingo_tpu.common.metrics import split_series_key

    out = []
    for key, value in dump.items():
        base, pairs = split_series_key(key)
        if base == name:
            out.append((dict(pairs), value))
    return out


def _total(dump: dict, name: str) -> float:
    return sum(v for _, v in _series(dump, name))


def _kernel_calls(dump: dict) -> dict:
    """Sentinel kernel name -> calls (jit-cache hits + traces)."""
    calls: dict = {}
    for metric in ("xla.cache_hits", "xla.recompiles_by_kernel"):
        for labels, v in _series(dump, metric):
            calls[labels["kernel"]] = calls.get(labels["kernel"], 0) + int(v)
    return calls


# ------------------------------------------------------------ served phase
def served_phase(args, out_dir: str, child_env: dict, summary: dict):
    import numpy as np

    from dingo_tpu.client import DingoClient
    from dingo_tpu.server import pb

    rehearsal = summary["rehearsal"]
    py = [sys.executable, "-m", "dingo_tpu.server.main"]
    coord_port, store_port = _free_port(), _free_port()
    coord_addr = f"127.0.0.1:{coord_port}"
    # the coordinator must never touch jax: it gets a backend name that
    # does not exist, so any backend initialisation there raises instead
    # of silently taking the chip from the store
    coord_env = dict(child_env, JAX_PLATFORMS="none_the_coordinator_is_off_jax")
    _spawn("coordinator", py + [
        "--role", "coordinator", "--port", str(coord_port),
        "--replication", "1",
    ], coord_env, out_dir)
    store_argv = py + [
        "--role", "store", "--id", "s0", "--port", str(store_port),
        "--coordinator", coord_addr, "--engine", "wal",
        "--data-dir", os.path.join(out_dir, "data", "s0"),
    ]
    conf_lines = []
    if args.chips > 1:
        conf_lines.append("use_mesh_sharded_ivf = true")
    if rehearsal:
        # walk the host code the chip will walk: force the TPU arm of the
        # 'auto' crossovers that change the store's data paths. The Pallas
        # arms stay off here (a 64 x 49 x 6-step grid takes tens of
        # minutes in interpret mode); the kernel phase interprets them.
        conf_lines += ["vector_blocked_layout = true",
                       "pipeline_enabled = true"]
    if conf_lines:
        conf = os.path.join(out_dir, "store.conf")
        with open(conf, "w") as f:
            f.write("\n".join(conf_lines) + "\n")
        store_argv += ["--config", conf]
    _spawn("store", store_argv, child_env, out_dir)

    client = DingoClient(coord_addr, {"s0": f"127.0.0.1:{store_port}"})

    def store_metrics() -> dict:
        stub = client._stub("s0", "DebugService")
        return json.loads(stub.MetricsDump(pb.MetricsDumpRequest()).json)

    t0 = time.monotonic()
    _wait_for("the coordinator", lambda: client.coordinator.Hello(
        pb.HelloRequest()) is not None, 60)
    first = _wait_for("the store", store_metrics, 180)
    dev = _series(first, "device.count")
    if len(dev) != 1:
        raise SmokeFailure(f"store reports no single device: {dev}")
    labels, count = dev[0]
    device = {"platform": labels["platform"], "kind": labels["kind"],
              "count": int(count)}
    summary["store_device"] = device
    say(f"store up in {time.monotonic() - t0:.1f}s: platform: "
        f"{device['platform']} device_kind: {device['kind']} "
        f"devices: {device['count']}")
    if not rehearsal and device["platform"] != "tpu":
        raise SmokeFailure(f"store platform is {device['platform']!r}")
    if device["count"] != args.chips and not rehearsal:
        raise SmokeFailure(
            f"store sees {device['count']} devices, --chips {args.chips}")
    arms = {labels["flag"]: bool(v)
            for labels, v in _series(first, "device.auto_arm")}
    summary["auto_arms_on"] = sorted(k for k, v in arms.items() if v)
    say(f"auto arms resolved on: {summary['auto_arms_on']}")

    # ---- region ----------------------------------------------------------
    param = pb.VectorIndexParameter(
        index_type=pb.VECTOR_INDEX_TYPE_IVF_FLAT, dimension=DIM,
        metric_type=pb.METRIC_TYPE_L2, ncentroids=NLIST,
        default_nprobe=NPROBE)
    # the store registers on its first heartbeat; creation is refused
    # until a store is known
    _wait_for("region creation", lambda: client.create_index_region(
        PARTITION, 0, 1 << 40, param, replication=1), 60, every_s=1.0)
    # placement rides the store's heartbeat
    _wait_for("the region on the store",
              lambda: client.vector_status(PARTITION), 60, every_s=1.0)

    # ---- load ------------------------------------------------------------
    t0 = time.monotonic()
    x, queries, fresh = make_corpus(args.rows, args.seed)
    say(f"corpus: {args.rows} x {DIM} fp32 from seed {args.seed} "
        f"({time.monotonic() - t0:.1f}s)")
    t0 = time.monotonic()
    for lo in range(0, args.rows, ADD_BATCH):
        hi = min(lo + ADD_BATCH, args.rows)
        client.vector_add(PARTITION, range(lo, hi), x[lo:hi])
        _check_alive()
    load_s = time.monotonic() - t0
    summary["load_s"] = round(load_s, 1)
    summary["load_rows_per_s"] = round(args.rows / load_s, 1)
    say(f"load: {args.rows} rows through vector_add in {ADD_BATCH}-row "
        f"batches ({ADD_BATCH * DIM * 4 / 1e6:.1f} MB each) in {load_s:.1f}s"
        f" = {args.rows / load_s:.0f} rows/s (set-up observation)")

    # ---- build / train ---------------------------------------------------
    t0 = time.monotonic()
    client.vector_build(PARTITION)

    def trained():
        st = client.vector_status(PARTITION)[0]
        return st if st["trained"] and st["ready"] else None

    status = _wait_for("the region to report trained", trained, 600)
    summary["build_s"] = round(time.monotonic() - t0, 1)
    say(f"build+train: {summary['build_s']}s (set-up observation); region "
        f"{status['region_id']} trained={status['trained']} "
        f"count={status['count']}")
    if status["count"] != args.rows or status["build_error"]:
        raise SmokeFailure(f"region status after build: {status}")

    # ---- reference -------------------------------------------------------
    t0 = time.monotonic()
    truth = exact_topk(x, queries, TOPK)
    say(f"numpy exact top-{TOPK} over the same rows: "
        f"{time.monotonic() - t0:.1f}s")

    def search_pass():
        got = []
        for b in range(N_BATCHES):
            rows = client.vector_search(
                PARTITION, queries[b * BATCH:(b + 1) * BATCH], topk=TOPK,
                nprobe=NPROBE)
            got.extend([vid for vid, _ in row] for row in rows)
        return got

    before = store_metrics()
    t0 = time.monotonic()
    pass1 = search_pass()
    summary["first_pass_s"] = round(time.monotonic() - t0, 1)
    mid = store_metrics()
    pass2 = search_pass()
    after = store_metrics()
    recall = float(np.mean([
        len(set(g) & set(t.tolist())) / TOPK for g, t in zip(pass1, truth)]))
    summary["recall_at_10"] = round(recall, 4)
    say(f"search: {N_BATCHES} batches x {BATCH} queries, k={TOPK}, "
        f"nprobe={NPROBE}, dim={DIM}, nlist={NLIST}, rows={args.rows}: "
        f"recall@10 = {recall:.4f} vs numpy exact (first pass, compiles "
        f"included: {summary['first_pass_s']}s, set-up observation)")
    if recall < RECALL_GATE:
        raise SmokeFailure(f"recall@10 {recall:.4f} < {RECALL_GATE}")
    if pass1 != pass2:
        raise SmokeFailure("the repeated pass returned different ids")
    recompiled = _total(after, "xla.recompiles") - _total(
        mid, "xla.recompiles")
    summary["compiles_first_pass"] = int(
        _total(mid, "xla.recompiles") - _total(before, "xla.recompiles"))
    summary["compiles_repeated_pass"] = int(recompiled)
    say(f"compiles: {summary['compiles_first_pass']} in the first pass, "
        f"{int(recompiled)} in the repeated pass")
    if recompiled:
        raise SmokeFailure(
            f"{int(recompiled)} compiles in the repeated pass: "
            f"{_series(after, 'xla.recompiles_by_kernel')}")
    c0, c1 = _kernel_calls(before), _kernel_calls(after)
    served = {k: c1[k] - c0.get(k, 0) for k in sorted(c1)
              if c1[k] - c0.get(k, 0) > 0}
    summary["serving_kernels"] = served
    say(f"kernels that served the searches (sentinel name: calls): "
        f"{served}")
    # the scan kernels are traced INSIDE the request's one program
    # (index.ivf.search), so the sentinel names that program; which loop
    # order scanned is the store's ivf.scan_arm counter
    scan_arms = {}
    for labels, v in _series(after, "ivf.scan_arm"):
        was = sum(w for lb, w in _series(before, "ivf.scan_arm")
                  if lb == labels)
        if v - was > 0:
            scan_arms[labels["arm"]] = int(v - was)
    summary["scan_arms"] = scan_arms
    say(f"IVF scan arms that served the searches (arm: searches): "
        f"{scan_arms}")
    if arms.get("use_pallas_ivf_search") and args.chips == 1 and not (
            scan_arms.get("batch") or scan_arms.get("query")):
        raise SmokeFailure(
            "use_pallas_ivf_search resolved on, yet no Pallas IVF kernel "
            "served a search")

    # ---- read your writes ------------------------------------------------
    fresh_ids = list(range(args.rows, args.rows + FRESH_ROWS))
    client.vector_add(PARTITION, fresh_ids, fresh)     # acknowledged
    rows = client.vector_search(PARTITION, fresh, topk=TOPK, nprobe=NPROBE)
    missed = [vid for vid, row in zip(fresh_ids, rows)
              if not row or row[0][0] != vid]
    summary["read_your_writes"] = not missed
    say(f"read-your-writes: {FRESH_ROWS - len(missed)}/{FRESH_ROWS} "
        f"acknowledged fresh rows are their own nearest neighbour in the "
        f"next search")
    if missed:
        raise SmokeFailure(f"fresh rows not found at rank 0: {missed[:8]}")

    # ---- what the store says about how it served ------------------------
    # the allocator gauges refresh on the hbm watermark crontab (10 s)
    time.sleep(11)
    final = store_metrics()
    status = client.vector_status(PARTITION)[0]
    must_be_zero = {
        name: _total(final, name) for name in (
            "fault.oom_recoveries", "fault.degraded_regions",
            "fault.host_exact_searches", "fault.bruteforce_searches",
            "build.train_failures", "hbm.alloc_failures",
            "fault.rematerializations")}
    summary["fallback_events"] = must_be_zero
    say(f"region trained={status['trained']} ready={status['ready']} "
        f"count={status['count']}; fallback events: {must_be_zero}")
    if not (status["trained"] and status["ready"]) \
            or status["count"] != args.rows + FRESH_ROWS:
        raise SmokeFailure(f"region status at the end: {status}")
    bad = {k: v for k, v in must_be_zero.items() if v}
    if bad:
        raise SmokeFailure(f"the store walked a fallback: {bad}")
    summary["peak_bytes_in_use"] = int(_total(final, "hbm.peak_bytes"))
    per_dev = {labels["device"]: int(v) for labels, v in
               _series(final, "device.bytes_in_use")}
    summary["bytes_in_use_per_device"] = per_dev
    say(f"device memory: peak_bytes_in_use = "
        f"{summary['peak_bytes_in_use'] / 1e9:.2f} GB, bytes_in_use per "
        f"device = { {k: round(v / 1e9, 2) for k, v in per_dev.items()} } GB"
        f", limit = {_total(final, 'hbm.bytes_limit') / 1e9:.2f} GB")
    if args.chips > 1 and not rehearsal:
        row_bytes = args.rows * DIM * 4 / args.chips
        thin = {k: v for k, v in per_dev.items() if v < row_bytes / 2}
        if len(per_dev) != args.chips or thin:
            raise SmokeFailure(
                f"rows are not on all {args.chips} devices: {per_dev}")
    summary["store_compile_s"] = round(
        _total(final, "xla.compile_ms_total") / 1e3, 1)
    say(f"store compile seconds (sentinel total over "
        f"{int(_total(final, 'xla.recompiles'))} traced programs, cache "
        f"loads included): {summary['store_compile_s']} (set-up observation)")
    for labels, v in _series(final, "ivf.pruned_dim_fraction"):
        say(f"ivf.pruned_dim_fraction (region {labels['region']}): {v:.4f}")
    client.close()
    return pass1


# ------------------------------------------------------------ kernel phase
def kernel_phase_child() -> int:
    """Runs in its own process once the store is gone: Mosaic-compile every
    Pallas kernel `auto` can select on a TPU at 768-d and the smoke's
    bucket width, through the index classes that route to them, and
    compare each with its XLA arm on the same inputs. Prints one JSON
    object as its last line."""
    from dingo_tpu.common.config import (
        FLAGS,
        enable_compile_cache,
        pallas_interpret,
        require_device,
    )

    rows, seed = int(sys.argv[2]), int(sys.argv[3])
    global _tag
    enable_compile_cache()
    device = require_device()
    if device["platform"] != "tpu":
        _tag = f"  [platform: {device['platform']} — rehearsal, not a chip result]"
    out = {"device": device, "kernels": {}}
    interpret = pallas_interpret()
    if device["platform"] == "tpu" and interpret:
        raise SystemExit("Pallas wrappers chose interpret mode on a TPU")
    out["interpret"] = interpret

    import jax
    import numpy as np

    from dingo_tpu.index.base import IndexParameter, IndexType
    from dingo_tpu.index.factory import new_index
    from dingo_tpu.index.ivf_layout import list_cap
    from dingo_tpu.obs.sentinel import SENTINEL

    rng = np.random.default_rng(seed)
    cap = list_cap(rows, NLIST)      # bucket width the served phase scanned
    nlist = 16
    n = nlist * cap * 3 // 4         # mean list 0.75 cap -> bucket width cap
    n_flat = 16_384                   # 8 row blocks of 2048
    centers = rng.standard_normal((64, DIM), dtype=np.float32)
    x = centers[rng.integers(0, 64, max(n, n_flat))]
    x += 0.35 * rng.standard_normal(x.shape, dtype=np.float32)
    q = x[rng.choice(len(x), BATCH, replace=False)] + 0.05 * (
        rng.standard_normal((BATCH, DIM), dtype=np.float32))

    def search_with(flags, idx, refresh=None, nq=BATCH, **kw):
        """One search of the first `nq` queries with tri-state `flags`
        forced (then back to 'auto'); `refresh` runs under the flags
        first. -> (ids, distances, secs)"""
        for f, v in flags.items():
            FLAGS.set(f, v)
        try:
            if refresh:
                refresh()
            t0 = time.monotonic()
            res = idx.search(q[:nq], TOPK, **kw)
            secs = time.monotonic() - t0
        finally:
            for f in flags:
                FLAGS.set(f, "auto")
        return ([np.asarray(r.ids) for r in res],
                [np.asarray(r.distances) for r in res], secs)

    def compare(name, kernel, idx, flags, ref, tol=1e-3, **kw):
        """Search with `flags` forced; require that sentinel kernel
        `kernel` ran, and that ids and distances match `ref` (the XLA
        arm's answer) to `tol`, relative to the row's largest distance."""
        calls0 = SENTINEL.state().get(kernel, {}).get("calls", 0)
        ids, dists, secs = search_with(flags, idx, **kw)
        if SENTINEL.state().get(kernel, {}).get("calls", 0) == calls0:
            raise SmokeFailure(f"{name}: sentinel {kernel} did not run")
        worst = 0.0
        for row, (gi, gd, ri, rd) in enumerate(zip(ids, dists, *ref[:2])):
            if len(gd) != len(rd):
                raise SmokeFailure(f"{name}: row {row} lengths differ")
            scale = max(1.0, float(np.abs(rd).max()))
            err = float(np.abs(np.sort(gd) - np.sort(rd)).max()) / scale
            worst = max(worst, err)
            if err > tol:
                raise SmokeFailure(
                    f"{name}: row {row} distances differ by {err:.2e} "
                    f"(relative, limit {tol:.0e}) from the XLA arm")
            # ids must agree except where the k-th place is a tie
            edge = float(np.sort(rd)[-1])
            for vid, d in list(zip(gi, gd)) + list(zip(ri, rd)):
                if (vid not in ri or vid not in gi) \
                        and abs(float(d) - edge) > tol * scale:
                    raise SmokeFailure(
                        f"{name}: row {row} id sets differ: {gi} vs {ri}")
        out["kernels"][name] = {
            "sentinel": kernel, "interpret": interpret,
            "first_call_s": round(secs, 2), "max_rel_err": worst,
            "tol": tol}
        say(f"kernel {name}: sentinel {kernel} interpret={interpret} "
            f"matches its XLA arm (max rel err {worst:.1e}, limit "
            f"{tol:.0e}); first call incl. compile {secs:.1f}s")

    FLAGS.set("vector_blocked_layout", True)   # the TPU arm of 'auto'
    fused, ivf_on = "use_pallas_fused_search", "use_pallas_ivf_search"
    # fp32 arms pin HIGHEST precision on both sides and agree to ~1e-6.
    # The sq8 tier multiplies in bf16 (ops/sq.py): on the chip the XLA
    # arm's batched bf16 einsum and the kernel's per-dim-block MXU dots
    # round differently — 1.9e-3 observed at 768-d (4e-6 in CPU interpret
    # mode) — so its limit is the tier's own resolution, not fp32's.
    for precision, tol in (("fp32", 1e-3), ("sq8", 1e-2)):
        flat = new_index(1, IndexParameter(
            index_type=IndexType.FLAT, dimension=DIM, precision=precision))
        flat.upsert(np.arange(n_flat, dtype=np.int64), x[:n_flat])
        ref = search_with({fused: False}, flat)
        compare(f"pruned_fused_topk[{precision}]",
                "ops.pallas.pruned_fused_topk", flat,
                {fused: True, "ivf_prune_scan": True}, ref, tol=tol)
        if precision == "fp32":
            compare("fused_topk", "ops.pallas.fused_topk", flat,
                    {fused: True, "ivf_prune_scan": False}, ref)
        del flat

        ivf = new_index(2, IndexParameter(
            index_type=IndexType.IVF_FLAT, dimension=DIM, ncentroids=nlist,
            precision=precision))
        ivf.upsert(np.arange(n, dtype=np.int64), x[:n])
        ivf.train()
        ref = search_with({ivf_on: False}, ivf, nprobe=nlist)
        # the scan's loop order comes from the batch (ops/pallas_ivf
        # scan_arm): all BATCH queries take the batch-major kernel, four
        # take the query-major ones. The kernels are traced inside the
        # request's program, so the sentinel counts them when a compare's
        # shapes are new, as every one's here are. compact() rebuilds the
        # bucket view, which is where the pruning metadata is built or
        # dropped for the flags then in force
        compare(f"ivf_batch_topk[{precision}]",
                "ops.pallas.ivf_batch_topk", ivf,
                {ivf_on: True, "ivf_prune_scan": True}, ref, tol=tol,
                refresh=ivf.compact, nprobe=nlist)
        ref4 = [r[:4] for r in ref[:2]]
        compare(f"ivf_pruned_topk[{precision}]",
                "ops.pallas.ivf_pruned_topk", ivf,
                {ivf_on: True, "ivf_prune_scan": True}, ref4, tol=tol,
                nq=4, nprobe=nlist)
        out["bucket_shape"] = [int(v) for v in ivf._buckets.shape]
        if precision == "fp32":
            compare("ivf_list_topk", "ops.pallas.ivf_list_topk", ivf,
                    {ivf_on: True, "ivf_prune_scan": False}, ref4,
                    refresh=ivf.compact, nq=4, nprobe=nlist)
        del ivf

    pq = new_index(3, IndexParameter(
        index_type=IndexType.IVF_PQ, dimension=DIM, ncentroids=nlist,
        nsubvector=96))
    n_pq = max(n, 4096)               # PQ codebooks need >= 256 rows
    pq.upsert(np.arange(n_pq, dtype=np.int64), x[:n_pq])
    pq.train()
    # rerank factor 1: no exact rerank behind the ADC scores, so the
    # kernel's own output is compared; 64: the shortlist of 640 that
    # ivfpq768's configuration reranks
    for factor in (1, 64):
        FLAGS.set("ivfpq_rerank_factor", factor)
        ref = search_with({ivf_on: False}, pq, nprobe=nlist)
        compare(f"ivf_pq_adc_scores[m=96,factor={factor}]",
                "ops.pallas.pq_adc", pq, {ivf_on: True}, ref, nprobe=nlist)
    # the ADC scan is traced inside the request's one program
    # (index.ivfpq.search); which arm served is counter ivfpq.scan_arm,
    # as ivf.scan_arm says it for IVF_FLAT in the served phase
    from dingo_tpu.common.metrics import METRICS

    out["ivfpq_scan_arms"] = {
        labels["arm"]: int(v)
        for labels, v in _series(METRICS.dump(), "ivfpq.scan_arm")}
    say(f"IVF_PQ scan arms that served the compare (arm: searches): "
        f"{out['ivfpq_scan_arms']}")

    out["peak_bytes_in_use"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices())
    print(json.dumps(out), flush=True)
    return 0


def kernel_phase(args, out_dir: str, child_env: dict, summary: dict):
    log = os.path.join(out_dir, "kernels.log")
    t0 = time.monotonic()
    with open(log, "w") as err:
        p = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--kernel-child",
             str(args.rows), str(args.seed)],
            env=child_env, cwd=HERE, stdout=subprocess.PIPE, stderr=err,
            text=True)
    _children.append(("kernels", p, log))
    last = ""
    with p.stdout:
        for line in p.stdout:
            line = line.rstrip("\n")
            if line.startswith("{"):
                last = line
            else:
                print(line, flush=True)
    rc = p.wait()
    _children.clear()
    if rc != 0:
        raise SmokeFailure(
            f"kernel phase exited with code {rc}; its last output:\n"
            f"{_log_tail(log)}")
    got = json.loads(last)
    summary["kernel_phase_s"] = round(time.monotonic() - t0, 1)
    summary["kernels"] = got["kernels"]
    summary["kernel_bucket_shape"] = got["bucket_shape"]
    summary["ivfpq_scan_arms"] = got["ivfpq_scan_arms"]
    summary["kernel_device"] = got["device"]
    summary["kernel_first_calls_s"] = round(
        sum(k["first_call_s"] for k in got["kernels"].values()), 1)
    say(f"kernel phase: {len(got['kernels'])} Pallas kernels at dim={DIM}, "
        f"buckets {got['bucket_shape']} in {summary['kernel_phase_s']}s, "
        f"first calls (compile included) "
        f"{summary['kernel_first_calls_s']}s (set-up observation)")


# -------------------------------------------------------------------- main
def _cache_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


def main() -> int:
    global _tag
    if len(sys.argv) >= 2 and sys.argv[1] == "--kernel-child":
        return kernel_phase_child()
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rows", type=int, default=None,
                   help=f"rows to load (default {DEFAULT_ROWS}; the spec is "
                        f"{SPEC_ROWS}; never below {MIN_ROWS} on the chip)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4 = start the store with use_mesh_sharded_ivf on "
                        "a 4-device mesh")
    p.add_argument("--out", default=os.path.join(HERE, "chiprun_out", "smoke"),
                   help="output directory (children's logs, the store's "
                        "data dir, summary.json, answers.json)")
    p.add_argument("--compare", default="",
                   help="answers.json of an earlier run (the 1-chip run "
                        "when this one is --chips 4) to compare ids with")
    args = p.parse_args()

    wanted = os.environ.get("JAX_PLATFORMS", "")
    rehearsal = wanted.strip().lower() == "cpu"
    if rehearsal:
        if args.rows is None:
            print("JAX_PLATFORMS=cpu: no accelerator for this smoke. A CPU "
                  "rehearsal needs an explicit --rows, and is never a pass.",
                  file=sys.stderr)
            return 2
        _tag = "  [platform: cpu — rehearsal, not a chip result]"
    elif args.rows is not None and args.rows < MIN_ROWS:
        print(f"--rows {args.rows} is below the {MIN_ROWS} floor",
              file=sys.stderr)
        return 2
    if args.rows is None:
        args.rows = DEFAULT_ROWS

    try:
        from dingo_tpu.common.config import compile_cache_dir
    except ImportError as e:
        print(f"chip_smoke.py needs the dingo_tpu package next to it: {e}",
              file=sys.stderr)
        return 2

    # the children get the environment this process was given; from here
    # on this process itself cannot initialise a jax backend by accident
    child_env = dict(os.environ)
    child_env["PYTHONPATH"] = HERE + os.pathsep + child_env.get(
        "PYTHONPATH", "")
    os.environ["JAX_PLATFORMS"] = "none_the_smoke_parent_is_off_jax"

    out_dir = os.path.abspath(args.out)
    shutil.rmtree(os.path.join(out_dir, "data"), ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    cache = compile_cache_dir()
    summary = {
        "rehearsal": rehearsal,
        "config": {"index": "IVF_FLAT", "dim": DIM, "metric": "L2",
                   "nlist": NLIST, "batch": BATCH, "k": TOPK,
                   "nprobe": NPROBE, "rows": args.rows, "seed": args.seed,
                   "chips": args.chips, "engine": "wal", "replication": 1},
        "reduced": ({} if args.rows >= SPEC_ROWS else {"rows": {
            "from": SPEC_ROWS, "to": args.rows, "why": REDUCED_WHY}}),
        "compile_cache": cache,
        "compile_cache_entries_before": _cache_entries(cache),
    }
    say(f"chip_smoke: IVF_FLAT dim={DIM} L2 nlist={NLIST} batch={BATCH} "
        f"k={TOPK} nprobe={NPROBE} rows={args.rows} seed={args.seed} "
        f"chips={args.chips}; reduced: {summary['reduced'] or 'nothing'}")
    say(f"compile cache: {cache} "
        f"({summary['compile_cache_entries_before']} entries before)")

    def on_alarm(signum, frame):
        raise SmokeFailure(f"deadline: {DEADLINE_S}s passed")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(DEADLINE_S)
    t_start = time.monotonic()
    try:
        answers = served_phase(args, out_dir, child_env, summary)
        stopped = _stop_children()
        say(f"children stopped on SIGTERM: {stopped}")
        if any(rc is None for _, rc in stopped):
            raise SmokeFailure(f"a child ignored SIGTERM: {stopped}")
        kernel_phase(args, out_dir, child_env, summary)
    except SmokeFailure as e:
        print(f"SMOKE FAILED: {e}{_tag}", flush=True)
        return 1
    finally:
        signal.alarm(0)
        _stop_children()
        # the store's data (engine WAL + raft log: every row twice) stays
        # out of what the chip tool copies back
        shutil.rmtree(os.path.join(out_dir, "data"), ignore_errors=True)
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge._backends:
            print("SMOKE FAILED: the parent initialised a jax backend",
                  flush=True)
            return 1
    summary["compile_cache_entries_after"] = _cache_entries(cache)
    say(f"compile cache: {summary['compile_cache_entries_after']} entries "
        f"after")
    with open(os.path.join(out_dir, "answers.json"), "w") as f:
        json.dump({"config": summary["config"], "ids": answers}, f)
    if args.compare:
        with open(args.compare) as f:
            other = json.load(f)
        same = sum(a == b for a, b in zip(answers, other["ids"]))
        overlap = sum(len(set(a) & set(b)) for a, b in
                      zip(answers, other["ids"])) / (len(answers) * TOPK)
        summary["compare"] = {
            "with": other["config"], "identical_rows": same,
            "rows": len(answers), "id_overlap": round(overlap, 4)}
        say(f"answers vs {args.compare}: {same}/{len(answers)} rows "
            f"identical, id overlap {overlap:.4f}")
    summary["total_s"] = round(time.monotonic() - t_start, 1)
    summary["claim"] = None
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary), flush=True)
    device = summary["kernel_device"]
    if rehearsal:
        print(json.dumps({"ok": False, "rehearsal": True,
                          "device": device}), flush=True)
        return 3
    if device["platform"] != "tpu" or device != summary["store_device"]:
        print(f"SMOKE FAILED: devices disagree: store "
              f"{summary['store_device']}, kernel phase {device}",
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
