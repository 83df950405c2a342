"""One load-generator process: a closed-loop caller or a share of an
open-loop arrival schedule (the first one also loads the corpus in set-up).
It imports the SDK only and never touches a
JAX backend (its JAX_PLATFORMS names none), so no caller shares a GIL with
the store or with another caller.

The parent (`run.py`) speaks to it in JSON lines: commands on stdin, one
reply per command on stdout. Every time is `time.monotonic()`, which all
processes of one machine share.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np  # noqa: E402

import columns  # noqa: E402
import reference  # noqa: E402

PARTITION = 1


class Caller:
    def __init__(self, spec: dict):
        from dingo_tpu.client import DingoClient
        from dingo_tpu.server import pb

        self.spec = spec
        self.cfg = spec["config"]
        self.mix = spec["traffic"]
        self.seed = spec["seed"]
        self.index = spec["caller"]
        # `wrap_client` (selftest.py only) names a function that breaks the
        # client underneath the timed path: [file, function]
        wrap = load_wrapper(spec.get("wrap_client"), spec)
        self._plain_client = lambda: DingoClient(
            spec["coordinator"], {"s0": spec["store"]})
        self._new_client = lambda: wrap(self._plain_client())
        self._local = threading.local()
        self.data = reference.Data(self.seed, self.cfg)
        self.search_args = dict(self.mix["search_args"])
        # a filtered search: the enums by their pb names, the predicates
        # filled per request (`columns.fill`); a read-back is never filtered
        self.predicates = self.search_args.pop("predicates", None)
        self.plain_args = {a: v for a, v in self.search_args.items()
                           if a not in columns.FILTER_ARGS}
        for arg, enum in (("filter", pb.VectorFilter),
                          ("filter_type", pb.VectorFilterType)):
            if arg in self.search_args:
                self.search_args[arg] = enum.Value(self.search_args[arg])
        self.k = self.search_args["topk"]
        self.batch = self.mix["batch"]
        self.pool = None
        self.rng = np.random.default_rng([7, self.seed, self.index])
        self.next_write = 0
        self.next_search = 0
        # one tuple per request, appended whole (threads share the list):
        # (t_due, t_start, t_done, ok, region_map_s, offset, fresh_request,
        #  ids, dists, predicates as JSON or '')
        self.done = []
        self.errors = []

    @property
    def client(self):
        c = getattr(self._local, "client", None)
        if c is None:
            c = self._local.client = self._new_client()
            # the SDK asks the coordinator for the region map in every
            # search; how long that took is kept beside each request
            refresh, local = c.refresh_region_map, self._local

            def timed_refresh():
                t0 = time.monotonic()
                try:
                    return refresh()
                finally:
                    local.map_s = getattr(local, "map_s", 0.0) \
                        + time.monotonic() - t0

            c.refresh_region_map = timed_refresh
        return c

    def _take_map_s(self) -> float:
        spent, self._local.map_s = getattr(self._local, "map_s", 0.0), 0.0
        return spent

    # ------------------------------------------------------------ requests
    def _queries(self, offset: int) -> np.ndarray:
        if self.pool is None:
            self.pool = self.data.query_pool(self.mix["query_pool"])
        return self.pool[(offset + np.arange(self.batch)) % len(self.pool)]

    def _fresh(self, request: int):
        return self.data.fresh_rows(request, self.batch)

    def _arrays(self, rows):
        ids = np.full((self.batch, self.k), -1, np.int64)
        dists = np.full((self.batch, self.k), np.inf, np.float32)
        for r, row in enumerate(rows[:self.batch]):
            for c, (vid, d) in enumerate(row[:self.k]):
                ids[r, c], dists[r, c] = vid, d
        return ids, dists

    def _request_number(self) -> int:
        """Taken where the request is made, not in the thread that sends it,
        so each number's draws are the same in every run."""
        self.next_search += 1
        return self.next_search - 1

    def search(self, t_due: float, offset: int, fresh_request: int = -1,
               request: int = -1):
        q = self._queries(offset) if fresh_request < 0 \
            else self._fresh(fresh_request)[1]
        kw, kept = self.plain_args, ""
        if fresh_request < 0:
            kw = self.search_args
            if self.predicates:
                filled = columns.fill(self.predicates, self.cfg, self.seed,
                                      self.index, request)
                kw, kept = dict(kw, predicates=filled), json.dumps(filled)
        t_start = time.monotonic()
        try:
            rows = self.client.vector_search(PARTITION, q, **kw)
            t_done, ok = time.monotonic(), len(rows) == len(q)
        except Exception as e:  # noqa: BLE001 — a failed request is counted
            t_done, ok, rows = time.monotonic(), False, []
            if len(self.errors) < 5:
                self.errors.append(f"{type(e).__name__}: {e}"[:300])
        self.done.append((t_due if t_due is not None else t_start, t_start,
                          t_done, ok, self._take_map_s(), offset,
                          fresh_request, *self._arrays(rows), kept))

    def insert(self):
        request = self.next_write
        self.next_write += 1
        ids, vectors = self._fresh(request)
        kw = {"scalars": columns.as_rows(self.data.columns.fresh(
            request, self.batch))} if self.data.columns else {}
        t_start = time.monotonic()
        try:
            self.client.vector_add(PARTITION, ids.tolist(), vectors, **kw)
            t_done, ok = time.monotonic(), True
        except Exception as e:  # noqa: BLE001
            t_done, ok = time.monotonic(), False
            if len(self.errors) < 5:
                self.errors.append(f"{type(e).__name__}: {e}"[:300])
        self.done.append((t_start, t_start, t_done, ok, self._take_map_s(),
                          -1, request, None, None, ""))
        return ok

    def one(self, t_due=None):
        if self.mix["operation"] == "vector_add":
            return self.insert()
        return self.search(
            t_due, int(self.rng.integers(0, self.mix["query_pool"])),
            request=self._request_number())

    # ------------------------------------------------------------ commands
    def load(self) -> dict:
        """Set-up: every block of the corpus through vector_add, with its
        scalar columns where the configuration states some (a client of its
        own: a test's wrapper sits under the timed path only)."""
        t0 = time.monotonic()
        client = self._plain_client()
        for b in range(self.data.n_blocks):
            x = self.data.block(b)
            lo = b * self.data.block_rows
            kw = {"scalars": columns.as_rows(self.data.columns.block(b))} \
                if self.data.columns else {}
            client.vector_add(PARTITION, range(lo, lo + len(x)), x, **kw)
        client.close()
        return {"seconds": time.monotonic() - t0}

    def warm(self, n: int) -> dict:
        t0 = time.monotonic()
        for _ in range(n):
            self.one()
        bad = [r for r in self.done if not r[3]]
        out = {"seconds": time.monotonic() - t0, "failed": len(bad),
               "errors": self.errors, "writes": self.next_write}
        self.done.clear()
        return out

    def go(self, t_open: float, t_close: float, due) -> dict:
        """Closed loop: next request when the reply is back, none started
        after the close. Open loop (`due` offsets from t_open): each sent
        when due from a pool of threads, timed from when it was due."""
        self.done.clear()
        time.sleep(max(0.0, t_open - time.monotonic()))
        cpu0 = time.process_time()
        if due is None:
            while time.monotonic() < t_close:
                self.one()
        else:
            with ThreadPoolExecutor(self.mix["threads_per_process"]) as ex:
                offsets = self.rng.integers(
                    0, self.mix["query_pool"], len(due))
                futures = []
                for off, d in zip(offsets, due):
                    t_due = t_open + d
                    time.sleep(max(0.0, t_due - time.monotonic()))
                    futures.append(ex.submit(self.search, t_due, int(off),
                                             -1, self._request_number()))
                for f in futures:
                    f.result()
        # this process's CPU seconds (all its threads) from the open to its
        # last reply: `generator_busy_share` takes the busiest caller's
        return dict(self._save("window"),
                    cpu_s=time.process_time() - cpu0)

    def readback(self, requests) -> dict:
        """Search for the rows of acknowledged write requests, 64 at a
        time: each has to come back as its own nearest neighbour."""
        self.done.clear()
        for r in requests:
            self.search(None, -1, fresh_request=int(r))
        return self._save("readback")

    def _save(self, what: str) -> dict:
        path = os.path.join(self.spec["out"],
                            f"caller{self.index}.{what}.npz")
        rec = np.asarray([r[:5] for r in self.done], np.float64).reshape(-1, 5)
        searched = [r for r in self.done if r[7] is not None]
        filtered = {"predicates": np.asarray([r[9] for r in self.done], str)} \
            if self.predicates else {}
        np.savez(
            path, records=rec, batch=self.batch, **filtered,
            offsets=np.asarray([r[5] for r in self.done], np.int64),
            fresh_request=np.asarray([r[6] for r in self.done], np.int64),
            ids=(np.stack([r[7] for r in searched]) if searched
                 else np.zeros((0, self.batch, self.k), np.int64)),
            dists=(np.stack([r[8] for r in searched]) if searched
                   else np.zeros((0, self.batch, self.k), np.float32)))
        return {"file": path, "requests": len(rec), "errors": self.errors,
                "writes": self.next_write}


def load_wrapper(named, caller_spec):
    """-> client -> the client broken by `named`'s function, which gets the
    caller's spec beside the client (seed, configuration, traffic)."""
    if not named:
        return lambda client: client
    import importlib.util

    path, function = named
    spec = importlib.util.spec_from_file_location("client_wrapper", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return lambda client: getattr(mod, function)(client, caller_spec)


def main() -> int:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    if spec.get("cores"):
        os.sched_setaffinity(0, spec["cores"])
    caller = Caller(spec)
    print(json.dumps({"ready": True, "pid": os.getpid()}), flush=True)
    for line in sys.stdin:
        cmd = json.loads(line)
        op = cmd.pop("cmd")
        if op == "exit":
            break
        try:
            reply = getattr(caller, op)(**cmd)
        except Exception as e:  # noqa: BLE001 — reported to the parent
            reply = {"error": f"{type(e).__name__}: {e}"[:500]}
        print(json.dumps(reply), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
