"""The plain reference and the comparison that decides `correct`.

Imports nothing of the program and takes nothing it made: the corpus, the
queries and the fresh rows are regenerated from `--seed` by the generator the
configuration names under `assumed.corpus` (block by block, so that any
process can make any block), the exact top-k is numpy (a float32 shortlist
re-ranked by float64 direct distances), and every distance a reply carries is
checked against the float64 direct distance of that id's row. The metric is a
file of its own, `distances/<metric>.py`; a configuration whose metric,
precision or generator has no arm here is refused, never run as another.
A filtered request (its predicates stored beside its reply) is judged
against the exact top-k over the rows that satisfy them, by the scalar
columns `columns.py` makes from the seed as the load did.

Runs as a process of its own once the window has closed and the store is
stopped (`python benchmark/reference.py --job job.json`), on every core, and
prints one JSON object. `--control bf16` puts the reference, computed in
bfloat16, in the program's place: its answers go through the same
comparison and have to come out as not correct.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import columns  # noqa: E402

CENTERS, ROWS, QUERIES, FRESH = 1, 2, 3, 4     # rng stream tags
#: the nearest precision below the configuration's, per stated precision
CONTROLS = {"fp32": "bf16"}
Unsupported = columns.Unsupported


def load_distance(metric: str):
    path = os.path.join(HERE, "distances", metric + ".py")
    if not os.path.exists(path):
        raise Unsupported(f"metric {metric!r}: no benchmark/distances/"
                          f"{metric}.py; the reference cannot judge it")
    spec = importlib.util.spec_from_file_location("distance_" + metric, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_supported(config: dict):
    """-> the configuration's distance module; raises `Unsupported` where
    the reference would have to judge it as something it is not."""
    if config["precision"] not in CONTROLS:
        raise Unsupported(
            f"precision {config['precision']!r}: the reference and its "
            f"control know {sorted(CONTROLS)} (a `benchmark` PR adds an arm "
            "with limits read from that precision's own runs)")
    Data(0, config)
    return load_distance(config["metric"])


# -------------------------------------------------------------------- data
class Data:
    """Corpus, queries and fresh rows of one seed and configuration, by the
    parameters under the configuration's `assumed.corpus`."""

    def __init__(self, seed: int, config: dict):
        p = config["assumed"]["corpus"]
        if p["generator"] != "clustered_mixture":
            raise Unsupported(f"corpus generator {p['generator']!r}")
        self.seed, self.rows, self.dim = seed, config["rows"], config["dimension"]
        self.block_rows = int(p["block_rows"])
        self.n_clusters = max(int(p["min_clusters"]),
                              self.rows // int(p["rows_per_cluster"]))
        self.cluster_noise = float(p["cluster_noise"])
        self.query_noise = float(p["query_noise"])
        self._centers = None
        self.columns = columns.Columns(seed, config)

    @property
    def centers(self) -> np.ndarray:
        if self._centers is None:
            rng = np.random.default_rng([CENTERS, self.seed])
            self._centers = rng.standard_normal(
                (self.n_clusters, self.dim), dtype=np.float32)
        return self._centers

    @property
    def n_blocks(self) -> int:
        return -(-self.rows // self.block_rows)

    def _mixture(self, rng, n: int) -> np.ndarray:
        x = self.centers[rng.integers(0, self.n_clusters, n)]
        x = x + self.cluster_noise * rng.standard_normal(
            (n, self.dim), dtype=np.float32)
        return x.astype(np.float32)

    def block(self, b: int) -> np.ndarray:
        """Rows [b * block_rows, min(rows, (b + 1) * block_rows))."""
        n = min(self.rows, (b + 1) * self.block_rows) - b * self.block_rows
        return self._mixture(np.random.default_rng([ROWS, self.seed, b]), n)

    def corpus(self) -> np.ndarray:
        return np.concatenate([self.block(b) for b in range(self.n_blocks)])

    def query_pool(self, pool: int) -> np.ndarray:
        """`pool` queries: distinct rows of block 0 (every block is the same
        mixture) plus a little noise, as the smoke's queries are."""
        block0 = self.block(0)
        if pool > len(block0):
            raise ValueError(f"query pool {pool} > block 0's {len(block0)} rows")
        rng = np.random.default_rng([QUERIES, self.seed])
        q = block0[rng.choice(len(block0), pool, replace=False)]
        q = q + self.query_noise * rng.standard_normal(
            (pool, self.dim), dtype=np.float32)
        return q.astype(np.float32)

    def fresh_rows(self, request: int, batch: int):
        """Write request `request`: ids above the corpus and rows from the
        mixture. -> (ids[int64], vectors[float32])"""
        rng = np.random.default_rng([FRESH, self.seed, request])
        ids = self.rows + request * batch + np.arange(batch, dtype=np.int64)
        return ids, self._mixture(rng, batch)


# --------------------------------------------------------------- reference
def to_bf16(a: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), kept in float32."""
    u = np.ascontiguousarray(a, np.float32).view(np.uint32)
    r = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return r.view(np.float32)


def shortlist(dist, x, queries, width: int, chunk: int = 65_536):
    """ids [nq, width] of the `width` nearest by the metric's float32
    ranking value, and those values, unsorted."""
    norms = dist.norms(x)
    best_d = np.full((len(queries), width), np.inf, np.float32)
    best_i = np.full((len(queries), width), -1, np.int64)
    for lo in range(0, len(x), chunk):
        d = dist.rank32(queries, x[lo:lo + chunk], norms[lo:lo + chunk])
        w = min(width, d.shape[1])
        top = np.argpartition(d, w - 1, axis=1)[:, :w]
        cat_d = np.concatenate(
            [best_d, np.take_along_axis(d, top, axis=1)], axis=1)
        cat_i = np.concatenate([best_i, top + lo], axis=1)
        keep = np.argpartition(cat_d, width - 1, axis=1)[:, :width]
        best_d = np.take_along_axis(cat_d, keep, axis=1)
        best_i = np.take_along_axis(cat_i, keep, axis=1)
    return best_i, best_d


def exact_topk(dist, x, queries, k: int):
    """Exact top-k: float32 shortlist of 4k, re-ranked by the float64
    direct distance. -> (ids [nq, k], distances [nq, k] float64, nearest
    first)"""
    ids, _ = shortlist(dist, x, queries, min(4 * k, len(x)))
    d = dist.served64(x, queries, ids)
    order = np.argsort(d if dist.ASCENDING else -d, axis=1)[:, :k]
    return np.take_along_axis(ids, order, 1), np.take_along_axis(d, order, 1)


def control_topk(dist, x, queries, k: int):
    """The reference in the nearest precision below the configuration's
    fp32: inputs rounded to bfloat16, products accumulated in float32, the
    ranking value as a served kernel would form it.
    -> (ids, values float32, nearest first): what a bf16 scan would reply."""
    ids, d = shortlist(dist, to_bf16(x), to_bf16(queries), k)
    order = np.argsort(d, axis=1)
    d = np.take_along_axis(d, order, 1)
    return np.take_along_axis(ids, order, 1), d if dist.ASCENDING else -d


def where_topk(fn, dist, x, queries, k: int, group, masks):
    """`fn` (`exact_topk` or `control_topk`) over the rows each query's
    predicates let through: `group` [nq] indexes `masks` (bool over x's
    rows), -1 for a query with none. -> (ids [nq, k], -1 past the rows that
    match; values [nq, k], inf past them), nearest first."""
    ids = np.full((len(queries), k), -1, np.int64)
    vals = np.full((len(queries), k), np.inf)
    for g in np.unique(group):
        rows = np.flatnonzero(group == g)
        sub = None if g < 0 else np.flatnonzero(masks[g])
        xs = x if sub is None else x[sub]
        kk = min(k, len(xs))
        if kk:
            i, v = fn(dist, xs, queries[rows], kk)
            ids[rows, :kk] = i if sub is None else sub[i]
            vals[rows, :kk] = v
    return ids, vals


# -------------------------------------------------------------- comparison
def compare_replies(dist, x, queries, served_ids, served_d, k: int,
                    truth_for: np.ndarray = None, allowed=None):
    """`served_ids`/`served_d` [nq, k] (ids -1 where a reply was short)
    against the reference. Every reply: shape, order and each distance.
    Rows listed in `truth_for` (indices): recall against the exact top-k.
    `allowed` = (group [nq], masks) where requests were filtered
    (`where_topk`): a filtered query's truth is the exact top-k over the
    rows that match, its recall is over min(k, matches), its reply may be
    short by what fewer than k matches leave, and every served id whose row
    does not match counts in `unmatched_rows`. -> dict of numbers."""
    nq = len(queries)
    out = {"compared_queries": int(nq)}
    in_range = (served_ids >= 0) & (served_ids < len(x))
    if allowed is None:
        short = int((served_ids < 0).sum())
    else:
        group, masks = allowed
        # group -1 (no predicates) takes the last entry: every row
        n_match = np.asarray([int(m.sum()) for m in masks] + [len(x)])
        need = np.minimum(k, n_match[group])
        short = int(np.maximum(
            0, (served_ids < 0).sum(axis=1) - (k - need)).sum())
    out["malformed_items"] = short + int(((served_ids >= 0) & ~in_range).sum())
    ids = np.where(in_range, served_ids, -1)
    srt = np.sort(ids, axis=1)
    dup = int(((srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] >= 0)).sum())
    out["malformed_items"] += dup
    d = np.where(in_range, served_d, 0.0).astype(np.float64)
    both = in_range[:, 1:] & in_range[:, :-1]
    step = np.diff(d, axis=1) * (1.0 if dist.ASCENDING else -1.0)
    out["unsorted_rows"] = int(((step < -1e-3) & both).any(axis=1).sum())
    safe = np.where(ids < 0, 0, ids)
    ref_d = np.where(ids >= 0, dist.served64(x, queries, safe), 0.0)
    err = np.where(ids >= 0,
                   np.abs(d - ref_d) / dist.scale(x, queries, safe), 0.0)
    out["dist_err"] = float(err.max()) if err.size else 0.0
    if allowed is not None:
        matched = np.ones(ids.shape, bool)
        for g, m in enumerate(masks):
            rows = group == g
            matched[rows] = m[safe[rows]]
        out["unmatched_rows"] = int(((ids >= 0) & ~matched).sum())
    if truth_for is not None and len(truth_for):
        tq = queries[truth_for]
        if allowed is None:
            _, td = exact_topk(dist, x, tq, k)
            kth = td[:, -1][:, None]
        else:
            _, td = where_topk(exact_topk, dist, x, tq, k, group[truth_for],
                               masks)
            want = need[truth_for]
            kth = td[np.arange(len(td)), np.maximum(want, 1) - 1][:, None]
        # a served id counts where its true distance is inside the exact
        # k-th (ties at the k-th place count for either side)
        inside = (ref_d[truth_for] <= kth + 1e-9 * np.abs(kth)) \
            if dist.ASCENDING else \
            (ref_d[truth_for] >= kth - 1e-9 * np.abs(kth))
        hit = (ids[truth_for] >= 0) & inside
        if allowed is None:
            out["recall_at_10"] = float(hit.sum(axis=1).mean() / k)
        else:
            # a query that no row matches has found all there was
            hit &= matched[truth_for]
            out["recall_at_10"] = float(np.where(
                want > 0, hit.sum(axis=1) / np.maximum(want, 1), 1.0).mean())
        out["recall_queries"] = int(len(truth_for))
    return out


def run_job(job: dict, control: str = "") -> dict:
    """job: {seed, config: the configuration's file, k, pool, replies: npz
    path, [fresh: {batch, acked_requests}], truth_rows: [...], [reply_rows:
    [...]]}. The npz holds `offsets` (start
    of each reply's queries in the pool, or -1 for a fresh-row read-back,
    then `fresh_request` names the write request whose rows were the
    queries), `batch`, `ids` [n, batch, k], `dists` [n, batch, k], and
    where requests were filtered `predicates` [n]: each request's
    predicates with their drawn values, as JSON ('' for none)."""
    k = job["k"]
    dist = check_supported(job["config"])
    data = Data(job["seed"], job["config"])
    dim = data.dim
    x = data.corpus()
    fresh = job.get("fresh")
    if fresh:
        # every acknowledged write is part of what a read must see
        extra = [data.fresh_rows(r, fresh["batch"])[1]
                 for r in range(fresh["acked_requests"])]
        if extra:
            x = np.concatenate([x] + extra)
    replies = np.load(job["replies"])
    batch = int(replies["batch"])
    keep = job.get("reply_rows")        # a sample of the replies, or all
    offsets, fresh_of = replies["offsets"], replies["fresh_request"]
    ids, dists = replies["ids"], replies["dists"]
    preds = replies["predicates"] if "predicates" in replies.files else None
    truth_rows = np.asarray(job["truth_rows"], np.int64)
    if keep is not None:
        keep = np.asarray(keep, np.int64)
        offsets, fresh_of = offsets[keep], fresh_of[keep]
        ids, dists = ids[keep], dists[keep]
        preds = None if preds is None else preds[keep]
        place = {int(r): i for i, r in enumerate(keep)}
        truth_rows = np.asarray(
            [place[int(t) // batch] * batch + int(t) % batch
             for t in truth_rows], np.int64)
    ids = ids.reshape(-1, k)
    dists = dists.reshape(-1, k)
    pool = data.query_pool(job["pool"]) if job["pool"] else None
    qs = []
    for off, fr in zip(offsets, fresh_of):
        if off >= 0:
            qs.append(pool[(off + np.arange(batch)) % len(pool)])
        else:
            qs.append(data.fresh_rows(int(fr), batch)[1])
    queries = np.concatenate(qs) if qs else np.zeros((0, dim), np.float32)
    allowed = None
    if preds is not None:
        # one mask per distinct set of predicates, over every row a read
        # must see: the corpus and the acknowledged writes
        cols = columns.concat([data.columns.corpus()] + [
            data.columns.fresh(r, fresh["batch"])
            for r in range(fresh["acked_requests"] if fresh else 0)])
        distinct = sorted(set(preds.tolist()) - {""})
        masks = [columns.matches(cols, json.loads(p)) for p in distinct]
        number = {p: g for g, p in enumerate(distinct)}
        group = np.repeat([number.get(p, -1) for p in preds.tolist()], batch)
        allowed = (group.astype(np.int64), masks)
    out = {}
    if control and control != CONTROLS[job["config"]["precision"]]:
        raise SystemExit(f"unknown control {control!r}")
    if control:
        # the control answers the truth rows only: the rest of the replies
        # stay the program's, and one failing number is enough
        cid, cd = control_topk(dist, x, queries[truth_rows], k) \
            if allowed is None else where_topk(
                control_topk, dist, x, queries[truth_rows], k,
                allowed[0][truth_rows], allowed[1])
        ids, dists = ids.copy(), dists.copy()
        ids[truth_rows], dists[truth_rows] = cid, cd
        out["control"] = control
    out.update(compare_replies(dist, x, queries, ids, dists, k, truth_rows,
                               allowed))
    if fresh:
        # read-your-writes: each read-back row is its own nearest neighbour
        own = []
        for i, (off, fr) in enumerate(zip(offsets, fresh_of)):
            if off < 0:
                want = data.fresh_rows(int(fr), batch)[0]
                own.append(ids[i * batch:(i + 1) * batch, 0] != want)
        out["unfound_rows"] = int(np.concatenate(own).sum()) if own else 0
        out["read_back_rows"] = int(sum(len(o) for o in own))
    return out


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--job", required=True)
    p.add_argument("--control", default="")
    args = p.parse_args()
    with open(args.job) as f:
        job = json.load(f)
    print(json.dumps(run_job(job, args.control)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
