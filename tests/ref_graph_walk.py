"""The plain reference of the graph search: a greedy beam walk over a given
adjacency and an exact top-k, in numpy, one query at a time. Nothing of
``dingo_tpu/ops`` is imported: the device walk (ops/beam.py) and the exact
rerank (ops/rerank.py) are compared with this, not with themselves.
"""

import heapq

import numpy as np


def l2_f64(q: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """Exact squared L2 distances of one query to rows, in float64."""
    diff = rows.astype(np.float64) - q.astype(np.float64)[None, :]
    return np.einsum("nd,nd->n", diff, diff)


def exact_topk(q: np.ndarray, rows: np.ndarray, k: int, valid=None):
    """(indices [k], float64 distances [k]) of the k nearest rows, nearest
    first; `valid` [n] bool masks rows out."""
    d = l2_f64(q, rows)
    if valid is not None:
        d = np.where(valid, d, np.inf)
    order = np.argsort(d, kind="stable")[:k]
    return order, d[order]


def beam_walk(adj: np.ndarray, rows: np.ndarray, q: np.ndarray, entry: int,
              ef: int, valid=None):
    """HNSW's level-0 search: best-first expansion from `entry` with a
    result list of at most `ef`, ended when the nearest unexpanded
    candidate is farther than the farthest result. `adj` [n, deg] holds
    neighbour indices, -1 padded; rows with `valid` False are routed
    around (never scored, never expanded). -> (candidate indices, the set
    of rows visited)."""
    if entry < 0:
        return np.empty(0, np.int64), set()
    ok = (lambda i: True) if valid is None else (lambda i: bool(valid[i]))
    dist = lambda i: float(l2_f64(q, rows[i:i + 1])[0])  # noqa: E731
    visited = {entry}
    d0 = dist(entry)
    cand = [(d0, entry)]                    # min-heap: nearest first
    result = [(-d0, entry)] if ok(entry) else []   # max-heap: farthest first
    while cand:
        d, node = heapq.heappop(cand)
        if len(result) >= ef and d > -result[0][0]:
            break
        for nb in adj[node]:
            nb = int(nb)
            if nb < 0 or nb in visited or not ok(nb):
                continue
            visited.add(nb)
            dn = dist(nb)
            if len(result) < ef or dn < -result[0][0]:
                heapq.heappush(cand, (dn, nb))
                heapq.heappush(result, (-dn, nb))
                if len(result) > ef:
                    heapq.heappop(result)
    return np.asarray([i for _, i in result], np.int64), visited


def search(adj, rows, q, entry, ef, k, valid=None):
    """Walk, then the exact top-k of the walk's candidates (the rerank).
    -> (indices [<=k], float64 distances)."""
    cand, visited = beam_walk(adj, rows, q, entry, ef, valid)
    if not len(cand):
        return cand, np.empty(0), visited
    order, d = exact_topk(q, rows[cand], k)
    return cand[order], d, visited
