"""Plain reference of a filtered vector search, for the tests: the exact
top-k over the rows whose scalars satisfy every predicate, squared L2 in
float64, no index. Imports nothing of dingo_tpu.

A predicate is {field, op, value} with op one of eq ne lt le gt ge in; a
row that lacks the field never matches (the store's contract).
"""

from __future__ import annotations

import numpy as np

OPS = {"eq": np.equal, "ne": np.not_equal, "lt": np.less,
       "le": np.less_equal, "gt": np.greater, "ge": np.greater_equal,
       "in": np.isin}


def matching(columns: dict, predicates, n: int) -> np.ndarray:
    """bool[n]: rows whose columns ({field: int64[n]}, a masked array where
    rows lack the field) satisfy every predicate."""
    keep = np.ones(n, bool)
    for p in predicates:
        col = columns.get(p["field"])
        if col is None:
            return np.zeros(n, bool)
        keep &= OPS[p["op"]](np.ma.getdata(col), p["value"])
        keep &= ~np.ma.getmaskarray(col)
    return keep


def filtered_topk(x: np.ndarray, ids: np.ndarray, columns: dict, queries,
                  predicates, k: int):
    """Per query, (ids, squared L2 distances) of the exact top-k among the
    matching rows, nearest first; fewer than k where fewer match."""
    keep = matching(columns, predicates, len(ids))
    xs = np.asarray(x, np.float64)[keep]
    kept = np.asarray(ids)[keep]
    out = []
    for q in np.asarray(queries, np.float64):
        d = ((xs - q) ** 2).sum(axis=1)
        order = np.argsort(d, kind="stable")[:k]
        out.append((kept[order], d[order]))
    return out


def distances(x: np.ndarray, ids: np.ndarray, query, served_ids):
    """float64 squared L2 from `query` to the rows of `served_ids`."""
    pos = {int(i): j for j, i in enumerate(ids)}
    rows = np.asarray(x, np.float64)[[pos[int(i)] for i in served_ids]]
    return ((rows - np.asarray(query, np.float64)) ** 2).sum(axis=1)
