"""Squared Euclidean distance, as the wire carries it for METRIC_TYPE_L2:
smaller is nearer, replies ascend. One file per metric: `reference.py` finds
`distances/<configuration's metric>.py` by name."""

import numpy as np

METRIC_TYPE = "METRIC_TYPE_L2"      # what the region has to be created with
ASCENDING = True                    # order of a reply's distances


def norms(x) -> np.ndarray:
    """What `rank32` wants beside the rows: ||x||^2 per row, float32."""
    return np.einsum("ij,ij->i", x, x).astype(np.float32)


def rank32(queries, xs, xs_norms) -> np.ndarray:
    """[nq, n] float32, smaller is nearer: the expansion ||q||^2 - 2 q.x +
    ||x||^2 a served kernel forms. Here it is also the wire's value."""
    qsq = (queries ** 2).sum(1)[:, None]
    return qsq - 2.0 * (queries @ xs.T) + xs_norms[None, :]


def served64(x, queries, ids) -> np.ndarray:
    """float64 sum((q - x[id])**2) for ids [nq, k], ids all valid."""
    out = np.empty(ids.shape, np.float64)
    for lo in range(0, len(queries), 512):
        rows = x[ids[lo:lo + 512]].astype(np.float64)
        diff = rows - queries[lo:lo + 512, None, :].astype(np.float64)
        out[lo:lo + 512] = np.einsum("qkd,qkd->qk", diff, diff)
    return out


def scale(x, queries, ids) -> np.ndarray:
    """What an error of a served distance is measured against:
    ||q||^2 + ||x[id]||^2, the size of the terms a kernel adds up."""
    xsq = np.einsum("ij,ij->i", x, x, dtype=np.float64)
    return (queries.astype(np.float64) ** 2).sum(1)[:, None] + xsq[ids]
