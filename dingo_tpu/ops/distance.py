"""Batched distance kernels for the MXU.

TPU-native replacement for the reference's per-pair SIMD hooks
(src/simd/hook.h:23-31: fvec_L2sqr, fvec_inner_product, fvec_L1, fvec_Linf,
fvec_norm_L2sqr, fvec_L2sqr_ny, fvec_inner_products_ny, fvec_madd, ...) and
the faiss distance backends used by VectorIndexFlat / IvfFlat / IvfPq
(reference src/vector/vector_index_flat.cc, vector_index_utils.h:43-160
CalcDistanceEntry).

Design: the reference computes one scalar distance per (query, vector) pair in
an AVX loop; on TPU the whole [batch, n] distance matrix is one matmul:

    L2sqr(q, x)  = ||q||^2 - 2 q.x + ||x||^2     (one einsum + rank-1 adds)
    IP(q, x)     =  q.x
    cosine(q, x) =  q.x / (||q|| ||x||)          (normalize, then IP)
    hamming(a,b) = (nbits - pm(a).pm(b)) / 2     (pm: bits -> +/-1 floats,
                                                  so binary distance is ALSO
                                                  an MXU matmul)

All functions accept an optional precomputed ``x_sqnorm`` so indexes can cache
database norms (the reference caches nothing — faiss recomputes; caching is
free QPS on TPU).

Score convention: ``score_matrix`` returns "larger is better" scores for every
metric (negated L2) so a single top-k kernel serves all metrics;
``scores_to_distances`` converts back to the faiss/dingo wire convention
(L2: squared distance ascending; IP/cosine: similarity descending — see
reference vector_index_utils.h FillSearchResult).
"""

from __future__ import annotations

import enum
from typing import Optional

import jax
import jax.numpy as jnp

#: Matmul precision for distance contractions. On TPU the default matmul
#: precision is bf16 which costs recall (measured: flat recall@10 0.9875 vs
#: 1.0, PQ encode collapses); HIGHEST keeps f32 accumulation on the MXU.
#: Index configs may pass precision="default" for the big [b, n] scan when
#: the recall budget allows trading exactness for ~4x matmul throughput.
PRECISION = jax.lax.Precision.HIGHEST


class Metric(enum.Enum):
    """Mirrors pb::common::MetricType (METRIC_TYPE_L2 / _INNER_PRODUCT /
    _COSINE) plus HAMMING for the binary index family
    (reference vector_index_flat.h binary variant via faiss::IndexBinary)."""

    L2 = "l2"
    INNER_PRODUCT = "ip"
    COSINE = "cosine"
    HAMMING = "hamming"


def squared_norms(x: jax.Array) -> jax.Array:
    """||x_i||^2 per row. Replacement for fvec_norm_L2sqr (src/simd/hook.h:27)."""
    x = x.astype(jnp.float32)
    return jnp.einsum("nd,nd->n", x, x, precision=PRECISION)


def _dot(q: jax.Array, x: jax.Array, precision=None) -> jax.Array:
    """[b,d] @ [n,d]^T with f32 accumulation regardless of storage dtype.

    bf16-resident databases (the bf16 precision tier) pair the query down
    to bf16 so the contraction is a native bf16 MXU matmul instead of XLA
    materializing an f32 upcast of the whole [n, d] operand; accumulation
    stays f32 via preferred_element_type either way."""
    if x.dtype == jnp.bfloat16:
        q = q.astype(jnp.bfloat16)
    return jnp.einsum(
        "bd,nd->bn",
        q,
        x,
        preferred_element_type=jnp.float32,
        precision=PRECISION if precision is None else precision,
    )


def pairwise_l2sqr(
    q: jax.Array,
    x: jax.Array,
    x_sqnorm: Optional[jax.Array] = None,
    precision=None,
) -> jax.Array:
    """Squared L2 distance matrix [b, n]. Replaces fvec_L2sqr / fvec_L2sqr_ny
    (src/simd/hook.h:23,28); faiss METRIC_L2 convention (squared, ascending)."""
    if x_sqnorm is None:
        x_sqnorm = squared_norms(x)
    q_sqnorm = squared_norms(q)
    d = q_sqnorm[:, None] - 2.0 * _dot(q, x, precision) + x_sqnorm[None, :]
    # Guard tiny negatives from cancellation so downstream sqrt/compare is safe.
    return jnp.maximum(d, 0.0)


def pairwise_inner_product(
    q: jax.Array, x: jax.Array, precision=None
) -> jax.Array:
    """Inner-product similarity matrix [b, n] (descending = better).
    Replaces fvec_inner_product / fvec_inner_products_ny (src/simd/hook.h:24,29)."""
    return _dot(q, x, precision)


def normalize(x: jax.Array, eps: float = 1e-30) -> jax.Array:
    """Row L2-normalization (reference VectorIndexUtils normalization,
    vector_index_utils.h:183-184 — applied for COSINE metric)."""
    x32 = x.astype(jnp.float32)
    n = jnp.sqrt(jnp.maximum(squared_norms(x32), eps))
    return (x32 / n[:, None]).astype(x.dtype)


def np_normalize(x, eps: float = 1e-30):
    """Host-side counterpart of ``normalize`` with the SAME epsilon
    convention (floor on the SQUARED norm): cosine rows must normalize
    to the same values no matter which side of the H2D boundary prepped
    them — index families post-filter and parity-check each other, so
    one divergent near-zero-row convention shows up as a ranking flake.
    Pure numpy: no device round-trip on the write/search prep path."""
    import numpy as np

    x = np.ascontiguousarray(x, np.float32)
    n = np.sqrt(np.maximum((x * x).sum(axis=1, dtype=np.float32), eps))
    return np.ascontiguousarray(x / n[:, None])


def pairwise_cosine(
    q: jax.Array,
    x: jax.Array,
    x_is_normalized: bool = False,
    x_sqnorm: Optional[jax.Array] = None,
    precision=None,
) -> jax.Array:
    """Cosine similarity matrix [b, n] (descending = better)."""
    qn = normalize(q)
    if x_is_normalized:
        return _dot(qn, x, precision)
    if x_sqnorm is None:
        x_sqnorm = squared_norms(x)
    inv = jax.lax.rsqrt(jnp.maximum(x_sqnorm, 1e-30))
    return _dot(qn, x, precision) * inv[None, :]


def bits_to_pm1(packed: jax.Array, nbits: int) -> jax.Array:
    """Unpack uint8-packed bits [n, nbytes] -> +/-1 float matrix [n, nbits].

    This is the trick that moves hamming distance onto the MXU:
    hamming(a, b) = (nbits - <pm(a), pm(b)>) / 2.
    """
    n, nbytes = packed.shape
    shifts = jnp.arange(8, dtype=packed.dtype)
    bits = (packed[:, :, None] >> shifts[None, None, :]) & 1  # [n, nbytes, 8]
    bits = bits.reshape(n, nbytes * 8)[:, :nbits]
    return (bits.astype(jnp.float32) * 2.0 - 1.0)


def pairwise_hamming(
    q_packed: jax.Array, x_packed: jax.Array, nbits: int, precision=None
) -> jax.Array:
    """Hamming distance matrix [b, n] (ascending = better) over uint8-packed
    bit vectors. Binary-index replacement for faiss::IndexBinaryFlat search."""
    qp = bits_to_pm1(q_packed, nbits)
    xp = bits_to_pm1(x_packed, nbits)
    return (nbits - _dot(qp, xp, precision)) * 0.5


def metric_ascending(metric: Metric) -> bool:
    """True when smaller distance means better (L2, hamming)."""
    return metric in (Metric.L2, Metric.HAMMING)


def score_matrix(
    q: jax.Array,
    x: jax.Array,
    metric: Metric,
    x_sqnorm: Optional[jax.Array] = None,
    x_is_normalized: bool = False,
    nbits: int = 0,
    precision=None,
) -> jax.Array:
    """Unified 'larger is better' score matrix for all metrics, so one top-k
    kernel (ops/topk.py) serves the whole index family."""
    if metric is Metric.L2:
        return -pairwise_l2sqr(q, x, x_sqnorm, precision)
    if metric is Metric.INNER_PRODUCT:
        return pairwise_inner_product(q, x, precision)
    if metric is Metric.COSINE:
        return pairwise_cosine(q, x, x_is_normalized, x_sqnorm, precision)
    if metric is Metric.HAMMING:
        return -pairwise_hamming(q, x, nbits, precision)
    raise ValueError(f"unknown metric {metric}")


def scores_to_distances(scores: jax.Array, metric: Metric) -> jax.Array:
    """Convert internal scores back to the faiss/dingo wire convention
    (pb::index::VectorWithDistance.distance)."""
    if metric_ascending(metric):
        return -scores
    return scores


def device_wait_begin(name: str):
    """Mark the point where a request's kernels have been enqueued. When
    the current trace is sampled this starts the ``ops.<name>`` span (one
    clock read) and returns it; the request's ``resolve()`` ends it right
    after the ONE ``jax.device_get`` every reply already makes, so the
    span reads "dispatch to result on the host". Nothing synchronises
    here: a sampled request makes exactly the device calls an unsampled
    one makes (an earlier version blocked until ready, under the store's
    device lock on the IVF path, and a traced store measured another
    regime). Otherwise NOOP_SPAN: one sampled-check, no clock, no
    allocation. One span per request, named for the last kernel stage
    enqueued; a dispatch with no surrounding request trace is never
    timed, so background kernels don't mint single-span root traces."""
    from dingo_tpu.trace import NOOP_SPAN, TRACER, current_span

    cur = current_span()
    if cur is None or not cur.sampled:
        return NOOP_SPAN
    return TRACER.start_child("ops." + name)
