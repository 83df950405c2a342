"""Device-resident exact rerank of quantized/approximate shortlists.

The host rerank (`ivf_pq._exact_rerank_host`) pays a per-candidate host
fancy-index + H2D upload at RESOLVE time — the right call when the full
rows only exist in host RAM (host_vectors mode), and the wrong one when
the rows (or a cached subset) are already resident in HBM: the gather is
then one device `take`, the whole rerank dispatches in the same stream as
the scan kernel, and search_async keeps pipelining instead of
synchronizing on a host round-trip.

Two kernels, both in the WIRE distance convention (L2 ascending, IP/cos
descending) so they drop in right after any scan kernel:

  exact_rerank_device   — rows for EVERY candidate are on device (fp32 or
                          bf16 SlotStore; IVF_PQ's non-host store). ADC /
                          quantized scores are discarded and recomputed
                          exactly.
  cached_rerank_device  — only a bounded row cache is resident
                          (index/rerank_cache.py). Candidates present in
                          the cache get exact scores; the rest keep their
                          quantized score, so a partial cache can only
                          IMPROVE the ranking, never lose a candidate.
  sq_rerank_device      — the sq8 tier's exact-for-the-tier rerank:
                          candidates gather as uint8 codes, decode to the
                          bf16 surrogate in-kernel, and score with f32
                          accumulation. Ends the HNSW sq8 tier's
                          search; chain
                          cached_rerank_device after it to upgrade cached
                          rows to true f32-exact scores.

One-sync epilogue contract (serving pipeline): every device rerank here
CHAINS onto the scan in the same stream and its outputs join the reply's
single ``copy_to_host_async`` group (ops/topk.begin_host_fetch) — a
family's resolve() then performs exactly one ``jax.device_get`` for
rerank + stats + top-k together. The host rerank above is the one
adjudicated exception (two syncs are inherent to a host gather);
dingolint's resolve-sync checker enforces the rest.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from dingo_tpu.obs.sentinel import sentinel_jit

from dingo_tpu.ops.distance import (
    Metric,
    metric_ascending,
    scores_to_distances,
    squared_norms,
)


def _scores_from_rows(rows, c_sq, queries, metric):
    """THE shared 'larger is better' metric math for per-candidate
    scoring: every rerank kernel here AND the beam walk (ops/beam.py)
    score through this one function, because the HNSW tier's
    byte-identical host/device final-ordering guarantee holds only while
    the L2/cosine/IP formulas (and the cosine epsilon) stay bit-equal
    across paths.

    rows [b, k', d] arrive ALREADY in the compute dtype — f32 for exact
    scoring, the bf16 surrogate for quantized tiers (the query pairs
    down to match); c_sq [b, k'] are the cached norms of exactly those
    rows (unused for IP — XLA drops the dead gather)."""
    qd = queries.astype(jnp.float32)
    dots = jnp.einsum(
        "bd,bkd->bk",
        qd.astype(rows.dtype),
        rows,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    if metric is Metric.L2:
        return -(squared_norms(qd)[:, None] - 2.0 * dots + c_sq)
    if metric is Metric.COSINE:
        return dots * jax.lax.rsqrt(jnp.maximum(c_sq, 1e-30))
    return dots


def _exact_candidate_scores(vecs, sqnorm, queries, rows, metric):
    """Exact 'larger is better' scores [b, k'] for candidate row indices
    [b, k'] into vecs (callers pre-clamp negatives to 0); rows widen to
    f32 so bf16 caches still rerank with f32 multiplies."""
    cand = jnp.take(vecs, rows, axis=0).astype(jnp.float32)  # [b, k', d]
    c_sq = jnp.take(sqnorm, rows, axis=0)                    # [b, k']
    return _scores_from_rows(cand, c_sq, queries, metric)


def _topk_epilogue(scores, cand_slots, k, metric):
    """Shared tail of both rerank kernels: mask padding, top-k over the
    shortlist, -1 the empty winners, pad out to k, convert to the wire
    distance convention."""
    scores = jnp.where(cand_slots >= 0, scores, jnp.float32(-jnp.inf))
    kk = min(k, int(cand_slots.shape[1]))
    vals, pos = jax.lax.top_k(scores, kk)
    slots = jnp.take_along_axis(cand_slots, pos, axis=1)
    slots = jnp.where(jnp.isneginf(vals), -1, slots)
    if kk < k:
        vals = jnp.pad(vals, ((0, 0), (0, k - kk)),
                       constant_values=float("-inf"))
        slots = jnp.pad(slots, ((0, 0), (0, k - kk)), constant_values=-1)
    return scores_to_distances(vals, metric), slots


@sentinel_jit("ops.rerank.exact", static_argnames=("k", "metric"))
def exact_rerank_device(
    vecs, sqnorm, queries, cand_slots, k, metric
):
    """Exact top-k over the candidate slots, rows gathered ON DEVICE.

    vecs/sqnorm  — the full store arrays [capacity, d] / [capacity]
    cand_slots   — [b, k'] int32 shortlist (-1 pad)
    Returns (wire distances [b, k], slots [b, k]); same contract as
    `_exact_rerank_host`, minus the host gather."""
    safe = jnp.where(cand_slots >= 0, cand_slots, 0)
    scores = _exact_candidate_scores(vecs, sqnorm, queries, safe, metric)
    return _topk_epilogue(scores, cand_slots, k, metric)


@sentinel_jit("ops.rerank.sq", static_argnames=("k", "metric"))
def sq_rerank_device(
    codes, vmin, scale, sqnorm, queries, cand_slots, k, metric
):
    """Top-k over candidate slots whose device rows are SQ8 CODES.

    codes   — [capacity, d] uint8 (SqSlotStore.vecs)
    sqnorm  — [capacity] f32 norms of the DECODED surrogate rows (the
              SqSlotStore convention), so L2/cosine stay self-consistent
              with the values actually scored.
    Same (wire distances [b, k], slots [b, k]) contract as
    exact_rerank_device; exact with respect to the decoded surrogate —
    the best ordering the tier can produce without f32 rows."""
    from dingo_tpu.ops.sq import sq_decode_device

    safe = jnp.where(cand_slots >= 0, cand_slots, 0)
    rows = sq_decode_device(jnp.take(codes, safe, axis=0), vmin, scale)
    c_sq = jnp.take(sqnorm, safe, axis=0)
    scores = _scores_from_rows(rows, c_sq, queries, metric)
    return _topk_epilogue(scores, cand_slots, k, metric)


@sentinel_jit("ops.rerank.cached", static_argnames=("k", "metric"))
def cached_rerank_device(
    cache_vecs, cache_sqnorm, cache_map,
    cand_dists, cand_slots, queries, k, metric,
):
    """Rerank against a BOUNDED device row cache with quantized-score
    fallback.

    cache_map  — [store_capacity] int32: store slot -> cache row (-1 when
                 the row is not cached); maintained host-side and uploaded
                 lazily (index/rerank_cache.py), so this whole kernel
                 dispatches with zero host synchronization.
    cand_dists — [b, k'] WIRE distances from the quantized scan; kept
                 verbatim for uncached candidates.
    """
    safe_slot = jnp.where(cand_slots >= 0, cand_slots, 0)
    rows = jnp.take(cache_map, safe_slot, axis=0)       # [b, k'] (-1 miss)
    cached = (rows >= 0) & (cand_slots >= 0)
    exact = _exact_candidate_scores(
        cache_vecs, cache_sqnorm, queries, jnp.where(cached, rows, 0),
        metric,
    )
    quant = -cand_dists if metric_ascending(metric) else cand_dists
    scores = jnp.where(cached, exact, quant)
    return _topk_epilogue(scores, cand_slots, k, metric)
