"""k-selection kernels.

Replaces faiss's heap-based k-selection (per-query CPU heaps in
IndexFlat::search and the reference's brute-force merge of per-batch top-k
heaps, src/vector/vector_reader.cc:1873+) with lax.top_k over score rows,
plus a streaming/shard merge used both for scan-batched brute force and for
cross-device top-k reduction (per-device topk -> all-gather -> merge).

Masking contract: invalid slots (tombstones, filter-rejected ids, padding)
carry score -inf and id -1; merge and topk preserve that, so a fully-masked
row yields (distance=+inf-equivalent, id=-1) entries the host layer drops —
matching the reference's behavior of returning fewer than topN results when
the region has fewer candidates.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = float("-inf")


def topk_scores(
    scores: jax.Array,
    k: int,
    valid: Optional[jax.Array] = None,
    ids: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k per row of a 'larger is better' score matrix.

    scores: [b, n]; valid: [n] or [b, n] bool mask; ids: [n] external ids.
    Returns (scores[b,k] desc, ids[b,k]) with -1 ids on masked-out picks.
    """
    b, n = scores.shape
    if valid is not None:
        scores = jnp.where(valid, scores, NEG_INF)
    if k > n:
        pad = jnp.full((b, k - n), NEG_INF, scores.dtype)
        scores = jnp.concatenate([scores, pad], axis=1)
        if ids is not None:
            ids = jnp.concatenate([ids, jnp.full((k - n,), -1, ids.dtype)])
        n = k
    vals, idx = jax.lax.top_k(scores, k)
    out_ids = idx if ids is None else jnp.take(ids, idx, axis=0)
    out_ids = jnp.where(jnp.isneginf(vals), -1, out_ids)
    return vals, out_ids


def begin_host_fetch(*arrays):
    """Start ONE D2H copy group for a reply's whole fetch tuple.

    The one-sync epilogue contract (serving pipeline): everything a
    resolve() needs on the host — distances, slots, prune stats,
    diagnostic counters — joins a single ``copy_to_host_async`` group
    here, and resolve performs exactly one ``jax.device_get`` on the
    returned tuple. None entries are dropped (optional members like the
    prune-stats block just don't join), so the caller indexes the
    result positionally over its non-None arguments. Host-side values
    (numpy fallbacks) pass through untouched."""
    out = []
    for a in arrays:
        if a is None:
            continue
        start = getattr(a, "copy_to_host_async", None)
        if start is not None:
            start()
        out.append(a)
    return tuple(out)


def merge_topk(
    scores_a: jax.Array,
    ids_a: jax.Array,
    scores_b: jax.Array,
    ids_b: jax.Array,
    k: int,
) -> Tuple[jax.Array, jax.Array]:
    """Merge two per-row top-k result sets into one (streaming scan batches,
    reference vector_reader.cc:1873 'merge per-query topk heaps'; also the
    cross-shard reduce step in parallel/)."""
    scores = jnp.concatenate([scores_a, scores_b], axis=1)
    ids = jnp.concatenate([ids_a, ids_b], axis=1)
    vals, idx = jax.lax.top_k(scores, k)
    out_ids = jnp.take_along_axis(ids, idx, axis=1)
    out_ids = jnp.where(jnp.isneginf(vals), -1, out_ids)
    return vals, out_ids


def merge_sharded_topk(
    shard_scores: jax.Array, shard_ids: jax.Array, k: int
) -> Tuple[jax.Array, jax.Array]:
    """[s, b, k'] per-shard results -> [b, k] global results.

    Used after an all_gather of per-device top-k blocks (the TPU analog of the
    reference's client-side scatter-gather across regions, SURVEY.md §5
    'long-context' note)."""
    s, b, kk = shard_scores.shape
    flat_scores = jnp.transpose(shard_scores, (1, 0, 2)).reshape(b, s * kk)
    flat_ids = jnp.transpose(shard_ids, (1, 0, 2)).reshape(b, s * kk)
    vals, idx = jax.lax.top_k(flat_scores, k)
    out_ids = jnp.take_along_axis(flat_ids, idx, axis=1)
    out_ids = jnp.where(jnp.isneginf(vals), -1, out_ids)
    return vals, out_ids
