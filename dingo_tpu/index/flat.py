"""TpuFlat: exact brute-force index (reference VectorIndexFlat,
src/vector/vector_index_flat.{h,cc} — faiss::IndexFlatL2/IP inside
IndexIDMap2) and TpuBinaryFlat (faiss::IndexBinaryFlat equivalent).

One jit'd program does the whole search: [b, capacity] score matrix on the
MXU + masked top-k. Query batches are padded to power-of-two buckets and
capacity grows by doubling, so the compile cache stays small and steady-state
searches hit cached executables.
"""

from __future__ import annotations

import json
import os
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dingo_tpu.index.base import (
    FilterSpec,
    IndexParameter,
    IndexType,
    InvalidParameter,
    NotSupported,
    SearchResult,
    VectorIndex,
    resolve_precision,
    strip_invalid,
)
from dingo_tpu.index.rerank_cache import DeviceRerankCache
from dingo_tpu.index.slot_store import SlotStore, SqSlotStore, _next_pow2
from dingo_tpu.ops.distance import (
    Metric,
    device_wait_begin,
    np_normalize,
    score_matrix,
    scores_to_distances,
)
from dingo_tpu.ops.topk import begin_host_fetch, topk_scores
from dingo_tpu.obs.quality import QUALITY
from dingo_tpu.obs.sentinel import sentinel_jit
from dingo_tpu.trace import TRACER


@sentinel_jit("index.flat.search", static_argnames=("k", "metric", "nbits"))
def _flat_search_kernel(vecs, sqnorm, mask, queries, k, metric, nbits):
    """Whole-index scan + masked top-k; returns distances and SLOT indices
    (host translates slots -> 64-bit external ids, see slot_store.py)."""
    scores = score_matrix(
        queries,
        vecs,
        metric,
        x_sqnorm=sqnorm,
        x_is_normalized=(metric is Metric.COSINE),
        nbits=nbits,
    )
    vals, slots = topk_scores(scores, k, valid=mask)
    return scores_to_distances(vals, metric), slots


@sentinel_jit("index.flat.search_sq", static_argnames=("k", "metric"))
def _sq_flat_search_kernel(codes, vmin, scale, sqnorm, mask, queries, k,
                           metric):
    """SQ8 whole-index scan: decode-on-the-fly bf16 compute over uint8
    codes, fp32 accumulate (ops/sq.py), then the same masked top-k."""
    from dingo_tpu.ops.sq import sq_score_matrix

    scores = sq_score_matrix(
        queries, codes, vmin, scale, metric, x_sqnorm=sqnorm
    )
    vals, slots = topk_scores(scores, k, valid=mask)
    return scores_to_distances(vals, metric), slots


def _new_tier_store(precision: str, dim: int, parameter: IndexParameter,
                    capacity: int = 0):
    """SlotStore for a precision tier: fp32/bf16 are dtype choices on the
    float store; sq8 swaps in the quantizing store."""
    kw = {"capacity": capacity} if capacity else {}
    if precision == "sq8":
        return SqSlotStore(dim, **kw)
    dtype = jnp.bfloat16 if precision == "bf16" \
        else jnp.dtype(parameter.dtype)
    return SlotStore(dim, dtype, **kw)


def integrity_mutation(fn):
    """Bracket an index write path for the state-integrity plane: bumps
    the ledger's pending/mutation counters BEFORE any device state can
    mutate and releases the pending bracket when the method exits (even
    on error). While the bracket is open a concurrent scrub classifies
    as raced (device may be ahead of the ledger) and the heartbeat
    withholds the digest vector (the applied-index tag may be pending).
    No-op while the index is untracked."""
    import functools

    @functools.wraps(fn)
    def wrapped(self, *args, **kwargs):
        self._integrity_begin()
        try:
            return fn(self, *args, **kwargs)
        finally:
            self._integrity_end()
    return wrapped


def _resolve_train_cap(derived: int) -> int:
    """Effective train-sample row cap: the shared conf cap
    (train.sample_rows) meets the caller's derived cap (e.g.
    max_points_per_centroid * nlist). 0 from conf = full corpus — an
    explicit opt-in that lifts the derived cap too (ISSUE 18b: chunked
    device Lloyd makes full-corpus training one compiled scan, the Faiss
    derive-from-corpus stance instead of a fixed host-sample ceiling).
    Returns 0 for uncapped."""
    from dingo_tpu.common.config import train_sample_rows

    conf = train_sample_rows()
    if conf == 0:
        return 0
    if derived <= 0:
        return conf
    return min(conf, derived)


def _pad_batch(q: np.ndarray) -> np.ndarray:
    b = q.shape[0]
    bb = _next_pow2(max(1, b))
    if bb != b:
        q = np.concatenate([q, np.zeros((bb - b,) + q.shape[1:], q.dtype)])
    return q


class _SlotStoreIndex(VectorIndex):
    """Shared machinery for indexes whose whole search is one flat-scan
    kernel over a SlotStore (float flat + binary flat)."""

    store: SlotStore
    _kernel_metric: Metric
    _kernel_nbits: int
    #: precision tier ("fp32"/"bf16"/"sq8"); binary indexes stay "fp32"
    _precision: str = "fp32"
    #: bounded device row cache for exact rerank of quantized shortlists
    _rerank_cache = None

    # -- precision tier / rerank plumbing ---------------------------------
    def _init_precision(self, parameter: IndexParameter,
                        tier: Optional[str] = None) -> None:
        """Resolve the tier and (for quantized tiers) attach the rerank
        cache. Call AFTER self.store exists — the cache shares its lock.
        Pass `tier` to pin an already-resolved tier (reload paths must not
        re-consult the mutable conf default mid-life)."""
        from dingo_tpu.common.config import FLAGS

        self._precision = tier or resolve_precision(parameter)
        self._rerank_cache = None
        if self._precision in ("bf16", "sq8"):
            rows = int(FLAGS.get("rerank_cache_rows"))
            if rows > 0:
                self._rerank_cache = DeviceRerankCache(
                    self.dimension,
                    rows,
                    dtype=jnp.dtype(str(FLAGS.get("rerank_cache_dtype"))),
                    device_lock=self.store.device_lock,
                )

    def _offer_rerank(self, slots, vectors) -> None:
        if self._rerank_cache is not None:
            self._rerank_cache.offer(slots, vectors)

    def _invalidate_rerank(self, slots) -> None:
        if self._rerank_cache is not None:
            self._rerank_cache.invalidate(slots[slots >= 0])

    def _rerank_shortlist(self, topk: int):
        """k' to over-fetch for the rerank stage, or None when the stage
        is off (fp32 tier, no cache, empty cache, or factor <= 1). The
        SLO tuner can override the conf factor per region (obs/tuner.py),
        riding the same ladder values."""
        cache = self._rerank_cache
        if cache is None or not len(cache):
            return None
        from dingo_tpu.common.config import FLAGS

        factor = self.tuned(
            "rerank_factor", int(FLAGS.get("quantized_rerank_factor"))
        )
        if factor <= 1:
            return None
        return topk * factor

    def _dispatch_rerank(self, qpad, dists, slots, topk: int):
        """Exact rerank of the quantized shortlist against the device row
        cache; caller holds store.device_lock (cache arrays are donated by
        its write programs under the same lock)."""
        from dingo_tpu.ops.rerank import cached_rerank_device

        cache = self._rerank_cache
        return cached_rerank_device(
            cache.vecs,
            cache.sqnorm,
            cache.device_map(self.store.capacity),
            dists,
            slots,
            qpad,
            k=topk,
            metric=self.metric,
        )

    # -- train sampling (device-resident, ISSUE 18b) -----------------------
    def _train_rows_device(self, derived_cap: int = 0):
        """Live stored rows for implicit training, as a DEVICE f32 array:
        samples slot INDICES host-side (cheap ints, seeded by index id so
        retrains are reproducible) and gathers the rows on device via
        store.rows_device — the corpus never materializes on the host the
        way the old to_host() path did. `derived_cap` is the caller's own
        ceiling (0 = none); conf train.sample_rows=0 lifts both."""
        live = np.flatnonzero(self.store.ids_by_slot >= 0)
        cap = _resolve_train_cap(derived_cap)
        if cap and len(live) > cap:
            sel = np.random.default_rng(self.id).choice(
                len(live), cap, replace=False
            )
            live = np.sort(live[sel])   # ascending gather, stable order
        return self.store.rows_device(live)

    # -- state-integrity ledger hooks (obs/integrity.py) -------------------
    def _integrity_begin(self) -> None:
        """Called BEFORE any device state mutates in a write path (the
        integrity_mutation decorator): bumps the ledger's pending +
        mutation counters so a scrub overlapping the device-written-but-
        not-yet-folded window classifies as raced instead of phantom
        corruption. No-op while untracked."""
        from dingo_tpu.obs.integrity import INTEGRITY

        INTEGRITY.note_mutation_begin(self)

    def _integrity_end(self) -> None:
        from dingo_tpu.obs.integrity import INTEGRITY

        INTEGRITY.note_mutation_end(self)

    def _integrity_write(self, ids: np.ndarray, vectors: np.ndarray) -> None:
        """Fold a write batch into the region's incremental state digests:
        'rows' always (canonical stored bytes — codes for sq8), 'blocked'
        when the store maintains the dimension-blocked mirror. O(batch)
        host hashing; zero device work; no-op while the index is
        untracked (integrity.enabled off AND no ledger — an existing
        ledger keeps folding through a flag toggle)."""
        from dingo_tpu.obs.integrity import INTEGRITY

        if len(ids) == 0 or not INTEGRITY.tracking(self):
            return
        stored = self.store.canonical_rows(vectors)
        ids = np.asarray(ids, np.int64)
        INTEGRITY.note_write(self, "rows", ids, stored)
        if getattr(self.store, "vecs_blk", None) is not None:
            # the blocked mirror holds the same values per slot (the
            # transform is a per-row reshape), digested under its own tag
            # so the scrub can tell WHICH copy rotted
            INTEGRITY.note_write(self, "blocked", ids, stored)

    def _integrity_delete(self, ids: np.ndarray) -> None:
        from dingo_tpu.obs.integrity import INTEGRITY

        INTEGRITY.note_delete(self, np.asarray(ids, np.int64))

    def _integrity_on_restore(self, meta: dict) -> None:
        """Recompute digests from the restored state and verify them
        against the snapshot's persisted vector (raises
        SnapshotCorruption; the manager falls back to an engine rebuild).

        A precision-tier flip across the snapshot (fp32 <-> bf16 share
        the f32-on-disk row format and legitimately load across tiers,
        incl. legacy pre-tier snapshots with no precision key) re-casts
        every stored byte, so digest comparison is undefined — the
        ledger still rebuilds from the restored state, verification is
        skipped, and the next scrub covers it from there."""
        from dingo_tpu.obs.integrity import INTEGRITY

        integ = meta.get("integrity")
        if meta.get("precision") != self._precision:
            integ = None
        INTEGRITY.verify_restore(self, integ)

    def _count_search(self) -> None:
        from dingo_tpu.common.metrics import METRICS

        METRICS.counter(
            "vector.search_by_precision",
            region_id=self.id,
            labels={"precision": self._precision},
        ).add(1)

    def _note_prune_stats(self, stats_h) -> None:
        """Fold a pruned-scan stats block ([b, 4] host array: scanned
        pairs, total pairs, full scans, candidates — see
        ops/pallas_ivf._ivf_pruned_kernel) into the metrics plane. Called
        from resolve() so the hot path never synchronizes for it."""
        from dingo_tpu.common.metrics import METRICS

        sums = np.asarray(stats_h, np.float64).sum(axis=0)
        scanned, total, full, cand = (float(x) for x in sums[:4])
        if total > 0:
            METRICS.gauge(
                "ivf.pruned_dim_fraction", region_id=self.id
            ).set(max(0.0, 1.0 - scanned / total))
        METRICS.counter("ivf.pruned_candidates", region_id=self.id).add(
            int(max(0.0, cand - full))
        )

    # subclasses set these
    def _prep_vectors(self, vectors: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def _prep_queries(self, queries: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    # -- mutation ----------------------------------------------------------
    def add(self, ids: np.ndarray, vectors: np.ndarray) -> None:
        ids = np.asarray(ids, np.int64)
        uniq, counts = np.unique(ids, return_counts=True)
        if (counts > 1).any():
            raise InvalidParameter(
                f"duplicate ids within batch: {uniq[counts > 1][:5].tolist()}"
            )
        dup = [int(i) for i in ids if int(i) in self.store]
        if dup:
            raise InvalidParameter(f"duplicate ids {dup[:5]} (use upsert)")
        self.upsert(ids, vectors)

    @integrity_mutation
    def upsert(self, ids: np.ndarray, vectors: np.ndarray) -> None:
        vectors = self._prep_vectors(vectors)
        if len(ids) != len(vectors):
            raise InvalidParameter("ids/vectors length mismatch")
        slots = self.store.put(np.asarray(ids, np.int64), vectors)
        self._offer_rerank(slots, vectors)
        # quality plane: quantized tiers keep an fp32 ground-truth mirror
        # fed the PRE-quantization rows (no-op while sampling is off)
        QUALITY.observe_write(self, np.asarray(ids, np.int64), vectors)
        self._integrity_write(ids, vectors)
        self.write_count_since_save += len(ids)

    @integrity_mutation
    def delete(self, ids: np.ndarray) -> None:
        ids = np.asarray(ids, np.int64)
        slots = self.store.remove_slots(ids)
        removed = int((slots >= 0).sum())
        self._invalidate_rerank(slots)
        QUALITY.observe_delete(self, ids)
        self._integrity_delete(ids)
        self.write_count_since_save += removed

    # -- search ------------------------------------------------------------
    def search(
        self,
        queries: np.ndarray,
        topk: int,
        filter_spec: Optional[FilterSpec] = None,
    ) -> List[SearchResult]:
        return self.search_async(queries, topk, filter_spec)()

    def search_async(
        self,
        queries: np.ndarray,
        topk: int,
        filter_spec: Optional[FilterSpec] = None,
        staged=None,
    ) -> Callable[[], List[SearchResult]]:
        """Dispatch the search and return a thunk materializing results.

        Premise: the device->host hop dominates wall time (its cost and
        the kernel's are not measured on the current machine, ROADMAP C9);
        callers with concurrent requests (service layer, bench) dispatch
        many searches and resolve later, pipelining the device. Slots freed while a search is in flight park
        in limbo (slot_store.py) so resolve never misattributes results.

        ``staged`` (common/pipeline.StagedBatch) carries a pre-padded
        device upload from the serving pipeline's staging ring; it is
        claimed only when its identity check proves it was built from
        THESE queries (``_prep_queries`` rebinding — binary bit-unpack,
        dtype cast — makes the claim fail and the local pad run instead).

        One-sync contract: resolve() performs exactly ONE
        ``jax.device_get`` on the whole fetch tuple (dists, slots, and
        the prune-stats block when present) — dingolint's resolve-sync
        checker enforces this across index families."""
        from dingo_tpu.obs.heat import HEAT, heat_enabled

        store = self.store
        # index.dispatch: entry to kernels enqueued (prep, pad and H2D,
        # mask capture, enqueue); NOOP for an unsampled request
        with TRACER.start_child("index.dispatch"):
            queries = self._prep_queries(queries)
            b = queries.shape[0]
            qpad = staged.take(queries) if staged is not None else None
            if qpad is None:
                qpad = jnp.asarray(_pad_batch(queries))
            # lease BEFORE dispatch: kernel-produced slots must stay limbo-
            # parked (not reassigned) until resolve translates them
            lease = store.begin_search()
            self._count_search()
            try:
                # asking for the lock to holding it (timed when sampled)
                with TRACER.start_child("index.lock_wait"):
                    store.device_lock.acquire()
                try:
                    # mask capture AND dispatch under the device lock: a
                    # concurrent donated write or growth would invalidate
                    # the vecs reference / change the capacity mid-dispatch
                    if filter_spec is None or filter_spec.is_empty():
                        mask = store.device_mask()
                    else:
                        mask = jnp.asarray(
                            filter_spec.slot_mask(store.ids_by_slot)
                        )
                    kprime = self._rerank_shortlist(int(topk))
                    dists, slots, stats = self._run_search_kernel(
                        qpad, mask, kprime or int(topk)
                    )
                    if kprime is not None:
                        # exact rerank of the quantized shortlist, still
                        # under the lock (cache arrays share it) and still
                        # async
                        dists, slots = self._dispatch_rerank(
                            qpad, dists, slots, int(topk)
                        )
                finally:
                    store.device_lock.release()
            except Exception:
                lease.release()
                raise
            # Start the D2H copy as soon as the kernel finishes — ONE group
            # covering the whole reply (stats included): the fetch round
            # trip then overlaps across in-flight searches instead of
            # serializing at resolve time.
            fetch = begin_host_fetch(dists, slots, stats)
            heat_on = heat_enabled()
            if heat_on:
                HEAT.register_layout(self.id, "slot", self._heat_layout)
        # the device wait of a sampled request: from here (kernels
        # enqueued, lock released) to the fetch's return in resolve();
        # never a sync of its own (ops/distance.device_wait_begin)
        wait = device_wait_begin(
            "flat_scan" if kprime is None else "rerank")

        def resolve() -> List[SearchResult]:
            try:
                fetched = jax.device_get(fetch)
                wait.end()
                # index.resolve: the host work after the fetch
                with TRACER.start_child("index.resolve"):
                    dists_h, slots_h = fetched[0], fetched[1]
                    if stats is not None:
                        self._note_prune_stats(fetched[2][:b])
                    if heat_on:
                        # result slots -> slot-block heat units, from the
                        # array this resolve ALREADY fetched (no new sync;
                        # -1 padding filtered on the heat worker)
                        HEAT.observe(self.id, "slot", slots_h[:b])
                    ids = store.ids_of_slots(slots_h[:b])
                    dists_h = self._convert_distances(dists_h)
                    # head-sampled shadow scoring (async lane; noop at
                    # rate 0); filtered searches carry their spec so the
                    # ground truth is restricted to the same candidate set
                    QUALITY.observe_search(
                        self, queries, topk, ids, dists_h[:b],
                        bucket="flat", filter_spec=filter_spec,
                    )
                    return [strip_invalid(i, d)
                            for i, d in zip(ids, dists_h[:b])]
            finally:
                lease.release()

        return resolve

    def _convert_distances(self, dists: np.ndarray) -> np.ndarray:
        """Kernel-score -> wire-distance hook (identity for float metrics;
        binary hamming converts from the cached-pm1 IP score)."""
        return dists

    def _heat_layout(self) -> dict:
        """Heat-plane layout provider: FLAT heat units are fixed
        SLOT_BLOCK slot ranges, priced at this tier's bytes/row (heat
        worker thread)."""
        from dingo_tpu.obs.heat import SLOT_BLOCK, TIER_BYTES

        tier = getattr(self, "_precision", "fp32")
        return {
            "rows_per_unit": SLOT_BLOCK,
            "row_bytes": self.dimension * TIER_BYTES.get(tier, 4.0),
            "tier": tier,
            "dim": self.dimension,
        }

    def _run_search_kernel(self, qpad, mask, k):
        """Kernel crossover for the whole-store scan; returns (dists,
        slots, prune_stats_or_None). Three arms per tier:

          * pruned Pallas streaming kernel — fused crossover fired AND the
            store maintains the dimension-blocked mirror (vecs_blk):
            partial distances per dim block, early candidate pruning, no
            [b, capacity] HBM score matrix;
          * plain fused Pallas kernel — crossover fired, no blocked mirror;
          * XLA scan + masked top-k otherwise.
        """
        from dingo_tpu.common.config import pallas_fused_enabled
        from dingo_tpu.ops.distance import metric_ascending

        store = self.store
        fused_on = (
            pallas_fused_enabled(store.capacity)
            and self._kernel_metric in (Metric.L2, Metric.INNER_PRODUCT)
        )
        pruned_on = fused_on and store.vecs_blk is not None
        if pruned_on:
            from dingo_tpu.common.config import prune_scan_enabled

            pruned_on = prune_scan_enabled()
        if self._precision == "sq8":
            if store.sq_params is None:
                # empty untrained store: nothing valid to scan; identity
                # codec keeps the kernel well-defined WITHOUT installing
                # params (the first real write must still train them)
                vmin = jnp.zeros((self.dimension,), jnp.float32)
                scale = jnp.ones((self.dimension,), jnp.float32)
            elif pruned_on:
                from dingo_tpu.ops.pallas_topk import pruned_fused_search

                vals, slots, stats = pruned_fused_search(
                    qpad, store.vecs_blk, store.bsq_blk, store.sqnorm,
                    mask, k,
                    ascending=metric_ascending(self._kernel_metric),
                    sq_vmin=store.sq_vmin_d, sq_scale=store.sq_scale_d,
                )
                return (
                    scores_to_distances(vals, self._kernel_metric),
                    slots, stats,
                )
            else:
                vmin = store.sq_vmin_d
                scale = store.sq_scale_d
            dists, slots = _sq_flat_search_kernel(
                store.vecs,
                vmin,
                scale,
                store.sqnorm,
                mask,
                qpad,
                k=k,
                metric=self._kernel_metric,
            )
            return dists, slots, None
        # float stores only (f32/bf16 — the kernels promote in VMEM):
        # TpuBinaryFlat reaches here with an int8 ±1 store and mixed
        # int dot under Mosaic is unvalidated; keep it on XLA.
        if fused_on and store.vecs.dtype in (jnp.float32, jnp.bfloat16):
            if pruned_on:
                from dingo_tpu.ops.pallas_topk import pruned_fused_search

                vals, slots, stats = pruned_fused_search(
                    qpad, store.vecs_blk, store.bsq_blk, store.sqnorm,
                    mask, k,
                    ascending=metric_ascending(self._kernel_metric),
                )
                return (
                    scores_to_distances(vals, self._kernel_metric),
                    slots, stats,
                )
            from dingo_tpu.ops.pallas_topk import fused_search

            vals, slots = fused_search(
                qpad, store.vecs, store.sqnorm,
                mask, k, ascending=metric_ascending(self._kernel_metric),
            )
            return (
                scores_to_distances(vals, self._kernel_metric), slots, None
            )
        dists, slots = _flat_search_kernel(
            store.vecs,
            store.sqnorm,
            mask,
            qpad,
            k=k,
            metric=self._kernel_metric,
            nbits=self._kernel_nbits,
        )
        return dists, slots, None

    # -- lifecycle ---------------------------------------------------------
    def get_count(self) -> int:
        return len(self.store)

    def get_memory_size(self) -> int:
        return self.store.memory_size()

    def _save_meta(self) -> dict:
        from dingo_tpu.obs.integrity import INTEGRITY

        meta = {
            "index_type": self.index_type.value,
            "dimension": self.dimension,
            "metric": self.metric.value,
            "apply_log_id": self.apply_log_id,
            "count": self.get_count(),
            "precision": self._precision,
            # scan-layout metadata: informational (rows persist FLAT; the
            # blocked mirror is a runtime arrangement rebuilt at load time
            # from conf vector.blocked_layout), recorded so operators can
            # tell which layout produced a snapshot's bench numbers
            "blocked_layout": bool(
                getattr(self.store, "vecs_blk", None) is not None
            ),
            "dim_block": int(getattr(self.store, "dim_block", 0) or 0),
        }
        # state-integrity digest vector (obs/integrity.py): restore
        # recomputes from the loaded state and refuses to serve a
        # mismatch. Only persistable artifacts ride (the blocked mirror
        # is rebuilt from conf at load; the live scrub covers it)
        integ = INTEGRITY.snapshot_artifacts(self)
        if integ:
            meta["integrity"] = integ
        return meta

    def _check_meta(self, meta: dict) -> None:
        if meta["dimension"] != self.dimension:
            raise InvalidParameter(
                f"snapshot dimension {meta['dimension']} != {self.dimension}"
            )
        if meta["metric"] != self.metric.value:
            raise InvalidParameter(
                f"snapshot metric {meta['metric']} != {self.metric.value}"
            )
        snap_p = meta.get("precision")
        if snap_p is not None and snap_p != self._precision:
            # fp32<->bf16 snapshots share the f32-on-disk row format, so a
            # tier flip (conf default change) loads fine — rows re-cast
            # into the new store. sq8 is a different CONTAINER (codes +
            # codec params), so crossing it is a hard error. Pre-tier
            # snapshots have no key and load under any tier.
            if "sq8" in (snap_p, self._precision):
                raise InvalidParameter(
                    f"snapshot precision {snap_p} != {self._precision}"
                )

    def need_to_save(self, last_save_log_behind: int) -> bool:
        """Reference wrapper policy (vector_index.h:497-500): save when the
        accumulated write count or raft-log lag crosses thresholds."""
        return (
            self.write_count_since_save >= 10000
            or last_save_log_behind >= 10000000
        )


class TpuFlat(_SlotStoreIndex):
    """Exact search; also used internally as IVF_PQ's pre-train stage
    (reference hybrid contract vector_index_ivf_pq.h:113-115) and as the
    brute-force engine behind VectorReader's scan path."""

    def __init__(self, index_id: int, parameter: IndexParameter):
        super().__init__(index_id, parameter)
        if parameter.dimension <= 0:
            raise InvalidParameter(f"dimension {parameter.dimension}")
        precision = resolve_precision(parameter)
        if precision == "sq8" and parameter.metric is Metric.HAMMING:
            raise InvalidParameter("sq8 tier needs a float metric")
        self.store = _new_tier_store(
            precision, parameter.dimension, parameter
        )
        self._init_precision(parameter)
        self._kernel_metric = parameter.metric
        self._kernel_nbits = 0

    def train(self, vectors: Optional[np.ndarray] = None) -> None:
        """FLAT needs no geometric training, but the sq8 tier can install
        its per-dim min/max codec from an explicit train set BEFORE ingest
        (otherwise the first write batch trains it — faiss's
        train-once-clip-later convention). need_train() stays False so the
        manager never blocks on this."""
        if self._precision == "sq8" and vectors is not None:
            self.store.maybe_train(self._prep_vectors(vectors))

    def _prep_vectors(self, vectors: np.ndarray) -> np.ndarray:
        vectors = np.asarray(vectors, np.float32)
        if vectors.ndim != 2 or vectors.shape[1] != self.dimension:
            raise InvalidParameter(
                f"vector dim {vectors.shape} != {self.dimension}"
            )
        if self.metric is Metric.COSINE:
            # Store normalized; search then runs plain IP on the MXU
            # (reference normalizes for cosine, vector_index_utils.h:183).
            # Host-side normalize: the jnp round-trip here synchronized
            # the device on every write batch (dingolint host-sync).
            vectors = np_normalize(vectors)
        return vectors

    def _prep_queries(self, queries: np.ndarray) -> np.ndarray:
        queries = np.asarray(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None, :]
        if queries.shape[1] != self.dimension:
            raise InvalidParameter(
                f"query dim {queries.shape[1]} != {self.dimension}"
            )
        return queries

    def save(self, path: str) -> None:
        os.makedirs(path, exist_ok=True)
        if self._precision == "sq8" and self.store.sq_params is not None:
            # codes + codec params persist verbatim (1 byte/dim on disk,
            # bit-exact restore — the SQ analog of PQ codebooks riding
            # ivf_pq.npz); a decoded save would re-encode on load and
            # silently double the quantization error
            snap = self.store.codes_to_host()
            np.savez(
                os.path.join(path, "flat.npz"),
                ids=snap["ids"],
                codes=snap["codes"],
                sq_vmin=self.store.sq_params.vmin,
                sq_scale=self.store.sq_params.scale,
            )
        else:
            snap = self.store.to_host()
            np.savez(
                os.path.join(path, "flat.npz"),
                ids=snap["ids"],
                # f32 on disk: numpy's savez can't serialize ml_dtypes
                # bfloat16, and widening loses nothing
                vectors=np.asarray(snap["vectors"], np.float32),
            )
        with open(os.path.join(path, "meta.json"), "w") as f:
            json.dump(self._save_meta(), f)

    def load(self, path: str) -> None:
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        self._check_meta(meta)
        data = np.load(os.path.join(path, "flat.npz"))
        self.store = _new_tier_store(
            self._precision, self.dimension, self.parameter,
            capacity=max(len(data["ids"]), 1),
        )
        # fresh rerank cache sharing the NEW store's lock; rows refill as
        # post-restore writes arrive
        self._init_precision(self.parameter, tier=self._precision)
        if "codes" in data.files:
            from dingo_tpu.ops.sq import SqParams

            self.store.set_params(SqParams(
                np.asarray(data["sq_vmin"], np.float32),
                np.asarray(data["sq_scale"], np.float32),
            ))
            if len(data["ids"]):
                self.store.put_codes(
                    np.asarray(data["ids"], np.int64),
                    np.asarray(data["codes"], np.uint8),
                )
        elif len(data["ids"]):
            self.store.put(np.asarray(data["ids"], np.int64),
                           data["vectors"])
        self.apply_log_id = meta["apply_log_id"]
        self.write_count_since_save = 0
        self._integrity_on_restore(meta)


class BinaryPm1Mixin:
    """Shared bit-packed <-> ±1 codec for binary indexes (TpuBinaryFlat,
    TpuBinaryIvfFlat). dimension is in BITS; wire rows are dimension//8
    uint8. Unpacking happens ONCE at write time into a ±1 int8 store so
    every search is an int8 MXU matmul —
    hamming(a, b) = (nbits - <pm(a), pm(b)>) / 2."""

    dimension: int
    nbytes: int

    def _unpack_pm1(self, packed: np.ndarray) -> np.ndarray:
        bits = np.unpackbits(packed, axis=1, bitorder="little")
        bits = bits[:, : self.dimension]
        return (bits.astype(np.int8) * 2 - 1)

    def _repack(self, pm1: np.ndarray) -> np.ndarray:
        return np.packbits(pm1 > 0, axis=1, bitorder="little")

    def _convert_distances(self, dists: np.ndarray) -> np.ndarray:
        # kernel returned IP of ±1 vectors (descending); hamming ascending
        return (self.dimension - dists) * 0.5

    def _prep_vectors(self, vectors: np.ndarray) -> np.ndarray:
        vectors = np.asarray(vectors, np.uint8)
        if vectors.ndim != 2 or vectors.shape[1] != self.nbytes:
            raise InvalidParameter(f"binary vector shape {vectors.shape}")
        return self._unpack_pm1(vectors)

    def _prep_queries(self, queries: np.ndarray) -> np.ndarray:
        queries = np.asarray(queries, np.uint8)
        if queries.ndim == 1:
            queries = queries[None, :]
        if queries.shape[1] != self.nbytes:
            raise InvalidParameter(f"binary query shape {queries.shape}")
        return self._unpack_pm1(queries).astype(np.float32)


class TpuBinaryFlat(BinaryPm1Mixin, _SlotStoreIndex):
    """Binary (uint8 bit-packed) exact hamming search — the reference's
    faiss::IndexBinaryFlat variant (vector_index_flat.h binary template
    arm); codec shared via BinaryPm1Mixin."""

    def __init__(self, index_id: int, parameter: IndexParameter):
        super().__init__(index_id, parameter)
        if parameter.dimension <= 0 or parameter.dimension % 8:
            raise InvalidParameter("binary dimension must be multiple of 8")
        self.nbytes = parameter.dimension // 8
        self.store = SlotStore(parameter.dimension, jnp.int8)
        self._kernel_metric = Metric.INNER_PRODUCT
        self._kernel_nbits = 0

    def save(self, path: str) -> None:
        os.makedirs(path, exist_ok=True)
        snap = self.store.to_host()
        np.savez(
            os.path.join(path, "binary_flat.npz"),
            ids=snap["ids"],
            vectors=self._repack(snap["vectors"]),
        )
        with open(os.path.join(path, "meta.json"), "w") as f:
            json.dump(self._save_meta(), f)

    def load(self, path: str) -> None:
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        self._check_meta(meta)
        data = np.load(os.path.join(path, "binary_flat.npz"))
        self.store = SlotStore(self.dimension, jnp.int8)
        if len(data["ids"]):
            self.store.put(
                np.asarray(data["ids"], np.int64),
                self._unpack_pm1(np.asarray(data["vectors"], np.uint8)),
            )
        self.apply_log_id = meta["apply_log_id"]
        self.write_count_since_save = 0
        self._integrity_on_restore(meta)


class TpuBruteforce(VectorIndex):
    """Reference VectorIndexBruteforce (vector_index_bruteforce.cc:111):
    holds no data; Search returns EVECTOR_NOT_SUPPORT so VectorReader takes
    the scan+temp-flat path. Kept for index-type parity."""

    def __init__(self, index_id: int, parameter: IndexParameter):
        super().__init__(index_id, parameter)

    def add(self, ids, vectors):  # noqa: D102
        pass

    def upsert(self, ids, vectors):  # noqa: D102
        pass

    def delete(self, ids):  # noqa: D102
        pass

    def search(self, queries, topk, filter_spec=None):
        raise NotSupported("BRUTEFORCE index has no in-memory search")

    def save(self, path):
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, "meta.json"), "w") as f:
            json.dump({"index_type": self.index_type.value}, f)

    def load(self, path):
        pass

    def get_count(self) -> int:
        return 0

    def get_memory_size(self) -> int:
        return 0
