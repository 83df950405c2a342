"""TpuIvfFlat: inverted-file index with TPU k-means training and
bucketed list-scan search.

Reference: VectorIndexIvfFlat (src/vector/vector_index_ivf_flat.{h,cc} —
faiss::IndexIVFFlat with a separately-held quantizer, vector_index_ivf_flat.h:
137; train-data bookkeeping :144-145; untrained search returns
EVECTOR_NOT_SUPPORT so VectorReader falls back to brute force,
vector_reader.cc:1814-1833).

TPU-first design:
  train  — on-device Lloyd k-means (ops/kmeans.py) over a sampled subset
           (max_points_per_centroid * nlist, faiss ClusteringParameters
           convention), deterministic farthest-first init.
  layout — ground truth lives in a flat SlotStore (same arrays as TpuFlat);
           a *bucketed view* [B, cap_list, d] of fixed-width spill buckets
           (ivf_layout.py) is maintained INCREMENTALLY: upserts append
           into free rows of the assigned list's tail bucket via small
           donated scatters, deletes flip the row invalid, and a deferred
           compaction (crontab / threshold-driven, see IvfViewMaintenance)
           restores the dense layout off the hot path. The full rebuild
           survives only as the compaction/restore fallback — a write
           between two searches no longer costs an O(N) host gather.
           cap_list tracks the MEAN list size; long lists spill into extra
           buckets, so HBM is bounded by ~n*d + nlist*cap_list*d
           regardless of assignment skew.
  search — [b, nlist] centroid scores -> top-nprobe coarse lists ->
           on-device expansion to virtual bucket probes -> lax.scan over
           probe ranks: gather one bucket per query per rank
           ([b, cap_list, d] dynamic gather), distance einsum, running
           top-k merge. HBM traffic per query ~ nprobe/nlist of the index
           (vs full scan) — the win IVF exists for. (A Pallas kernel that
           DMAs list tiles and skips unprobed lists is the planned upgrade.)

Semantics parity: untrained index raises NotTrained (reader brute-force
fallback contract); deletes tombstone; adds are accepted before training
(vectors buffer in the SlotStore; assignment happens at train time —
the reference buffers train data similarly).
"""

from __future__ import annotations

import json
import os
from typing import List, Optional

import jax
import jax.numpy as jnp

from dingo_tpu.obs.sentinel import sentinel_jit
import numpy as np
from jax import lax

from dingo_tpu.index.base import (
    FilterSpec,
    IndexParameter,
    InvalidParameter,
    NotTrained,
    SearchResult,
    VectorIndex,
    resolve_precision,
    strip_invalid,
)
from dingo_tpu.common.config import FLAGS
from dingo_tpu.common.metrics import METRICS
from dingo_tpu.index.flat import (
    BinaryPm1Mixin,
    _SlotStoreIndex,
    _pad_batch,
    _resolve_train_cap,
    integrity_mutation,
)
from dingo_tpu.index.ivf_layout import (
    MutableIvfView,
    expand_probes,
    shape_bucket,
)
from dingo_tpu.index.slot_store import SlotStore, _next_pow2
from dingo_tpu.trace import TRACER
from dingo_tpu.ops.distance import (
    Metric,
    np_normalize,
    score_matrix,
    scores_to_distances,
    squared_norms,
)
from dingo_tpu.ops.kmeans import (
    MAX_POINTS_PER_CENTROID,
    kmeans_assign,
    train_kmeans,
)
from dingo_tpu.ops.topk import begin_host_fetch, merge_topk, topk_scores


def coarse_probes(queries, centroids, c_sqnorm, nprobe):
    """Top-nprobe coarse lists per query: [b, nprobe] int32. Plain function
    (shard_map-safe); `_probe_lists` is the jitted wrapper."""
    # Coarse quantizer is always L2 (faiss uses the metric's quantizer, but
    # L2 on normalized data == cosine ordering; IP uses L2 quantizer too in
    # the reference's faiss config).
    d = (
        squared_norms(queries)[:, None]
        - 2.0
        * jnp.einsum(
            "bd,nd->bn",
            queries,
            centroids,
            precision=jax.lax.Precision.HIGHEST,
        )
        + c_sqnorm[None, :]
    )
    _, idx = jax.lax.top_k(-d, nprobe)
    return idx.astype(jnp.int32)


_probe_lists = sentinel_jit("index.ivf.probe_lists", coarse_probes,
                            static_argnames=("nprobe",))


def ivf_scan_scores(
    buckets, bucket_sqnorm, bucket_valid, bucket_slot, probes, queries, k,
    metric, sq_vmin=None, sq_scale=None,
):
    """Scan nprobe bucket ranks per query with a running top-k.

    buckets:     [nlist, cap_list, d]
    bucket_*:    [nlist, cap_list] (sqnorm f32 / valid bool / slot int32)
    probes:      [b, nprobe] int32
    queries:     [b, d]
    sq_*:        [d] SQ8 codec params when buckets hold uint8 codes —
                 gathered buckets decode on the fly (ops/sq.py) with fp32
                 accumulation; bucket_sqnorm then caches DECODED norms
    Returns raw SCORES (descending-better) + slots — shard_map-safe (no
    jit, no distance conversion) so the mesh-sharded IVF can merge scores
    across shards before converting; `ivf_search_program` is the single-
    device jitted program around it.
    """
    b = queries.shape[0]
    nprobe = probes.shape[1]
    neg_inf = jnp.float32(-jnp.inf)

    def body(carry, r):
        best_vals, best_slots = carry
        lists_r = jnp.take(probes, r, axis=1)        # [b] (-1 = padded rank)
        rank_ok = lists_r >= 0
        lists_c = jnp.where(rank_ok, lists_r, 0)
        data = jnp.take(buckets, lists_c, axis=0)
        if sq_vmin is None and not jnp.issubdtype(data.dtype, jnp.floating):
            # int8 stores (binary ivf): promote after the gather; float
            # stores (incl. bf16) keep their dtype — the einsum accumulates
            # in f32 via preferred_element_type either way
            data = data.astype(jnp.float32)
        sq = jnp.take(bucket_sqnorm, lists_c, axis=0)
        val = jnp.take(bucket_valid, lists_c, axis=0) & rank_ok[:, None]
        slot = jnp.take(bucket_slot, lists_c, axis=0)
        # per-query distance to its own bucket: einsum over d
        if sq_vmin is not None:
            from dingo_tpu.ops.sq import sq_bucket_scores

            scores = sq_bucket_scores(
                queries, data, sq, sq_vmin, sq_scale, metric
            )
        elif metric is Metric.L2:
            dots = jnp.einsum(
                "bd,bcd->bc", queries, data,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
            scores = -(squared_norms(queries)[:, None] - 2.0 * dots + sq)
        else:  # IP / cosine (queries pre-normalized for cosine)
            scores = jnp.einsum(
                "bd,bcd->bc", queries, data,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
        scores = jnp.where(val, scores, neg_inf)
        vals_r, idx_r = jax.lax.top_k(scores, min(k, scores.shape[1]))
        slots_r = jnp.take_along_axis(slot, idx_r, axis=1)
        slots_r = jnp.where(jnp.isneginf(vals_r), -1, slots_r)
        best_vals, best_slots = merge_topk(
            best_vals, best_slots, vals_r, slots_r, k
        )
        return (best_vals, best_slots), None

    init = (
        jnp.full((b, k), neg_inf, jnp.float32),
        jnp.full((b, k), -1, jnp.int32),
    )
    (vals, slots), _ = jax.lax.scan(body, init, jnp.arange(nprobe))
    return vals, slots


@sentinel_jit("index.ivf.search",
              static_argnames=("k", "nprobe", "metric", "pallas", "interpret",
                               "check_every", "inbucket"))
def ivf_search_program(
    qpad,            # [b, d] f32 padded queries
    centroids,       # [nlist, d]
    c_sqnorm,        # [nlist]
    probe_table,     # [nlist, max_spill] int32 bucket ids per list (-1 pad)
    valid,           # [B, cap] bool: resident bucket_valid, or a filter's
    bucket_slot,     # [B, cap] int32
    buckets,         # [B, cap, d] rows or sq8 codes
    bucket_sqnorm,   # [B, cap] f32
    bucket_bsq,      # [B, nblk, cap] f32 pruning norms, or None
    sq_vmin,         # [d] sq8 codec params, or None
    sq_scale,
    k: int,
    nprobe: int,
    metric: Metric,
    pallas: bool,
    interpret: bool = False,
    check_every: int = 1,
    inbucket: bool = True,
):
    """A request's whole device side as ONE program: coarse probe
    selection, probe expansion through the view's probe table, the scan
    and the wire-convention distances -> (distances[b, k], slots[b, k],
    probes[b, nprobe], vprobes[b, budget], aux).

    The host launches nothing else between the H2D of the queries and the
    reply's one fetch: the op-by-op `jnp` glue that used to stand here
    cost a b = 64 request more host time, under store.device_lock, than
    its kernels cost the device (PERF.md, PR 28). Statics are the request
    shape (k, nprobe), the metric and the scan family; `max_spill` is the
    probe table's width. `pallas` takes ops/pallas_ivf.ivf_probe_scan,
    which picks its loop order from the batch (aux: touched-bucket count,
    pruning stats or None); otherwise the XLA rank scan (aux None)."""
    from dingo_tpu.ops.distance import metric_ascending

    probes = coarse_probes(qpad, centroids, c_sqnorm, nprobe)
    vprobes = expand_probes(probes, probe_table, nprobe, probe_table.shape[1])
    if pallas:
        from dingo_tpu.ops.pallas_ivf import ivf_probe_scan

        vals, slots, aux = ivf_probe_scan(
            vprobes, qpad, buckets, bucket_bsq, bucket_sqnorm, valid,
            bucket_slot, sq_vmin, sq_scale, k=k,
            ascending=metric_ascending(metric), interpret=interpret,
            check_every=check_every, inbucket=inbucket,
        )
    else:
        vals, slots = ivf_scan_scores(
            buckets, bucket_sqnorm, valid, bucket_slot, vprobes, qpad, k,
            metric, sq_vmin=sq_vmin, sq_scale=sq_scale,
        )
        aux = None
    return scores_to_distances(vals, metric), slots, probes, vprobes, aux


@sentinel_jit("index.ivf.filter_mask")
def _filter_bucket_mask(slot_mask, bucket_slot):
    """Expand a [capacity] slot mask to [B, cap_list] ON DEVICE. The
    filtered path used to build (and upload) the full bucket-shaped mask
    in numpy per request; uploading the slot-level delta and expanding it
    against the resident bucket_slot map keeps the per-request H2D at
    [capacity] bools."""
    safe = jnp.where(bucket_slot >= 0, bucket_slot, 0)
    return jnp.take(slot_mask, safe, axis=0) & (bucket_slot >= 0)


#: filter-mask cache entries kept per index (distinct live filter shapes
#: per region are few: the region's base id-window plus ad-hoc id sets)
FILTER_CACHE_SIZE = 16


class IvfViewMaintenance:
    """Incremental bucketed-view lifecycle shared by TpuIvfFlat and
    TpuIvfPq: append-in-place upserts, tombstone deletes, deferred
    compaction, the filter-mask cache, and (batch, k, nprobe) shape
    bucketing. Subclasses own the bucket-shaped DATA arrays and implement
    the two hooks `_materialize_view_data` / `_scatter_view_data`.

    Counters/spans (tools/check_metrics_names.py naming contract):
      ivf.inplace_appends / ivf.tombstones / ivf.full_rebuild /
      ivf.compactions counters, ivf.tombstone_ratio gauge; spans
      ivf.append_inplace / ivf.compact / ivf.full_rebuild.
    """

    _view: Optional[MutableIvfView]
    _view_dirty: bool

    # -- hooks (owning index's data arrays) --------------------------------
    def _materialize_view_data(self, view: MutableIvfView) -> None:
        raise NotImplementedError

    def _scatter_view_data(self, upd, rows) -> None:
        raise NotImplementedError

    def _warmup_queries(self, b: int) -> np.ndarray:
        return np.ones((b, self.dimension), np.float32)

    @staticmethod
    def _note_probed(gauge, view, vprobes_h) -> None:
        """Rows in the buckets a sampled batch probed, per query (mean),
        into `gauge`: from the bucket ids its one fetch brought and the
        view's host-side fill counts, what the scan walks, beside the
        benchmark's work function's nprobe * rows / nlist."""
        vp = np.asarray(vprobes_h)
        rows = np.where(vp >= 0, view.bucket_fill[np.maximum(vp, 0)], 0)
        gauge.set(float(rows.sum(axis=1).mean()))

    # -- view lifecycle ----------------------------------------------------
    def _ensure_view(self) -> None:
        """Hot-path entry: only (re)builds when there is no usable view —
        steady-state searches find a fresh view and do nothing here."""
        if self._view is None or self._view_dirty:
            self._rebuild_view("search")

    def _rebuild_view(self, reason: str = "search") -> None:
        """Full dense rebuild (build_layout + gather). On the hot path
        this survives only as the restore fallback (first search after
        train/load, or a write batch too large to point-scatter); the
        compaction path runs it deliberately, off the serving path."""
        compacting = reason == "compact"
        name = "ivf.compact" if compacting else "ivf.full_rebuild"
        with TRACER.start_span(name) as span:
            with self.store.device_lock:
                # the WHOLE rebuild under one hold: the host snapshot
                # (assign/valid), the data gather, and the view swap. A
                # write landing mid-rebuild would otherwise be captured by
                # neither the snapshot nor the (orphaned) old view — and
                # nothing would mark the fresh view dirty.
                view = MutableIvfView.build(
                    self._assign_h, self.store.valid_h, self.nlist,
                    self.store.capacity,
                )
                self._materialize_view_data(view)
                self._view = view
                self._view_dirty = False
                self._filter_cache.clear()
            if span.sampled:
                span.set_attr("region_id", self.id)
                span.set_attr("buckets", view.nbuckets)
                span.set_attr("rows", view.live_rows)
        METRICS.counter(
            "ivf.compactions" if compacting else "ivf.full_rebuild",
            region_id=self.id,
        ).add(1)
        self._update_view_gauges()

    def _invalidate_view(self) -> None:
        with self.store.device_lock:
            # lock pairs with the filtered-search path, which iterates
            # _filter_cache under the same lock (an unlocked clear() could
            # land mid-iteration and crash the search)
            self._view_dirty = True
            self._filter_cache.clear()

    def _update_view_gauges(self) -> None:
        v = self._view
        if v is not None:
            METRICS.gauge("ivf.tombstone_ratio", region_id=self.id).set(
                v.tombstone_ratio()
            )

    # -- incremental write path --------------------------------------------
    def _view_apply_upsert(self, slots, assign, rows) -> None:
        from dingo_tpu.ops.scatter import MAX_SCATTER_BATCH

        if len(slots) > MAX_SCATTER_BATCH:
            # batch big enough to amortize a dense rebuild — defer it
            self._invalidate_view()
            return
        with TRACER.start_span("ivf.append_inplace") as span:
            # stage (host bookkeeping) + apply (donated scatters) under
            # ONE device_lock hold: a search dispatching concurrently must
            # never observe staged host state (max_spill, probe chains)
            # ahead of the device arrays it describes. self._view re-read
            # inside the hold: a concurrent compaction may have swapped it.
            with self.store.device_lock:
                view = self._view
                if view is None or self._view_dirty:
                    self._view_dirty = True   # raced with invalidation
                    return
                view.ensure_slot_capacity(self.store.capacity)
                upd = view.stage_upsert(slots, np.asarray(assign))
                if upd is None:               # no-op batch
                    return
                view.apply_device(upd)
                self._scatter_view_data(upd, rows)
            if span.sampled:
                span.set_attr("region_id", self.id)
                span.set_attr("rows", int(len(slots)))
        METRICS.counter("ivf.inplace_appends", region_id=self.id).add(
            len(upd.appended)
        )
        self._update_view_gauges()

    def _view_apply_delete(self, slots) -> None:
        with self.store.device_lock:
            view = self._view
            if view is None or self._view_dirty:
                self._view_dirty = True
                return
            upd = view.stage_delete(slots)
            if upd is None:
                return
            view.apply_device(upd)
        METRICS.counter("ivf.tombstones", region_id=self.id).add(
            len(upd.touched)
        )
        self._update_view_gauges()

    # -- compaction --------------------------------------------------------
    def need_compact(self) -> bool:
        """True when the view accumulated enough garbage (tombstones /
        spill buckets) for the dense rebuild to pay for itself, or a
        deferred full rebuild is pending that the compaction crontab can
        absorb off the hot path."""
        v = self._view
        if v is None:
            return False
        if self._view_dirty:
            return True
        return (
            v.tombstone_ratio() >= FLAGS.get("ivf_compact_tombstone_ratio")
            or v.spill_ratio() >= FLAGS.get("ivf_compact_spill_ratio")
        )

    def compact(self) -> None:
        """Rebuild the dense layout now (O(N); callers keep this OFF the
        serving path — crontab / scrub / tests)."""
        self._rebuild_view("compact")

    def maybe_compact(self) -> bool:
        if self.need_compact():
            self.compact()
            return True
        return False

    def view_stats(self) -> dict:
        out = {"built": self._view is not None, "dirty": self._view_dirty}
        if self._view is not None:
            out.update(self._view.stats())
        return out

    def _heat_layout(self) -> Optional[dict]:
        """Heat-plane layout provider: rows per IVF bucket from the host
        assignment array, priced at this tier's bytes/row. Invoked on
        the heat plane's WORKER thread (<= once per layout TTL), so the
        bincount never rides a serving thread."""
        assign = self._assign_h
        if assign is None:
            return None
        from dingo_tpu.obs.heat import TIER_BYTES

        rows = np.bincount(assign[assign >= 0].astype(np.int64),
                           minlength=self.nlist)
        return {
            "unit_rows": rows,
            "row_bytes": self.dimension * TIER_BYTES.get(
                self._precision, 4.0),
            "tier": self._precision,
            "dim": self.dimension,
        }

    # -- state-integrity: bucket-assignment artifact -----------------------
    def _integrity_assign(self, ids: np.ndarray, assign: np.ndarray) -> None:
        """Fold a write batch's coarse-list assignments into the
        'ivf_buckets' digest (the ledger tracks the assignment TRUTH; the
        scrub reads the device view's arrangement back and compares)."""
        from dingo_tpu.obs.integrity import INTEGRITY

        if len(ids) == 0 or not INTEGRITY.tracking(self):
            return
        ids = np.asarray(ids, np.int64)
        assign = np.asarray(assign, np.int32)
        placed = assign >= 0
        if placed.any():
            INTEGRITY.note_write(self, "ivf_buckets", ids[placed],
                                 assign[placed])

    def _integrity_reset_assign(self) -> None:
        """Rebuild the assignment digest from _assign_h (train/load paths
        reassign every stored row at once)."""
        from dingo_tpu.obs.integrity import INTEGRITY

        if not INTEGRITY.tracking(self):
            return
        INTEGRITY.reset_artifact(self, "ivf_buckets")
        live = np.flatnonzero(self.store.ids_by_slot >= 0)
        if len(live):
            assign = self._assign_h[live].astype(np.int32)
            self._integrity_assign(self.store.ids_by_slot[live], assign)

    # -- filter-mask cache -------------------------------------------------
    def _mask_version(self, view) -> tuple:
        """What a cached filter mask was built from: the bucketed view and
        the store's host state (slots and mirrored columns)."""
        return (view.version if view is not None else -1,
                self.store.mutation_version)

    def _prep_filter_mask(self, filter_spec: Optional[FilterSpec]):
        """Host-side filter work done OUTSIDE the device lock: fingerprint
        hashing and the O(capacity) numpy slot-mask build can cost
        milliseconds on big include sets, and must not serialize every
        concurrent search/write behind the lock. Returns (fp, version,
        (mask, share) or None); the in-lock consumer revalidates against
        the live version and rebuilds in the (rare) raced case."""
        if filter_spec is None or filter_spec.is_empty():
            return None
        with self._filter_span(filter_spec):
            fp = filter_spec.fingerprint()
            ver = self._mask_version(self._view)
            hit = self._filter_cache.get(fp)
            if hit is not None and hit[0] == ver:
                # expected cache hit; skip the build
                self._note_matched(hit[2])
                return (fp, ver, None)
            return (fp, ver, self._filter_slot_mask(filter_spec))

    def _bucket_valid_for_filter(
        self, filter_spec: Optional[FilterSpec], prep=None
    ):
        """Device validity mask for the scan kernel. Unfiltered searches
        reuse the resident bucket_valid (zero per-request H2D); filtered
        searches hit a (filter-fingerprint, mask version) cache, and a
        miss uploads only the [capacity] slot mask, expanding it on
        device (_filter_bucket_mask). Callers hold store.device_lock;
        pass `prep` from _prep_filter_mask to keep the host work outside
        the hold."""
        view = self._view
        if filter_spec is None or filter_spec.is_empty():
            return view.bucket_valid
        cur = self._mask_version(view)
        fp, ver, built = prep if prep is not None else (
            filter_spec.fingerprint(), cur, None
        )
        hit = self._filter_cache.get(fp)
        if hit is not None and hit[0] == cur:
            METRICS.counter("ivf.filter_mask_hits", region_id=self.id).add(1)
            return hit[1]
        if built is None or ver != cur:
            # raced with a write since prep (or the expected hit was
            # evicted): rebuild against the live host state
            built = self._filter_slot_mask(filter_spec)
        mask, share = built
        bmask = _filter_bucket_mask(jnp.asarray(mask), view.bucket_slot)
        if len(self._filter_cache) >= FILTER_CACHE_SIZE:
            stale = [k for k, v in self._filter_cache.items()
                     if v[0] != cur]
            for k in stale:
                del self._filter_cache[k]
            while len(self._filter_cache) >= FILTER_CACHE_SIZE:
                self._filter_cache.pop(next(iter(self._filter_cache)))
        self._filter_cache[fp] = (cur, bmask, share)
        METRICS.counter("ivf.filter_mask_misses", region_id=self.id).add(1)
        return bmask

    # -- shape bucketing + warmup ------------------------------------------
    def _shape_buckets(self, topk: int, nprobe: int):
        """(k_eff, nprobe_eff) on the {1, 1.5}x-pow2 ladder so steady-state
        serving reuses a handful of compiled programs. k_eff >= topk
        (resolve slices back); a larger nprobe only adds recall."""
        if not FLAGS.get("ivf_shape_bucketing"):
            return topk, nprobe
        return shape_bucket(topk), min(shape_bucket(nprobe), self.nlist)

    def warmup(self, batches=(1, 8, 64), topk: int = 10,
               nprobe: Optional[int] = None) -> int:
        """Pre-compile the steady-state search programs (one per
        shape-bucketed (batch, k, nprobe) triple) so first real traffic
        never pays an XLA compile. Returns the number of probe searches
        issued."""
        if not self.is_trained():
            return 0
        self._ensure_view()
        for bsz in batches:
            self.search(self._warmup_queries(int(bsz)), topk, nprobe=nprobe)
        return len(batches)


class TpuIvfFlat(IvfViewMaintenance, _SlotStoreIndex):
    #: metric the bucketed scan kernel runs with (the binary subclass scans
    #: with INNER_PRODUCT over ±1 vectors and converts to hamming after)
    _scan_metric: Metric

    def __init__(self, index_id: int, parameter: IndexParameter):
        VectorIndex.__init__(self, index_id, parameter)
        if parameter.dimension <= 0:
            raise InvalidParameter(f"dimension {parameter.dimension}")
        if parameter.ncentroids <= 0:
            raise InvalidParameter(f"ncentroids {parameter.ncentroids}")
        if parameter.metric is Metric.HAMMING and type(self) is TpuIvfFlat:
            raise InvalidParameter("use BINARY_IVF_FLAT for hamming")
        self._scan_metric = parameter.metric
        from dingo_tpu.index.flat import _new_tier_store

        self.store = _new_tier_store(
            resolve_precision(parameter), parameter.dimension, parameter
        )
        self._init_precision(parameter)
        self.nlist = parameter.ncentroids
        self.centroids: Optional[jax.Array] = None       # [nlist, d]
        self._c_sqnorm: Optional[jax.Array] = None
        self._assign_h = np.full((self.store.capacity,), -1, np.int32)
        self._view: Optional[MutableIvfView] = None
        self._buckets = None          # [alloc, cap_list, d]
        self._bucket_sqnorm = None
        self._bucket_bsq = None       # [alloc, nblk, cap_list] prune norms
        self._view_dirty = True
        self._filter_cache: dict = {}

    def _prep_vectors(self, vectors: np.ndarray) -> np.ndarray:
        vectors = np.asarray(vectors, np.float32)
        if vectors.ndim != 2 or vectors.shape[1] != self.dimension:
            raise InvalidParameter(
                f"vector dim {vectors.shape} != {self.dimension}"
            )
        if self.metric is Metric.COSINE:
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
        if self.metric is Metric.COSINE:
            queries = np_normalize(queries)
        return queries

    # -- mutation: track assignments ---------------------------------------
    @integrity_mutation
    def upsert(self, ids: np.ndarray, vectors: np.ndarray) -> None:
        vectors = self._prep_vectors(vectors)
        if len(ids) != len(vectors):
            raise InvalidParameter("ids/vectors length mismatch")
        slots = self.store.put(np.asarray(ids, np.int64), vectors)
        self._offer_rerank(slots, vectors)
        from dingo_tpu.obs.quality import QUALITY

        # quality plane: quantized tiers mirror the pre-quantization rows
        # for shadow ground truth (no-op while sampling is off)
        QUALITY.observe_write(self, np.asarray(ids, np.int64), vectors)
        self._integrity_write(ids, vectors)
        if self._assign_h.shape[0] < self.store.capacity:
            grown = np.full((self.store.capacity,), -1, np.int32)
            grown[: self._assign_h.shape[0]] = self._assign_h
            self._assign_h = grown
        if self.is_trained():
            assign = np.asarray(kmeans_assign(jnp.asarray(vectors), self.centroids))
            self._assign_h[slots] = assign
            self._integrity_assign(ids, assign)
            if self._view is not None and not self._view_dirty:
                # incremental append-in-place; the next search reuses the
                # maintained view instead of rebuilding from scratch
                self._view_apply_upsert(slots, assign, vectors)
            else:
                self._invalidate_view()
        else:
            self._view_dirty = True
        self.write_count_since_save += len(ids)

    @integrity_mutation
    def delete(self, ids: np.ndarray) -> None:
        ids = np.asarray(ids, np.int64)
        slots = self.store.remove_slots(ids)
        removed = int((slots >= 0).sum())
        self._invalidate_rerank(slots)
        from dingo_tpu.obs.quality import QUALITY

        QUALITY.observe_delete(self, ids)
        self._integrity_delete(ids)
        if removed:
            if self._view is not None and not self._view_dirty:
                self._view_apply_delete(slots[slots >= 0])
            else:
                self._invalidate_view()
        self.write_count_since_save += removed

    # -- training ----------------------------------------------------------
    def need_train(self) -> bool:
        return True

    def is_trained(self) -> bool:
        return self.centroids is not None

    @integrity_mutation
    def train(self, vectors: Optional[np.ndarray] = None) -> None:
        """Train the coarse quantizer. With no explicit train set, samples
        the stored vectors (VectorIndexManager::TrainForBuild samples the
        region, vector_index_manager.cc:1365)."""
        if vectors is None:
            # implicit path (ISSUE 18b): sample slot indices host-side,
            # gather + decode + normalize on DEVICE — only centroids ever
            # come back to the host. Conf train.sample_rows caps the
            # sample (0 = full corpus, lifting the derived cap too).
            dv = self._train_rows_device(
                MAX_POINTS_PER_CENTROID * self.nlist
            )
            if int(dv.shape[0]) < self.nlist:
                raise NotTrained(
                    f"need >= {self.nlist} train vectors, "
                    f"have {int(dv.shape[0])}"
                )
            if self.metric is Metric.COSINE:
                # stored rows are prep-normalized; quantized tiers decode
                # with drift, so renormalize (the old host path did too)
                dv = dv * jax.lax.rsqrt(jnp.maximum(
                    jnp.sum(dv * dv, axis=1, keepdims=True), 1e-30
                ))
            self.centroids, _ = train_kmeans(
                dv, k=self.nlist, iters=10, seed=self.id
            )
        else:
            if self._precision == "sq8":
                # an explicit train set reaches the codec BEFORE any
                # encode happened — per-dim min/max from the true
                # distribution beats first-batch lazy training
                self.store.maybe_train(self._prep_vectors(vectors))
            vectors = np.asarray(vectors, np.float32)
            if len(vectors) < self.nlist:
                raise NotTrained(
                    f"need >= {self.nlist} train vectors, "
                    f"have {len(vectors)}"
                )
            if self.metric is Metric.COSINE:
                vectors = np_normalize(vectors)
            cap = _resolve_train_cap(MAX_POINTS_PER_CENTROID * self.nlist)
            if cap and len(vectors) > cap:
                sel = np.random.default_rng(self.id).choice(
                    len(vectors), cap, replace=False
                )
                vectors = vectors[sel]
            self.centroids, _ = train_kmeans(
                jnp.asarray(vectors), k=self.nlist, iters=10, seed=self.id
            )
        self._c_sqnorm = squared_norms(self.centroids)
        # (re)assign everything currently stored — device gather, one
        # assign kernel, host copy of the int32 labels only
        live = np.flatnonzero(self.store.ids_by_slot >= 0)
        if len(live):
            vecs = self.store.rows_device(live)
            assign = np.asarray(kmeans_assign(vecs, self.centroids))
            self._assign_h[live] = assign
        self._integrity_reset_assign()
        self._invalidate_view()
        # retrain moves centroids + reassignments: the same query bytes now
        # produce different results with no row having been written, so
        # serving-state version consumers (the serving-edge result cache
        # keys on mutation_version) must see a new version
        self.store.mutation_version += 1

    # -- bucketed view (IvfViewMaintenance data hooks) -----------------------
    def _prune_dim_block(self):
        """Dimension-block width the pruned scan kernel would use for this
        index, or None when pruning cannot apply (flag off, binary ±1
        store, sq8+cosine — the XLA arm divides by the decoded norm, the
        kernel doesn't — or a dimension that doesn't block)."""
        from dingo_tpu.common.config import (
            pallas_ivf_enabled,
            prune_scan_enabled,
        )
        from dingo_tpu.ops.blocked import resolve_dim_block

        # metadata is only worth building where the Pallas route will
        # read it (a flag flip takes effect at the next view rebuild)
        if not pallas_ivf_enabled(self.dimension):
            return None
        if not prune_scan_enabled():
            return None
        if self._scan_metric not in (
            Metric.L2, Metric.INNER_PRODUCT, Metric.COSINE
        ):
            return None
        if self.store.vecs.dtype == jnp.int8:
            return None                       # binary ±1 family stays XLA
        if self._precision == "sq8" and self.metric is Metric.COSINE:
            return None
        return resolve_dim_block(self.dimension)

    def _materialize_view_data(self, view: MutableIvfView) -> None:
        """Dense gather of the whole store into the bucket coordinates —
        the O(N) path, reached only via rebuild/compaction. Caller holds
        device_lock (gather reads store.vecs, which is donatable)."""
        self._buckets = view.gather_rows(self.store.vecs)
        if self._bf16_widen_view():
            # CPU arm of the bf16 tier: rows are already bf16-quantized in
            # the store; widening the SCAN copy once per rebuild dodges
            # XLA CPU's scalar bf16 convert on every probe gather
            self._buckets = self._buckets.astype(jnp.float32)
        self._bucket_sqnorm = view.gather_rows(self.store.sqnorm)
        # pruning metadata: per-dimension-block squared norms of what the
        # scan kernel accumulates (decoded values for sq8 code buckets)
        self._bucket_bsq = None
        dblk = self._prune_dim_block()
        if dblk:
            from dingo_tpu.ops.blocked import bucket_block_sqnorms

            data = self._buckets
            if self._precision == "sq8":
                from dingo_tpu.ops.sq import sq_decode_device

                data = sq_decode_device(
                    data, self.store.sq_vmin_d, self.store.sq_scale_d,
                    jnp.float32,
                )
            self._bucket_bsq = bucket_block_sqnorms(data, dblk)

    def _bf16_widen_view(self) -> bool:
        from dingo_tpu.common.config import bf16_compute_native

        return self._precision == "bf16" and not bf16_compute_native()

    def _scatter_view_data(self, upd, rows) -> None:
        """Apply a staged append batch to the data arrays (caller holds
        device_lock; arrays are donated to the scatter programs)."""
        from dingo_tpu.ops.scatter import pad_buckets, scatter_bucket_update

        if upd.grew_alloc is not None:
            self._buckets = pad_buckets(self._buckets, upd.grew_alloc)
            self._bucket_sqnorm = pad_buckets(
                self._bucket_sqnorm, upd.grew_alloc
            )
            if self._bucket_bsq is not None:
                self._bucket_bsq = pad_buckets(
                    self._bucket_bsq, upd.grew_alloc
                )
        if not upd.appended:
            return
        cap = self._view.cap_list
        pos = np.asarray([p for p, _ in upd.appended], np.int64)
        src = np.asarray([i for _, i in upd.appended], np.int64)
        b_idx = (pos // cap).astype(np.int32)
        r_idx = (pos % cap).astype(np.int32)
        sel = np.asarray(rows)[src]
        if self._precision == "sq8":
            # bucket view mirrors the store: scatter CODES, cache DECODED
            # norms (same codec → bit-identical to the store rows)
            sel = self.store.encode(sel)
            deq = self.store.decode(sel)
            sq = (deq ** 2).sum(axis=1).astype(np.float32)
            norm_rows = deq
        else:
            norm_rows = sel.astype(np.float32)
            if self._precision == "bf16":
                # norms describe the bf16-quantized rows the scan reads
                # (same stored-row convention as slot_store._write_run)
                norm_rows = sel.astype(jnp.bfloat16).astype(np.float32)
                if self._bf16_widen_view():
                    # widened-view arm: quantize through bf16 first so the
                    # f32 scan copy matches the store rows bit-for-bit
                    sel = norm_rows
            sq = (norm_rows ** 2).sum(axis=1)
        self._buckets = scatter_bucket_update(
            self._buckets, b_idx, r_idx, sel
        )
        self._bucket_sqnorm = scatter_bucket_update(
            self._bucket_sqnorm, b_idx, r_idx, sq
        )
        if self._bucket_bsq is not None:
            from dingo_tpu.ops.blocked import block_sqnorms
            from dingo_tpu.ops.scatter import scatter_bucket_dim_update

            dblk = self.dimension // self._bucket_bsq.shape[1]
            bsq_rows = np.asarray(
                block_sqnorms(np.asarray(norm_rows, np.float32), dblk)
            ).T                                            # [n, nblk]
            self._bucket_bsq = scatter_bucket_dim_update(
                self._bucket_bsq, b_idx, r_idx, bsq_rows
            )

    # -- search -------------------------------------------------------------
    def search(
        self,
        queries: np.ndarray,
        topk: int,
        filter_spec: Optional[FilterSpec] = None,
        nprobe: Optional[int] = None,
    ) -> List[SearchResult]:
        return self.search_async(queries, topk, filter_spec, nprobe)()

    def search_async(
        self,
        queries: np.ndarray,
        topk: int,
        filter_spec: Optional[FilterSpec] = None,
        nprobe: Optional[int] = None,
        staged=None,
    ):
        if not self.is_trained():
            raise NotTrained("IVF_FLAT not trained")  # reader falls back
        from dingo_tpu.common.config import (
            pallas_interpret,
            pallas_ivf_enabled,
        )
        from dingo_tpu.obs.heat import HEAT, heat_enabled
        from dingo_tpu.ops.distance import device_wait_begin
        from dingo_tpu.ops.pallas_ivf import scan_arm

        store = self.store
        # index.dispatch: entry to the program enqueued (prep, pad and
        # H2D, the view snapshot, one launch); NOOP when unsampled
        with TRACER.start_child("index.dispatch") as dspan:
            queries = self._prep_queries(queries)
            self._ensure_view()
            self._count_search()
            b = queries.shape[0]
            topk = int(topk)
            # request-pinned nprobe wins; else the SLO tuner's override;
            # else the configured default (obs/tuner.py walks ladder
            # values only)
            nprobe = min(
                nprobe
                or self.tuned("nprobe", self.parameter.default_nprobe),
                self.nlist,
            )
            kprime = self._rerank_shortlist(topk)
            k_eff, nprobe = self._shape_buckets(
                max(topk, kprime or 0), nprobe)
            # staging-ring upload (serving pipeline): claimed only when
            # the identity check proves it was built from THESE queries
            qpad = staged.take(queries) if staged is not None else None
            if qpad is None:
                qpad = jnp.asarray(_pad_batch(queries))
            # what the program is specialised on, read once per request
            # and outside the lock. The Pallas kernels keep top-k in a
            # 128-lane output block; larger k (and its unrolled select
            # rounds), the binary family's int8 rows and sq8 without the
            # blocked norms stay on the XLA rank scan
            sq = self._precision == "sq8"
            pallas = (
                pallas_ivf_enabled(self.dimension)
                and self.metric in (
                    Metric.L2, Metric.INNER_PRODUCT, Metric.COSINE
                )
                and k_eff <= 64
                and (sq or store.vecs.dtype in (jnp.float32, jnp.bfloat16))
            )
            interpret = pallas_interpret() if pallas else False
            check = max(1, int(FLAGS.get("ivf_prune_check_interval")))
            inbucket = bool(FLAGS.get("ivf_prune_inbucket_bound"))
            fprep = self._prep_filter_mask(filter_spec)
            # lease BEFORE dispatch: kernel slots must stay limbo-parked
            # until resolve translates them (delete+reinsert would
            # misattribute)
            lease = store.begin_search()
            try:
                # view snapshot + ONE launch under the device lock: the
                # incremental write path DONATES bucket arrays to its
                # scatter programs, so a concurrent write must not
                # invalidate a captured reference between here and the
                # enqueue (same contract as slot_store.put); reading
                # self._view inside the same hold keeps view metadata and
                # self._buckets consistent. Nothing eager runs in the hold.
                # index.lock_wait: asking for the lock to holding it
                with TRACER.start_child("index.lock_wait"):
                    store.device_lock.acquire()
                try:
                    view = self._view
                    bsq = self._bucket_bsq
                    if sq and bsq is None:
                        pallas = False
                    # loop order of the Pallas scan, as the program will
                    # pick it from the same shapes (ops/pallas_ivf)
                    arm = scan_arm(
                        qpad.shape[0], view.cap_list, self.dimension,
                        self._buckets.dtype.itemsize,
                    ) if pallas else "xla"
                    dists, slots, probes, vprobes, aux = ivf_search_program(
                        qpad, self.centroids, self._c_sqnorm,
                        view.probe_table,
                        self._bucket_valid_for_filter(filter_spec, fprep),
                        view.bucket_slot, self._buckets,
                        self._bucket_sqnorm, bsq if pallas else None,
                        store.sq_vmin_d if sq else None,
                        store.sq_scale_d if sq else None,
                        k=k_eff, nprobe=nprobe, metric=self._scan_metric,
                        pallas=pallas, interpret=interpret,
                        check_every=check, inbucket=inbucket,
                    )
                    if kprime is not None:
                        # exact rerank of the quantized shortlist against
                        # the device row cache, dispatched under the same
                        # lock (cache arrays share it); still fully async
                        dists, slots = self._dispatch_rerank(
                            qpad, dists, slots, topk
                        )
                finally:
                    store.device_lock.release()
            except Exception:
                lease.release()
                raise
            METRICS.counter("ivf.scan_arm", region_id=self.id,
                            labels={"arm": arm}).add(1)
            # the ops.<stage> span's name: what the request waits for
            stage = "rerank" if kprime is not None else {
                "batch": "batch_scan", "xla": "ivf_scan",
                "query": "pruned_scan" if bsq is not None
                else "pallas_ivf_search",
            }[arm]
            # one-sync epilogue: the whole reply (the scan's aux block
            # included) joins a single D2H copy group; resolve device_gets
            # it exactly once. The heat plane's probed-list ids and, for a
            # sampled request, the probed bucket ids ride the SAME group:
            # the access sketch and ivf.probed_rows_per_query cost zero
            # extra syncs (resolve-sync contract)
            heat_on = heat_enabled()
            if heat_on:
                HEAT.register_layout(self.id, "ivf", self._heat_layout)
            probed = vprobes if dspan.sampled else None
            fetch = begin_host_fetch(dists, slots, aux,
                                     probes if heat_on else None, probed)
        # the device wait of a sampled request: from here (program
        # enqueued, lock released) to the fetch's return in resolve();
        # never a sync of its own (ops/distance.device_wait_begin)
        wait = device_wait_begin(stage)

        def resolve() -> List[SearchResult]:
            try:
                fetched = jax.device_get(fetch)
                wait.end()
                # index.resolve: the host work after the fetch
                with TRACER.start_child("index.resolve"):
                    dists_h, slots_h = fetched[0], fetched[1]
                    if aux is not None:
                        # scan observability rides the result fetch — no
                        # extra sync on the dispatch path
                        self._note_scan_aux(arm, fetched[2], b)
                    if probed is not None:
                        # joined LAST: [-1] whatever else is in the group
                        self._note_probed(
                            METRICS.gauge("ivf.probed_rows_per_query",
                                          region_id=self.id),
                            view, fetched[-1][:b])
                    if heat_on:
                        # probed list ids = which partitions this batch
                        # actually read (bounded enqueue; folds async)
                        HEAT.observe(
                            self.id, "ivf",
                            fetched[-1 if probed is None else -2][:b])
                    # shape bucketing may have run a larger k; slice back
                    ids = store.ids_of_slots(slots_h[:b, :topk])
                    dists_h = self._convert_distances(dists_h[:b, :topk])
                    # head-sampled shadow scoring, attributed to the
                    # nprobe bucket actually scanned (async lane; noop at
                    # rate 0)
                    from dingo_tpu.obs.quality import QUALITY

                    QUALITY.observe_search(
                        self, queries, topk, ids, dists_h,
                        bucket=f"nprobe={nprobe}", filter_spec=filter_spec,
                    )
                    return [strip_invalid(i, d)
                            for i, d in zip(ids, dists_h)]
            finally:
                lease.release()

        return resolve

    def _note_scan_aux(self, arm: str, aux_h, b: int) -> None:
        """Fold the scan's aux block, fetched with the reply: the
        query-major pruned arm's stats, or the batch-major arm's count of
        touched buckets (each read once: buckets x cap x d x itemsize
        bytes of HBM). A batch-major scan skipped nothing, and says so:
        the last pruned request's fraction would be a false reading."""
        if arm != "batch":
            self._note_prune_stats(aux_h[:b])
            return
        METRICS.gauge("ivf.batch_scan_buckets", region_id=self.id).set(
            float(aux_h))
        METRICS.gauge("ivf.pruned_dim_fraction", region_id=self.id).set(0.0)

    # -- lifecycle -----------------------------------------------------------
    def save(self, path: str) -> None:
        os.makedirs(path, exist_ok=True)
        if self._precision == "sq8" and self.store.sq_params is not None:
            snap = self.store.codes_to_host()
            # codes + codec params ride the snapshot exactly like PQ
            # codebooks: bit-exact restore, 1 byte/dim on disk
            snap["sq_vmin"] = self.store.sq_params.vmin
            snap["sq_scale"] = self.store.sq_params.scale
        else:
            snap = self.store.to_host()
            snap["vectors"] = np.asarray(snap["vectors"], np.float32)
        extras = {}
        if self.is_trained():
            extras["centroids"] = np.asarray(self.centroids)
            live = self.store.ids_by_slot >= 0
            extras["assign"] = self._assign_h[np.flatnonzero(live)]
        np.savez(os.path.join(path, "ivf_flat.npz"), **snap, **extras)
        meta = self._save_meta()
        meta["nlist"] = self.nlist
        meta["trained"] = self.is_trained()
        with open(os.path.join(path, "meta.json"), "w") as f:
            json.dump(meta, f)

    def load(self, path: str) -> None:
        from dingo_tpu.index.flat import _new_tier_store

        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        self._check_meta(meta)
        if meta["nlist"] != self.nlist:
            raise InvalidParameter(
                f"snapshot nlist {meta['nlist']} != {self.nlist}"
            )
        data = np.load(os.path.join(path, "ivf_flat.npz"))
        self.store = _new_tier_store(
            self._precision, self.dimension, self.parameter,
            capacity=max(len(data["ids"]), 1),
        )
        self._init_precision(self.parameter, tier=self._precision)
        self._assign_h = np.full((self.store.capacity,), -1, np.int32)
        self.centroids = None
        self._c_sqnorm = None
        if "codes" in data.files:
            from dingo_tpu.ops.sq import SqParams

            self.store.set_params(SqParams(
                np.asarray(data["sq_vmin"], np.float32),
                np.asarray(data["sq_scale"], np.float32),
            ))
            slots = self.store.put_codes(
                np.asarray(data["ids"], np.int64),
                np.asarray(data["codes"], np.uint8),
            ) if len(data["ids"]) else np.empty(0, np.int64)
        elif len(data["ids"]):
            # bypass upsert's assignment (we restore it directly). Rows on
            # disk came from the store, so cosine rows are ALREADY
            # normalized — re-normalizing drifts low-order bits and would
            # break the snapshot's bit-exact restore-digest verification
            slots = self.store.put(np.asarray(data["ids"], np.int64),
                                   data["vectors"])
        else:
            slots = np.empty(0, np.int64)
        if self._assign_h.shape[0] < self.store.capacity:
            grown = np.full((self.store.capacity,), -1, np.int32)
            grown[: self._assign_h.shape[0]] = self._assign_h
            self._assign_h = grown
        if meta.get("trained"):
            self.centroids = jnp.asarray(data["centroids"])
            self._c_sqnorm = squared_norms(self.centroids)
            self._assign_h[slots] = data["assign"]
        self.apply_log_id = meta["apply_log_id"]
        self._view = None
        self._view_dirty = True
        self._filter_cache.clear()
        self.write_count_since_save = 0
        self._integrity_on_restore(meta)


class TpuBinaryIvfFlat(BinaryPm1Mixin, TpuIvfFlat):
    """Binary (bit-packed) IVF with hamming list scan.

    Reference: faiss::IndexBinaryIVF behind the NewBinaryIVFFlat factory arm
    (vector_index_factory.h:37-68; vector_index_ivf_flat.cc:60-62).
    dimension is in BITS; the wire format is [n, dimension//8] uint8 rows.

    TPU-first: vectors unpack once at write time into a ±1 int8 store (same
    trick as TpuBinaryFlat), so the coarse quantizer is plain float k-means
    over ±1 space and the list scan is an int8 MXU matmul —
    hamming(a, b) = (nbits - <pm(a), pm(b)>) / 2. Centroids stay float
    (fractional centroids order candidate lists strictly better than
    re-binarized ones; faiss quantizes them because CPU hamming is its only
    fast kernel, a constraint the MXU does not have).
    """

    def __init__(self, index_id: int, parameter: IndexParameter):
        if parameter.dimension <= 0 or parameter.dimension % 8:
            raise InvalidParameter("binary dimension must be multiple of 8")
        super().__init__(index_id, parameter)
        self.nbytes = parameter.dimension // 8
        self.store = SlotStore(parameter.dimension, jnp.int8)
        # the ±1 int8 store IS the binary family's quantized form; the
        # float precision tiers don't apply on top of it
        self._precision = "fp32"
        self._rerank_cache = None
        self._scan_metric = Metric.INNER_PRODUCT
        self._assign_h = np.full((self.store.capacity,), -1, np.int32)

    # packed <-> ±1 codec + distance conversion come from BinaryPm1Mixin

    def _warmup_queries(self, b: int) -> np.ndarray:
        return np.ones((b, self.nbytes), np.uint8)   # wire format is packed

    def train(self, vectors: Optional[np.ndarray] = None) -> None:
        """Float k-means over ±1 space. An explicit train set arrives
        bit-packed (the wire format); the implicit path samples the already-
        unpacked store."""
        if vectors is not None:
            vectors = self._prep_vectors(vectors)
        super().train(vectors)

    # -- lifecycle (packed on disk) -----------------------------------------
    def save(self, path: str) -> None:
        os.makedirs(path, exist_ok=True)
        snap = self.store.to_host()
        extras = {}
        if self.is_trained():
            extras["centroids"] = np.asarray(self.centroids)
            live = self.store.ids_by_slot >= 0
            extras["assign"] = self._assign_h[np.flatnonzero(live)]
        np.savez(
            os.path.join(path, "binary_ivf_flat.npz"),
            ids=snap["ids"],
            vectors=self._repack(snap["vectors"]),
            **extras,
        )
        meta = self._save_meta()
        meta["nlist"] = self.nlist
        meta["trained"] = self.is_trained()
        with open(os.path.join(path, "meta.json"), "w") as f:
            json.dump(meta, f)

    def load(self, path: str) -> None:
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        self._check_meta(meta)
        if meta["nlist"] != self.nlist:
            raise InvalidParameter(
                f"snapshot nlist {meta['nlist']} != {self.nlist}"
            )
        data = np.load(os.path.join(path, "binary_ivf_flat.npz"))
        self.store = SlotStore(self.dimension, jnp.int8,
                               max(len(data["ids"]), 1))
        self._assign_h = np.full((self.store.capacity,), -1, np.int32)
        self.centroids = None
        self._c_sqnorm = None
        if len(data["ids"]):
            slots = self.store.put(
                np.asarray(data["ids"], np.int64),
                self._unpack_pm1(np.asarray(data["vectors"], np.uint8)),
            )
        else:
            slots = np.empty(0, np.int64)
        if self._assign_h.shape[0] < self.store.capacity:
            grown = np.full((self.store.capacity,), -1, np.int32)
            grown[: self._assign_h.shape[0]] = self._assign_h
            self._assign_h = grown
        if meta.get("trained"):
            self.centroids = jnp.asarray(data["centroids"])
            self._c_sqnorm = squared_norms(self.centroids)
            self._assign_h[slots] = data["assign"]
        self.apply_log_id = meta["apply_log_id"]
        self._view = None
        self._view_dirty = True
        self._filter_cache.clear()
        self.write_count_since_save = 0
        self._integrity_on_restore(meta)
