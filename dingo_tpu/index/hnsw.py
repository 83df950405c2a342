"""TpuHnsw: dual-representation graph index — host graph for writes,
device graph for reads.

Reference: VectorIndexHnsw (src/vector/vector_index_hnsw.{h,cc} — wraps
hnswlib::HierarchicalNSW with L2Space/InnerProductSpace,
vector_index_hnsw.cc:154-181; NeedToRebuild when deleted count exceeds half
the TOTAL element count :577-589; hnswlib-file Save/Load :310).

Two serving paths share one SlotStore + one exact device rerank:

  host path (fallback + parity oracle) — graph construction and beam
  search run in our own C++ NSW implementation (native/hnsw/hnsw.cc, an
  original implementation, not a copy of hnswlib). The graph returns an
  over-fetched candidate set (ef per query), and the device re-ranks the
  candidates with exact batched distances against the authoritative
  SlotStore copy.

  device path (``hnsw.device_search``, ISSUE 8 tentpole) — the native
  level-0 adjacency exports into a dense slot-space ``[capacity, deg]``
  int32 mirror (SlotStore.adj, deg = nlinks*2) and the whole walk runs as
  one jitted lockstep beam search (ops/beam.py): frontier gather on the
  adjacency, candidate distances via one ``[b, beam*deg] x d`` einsum
  against the SlotStore (bf16/sq8 precision tiers included), a per-query
  packed visited bitmask over capacity, masked top-k beam updates, and a
  fixed iteration cap with early exit once every query's beam converges.
  The mirror stays in sync with upsert/delete/load by keying on
  (native graph version, store mutation version) and lazily re-exporting
  on the first search after a write — the IVF `_ensure_view` discipline.

Both paths end in the SAME exact device rerank (ops/rerank.py), so the
final ordering is byte-identical whenever the candidate sets agree.
Filter pushdown applies the PR 3 filter-mask cache device-side inside
the beam kernel (masked candidates never enter the result beam); the
host path reuses the same cached mask for its post-filter.
"""

from __future__ import annotations

import ctypes
import json
import os
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dingo_tpu.common.metrics import METRICS
from dingo_tpu.index.base import (
    FilterSpec,
    IndexParameter,
    InvalidParameter,
    SearchResult,
    VectorIndex,
    resolve_precision,
    strip_invalid,
)
from dingo_tpu.index.flat import (
    _new_tier_store,
    _SlotStoreIndex,
    _pad_batch,
    integrity_mutation,
)
from dingo_tpu.ops.distance import Metric, np_normalize

_LIB = None

#: filter-mask cache entries kept per index (same bound as the IVF cache:
#: distinct live filter shapes per region are few)
FILTER_CACHE_SIZE = 16

#: rows replayed per native back-fill chunk after a device bulk build
#: (O(chunk) host memory, the streaming-rebuild discipline)
BACKFILL_CHUNK = 8192


def _lib():
    global _LIB
    if _LIB is None:
        from dingo_tpu.native import load_hnsw

        _LIB = load_hnsw()
    return _LIB


class TpuHnsw(_SlotStoreIndex):
    def __init__(self, index_id: int, parameter: IndexParameter):
        VectorIndex.__init__(self, index_id, parameter)
        p = parameter
        if p.dimension <= 0:
            raise InvalidParameter(f"dimension {p.dimension}")
        if p.metric is Metric.HAMMING:
            raise InvalidParameter("hamming not valid for HNSW")
        precision = resolve_precision(parameter)
        self.store = _new_tier_store(precision, p.dimension, parameter)
        self._init_precision(parameter, tier=precision)
        self.ef_search_default = max(64, p.efconstruction // 2)
        metric_code = 0 if p.metric is Metric.L2 else 1
        self._graph = _lib().hnsw_new(
            p.dimension, metric_code, p.nlinks, p.efconstruction, index_id
        )
        self._kernel_metric = p.metric
        self._kernel_nbits = 0
        #: level-0 degree cap of the exported adjacency (hnsw M0 = 2*M)
        self._graph_deg = max(1, int(p.nlinks)) * 2
        #: (native graph version, store mutation version) the device
        #: adjacency mirror was built against; None = never built
        self._graph_key = None
        self._entry_slot = -1
        #: device bulk build installed an adjacency the native graph does
        #: not hold yet — the first host-path use (write, host search,
        #: save) back-fills it (ISSUE 18 tentpole a)
        self._native_pending = False
        #: fingerprint -> (store version, numpy mask, device mask or None)
        self._filter_cache: dict = {}

    def __del__(self):  # noqa: D105
        try:
            if getattr(self, "_graph", None):
                _lib().hnsw_free(self._graph)
        except Exception:
            pass

    # -- prep ---------------------------------------------------------------
    def _prep_vectors(self, vectors: np.ndarray) -> np.ndarray:
        vectors = np.ascontiguousarray(vectors, np.float32)
        if vectors.ndim != 2 or vectors.shape[1] != self.dimension:
            raise InvalidParameter(
                f"vector dim {vectors.shape} != {self.dimension}"
            )
        if self.metric is Metric.COSINE:
            vectors = np_normalize(vectors)
        return vectors

    def _prep_queries(self, queries: np.ndarray) -> np.ndarray:
        queries = np.ascontiguousarray(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None, :]
        if queries.shape[1] != self.dimension:
            raise InvalidParameter(
                f"query dim {queries.shape[1]} != {self.dimension}"
            )
        if self.metric is Metric.COSINE:
            queries = np_normalize(queries)
        return queries

    # -- mutation ------------------------------------------------------------
    def train(self, vectors: Optional[np.ndarray] = None) -> None:
        """Graph needs no training; the sq8 tier can pre-install its codec
        from an explicit train set (else the first write batch trains it —
        the FLAT convention)."""
        if self._precision == "sq8" and vectors is not None:
            self.store.maybe_train(self._prep_vectors(vectors))

    @integrity_mutation
    def upsert(self, ids: np.ndarray, vectors: np.ndarray) -> None:
        self._ensure_native_graph()
        vectors = self._prep_vectors(vectors)
        ids = np.ascontiguousarray(ids, np.int64)
        if len(ids) != len(vectors):
            raise InvalidParameter("ids/vectors length mismatch")
        slots = self.store.put(ids, vectors)
        self._offer_rerank(slots, vectors)
        from dingo_tpu.obs.quality import QUALITY

        # quality plane: quantized tiers mirror the pre-quantization rows
        # for shadow ground truth (no-op while sampling is off)
        QUALITY.observe_write(self, ids, vectors)
        self._integrity_write(ids, vectors)
        _lib().hnsw_add(
            self._graph,
            len(ids),
            ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            vectors.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        )
        self.write_count_since_save += len(ids)

    @integrity_mutation
    def delete(self, ids: np.ndarray) -> None:
        self._ensure_native_graph()
        ids = np.ascontiguousarray(ids, np.int64)
        slots = self.store.remove_slots(ids)
        removed = int((slots >= 0).sum())
        self._invalidate_rerank(slots)
        from dingo_tpu.obs.quality import QUALITY

        QUALITY.observe_delete(self, ids)
        self._integrity_delete(ids)
        _lib().hnsw_delete(
            self._graph, len(ids),
            ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        )
        self.write_count_since_save += removed

    # -- device graph mirror -------------------------------------------------
    def _install_adjacency(self, labels: np.ndarray, adj_nodes: np.ndarray,
                           entry_label: int) -> None:
        """Remap a node-space level-0 export ([n] labels, [n, deg] neighbor
        node indices, -1 padded) into the slot-space device mirror.
        Caller holds store.device_lock. Nodes whose label has no live slot
        (store-deleted tombstones) are dropped — their slot may already
        serve a different vector, so they cannot route device-side; the
        need_to_rebuild() trigger bounds how degraded the graph can get.

        Integrity-bracketed like a write path: the install swaps the
        mirror AND rebuilds the adjacency ledger mid-flight — a scrub
        overlapping it must classify as raced, not corruption."""
        self._integrity_begin()
        try:
            self._install_adjacency_inner(labels, adj_nodes, entry_label)
        finally:
            self._integrity_end()

    def _install_adjacency_inner(self, labels, adj_nodes,
                                 entry_label: int) -> None:
        store = self.store
        deg = self._graph_deg
        full = np.full((store.capacity, deg), -1, np.int32)
        n = len(labels)
        if n:
            slot_by_node = store.slots_of(labels)
            safe = np.where(adj_nodes >= 0, adj_nodes, 0)
            neigh_slot = slot_by_node[safe].astype(np.int32)
            adj_slots = np.where(adj_nodes >= 0, neigh_slot, np.int32(-1))
            live = slot_by_node >= 0
            full[slot_by_node[live]] = adj_slots[live]
        store.set_graph(full, deg)
        entry = -1
        if entry_label >= 0:
            entry = int(store.slots_of(
                np.asarray([entry_label], np.int64))[0])
        if entry < 0 and n:
            # entry tombstoned in the store: any live slot restarts the
            # walk (greedy descent reaches the same basin in a few hops)
            live_slots = np.flatnonzero(store.valid_h)
            if len(live_slots):
                entry = int(live_slots[0])
        self._entry_slot = entry
        METRICS.gauge("hnsw.graph_nodes", region_id=self.id).set(float(n))
        # state-integrity: the adjacency artifact resets with every mirror
        # swap (a full install, not an incremental write). Neighbor slots
        # translate to EXTERNAL ids so the digest survives slot
        # renumbering across snapshot load — the same canonical form the
        # scrub recomputes from the device mirror.
        from dingo_tpu.obs.integrity import INTEGRITY

        if INTEGRITY.tracking(self):
            INTEGRITY.reset_artifact(self, "adjacency")
            live_slots = np.flatnonzero(store.ids_by_slot >= 0)
            if len(live_slots):
                INTEGRITY.note_write(
                    self, "adjacency", store.ids_by_slot[live_slots],
                    store.ids_of_slots(full[live_slots]),
                )

    def _export_level0(self):
        """(labels [n], adjacency [n, deg]) snapshot of the native level-0
        graph (node space)."""
        n = int(_lib().hnsw_total_count(self._graph))
        labels = np.empty(n, np.int64)
        adj = np.full((n, self._graph_deg), -1, np.int32)
        if n:
            # n is passed back in as the buffer capacity: the native side
            # clamps to it, so an insert racing between the count and the
            # export cannot overflow these arrays (the version key forces
            # a clean re-export on the next search either way)
            _lib().hnsw_export_level0(
                self._graph,
                n,
                self._graph_deg,
                labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                adj.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            )
        return labels, adj

    def _ensure_device_graph(self) -> None:
        """Lazy sync of the device adjacency (caller holds
        store.device_lock): steady-state read traffic finds a fresh mirror
        and pays one tuple compare; the first search after a write batch
        re-exports. Keyed on the native graph version AND the store
        mutation version — an upsert of an existing id re-slots nothing
        natively but can remap label->slot (delete + re-add), so both
        sides gate."""
        want = (
            int(_lib().hnsw_graph_version(self._graph)),
            self.store.mutation_version,
        )
        if self._graph_key == want and self.store.adj is not None:
            return
        labels, adj = self._export_level0()
        self._install_adjacency(
            labels, adj, int(_lib().hnsw_entry_label(self._graph))
        )
        self._graph_key = want
        METRICS.counter("hnsw.adjacency_rebuilds", region_id=self.id).add(1)

    def adjacency_in_sync(self) -> bool:
        """True while the device adjacency mirror matches the native graph
        AND the store (the scrub only checks the adjacency artifact then —
        a pending lazy re-export is staleness, not corruption)."""
        return (
            self.store.adj is not None
            and self._graph_key == (
                int(_lib().hnsw_graph_version(self._graph)),
                self.store.mutation_version,
            )
        )

    # -- device bulk build (ISSUE 18) ----------------------------------------
    def bulk_builder(self, expect_rows: int = 0):
        """Bulk-construction session (manager.build_index feeds scan
        chunks through it): rows stream into the SlotStore and the level-0
        graph builds on device in pow2 batches (ops/graph_build.py),
        batches-of-rows MXU work instead of one native insert at a time.

        Returns None when the crossover gate says host (``hnsw.device_build``
        auto = TPU-only — the host insert loop stays the CPU arm and the
        parity oracle) or when the index already holds rows (bulk build
        constructs from empty; incremental inserts keep the native path).
        """
        from dingo_tpu.common.config import hnsw_device_build_enabled

        if not hnsw_device_build_enabled():
            return None
        if len(self.store) or int(_lib().hnsw_total_count(self._graph)):
            return None
        return _HnswBulkSession(self, expect_rows)

    @integrity_mutation
    def _bulk_put(self, ids: np.ndarray, vectors: np.ndarray) -> np.ndarray:
        """upsert() minus the native ``hnsw_add``: store put + rerank offer
        + quality/integrity ledgers. The graph edge work happens in the
        bulk session's device builder; the native graph back-fills lazily
        via _ensure_native_graph()."""
        vectors = self._prep_vectors(vectors)
        ids = np.ascontiguousarray(ids, np.int64)
        if len(ids) != len(vectors):
            raise InvalidParameter("ids/vectors length mismatch")
        slots = self.store.put(ids, vectors)
        self._offer_rerank(slots, vectors)
        from dingo_tpu.obs.quality import QUALITY

        QUALITY.observe_write(self, ids, vectors)
        self._integrity_write(ids, vectors)
        self.write_count_since_save += len(ids)
        return slots

    def _install_built_adjacency(self, adj, entry_slot: int) -> None:
        """Install a device-built [capacity, deg] adjacency as THE graph:
        the mirror serves device searches immediately, `_graph_key` pins it
        against the lazy native re-export (which would clobber it with an
        empty graph), and `_native_pending` arms the back-fill. Integrity-
        bracketed like _install_adjacency — same mirror-swap semantics."""
        self._integrity_begin()
        try:
            store = self.store
            with store.device_lock:
                store.set_graph(adj, self._graph_deg)
                entry = int(entry_slot)
                if entry < 0 or not store.valid_h[entry]:
                    live_slots = np.flatnonzero(store.valid_h)
                    entry = int(live_slots[0]) if len(live_slots) else -1
                self._entry_slot = entry
                self._graph_key = (
                    int(_lib().hnsw_graph_version(self._graph)),
                    store.mutation_version,
                )
                self._native_pending = True
            n = len(store)
            METRICS.gauge("hnsw.graph_nodes", region_id=self.id).set(
                float(n)
            )
            from dingo_tpu.obs.integrity import INTEGRITY

            if INTEGRITY.tracking(self):
                full = np.asarray(adj)
                INTEGRITY.reset_artifact(self, "adjacency")
                live_slots = np.flatnonzero(store.ids_by_slot >= 0)
                if len(live_slots):
                    INTEGRITY.note_write(
                        self, "adjacency", store.ids_by_slot[live_slots],
                        store.ids_of_slots(full[live_slots]),
                    )
        finally:
            self._integrity_end()

    def _ensure_native_graph(self) -> None:
        """Replay the store's rows into the native graph after a device
        bulk build — triggered by the first host-path use (write, host
        search, save), not by the build itself: a device-served region
        never pays it. Streams BACKFILL_CHUNK rows per native add call
        (O(chunk) host memory); quantized tiers replay the decoded
        surrogate, the store's tier semantics. The handover COMPLETES
        here: once the native graph holds the rows, its level-0 export
        re-installs as the device mirror (one ordinary lazy re-export),
        so every representation — device walk, host beam, snapshot,
        integrity adjacency digest — describes the same topology from
        this point on."""
        if not self._native_pending:
            return
        self._native_pending = False
        store = self.store
        live = np.flatnonzero(store.valid_h)
        ids = store.ids_by_slot[live]
        for s in range(0, len(ids), BACKFILL_CHUNK):
            chunk = np.ascontiguousarray(ids[s:s + BACKFILL_CHUNK],
                                         np.int64)
            _, rows = store.gather(chunk)
            rows = np.ascontiguousarray(rows, np.float32)
            _lib().hnsw_add(
                self._graph,
                len(chunk),
                chunk.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                rows.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            )
        self._graph_key = None
        with store.device_lock:
            self._ensure_device_graph()
        METRICS.counter("build.backfills", region_id=self.id).add(1)

    # -- filter-mask cache ---------------------------------------------------
    def _prep_filter(self, filter_spec: Optional[FilterSpec]):
        """Fingerprint + (on miss) numpy mask build, OUTSIDE the device
        lock — the ivf_flat._prep_filter_mask discipline, keyed on
        (FilterSpec.fingerprint(), store mutation version) instead of the
        view version. Returns (fp, version, numpy mask, device mask or
        None), or None for no/empty filter."""
        if filter_spec is None or filter_spec.is_empty():
            return None
        fp = filter_spec.fingerprint()
        ver = self.store.mutation_version
        hit = self._filter_cache.get(fp)
        if hit is not None and hit[0] == ver:
            METRICS.counter(
                "hnsw.filter_mask_hits", region_id=self.id
            ).add(1)
            return (fp, ver, hit[1], hit[2])
        mask = filter_spec.slot_mask(self.store.ids_by_slot)
        self._cache_filter(fp, (ver, mask, None))
        METRICS.counter("hnsw.filter_mask_misses", region_id=self.id).add(1)
        return (fp, ver, mask, None)

    def _cache_filter(self, fp: bytes, entry) -> None:
        if len(self._filter_cache) >= FILTER_CACHE_SIZE:
            ver = self.store.mutation_version
            stale = [k for k, v in self._filter_cache.items()
                     if v[0] != ver]
            for k in stale:
                del self._filter_cache[k]
            while len(self._filter_cache) >= FILTER_CACHE_SIZE:
                self._filter_cache.pop(next(iter(self._filter_cache)))
        self._filter_cache[fp] = entry

    def _device_filter_mask(self, filter_spec, prep):
        """[capacity] bool device mask for the beam kernel (caller holds
        store.device_lock). Uploads the slot mask once per (filter,
        store version) and revalidates against the live version — a write
        racing between prep and dispatch rebuilds."""
        if prep is None:
            return None
        fp, ver, np_mask, dev = prep
        cur = self.store.mutation_version
        if dev is not None and ver == cur:
            return dev
        if ver != cur or np_mask is None:
            np_mask = filter_spec.slot_mask(self.store.ids_by_slot)
            ver = cur
        dev = jnp.asarray(np_mask)
        self._cache_filter(fp, (ver, np_mask, dev))
        return dev

    # -- search --------------------------------------------------------------
    def search(
        self,
        queries: np.ndarray,
        topk: int,
        filter_spec: Optional[FilterSpec] = None,
        ef: Optional[int] = None,
    ) -> List[SearchResult]:
        return self.search_async(queries, topk, filter_spec, ef)()

    def search_async(
        self,
        queries: np.ndarray,
        topk: int,
        filter_spec: Optional[FilterSpec] = None,
        ef: Optional[int] = None,
        staged=None,
    ):
        queries = self._prep_queries(queries)
        b = queries.shape[0]
        # request-pinned ef wins; else the SLO tuner's override; else the
        # construction-derived default (obs/tuner.py walks ladder values)
        ef = max(int(ef or self.tuned("ef", self.ef_search_default)),
                 int(topk))
        self._count_search()
        if self._device_search_on():
            return self._device_search_async(
                queries, b, int(topk), filter_spec, ef, staged=staged
            )
        return self._host_search_async(queries, b, int(topk), filter_spec,
                                       ef, staged=staged)

    def _device_search_on(self) -> bool:
        from dingo_tpu.common.config import hnsw_device_enabled

        return hnsw_device_enabled() and len(self.store) > 0

    def _beam_width(self, ef: int, topk: int) -> int:
        """ef -> beam ladder: a fixed conf width wins, else the
        {1,1.5}x-pow2 shape bucket keeps steady-state serving on a
        handful of compiled programs (k/beam/max_iters are static)."""
        from dingo_tpu.common.config import FLAGS
        from dingo_tpu.index.ivf_layout import shape_bucket

        fixed = int(FLAGS.get("hnsw_device_beam"))
        if fixed > 0:
            return max(fixed, topk)
        return max(shape_bucket(max(ef, topk)), 1)

    def _device_search_async(self, queries, b, topk, filter_spec, ef,
                             staged=None):
        from dingo_tpu.common.config import FLAGS
        from dingo_tpu.ops.beam import beam_search

        store = self.store
        beam = self._beam_width(ef, topk)
        max_iters = max(1, int(FLAGS.get("hnsw_max_iters")))
        METRICS.counter("hnsw.device_searches", region_id=self.id).add(1)
        prep = self._prep_filter(filter_spec)
        # staging-ring upload (serving pipeline): claimed only when the
        # identity check proves it was built from THESE queries
        qpad = staged.take(queries) if staged is not None else None
        if qpad is None:
            qpad = jnp.asarray(_pad_batch(queries))
        lease = store.begin_search()
        try:
            with store.device_lock:
                self._ensure_device_graph()
                valid = store.device_mask()
                fmask = self._device_filter_mask(filter_spec, prep)
                sq_on = (
                    self._precision == "sq8"
                    and store.sq_params is not None
                )
                if sq_on:
                    vmin, scale = store.sq_vmin_d, store.sq_scale_d
                else:
                    vmin = jnp.zeros((self.dimension,), jnp.float32)
                    scale = jnp.ones((self.dimension,), jnp.float32)
                cap = store.capacity
                rslots, hops, vcount, occ = beam_search(
                    store.adj,
                    store.vecs,
                    store.sqnorm,
                    valid,
                    fmask if fmask is not None else valid,
                    qpad,
                    jnp.asarray(self._entry_slot, jnp.int32),
                    vmin,
                    scale,
                    beam=beam,
                    max_iters=max_iters,
                    metric=self._kernel_metric,
                    sq=sq_on,
                )
                dists, out_slots = self._final_rerank(qpad, rslots, topk)
        except Exception:
            lease.release()
            raise
        # one-sync epilogue: walk diagnostics (hops/vcount/occ) join the
        # SAME D2H copy group as the reply — previously they rode the
        # device_get cold (no async copy started), adding a serialized
        # transfer to every resolve
        from dingo_tpu.ops.topk import begin_host_fetch

        fetch = begin_host_fetch(dists, out_slots, hops, vcount, occ)
        from dingo_tpu.ops.distance import device_wait_begin

        # device wait of a sampled request, ended at resolve()'s one fetch
        wait = device_wait_begin("beam_search")
        from dingo_tpu.obs.heat import HEAT, heat_enabled

        heat_on = heat_enabled()
        if heat_on:
            HEAT.register_layout(self.id, "slot", self._heat_layout)

        def resolve() -> List[SearchResult]:
            try:
                dists_h, slots_h, hops_h, vc_h, occ_h = jax.device_get(
                    fetch
                )
                wait.end()
                self._note_walk_stats(
                    hops_h[:b], vc_h[:b], occ_h[:b], cap, beam
                )
                if heat_on:
                    # result slots mark the graph neighborhoods the walk
                    # landed in; the per-query visited count weights the
                    # touch by how much of the graph the walk crossed.
                    # Both arrays were ALREADY in this fetch group.
                    w = float(max(1.0, np.mean(vc_h[:b]) / max(1, beam)))
                    HEAT.observe(self.id, "slot", slots_h[:b], weight=w)
                ids = store.ids_of_slots(slots_h[:b])
                # head-sampled shadow scoring, attributed to the beam
                # bucket the walk ran with (async lane; noop at rate 0)
                from dingo_tpu.obs.quality import QUALITY

                QUALITY.observe_search(
                    self, queries, topk, ids, dists_h[:b],
                    bucket=f"ef={beam}", filter_spec=filter_spec,
                )
                return [strip_invalid(i, d)
                        for i, d in zip(ids, dists_h[:b])]
            finally:
                lease.release()

        return resolve

    def _host_search_async(self, queries, b, topk, filter_spec, ef,
                           staged=None):
        self._ensure_native_graph()
        METRICS.counter("hnsw.host_searches", region_id=self.id).add(1)
        # 1) CPU graph: over-fetched candidate labels per query.
        cand_labels = np.empty((b, ef), np.int64)
        cand_d = np.empty((b, ef), np.float32)
        _lib().hnsw_search(
            self._graph, b,
            queries.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            ef, ef,
            cand_labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            cand_d.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        )
        # 2) host filter on candidates via the shared (fingerprint, store
        #    version) mask cache (the graph has no filter pushdown; the
        #    reference's HnswRangeFilterFunctor filters inside the beam —
        #    over-fetch + post-filter keeps the graph branch-free instead).
        prep = self._prep_filter(filter_spec)
        flat = cand_labels.reshape(-1)
        slots = self.store.slots_of(flat).reshape(b, ef)
        valid = slots >= 0
        if prep is not None:
            fmask = prep[2]
            if prep[1] != self.store.mutation_version:  # raced with write
                fmask = filter_spec.slot_mask(self.store.ids_by_slot)
            safe = np.where(slots >= 0, slots, 0)
            valid &= fmask[safe]
        # 3) exact device rerank (shared with the device path).
        qpad = staged.take(queries) if staged is not None else None
        if qpad is None:
            qpad = jnp.asarray(_pad_batch(queries))
        bb = qpad.shape[0]
        cand = np.where(valid, slots, -1).astype(np.int32)
        if bb != b:
            cand = np.concatenate(
                [cand, np.full((bb - b, ef), -1, np.int32)]
            )
        store = self.store
        lease = store.begin_search()   # slots stable until resolve
        try:
            with store.device_lock:    # vecs/sqnorm are donatable
                dists, out_slots = self._final_rerank(
                    qpad, jnp.asarray(cand), topk
                )
        except Exception:
            lease.release()
            raise
        from dingo_tpu.ops.topk import begin_host_fetch

        fetch = begin_host_fetch(dists, out_slots)
        from dingo_tpu.obs.heat import HEAT, heat_enabled

        heat_on = heat_enabled()
        if heat_on:
            HEAT.register_layout(self.id, "slot", self._heat_layout)

        def resolve() -> List[SearchResult]:
            try:
                dists_h, slots_h = jax.device_get(fetch)
                if heat_on:
                    HEAT.observe(self.id, "slot", slots_h[:b])
                ids = store.ids_of_slots(slots_h[:b])
                from dingo_tpu.obs.quality import QUALITY

                # bucket = the LADDER value (same attribution as the
                # device path): raw client-pinned ef would mint unbounded
                # label cardinality and split one setting across names
                QUALITY.observe_search(
                    self, queries, topk, ids, dists_h[:b],
                    bucket=f"ef={self._beam_width(ef, topk)}",
                    filter_spec=filter_spec,
                )
                return [strip_invalid(i, d)
                        for i, d in zip(ids, dists_h[:b])]
            finally:
                lease.release()

        return resolve

    def _final_rerank(self, qpad, cand_slots, topk: int):
        """Exact device rerank of a candidate set (ops/rerank.py); caller
        holds store.device_lock. fp32 reranks exactly; bf16 gathers the
        stored bf16 rows and scores in f32 (bf16-exact); sq8 decodes codes
        in-kernel (exact for the tier) and, when the PR 4 rerank cache
        holds rows, chains the cached f32-exact rerank on top."""
        from dingo_tpu.ops.rerank import (
            exact_rerank_device,
            sq_rerank_device,
        )

        store = self.store
        metric = self._kernel_metric
        if self._precision == "sq8":
            if store.sq_params is None:
                # empty untrained store: identity codec keeps the kernel
                # well-defined without installing params (FLAT convention)
                vmin = jnp.zeros((self.dimension,), jnp.float32)
                scale = jnp.ones((self.dimension,), jnp.float32)
            else:
                vmin, scale = store.sq_vmin_d, store.sq_scale_d
            cache = self._rerank_cache
            if cache is not None and len(cache):
                kk = int(cand_slots.shape[1])
                dists, slots = sq_rerank_device(
                    store.vecs, vmin, scale, store.sqnorm, qpad,
                    cand_slots, k=kk, metric=metric,
                )
                return self._dispatch_rerank(qpad, dists, slots, topk)
            return sq_rerank_device(
                store.vecs, vmin, scale, store.sqnorm, qpad, cand_slots,
                k=topk, metric=metric,
            )
        return exact_rerank_device(
            store.vecs, store.sqnorm, qpad, cand_slots, k=topk,
            metric=metric,
        )

    def _note_walk_stats(self, hops, vcount, occ, cap, beam) -> None:
        """Fold one resolved device walk into the metrics plane (called
        from resolve(): the hot path never synchronizes for stats)."""
        METRICS.gauge("hnsw.mean_hops", region_id=self.id).set(
            float(np.mean(hops)) if len(hops) else 0.0
        )
        METRICS.gauge("hnsw.visited_fraction", region_id=self.id).set(
            float(np.mean(vcount)) / max(1, cap) if len(vcount) else 0.0
        )
        METRICS.gauge("hnsw.beam_occupancy", region_id=self.id).set(
            float(np.mean(occ)) / max(1, beam) if len(occ) else 0.0
        )

    def _heat_layout(self) -> dict:
        """Heat-plane layout provider: HNSW heat units are SLOT_BLOCK
        slot ranges of the backing store (graph adjacency bytes ride
        with the rows they index), priced at this tier's bytes/row."""
        from dingo_tpu.obs.heat import SLOT_BLOCK, TIER_BYTES

        tier = getattr(self, "_precision", "fp32")
        return {
            "rows_per_unit": SLOT_BLOCK,
            "row_bytes": self.dimension * TIER_BYTES.get(tier, 4.0),
            "tier": tier,
            "dim": self.dimension,
        }

    def warmup(self, batches=(1, 8, 64), topk: int = 10,
               ef: Optional[int] = None) -> int:
        """Pre-compile the steady-state device-walk programs (one per
        (batch bucket, beam bucket, k) triple) so first real traffic never
        pays an XLA compile. No-op on an empty index."""
        if len(self.store) == 0:
            return 0
        n = 0
        for bsz in batches:
            self.search(
                np.ones((int(bsz), self.dimension), np.float32), topk,
                ef=ef,
            )
            n += 1
        return n

    # -- lifecycle ------------------------------------------------------------
    def get_count(self) -> int:
        return len(self.store)

    def get_deleted_count(self) -> int:
        return int(_lib().hnsw_deleted_count(self._graph))

    def get_memory_size(self) -> int:
        return self.store.memory_size() + int(_lib().hnsw_memory(self._graph))

    def need_to_rebuild(self) -> bool:
        """Reference trigger: deleted_count > total/2
        (vector_index_hnsw.cc:577-589; note hnswlib's getCurrentElementCount
        includes tombstones, so the threshold is half of TOTAL)."""
        deleted = self.get_deleted_count()
        total = deleted + self.get_count()
        return total > 0 and deleted * 2 > total

    def _save_meta(self) -> dict:
        meta = super()._save_meta()
        meta["hnsw_graph"] = {
            "deg": self._graph_deg,
            "nodes": int(_lib().hnsw_total_count(self._graph)),
            "entry_label": int(_lib().hnsw_entry_label(self._graph)),
        }
        return meta

    def save(self, path: str) -> None:
        self._ensure_native_graph()
        os.makedirs(path, exist_ok=True)
        if self._precision == "sq8" and self.store.sq_params is not None:
            snap = self.store.codes_to_host()
            np.savez(
                os.path.join(path, "hnsw_vectors.npz"),
                ids=snap["ids"],
                codes=snap["codes"],
                sq_vmin=self.store.sq_params.vmin,
                sq_scale=self.store.sq_params.scale,
            )
        else:
            snap = self.store.to_host()
            np.savez(
                os.path.join(path, "hnsw_vectors.npz"),
                ids=snap["ids"],
                # f32 on disk (bf16 isn't npz-serializable; widening is
                # lossless)
                vectors=np.asarray(snap["vectors"], np.float32),
            )
        size = _lib().hnsw_save_size(self._graph)
        buf = np.empty(size, np.uint8)
        written = _lib().hnsw_save(
            self._graph, buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
        )
        with open(os.path.join(path, "hnsw_graph.bin"), "wb") as f:
            f.write(buf[:written].tobytes())
        # device-graph adjacency rides the snapshot (node space + labels)
        # so load() serves device searches without a native re-export
        labels, adj = self._export_level0()
        np.savez(
            os.path.join(path, "hnsw_adj.npz"), labels=labels, adj=adj
        )
        with open(os.path.join(path, "meta.json"), "w") as f:
            json.dump(self._save_meta(), f)

    def load(self, path: str) -> None:
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        self._check_meta(meta)
        data = np.load(os.path.join(path, "hnsw_vectors.npz"))
        self.store = _new_tier_store(
            self._precision, self.dimension, self.parameter,
            capacity=max(len(data["ids"]), 1),
        )
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
        blob = np.fromfile(os.path.join(path, "hnsw_graph.bin"), np.uint8)
        new_graph = _lib().hnsw_load(
            blob.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), len(blob)
        )
        if not new_graph:
            raise InvalidParameter("bad hnsw graph blob")
        _lib().hnsw_free(self._graph)
        self._graph = new_graph
        self._filter_cache.clear()
        self._graph_key = None
        self._entry_slot = -1
        self._native_pending = False   # the loaded blob IS the graph
        adj_path = os.path.join(path, "hnsw_adj.npz")
        graph_meta = meta.get("hnsw_graph")
        if graph_meta and os.path.exists(adj_path) \
                and int(graph_meta.get("deg", -1)) == self._graph_deg:
            snap = np.load(adj_path)
            with self.store.device_lock:
                self._install_adjacency(
                    np.asarray(snap["labels"], np.int64),
                    np.asarray(snap["adj"], np.int32),
                    int(graph_meta.get("entry_label", -1)),
                )
                self._graph_key = (
                    int(_lib().hnsw_graph_version(self._graph)),
                    self.store.mutation_version,
                )
        self.apply_log_id = meta["apply_log_id"]
        self.write_count_since_save = 0
        self._integrity_on_restore(meta)


class _HnswBulkSession:
    """One bulk construction: rows in via add(), graph installed by
    finish(). Owns a BulkGraphBuilder over the index's SlotStore;
    index-level bookkeeping (ledgers, rerank offers, native back-fill
    arming) stays in TpuHnsw."""

    def __init__(self, index: TpuHnsw, expect_rows: int = 0):
        from dingo_tpu.common.config import FLAGS
        from dingo_tpu.ops.graph_build import BulkGraphBuilder

        self.index = index
        if expect_rows > 0:
            # one reservation = one compiled ladder: growth mid-build
            # would re-specialize the insert program per pow2 step
            index.store.reserve(expect_rows)
        self._builder = BulkGraphBuilder(
            index.store,
            index._graph_deg,
            index._kernel_metric,
            sq=(index._precision == "sq8"),
            batch_rows=int(FLAGS.get("hnsw_build_batch")),
            beam=index._beam_width(index.parameter.efconstruction, 1),
            max_iters=max(1, int(FLAGS.get("hnsw_max_iters"))),
            alpha=float(FLAGS.get("hnsw_build_alpha")),
            region_id=index.id,
        )

    def add(self, ids: np.ndarray, vectors: np.ndarray) -> None:
        slots = self.index._bulk_put(ids, vectors)
        self._builder.add_slots(np.asarray(slots, np.int32))

    def finish(self) -> dict:
        adj, entry, stats = self._builder.finish()
        self.index._install_built_adjacency(adj, entry)
        METRICS.counter(
            "build.device_builds", region_id=self.index.id
        ).add(1)
        return stats
