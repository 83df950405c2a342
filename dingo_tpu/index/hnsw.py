"""TpuHnsw: a graph index with ONE graph, the device adjacency.

Reference: VectorIndexHnsw (src/vector/vector_index_hnsw.{h,cc} — wraps
hnswlib::HierarchicalNSW with L2Space/InnerProductSpace,
vector_index_hnsw.cc:154-181; NeedToRebuild when deleted count exceeds half
the TOTAL element count :577-589; hnswlib-file Save/Load :310).

The level-0 adjacency in slot space (``SlotStore.adj``, dense
``[capacity, deg]`` int32, deg = nlinks*2) IS the graph, on whatever
backend the process has. ``upsert`` puts the rows and inserts them into
the live adjacency in pow2 batches (ops/graph_build.insert_batch:
candidate discovery by the lockstep beam walk, occlusion pruning, reverse
edges; the adjacency is donated under ``store.device_lock``), so an
acknowledged row is found by the next search with no O(N) step;
``delete`` tombstones the slot and the walk routes around it; searches
run as one jitted lockstep beam search (ops/beam.py: frontier gather on
the adjacency, candidate distances via one einsum against the SlotStore,
a per-query packed visited bitmask, masked top-k beam updates, a fixed
iteration cap with early exit) ending in the exact device rerank
(ops/rerank.py); ``save`` persists rows + adjacency + entry and ``load``
serves from them. An index is in one of two states: adjacency installed,
or not yet (no row written).

Filter pushdown applies the PR 3 filter-mask cache device-side inside
the beam kernel (masked candidates never enter the result beam).
"""

from __future__ import annotations

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
from dingo_tpu.obs.sentinel import sentinel_jit
from dingo_tpu.ops.distance import Metric, np_normalize

#: filter-mask cache entries kept per index (same bound as the IVF cache:
#: distinct live filter shapes per region are few)
FILTER_CACHE_SIZE = 16


@sentinel_jit("index.hnsw.search",
              static_argnames=("beam", "max_iters", "metric", "k"))
def hnsw_search_program(adj, vecs, sqnorm, valid, fmask, queries, entry,
                        beam, max_iters, metric, k):
    """A float-tier search request as ONE device program: the lockstep
    walk (ops/beam.py) and the exact rerank of its candidate set
    (ops/rerank.py), one launch inside one ``store.device_lock`` hold and
    three arrays to fetch — as IVF_FLAT's ``ivf_search_program``. The sq8
    tier keeps the two launches (its rerank may chain the row cache).

    Returns (wire distances [b, k], slots [b, k], walk diagnostics
    [b, 3] int32: rounds, visited rows, live result entries)."""
    from dingo_tpu.ops.beam import beam_search
    from dingo_tpu.ops.rerank import exact_rerank_device

    unit = jnp.zeros((vecs.shape[1],), jnp.float32)   # sq codec, unused
    rslots, hops, vcount, occ = beam_search.__wrapped__(
        adj, vecs, sqnorm, valid, fmask, queries, entry, unit, unit,
        beam, max_iters, metric, False,
    )
    dists, slots = exact_rerank_device.__wrapped__(
        vecs, sqnorm, queries, rslots, k, metric
    )
    return dists, slots, jnp.stack([hops, vcount, occ], axis=1)


class TpuHnsw(_SlotStoreIndex):
    def __init__(self, index_id: int, parameter: IndexParameter):
        VectorIndex.__init__(self, index_id, parameter)
        p = parameter
        if p.dimension <= 0:
            raise InvalidParameter(f"dimension {p.dimension}")
        if p.metric is Metric.HAMMING:
            raise InvalidParameter("hamming not valid for HNSW")
        precision = resolve_precision(parameter)
        # `max_elements` (the upstream's hnsw parameter): the slot store,
        # and with it the device adjacency, is sized for the region's rows
        # at creation, so a load never re-shapes them (each pow2 step of a
        # growing store re-allocates both and recompiles the insert and
        # search programs for the new shape). 0 = grow.
        self.store = _new_tier_store(precision, p.dimension, parameter,
                                     capacity=max(0, int(p.max_elements)))
        self._init_precision(parameter, tier=precision)
        self.ef_search_default = max(64, p.efconstruction // 2)
        self._kernel_metric = p.metric
        self._kernel_nbits = 0
        #: level-0 degree cap of the adjacency (hnsw M0 = 2*M)
        self._graph_deg = max(1, int(p.nlinks)) * 2
        self._entry_slot = -1
        #: rows tombstoned since the adjacency was installed
        self._deleted_slots = 0
        #: writes since the adjacency ledger was last seeded: the scrub
        #: and the snapshot skip the artifact until save() re-seeds it
        #: from the host copy it takes anyway
        self._adj_ledger_stale = False
        #: reverse edges dropped by inserts (device scalar, folded into
        #: ``build.reverse_dropped`` at save: no sync on a write)
        self._dropped_d = None
        self._identity_codec = None
        #: (entry slot, its device scalar): uploaded when the entry moves,
        #: not with every search
        self._entry_cached = (None, None)
        # `hnsw.native_adds`, `hnsw.adjacency_rebuilds` and
        # `hnsw.host_searches` count nothing any more (there is no native
        # graph): benchmark/metrics/hnsw_{native_adds,adjacency_rebuilds,
        # host_searches}.json read them in `hnsw768.conc4`, those files are
        # the benchmark's, and the `benchmark` issue that retires the
        # three metrics takes these registrations with them
        for name in ("native_adds", "adjacency_rebuilds", "host_searches",
                     "device_searches"):
            METRICS.counter("hnsw." + name, region_id=index_id).add(0)
        #: fingerprint -> (store version, numpy mask, device mask or None)
        self._filter_cache: dict = {}

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

    def _put_rows(self, ids: np.ndarray, vectors: np.ndarray):
        """Store put + rerank offer + quality/integrity ledgers: what an
        upsert and a bulk session's add share. -> (ids, vectors, slots)"""
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
        self.write_count_since_save += len(ids)
        return ids, vectors, slots

    @integrity_mutation
    def upsert(self, ids: np.ndarray, vectors: np.ndarray) -> None:
        _, _, slots = self._put_rows(ids, vectors)
        self._device_insert(np.asarray(slots, np.int32))

    @integrity_mutation
    def delete(self, ids: np.ndarray) -> None:
        ids = np.ascontiguousarray(ids, np.int64)
        slots = self.store.remove_slots(ids)
        removed = int((slots >= 0).sum())
        self._invalidate_rerank(slots)
        from dingo_tpu.obs.quality import QUALITY

        QUALITY.observe_delete(self, ids)
        self._integrity_delete(ids)
        # a tombstone: the slot leaves the validity mask, the walk routes
        # around it, and neighbours' edges to it now translate to no id —
        # the adjacency ledger waits for the next save
        self._deleted_slots += removed
        self._adj_ledger_stale = True
        self._entry_slot = self._live_entry(self._entry_slot)
        self.write_count_since_save += removed

    # -- the device adjacency -------------------------------------------------
    def _live_entry(self, entry: int) -> int:
        """`entry` while its slot holds a live row, else any live slot
        (greedy descent reaches the same basin in a few hops; a walk from
        a tombstoned entry whose neighbours are tombstoned too finds
        nothing), else -1: an index with no live row answers empty."""
        valid = self.store.valid_h
        if 0 <= entry < len(valid) and valid[entry]:
            return int(entry)
        live_slots = np.flatnonzero(valid)
        return int(live_slots[0]) if len(live_slots) else -1

    def _entry_device(self):
        if self._entry_cached[0] != self._entry_slot:
            self._entry_cached = (
                self._entry_slot, jnp.asarray(self._entry_slot, jnp.int32)
            )
        return self._entry_cached[1]

    def _codec(self):
        """(sq_on, vmin, scale) for the beam and build kernels; float tiers
        pass one cached identity codec (no per-call device allocation)."""
        store = self.store
        if self._precision == "sq8" and store.sq_params is not None:
            return True, store.sq_vmin_d, store.sq_scale_d
        if self._identity_codec is None:
            self._identity_codec = (
                jnp.zeros((self.dimension,), jnp.float32),
                jnp.ones((self.dimension,), jnp.float32),
            )
        return (False,) + self._identity_codec

    def _device_insert(self, slots: np.ndarray) -> None:
        """Insert freshly put slots into the live adjacency, in the pow2
        batch ladder of the bulk build (full ``hnsw.build_batch`` batches,
        the remainder padded to its own pow2 with -1): candidate discovery
        walks the graph as it stands, so rows of an earlier batch — and of
        every earlier upsert — are found. ``store.device_lock`` is held
        for the donated update only; nothing is read back."""
        from dingo_tpu.common.config import FLAGS
        from dingo_tpu.ops.graph_build import insert_batch, ladder_batches
        from dingo_tpu.trace import TRACER

        if not len(slots):
            return
        store = self.store
        if store.adj is None:      # the index's first rows
            store.set_graph(
                np.full((store.capacity, self._graph_deg), -1, np.int32),
                self._graph_deg,
            )
        beam = self._beam_width(self.parameter.efconstruction, 1)
        max_iters = max(1, int(FLAGS.get("hnsw_max_iters")))
        alpha = float(FLAGS.get("hnsw_build_alpha"))
        with TRACER.start_child("hnsw.insert_batch"):
            for chunk in ladder_batches(
                    slots, int(FLAGS.get("hnsw_build_batch"))):
                with store.device_lock:
                    sq_on, vmin, scale = self._codec()
                    store.adj, _, dropped = insert_batch(
                        store.adj, store.vecs, store.sqnorm,
                        store.device_mask(), chunk,
                        self._entry_device(), vmin, scale,
                        beam=beam, max_iters=max_iters,
                        metric=self._kernel_metric, sq=sq_on,
                        alpha_sq=alpha * alpha,
                    )
                    self._dropped_d = dropped if self._dropped_d is None \
                        else self._dropped_d + dropped
                if self._entry_slot < 0:
                    # the first inserted row anchors all later walks (what
                    # insert_batch answers too; known here without a sync)
                    self._entry_slot = int(chunk[0])
        self._adj_ledger_stale = True
        METRICS.gauge("hnsw.graph_nodes", region_id=self.id).set(
            float(len(store))
        )

    def _install_adjacency(self, labels: np.ndarray, adj_nodes: np.ndarray,
                           entry_label: int) -> None:
        """Remap a snapshot's node-space adjacency ([n] labels, [n, deg]
        neighbor node indices, -1 padded) into the slot-space device
        adjacency. Caller holds store.device_lock. Nodes whose label has
        no live slot are dropped — their slot may already serve a
        different vector, so they cannot route device-side; the
        need_to_rebuild() trigger bounds how degraded the graph can get.

        Integrity-bracketed like a write path: the install swaps the
        adjacency AND rebuilds its ledger mid-flight — a scrub
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
        self._entry_slot = self._live_entry(entry)
        METRICS.gauge("hnsw.graph_nodes", region_id=self.id).set(float(n))
        # state-integrity: the adjacency artifact resets with every full
        # install (not an incremental write)
        self._adj_ledger_stale = False
        self._seed_adjacency_ledger(full)

    def adjacency_in_sync(self) -> bool:
        """True while the adjacency ledger describes the device adjacency
        (the scrub only checks the artifact then): it is stale between a
        write and the next save, which re-seeds it — staleness, not
        corruption."""
        return self.store.adj is not None and not self._adj_ledger_stale

    # -- device bulk build (ISSUE 18) ----------------------------------------
    def bulk_builder(self, expect_rows: int = 0):
        """Bulk-construction session (manager.build_index feeds scan
        chunks through it): rows stream into the SlotStore and the level-0
        graph builds on device in pow2 batches (ops/graph_build.py).

        Returns None when the index already holds rows (bulk build
        constructs from empty; incremental inserts go through upsert()).
        """
        if len(self.store) or self.store.adj is not None:
            return None
        return _HnswBulkSession(self, expect_rows)

    @integrity_mutation
    def _bulk_put(self, ids: np.ndarray, vectors: np.ndarray) -> np.ndarray:
        """The rows of a bulk session: store put + ledgers, no edges (the
        session's device builder makes them)."""
        return self._put_rows(ids, vectors)[2]

    def _install_built_adjacency(self, adj, entry_slot: int) -> None:
        """Install a bulk-built [capacity, deg] adjacency as the graph.
        Integrity-bracketed like _install_adjacency — same swap
        semantics."""
        self._integrity_begin()
        try:
            store = self.store
            with store.device_lock:
                store.set_graph(adj, self._graph_deg)
                self._entry_slot = self._live_entry(int(entry_slot))
            self._adj_ledger_stale = False
            METRICS.gauge("hnsw.graph_nodes", region_id=self.id).set(
                float(len(store))
            )
            self._seed_adjacency_ledger(adj)
        finally:
            self._integrity_end()

    def _seed_adjacency_ledger(self, adj) -> None:
        """Reset the adjacency artifact to what `adj` ([capacity, deg],
        device or host) holds. Neighbour slots translate to EXTERNAL ids so
        the digest survives slot renumbering across snapshot load — the
        same canonical form the scrub recomputes from the device."""
        from dingo_tpu.obs.integrity import INTEGRITY

        if not INTEGRITY.tracking(self):
            return
        store = self.store
        INTEGRITY.reset_artifact(self, "adjacency")
        live_slots = np.flatnonzero(store.ids_by_slot >= 0)
        if len(live_slots):
            full = np.asarray(adj)
            INTEGRITY.note_write(
                self, "adjacency", store.ids_by_slot[live_slots],
                store.ids_of_slots(full[live_slots]),
            )

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
        from dingo_tpu.trace import TRACER

        # index.dispatch: entry to kernels enqueued (prep, pad and H2D,
        # mask capture, enqueue); NOOP for an unsampled request
        with TRACER.start_child("index.dispatch"):
            queries = self._prep_queries(queries)
            b = queries.shape[0]
            # request-pinned ef wins; else the SLO tuner's override; else
            # the construction-derived default (obs/tuner.py walks ladder
            # values)
            ef = max(int(ef or self.tuned("ef", self.ef_search_default)),
                     int(topk))
            self._count_search()
            if self._entry_slot < 0:
                # no live row: nothing to walk, no launch
                empty = SearchResult(ids=np.empty(0, np.int64),
                                     distances=np.empty(0, np.float32))
                return lambda: [empty] * b
            fetch, finish = self._device_dispatch(
                queries, b, int(topk), filter_spec, ef, staged)
        # the device wait of a sampled request: from here (kernels
        # enqueued, lock released) to the fetch's return in resolve();
        # never a sync of its own (ops/distance.device_wait_begin)
        from dingo_tpu.ops.distance import device_wait_begin

        wait = device_wait_begin("beam_search")
        lease = finish.lease

        def resolve() -> List[SearchResult]:
            try:
                fetched = jax.device_get(fetch)
                wait.end()
                # index.resolve: the host work after the fetch
                with TRACER.start_child("index.resolve"):
                    return finish(fetched)
            finally:
                lease.release()

        return resolve

    def _beam_width(self, ef: int, topk: int) -> int:
        """ef -> beam ladder: the {1,1.5}x-pow2 shape bucket keeps
        steady-state serving on a handful of compiled programs
        (k/beam/max_iters are static)."""
        from dingo_tpu.index.ivf_layout import shape_bucket

        return max(shape_bucket(max(ef, topk)), 1)

    def _finisher(self, lease, queries, b, topk, filter_spec, beam, walk):
        """The host half of a search, run by resolve() on the ONE fetched
        group: slots -> ids, walk diagnostics, heat, quality; `walk` =
        (live rows at dispatch, candidate slots a round gathers)."""
        from dingo_tpu.obs.heat import HEAT, heat_enabled

        store = self.store
        heat_on = heat_enabled()
        if heat_on:
            HEAT.register_layout(self.id, "slot", self._heat_layout)

        def finish(fetched) -> List[SearchResult]:
            dists_h, slots_h = fetched[0], fetched[1]
            stats_h = fetched[2][:b]      # [b, 3]: rounds, visited, live
            self._note_walk_stats(
                stats_h[:, 0], stats_h[:, 1], stats_h[:, 2], beam, *walk
            )
            # the per-query visited count weights the heat touch by how
            # much of the graph the walk crossed
            w = float(max(1.0, np.mean(stats_h[:, 1]) / max(1, beam)))
            if heat_on:
                # result slots mark the graph neighborhoods the walk
                # landed in; arrays ALREADY in this fetch group
                HEAT.observe(self.id, "slot", slots_h[:b], weight=w)
            ids = store.ids_of_slots(slots_h[:b])
            # head-sampled shadow scoring, attributed to the beam bucket
            # (the LADDER value: a raw client-pinned ef would mint
            # unbounded label cardinality; async lane, noop at rate 0)
            from dingo_tpu.obs.quality import QUALITY

            QUALITY.observe_search(
                self, queries, topk, ids, dists_h[:b],
                bucket=f"ef={beam}", filter_spec=filter_spec,
            )
            return [strip_invalid(i, d) for i, d in zip(ids, dists_h[:b])]

        finish.lease = lease
        return finish

    def _device_dispatch(self, queries, b, topk, filter_spec, ef, staged):
        """Enqueue walk + exact rerank; -> (fetch group, finisher)."""
        from dingo_tpu.common.config import FLAGS
        from dingo_tpu.ops.beam import beam_search, round_slots
        from dingo_tpu.ops.topk import begin_host_fetch
        from dingo_tpu.trace import TRACER

        store = self.store
        beam = self._beam_width(ef, topk)
        max_iters = max(1, int(FLAGS.get("hnsw_max_iters")))
        METRICS.counter("hnsw.device_searches", region_id=self.id).add(1)
        prep = self._prep_filter(filter_spec)
        # staging-ring upload (serving pipeline): claimed only when the
        # identity check proves it was built from THESE queries; else the
        # padded rows ride the program's own launch
        qpad = staged.take(queries) if staged is not None else None
        if qpad is None:
            qpad = _pad_batch(queries)
        lease = store.begin_search()
        try:
            # asking for the lock to holding it (timed when sampled)
            with TRACER.start_child("index.lock_wait"):
                store.device_lock.acquire()
            try:
                valid = store.device_mask()
                fmask = self._device_filter_mask(filter_spec, prep)
                if fmask is None:
                    fmask = valid
                if self._precision == "sq8":
                    sq_on, vmin, scale = self._codec()
                    qpad = jnp.asarray(qpad)
                    rslots, hops, vcount, occ = beam_search(
                        store.adj, store.vecs, store.sqnorm, valid, fmask,
                        qpad, self._entry_device(), vmin, scale,
                        beam=beam, max_iters=max_iters,
                        metric=self._kernel_metric, sq=sq_on,
                    )
                    dists, out_slots = self._final_rerank(
                        qpad, rslots, topk, vmin, scale)
                    stats = jnp.stack([hops, vcount, occ], axis=1)
                else:
                    dists, out_slots, stats = hnsw_search_program(
                        store.adj, store.vecs, store.sqnorm, valid, fmask,
                        qpad, self._entry_device(),
                        beam=beam, max_iters=max_iters,
                        metric=self._kernel_metric, k=topk,
                    )
            finally:
                store.device_lock.release()
        except Exception:
            lease.release()
            raise
        # one-sync epilogue: the walk's diagnostics join the SAME D2H copy
        # group as the reply
        fetch = begin_host_fetch(dists, out_slots, stats)
        return fetch, self._finisher(
            lease, queries, b, topk, filter_spec, beam,
            walk=(len(store), round_slots(beam, self._graph_deg)),
        )

    def _final_rerank(self, qpad, cand_slots, topk: int, vmin, scale):
        """The sq8 tier's rerank of a walk's candidate set (ops/rerank.py;
        the float tiers rerank inside ``hnsw_search_program``); caller
        holds store.device_lock. Decodes codes in-kernel (exact for the
        tier) and, when the PR 4 rerank cache holds rows, chains the
        cached f32-exact rerank on top."""
        from dingo_tpu.ops.rerank import sq_rerank_device

        store = self.store
        cache = self._rerank_cache
        if cache is not None and len(cache):
            kk = int(cand_slots.shape[1])
            dists, slots = sq_rerank_device(
                store.vecs, vmin, scale, store.sqnorm, qpad,
                cand_slots, k=kk, metric=self._kernel_metric,
            )
            return self._dispatch_rerank(qpad, dists, slots, topk)
        return sq_rerank_device(
            store.vecs, vmin, scale, store.sqnorm, qpad, cand_slots,
            k=topk, metric=self._kernel_metric,
        )

    def _note_walk_stats(self, hops, vcount, occ, beam, live,
                         round_slots) -> None:
        """Fold one resolved device walk into the metrics plane (called
        from resolve(): the hot path never synchronizes for stats).
        `live` = the index's live rows at dispatch, `round_slots` = the
        candidate slots one round gathers and scores."""
        if not len(hops):
            return
        hops_mean = float(np.mean(hops))
        METRICS.gauge("hnsw.mean_hops", region_id=self.id).set(hops_mean)
        # over LIVE rows, not the slot store's capacity: the reading does
        # not halve when the store doubles
        METRICS.gauge("hnsw.visited_fraction", region_id=self.id).set(
            float(np.mean(vcount)) / max(1, live)
        )
        METRICS.gauge("hnsw.beam_occupancy", region_id=self.id).set(
            float(np.mean(occ)) / max(1, beam)
        )
        # what the implementation gathers, beside the rows it visits
        METRICS.gauge("hnsw.gathered_rows_per_query", region_id=self.id).set(
            hops_mean * round_slots
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
        return self._deleted_slots

    def need_to_rebuild(self) -> bool:
        """Reference trigger: deleted_count > total/2
        (vector_index_hnsw.cc:577-589; note hnswlib's getCurrentElementCount
        includes tombstones, so the threshold is half of TOTAL)."""
        deleted = self.get_deleted_count()
        total = deleted + self.get_count()
        return total > 0 and deleted * 2 > total

    def _graph_snapshot(self, adj_h: np.ndarray):
        """The adjacency as it is written to disk: (labels [n], adjacency
        [n, deg] in the node space of the live rows in slot order, graph
        meta). load() puts the rows back in that order, so node space is
        the loaded store's slot space; an edge to a tombstoned slot is
        dropped."""
        store = self.store
        live = store.ids_by_slot >= 0
        rank = np.cumsum(live, dtype=np.int64).astype(np.int32) - 1
        rows = adj_h[live]
        safe = np.maximum(rows, 0)
        nodes = np.where((rows >= 0) & live[safe], rank[safe], np.int32(-1))
        entry = self._entry_slot
        entry_live = 0 <= entry < len(live) and bool(live[entry])
        return store.ids_by_slot[live], nodes, {
            "deg": self._graph_deg,
            "nodes": int(live.sum()),
            "entry_label": int(store.ids_by_slot[entry]) if entry_live
            else -1,
            "entry_slot": int(rank[entry]) if entry_live else -1,
            "device_graph": True,
        }

    def save(self, path: str) -> None:
        """Rows + the adjacency itself + entry. The copy off the device
        holds ``store.device_lock`` (one hold, so rows and adjacency are
        of one moment), the file writes do not."""
        os.makedirs(path, exist_ok=True)
        store = self.store
        sq = self._precision == "sq8" and store.sq_params is not None
        with store.device_lock:
            snap = store.codes_to_host() if sq else store.to_host()
            # no row was ever written: an empty graph of the same shape
            adj_h = np.asarray(store.adj) if store.adj is not None \
                else np.full((store.capacity, self._graph_deg), -1, np.int32)
            dropped, self._dropped_d = self._dropped_d, None
        if sq:
            np.savez(
                os.path.join(path, "hnsw_vectors.npz"),
                ids=snap["ids"],
                codes=snap["codes"],
                sq_vmin=store.sq_params.vmin,
                sq_scale=store.sq_params.scale,
            )
        else:
            np.savez(
                os.path.join(path, "hnsw_vectors.npz"),
                ids=snap["ids"],
                # f32 on disk (bf16 isn't npz-serializable; widening is
                # lossless)
                vectors=np.asarray(snap["vectors"], np.float32),
            )
        labels, adj, graph_meta = self._graph_snapshot(adj_h)
        blob_path = os.path.join(path, "hnsw_graph.bin")
        if os.path.exists(blob_path):
            # a snapshot of before PR 33 from a store without a TPU held
            # the native graph's blob here: nothing reads it any more
            os.remove(blob_path)
        if self._adj_ledger_stale:
            # writes since the last seeding: the ledger takes the host
            # copy this save made anyway
            self._adj_ledger_stale = False
            self._seed_adjacency_ledger(adj_h)
        if dropped is not None:
            METRICS.counter(
                "build.reverse_dropped", region_id=self.id
            ).add(int(dropped))
        # node space + labels: load() remaps them into the slot space of
        # the store it fills, and serves without a rebuild
        np.savez(
            os.path.join(path, "hnsw_adj.npz"), labels=labels, adj=adj
        )
        meta = self._save_meta()
        meta["hnsw_graph"] = graph_meta
        with open(os.path.join(path, "meta.json"), "w") as f:
            json.dump(meta, f)

    def load(self, path: str) -> None:
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        self._check_meta(meta)
        graph_meta = meta.get("hnsw_graph") or {}
        adj_path = os.path.join(path, "hnsw_adj.npz")
        if not os.path.exists(adj_path) \
                or int(graph_meta.get("deg", -1)) != self._graph_deg:
            # the manager's rebuild from the engine takes over
            raise InvalidParameter(
                "hnsw snapshot without a usable adjacency"
            )
        data = np.load(os.path.join(path, "hnsw_vectors.npz"))
        self.store = _new_tier_store(
            self._precision, self.dimension, self.parameter,
            capacity=max(len(data["ids"]), int(self.parameter.max_elements),
                         1),
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
        self._filter_cache.clear()
        self._deleted_slots = 0
        self._dropped_d = None
        snap = np.load(adj_path)
        # a snapshot of before PR 33 from a store without a TPU carries
        # the same labels + level-0 adjacency (its native graph's export)
        # and a blob, hnsw_graph.bin, that nothing reads any more
        with self.store.device_lock:
            self._install_adjacency(
                np.asarray(snap["labels"], np.int64),
                np.asarray(snap["adj"], np.int32),
                int(graph_meta.get("entry_label", -1)),
            )
        self.apply_log_id = meta["apply_log_id"]
        self.write_count_since_save = 0
        self._integrity_on_restore(meta)


class _HnswBulkSession:
    """One bulk construction: rows in via add(), graph installed by
    finish(). Owns a BulkGraphBuilder over the index's SlotStore;
    index-level bookkeeping (ledgers, rerank offers) stays in TpuHnsw."""

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
