"""TpuHnsw: a graph index whose TPU arm keeps ONE graph, on the device.

Reference: VectorIndexHnsw (src/vector/vector_index_hnsw.{h,cc} — wraps
hnswlib::HierarchicalNSW with L2Space/InnerProductSpace,
vector_index_hnsw.cc:154-181; NeedToRebuild when deleted count exceeds half
the TOTAL element count :577-589; hnswlib-file Save/Load :310).

Two arms share one SlotStore + one exact device rerank; which one a
process walks follows from the backend it observes (``hnsw.device_search``
and ``hnsw.device_build``, both ``auto`` = TPU-only):

  TPU arm (both on) — the level-0 adjacency in slot space
  (``SlotStore.adj``, dense ``[capacity, deg]`` int32, deg = nlinks*2) IS
  the graph. ``upsert`` puts the rows and inserts them into the live
  adjacency in pow2 batches (ops/graph_build.insert_batch: candidate
  discovery by the lockstep beam walk, occlusion pruning, reverse edges;
  the adjacency is donated under ``store.device_lock``), so an
  acknowledged row is found by the next search with no O(N) step;
  ``delete`` tombstones the slot and the walk routes around it; searches
  run as one jitted lockstep beam search (ops/beam.py: frontier gather on
  the adjacency, candidate distances via one einsum against the SlotStore,
  a per-query packed visited bitmask, masked top-k beam updates, a fixed
  iteration cap with early exit); ``save`` persists rows + adjacency +
  entry and ``load`` serves from them. No native graph is fed, exported or
  written: ``hnsw.native_adds``, ``hnsw.adjacency_rebuilds`` and
  ``hnsw.host_searches`` stay 0.

  CPU arm (either off) and parity oracle — graph construction and beam
  search run in our own C++ NSW implementation (native/hnsw/hnsw.cc, an
  original implementation, not a copy of hnswlib). The graph returns an
  over-fetched candidate set (ef per query) and the device re-ranks it.
  With ``hnsw.device_search`` forced on over a native-built graph, the
  native level-0 adjacency exports into the device mirror, keyed on
  (native graph version, store mutation version) and lazily re-exported
  on the first search after a write — the IVF `_ensure_view` discipline.
  A device-owned graph met by this arm (a flag flipped, a host search
  asked for) is replayed into the native graph first
  (``_ensure_native_graph``, ``build.backfills``).

Both arms end in the SAME exact device rerank (ops/rerank.py), so the
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
from dingo_tpu.obs.sentinel import sentinel_jit
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
        self._graph = self._new_native_graph()
        self._kernel_metric = p.metric
        self._kernel_nbits = 0
        #: level-0 degree cap of the exported adjacency (hnsw M0 = 2*M)
        self._graph_deg = max(1, int(p.nlinks)) * 2
        #: (native graph version, store mutation version) the device
        #: adjacency mirror was built against; None = never built
        self._graph_key = None
        self._entry_slot = -1
        #: rows tombstoned while the device adjacency was the graph
        self._deleted_slots = 0
        #: the device adjacency is THE graph and the native graph does not
        #: hold it (TPU-arm writes, a device bulk build, a loaded device
        #: snapshot) — the first CPU-arm use (write, host search, save)
        #: back-fills the native graph from the store's rows
        self._native_pending = False
        #: TPU-arm writes since the adjacency ledger was last seeded: the
        #: scrub and the snapshot skip the artifact until save() re-seeds
        #: it from the host copy it takes anyway
        self._adj_ledger_stale = False
        #: reverse edges dropped by TPU-arm inserts (device scalar, folded
        #: into ``build.reverse_dropped`` at save: no sync on a write)
        self._dropped_d = None
        self._identity_codec = None
        #: (entry slot, its device scalar): uploaded when the entry moves,
        #: not with every search
        self._entry_cached = (None, None)
        # the counters that say which arm served exist from the start: an
        # arm that never ran reads 0, not "no such series"
        for name in ("native_adds", "adjacency_rebuilds", "host_searches",
                     "device_searches"):
            METRICS.counter("hnsw." + name, region_id=index_id).add(0)
        #: fingerprint -> (store version, numpy mask, device mask or None)
        self._filter_cache: dict = {}

    def _new_native_graph(self):
        p = self.parameter
        return _lib().hnsw_new(
            p.dimension, 0 if p.metric is Metric.L2 else 1, p.nlinks,
            p.efconstruction, self.id,
        )

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

    def _tpu_arm(self) -> bool:
        """True where the device adjacency is the one graph: searches walk
        it (``hnsw.device_search``) and writes insert into it
        (``hnsw.device_build``); both ``auto`` = TPU-only, so the arm
        follows the backend the process observes."""
        from dingo_tpu.common.config import (
            hnsw_device_build_enabled,
            hnsw_device_enabled,
        )

        return hnsw_device_build_enabled() and hnsw_device_enabled()

    def _put_rows(self, ids: np.ndarray, vectors: np.ndarray):
        """Store put + rerank offer + quality/integrity ledgers: what every
        write does whichever graph takes the edges. -> (ids, vectors,
        slots)"""
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

    def _native_add(self, ids: np.ndarray, vectors: np.ndarray) -> None:
        _lib().hnsw_add(
            self._graph,
            len(ids),
            ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            vectors.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        )
        METRICS.counter("hnsw.native_adds", region_id=self.id).add(len(ids))

    @integrity_mutation
    def upsert(self, ids: np.ndarray, vectors: np.ndarray) -> None:
        if self._tpu_arm():
            self._own_device_graph()
            _, _, slots = self._put_rows(ids, vectors)
            self._device_insert(np.asarray(slots, np.int32))
            return
        self._ensure_native_graph()
        ids, vectors, _ = self._put_rows(ids, vectors)
        self._native_add(ids, vectors)

    @integrity_mutation
    def delete(self, ids: np.ndarray) -> None:
        tpu = self._tpu_arm() and self._native_pending
        if not tpu:
            self._ensure_native_graph()
        ids = np.ascontiguousarray(ids, np.int64)
        slots = self.store.remove_slots(ids)
        removed = int((slots >= 0).sum())
        self._invalidate_rerank(slots)
        from dingo_tpu.obs.quality import QUALITY

        QUALITY.observe_delete(self, ids)
        self._integrity_delete(ids)
        if tpu:
            # a tombstone: the slot leaves the validity mask, the walk
            # routes around it, and neighbours' edges to it now translate
            # to no id — the adjacency ledger waits for the next save
            self._deleted_slots += removed
            self._adj_ledger_stale = True
        else:
            _lib().hnsw_delete(
                self._graph, len(ids),
                ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            )
        self.write_count_since_save += removed

    # -- TPU arm: the device adjacency as the one graph ----------------------
    def _own_device_graph(self) -> None:
        """Make the device adjacency the graph writes go into. A fresh
        index gets an empty adjacency; a native-built graph (a loaded
        CPU-arm snapshot, a flag flipped under a live index) hands its
        level-0 export over once. From here the native graph is stale
        (`_native_pending`) until a CPU-arm use replays the rows."""
        store = self.store
        if self._native_pending and store.adj is not None:
            return
        with store.device_lock:
            if int(_lib().hnsw_total_count(self._graph)):
                self._ensure_device_graph()
            elif store.adj is None:
                store.set_graph(
                    np.full((store.capacity, self._graph_deg), -1, np.int32),
                    self._graph_deg,
                )
            self._native_pending = True

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
        # swap (a full install, not an incremental write)
        self._adj_ledger_stale = False
        self._seed_adjacency_ledger(full)

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
        sides gate. A device-owned graph (`_native_pending`) is never
        re-exported: there is nothing to re-export it from."""
        if self._native_pending and self.store.adj is not None:
            return
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
        a pending lazy re-export is staleness, not corruption). A device-
        owned graph is in sync with itself; its ledger is stale between a
        TPU-arm write and the next save, which re-seeds it."""
        if self.store.adj is None:
            return False
        if self._native_pending:
            return not self._adj_ledger_stale
        return self._graph_key == (
            int(_lib().hnsw_graph_version(self._graph)),
            self.store.mutation_version,
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
        constructs from empty; incremental inserts go through upsert()).
        """
        from dingo_tpu.common.config import hnsw_device_build_enabled

        if not hnsw_device_build_enabled():
            return None
        if len(self.store) or int(_lib().hnsw_total_count(self._graph)):
            return None
        return _HnswBulkSession(self, expect_rows)

    @integrity_mutation
    def _bulk_put(self, ids: np.ndarray, vectors: np.ndarray) -> np.ndarray:
        """The rows of a bulk session: store put + ledgers, no edges (the
        session's device builder makes them)."""
        return self._put_rows(ids, vectors)[2]

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

    def _ensure_native_graph(self) -> None:
        """Replay the store's rows into the native graph when the device
        adjacency has been the graph (a device bulk build, TPU-arm writes,
        a loaded device snapshot) — triggered by the first CPU-arm use
        (write, host search, save), never on the TPU arm: a device-served
        region does not pay it. A native graph that holds older rows is
        replaced, not patched. Streams BACKFILL_CHUNK rows per native add
        call (O(chunk) host memory); quantized tiers replay the decoded
        surrogate, the store's tier semantics. The handover COMPLETES
        here: once the native graph holds the rows, its level-0 export
        re-installs as the device mirror (one ordinary lazy re-export),
        so every representation — device walk, host beam, snapshot,
        integrity adjacency digest — describes the same topology from
        this point on."""
        if not self._native_pending:
            return
        self._native_pending = False
        if int(_lib().hnsw_total_count(self._graph)):
            _lib().hnsw_free(self._graph)
            self._graph = self._new_native_graph()
        store = self.store
        live = np.flatnonzero(store.valid_h)
        ids = store.ids_by_slot[live]
        for s in range(0, len(ids), BACKFILL_CHUNK):
            chunk = np.ascontiguousarray(ids[s:s + BACKFILL_CHUNK],
                                         np.int64)
            _, rows = store.gather(chunk)
            self._native_add(chunk, np.ascontiguousarray(rows, np.float32))
        self._deleted_slots = 0
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
            if self._device_search_on():
                fetch, finish = self._device_dispatch(
                    queries, b, int(topk), filter_spec, ef, staged)
                name = "beam_search"
            else:
                fetch, finish = self._host_dispatch(
                    queries, b, int(topk), filter_spec, ef, staged)
                name = "rerank"
        # the device wait of a sampled request: from here (kernels
        # enqueued, lock released) to the fetch's return in resolve();
        # never a sync of its own (ops/distance.device_wait_begin)
        from dingo_tpu.ops.distance import device_wait_begin

        wait = device_wait_begin(name)
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

    def _finisher(self, lease, queries, b, topk, filter_spec, beam,
                  walk=None):
        """The host half of a search, run by resolve() on the ONE fetched
        group: slots -> ids, heat, quality; `walk` = (capacity-free walk
        diagnostics follow the reply in the same group)."""
        from dingo_tpu.obs.heat import HEAT, heat_enabled

        store = self.store
        heat_on = heat_enabled()
        if heat_on:
            HEAT.register_layout(self.id, "slot", self._heat_layout)

        def finish(fetched) -> List[SearchResult]:
            dists_h, slots_h = fetched[0], fetched[1]
            w = 1.0
            if walk is not None:
                stats_h = fetched[2][:b]      # [b, 3]: rounds, visited, live
                self._note_walk_stats(
                    stats_h[:, 0], stats_h[:, 1], stats_h[:, 2], beam, *walk
                )
                # the per-query visited count weights the heat touch by
                # how much of the graph the walk crossed
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
                self._ensure_device_graph()
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
                        qpad, rslots, topk)
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

    def _host_dispatch(self, queries, b, topk, filter_spec, ef, staged):
        """Native graph candidates + enqueued exact rerank; -> (fetch
        group, finisher)."""
        from dingo_tpu.trace import TRACER

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
            with TRACER.start_child("index.lock_wait"):
                store.device_lock.acquire()    # vecs/sqnorm are donatable
            try:
                dists, out_slots = self._final_rerank(
                    qpad, jnp.asarray(cand), topk
                )
            finally:
                store.device_lock.release()
        except Exception:
            lease.release()
            raise
        from dingo_tpu.ops.topk import begin_host_fetch

        return begin_host_fetch(dists, out_slots), self._finisher(
            lease, queries, b, topk, filter_spec,
            self._beam_width(ef, topk),
        )

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
        return int(_lib().hnsw_deleted_count(self._graph)) \
            + self._deleted_slots

    def get_memory_size(self) -> int:
        return self.store.memory_size() + int(_lib().hnsw_memory(self._graph))

    def need_to_rebuild(self) -> bool:
        """Reference trigger: deleted_count > total/2
        (vector_index_hnsw.cc:577-589; note hnswlib's getCurrentElementCount
        includes tombstones, so the threshold is half of TOTAL)."""
        deleted = self.get_deleted_count()
        total = deleted + self.get_count()
        return total > 0 and deleted * 2 > total

    def _save_meta(self, graph: Optional[dict] = None) -> dict:
        meta = super()._save_meta()
        meta["hnsw_graph"] = graph or {
            "deg": self._graph_deg,
            "nodes": int(_lib().hnsw_total_count(self._graph)),
            "entry_label": int(_lib().hnsw_entry_label(self._graph)),
        }
        return meta

    def _device_graph_snapshot(self, adj_h: np.ndarray):
        """The device adjacency as it is written to disk: (labels [n],
        adjacency [n, deg] in the node space of the live rows in slot
        order, graph meta). load() puts the rows back in that order, so
        node space is the loaded store's slot space; an edge to a
        tombstoned slot is dropped."""
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
        """TPU arm: rows + the device adjacency itself + entry; no native
        blob is made or written. CPU arm: rows + native blob + its level-0
        export, after the back-fill a device-built graph owes it. The
        copy off the device holds ``store.device_lock`` (one hold, so rows
        and adjacency are of one moment), the file writes do not."""
        device = self._native_pending and self._tpu_arm()
        if not device:
            self._ensure_native_graph()
        os.makedirs(path, exist_ok=True)
        store = self.store
        sq = self._precision == "sq8" and store.sq_params is not None
        adj_h = dropped = None
        with store.device_lock:
            snap = store.codes_to_host() if sq else store.to_host()
            if device:
                adj_h = np.asarray(store.adj)
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
        blob_path = os.path.join(path, "hnsw_graph.bin")
        graph_meta = None
        if device:
            labels, adj, graph_meta = self._device_graph_snapshot(adj_h)
            if os.path.exists(blob_path):
                os.remove(blob_path)   # an older CPU-arm snapshot's
            if self._adj_ledger_stale:
                # TPU-arm writes since the last seeding: the ledger takes
                # the host copy this save made anyway
                self._adj_ledger_stale = False
                self._seed_adjacency_ledger(adj_h)
            if dropped is not None:
                METRICS.counter(
                    "build.reverse_dropped", region_id=self.id
                ).add(int(dropped))
        else:
            size = _lib().hnsw_save_size(self._graph)
            buf = np.empty(size, np.uint8)
            written = _lib().hnsw_save(
                self._graph,
                buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            )
            with open(blob_path, "wb") as f:
                f.write(buf[:written].tobytes())
            labels, adj = self._export_level0()
        # the adjacency rides the snapshot (node space + labels) so load()
        # serves device searches without a rebuild or a native re-export
        np.savez(
            os.path.join(path, "hnsw_adj.npz"), labels=labels, adj=adj
        )
        with open(os.path.join(path, "meta.json"), "w") as f:
            json.dump(self._save_meta(graph_meta), f)

    def load(self, path: str) -> None:
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        self._check_meta(meta)
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
        graph_meta = meta.get("hnsw_graph") or {}
        device = bool(graph_meta.get("device_graph"))
        if device:
            # a device-graph snapshot carries no native blob: the native
            # graph starts empty and is back-filled only by a CPU-arm use
            new_graph = self._new_native_graph()
        else:
            blob = np.fromfile(os.path.join(path, "hnsw_graph.bin"),
                               np.uint8)
            new_graph = _lib().hnsw_load(
                blob.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                len(blob),
            )
            if not new_graph:
                raise InvalidParameter("bad hnsw graph blob")
        _lib().hnsw_free(self._graph)
        self._graph = new_graph
        self._filter_cache.clear()
        self._graph_key = None
        self._entry_slot = -1
        self._deleted_slots = 0
        self._dropped_d = None
        self._native_pending = False   # the loaded blob IS the graph
        adj_path = os.path.join(path, "hnsw_adj.npz")
        installed = False
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
            installed = True
        if device:
            if not installed:
                raise InvalidParameter(
                    "device-graph snapshot without a usable adjacency"
                )
            self._native_pending = True    # the adjacency IS the graph
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
