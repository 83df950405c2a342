"""VectorIndexWrapper: lifecycle state machine around a VectorIndex.

Reference: src/vector/vector_index.h:283-506 — tracks ready/stop/build-error
flags, apply_log_id & snapshot_log_id (:467-470), own/share/sibling index
pointers used during region split & merge (:476-480), pending-task counters,
and the save threshold by write count (:497-500). The raft apply handlers
talk to the wrapper, never to the index directly (§3.2 dual-write contract:
RocksDB is the source of truth; the in-memory index is an apply-log-tracked
materialized view).
"""

from __future__ import annotations

import threading
from typing import Callable, List, Optional

import numpy as np


from dingo_tpu.index.base import (
    FilterSpec,
    IndexParameter,
    SearchResult,
    VectorIndex,
    VectorIndexError,
)
from dingo_tpu.index.factory import new_index
from dingo_tpu.ops.distance import metric_ascending


def _merge_results(a: SearchResult, b: SearchResult, topk: int, metric):
    ids = np.concatenate([a.ids, b.ids])
    d = np.concatenate([a.distances, b.distances])
    order = np.argsort(d if metric_ascending(metric) else -d)[:topk]
    return SearchResult(ids[order], d[order])


class VectorIndexWrapper:
    def __init__(self, index_id: int, parameter: IndexParameter,
                 save_write_threshold: int = 10000):
        self.id = index_id
        self.parameter = parameter
        self._lock = threading.RLock()
        self.own_index: Optional[VectorIndex] = None
        #: parent's index served by a child region after split until its own
        #: rebuild completes (SplitHandler SetShareVectorIndex,
        #: raft_apply_handler.cc:372,630)
        self.share_index: Optional["VectorIndexWrapper"] = None
        #: pre-merge sibling's index (raft_apply_handler.cc:1021)
        self.sibling_index: Optional["VectorIndexWrapper"] = None
        self.ready = False
        self.stopped = False
        self.build_error = False
        self.is_switching = False
        self.apply_log_id = 0
        self.snapshot_log_id = 0
        self.pending_tasks = 0
        self.write_count = 0
        self.save_write_threshold = save_write_threshold

    # -- index lifecycle -----------------------------------------------------
    def build_own(self) -> VectorIndex:
        with self._lock:
            self.own_index = new_index(self.id, self.parameter)
            return self.own_index

    def set_own(self, index: VectorIndex) -> None:
        """Atomic switch after rebuild/catch-up (UpdateVectorIndex,
        vector_index_manager.cc:1149 'final round under switching flag')."""
        with self._lock:
            self.own_index = index
            self.apply_log_id = index.apply_log_id
            self.ready = True
            self.build_error = False

    def set_share(self, share: Optional["VectorIndexWrapper"]) -> None:
        with self._lock:
            self.share_index = share

    def set_sibling(self, sibling: Optional["VectorIndexWrapper"]) -> None:
        with self._lock:
            self.sibling_index = sibling

    def active(self) -> Optional[VectorIndex]:
        """Index to serve searches from: own if ready, else shared parent's
        (split children serve the parent's index filtered to their range)."""
        with self._lock:
            if self.ready and self.own_index is not None:
                return self.own_index
            if self.share_index is not None:
                return self.share_index.active()
            return None

    def is_ready(self) -> bool:
        with self._lock:
            return (self.ready and not self.stopped) or (
                self.share_index is not None and self.share_index.is_ready()
            )

    def stop(self) -> None:
        with self._lock:
            self.stopped = True

    # -- writes (apply-log contract, §3.2) ------------------------------------
    def add(self, ids: np.ndarray, vectors: np.ndarray, log_id: int,
            is_upsert: bool = True, scalars=None) -> None:
        """Apply a raft-committed VECTOR_ADD iff log_id advances
        (VectorAddHandler guard: 'if log_id > ApplyLogId',
        raft_apply_handler.cc:1115). `scalars` are the write's scalar rows
        (None: it carried none), which an index that mirrors the region's
        flagged fields takes in the same mutation."""
        with self._lock:
            idx = self.own_index if self.ready else None
            if idx is None:
                # split child before rebuild: writes land in the SHARED
                # parent index (same physical keyspace; both sides filter
                # searches by their own id range) — SetShareVectorIndex flow
                idx = self.active()
            if idx is None or self.stopped:
                return
            if log_id != 0 and log_id <= self.apply_log_id:
                return  # already materialized (snapshot load or replay)
            from dingo_tpu.index.recovery import RECOVERY, DeviceDegraded

            if RECOVERY.is_degraded(self.id):
                # engine (raft/WAL) holds the write; the device index is
                # awaiting re-materialization. apply_log_id does NOT
                # advance — replica digest comparisons happen at equal
                # applied indices, and this index's state describes the
                # LAST advanced log id, not this write.
                return

            def _mutate():
                with self._integrity_bracket(idx):
                    if is_upsert:
                        idx.upsert(ids, vectors)
                    else:
                        idx.add(ids, vectors)
                    idx.set_scalars(ids, scalars)
                    # post-merge: purge absorbed-range versions from the
                    # sibling so search's sibling merge can't resurrect
                    # stale vectors
                    sib = (self.sibling_index.active()
                           if self.sibling_index else None)
                    if sib is not None and sib is not idx:
                        sib.delete(ids)
                    if log_id:
                        self.apply_log_id = log_id
                        if idx is self.own_index:
                            idx.apply_log_id = log_id
                            self._tag_integrity(idx, log_id)

            try:
                # a device OOM walks the recovery ladder (drop rerank ->
                # evict mirrors -> retry); mutations are upserts/deletes,
                # idempotent, so the whole block re-applies safely
                RECOVERY.attempt(self, self.id, _mutate, kind="write")
            except DeviceDegraded:
                return
            self.write_count += len(ids)

    def _integrity_bracket(self, idx):
        """Pending-write bracket spanning the index mutation AND its
        applied-index tag: between the ledger fold (inside idx.upsert)
        and tag_applied the (digest, applied) pair is torn, and a
        heartbeat collected in that window would read a healthy replica
        as DIVERGED — while the bracket is open the ledger withholds its
        digest vector instead (obs/integrity.py heartbeat_view)."""
        import contextlib

        from dingo_tpu.obs.integrity import INTEGRITY

        if idx is not self.own_index:
            return contextlib.nullcontext()

        @contextlib.contextmanager
        def bracket():
            INTEGRITY.note_mutation_begin(idx)
            try:
                yield
            finally:
                INTEGRITY.note_mutation_end(idx)

        return bracket()

    @staticmethod
    def _tag_integrity(idx, log_id: int) -> None:
        """Stamp the state-integrity ledger with the raft applied index
        this write advanced to — still inside the wrapper lock AND the
        pending bracket, so the (digest, applied_index) pair a heartbeat
        reads is always consistent and the coordinator can compare
        replicas at EQUAL applied indices."""
        from dingo_tpu.obs.integrity import INTEGRITY

        INTEGRITY.tag_applied(idx, log_id)

    def delete(self, ids: np.ndarray, log_id: int) -> None:
        with self._lock:
            idx = self.own_index if self.ready else None
            if idx is None:
                idx = self.active()
            if idx is None or self.stopped:
                return
            if log_id != 0 and log_id <= self.apply_log_id:
                return
            from dingo_tpu.index.recovery import RECOVERY, DeviceDegraded

            if RECOVERY.is_degraded(self.id):
                return   # same contract as add(): engine keeps the delete

            def _mutate():
                with self._integrity_bracket(idx):
                    idx.delete(ids)
                    sib = (self.sibling_index.active()
                           if self.sibling_index else None)
                    if sib is not None and sib is not idx:
                        sib.delete(ids)
                    if log_id:
                        self.apply_log_id = log_id
                        if idx is self.own_index:
                            idx.apply_log_id = log_id
                            self._tag_integrity(idx, log_id)

            try:
                RECOVERY.attempt(self, self.id, _mutate, kind="write")
            except DeviceDegraded:
                return
            self.write_count += len(ids)

    # -- reads ---------------------------------------------------------------
    def search(
        self,
        queries: np.ndarray,
        topk: int,
        filter_spec: Optional[FilterSpec] = None,
        **kw,
    ) -> List[SearchResult]:
        idx = self.active()
        if idx is None:
            raise VectorIndexError(f"vector index {self.id} not ready")
        results = idx.search(queries, topk, filter_spec, **kw)
        sibling = self.sibling_index
        if sibling is not None and sibling.active() is not None:
            # post-merge: the absorbed region's index serves its id range
            # until the target rebuild covers it (CommitMerge sibling flow)
            other = sibling.active().search(queries, topk, filter_spec, **kw)
            results = [
                _merge_results(a, b, topk, self.parameter.metric)
                for a, b in zip(results, other)
            ]
        return results

    def search_async(
        self,
        queries: np.ndarray,
        topk: int,
        filter_spec: Optional[FilterSpec] = None,
        staged=None,
        **kw,
    ) -> Callable[[], List[SearchResult]]:
        """Dispatch-now/resolve-later arm of search() for the serving
        pipeline: kernels enqueue here, the returned thunk performs the
        single host sync. The sibling-merge window (post-merge, absorbed
        region still serving its id range) falls back to a thunk around
        the serial path — merging two result sets needs both on host
        anyway, and the window is short-lived."""
        idx = self.active()
        if idx is None:
            raise VectorIndexError(f"vector index {self.id} not ready")
        sibling = self.sibling_index
        if sibling is not None and sibling.active() is not None:
            return lambda: self.search(queries, topk, filter_spec, **kw)
        dispatch = getattr(idx, "search_async", None)
        if dispatch is None:
            return lambda: idx.search(queries, topk, filter_spec, **kw)
        return dispatch(queries, topk, filter_spec, staged=staged, **kw)

    # -- policies --------------------------------------------------------------
    def need_to_save(self) -> bool:
        idx = self.own_index
        if idx is None:
            return False
        log_behind = self.apply_log_id - self.snapshot_log_id
        return self.write_count >= self.save_write_threshold or idx.need_to_save(
            log_behind
        )

    def need_to_rebuild(self) -> bool:
        idx = self.own_index
        return idx is not None and idx.need_to_rebuild()

    def get_count(self) -> int:
        idx = self.active()
        return idx.get_count() if idx else 0

    def get_memory_size(self) -> int:
        idx = self.own_index
        return idx.get_memory_size() if idx else 0

    def get_device_memory_size(self) -> int:
        """Device bytes of the OWN index (a shared parent's arrays are
        accounted on the parent's region, not double-counted here)."""
        idx = self.own_index
        return idx.get_device_memory_size() if idx else 0
