"""Device-resident slot store: the IndexIDMap2 equivalent.

The reference wraps faiss indexes in faiss::IndexIDMap2 (vector_index_flat.h:
57-127) to map external vector ids <-> internal sequential slots. Here the
mapping is split to fit TPU + XLA realities (premises: row-scatter into a
big array, device->host materialization per call and H2D bandwidth are all
expensive — none is measured on the current machine, ROADMAP C9):

  host side   — ids_by_slot np.int64[capacity] (-1 = empty) + dict id->slot +
                free-slot list + validity bitmap. 64-bit external ids NEVER
                go on device (JAX x64-off truncates them); kernels work in
                slot space and the host translates slots->ids after top-k.
                The validity bitmap lives host-side and is lazily refreshed
                to device only when dirty (uploading [cap] bools is far
                cheaper than TPU scatter).
  device side — vecs[capacity, d] and sqnorm[capacity] f32 (cached ||x||^2),
                updated by contiguous-run dynamic_update_slice writes with
                donated buffers (TPU scatter is the slow path; appends are
                contiguous because free slots are handed out ascending).

Capacity grows by doubling (static shapes per power-of-two bucket keep the
XLA compile cache bounded — SURVEY.md §7 'capacity-bucketed arrays').
Deletes are tombstones in the host bitmap; compaction happens on
save/rebuild, mirroring the reference's rebuild-on-too-many-deletes policy.
"""

from __future__ import annotations

import threading

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from dingo_tpu.obs.sentinel import sentinel_jit

MIN_CAPACITY = 4096
#: Max rows per dynamic_update_slice program (pads to pow2 buckets up to this).
MAX_WRITE_BUCKET = 4096


@sentinel_jit("index.slot_store.write_run",
              static_argnames=("nrows",), donate_argnums=(0, 1))
def _write_run(vecs, sqnorm, rows, start, lo, hi, nrows):
    """Blend rows[lo:hi] of the padded [nrows] window into vecs/sqnorm at
    window position `start` (i.e. slots start+lo .. start+hi-1).

    Rows outside [lo, hi) keep the old content — the pad can sit at either
    end, which lets the caller shift the window left at the capacity
    boundary instead of letting dynamic_update_slice clamp (a clamped start
    silently lands the write one slot off and corrupts neighbors).
    Donated buffers -> in-place on device.

    sqnorm caches the norms of the STORED rows (post dtype cast): a bf16
    store's scan kernels read bf16-quantized values, so ||bf16(x)||^2 is
    the self-consistent cache — the sq8 tier's decoded-norm convention
    applied to the bf16 tier (norms of the original f32 rows drift ~1e-3
    relative, which breaks the pruned scan's partial-sum bookkeeping and
    mis-ranks near-ties either way)."""
    d = vecs.shape[1]
    stored = rows.astype(vecs.dtype)
    rows32 = stored.astype(jnp.float32)
    old = lax.dynamic_slice(vecs, (start, 0), (nrows, d))
    idx = jnp.arange(nrows)
    keep = (idx >= lo) & (idx < hi)
    blend = jnp.where(keep[:, None], stored, old)
    vecs = lax.dynamic_update_slice(vecs, blend, (start, 0))
    sq = jnp.einsum(
        "ld,ld->l", rows32, rows32, precision=jax.lax.Precision.HIGHEST
    )
    old_sq = lax.dynamic_slice(sqnorm, (start,), (nrows,))
    sqnorm = lax.dynamic_update_slice(
        sqnorm, jnp.where(keep, sq, old_sq), (start,)
    )
    return vecs, sqnorm


@sentinel_jit("index.slot_store.write_run_presq",
              static_argnames=("nrows",), donate_argnums=(0, 1))
def _write_run_presq(vecs, sqnorm, rows, row_sq, start, lo, hi, nrows):
    """`_write_run` variant taking PRECOMPUTED row norms: quantized stores
    write uint8 codes but must cache the norms of the DECODED rows (the
    values the distance kernels actually scan), which the device cannot
    derive from the codes row-dtype-agnostically. Same window/blend/donate
    contract as _write_run."""
    d = vecs.shape[1]
    old = lax.dynamic_slice(vecs, (start, 0), (nrows, d))
    idx = jnp.arange(nrows)
    keep = (idx >= lo) & (idx < hi)
    blend = jnp.where(keep[:, None], rows.astype(vecs.dtype), old)
    vecs = lax.dynamic_update_slice(vecs, blend, (start, 0))
    old_sq = lax.dynamic_slice(sqnorm, (start,), (nrows,))
    sqnorm = lax.dynamic_update_slice(
        sqnorm, jnp.where(keep, row_sq, old_sq), (start,)
    )
    return vecs, sqnorm


@sentinel_jit("index.slot_store.write_run_blk",
              static_argnames=("nrows",), donate_argnums=(0, 1))
def _write_run_blk(vecs_blk, bsq_blk, rows_blk, row_bsq, start, lo, hi, nrows):
    """Blocked-mirror arm of _write_run: blend rows [lo, hi) of the padded
    window into the dimension-blocked arrays ([nblk, capacity, dblk] data +
    [nblk, capacity] per-block norms) at window position `start` along the
    slot axis. Same window/blend/donate contract as _write_run."""
    nblk, _, dblk = vecs_blk.shape
    old = lax.dynamic_slice(vecs_blk, (0, start, 0), (nblk, nrows, dblk))
    idx = jnp.arange(nrows)
    keep = (idx >= lo) & (idx < hi)
    blend = jnp.where(keep[None, :, None], rows_blk.astype(vecs_blk.dtype),
                      old)
    vecs_blk = lax.dynamic_update_slice(vecs_blk, blend, (0, start, 0))
    old_b = lax.dynamic_slice(bsq_blk, (0, start), (nblk, nrows))
    bsq_blk = lax.dynamic_update_slice(
        bsq_blk, jnp.where(keep[None, :], row_bsq, old_b), (0, start)
    )
    return vecs_blk, bsq_blk


class SlotStore:
    def __init__(self, dim: int, dtype=jnp.float32, capacity: int = MIN_CAPACITY,
                 blocked: Optional[bool] = None):
        self.dim = dim
        self.dtype = dtype
        self.capacity = max(MIN_CAPACITY, _next_pow2(capacity))
        # Dimension-blocked scan mirror (PDX vertical layout, ops/blocked.py):
        # [nblk, capacity, dblk] data + [nblk, capacity] per-block norms,
        # read by the pruned FLAT streaming kernel. Decided once at
        # construction (conf vector.blocked_layout; `blocked` forces) —
        # None when off / dtype unsupported / dimension doesn't block.
        self.dim_block: Optional[int] = None
        self.nblk = 0
        self.vecs_blk: Optional[jax.Array] = None
        self.bsq_blk: Optional[jax.Array] = None
        if blocked is None:
            from dingo_tpu.common.config import blocked_layout_enabled

            blocked = blocked_layout_enabled()
        if blocked and self._blocked_dtype_ok():
            from dingo_tpu.ops.blocked import resolve_dim_block

            self.dim_block = resolve_dim_block(dim)
            if self.dim_block:
                self.nblk = dim // self.dim_block
                self.vecs_blk = jnp.zeros(
                    (self.nblk, self.capacity, self.dim_block), self.dtype
                )
                self.bsq_blk = jnp.zeros(
                    (self.nblk, self.capacity), jnp.float32
                )
        # Graph adjacency mirror (device HNSW tier, index/hnsw.py): dense
        # [capacity, deg] int32 slot-space neighbor lists, -1 padded, read
        # by the batched beam kernel (ops/beam.py). Installed/refreshed by
        # set_graph(); grows with capacity like the blocked mirror above.
        self.graph_deg = 0
        self.adj: Optional[jax.Array] = None
        # Monotonic host-mutation counter: bumped by put/remove/growth.
        # Cache keys that depend on the slot<->id mapping (the HNSW
        # filter-mask cache, the device adjacency mirror) key on it the
        # way IVF caches key on view.version.
        self.mutation_version = 0
        self.vecs, self.sqnorm = self._alloc_storage(self.capacity)
        self.ids_by_slot = np.full((self.capacity,), -1, np.int64)
        self.valid_h = np.zeros((self.capacity,), np.bool_)
        self._dmask: Optional[jax.Array] = None   # lazy device copy of valid_h
        self._id_to_slot: dict[int, int] = {}
        self._free: list[int] = list(range(self.capacity - 1, -1, -1))
        # Epoch-based reclamation: slots freed while searches are in flight
        # park in limbo so an async resolve never sees a reassigned slot
        # (it translates them to -1/dropped instead of to the wrong id).
        self._inflight: int = 0
        self._limbo: list[int] = []
        # Guards the _inflight/_limbo/_free transitions: end_search's
        # check-then-drain and remove_slots' limbo-vs-free choice are
        # read-modify-write pairs, and with the serving pipeline's
        # completion lane they run on a thread of their own — unlocked,
        # a release racing a writer could drain a slot to _free while
        # the search that must still translate it is in flight.
        self._lease_lock = threading.Lock()
        # Serializes DONATED device writes against kernel dispatch: the DUS
        # write path donates vecs/sqnorm (invalidating the old Array), so a
        # concurrent search must not dispatch with a stale reference (the
        # reference uses a per-index RWLock, vector_index_flat.h:129).
        # Held only across dispatch, never across device execution.
        self.device_lock = threading.RLock()
        # H2D hook for the write programs' row upload: default is a plain
        # jnp.asarray; the tier ladder's promotion path temporarily swaps
        # in a staging-ring uploader (common/pipeline.StagingRing) so bulk
        # code ingest overlaps the previous chunk's donated write program
        # instead of serializing copy-then-dispatch (index/tiering.py).
        self._upload = jnp.asarray

    # -- storage hooks (HostSlotStore overrides with numpy) ----------------
    def _blocked_dtype_ok(self) -> bool:
        """Tiers whose scan kernels can read a blocked mirror: f32/bf16
        rows (binary ±1 int8 stays on the XLA path; HostSlotStore has no
        device arrays at all). SqSlotStore overrides — its uint8 codes
        decode inside the kernel."""
        return jnp.dtype(self.dtype) in (jnp.float32, jnp.bfloat16)

    def _alloc_storage(self, capacity: int):
        return (
            jnp.zeros((capacity, self.dim), self.dtype),
            jnp.zeros((capacity,), jnp.float32),
        )

    def _grow_storage(self, pad: int):
        return (
            jnp.concatenate(
                [self.vecs, jnp.zeros((pad, self.dim), self.dtype)]
            ),
            jnp.concatenate([self.sqnorm, jnp.zeros((pad,), jnp.float32)]),
        )

    # -- bookkeeping -------------------------------------------------------
    def __len__(self) -> int:
        return len(self._id_to_slot)

    def __contains__(self, vid: int) -> bool:
        return int(vid) in self._id_to_slot

    def slots_of(self, ids: np.ndarray) -> np.ndarray:
        return np.asarray(
            [self._id_to_slot.get(int(i), -1) for i in ids], np.int64
        )

    def ids_of_slots(self, slots: np.ndarray) -> np.ndarray:
        """Translate kernel-space slots (-1 allowed) back to external ids."""
        safe = np.where(slots >= 0, slots, 0)
        out = self.ids_by_slot[safe]
        return np.where(slots >= 0, out, -1)

    def device_mask(self) -> jax.Array:
        """Validity bitmap on device, refreshed only when host state changed."""
        if self._dmask is None:
            self._dmask = jnp.asarray(self.valid_h)
        return self._dmask

    def canonical_rows(self, rows: np.ndarray) -> np.ndarray:
        """Stored-form payload of prepped input rows — the exact bytes the
        device arrays hold after a put() of `rows` (the state-integrity
        ledger digests these so an incremental digest and a device-state
        readback agree bit-for-bit). Float stores cast to the storage
        dtype; SqSlotStore overrides to encode."""
        return np.asarray(rows).astype(np.dtype(self.dtype), copy=False)

    def memory_size(self) -> int:
        itemsize = jnp.zeros((), self.dtype).dtype.itemsize
        size = self.capacity * (self.dim * itemsize + 8 + 4 + 1)
        if self.vecs_blk is not None:
            # blocked scan mirror: one more copy of the rows + block norms
            size += self.capacity * (self.dim * itemsize + self.nblk * 4)
        if self.adj is not None:
            size += self.capacity * self.graph_deg * 4
        return size

    def set_graph(self, adj: np.ndarray, deg: int) -> None:
        """Install the slot-space adjacency: [capacity, deg] int32
        neighbor slots, -1 padded. The owning index (TpuHnsw) hands over
        an empty one before its first insert, a snapshot's on load and a
        bulk session's at its end; inserts then donate it in place."""
        if adj.shape != (self.capacity, deg):
            raise ValueError(
                f"adjacency shape {adj.shape} != ({self.capacity}, {deg})"
            )
        with self.device_lock:
            self.graph_deg = deg
            self.adj = jnp.asarray(adj, jnp.int32)

    def reserve(self, capacity: int) -> None:
        """Pre-size device arrays (bulk ingest avoids per-growth recompiles
        of the write program — each growth step re-specializes the DUS)."""
        if capacity > self.capacity:
            self._grow(capacity)

    # -- mutation ----------------------------------------------------------
    def put(self, ids: np.ndarray, vectors: np.ndarray) -> np.ndarray:
        """Insert/replace rows; returns assigned slots. Contiguous slot runs
        are written with dynamic_update_slice (fresh appends are one run);
        scattered overwrites degrade to per-run writes."""
        n = len(ids)
        if n == 0:
            return np.empty(0, np.int64)
        slots = np.empty(n, np.int64)
        for i, vid in enumerate(ids):
            vid = int(vid)
            s = self._id_to_slot.get(vid)
            if s is None:
                if not self._free:
                    self._grow(max(self.capacity * 2, _next_pow2(self.capacity + n)))
                s = self._free.pop()
                self._id_to_slot[vid] = s
                self.ids_by_slot[s] = vid
            slots[i] = s
        vectors = np.asarray(vectors)
        # Sort into ascending slot order, then split into contiguous runs.
        order = np.argsort(slots, kind="stable")
        sslots = slots[order]
        svecs = vectors[order]
        run_starts = np.flatnonzero(np.diff(sslots) != 1) + 1
        with self.device_lock:
            for seg_lo, seg_hi in zip(
                np.concatenate([[0], run_starts]),
                np.concatenate([run_starts, [n]]),
            ):
                self._write_segment(int(sslots[seg_lo]), svecs[seg_lo:seg_hi])
        self.valid_h[slots] = True
        self._dmask = None
        self.mutation_version += 1
        return slots

    def _write_segment(self, start: int, rows: np.ndarray) -> None:
        """One contiguous run, chunked into pow2 buckets <= MAX_WRITE_BUCKET.
        Callers arrive via put(), which holds device_lock."""
        off = 0
        total = rows.shape[0]
        while off < total:
            chunk = min(MAX_WRITE_BUCKET, total - off)
            bucket = min(MAX_WRITE_BUCKET, _next_pow2(chunk))
            padded = rows[off:off + chunk]
            if bucket != chunk:
                padded = np.concatenate(
                    [padded, np.zeros((bucket - chunk, self.dim), padded.dtype)]
                )
            win_start = start + off
            lo = 0
            if win_start + bucket > self.capacity:
                # Shift the window left so it stays in bounds; the pad moves
                # to the front (dynamic_update_slice would otherwise clamp
                # the start and shift the whole write — data corruption).
                lo = win_start + bucket - self.capacity
                win_start = self.capacity - bucket
                padded = np.roll(padded, lo, axis=0)
            self._dispatch_write(padded, win_start, lo, chunk, bucket)
            off += chunk

    def _dispatch_write(self, padded, win_start, lo, chunk, bucket) -> None:
        """One donated write program over a padded pow2 window (quantized
        stores override to supply precomputed decoded-row norms)."""
        self.vecs, self.sqnorm = _write_run(
            self.vecs,
            self.sqnorm,
            self._upload(padded),
            jnp.int32(win_start),
            jnp.int32(lo),
            jnp.int32(lo + chunk),
            nrows=bucket,
        )
        self._write_blocked(padded, None, win_start, lo, chunk, bucket)

    def _write_blocked(self, rows, rows_f32, win_start, lo, chunk,
                       bucket) -> None:
        """Mirror the same padded window into the blocked arrays (no-op
        when the mirror is off). `rows` carries what the device stores
        (codes for sq8); `rows_f32` the decoded values the norms must
        describe (None = derive by casting rows through the store dtype,
        matching _write_run's stored-row norm convention). Caller holds
        device_lock (the program donates)."""
        if self.vecs_blk is None:
            return
        from dingo_tpu.ops.blocked import block_sqnorms, to_blocked

        if rows_f32 is None:
            rows_f32 = np.asarray(rows)
            store_dt = jnp.zeros((), self.dtype).dtype
            if store_dt != np.float32:
                rows_f32 = rows_f32.astype(store_dt)
        rows_blk = to_blocked(np.asarray(rows), self.dim_block)
        bsq = block_sqnorms(
            np.asarray(rows_f32, np.float32), self.dim_block
        ).astype(np.float32)
        self.vecs_blk, self.bsq_blk = _write_run_blk(
            self.vecs_blk,
            self.bsq_blk,
            jnp.asarray(rows_blk),
            jnp.asarray(bsq),
            jnp.int32(win_start),
            jnp.int32(lo),
            jnp.int32(lo + chunk),
            nrows=bucket,
        )

    def remove(self, ids: np.ndarray) -> int:
        """Tombstone rows; returns number actually removed."""
        return int((self.remove_slots(ids) >= 0).sum())

    def remove_slots(self, ids: np.ndarray) -> np.ndarray:
        """Tombstone rows; returns the slot each id occupied (-1 for ids
        that were not present). Incremental view maintenance needs the
        freed slots to tombstone the matching bucket rows — returning them
        here avoids a second id->slot resolution pass before removal."""
        slots = np.full(len(ids), -1, np.int64)
        removed = 0
        with self._lease_lock:
            dest = self._limbo if self._inflight > 0 else self._free
            for i, vid in enumerate(ids):
                s = self._id_to_slot.pop(int(vid), None)
                if s is not None:
                    self.ids_by_slot[s] = -1
                    self.valid_h[s] = False
                    dest.append(s)
                    slots[i] = s
                    removed += 1
        if removed:
            self._dmask = None
            self.mutation_version += 1
        return slots

    # -- in-flight search accounting --------------------------------------
    def begin_search(self) -> "SearchLease":
        with self._lease_lock:
            self._inflight += 1
        return SearchLease(self)

    def end_search(self) -> None:
        with self._lease_lock:
            self._inflight -= 1
            if self._inflight == 0 and self._limbo:
                self._free.extend(self._limbo)
                self._limbo.clear()

    def _grow(self, new_capacity: int) -> None:
        new_capacity = _next_pow2(new_capacity)
        pad = new_capacity - self.capacity
        with self.device_lock:
            self.vecs, self.sqnorm = self._grow_storage(pad)
            if self.adj is not None:
                # slots are stable across growth: existing adjacency rows
                # stay correct, fresh capacity starts unlinked
                self.adj = jnp.concatenate(
                    [self.adj,
                     jnp.full((pad, self.graph_deg), -1, jnp.int32)]
                )
            if self.vecs_blk is not None:
                self.vecs_blk = jnp.concatenate(
                    [self.vecs_blk,
                     jnp.zeros((self.nblk, pad, self.dim_block), self.dtype)],
                    axis=1,
                )
                self.bsq_blk = jnp.concatenate(
                    [self.bsq_blk, jnp.zeros((self.nblk, pad), jnp.float32)],
                    axis=1,
                )
        self.ids_by_slot = np.concatenate(
            [self.ids_by_slot, np.full((pad,), -1, np.int64)]
        )
        self.valid_h = np.concatenate(
            [self.valid_h, np.zeros((pad,), np.bool_)]
        )
        self._dmask = None
        self._free.extend(range(new_capacity - 1, self.capacity - 1, -1))
        self.capacity = new_capacity
        # capacity is part of every [capacity]-shaped cached artifact
        # (filter masks, adjacency) — growth invalidates them all
        self.mutation_version += 1

    # -- host round-trips --------------------------------------------------
    def gather(self, ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Fetch vectors by external id (found_mask, vectors)."""
        slots = self.slots_of(ids)
        found = slots >= 0
        safe = np.where(found, slots, 0)
        with self.device_lock:   # vecs reference is donatable
            vecs = np.asarray(
                jnp.take(self.vecs, jnp.asarray(safe, jnp.int32), axis=0)
            )
        return found, vecs

    def rows_device(self, slots: np.ndarray) -> jax.Array:
        """Decoded f32 rows at `slots` as a DEVICE array — the train-path
        gather (ISSUE 18b): samplers pick slot indices host-side (cheap
        ints) and the rows themselves never round-trip; only centroids
        come back. One take per call; quantized stores decode in-device."""
        with self.device_lock:   # vecs reference is donatable
            return jnp.take(
                self.vecs, jnp.asarray(slots, jnp.int32), axis=0
            ).astype(jnp.float32)

    def to_host(self) -> dict:
        """Compacted host snapshot {ids, vectors} of live rows (save path)."""
        live = self.ids_by_slot >= 0
        with self.device_lock:
            vecs_h = np.asarray(self.vecs)
        return {
            "ids": self.ids_by_slot[live],
            "vectors": vecs_h[live],
        }

    @classmethod
    def from_host(cls, dim: int, dtype, ids: np.ndarray, vectors: np.ndarray,
                  capacity: Optional[int] = None) -> "SlotStore":
        store = cls(dim, dtype, capacity or max(MIN_CAPACITY, len(ids)))
        if len(ids):
            store.put(np.asarray(ids, np.int64), vectors)
        return store


class SearchLease:
    """Pairs begin_search with exactly one end_search even when the caller
    drops the resolve thunk or resolve raises: release() is idempotent and
    __del__ backstops it at GC, so limbo can't starve the free list."""

    __slots__ = ("_store", "_done")

    def __init__(self, store: "SlotStore"):
        self._store = store
        self._done = False

    def release(self) -> None:
        if not self._done:
            self._done = True
            self._store.end_search()

    def __del__(self):  # noqa: D105
        self.release()


def _next_pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1)).bit_length()


class HostSlotStore(SlotStore):
    """SlotStore variant keeping the vectors in HOST memory.

    For indexes whose SEARCH path never reads full vectors from the device
    (IVF_PQ serves from codes; DiskANN from disk), device-resident vectors
    only cap the index size at HBM: 10M x 768 f32 is ~30 GB, far beyond a
    v5e chip. This store keeps [capacity, d] in numpy; training/encoding
    stream chunks to the device, and the untrained exact fallback scans
    host chunks with a running top-k merge.
    """

    def _blocked_dtype_ok(self) -> bool:
        return False   # rows live in host RAM; no device scan mirror

    def _np_dtype(self):
        return np.dtype(jnp.zeros((), self.dtype).dtype.name)

    def _alloc_storage(self, capacity: int):
        return (
            np.zeros((capacity, self.dim), self._np_dtype()),
            np.zeros((capacity,), np.float32),
        )

    def _grow_storage(self, pad: int):
        return (
            np.concatenate(
                [self.vecs, np.zeros((pad, self.dim), self.vecs.dtype)]
            ),
            np.concatenate([self.sqnorm, np.zeros((pad,), np.float32)]),
        )

    def _write_segment(self, start: int, rows: np.ndarray) -> None:
        n = rows.shape[0]
        stored = rows.astype(self.vecs.dtype)
        rows32 = stored.astype(np.float32)   # stored-row norms (bf16 tier)
        self.vecs[start:start + n] = stored
        self.sqnorm[start:start + n] = (rows32 * rows32).sum(1)

    def gather(self, ids: np.ndarray):
        slots = self.slots_of(ids)
        found = slots >= 0
        safe = np.where(found, slots, 0)
        return found, self.vecs[safe]

    def rows_device(self, slots: np.ndarray) -> jax.Array:
        # rows live in host RAM: the gather itself is the upload
        rows = np.asarray(self.vecs[np.asarray(slots, np.int64)],
                          np.float32)
        return jnp.asarray(rows)

    def memory_size(self) -> int:
        # host bytes; device footprint is the caller's codes/centroids
        return int(self.vecs.nbytes + self.sqnorm.nbytes)


class SqSlotStore(SlotStore):
    """SlotStore whose device rows are SQ8 codes (uint8, 1 byte/dim —
    4x the vectors per chip vs f32; ops/sq.py codec).

    The external contract stays FLOAT: put()/gather()/to_host() speak f32
    rows (encode at the write boundary, decode at the read boundary), so
    index code above — training, reassignment, exact fallbacks — runs
    unchanged. Only the search kernels look at codes directly (via .vecs +
    .sq_vmin_d/.sq_scale_d), and sqnorm caches ||x̂||^2 of the DECODED
    surrogate so L2/cosine scores stay self-consistent with what the
    kernels scan.

    Codec params train lazily on the first write batch (min/max + margin,
    faiss train-once-clip-later convention) unless maybe_train()/
    set_params() installed them earlier (index.train with an explicit
    train set, or a snapshot load)."""

    def __init__(self, dim: int, dtype=jnp.uint8, capacity: int = MIN_CAPACITY,
                 blocked: Optional[bool] = None):
        if jnp.dtype(dtype) != jnp.uint8:
            raise ValueError("SqSlotStore stores uint8 codes")
        super().__init__(dim, jnp.uint8, capacity, blocked=blocked)
        self.sq_params = None            # ops.sq.SqParams (host)
        self._sq_vmin_d = None           # lazy device copies
        self._sq_scale_d = None
        #: (id(float rows), n, codes) of the latest put() — canonical_rows
        #: reuses it so the integrity ledger never re-encodes the batch
        self._canonical_memo = None

    # -- codec lifecycle ---------------------------------------------------
    def set_params(self, params) -> None:
        if self.sq_params is not None and len(self):
            raise RuntimeError(
                "cannot swap SQ params under live codes (re-ingest instead)"
            )
        self.sq_params = params
        self._sq_vmin_d = None
        self._sq_scale_d = None

    def maybe_train(self, rows: np.ndarray) -> None:
        """Install params from `rows` when none exist yet (no-op after)."""
        if self.sq_params is None and len(rows):
            from dingo_tpu.ops.sq import sq_train

            self.set_params(sq_train(np.asarray(rows, np.float32)))

    @property
    def sq_vmin_d(self) -> jax.Array:
        if self._sq_vmin_d is None:
            self._sq_vmin_d = jnp.asarray(self.sq_params.vmin)
        return self._sq_vmin_d

    @property
    def sq_scale_d(self) -> jax.Array:
        if self._sq_scale_d is None:
            self._sq_scale_d = jnp.asarray(self.sq_params.scale)
        return self._sq_scale_d

    def encode(self, rows: np.ndarray) -> np.ndarray:
        from dingo_tpu.ops.sq import sq_encode

        return sq_encode(rows, self.sq_params)

    def decode(self, codes: np.ndarray) -> np.ndarray:
        from dingo_tpu.ops.sq import sq_decode

        return sq_decode(codes, self.sq_params)

    # -- float-facing mutation/read paths ----------------------------------
    def put(self, ids: np.ndarray, vectors: np.ndarray) -> np.ndarray:
        self.maybe_train(vectors)
        codes = self.encode(np.asarray(vectors, np.float32))
        # memo for canonical_rows: the integrity ledger digests the SAME
        # batch right after put() with the SAME float array object —
        # re-encoding it would double the write path's quantization cost
        # for bytes that are identical by construction
        self._canonical_memo = (id(vectors), len(codes), codes)
        return super().put(ids, codes)

    def put_codes(self, ids: np.ndarray, codes: np.ndarray) -> np.ndarray:
        """Raw-code ingest (snapshot load): bypasses encode so a saved
        code array round-trips bit-exactly."""
        assert self.sq_params is not None, "set_params before put_codes"
        return super().put(ids, np.asarray(codes, np.uint8))

    def canonical_rows(self, rows: np.ndarray) -> np.ndarray:
        """Stored payload = the CODES (what the device actually holds and
        the scan kernels decode); the integrity ledger's 'rows' artifact
        for an sq8 store therefore digests codes — a single flipped code
        byte is a rows-artifact mismatch. Reuses the codes the
        immediately-preceding put() of the SAME array object produced
        (memo consumed on use; put() always refreshes it first, so a
        recycled object id can never pair with stale codes)."""
        memo = getattr(self, "_canonical_memo", None)
        if memo is not None and memo[0] == id(rows) \
                and memo[1] == len(rows):
            self._canonical_memo = None
            return memo[2]
        return self.encode(np.asarray(rows, np.float32))

    def _blocked_dtype_ok(self) -> bool:
        # codes mirror blocks fine: the pruned kernel decodes per tile
        return True

    def _dispatch_write(self, padded, win_start, lo, chunk, bucket) -> None:
        # padded rows are CODES here; norms come from the decoded surrogate
        deq = self.decode(padded)
        row_sq = np.einsum("ld,ld->l", deq, deq).astype(np.float32)
        self.vecs, self.sqnorm = _write_run_presq(
            self.vecs,
            self.sqnorm,
            self._upload(padded),
            jnp.asarray(row_sq),
            jnp.int32(win_start),
            jnp.int32(lo),
            jnp.int32(lo + chunk),
            nrows=bucket,
        )
        # blocked mirror scatters the CODES; the per-block norms describe
        # the decoded surrogate the pruned kernel actually accumulates
        self._write_blocked(padded, deq, win_start, lo, chunk, bucket)

    def gather(self, ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        found, codes = super().gather(ids)
        return found, self.decode(np.asarray(codes, np.uint8))

    def rows_device(self, slots: np.ndarray) -> jax.Array:
        from dingo_tpu.ops.sq import sq_decode_device

        with self.device_lock:
            codes = jnp.take(
                self.vecs, jnp.asarray(slots, jnp.int32), axis=0
            )
            if self.sq_params is None:   # no writes yet: nothing to decode
                return codes.astype(jnp.float32)
            return sq_decode_device(
                codes, self.sq_vmin_d, self.sq_scale_d, dtype=jnp.float32
            )

    def to_host(self) -> dict:
        """Decoded float snapshot — the safe default for callers that mean
        'give me the vectors' (train sampling, rebuild). Use
        codes_to_host() for the compact persistence form."""
        snap = super().to_host()
        if self.sq_params is None:
            # untrained codec == no writes ever happened; the live set is
            # empty and there is nothing to decode (an unconditional
            # decode would dereference the missing params)
            snap["vectors"] = np.zeros_like(snap["vectors"], np.float32)
        else:
            snap["vectors"] = self.decode(snap["vectors"])
        return snap

    def codes_to_host(self) -> dict:
        """Compacted {ids, codes} of live rows (save path; 1 byte/dim)."""
        snap = super().to_host()   # base returns raw device rows = codes
        return {"ids": snap["ids"], "codes": snap["vectors"]}


class HostSqSlotStore(SqSlotStore):
    """SqSlotStore variant keeping the uint8 codes in HOST RAM.

    The host rung of the memory-tier ladder (index/tiering.py): a demoted
    region's codes leave HBM entirely, the serving arm becomes a paged
    exact decoded scan on the host, and the device footprint drops to
    zero. Same float-facing contract as SqSlotStore — put() encodes,
    gather() decodes — and canonical_rows() still digests CODES, so the
    state-integrity ledger's 'rows' artifact is byte-comparable across
    the HBM-sq8 / host-sq8 / mmap-sq8 rungs (the digest gate that
    tier transitions verify before swapping)."""

    def _blocked_dtype_ok(self) -> bool:
        return False   # codes live host-side; no device scan mirror

    def _alloc_storage(self, capacity: int):
        return (
            np.zeros((capacity, self.dim), np.uint8),
            np.zeros((capacity,), np.float32),
        )

    def _grow_storage(self, pad: int):
        return (
            np.concatenate(
                [np.asarray(self.vecs),
                 np.zeros((pad, self.dim), np.uint8)]
            ),
            np.concatenate([self.sqnorm, np.zeros((pad,), np.float32)]),
        )

    def _write_segment(self, start: int, rows: np.ndarray) -> None:
        # rows arrive as CODES (SqSlotStore.put encodes before super().put);
        # sqnorm caches the decoded-surrogate norms, same convention as the
        # device store so tier moves never change what a scan accumulates
        n = rows.shape[0]
        codes = np.asarray(rows, np.uint8)
        self.vecs[start:start + n] = codes
        deq = self.decode(codes)
        self.sqnorm[start:start + n] = \
            np.einsum("ld,ld->l", deq, deq).astype(np.float32)

    def gather(self, ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        slots = self.slots_of(ids)
        found = slots >= 0
        safe = np.where(found, slots, 0)
        codes = np.asarray(self.vecs[safe], np.uint8)
        if self.sq_params is None:
            return found, codes.astype(np.float32)
        return found, self.decode(codes)

    def rows_device(self, slots: np.ndarray) -> jax.Array:
        codes = np.asarray(self.vecs[np.asarray(slots, np.int64)], np.uint8)
        if self.sq_params is None:
            return jnp.asarray(codes.astype(np.float32))
        return jnp.asarray(self.decode(codes))

    def memory_size(self) -> int:
        # host bytes; this store holds nothing on device
        return int(np.asarray(self.vecs).nbytes + self.sqnorm.nbytes)


class MmapSqSlotStore(HostSqSlotStore):
    """HostSqSlotStore whose code array is an np.memmap on disk.

    The bottom rung of the tier ladder: codes page in on demand under the
    paged exact scan, so a fully-cold region's steady-state RAM cost is
    the bookkeeping arrays (~13 bytes/slot), not the corpus. The file
    layout is the raw [capacity, dim] uint8 code matrix — identical bytes
    to the host rung's array, which keeps the digest-gated tier copy a
    straight transcription."""

    def __init__(self, dim: int, path: str, dtype=jnp.uint8,
                 capacity: int = MIN_CAPACITY,
                 blocked: Optional[bool] = None):
        # the storage hooks run inside super().__init__ — path first
        self._mmap_path = path
        super().__init__(dim, dtype, capacity, blocked=blocked)

    def _alloc_storage(self, capacity: int):
        import os

        os.makedirs(os.path.dirname(self._mmap_path) or ".", exist_ok=True)
        return (
            np.memmap(self._mmap_path, dtype=np.uint8, mode="w+",
                      shape=(capacity, self.dim)),
            np.zeros((capacity,), np.float32),
        )

    def _grow_storage(self, pad: int):
        new_cap = self.capacity + pad
        self.vecs.flush()
        with open(self._mmap_path, "r+b") as f:
            f.truncate(new_cap * self.dim)
        return (
            np.memmap(self._mmap_path, dtype=np.uint8, mode="r+",
                      shape=(new_cap, self.dim)),
            np.concatenate([self.sqnorm, np.zeros((pad,), np.float32)]),
        )

    def disk_bytes(self) -> int:
        return int(self.capacity) * int(self.dim)

    def memory_size(self) -> int:
        # the codes are disk-resident; RAM cost is the norm cache (+ the
        # base bookkeeping the caller already accounts per slot)
        return int(self.sqnorm.nbytes)

    def close(self, unlink: bool = True) -> None:
        """Release the mapping (promotion/retirement): flush, drop the
        mmap reference, optionally unlink the backing file."""
        import os

        with self.device_lock:
            try:
                self.vecs.flush()
            except (ValueError, OSError):
                pass
            # replace with a zero-row array so a straggling reader fails
            # loudly instead of touching an unmapped page
            self.vecs = np.zeros((0, self.dim), np.uint8)
        if unlink:
            try:
                os.unlink(self._mmap_path)
            except OSError:
                pass
