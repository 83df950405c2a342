"""Skew-proof bucketed IVF layout shared by TpuIvfFlat / TpuIvfPq.

Round-1 layout padded every coarse list to the LARGEST list's pow2 size
([nlist, cap_max, d]); with realistic k-means skew that multiplies HBM by
the skew factor (a 10x-hot list inflates every other list 10x). This layout
fixes the bucket width near the MEAN list size and lets a long list spill
into several fixed-width buckets instead:

  data        [B, cap_list, d]   B = sum_l ceil(count_l / cap_list)  (>= nlist)
  bucket_slot [B, cap_list]      slot per row, -1 pad
  probe_table [nlist, max_spill] bucket ids per coarse list, -1 pad

Memory is bounded by n*d + nlist*cap_list*d regardless of skew, and the
probe expansion (coarse list -> its spill buckets) happens ON DEVICE so no
D2H round-trip enters the search path. Construction is fully vectorized —
the round-1 per-row Python loop was itself a 1M-scale ingest bug.

Reference contract: faiss IndexIVF inverted lists are exact-size per list
(vector_index_ivf_flat.cc:60-62); the fixed-width spill encoding is the
static-shape equivalent XLA needs.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from dingo_tpu.index.slot_store import _next_pow2

#: bucket width bounds: small enough to bound padding waste (<= nlist*cap*d),
#: large enough to keep per-bucket matmuls MXU-friendly
MIN_CAP = 8
MAX_CAP = 2048


@dataclasses.dataclass
class BucketLayout:
    """Host-side layout description + device probe/slot arrays."""

    cap_list: int
    max_spill: int
    nbuckets: int
    bucket_slot_h: np.ndarray      # [B, cap_list] int32, -1 pad
    bucket_slot: jax.Array         # device copy
    bucket_valid: jax.Array        # [B, cap_list] bool
    probe_table: jax.Array         # [nlist, max_spill] int32, -1 pad
    gather_idx: jax.Array          # [B * cap_list] int32 (slot or 0)
    bucket_coarse: jax.Array       # [B] int32: coarse list of each bucket

    def gather_rows(self, source: jax.Array) -> jax.Array:
        """[B, cap_list, *source.shape[1:]] rows grouped by bucket."""
        out = jnp.take(source, self.gather_idx, axis=0)
        return out.reshape(
            (self.nbuckets, self.cap_list) + source.shape[1:]
        )


def list_cap(n_rows: int, nlist: int) -> int:
    """Bucket width for n_rows spread over nlist lists: the pow2 at or
    above the MEAN list size, clamped to [MIN_CAP, MAX_CAP]."""
    mean = max(1, -(-n_rows // max(1, nlist)))
    return min(MAX_CAP, max(MIN_CAP, _next_pow2(mean)))


def build_layout(
    assign_h: np.ndarray,
    valid_h: np.ndarray,
    nlist: int,
    cap_hint: Optional[int] = None,
) -> BucketLayout:
    """Group live slots by coarse assignment into fixed-width spill buckets.

    assign_h: [capacity] int32 coarse list per slot (-1 unassigned)
    valid_h:  [capacity] bool liveness
    """
    live = np.flatnonzero(valid_h)
    assign = assign_h[live]
    keep = assign >= 0
    live, assign = live[keep], assign[keep]

    counts = np.bincount(assign, minlength=nlist).astype(np.int64)
    cap_list = cap_hint or list_cap(len(live), nlist)

    # buckets per list (every list gets >= 1 so probe_table[:, 0] is valid)
    nb = np.maximum(1, -(-counts // cap_list))           # ceil div
    max_spill = int(nb.max()) if len(nb) else 1
    offsets = np.zeros(nlist + 1, np.int64)
    np.cumsum(nb, out=offsets[1:])
    nbuckets = int(offsets[-1])

    # stable sort by list; position within list -> (bucket, row) coordinates
    order = np.argsort(assign, kind="stable")
    live_s, assign_s = live[order], assign[order]
    starts = np.zeros(nlist, np.int64)
    np.cumsum(counts, out=starts)
    starts = np.concatenate([[0], starts[:-1]])
    pos = np.arange(len(live_s), dtype=np.int64) - starts[assign_s]
    bucket_id = offsets[assign_s] + pos // cap_list
    row = pos % cap_list

    bucket_slot = np.full((nbuckets, cap_list), -1, np.int32)
    bucket_slot[bucket_id, row] = live_s

    probe = offsets[:nlist, None] + np.arange(max_spill)[None, :]
    probe = np.where(
        np.arange(max_spill)[None, :] < nb[:, None], probe, -1
    ).astype(np.int32)

    safe = np.where(bucket_slot >= 0, bucket_slot, 0)
    coarse = np.repeat(np.arange(nlist, dtype=np.int32), nb)
    return BucketLayout(
        cap_list=cap_list,
        max_spill=max_spill,
        nbuckets=nbuckets,
        bucket_slot_h=bucket_slot,
        bucket_slot=jnp.asarray(bucket_slot),
        bucket_valid=jnp.asarray(bucket_slot >= 0),
        probe_table=jnp.asarray(probe),
        gather_idx=jnp.asarray(safe.reshape(-1), jnp.int32),
        bucket_coarse=jnp.asarray(coarse),
    )


def alloc_buckets(n: int) -> int:
    """Physical bucket allocation for n logical buckets: the smallest
    {1, 1.25, 1.5, 1.75} x pow2 ladder value >= n. The bucket count is a
    traced dimension of the scan kernels, so every distinct allocation is
    a compile — the ladder bounds the cache at 4 entries per octave while
    capping padding waste at 25% (plain pow2 doubling would waste up to
    2x HBM on the [B, cap_list, d] data array)."""
    n = max(1, int(n))
    if n <= 8:
        return _next_pow2(n)
    p = _next_pow2(n)
    for num in (5, 6, 7):
        cand = (p // 8) * num       # 1.25/1.5/1.75 x (p/2)
        if cand >= n:
            return cand
    return p


def shape_bucket(n: int) -> int:
    """Round a request shape (topk, nprobe) up to the {1, 1.5} x pow2
    ladder (..., 8, 12, 16, 24, 32, 48, 64, ...). Kernel k/nprobe are
    static arguments, so serving raw request values compiles one program
    per distinct (batch, k, nprobe) triple; the ladder keeps steady-state
    traffic on a handful of cached executables. Searching a slightly
    larger k/nprobe is strictly recall-neutral-or-better; callers slice
    results back to the requested k."""
    n = int(n)
    if n <= 4:
        return max(1, n)
    p = _next_pow2(n)
    mid = 3 * (p // 4)               # 1.5 x p/2
    return mid if mid >= n else p


class MutableIvfView:
    """Incrementally-maintained bucketed IVF view.

    Wraps the dense layout from build_layout() with the host bookkeeping
    needed to mutate it in place: slot -> (bucket, row) positions,
    per-bucket fill cursors, per-list bucket chains. Upserts append into
    free rows of a list's tail bucket (allocating a new spill bucket when
    the chain is full), deletes flip the row invalid — both become O(batch)
    device scatters (ops/scatter.py) instead of the O(N) gather+re-upload
    that build_layout costs. A deferred compaction (the owning index's
    compact()) restores the dense layout off the hot path.

    Ownership split: this class owns the INDEX-AGNOSTIC device arrays
    (bucket_slot / bucket_valid / probe_table / bucket_coarse); the data
    arrays grouped by the same coordinates ([B, cap, d] vectors, [B, cap]
    sqnorm, [B, cap, m] codes) belong to the owning index, which applies
    the scatter coordinates staged here to its own arrays. All device
    writes are donated — stage_*() is host-only; apply_device() and the
    index's data scatters must run under the store's device_lock.

    Invariant: a row is live iff bucket_slot[b, r] >= 0 (tombstones reset
    the slot to -1 so the filtered path can never resurrect a reassigned
    slot through a stale id).
    """

    def __init__(self, lay: BucketLayout, nlist: int, slot_capacity: int):
        self.cap_list = lay.cap_list
        self.nlist = nlist
        self.nbuckets = lay.nbuckets
        self.alloc = alloc_buckets(lay.nbuckets)
        self.max_spill = lay.max_spill

        cap = self.cap_list
        self.bucket_slot_h = np.full((self.alloc, cap), -1, np.int32)
        self.bucket_slot_h[: lay.nbuckets] = lay.bucket_slot_h
        self.bucket_coarse_h = np.full((self.alloc,), -1, np.int32)
        self.bucket_coarse_h[: lay.nbuckets] = np.asarray(lay.bucket_coarse)
        # dense layout packs each bucket's rows from 0 -> fill = live count
        self.bucket_fill = (self.bucket_slot_h >= 0).sum(axis=1).astype(
            np.int32
        )
        self.probe_table_h = np.full(
            (nlist, self.max_spill), -1, np.int32
        )
        self.probe_table_h[:] = np.asarray(lay.probe_table)
        self.list_nb = (self.probe_table_h >= 0).sum(axis=1).astype(np.int32)

        self.slot_pos = np.full((slot_capacity,), -1, np.int32)
        flat = self.bucket_slot_h.reshape(-1)
        live = np.flatnonzero(flat >= 0)
        self.slot_pos[flat[live]] = live

        # mutation accounting (since the last dense build)
        self.version = 0
        self.tombstones = 0
        self.inplace_appends = 0
        self.buckets_added = 0
        self.base_buckets = lay.nbuckets
        self.base_rows = int(len(live))
        self.live_rows = int(len(live))

        # device mirrors
        self.bucket_slot = jnp.asarray(self.bucket_slot_h)
        self.bucket_valid = jnp.asarray(self.bucket_slot_h >= 0)
        self.probe_table = jnp.asarray(self.probe_table_h)
        self.bucket_coarse = jnp.asarray(
            np.where(self.bucket_coarse_h >= 0, self.bucket_coarse_h, 0)
        )

    @classmethod
    def build(cls, assign_h: np.ndarray, valid_h: np.ndarray, nlist: int,
              slot_capacity: int,
              cap_hint: Optional[int] = None) -> "MutableIvfView":
        lay = build_layout(assign_h, valid_h, nlist, cap_hint)
        return cls(lay, nlist, slot_capacity)

    # -- derived -----------------------------------------------------------
    @property
    def gather_idx(self) -> jax.Array:
        """[alloc * cap_list] int32 slot-or-0 gather map (rebuild path of
        the owning index's data arrays)."""
        flat = self.bucket_slot_h.reshape(-1)
        return jnp.asarray(np.where(flat >= 0, flat, 0), jnp.int32)

    def gather_rows(self, source: jax.Array) -> jax.Array:
        """[alloc, cap_list, *source.shape[1:]] rows grouped by bucket."""
        out = jnp.take(source, self.gather_idx, axis=0)
        return out.reshape(
            (self.alloc, self.cap_list) + source.shape[1:]
        )

    def tombstone_ratio(self) -> float:
        return self.tombstones / max(1, self.live_rows + self.tombstones)

    def spill_ratio(self) -> float:
        return self.buckets_added / max(1, self.base_buckets)

    def stats(self) -> dict:
        return {
            "nbuckets": self.nbuckets,
            "alloc_buckets": self.alloc,
            "cap_list": self.cap_list,
            "live_rows": self.live_rows,
            "tombstones": self.tombstones,
            "tombstone_ratio": self.tombstone_ratio(),
            "inplace_appends": self.inplace_appends,
            "buckets_added": self.buckets_added,
            "spill_ratio": self.spill_ratio(),
            "version": self.version,
        }

    # -- staging (host bookkeeping; no device work) ------------------------
    def ensure_slot_capacity(self, capacity: int) -> None:
        if capacity > len(self.slot_pos):
            grown = np.full((capacity,), -1, np.int32)
            grown[: len(self.slot_pos)] = self.slot_pos
            self.slot_pos = grown

    def _alloc_bucket(self, coarse: int) -> int:
        """Allocate a fresh spill bucket for `coarse`; returns bucket id.
        Grows the physical allocation / probe-table width when needed
        (both already reflected host-side; _ViewUpdate carries the device
        growth directives)."""
        if self.nbuckets == self.alloc:
            new_alloc = alloc_buckets(self.nbuckets + 1)
            grown = np.full((new_alloc, self.cap_list), -1, np.int32)
            grown[: self.alloc] = self.bucket_slot_h
            self.bucket_slot_h = grown
            gc = np.full((new_alloc,), -1, np.int32)
            gc[: self.alloc] = self.bucket_coarse_h
            self.bucket_coarse_h = gc
            gf = np.zeros((new_alloc,), np.int32)
            gf[: self.alloc] = self.bucket_fill
            self.bucket_fill = gf
            self.alloc = new_alloc
        s = int(self.list_nb[coarse])
        if s == self.max_spill:
            new_spill = max(self.max_spill + 1,
                            self.max_spill + self.max_spill // 2)
            grown = np.full((self.nlist, new_spill), -1, np.int32)
            grown[:, : self.max_spill] = self.probe_table_h
            self.probe_table_h = grown
            self.max_spill = new_spill
        b = self.nbuckets
        self.nbuckets += 1
        self.buckets_added += 1
        self.bucket_coarse_h[b] = coarse
        self.probe_table_h[coarse, s] = b
        self.list_nb[coarse] = s + 1
        return b

    def stage_delete(self, slots: np.ndarray) -> Optional["_ViewUpdate"]:
        """Tombstone the given slots' rows. Host arrays are updated here;
        returns the device scatter batch (None when nothing changed).
        Unlike stage_upsert there is no size cutoff: a delete-only batch
        never allocates buckets, and the scatter payload is one int32 per
        row — far cheaper than invalidating the whole view."""
        upd = _ViewUpdate(self.alloc, self.nbuckets)
        for s in np.asarray(slots, np.int64):
            self._tombstone(int(s), upd)
        return self._finish(upd)

    def stage_upsert(
        self, slots: np.ndarray, assigns: np.ndarray
    ) -> Optional["_ViewUpdate"]:
        """Place upserted slots: tombstone any previous position, append
        into the assigned list's tail bucket. Returns None when the batch
        was a no-op (callers must NOT treat that as a rebuild request —
        oversize batches are the CALLER's cutoff, ops/scatter.py
        MAX_SCATTER_BATCH, checked before staging)."""
        slots = np.asarray(slots, np.int64)
        upd = _ViewUpdate(self.alloc, self.nbuckets)
        placed: dict = {}            # slot -> batch index of surviving row
        for i, (s, lst) in enumerate(zip(slots, np.asarray(assigns))):
            s, lst = int(s), int(lst)
            self._tombstone(s, upd)
            if lst < 0:
                continue
            # find a free row: tail bucket of the list's chain, else a
            # fresh spill bucket
            tail = int(self.probe_table_h[lst, self.list_nb[lst] - 1]) \
                if self.list_nb[lst] else -1
            if tail < 0 or self.bucket_fill[tail] >= self.cap_list:
                tail = self._alloc_bucket(lst)
            r = int(self.bucket_fill[tail])
            self.bucket_fill[tail] = r + 1
            self.bucket_slot_h[tail, r] = s
            self.slot_pos[s] = tail * self.cap_list + r
            self.live_rows += 1
            self.inplace_appends += 1
            placed[s] = i
            upd.touched.append(tail * self.cap_list + r)
        upd.appended = [(int(self.slot_pos[s]), i) for s, i in placed.items()]
        return self._finish(upd)

    def _tombstone(self, slot: int, upd: "_ViewUpdate") -> None:
        if slot < 0 or slot >= len(self.slot_pos):
            return
        pos = int(self.slot_pos[slot])
        if pos < 0:
            return
        self.slot_pos[slot] = -1
        self.bucket_slot_h[pos // self.cap_list, pos % self.cap_list] = -1
        self.tombstones += 1
        self.live_rows -= 1
        upd.touched.append(pos)

    def _finish(self, upd: "_ViewUpdate") -> Optional["_ViewUpdate"]:
        if not upd.touched and upd.nbuckets_before == self.nbuckets:
            return None
        self.version += 1
        # final value per touched position comes from the HOST truth, so
        # a slot upserted twice in one batch (tombstone of its own fresh
        # row) cannot race inside one scatter
        pos = np.unique(np.asarray(upd.touched, np.int64))
        upd.b_idx = (pos // self.cap_list).astype(np.int32)
        upd.r_idx = (pos % self.cap_list).astype(np.int32)
        upd.slot_vals = self.bucket_slot_h[upd.b_idx, upd.r_idx]
        upd.grew_alloc = self.alloc if upd.alloc_before != self.alloc else None
        upd.new_probe = upd.nbuckets_before != self.nbuckets
        return upd

    # -- device apply (caller holds the store's device_lock) ---------------
    def apply_device(self, upd: "_ViewUpdate") -> None:
        from dingo_tpu.ops.scatter import (
            pad_buckets,
            scatter_bucket_update,
        )

        if upd.grew_alloc is not None:
            self.bucket_slot = pad_buckets(self.bucket_slot, upd.grew_alloc,
                                           fill=-1)
            self.bucket_valid = pad_buckets(self.bucket_valid, upd.grew_alloc,
                                            fill=False)
        if len(upd.b_idx):
            self.bucket_slot = scatter_bucket_update(
                self.bucket_slot, upd.b_idx, upd.r_idx, upd.slot_vals
            )
            self.bucket_valid = scatter_bucket_update(
                self.bucket_valid, upd.b_idx, upd.r_idx, upd.slot_vals >= 0
            )
        if upd.new_probe:
            # probe table / coarse map are tiny ([nlist, spill] + [alloc])
            # — re-upload beats tracking their deltas
            self.probe_table = jnp.asarray(self.probe_table_h)
            self.bucket_coarse = jnp.asarray(
                np.where(self.bucket_coarse_h >= 0, self.bucket_coarse_h, 0)
            )


class _ViewUpdate:
    """Scatter batch staged by MutableIvfView: touched (bucket, row)
    coordinates with their final slot values, data-append mapping
    (position -> input-batch index), and growth directives."""

    __slots__ = ("alloc_before", "nbuckets_before", "touched", "appended",
                 "b_idx", "r_idx", "slot_vals", "grew_alloc", "new_probe")

    def __init__(self, alloc_before: int, nbuckets_before: int):
        self.alloc_before = alloc_before
        self.nbuckets_before = nbuckets_before
        self.touched: list = []
        self.appended: list = []
        self.b_idx = np.empty(0, np.int32)
        self.r_idx = np.empty(0, np.int32)
        self.slot_vals = np.empty(0, np.int32)
        self.grew_alloc: Optional[int] = None
        self.new_probe = False


def expand_probes(
    probes: jax.Array, probe_table: jax.Array, nprobe: int, max_spill: int
) -> jax.Array:
    """Coarse probes [b, nprobe] -> virtual bucket probes [b, budget].

    Valid buckets come first in original rank order; when the expansion
    exceeds the budget, the LOWEST-ranked coarse lists' spill buckets are
    dropped (they contribute least to recall). budget == nprobe when there
    is no spill, so the common case is a plain table lookup.
    """
    virt, _ = expand_probes_ranked(probes, probe_table, nprobe, max_spill)
    return virt


def expand_probes_ranked(
    probes: jax.Array, probe_table: jax.Array, nprobe: int, max_spill: int
):
    """expand_probes plus, per virtual probe, the POSITION of its coarse
    list within the query's probe ranking ([b, budget] int32). Lets callers
    that precompute per-(query, coarse-list) state (the IVF-PQ residual
    LUT) share it across a list's spill buckets instead of recomputing."""
    b = probes.shape[0]
    virt = jnp.take(probe_table, probes, axis=0)        # [b, nprobe, spill]
    virt = virt.reshape(b, nprobe * max_spill)
    if max_spill == 1:
        pos = jnp.broadcast_to(
            jnp.arange(nprobe, dtype=jnp.int32)[None, :], (b, nprobe)
        )
        return virt, pos
    width = nprobe * max_spill
    # rank-preserving compaction: valid entries keep their column index as
    # sort key, invalid ones sink to the end
    cols = jnp.arange(width, dtype=jnp.int32)[None, :]
    key = jnp.where(virt >= 0, cols, jnp.int32(width))
    order = jnp.argsort(key, axis=1)
    virt = jnp.take_along_axis(virt, order, axis=1)
    budget = min(width, nprobe + max(8, nprobe // 2) + max_spill - 1)
    pos = (order // max_spill).astype(jnp.int32)
    return virt[:, :budget], pos[:, :budget]
