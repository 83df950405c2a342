"""Batched device-side HNSW construction (ISSUE 18 tentpole).

Host construction inserts one row at a time through native ``hnsw_add``
— pointer-chasing work the reference (vector_index_hnsw.cc) parallelizes
with a thread pool and CS-PQ (PAPERS.md) identifies as THE bottleneck of
large-scale ANNS. This module builds the level-0 graph the device graph
tier serves (``SlotStore.adj``) directly on the accelerator, one pow2
insert batch at a time:

  candidate discovery   the PR 8 lockstep beam walk (ops/beam.py, raw
                        body inlined — this kernel is already
                        sentineled) runs the BATCH ROWS as queries
                        against the partially-built adjacency; an
                        intra-batch all-pairs top-k adds same-batch
                        neighbors the partial graph cannot see yet, and
                        bootstraps the first batch, whose graph is empty

  neighbor selection    RNG*-style occlusion pruning as ``deg`` rounds
                        of masked argmax over the candidate score
                        matrix: each round keeps the best surviving
                        candidate and occludes every candidate scoring
                        closer to the kept one than to the inserted
                        point — ``alpha^2 * s(c, kept) > s(c, p)`` in
                        the shared larger-is-better score space of
                        ops/rerank._scores_from_rows (for L2's negated
                        squared distances this is exactly DiskANN's
                        ``alpha * d(kept, c) <= d(p, c)`` prune)

  reverse edges         the selected edges flatten to (dst, src) pairs
                        and sort by dst; each run head re-prunes its
                        destination row ONCE against old neighbors plus
                        up to REVERSE_WINDOW same-batch incomers,
                        degree-clamped by plain top-deg, and the rows
                        install with the PR 3 donated scatter idiom
                        (out-of-range targets drop). Incomers past the
                        window drop and are counted
                        (``build.reverse_dropped``) — the next batch's
                        walk rediscovers those neighborhoods.

Live insertion: ``insert_batch`` reads the adjacency as it stands, so it
serves the bulk build (from empty) and ``upsert`` into a
graph that is being searched (index/hnsw.py ``_device_insert``) alike;
``ladder_batches`` cuts either's rows into the same pow2 ladder.

Shape discipline: the batch is pow2-padded with -1 slots and the caller
reserves store capacity up front, so a full build ladder compiles a
handful of programs and steady state (batch 2..N) compiles ZERO — the
monitored PR 3/5 invariant extended to construction.

Sync discipline: nothing here reads device values back per batch; the
entry slot and drop counter live on device across the whole build and
``BulkGraphBuilder.finish()`` performs the single host sync. Bulk build
is off the serving path — dingolint's host-sync checker covers this
module and that one sync is adjudicated in the baseline.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dingo_tpu.common.metrics import METRICS
from dingo_tpu.obs.sentinel import sentinel_jit
from dingo_tpu.ops.distance import Metric

#: same-batch incomers one destination row can absorb per flushed batch
#: (the reverse re-prune's static window); overflow drops and counts
REVERSE_WINDOW = 8

#: edge-list chunk of the reverse re-prune: bounds the resident
#: [chunk, deg + REVERSE_WINDOW, d] candidate-row gather
REVERSE_CHUNK = 1024


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def ladder_batches(slots: np.ndarray, batch_rows: int):
    """Split slots into the insert ladder: full batches of `batch_rows`
    (pow2, at least 8), then the remainder padded with -1 to its own pow2
    — a handful of compiled programs whatever the write sizes."""
    bb = _next_pow2(max(8, int(batch_rows)))
    slots = np.asarray(slots, np.int32)
    for s in range(0, len(slots), bb):
        chunk = slots[s:s + bb]
        size = bb if len(chunk) == bb else max(8, _next_pow2(len(chunk)))
        if len(chunk) < size:
            chunk = np.concatenate(
                [chunk, np.full(size - len(chunk), -1, np.int32)]
            )
        yield chunk


def _decoded_rows(vecs, slots, sq, vmin, scale):
    """Gather rows at ``slots`` in the compute representation the scoring
    kernels expect: sq8 codes decode to the bf16 surrogate (the store's
    sqnorm convention), float tiers gather as stored."""
    rows = jnp.take(vecs, slots, axis=0)
    if sq:
        from dingo_tpu.ops.sq import sq_decode_device

        rows = sq_decode_device(rows, vmin, scale)
    return rows


def _pair_scores(rows, sqn, metric):
    """[B, B] larger-is-better scores among the batch rows — the same
    formulas as ops/rerank._scores_from_rows, computed as one [B, B]
    matmul instead of a broadcast [B, B, d] gather. These only PROPOSE
    candidates; every survivor is re-scored through _scores_from_rows
    itself in the selection stage, so no cross-path drift can leak into
    the installed adjacency."""
    dots = jnp.einsum(
        "id,jd->ij", rows, rows,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    if metric is Metric.L2:
        return -(sqn[:, None] - 2.0 * dots + sqn[None, :])
    if metric is Metric.COSINE:
        return dots * jax.lax.rsqrt(jnp.maximum(sqn, 1e-30))[None, :]
    return dots


@sentinel_jit(
    "ops.build.insert",
    static_argnames=("beam", "max_iters", "metric", "sq", "alpha_sq"),
    donate_argnums=(0,),
)
def insert_batch(adj, vecs, sqnorm, valid, batch_slots, entry, vmin,
                 scale, beam, max_iters, metric, sq, alpha_sq):
    """Insert one pow2 batch of store rows into the partial adjacency.

    adj [cap, deg] int32 (-1 padded) is DONATED — the caller (a
    BulkGraphBuilder holding store.device_lock) rebinds its reference to
    the returned array, the ops/scatter.py discipline. batch_slots [B]
    int32; -1 pads the final partial batch (padded lanes select nothing
    and install nothing). entry [] int32 is the walk entry (-1 while the
    graph is empty).

    Returns (adj' [cap, deg], entry' [] int32, reverse_dropped []
    int32 — same-batch reverse edges past REVERSE_WINDOW).
    """
    from dingo_tpu.ops.beam import beam_search
    from dingo_tpu.ops.rerank import _scores_from_rows

    cap, deg = adj.shape
    b = batch_slots.shape[0]
    bvalid = batch_slots >= 0
    safe_b = jnp.where(bvalid, batch_slots, 0)
    rows = _decoded_rows(vecs, safe_b, sq, vmin, scale)
    qd = rows.astype(jnp.float32)
    bsq = jnp.take(sqnorm, safe_b)

    # -- candidate discovery -------------------------------------------------
    res_slots, _, _, _ = beam_search.__wrapped__(
        adj, vecs, sqnorm, valid, valid, qd, entry, vmin, scale,
        beam, max_iters, metric, sq,
    )
    ib = min(b, beam)
    pair = _pair_scores(qd, bsq, metric)
    pair = jnp.where(
        jnp.eye(b, dtype=bool) | ~bvalid[None, :] | ~bvalid[:, None],
        -jnp.inf, pair,
    )
    pv, pi = lax.top_k(pair, ib)
    intra = jnp.where(jnp.isneginf(pv), -1, jnp.take(safe_b, pi))

    # merge + self-mask + dedup (the beam.py sort trick: holes sort last)
    cand = jnp.concatenate([res_slots, intra], axis=1)        # [b, C]
    cand = jnp.where(cand == batch_slots[:, None], -1, cand)
    cs = jnp.where(cand >= 0, cand, cap)
    cs = jnp.sort(cs, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((b, 1), bool), cs[:, 1:] == cs[:, :-1]], axis=1
    )
    cand = jnp.where((cs < cap) & ~dup, cs, -1).astype(jnp.int32)

    # -- occlusion selection -------------------------------------------------
    nc = cand.shape[1]
    csafe = jnp.where(cand >= 0, cand, 0)
    crows = _decoded_rows(vecs, csafe, sq, vmin, scale)       # [b, C, d]
    csq = jnp.take(sqnorm, csafe)
    s_pc = _scores_from_rows(crows, csq, qd, metric)
    s_pc = jnp.where(cand >= 0, s_pc, -jnp.inf)

    # candidate-pair dots once ([b, C, C], one batched matmul): a round of
    # the selection then reads a column of it instead of every candidate
    # row again (deg rounds over [b, C, d] were 12 % of an insert on the
    # v5e, PERF.md PR 31)
    cdots = jnp.einsum(
        "bid,bjd->bij", crows, crows,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    def kept_scores(j):
        """[b, C] scores of every candidate against the kept one j [b, 1]
        (the formulas of ops/rerank._scores_from_rows with the kept row as
        the query; these only prune, every installed edge was scored by
        _scores_from_rows itself)."""
        dots = jnp.take_along_axis(cdots, j[:, None, :], axis=2)[:, :, 0]
        if metric is Metric.L2:
            return -(jnp.take_along_axis(csq, j, axis=1) - 2.0 * dots + csq)
        if metric is Metric.COSINE:
            return dots * jax.lax.rsqrt(jnp.maximum(csq, 1e-30))
        return dots

    def select(i, st):
        selected, alive = st
        masked = jnp.where(alive, s_pc, -jnp.inf)
        j = jnp.argmax(masked, axis=1)[:, None]               # [b, 1]
        ok = jnp.take_along_axis(masked, j, axis=1)[:, 0] > -jnp.inf
        pick = jnp.take_along_axis(cand, j, axis=1)[:, 0]
        selected = selected.at[:, i].set(jnp.where(ok, pick, -1))
        alive = alive & (jnp.arange(nc)[None, :] != j)
        s_ck = kept_scores(j)
        # RNG* occlusion: c is dominated once the kept neighbor explains
        # it better than the inserted point does
        alive = alive & ~(ok[:, None] & (alpha_sq * s_ck > s_pc))
        return selected, alive

    selected, _ = lax.fori_loop(
        0, deg, select,
        (jnp.full((b, deg), -1, jnp.int32), cand >= 0),
    )

    # -- forward install (donated scatter; padded lanes drop) ---------------
    adj = adj.at[jnp.where(bvalid, batch_slots, cap)].set(
        selected, mode="drop"
    )

    # -- reverse edges with degree-clamped re-pruning -----------------------
    ne = b * deg
    w = REVERSE_WINDOW
    dst = selected.reshape(-1)
    src = jnp.repeat(batch_slots, deg)
    ok_e = (dst >= 0) & (src >= 0)
    key = jnp.where(ok_e, dst, cap).astype(jnp.int32)
    order = jnp.argsort(key)                                  # stable
    dsts = jnp.take(key, order)
    srcs = jnp.take(jnp.where(ok_e, src, -1), order)
    idx = jnp.arange(ne)
    head = (dsts < cap) & jnp.concatenate(
        [jnp.ones((1,), bool), dsts[1:] != dsts[:-1]]
    )
    # run position via cummax over head indices: edges past the window
    # drop (counted; the next batch's walk rediscovers them)
    run_start = lax.associative_scan(
        jnp.maximum, jnp.where(head, idx, -1)
    )
    dropped = jnp.sum(
        ((dsts < cap) & (idx - run_start >= w)).astype(jnp.int32)
    )

    rc = min(REVERSE_CHUNK, _next_pow2(ne))
    pad = (-ne) % rc
    if pad:
        dsts = jnp.concatenate([dsts, jnp.full((pad,), cap, jnp.int32)])
        srcs = jnp.concatenate([srcs, jnp.full((pad,), -1, jnp.int32)])
        head = jnp.concatenate([head, jnp.zeros((pad,), bool)])
    nep = ne + pad

    def reprune(s):
        ii = s + jnp.arange(rc)
        d_e = lax.dynamic_slice(dsts, (s,), (rc,))
        h_e = lax.dynamic_slice(head, (s,), (rc,))
        dsafe = jnp.where(d_e < cap, d_e, 0)
        old = jnp.take(adj, dsafe, axis=0)                    # [rc, deg]
        # same-dst incomers in the static window after each head; a
        # destination inserted THIS batch already carries its incomers
        # in the just-installed forward row, so old ∩ incomers can be
        # non-empty — dedup with the same sort trick as discovery
        win = ii[:, None] + jnp.arange(w)[None, :]
        wclip = jnp.clip(win, 0, nep - 1)
        inc = jnp.where(
            (jnp.take(dsts, wclip) == d_e[:, None]) & (win < nep),
            jnp.take(srcs, wclip), -1,
        )
        cand2 = jnp.concatenate([old, inc], axis=1)           # [rc, deg+w]
        # a live graph can hold edges to rows deleted since (tombstones):
        # a re-pruned row sheds them
        cand2 = jnp.where(
            (cand2 == d_e[:, None])
            | ~jnp.take(valid, jnp.maximum(cand2, 0)), -1, cand2
        )
        c2 = jnp.where(cand2 >= 0, cand2, cap)
        c2 = jnp.sort(c2, axis=1)
        dup2 = jnp.concatenate(
            [jnp.zeros((rc, 1), bool), c2[:, 1:] == c2[:, :-1]], axis=1
        )
        cand2 = jnp.where((c2 < cap) & ~dup2, c2, -1).astype(jnp.int32)
        c2safe = jnp.where(cand2 >= 0, cand2, 0)
        c2rows = _decoded_rows(vecs, c2safe, sq, vmin, scale)
        c2sq = jnp.take(sqnorm, c2safe)
        drow = _decoded_rows(vecs, dsafe, sq, vmin, scale)
        s2 = _scores_from_rows(
            c2rows, c2sq, drow.astype(jnp.float32), metric
        )
        s2 = jnp.where(cand2 >= 0, s2, -jnp.inf)
        v2, i2 = lax.top_k(s2, deg)
        new_row = jnp.where(
            jnp.isneginf(v2), -1, jnp.take_along_axis(cand2, i2, axis=1)
        )
        return jnp.where(h_e & (d_e < cap), d_e, cap), new_row

    tgt2, new_rows = lax.map(reprune, jnp.arange(nep // rc) * rc)
    adj = adj.at[tgt2.reshape(-1)].set(
        new_rows.reshape(-1, deg), mode="drop"
    )

    # -- entry: the first inserted row anchors all later walks ---------------
    entry = jnp.where(
        entry >= 0, entry,
        jnp.where(jnp.any(bvalid),
                  jnp.take(batch_slots, jnp.argmax(bvalid)), -1),
    ).astype(jnp.int32)
    return adj, entry, dropped


class BulkGraphBuilder:
    """Accumulates store slots into pow2 insert batches and maintains the
    under-construction adjacency as a device array. Pure slot/store
    level: index-level concerns (row puts, integrity ledgers) live in
    index/hnsw.py's bulk session.

    Not thread-safe; one builder per build. Flushes take
    store.device_lock (the vecs/sqnorm references are donatable by
    writers) and donate the adjacency back into ``insert_batch``.
    """

    def __init__(self, store, deg: int, metric, *, sq: bool = False,
                 batch_rows: int = 256, beam: int = 64,
                 max_iters: int = 48, alpha: float = 1.0,
                 region_id: int = 0):
        self.store = store
        self.deg = max(1, int(deg))
        self.metric = metric
        self.sq = bool(sq)
        self.batch_rows = _next_pow2(max(8, int(batch_rows)))
        self.beam = max(8, int(beam))
        self.max_iters = max(1, int(max_iters))
        self.alpha_sq = float(alpha) * float(alpha)
        self.region_id = region_id
        self.rows = 0
        self.batches = 0
        self._pend = np.empty((0,), np.int32)
        self._adj = None
        self._entry_d = jnp.asarray(-1, jnp.int32)
        self._dropped_d = jnp.asarray(0, jnp.int32)
        self._done = False

    def _ensure_adj(self) -> None:
        cap = self.store.capacity
        if self._adj is None:
            self._adj = jnp.full((cap, self.deg), -1, jnp.int32)
        elif self._adj.shape[0] != cap:
            # the store grew under us (pow2 ladder): pad the building
            # adjacency to match — callers that reserve() capacity up
            # front never hit this and stay on one compiled program
            self._adj = jnp.concatenate([
                self._adj,
                jnp.full((cap - self._adj.shape[0], self.deg), -1,
                         jnp.int32),
            ])

    def add_slots(self, slots: np.ndarray) -> None:
        """Queue freshly-put store slots; full batches flush immediately."""
        assert not self._done, "builder already finished"
        self._pend = np.concatenate(
            [self._pend, np.asarray(slots, np.int32)]
        )
        while len(self._pend) >= self.batch_rows:
            self._flush(self._pend[:self.batch_rows])
            self._pend = self._pend[self.batch_rows:]

    def _flush(self, slots: np.ndarray) -> None:
        """Insert one ladder batch (pow2 long, -1 padded)."""
        store = self.store
        with store.device_lock:
            self._ensure_adj()
            sq_on = self.sq and getattr(store, "sq_params", None) is not None
            if sq_on:
                vmin, scale = store.sq_vmin_d, store.sq_scale_d
            else:
                d = store.vecs.shape[1]
                vmin = jnp.zeros((d,), jnp.float32)
                scale = jnp.ones((d,), jnp.float32)
            self._adj, self._entry_d, dropped = insert_batch(
                self._adj, store.vecs, store.sqnorm, store.device_mask(),
                jnp.asarray(slots), self._entry_d, vmin, scale,
                beam=self.beam, max_iters=self.max_iters,
                metric=self.metric, sq=sq_on, alpha_sq=self.alpha_sq,
            )
            self._dropped_d = self._dropped_d + dropped
        n = int((slots >= 0).sum())
        self.rows += n
        self.batches += 1
        METRICS.counter("build.rows", region_id=self.region_id).add(n)
        METRICS.counter("build.batches", region_id=self.region_id).add(1)

    def finish(self) -> Tuple[jax.Array, int, dict]:
        """Flush the remainder and return (adj [cap, deg] int32 device,
        entry_slot, stats). The device_get here is the build's ONE host
        sync — per-batch state (entry, drop counter) stays device-side."""
        assert not self._done, "builder already finished"
        self._done = True
        for chunk in ladder_batches(self._pend, self.batch_rows):
            self._flush(chunk)
        self._pend = np.empty((0,), np.int32)
        self._ensure_adj()    # a zero-row build still yields a mirror
        entry, dropped = jax.device_get((self._entry_d, self._dropped_d))
        METRICS.counter(
            "build.reverse_dropped", region_id=self.region_id
        ).add(int(dropped))
        return self._adj, int(entry), {
            "rows": self.rows,
            "batches": self.batches,
            "reverse_dropped": int(dropped),
        }
