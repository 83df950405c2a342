"""Memory-tier ladder: policy-driven HBM <-> host <-> mmap serving tiers.

ROADMAP item 1 (ISSUE 19): every region used to live entirely in HBM, so
corpus size was bounded by device memory no matter how fast the kernels
were. The Faiss paper frames large-scale ANN serving as a memory-budget
optimization problem and the reference ships a dedicated DiskANN role for
it; here the same budget pressure is answered by moving a region's
SERVING STATE along a four-rung ladder, coldest regions first:

  rung 0  hbm       — declared fp32/bf16 device index (full kernels)
  rung 1  hbm_sq8   — device index rebuilt at the sq8 tier (4x density,
                      device-resident exact rerank; PR 13's OOM-remat
                      build arm, now deliberate and flag-gated)
  rung 2  host_sq8  — uint8 codes in host RAM (HostSqSlotStore), served
                      by a paged exact decoded scan (HostSqFlat) — the
                      device footprint drops to ZERO
  rung 3  mmap_sq8  — the same codes as an np.memmap on disk
                      (MmapSqSlotStore); cold pages never fault in,
                      steady-state RAM is the per-slot bookkeeping

A region declared at the sq8 tier starts at rung 1 (rung 0 and 1 are the
same state for it); binary/HAMMING regions have no sq8 codec and never
ride the ladder.

Policy inputs are the EXISTING planes, not new telemetry:

  demotion  — coordinator capacity advisories (coordinator/capacity.py
              emits per-region demote advisories that, before this PR,
              nothing acted on; the TIER_DEMOTE region command closes the
              loop) PLUS a store-local pressure check: HBM ledger
              headroom (hbm.bytes_limit - bytes_in_use, obs/hbm.py)
              under tier.demote_headroom. Victim choice prefers
              advisory-flagged regions, then the coldest by windowed
              vector_search QPS, tie-broken toward the region with the
              most resident bytes its 99th-percentile working set
              (heat.working_set_bytes{pct=99,tier}) does not need —
              most bytes freed per unit of traffic hurt.
  promotion — sustained windowed QPS above tier.promote_qps re-warms a
              region one rung, gated on projected headroom so a promote
              cannot immediately re-trip the demote tripwire (thrash
              guard).

Transition mechanics:

  * precision-crossing moves (rung 0 <-> 1) are full engine rebuilds via
    the ONE shared arm `VectorIndexManager.rebuild_at_precision` — the
    same helper the device-OOM re-materialization (index/recovery.py)
    rides, so there is exactly one copy of the narrow-then-rebuild logic.
  * sq8 <-> sq8 moves (rungs 1-3) are byte-exact code TRANSCRIPTIONS:
    snapshot {ids, codes, sq_params} under the wrapper lock, pour into
    the destination store, then verify.
  * every transition is digest-gated (PR 11, obs/integrity.py): the
    destination copy's 'rows' artifact is recomputed from its live state
    and compared against the source ledger BEFORE the swap; on mismatch
    the copy is abandoned, tier.digest_refusals bumps, and reads keep
    serving the old tier. The sq8 'rows' artifact digests CODES, so the
    gate is exact across the hbm_sq8/host_sq8/mmap_sq8 rungs.
  * the install itself is the manager's catch-up protocol
    (_catch_up_and_install): writes that landed during the copy replay
    from the raft log with the SAME sq params — identical codes — and
    the swap happens under the wrapper lock with the switching flag set.
  * promotion H2D rides PR 15's staging rings (common/pipeline.py): the
    destination store's `_upload` hook is temporarily a ring uploader, so
    each code chunk's host->device copy overlaps the previous chunk's
    donated write program instead of serializing copy-then-dispatch.
  * demoting OUT of HBM runs the retire hook: rerank cache, blocked scan
    mirror, adjacency mirror, and filter-mask cache are dropped under the
    store's device lock and the HBM ledger forgets the region, so
    hbm.region.bytes and `cluster top` DEVPEAK reflect the demotion
    instead of reporting ghost residency.

Crossover economics (ARCHITECTURE.md "Memory tiering"): rung 1 buys 4x
density for a rerank-recoverable recall dip; rung 2 trades device scan
latency for host exact-scan latency (~10-50x slower per query, exact
recall) at zero HBM; rung 3 adds first-touch page-in latency but drops
RAM to ~13 bytes/slot. The ladder therefore only pays off on SKEWED
workloads — which the heat plane (PR 17) measures before the policy acts.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from dingo_tpu.common.log import get_logger, region_log
from dingo_tpu.common.metrics import METRICS
from dingo_tpu.index.base import (
    FilterSpec,
    IndexParameter,
    InvalidParameter,
    resolve_precision,
    strip_invalid,
)
from dingo_tpu.index.flat import _SlotStoreIndex
from dingo_tpu.index.slot_store import (
    HostSqSlotStore,
    MIN_CAPACITY,
    MmapSqSlotStore,
    SqSlotStore,
    _next_pow2,
)
from dingo_tpu.ops.distance import Metric, metric_ascending, np_normalize

_log = get_logger("index.tiering")

#: ladder rungs, warmest first (metric label values for tier.demotions/
#: tier.promotions{to} and the heartbeat's serving_tier field)
RUNGS = ("hbm", "hbm_sq8", "host_sq8", "mmap_sq8")
RUNG_HBM, RUNG_HBM_SQ8, RUNG_HOST_SQ8, RUNG_MMAP_SQ8 = range(4)

#: slots per decoded page of the host/mmap exact scan — small enough that
#: the decoded f32 page (+ score block) stays cache-friendly, large enough
#: that numpy matmul amortizes (8192 x 128 f32 = 4 MB/page)
SCAN_PAGE = 8192
#: rows per promotion H2D chunk (== MAX_WRITE_BUCKET: one donated write
#: program per chunk, so the staging ring overlap is chunk-granular)
PROMOTE_CHUNK = 4096


class TierRefused(RuntimeError):
    """A tier transition was refused before the swap (digest mismatch on
    the destination copy, unsupported source store, or a write raced an
    unlogged copy). The region keeps serving its CURRENT tier; the next
    policy tick may retry."""


# ---------------------------------------------------------------------------
# Host/mmap serving arm
# ---------------------------------------------------------------------------

class HostSqFlat(_SlotStoreIndex):
    """Serving index for the host_sq8/mmap_sq8 rungs: a paged exact
    decoded scan over a HostSqSlotStore/MmapSqSlotStore, pure numpy on
    the search path (no device work, no host-sync hazards — the paged
    loop skips pages with no valid slots, so a cold mmap'd region never
    faults its codes in).

    Wire behavior matches the device family: same distance conventions
    (ops/distance.py — L2/hamming ascending, IP/cosine descending; cosine
    rows stored normalized, queries normalized at scan time), same
    FilterSpec slot-mask composition, same integrity/quality/heat hooks.
    Scan scores are computed over the DECODED surrogate with the store's
    cached decoded-norm sqnorm — exact f32 over the same codes the
    device sq8 kernels read. The device kernels accumulate that
    surrogate in bf16 compute, so a demoted region's wire distances
    agree with the hbm_sq8 rung to bf16 tolerance (the host scan is the
    tighter of the two) and the ranking matches except across
    sub-bf16-resolution near-ties."""

    def __init__(self, index_id: int, parameter: IndexParameter, store):
        super().__init__(index_id, parameter)
        if parameter.metric is Metric.HAMMING:
            raise InvalidParameter("host sq8 tier needs a float metric")
        self.store = store
        self._precision = "sq8"
        self._rerank_cache = None     # host rung: no device row cache
        self._kernel_metric = parameter.metric
        self._kernel_nbits = 0

    # -- prep (same contract as TpuFlat) -----------------------------------
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
        return queries

    # -- search ------------------------------------------------------------
    def search_async(
        self,
        queries: np.ndarray,
        topk: int,
        filter_spec: Optional[FilterSpec] = None,
        staged=None,
    ):
        """Paged exact scan; `staged` is accepted for wrapper-signature
        parity and ignored (there is no device upload to claim). The scan
        runs eagerly — host work IS the dispatch — and the returned thunk
        only materializes the already-computed results, preserving the
        dispatch-now/resolve-later calling convention the serving
        pipeline assumes."""
        queries = self._prep_queries(queries)
        if self.metric is Metric.COSINE:
            # device path normalizes q inside pairwise_cosine; rows are
            # stored normalized, so the scan below is a plain matmul
            queries = np_normalize(queries)
        store = self.store
        lease = store.begin_search()
        try:
            self._count_search()
            ids, dists, slots = self._paged_scan(
                queries, int(topk), filter_spec
            )
        finally:
            lease.release()
        from dingo_tpu.obs.heat import HEAT, heat_enabled
        from dingo_tpu.obs.quality import QUALITY

        if heat_enabled():
            HEAT.register_layout(self.id, "slot", self._heat_layout)
            HEAT.observe(self.id, "slot", slots)
        QUALITY.observe_search(
            self, queries, topk, ids, dists, bucket="tier_host",
            filter_spec=filter_spec,
        )
        results = [strip_invalid(i, d) for i, d in zip(ids, dists)]

        def resolve():
            return results

        return resolve

    def _paged_scan(self, q: np.ndarray, k: int,
                    filter_spec: Optional[FilterSpec]
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Running top-k merge over SCAN_PAGE-slot decoded pages.
        Internal scores follow the kernel convention (larger = better:
        L2 scores are negated squared distances); the final conversion
        mirrors scores_to_distances. Returns (ids, distances, slots),
        each [nq, k], -1-padded."""
        store = self.store
        nq = q.shape[0]
        metric = self.metric
        best_s = np.full((nq, k), -np.inf, np.float32)
        best_slot = np.full((nq, k), -1, np.int64)
        with store.device_lock:
            valid = store.valid_h.copy()
            if filter_spec is not None and not filter_spec.is_empty():
                valid &= filter_spec.slot_mask(store.ids_by_slot,
                                               store.columns)
            if store.sq_params is not None and valid.any():
                q_sq = np.einsum("bd,bd->b", q, q)
                for lo in range(0, store.capacity, SCAN_PAGE):
                    hi = min(store.capacity, lo + SCAN_PAGE)
                    vmask = valid[lo:hi]
                    if not vmask.any():
                        continue   # cold page: never touched (mmap rung)
                    deq = store.decode(
                        np.asarray(store.vecs[lo:hi], np.uint8)
                    )
                    if metric is Metric.L2:
                        # ||q||^2 - 2 q.x + ||x||^2, negated; sqnorm is
                        # the cached decoded-surrogate norm, the same
                        # values _sq_flat_search_kernel accumulates
                        scores = -(q_sq[:, None] - 2.0 * (q @ deq.T)
                                   + store.sqnorm[lo:hi][None, :])
                    else:   # IP, and cosine over normalized rows/queries
                        scores = q @ deq.T
                    scores = np.where(
                        vmask[None, :], scores, -np.inf
                    ).astype(np.float32)
                    kk = min(k, scores.shape[1])
                    part = np.argpartition(-scores, kk - 1, axis=1)[:, :kk]
                    vals = np.take_along_axis(scores, part, axis=1)
                    slots = (part + lo).astype(np.int64)
                    cat_s = np.concatenate([best_s, vals], axis=1)
                    cat_slot = np.concatenate([best_slot, slots], axis=1)
                    sel = np.argpartition(-cat_s, k - 1, axis=1)[:, :k]
                    best_s = np.take_along_axis(cat_s, sel, axis=1)
                    best_slot = np.take_along_axis(cat_slot, sel, axis=1)
            ids = store.ids_of_slots(best_slot)
        order = np.argsort(-best_s, axis=1, kind="stable")
        best_s = np.take_along_axis(best_s, order, axis=1)
        best_slot = np.take_along_axis(best_slot, order, axis=1)
        ids = np.take_along_axis(ids, order, axis=1)
        hit = np.isfinite(best_s)
        ids = np.where(hit, ids, -1)
        best_slot = np.where(hit, best_slot, -1)
        dists = np.where(
            hit,
            -best_s if metric_ascending(metric) else best_s,
            0.0,
        ).astype(np.float32)
        return ids, dists, best_slot

    # -- lifecycle ---------------------------------------------------------
    def save(self, path: str) -> None:
        """Same on-disk form as TpuFlat's sq8 snapshot (flat.npz: ids +
        codes + codec params, meta precision 'sq8'), so a declared-sq8
        region restores through the ordinary TpuFlat.load path — and a
        declared-fp32/bf16 region's restore hits the sq8 container check
        in _check_meta, fails the load, and the manager rebuilds at the
        DECLARED tier from the engine: exactly the post-restart ladder
        reset the chaos harness asserts."""
        os.makedirs(path, exist_ok=True)
        snap = self.store.codes_to_host()
        out = {"ids": snap["ids"], "codes": snap["codes"]}
        if self.store.sq_params is not None:
            out["sq_vmin"] = self.store.sq_params.vmin
            out["sq_scale"] = self.store.sq_params.scale
        np.savez(os.path.join(path, "flat.npz"), **out)
        with open(os.path.join(path, "meta.json"), "w") as f:
            json.dump(self._save_meta(), f)

    def load(self, path: str) -> None:
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        self._check_meta(meta)
        data = np.load(os.path.join(path, "flat.npz"))
        self.store = HostSqSlotStore(
            self.dimension, capacity=max(len(data["ids"]), 1)
        )
        if "sq_vmin" in data.files:
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
        self.apply_log_id = meta["apply_log_id"]
        self.write_count_since_save = 0
        self._integrity_on_restore(meta)


# ---------------------------------------------------------------------------
# Tier manager
# ---------------------------------------------------------------------------

class _RegionTier:
    """Per-region ladder state (store-local, in-memory: a restart resets
    every region to its base rung because the restart REBUILDS at the
    declared tier — the state and the serving reality reset together)."""

    __slots__ = ("rung", "base", "advisory", "mmap_path", "last_change")

    def __init__(self, base: int):
        self.rung = base
        self.base = base
        self.advisory = False         # coordinator demote advisory pending
        self.mmap_path: Optional[str] = None
        self.last_change = 0.0


class TierManager:
    """Per-store ladder actuator. One transition per tick, worst/best
    candidate first — tier moves are full-region copies and the policy
    signals (QPS windows, ledger headroom) need a tick to re-settle
    before the next decision is meaningful."""

    def __init__(self, registry=METRICS):
        self._lock = threading.Lock()
        self._tick_lock = threading.Lock()
        self._regions: Dict[int, _RegionTier] = {}
        self._reg = registry
        #: synthetic HBM bytes_limit for CPU smoke tests and the
        #: memory-pressure bench — there is no real allocator watermark to
        #: read, so in-use falls back to the HBM ledger's per-region sums
        self.budget_override: Optional[int] = None
        #: chaos/test seam: called with a stage name at fixed points
        #: inside a transition ("copied" — between copy and digest
        #: verify; "mid_demote"/"mid_promote" — after verify, before
        #: install). The chaos harness kills the process here; the
        #: corruption test flips destination bytes here.
        self.test_hook: Optional[Callable[[str], None]] = None
        self.transitions = 0
        #: policy inputs of the CURRENT tick (headroom fraction, windowed
        #: QPS, advisory flag) — stashed by _tick_inner so _transition can
        #: snapshot the evidence it decided on into the event ledger.
        #: Direct demote()/promote() calls (tests, forced walks) carry no
        #: policy context and emit without it.
        self._decision_ctx: Optional[Dict[str, Any]] = None

    @staticmethod
    def enabled() -> bool:
        from dingo_tpu.common.config import FLAGS

        try:
            return bool(FLAGS.get("tier_enabled"))
        except KeyError:   # registry not populated (unit contexts)
            return False

    # -- state -------------------------------------------------------------
    def _base_rung(self, region) -> int:
        param = region.definition.index_parameter
        try:
            return (RUNG_HBM_SQ8
                    if resolve_precision(param) == "sq8" else RUNG_HBM)
        except Exception:  # noqa: BLE001 — unknown tier string
            return RUNG_HBM

    def _state(self, region) -> _RegionTier:
        with self._lock:
            st = self._regions.get(region.id)
            if st is None:
                st = _RegionTier(self._base_rung(region))
                self._regions[region.id] = st
            return st

    def region_tier(self, region_id: int, precision: str = "") -> str:
        """Current rung name for the heartbeat harvest. Untracked regions
        report their resident tier (the collector passes the serving
        index's precision so a declared-sq8 region reads hbm_sq8, not
        hbm, before its first transition)."""
        with self._lock:
            st = self._regions.get(region_id)
        if st is not None:
            return RUNGS[st.rung]
        return RUNGS[RUNG_HBM_SQ8] if precision == "sq8" else RUNGS[RUNG_HBM]

    def note_advisory(self, region_id: int) -> None:
        """Coordinator TIER_DEMOTE command landed (the capacity plane's
        advisory -> actuation handshake): flag the region so the next
        policy tick prefers it as the demotion victim. A no-op flag, not
        an immediate demotion — actuation stays on the store's tick so a
        coordinator burst cannot stack concurrent copies."""
        with self._lock:
            st = self._regions.get(region_id)
            if st is None:
                st = self._regions[region_id] = _RegionTier(RUNG_HBM)
            st.advisory = True
        self._reg.counter("tier.advisories", region_id=region_id).add(1)

    def forget_region(self, region_id: int) -> None:
        with self._lock:
            self._regions.pop(region_id, None)

    def reset(self) -> None:
        with self._lock:
            self._regions.clear()
        self.budget_override = None
        self.test_hook = None

    def state(self) -> Dict[int, Dict[str, Any]]:
        with self._lock:
            return {
                rid: {"rung": RUNGS[st.rung], "base": RUNGS[st.base],
                      "advisory": st.advisory}
                for rid, st in self._regions.items()
            }

    def resident_fraction(self, node) -> float:
        """Device-resident share of the store's total index bytes — the
        bench's memory-pressure curve x-axis. 1.0 while everything is in
        HBM; falls as regions demote."""
        dev = tot = 0
        for region in node.meta.get_all_regions():
            w = region.vector_index_wrapper
            if w is None or w.own_index is None:
                continue
            d = int(w.get_device_memory_size())
            m = int(w.get_memory_size())
            dev += d
            tot += max(d, m)
        return (dev / tot) if tot else 1.0

    # -- policy tick ---------------------------------------------------------
    def tick(self, node) -> Dict[str, Any]:
        """One policy pass: refresh headroom, demote ONE victim when
        pressed (ledger headroom below tier.demote_headroom, or a
        coordinator advisory pending), else promote ONE sustained-hot
        region a rung when the projected footprint fits. Returns a
        transition report (empty dict when disabled/idle)."""
        if not self.enabled():
            return {}
        with self._tick_lock:
            return self._tick_inner(node)

    def _tick_inner(self, node) -> Dict[str, Any]:
        regions = {r.id: r for r in node.meta.get_all_regions()}
        with self._lock:
            gone = [rid for rid in self._regions if rid not in regions]
            for rid in gone:
                self._regions.pop(rid, None)
        limit, in_use = self._headroom(node)
        headroom = ((limit - in_use) / limit) if limit else 1.0
        from dingo_tpu.common.config import FLAGS

        demote_at = float(FLAGS.get("tier_demote_headroom"))
        promote_qps = float(FLAGS.get("tier_promote_qps"))
        qps = {
            rid: self._reg.latency(
                "vector_search", region_id=rid
            ).windowed_qps()
            for rid in regions
        }
        advisory = any(
            st.advisory for st in self._regions.values()
        )
        # the exact policy inputs this tick decided on — snapshotted into
        # the transition's ledger event (obs/events.py)
        self._decision_ctx = {
            "headroom": round(headroom, 4),
            "demote_at": demote_at,
            "promote_qps": promote_qps,
            "advisory": advisory,
        }
        try:
            if headroom < demote_at or advisory:
                victim = self._pick_demote(regions, qps, promote_qps)
                if victim is not None:
                    self._decision_ctx["qps"] = round(
                        qps.get(victim, 0.0), 3)
                    return self.demote(node, regions[victim])
            target = self._pick_promote(
                regions, qps, promote_qps, limit, in_use, demote_at
            )
            if target is not None:
                self._decision_ctx["qps"] = round(qps.get(target, 0.0), 3)
                return self.promote(node, regions[target])
        finally:
            self._decision_ctx = None
        return {"idle": True, "headroom": headroom}

    def _headroom(self, node) -> Tuple[int, int]:
        """(bytes_limit, bytes_in_use). With a budget override (CPU
        smoke / bench) in-use is the HBM ledger's per-region sum over a
        fresh accounting pass; on real hardware the allocator watermark
        is the truth."""
        from dingo_tpu.obs.hbm import HBM

        if self.budget_override is not None:
            for region in node.meta.get_all_regions():
                w = region.vector_index_wrapper
                if w is not None:
                    HBM.account_index(region.id, w)
            state = HBM.state()
            in_use = sum(
                sum(r["bytes"].values())
                for r in state["regions"].values()
            )
            return int(self.budget_override), int(in_use)
        stats = HBM.poll_process()
        return (int(stats.get("bytes_limit", 0) or 0),
                int(stats.get("bytes_in_use", 0) or 0))

    def _pick_demote(self, regions, qps, promote_qps) -> Optional[int]:
        """Demotion victim: advisory-flagged first, then coldest by
        windowed QPS; ties broken toward the region whose resident bytes
        exceed its p99 working set the most (heat plane) — the bytes
        traffic would not miss. Regions hot enough to promote are never
        demoted (thrash guard)."""
        from dingo_tpu.obs.heat import HEAT, heat_enabled

        heat_on = heat_enabled()
        cands = []
        for rid, region in regions.items():
            st = self._state(region)
            if st.rung >= RUNG_MMAP_SQ8:
                continue     # already at the bottom
            param = region.definition.index_parameter
            if param is None or param.metric is Metric.HAMMING:
                continue     # binary family: no sq8 codec, no ladder
            w = region.vector_index_wrapper
            if w is None or w.own_index is None or not w.ready:
                continue
            r_qps = qps.get(rid, 0.0)
            if r_qps >= promote_qps and not st.advisory:
                continue     # hot region: demoting it would thrash
            waste = 0
            if heat_on:
                stats = HEAT.region_stats(rid)
                if stats:
                    ws = stats.get("ws_bytes") or {}
                    ws99 = int(ws.get(99, ws.get("99", 0)) or 0)
                    resident = int(w.get_device_memory_size()
                                   or w.get_memory_size())
                    waste = max(0, resident - ws99)
            cands.append((not st.advisory, r_qps, -waste, rid))
        if not cands:
            return None
        cands.sort()
        return cands[0][3]

    def _pick_promote(self, regions, qps, promote_qps, limit, in_use,
                      demote_at) -> Optional[int]:
        """Hottest demoted region whose next rung up fits: projected
        in-use after the promote must stay above the demote tripwire
        (limit * (1 - demote_headroom)) so promote->demote ping-pong
        cannot start."""
        from dingo_tpu.obs.heat import TIER_BYTES

        best = None
        for rid, region in regions.items():
            st = self._state(region)
            if st.rung <= st.base:
                continue
            r_qps = qps.get(rid, 0.0)
            if r_qps < promote_qps:
                continue
            target = st.rung - 1
            if target <= RUNG_HBM_SQ8 and limit:
                w = region.vector_index_wrapper
                count = w.get_count() if w is not None else 0
                tier = ("sq8" if target == RUNG_HBM_SQ8
                        else resolve_precision(
                            region.definition.index_parameter))
                est = int(count * region.definition.index_parameter.dimension
                          * TIER_BYTES.get(tier, 4.0))
                if in_use + est > limit * (1.0 - demote_at):
                    continue
            if best is None or r_qps > best[0]:
                best = (r_qps, rid)
        return best[1] if best else None

    # -- transitions ---------------------------------------------------------
    def demote(self, node, region) -> Dict[str, Any]:
        """Move one rung DOWN the ladder. rung 0->1 rebuilds from the
        engine at sq8 (shared arm); 1->2 and 2->3 are digest-gated code
        transcriptions."""
        st = self._state(region)
        st.advisory = False
        if st.rung >= RUNG_MMAP_SQ8:
            return {"region": region.id, "action": "demote",
                    "ok": False, "reason": "already at bottom rung"}
        return self._transition(node, region, st, st.rung + 1, "demote")

    def promote(self, node, region) -> Dict[str, Any]:
        """Move one rung UP the ladder. 3->2 transcribes mmap->RAM, 2->1
        re-enters the device via the staged put_codes fast path (or a
        rebuild when the family needs structure beyond raw codes), 1->0
        rebuilds at the declared precision (shared arm)."""
        st = self._state(region)
        if st.rung <= st.base:
            return {"region": region.id, "action": "promote",
                    "ok": False, "reason": "already at base rung"}
        return self._transition(node, region, st, st.rung - 1, "promote")

    def _transition(self, node, region, st: _RegionTier, target: int,
                    kind: str) -> Dict[str, Any]:
        rid = region.id
        src_rung = st.rung
        t0 = time.perf_counter()
        report = {"region": rid, "action": kind,
                  "from": RUNGS[src_rung], "to": RUNGS[target]}
        try:
            if target == RUNG_HBM or (
                kind == "demote" and target == RUNG_HBM_SQ8
            ):
                ok = self._rebuild_rung(node, region, target, kind)
            elif kind == "promote" and target == RUNG_HBM_SQ8:
                ok = self._promote_to_device(node, region, st)
            else:
                ok = self._transcribe(node, region, st, target, kind)
        except TierRefused as e:
            region_log(_log, rid).warning(
                "tier %s %s->%s refused: %s", kind,
                RUNGS[src_rung], RUNGS[target], e)
            report.update(ok=False, reason=str(e))
            return report
        if not ok:
            report.update(ok=False, reason="rebuild busy")
            return report
        st.rung = target
        st.last_change = time.time()
        self.transitions += 1
        elapsed_ms = (time.perf_counter() - t0) * 1e3
        from dingo_tpu.obs.events import EVENTS

        evidence: Dict[str, Any] = {"ms": round(elapsed_ms, 1)}
        if self._decision_ctx:
            evidence.update(self._decision_ctx)
        EVENTS.emit(
            "tier", rid, "tier", RUNGS[src_rung], RUNGS[target],
            trigger=kind, evidence=evidence,
        )
        self._reg.counter(
            "tier.demotions" if kind == "demote" else "tier.promotions",
            region_id=rid, labels={"to": RUNGS[target]},
        ).add(1)
        self._reg.gauge("tier.current", region_id=rid).set(float(target))
        self._reg.latency("tier.transition_ms").observe_us(elapsed_ms * 1e3)
        self._publish_mmap_bytes(region, st)
        region_log(_log, rid).info(
            "tier %s %s -> %s (%.0f ms)", kind,
            RUNGS[src_rung], RUNGS[target], elapsed_ms)
        report.update(ok=True, ms=elapsed_ms)
        return report

    def _publish_mmap_bytes(self, region, st: _RegionTier) -> None:
        w = region.vector_index_wrapper
        store = getattr(w.own_index, "store", None) if w and w.own_index \
            else None
        nbytes = (store.disk_bytes()
                  if isinstance(store, MmapSqSlotStore) else 0)
        self._reg.gauge("tier.mmap_bytes", region_id=region.id).set(
            float(nbytes))

    def _hook(self, stage: str, ctx=None) -> None:
        hook = self.test_hook
        if hook is not None:
            hook(stage, ctx)

    def _raft_log(self, node, region_id: int):
        raft_node = node.engine.get_node(region_id)
        return raft_node.log if raft_node is not None else None

    # -- transition arms -----------------------------------------------------
    def _rebuild_rung(self, node, region, target: int, kind: str) -> bool:
        """Precision-crossing move: full engine rebuild through the ONE
        shared arm (manager.rebuild_at_precision — also the OOM-remat
        path). The manager's own catch-up + locked switch is the
        integrity story here: the engine is the source of truth and the
        fresh index's ledger re-primes from live state on its first
        scrub; a digest gate against the OLD index would be comparing
        different bytes (different precision container) by design."""
        self._hook("mid_" + kind)
        precision = "sq8" if target == RUNG_HBM_SQ8 else None
        ok = node.index_manager.rebuild_at_precision(
            region, raft_log=self._raft_log(node, region.id),
            precision=precision,
        )
        if ok and kind == "promote":
            # left a host/mmap rung for the device: retire the old host
            # store's disk backing (the old index object is already
            # unreferenced by the wrapper)
            pass
        return ok

    def _snapshot_source(self, wrapper):
        """Atomically capture the source index's codes + codec params +
        integrity digests + applied index under the wrapper lock (no
        write can interleave: wrapper.add/delete hold the same lock for
        their whole mutation)."""
        from dingo_tpu.obs.integrity import INTEGRITY

        with wrapper._lock:
            src = wrapper.own_index
            store = getattr(src, "store", None)
            if not isinstance(store, SqSlotStore):
                raise TierRefused(
                    f"source store {type(store).__name__} holds no sq8 "
                    "codes to transcribe")
            snap = store.codes_to_host()
            params = store.sq_params
            digests = INTEGRITY.snapshot_artifacts(src)
            applied = wrapper.apply_log_id
        return src, snap, params, digests, applied

    def _verify_copy(self, src_digests: Dict[str, str], dest,
                     region_id: int) -> None:
        """The digest gate (PR 11): recompute the destination copy's
        'rows' artifact from its live state and compare against the
        source ledger BEFORE the swap. sq8 'rows' digests CODES
        (slot-order-free, id-keyed), so hbm_sq8/host_sq8/mmap_sq8 copies
        of the same logical state digest identically — one flipped byte
        in the destination is a refusal, and reads keep serving the old
        tier. Skipped when the integrity plane is off or unprimed
        (nothing trustworthy to compare against)."""
        if not src_digests or "rows" not in src_digests:
            return
        from dingo_tpu.obs.integrity import INTEGRITY

        dest_digests = INTEGRITY.rebuild_from_index(dest)
        if dest_digests.get("rows") != src_digests["rows"]:
            self._reg.counter("tier.digest_refusals",
                              region_id=region_id).add(1)
            raise TierRefused(
                "destination copy failed the rows-digest gate "
                f"(src {src_digests['rows'][:12]}.. != dest "
                f"{dest_digests.get('rows', '<none>')[:12]}..)")

    def _install(self, node, wrapper, dest, region, snap_applied: int
                 ) -> None:
        """Swap the verified destination in: the manager's catch-up
        protocol replays writes that landed during the copy (same sq
        params -> identical codes, so the ledger stays exact), then the
        switch happens under the wrapper lock with is_switching set.
        Without a raft log (unit contexts) the install refuses if any
        write raced the copy — there is nothing to replay from."""
        raft_log = self._raft_log(node, region.id)
        if raft_log is not None:
            node.index_manager._catch_up_and_install(
                wrapper, dest, region, raft_log)
            return
        with wrapper._lock:
            if wrapper.apply_log_id != snap_applied:
                raise TierRefused(
                    "writes raced the copy and there is no raft log to "
                    "catch up from")
            wrapper.own_index = dest
            wrapper.ready = True
            wrapper.build_error = False
            wrapper.share_index = None

    def _transcribe(self, node, region, st: _RegionTier, target: int,
                    kind: str) -> bool:
        """sq8 -> sq8 rung move (device->host, host->mmap, mmap->host):
        byte-exact code transcription, digest-gated, catch-up installed."""
        rid = region.id
        wrapper = region.vector_index_wrapper
        src, snap, params, digests, applied = self._snapshot_source(wrapper)
        if target == RUNG_MMAP_SQ8:
            path = self._mmap_file(rid)
            st.mmap_path = path
            dest_store = MmapSqSlotStore(
                region.definition.index_parameter.dimension, path,
                capacity=max(MIN_CAPACITY, _next_pow2(len(snap["ids"]))),
            )
        else:
            dest_store = HostSqSlotStore(
                region.definition.index_parameter.dimension,
                capacity=max(MIN_CAPACITY, _next_pow2(len(snap["ids"]))),
            )
        dest = HostSqFlat(rid, region.definition.index_parameter, dest_store)
        try:
            if params is not None:
                dest_store.set_params(params)
                if len(snap["ids"]):
                    dest_store.put_codes(
                        np.asarray(snap["ids"], np.int64),
                        np.asarray(snap["codes"], np.uint8),
                    )
            dest.apply_log_id = applied
            self._hook("copied", dest)
            self._verify_copy(digests, dest, rid)
            self._hook("mid_" + kind, dest)
            self._install(node, wrapper, dest, region, applied)
        except Exception:
            if isinstance(dest_store, MmapSqSlotStore):
                dest_store.close(unlink=True)
            raise
        # swap done: retire the source's residency
        if src_was_device := (st.rung <= RUNG_HBM_SQ8):
            self._release_device(src, rid)
        src_store = getattr(src, "store", None)
        if isinstance(src_store, MmapSqSlotStore) and not src_was_device:
            src_store.close(unlink=True)
            st.mmap_path = None
        return True

    def _promote_to_device(self, node, region, st: _RegionTier) -> bool:
        """host_sq8 -> hbm_sq8: FLAT regions re-enter the device by
        pouring the host codes straight into a fresh device SqSlotStore —
        the H2D upload rides a staging ring (PR 15) so each chunk's copy
        overlaps the previous chunk's donated write program — then the
        same digest gate + catch-up install. Families whose device form
        needs structure beyond raw codes (IVF views, HNSW graphs) take
        the engine-rebuild arm instead."""
        from dingo_tpu.index.base import IndexType
        from dingo_tpu.index.factory import new_index
        from dingo_tpu.index.flat import TpuFlat
        from dingo_tpu.index.manager import precision_override

        rid = region.id
        wrapper = region.vector_index_wrapper
        param = region.definition.index_parameter
        if param.index_type is not IndexType.FLAT:
            return node.index_manager.rebuild_at_precision(
                region, raft_log=self._raft_log(node, rid),
                precision="sq8")
        src, snap, params, digests, applied = self._snapshot_source(wrapper)
        dest = new_index(rid, precision_override(param, "sq8"))
        if not (type(dest) is TpuFlat
                and isinstance(dest.store, SqSlotStore)
                and not isinstance(dest.store, HostSqSlotStore)
                and params is not None):
            # sharded/custom flat variant or untrained codec: rebuild arm
            return node.index_manager.rebuild_at_precision(
                region, raft_log=self._raft_log(node, rid),
                precision="sq8")
        dest.store.set_params(params)
        if len(snap["ids"]):
            dest.store.reserve(_next_pow2(len(snap["ids"])))
            self._staged_put_codes(
                dest.store,
                np.asarray(snap["ids"], np.int64),
                np.asarray(snap["codes"], np.uint8),
            )
        dest.apply_log_id = applied
        self._hook("copied", dest)
        self._verify_copy(digests, dest, rid)
        self._hook("mid_promote", dest)
        self._install(node, wrapper, dest, region, applied)
        src_store = getattr(src, "store", None)
        if isinstance(src_store, MmapSqSlotStore):
            src_store.close(unlink=True)
            st.mmap_path = None
        return True

    @staticmethod
    def _staged_put_codes(dstore, ids: np.ndarray, codes: np.ndarray
                          ) -> None:
        """Bulk code ingest with staging-ring H2D overlap: the store's
        `_upload` hook becomes a ring uploader for the duration, so chunk
        N's host->device copy is in flight while chunk N-1's donated
        write program dispatches. The previous staged slot is recycled
        only once a NEWER upload begins — by then its write program was
        already dispatched under the device lock, so the host buffer is
        no longer the transfer source."""
        from dingo_tpu.common.pipeline import StagingRing

        ring = StagingRing(depth=2)
        pending = []

        def upload(arr):
            while len(pending) >= 2:
                pending.pop(0).release()
            staged = ring.stage(np.ascontiguousarray(arr))
            pending.append(staged)
            return staged.qpad

        prev = dstore._upload
        dstore._upload = upload
        try:
            for lo in range(0, len(ids), PROMOTE_CHUNK):
                dstore.put_codes(ids[lo:lo + PROMOTE_CHUNK],
                                 codes[lo:lo + PROMOTE_CHUNK])
        finally:
            dstore._upload = prev
            for staged in pending:
                staged.release()

    @staticmethod
    def _release_device(src, region_id: int) -> None:
        """The retire hook (ISSUE 19 satellite): a region leaving HBM
        must drop its device-side bookkeeping with it — rerank cache,
        blocked scan mirror, HNSW adjacency, filter-mask cache —
        and the HBM ledger must forget the region so hbm.region.bytes
        zeroes and DEVPEAK stops reporting ghost residency. Mirrors the
        recovery ladder's eviction rungs (index/recovery.py) plus the
        ledger retirement the emergency path deliberately skips (a
        degraded region is still device-resident; a demoted one is not)."""
        import contextlib

        store = getattr(src, "store", None)
        lock = getattr(store, "device_lock", None) if store is not None \
            else None
        with (lock if lock is not None else contextlib.nullcontext()):
            if getattr(src, "_rerank_cache", None) is not None:
                src._rerank_cache = None
            cache = getattr(src, "_filter_cache", None)
            if cache:
                cache.clear()
            if store is not None:
                if getattr(store, "vecs_blk", None) is not None:
                    store.vecs_blk = None
                    store.bsq_blk = None
                if getattr(store, "adj", None) is not None:
                    store.adj = None
                    store.graph_deg = 0
        from dingo_tpu.obs.hbm import HBM

        HBM.update_region(region_id, {})   # zero the live owner gauges
        HBM.forget_region(region_id)       # drop peaks: DEVPEAK reflects it

    def _mmap_file(self, region_id: int) -> str:
        from dingo_tpu.common.config import FLAGS

        root = str(FLAGS.get("tier_mmap_dir") or "").strip()
        if not root:
            root = os.path.join(
                tempfile.gettempdir(), f"dingo_tier_{os.getpid()}"
            )
        return os.path.join(root, f"region_{region_id}.codes")


class TierRunner:
    """`memory_tier` crontab body (server/main.py): re-applies
    tier.interval_s each tick (hot-reloadable like every other runner),
    gates on tier.enabled, and runs the policy tick on a single worker
    thread — a demotion is a full-region copy, and the crontab thread
    must not stall behind it (IntegrityScrubRunner discipline)."""

    def __init__(self, node, crontab=None):
        self.node = node
        self._crontab = crontab
        self._worker: Optional[threading.Thread] = None
        self.ticks = 0

    def tick(self) -> None:
        if self._crontab is not None:
            from dingo_tpu.common.config import FLAGS

            self._crontab.set_interval(
                "memory_tier", float(FLAGS.get("tier_interval_s"))
            )
        if not TierManager.enabled():
            return
        t = self._worker
        if t is not None and t.is_alive():
            return   # previous transition still copying; skip this tick

        def work():
            try:
                TIERING.tick(self.node)
            except Exception:  # noqa: BLE001 — maintenance must not die
                _log.exception("tier tick failed")
            self.ticks += 1

        t = threading.Thread(  # dingolint: ok[context-handoff]
            target=work, name="memory_tier", daemon=True
        )
        self._worker = t
        t.start()


#: process-global ladder (one device; regions share the HBM budget)
TIERING = TierManager()
