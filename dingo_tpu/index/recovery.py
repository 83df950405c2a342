"""Device-failure recovery: the graduated HBM OOM ladder + degraded mode.

A device allocation failure (real XlaRuntimeError RESOURCE_EXHAUSTED, or
the chaos shim's indistinguishable InjectedDeviceFault — ops/devfault.py)
during an index write or search used to propagate raw: a raft apply would
fail, a search would 500. The ladder turns it into graceful degradation:

  rung 1  drop_rerank   — free the region's DeviceRerankCache (bf16/sq8
                          tiers; recall-advisory, rebuilt by future offers)
  rung 2  evict_mirrors — free the dimension-blocked scan mirror (a
                          DERIVED copy; the pruned kernel falls back to
                          the dense path that gates on `vecs_blk is not
                          None`). An HNSW adjacency is the graph itself
                          and stays
  rung 3  retry         — re-run the failed op once against the slimmer
                          footprint (index mutations are upserts/deletes:
                          idempotent, safe to re-apply)

If the retry still OOMs the region goes **device-degraded**: writes stop
materializing into the device index (the engine — raft/WAL — remains the
source of truth and keeps every write; apply_log_id does NOT advance, so
replica digest comparisons at equal applied indices stay sound), searches
are served exact from the engine via the host path
(vector_reader._host_exact_search), the heartbeat carries a
device_degraded flag (`cluster top` shows DEV-DEGRADED), and a background
re-materialization rebuilds the index from the engine at an
advisory-lower precision tier (device_recovery.remat_precision) — the
region DEFINITION keeps its declared precision, only the resident build
narrows. On success the region exits degraded mode with full parity.

The same plane owns the scrub-corruption response: a region whose
integrity scrub confirmed a device-state mismatch (PR 11) is rebuilt
from the engine — rebuild-from-truth, same mechanism, no precision drop.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Optional

from dingo_tpu.common.log import get_logger, region_log
from dingo_tpu.common.metrics import METRICS

_log = get_logger("index.recovery")

#: ladder rung names (metric label values for fault.oom_recoveries)
RUNG_DROP_RERANK = "drop_rerank"
RUNG_EVICT_MIRRORS = "evict_mirrors"
RUNG_RETRY = "retry"
RUNG_DEGRADE = "degrade"


class DeviceDegraded(RuntimeError):
    """The ladder was exhausted: the region is now device-degraded and the
    op must be absorbed by the degraded path (host search / engine-only
    write), not retried against the device."""

    def __init__(self, region_id: int, cause: str = ""):
        super().__init__(
            f"region {region_id} device-degraded"
            + (f" ({cause})" if cause else "")
        )
        self.region_id = region_id


def _looks_like_oom(exc: BaseException) -> bool:
    from dingo_tpu.obs.hbm import looks_like_oom

    return looks_like_oom(exc)


class DeviceRecoveryPlane:
    """Process-global degraded-region registry + the OOM ladder."""

    def __init__(self, registry=METRICS):
        self._lock = threading.Lock()
        #: region_id -> {"reason", "since", "remat_pending"}
        self._degraded: Dict[int, Dict[str, Any]] = {}
        self._reg = registry
        self.ladder_runs = 0

    @staticmethod
    def enabled() -> bool:
        from dingo_tpu.common.config import FLAGS

        return bool(FLAGS.get("device_recovery_enabled"))

    # -- degraded registry ---------------------------------------------------
    def is_degraded(self, region_id: int) -> bool:
        if not self._degraded:      # serving fast path: one attribute read
            return False
        with self._lock:
            return region_id in self._degraded

    def degraded_regions(self) -> Dict[int, Dict[str, Any]]:
        with self._lock:
            return {rid: dict(info) for rid, info in self._degraded.items()}

    def mark_degraded(self, region_id: int, reason: str) -> None:
        with self._lock:
            fresh = region_id not in self._degraded
            self._degraded[region_id] = {
                "reason": reason,
                "since": time.time(),
                "remat_pending": True,
            }
            n = len(self._degraded)
        if fresh:
            self._reg.counter("fault.oom_recoveries",
                              labels={"rung": RUNG_DEGRADE}).add(1)
            from dingo_tpu.obs.events import EVENTS

            EVENTS.emit(
                "recovery", region_id, "device_degraded", 0, 1,
                trigger="oom",
                evidence={"rung": RUNG_DEGRADE, "reason": reason},
            )
            region_log(_log, region_id).error(
                "region device-degraded (%s): serving host-exact, "
                "device writes deferred to re-materialization", reason)
        self._reg.gauge("fault.degraded_regions").set(float(n))
        # published (digest, applied) pairs can be torn by the partial
        # device write that stranded us here — withhold this region's
        # verdict until the re-materialized index re-primes the ledger
        from dingo_tpu.obs.integrity import INTEGRITY

        INTEGRITY.forget_region(region_id)

    def clear_degraded(self, region_id: int) -> None:
        with self._lock:
            self._degraded.pop(region_id, None)
            n = len(self._degraded)
        self._reg.gauge("fault.degraded_regions").set(float(n))

    # -- the ladder ----------------------------------------------------------
    def attempt(self, wrapper, region_id: int, op: Callable[[], Any],
                kind: str = "op", cause: Optional[BaseException] = None):
        """Run `op()` with OOM recovery: on an OOM-classified failure walk
        the ladder (drop rerank -> evict mirrors) and retry once; a second
        OOM marks the region degraded and raises DeviceDegraded. Non-OOM
        exceptions propagate untouched. Pass `cause` when the caller
        already caught the first OOM itself — the initial run is skipped
        and the ladder starts immediately."""
        first = cause
        if first is None:
            try:
                return op()
            except Exception as e:  # noqa: BLE001 — classified below
                if not _looks_like_oom(e) or not self.enabled():
                    raise
                first = e
        t0 = time.perf_counter()
        self.ladder_runs += 1
        region_log(_log, region_id).warning(
            "device OOM during %s (%s: %s) — running recovery ladder",
            kind, type(first).__name__, first)
        self._run_ladder(wrapper, region_id)
        try:
            out = op()
        except Exception as e2:  # noqa: BLE001
            if not _looks_like_oom(e2):
                raise
            self.mark_degraded(region_id, f"oom during {kind}")
            self._reg.latency("fault.recovery_ms").observe_us(
                (time.perf_counter() - t0) * 1e6)
            raise DeviceDegraded(region_id, f"oom during {kind}") from e2
        self._reg.counter("fault.oom_recoveries",
                          labels={"rung": RUNG_RETRY}).add(1)
        self._reg.latency("fault.recovery_ms").observe_us(
            (time.perf_counter() - t0) * 1e6)
        region_log(_log, region_id).info(
            "device OOM recovered by ladder retry (%s)", kind)
        return out

    def _run_ladder(self, wrapper, region_id: int) -> None:
        from dingo_tpu.obs.events import EVENTS

        idx = getattr(wrapper, "own_index", None) if wrapper else None
        if idx is None:
            return
        if self._drop_rerank(idx):
            self._reg.counter("fault.oom_recoveries",
                              labels={"rung": RUNG_DROP_RERANK}).add(1)
            EVENTS.emit("recovery", region_id, "recovery_rung", "",
                        RUNG_DROP_RERANK, trigger="oom",
                        evidence={"rung": RUNG_DROP_RERANK})
        if self._evict_mirrors(idx):
            self._reg.counter("fault.oom_recoveries",
                              labels={"rung": RUNG_EVICT_MIRRORS}).add(1)
            EVENTS.emit("recovery", region_id, "recovery_rung", "",
                        RUNG_EVICT_MIRRORS, trigger="oom",
                        evidence={"rung": RUNG_EVICT_MIRRORS})

    @staticmethod
    def _drop_rerank(idx) -> bool:
        if getattr(idx, "_rerank_cache", None) is None:
            return False
        idx._rerank_cache = None
        return True

    @staticmethod
    def _evict_mirrors(idx) -> bool:
        store = getattr(idx, "store", None)
        if store is None:
            return False
        freed = False
        lock = getattr(store, "device_lock", None)
        import contextlib

        with (lock if lock is not None else contextlib.nullcontext()):
            if getattr(store, "vecs_blk", None) is not None:
                # the pruned streaming kernel gates on `vecs_blk is not
                # None` (index/flat.py) and the write path skips the
                # mirror when absent — dropping it is a clean fallback
                # to the dense scan, not a correctness change
                store.vecs_blk = None
                store.bsq_blk = None
                freed = True
        return freed

    # -- re-materialization --------------------------------------------------
    @staticmethod
    def remat_parameter(param):
        """The advisory-lower-precision build parameter for a degraded
        region's re-materialization. The region definition is untouched —
        this narrows only the resident rebuild. Thin shim over the ONE
        shared precision-override helper (index/manager.py
        precision_override, also the tier ladder's arm)."""
        from dingo_tpu.common.config import FLAGS
        from dingo_tpu.index.manager import precision_override

        target = str(FLAGS.get("device_recovery_remat_precision"))
        return precision_override(param, target)

    def rematerialize(self, manager, region, raft_log=None) -> bool:
        """Rebuild a degraded region's index from the engine (source of
        truth) at the advisory-lower precision, then exit degraded mode.
        Returns False when a rebuild is already in flight (retried by the
        next maintenance tick). Rides manager.rebuild_at_precision — the
        same arm the deliberate tier ladder uses — so the emergency path
        has no private rebuild copy."""
        from dingo_tpu.common.config import FLAGS

        rid = region.id
        target = str(FLAGS.get("device_recovery_remat_precision"))
        try:
            ok = manager.rebuild_at_precision(region, raft_log=raft_log,
                                              precision=target)
        except Exception:
            region_log(_log, rid).exception("re-materialization failed")
            return False
        if not ok:
            return False
        self._reg.counter("fault.rematerializations").add(1)
        # remat rides the streaming bulk-build arm (ISSUE 18c): repair
        # time IS degraded-serving time, so the build plane counts remats
        # next to its rows/batches series
        self._reg.counter("build.remat_rebuilds", region_id=rid).add(1)
        from dingo_tpu.obs.events import EVENTS

        EVENTS.emit(
            "recovery", rid, "device_degraded", 1, 0, trigger="remat",
            evidence={"precision": target or "default"},
        )
        self.clear_degraded(rid)
        region_log(_log, rid).info(
            "re-materialized from engine at precision=%s — degraded "
            "mode cleared", target or "default")
        return True

    def run_rematerializations(self, node) -> int:
        """Maintenance-tick body (rides the integrity scrub crontab):
        re-materialize every degraded region of `node`, and rebuild-from-
        engine every region whose scrub confirmed device-state corruption
        (the PR 11 poisoned-array response)."""
        n = 0
        pending = self.degraded_regions()
        for rid, info in pending.items():
            if not info.get("remat_pending"):
                continue
            region = node.meta.get_region(rid)
            if region is None:                 # region gone: just clear
                self.clear_degraded(rid)
                continue
            raft_node = node.engine.get_node(rid)
            raft_log = raft_node.log if raft_node is not None else None
            if self.rematerialize(node.index_manager, region,
                                  raft_log=raft_log):
                n += 1
        n += self._rebuild_corrupted(node)
        return n

    def _rebuild_corrupted(self, node) -> int:
        """Scrub-confirmed mismatches: rebuild the poisoned index from the
        engine. The scrub status holds ``mismatch=True`` until a clean
        decisive pass over the REBUILT index clears it."""
        from dingo_tpu.obs.integrity import INTEGRITY

        n = 0
        for region in node.meta.get_all_regions():
            _a, _d, mismatch = INTEGRITY.region_report(None, region.id)
            if not mismatch:
                continue
            wrapper = region.vector_index_wrapper
            if wrapper is None or wrapper.own_index is None:
                continue
            raft_node = node.engine.get_node(region.id)
            raft_log = raft_node.log if raft_node is not None else None
            try:
                if node.index_manager.rebuild(region, raft_log=raft_log):
                    self._reg.counter("fault.rebuilds").add(1)
                    # fresh index, fresh ledger; the stale CORRUPT verdict
                    # belongs to the poisoned index that no longer serves
                    INTEGRITY.forget_region(region.id)
                    INTEGRITY.rebuild_from_index(wrapper.own_index)
                    region_log(_log, region.id).warning(
                        "corrupted device state rebuilt from engine")
                    n += 1
            except Exception:
                region_log(_log, region.id).exception(
                    "corruption rebuild failed")
        return n

    def clear(self) -> None:
        with self._lock:
            self._degraded.clear()
        self._reg.gauge("fault.degraded_regions").set(0.0)


#: process-global plane (one device; regions share the HBM failure domain)
RECOVERY = DeviceRecoveryPlane()
