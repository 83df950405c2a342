"""Deterministic chaos harness: declarative fault scenarios with gates.

Every resilience claim the stack has accumulated — WAL-durable acked
writes (PR 3), raft failover, the QoS goodput floor (PR 10), digest-clean
state (PR 11), and the device-OOM recovery ladder (index/recovery.py) —
is exercised here against REAL injected faults and turned into a
machine-checked verdict. Scenarios run the in-process cluster topology
the integration tests use (LocalTransport + CoordinatorControl +
StoreNode) with the fault planes this PR added:

  * TransportFaults    — seeded drop/delay/duplicate/partition per
                         store-pair (raft/transport.py)
  * DEVFAULT           — synthetic RESOURCE_EXHAUSTED at the sentinel_jit
                         dispatch chokepoint (ops/devfault.py)
  * process kill       — node.stop() + engine close, the in-proc
                         equivalent of SIGKILL; restart goes through the
                         real recovery path (StoreNode.recover)
  * flipped byte       — host-side corruption of a device array, caught
                         by the PR 11 scrub and healed by the recovery
                         plane's rebuild-from-engine

Gates (per scenario): ZERO acknowledged-write loss — every id whose
vector_add returned is re-read after recovery AND the integrity scrub
reports digest-clean state; bounded recovery time; a goodput floor for
read traffic during the fault window; and zero steady-state recompiles
after recovery (warm searches must not re-trace).

Determinism: every randomized actor is seeded (numpy corpus, raft
election jitter via raft_kw seeds, TransportFaults rng, DEVFAULT count
arming) so a failing run replays exactly from its printed seed.

CLI:  python tools/chaos.py [--seed N] [--json] [scenario ...]
      (no scenario args = the full suite)
"""

from __future__ import annotations

import contextlib
import json
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

# scenario time bounds (seconds) — generous for the CPU smoke arm; the
# signal is "bounded at all", not a latency benchmark
RECOVERY_BOUND_S = 15.0
#: read goodput floor during the fault window for scenarios that keep
#: replicas serving (leader failover / partition: follower reads and the
#: survivor majority must keep answering)
GOODPUT_FLOOR = 0.9

DIM = 16


def _log(msg: str) -> None:
    print(f"[chaos] {msg}", file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# cluster scaffolding
# --------------------------------------------------------------------------

class Cluster:
    """In-process store cluster with the fault planes attached."""

    def __init__(self, n_stores: int, replication: int, seed: int,
                 data_dir: Optional[str] = None):
        from dingo_tpu.coordinator.control import CoordinatorControl
        from dingo_tpu.engine.raw_engine import MemEngine, WalEngine
        from dingo_tpu.raft.transport import LocalTransport, TransportFaults
        from dingo_tpu.store.node import StoreNode

        self.seed = seed
        self.data_dir = data_dir
        self.transport = LocalTransport(seed=seed)
        self.faults = TransportFaults(seed=seed)
        self.transport.faults = self.faults
        self.coord = CoordinatorControl(MemEngine(), replication=replication)
        self.nodes: Dict[str, StoreNode] = {}
        self._engines: Dict[str, Any] = {}
        for i in range(n_stores):
            sid = f"s{i}"
            if data_dir is not None:
                raw = WalEngine(f"{data_dir}/{sid}",
                                checkpoint_threshold_bytes=1 << 20)
            else:
                raw = MemEngine()
            self._engines[sid] = raw
            self.nodes[sid] = StoreNode(
                sid, self.transport, self.coord,
                raw_engine=raw, raft_kw={"seed": seed + i})

    def create_region(self, index_type=None, precision: str = "",
                      part: int = 0, **param_kw):
        """One region over partition `part`'s whole id range — pass
        distinct parts to host several regions on one store (ranges may
        not overlap)."""
        from dingo_tpu.index import codec as vcodec
        from dingo_tpu.index.base import IndexParameter, IndexType
        from dingo_tpu.store.region import RegionType

        param = IndexParameter(
            index_type=index_type or IndexType.FLAT, dimension=DIM,
            precision=precision, **param_kw)
        d = self.coord.create_region(
            start_key=vcodec.encode_vector_key(part, 0),
            end_key=vcodec.encode_vector_key(part, 1 << 40),
            partition_id=part,
            region_type=RegionType.INDEX,
            index_parameter=param,
        )
        self.drive(rounds=3)
        return d.region_id

    def drive(self, rounds: int = 1, sleep: float = 0.03) -> None:
        for _ in range(rounds):
            for n in self.nodes.values():
                with contextlib.suppress(Exception):
                    n.heartbeat_once()
            time.sleep(sleep)

    def leader(self, region_id: int):
        """(store_id, node) currently claiming leadership, or None."""
        for sid, n in self.nodes.items():
            rn = n.engine.get_node(region_id)
            if rn is not None and rn.is_leader():
                return sid, n
        return None

    def wait_leader(self, region_id: int, timeout: float = 10.0,
                    exclude: Tuple[str, ...] = ()):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            self.drive(rounds=1, sleep=0.02)
            got = self.leader(region_id)
            if got is not None and got[0] not in exclude:
                return got
        raise AssertionError(f"no leader for region {region_id}")

    def kill(self, store_id: str) -> None:
        """In-proc SIGKILL: stop raft (unregisters transport handlers),
        close the engine. Nothing is flushed beyond what was acked."""
        node = self.nodes.pop(store_id)
        node.stop()
        with contextlib.suppress(Exception):
            self._engines[store_id].close()

    def restart(self, store_id: str, seed_offset: int = 100):
        """Bring a killed store back through the real recovery path."""
        from dingo_tpu.engine.raw_engine import WalEngine
        from dingo_tpu.store.node import StoreNode

        assert self.data_dir is not None, "restart needs durable engines"
        raw = WalEngine(f"{self.data_dir}/{store_id}",
                        checkpoint_threshold_bytes=1 << 20)
        self._engines[store_id] = raw
        node = StoreNode(store_id, self.transport, self.coord,
                         raw_engine=raw,
                         raft_kw={"seed": self.seed + seed_offset})
        node.recover()
        self.nodes[store_id] = node
        return node

    def close(self) -> None:
        from dingo_tpu.index.recovery import RECOVERY
        from dingo_tpu.index.tiering import TIERING
        from dingo_tpu.obs.integrity import INTEGRITY

        for n in self.nodes.values():
            with contextlib.suppress(Exception):
                n.stop()
        self.transport.heal()
        # the planes are process-global: scrub scenario state so the next
        # scenario (or the surrounding test process) starts clean
        RECOVERY.clear()
        INTEGRITY.clear()
        TIERING.reset()


@contextlib.contextmanager
def cluster(n_stores: int, replication: int, seed: int,
            durable: bool = False):
    tmp = tempfile.mkdtemp(prefix="chaos-") if durable else None
    c = Cluster(n_stores, replication, seed, data_dir=tmp)
    try:
        yield c
    finally:
        c.close()
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


# --------------------------------------------------------------------------
# verification helpers
# --------------------------------------------------------------------------

def _corpus(seed: int, n: int) -> Tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(seed)
    return (np.arange(n, dtype=np.int64),
            rng.standard_normal((n, DIM)).astype(np.float32))


def _acked_lost(node, region, acked: Dict[int, np.ndarray]) -> List[int]:
    """Ids that were acked but are NOT readable after recovery."""
    ids = sorted(acked)
    got = node.storage.vector_batch_query(region, ids)
    lost = []
    for vid, v in zip(ids, got):
        if v is None or v.vector is None:
            lost.append(vid)
            continue
        if not np.allclose(np.asarray(v.vector), acked[vid], atol=1e-5):
            lost.append(vid)
    return lost


def _digest_clean(node) -> bool:
    """One scrub sweep over the node: every artifact must verify against
    the incremental ledger (the PR 11 'state is what the log says' gate)."""
    from dingo_tpu.obs.integrity import INTEGRITY

    results = INTEGRITY.scrub_node(node)
    for per_artifact in results.values():
        for r in per_artifact.values():
            if r.get("status") not in ("ok", "skipped", "advisory"):
                return False
    return True


def _steady_recompiles(node, region, queries: np.ndarray,
                       reps: int = 3) -> int:
    """Recompile delta across repeated identical searches AFTER one
    warmup (the steady-state invariant: warm serving never re-traces)."""
    from dingo_tpu.obs.sentinel import SENTINEL

    node.storage.vector_batch_search(region, queries, 3)  # warm
    before = SENTINEL.recompiles()
    for _ in range(reps):
        node.storage.vector_batch_search(region, queries, 3)
    return SENTINEL.recompiles() - before


def _result(name: str, seed: int, **kw) -> Dict[str, Any]:
    gates = kw.pop("gates")
    out = {"name": name, "seed": seed, **kw, "gates": gates,
           "passed": all(gates.values())}
    verdict = "PASS" if out["passed"] else "FAIL"
    _log(f"{name}: {verdict} "
         + " ".join(f"{g}={'ok' if v else 'VIOLATED'}"
                    for g, v in gates.items()))
    return out


# --------------------------------------------------------------------------
# scenarios
# --------------------------------------------------------------------------

def scenario_kill_restart(seed: int) -> Dict[str, Any]:
    """Kill a store mid-write-batch (engine closed un-flushed beyond acks),
    restart through StoreNode.recover(). Gate: every acked write survives,
    digest-clean, bounded recovery, post-restart writes work."""
    with cluster(1, replication=1, seed=seed, durable=True) as c:
        rid = c.create_region()
        _sid, node = c.wait_leader(rid)
        region = node.get_region(rid)
        ids, x = _corpus(seed, 96)
        acked: Dict[int, np.ndarray] = {}
        # write in small batches; the kill lands between two acks, which
        # is exactly "mid-write-batch" from the client's point of view
        for lo in range(0, 64, 8):
            sl = slice(lo, lo + 8)
            node.storage.vector_add(region, ids[sl], x[sl])
            for i in range(lo, lo + 8):
                acked[int(ids[i])] = x[i]
        c.kill("s0")

        t0 = time.perf_counter()
        node2 = c.restart("s0")
        c.wait_leader(rid)
        region2 = node2.get_region(rid)
        # recovered = first read answered
        node2.storage.vector_batch_search(region2, x[:1], 3)
        recovery_ms = (time.perf_counter() - t0) * 1e3

        lost = _acked_lost(node2, region2, acked)
        clean = _digest_clean(node2)
        # still writable after recovery
        node2.storage.vector_add(region2, ids[64:72], x[64:72])
        got = node2.storage.vector_batch_query(region2, [int(ids[64])])
        writable = got[0] is not None
        recompiles = _steady_recompiles(node2, region2, x[:4])
        return _result(
            "kill_restart", seed,
            acked=len(acked), lost=len(lost), lost_ids=lost[:8],
            recovery_ms=round(recovery_ms, 1),
            recovery_bound_ms=RECOVERY_BOUND_S * 1e3,
            steady_recompiles=recompiles,
            gates={
                "zero_acked_loss": not lost,
                "digest_clean": clean,
                "recovery_bounded": recovery_ms <= RECOVERY_BOUND_S * 1e3,
                "writable_after_recovery": writable,
                "zero_steady_recompiles": recompiles == 0,
            })


def _traffic_window(c: Cluster, rid: int, queries: np.ndarray,
                    duration_s: float, exclude: Tuple[str, ...] = ()
                    ) -> Tuple[int, int]:
    """Fire read traffic at every live replica for `duration_s` while
    driving heartbeats; returns (served, attempted)."""
    served = attempted = 0
    deadline = time.monotonic() + duration_s
    while time.monotonic() < deadline:
        c.drive(rounds=1, sleep=0.01)
        for sid, n in list(c.nodes.items()):
            if sid in exclude:
                continue
            region = n.get_region(rid)
            if region is None:
                continue
            attempted += 1
            try:
                res = n.storage.vector_batch_search(region, queries[:1], 3)
                if res and res[0]:
                    served += 1
            except Exception:  # noqa: BLE001 — counted as unserved
                pass
    return served, attempted


def _write_until_ok(c: Cluster, rid: int, ids, vecs,
                    timeout_s: float, exclude: Tuple[str, ...] = ()
                    ) -> float:
    """Retry one write batch against whichever node claims leadership
    until it lands; returns elapsed ms (the write-recovery time)."""
    from dingo_tpu.raft.core import NotLeader, ProposalFailed

    t0 = time.perf_counter()
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        c.drive(rounds=1, sleep=0.02)
        got = c.leader(rid)
        if got is None or got[0] in exclude:
            continue
        _sid, node = got
        region = node.get_region(rid)
        if region is None:
            continue
        try:
            node.storage.vector_add(region, ids, vecs)
            return (time.perf_counter() - t0) * 1e3
        except (NotLeader, ProposalFailed):
            continue
    raise AssertionError("write never recovered inside the bound")


def scenario_leader_failover(seed: int) -> Dict[str, Any]:
    """Kill the raft leader under live traffic. Gates: survivors keep
    serving reads (goodput floor), a new leader accepts writes inside the
    bound, no acked write is lost, replicas stay digest-clean."""
    with cluster(3, replication=3, seed=seed) as c:
        rid = c.create_region()
        lsid, lnode = c.wait_leader(rid)
        region = lnode.get_region(rid)
        ids, x = _corpus(seed, 96)
        acked: Dict[int, np.ndarray] = {}
        for lo in range(0, 48, 8):
            sl = slice(lo, lo + 8)
            lnode.storage.vector_add(region, ids[sl], x[sl])
            for i in range(lo, lo + 8):
                acked[int(ids[i])] = x[i]
        c.drive(rounds=3)  # let followers apply

        c.kill(lsid)
        # fault window: read traffic against the survivors
        served, attempted = _traffic_window(c, rid, x, duration_s=1.0)
        recovery_ms = _write_until_ok(
            c, rid, ids[48:56], x[48:56], RECOVERY_BOUND_S)
        for i in range(48, 56):
            acked[int(ids[i])] = x[i]

        _sid2, node2 = c.wait_leader(rid)
        region2 = node2.get_region(rid)
        lost = _acked_lost(node2, region2, acked)
        clean = all(_digest_clean(n) for n in c.nodes.values())
        goodput = served / attempted if attempted else 0.0
        recompiles = _steady_recompiles(node2, region2, x[:4])
        return _result(
            "leader_failover", seed,
            acked=len(acked), lost=len(lost), lost_ids=lost[:8],
            recovery_ms=round(recovery_ms, 1),
            recovery_bound_ms=RECOVERY_BOUND_S * 1e3,
            goodput=round(goodput, 4), goodput_floor=GOODPUT_FLOOR,
            reads_served=served, reads_attempted=attempted,
            steady_recompiles=recompiles,
            gates={
                "zero_acked_loss": not lost,
                "digest_clean": clean,
                "recovery_bounded": recovery_ms <= RECOVERY_BOUND_S * 1e3,
                "goodput_floor": goodput >= GOODPUT_FLOOR,
                "zero_steady_recompiles": recompiles == 0,
            })


def scenario_partition_heal(seed: int) -> Dict[str, Any]:
    """Partition the leader away from both followers; the majority side
    elects, keeps serving and accepting writes; heal; the old leader
    rejoins and catches up to byte-identical state."""
    with cluster(3, replication=3, seed=seed) as c:
        rid = c.create_region()
        lsid, lnode = c.wait_leader(rid)
        region = lnode.get_region(rid)
        ids, x = _corpus(seed, 96)
        acked: Dict[int, np.ndarray] = {}
        for lo in range(0, 32, 8):
            sl = slice(lo, lo + 8)
            lnode.storage.vector_add(region, ids[sl], x[sl])
            for i in range(lo, lo + 8):
                acked[int(ids[i])] = x[i]
        c.drive(rounds=3)

        others = [sid for sid in c.nodes if sid != lsid]
        for sid in others:
            c.faults.partition(lsid, sid)
        served, attempted = _traffic_window(
            c, rid, x, duration_s=1.0, exclude=(lsid,))
        recovery_ms = _write_until_ok(
            c, rid, ids[32:40], x[32:40], RECOVERY_BOUND_S, exclude=(lsid,))
        for i in range(32, 40):
            acked[int(ids[i])] = x[i]

        c.faults.heal()
        # old leader steps down and catches up; poll until it holds every
        # acked write (raft log replay through the real apply path)
        deadline = time.monotonic() + RECOVERY_BOUND_S
        caught_up = False
        while time.monotonic() < deadline and not caught_up:
            c.drive(rounds=2, sleep=0.03)
            old = c.nodes[lsid]
            r_old = old.get_region(rid)
            caught_up = r_old is not None and not _acked_lost(
                old, r_old, acked)
        lost_each = {sid: len(_acked_lost(n, n.get_region(rid), acked))
                     for sid, n in c.nodes.items()}
        clean = all(_digest_clean(n) for n in c.nodes.values())
        goodput = served / attempted if attempted else 0.0
        return _result(
            "partition_heal", seed,
            acked=len(acked), lost=max(lost_each.values()),
            lost_by_store=lost_each,
            recovery_ms=round(recovery_ms, 1),
            recovery_bound_ms=RECOVERY_BOUND_S * 1e3,
            goodput=round(goodput, 4), goodput_floor=GOODPUT_FLOOR,
            old_leader_caught_up=caught_up,
            gates={
                "zero_acked_loss": max(lost_each.values()) == 0,
                "digest_clean": clean,
                "recovery_bounded": recovery_ms <= RECOVERY_BOUND_S * 1e3,
                "goodput_floor": goodput >= GOODPUT_FLOOR,
                "partitioned_leader_caught_up": caught_up,
            })


def scenario_oom_storm(seed: int) -> Dict[str, Any]:
    """Arm the device-fault shim for EVERY dispatch: writes and reads must
    keep being served (ladder -> degraded -> host path), never raise; on
    disarm the background re-materialization restores device serving with
    zero steady-state recompiles."""
    from dingo_tpu.index.recovery import RECOVERY
    from dingo_tpu.ops.devfault import DEVFAULT

    with cluster(1, replication=1, seed=seed) as c:
        rid = c.create_region()
        _sid, node = c.wait_leader(rid)
        region = node.get_region(rid)
        ids, x = _corpus(seed, 96)
        node.storage.vector_add(region, ids[:32], x[:32])
        acked = {int(ids[i]): x[i] for i in range(32)}

        DEVFAULT.arm(1 << 30)
        try:
            served = attempted = 0
            unhandled: List[str] = []
            for lo in range(32, 64, 8):
                sl = slice(lo, lo + 8)
                attempted += 1
                try:
                    node.storage.vector_add(region, ids[sl], x[sl])
                    for i in range(lo, lo + 8):
                        acked[int(ids[i])] = x[i]
                    served += 1
                except Exception as e:  # noqa: BLE001 — the gate itself
                    unhandled.append(f"write: {type(e).__name__}: {e}")
                attempted += 1
                try:
                    res = node.storage.vector_batch_search(
                        region, x[lo:lo + 1], 3)
                    if res and res[0] and res[0][0].id == int(ids[lo]):
                        served += 1
                except Exception as e:  # noqa: BLE001
                    unhandled.append(f"search: {type(e).__name__}: {e}")
            degraded = RECOVERY.is_degraded(rid)
        finally:
            DEVFAULT.disarm()

        t0 = time.perf_counter()
        remats = RECOVERY.run_rematerializations(node)
        recovery_ms = (time.perf_counter() - t0) * 1e3
        lost = _acked_lost(node, region, acked)
        clean = _digest_clean(node)
        recompiles = _steady_recompiles(node, region, x[:4])
        goodput = served / attempted if attempted else 0.0
        return _result(
            "oom_storm", seed,
            acked=len(acked), lost=len(lost), lost_ids=lost[:8],
            degraded_during_storm=degraded, rematerializations=remats,
            recovery_ms=round(recovery_ms, 1),
            recovery_bound_ms=RECOVERY_BOUND_S * 1e3,
            goodput=round(goodput, 4), goodput_floor=1.0,
            unhandled=unhandled[:4],
            steady_recompiles=recompiles,
            gates={
                "every_request_served": not unhandled and goodput == 1.0,
                "region_degraded_then_recovered":
                    degraded and remats >= 1
                    and not RECOVERY.is_degraded(rid),
                "zero_acked_loss": not lost,
                "digest_clean": clean,
                "recovery_bounded": recovery_ms <= RECOVERY_BOUND_S * 1e3,
                "zero_steady_recompiles": recompiles == 0,
            })


def scenario_bitflip(seed: int) -> Dict[str, Any]:
    """One flipped byte in a device array: the integrity scrub must catch
    it and the recovery plane must rebuild from the engine instead of
    serving corruption."""
    import jax.numpy as jnp

    from dingo_tpu.index.recovery import RECOVERY
    from dingo_tpu.obs.integrity import INTEGRITY

    with cluster(1, replication=1, seed=seed) as c:
        rid = c.create_region()
        _sid, node = c.wait_leader(rid)
        region = node.get_region(rid)
        ids, x = _corpus(seed, 64)
        node.storage.vector_add(region, ids, x)
        acked = {int(ids[i]): x[i] for i in range(len(ids))}
        idx = region.vector_index_wrapper.own_index
        INTEGRITY.scrub_index(idx)
        assert INTEGRITY.region_report(idx)[2] is False

        # flip one byte of one resident row (silent HBM/restore corruption)
        slot = int(idx.store.slots_of(ids[:1])[0])
        arr = np.asarray(idx.store.vecs).copy()
        arr.view(np.uint8)[slot, 0] ^= 1
        with idx.store.device_lock:
            idx.store.vecs = jnp.asarray(arr)

        t0 = time.perf_counter()
        INTEGRITY.scrub_index(idx)
        detected = INTEGRITY.region_report(idx)[2] is True
        rebuilt = RECOVERY.run_rematerializations(node)
        recovery_ms = (time.perf_counter() - t0) * 1e3

        region2 = node.get_region(rid)
        lost = _acked_lost(node, region2, acked)
        res = node.storage.vector_batch_search(region2, x[:4], 1)
        parity = all(r[0].id == int(ids[i]) for i, r in enumerate(res))
        idx2 = region2.vector_index_wrapper.own_index
        INTEGRITY.scrub_index(idx2)
        clean = INTEGRITY.region_report(idx2)[2] is False
        return _result(
            "bitflip", seed,
            acked=len(acked), lost=len(lost),
            detected=detected, rebuilds=rebuilt,
            recovery_ms=round(recovery_ms, 1),
            recovery_bound_ms=RECOVERY_BOUND_S * 1e3,
            search_parity=parity,
            gates={
                "scrub_detected_flip": detected,
                "rebuilt_from_engine": rebuilt >= 1,
                "zero_acked_loss": not lost,
                "search_parity": parity,
                "digest_clean_after_rebuild": clean,
                "recovery_bounded": recovery_ms <= RECOVERY_BOUND_S * 1e3,
            })


class _TierKill(RuntimeError):
    """Sentinel the tier-transition test hook raises after the in-proc
    SIGKILL so the interrupted transition unwinds like the dying process
    would have."""


def scenario_tier_kill(seed: int) -> Dict[str, Any]:
    """Process kill MID-TIER-TRANSITION (ISSUE 19): once between the
    verified destination copy and the swap of a demotion, once inside a
    promotion. The ladder's crash story is that every transition is a
    copy + digest-gated swap over state the WAL already owns, so a kill
    at the worst moment costs nothing: restart rebuilds at the DECLARED
    tier from the engine and every acked write answers. Gates: zero
    acked-write loss after each restart, digest-clean scrub, bounded
    recovery, still writable, zero steady-state recompiles."""
    from dingo_tpu.index.tiering import RUNG_HOST_SQ8, TIERING

    with cluster(1, replication=1, seed=seed, durable=True) as c:
        rid = c.create_region()
        _sid, node = c.wait_leader(rid)
        region = node.get_region(rid)
        ids, x = _corpus(seed, 96)
        acked: Dict[int, np.ndarray] = {}
        for lo in range(0, 64, 8):
            sl = slice(lo, lo + 8)
            node.storage.vector_add(region, ids[sl], x[sl])
            for i in range(lo, lo + 8):
                acked[int(ids[i])] = x[i]

        # reach the device-sq8 rung, then die inside the hbm_sq8 ->
        # host_sq8 transcription: after the digest verify, before the swap
        assert TIERING.demote(node, region)["ok"]

        def kill_at(stage_name):
            def hook(stage, _ctx=None):
                if stage == stage_name:
                    c.kill("s0")
                    raise _TierKill(stage)
            return hook

        TIERING.test_hook = kill_at("mid_demote")
        try:
            TIERING.demote(node, region)
            raise AssertionError("demotion survived the kill hook")
        except _TierKill:
            pass
        finally:
            TIERING.test_hook = None

        t0 = time.perf_counter()
        node2 = c.restart("s0")
        c.wait_leader(rid)
        region2 = node2.get_region(rid)
        node2.storage.vector_batch_search(region2, x[:1], 3)
        recovery1_ms = (time.perf_counter() - t0) * 1e3
        TIERING.reset()   # in-proc restart: a real process loses this too
        lost1 = _acked_lost(node2, region2, acked)
        clean1 = _digest_clean(node2)

        # walk the survivor down to host RAM, then die mid-PROMOTION
        assert TIERING.demote(node2, region2)["ok"]
        assert TIERING.demote(node2, region2)["ok"]
        assert TIERING.state()[rid]["rung"] == "host_sq8"
        assert TIERING._regions[rid].rung == RUNG_HOST_SQ8
        TIERING.test_hook = kill_at("mid_promote")
        try:
            TIERING.promote(node2, region2)
            raise AssertionError("promotion survived the kill hook")
        except _TierKill:
            pass
        finally:
            TIERING.test_hook = None

        t0 = time.perf_counter()
        node3 = c.restart("s0", seed_offset=200)
        c.wait_leader(rid)
        region3 = node3.get_region(rid)
        node3.storage.vector_batch_search(region3, x[:1], 3)
        recovery2_ms = (time.perf_counter() - t0) * 1e3
        TIERING.reset()
        lost2 = _acked_lost(node3, region3, acked)
        clean2 = _digest_clean(node3)
        node3.storage.vector_add(region3, ids[64:72], x[64:72])
        got = node3.storage.vector_batch_query(region3, [int(ids[64])])
        writable = got[0] is not None
        recompiles = _steady_recompiles(node3, region3, x[:4])
        recovery_ms = max(recovery1_ms, recovery2_ms)
        return _result(
            "tier_kill", seed,
            acked=len(acked), lost=len(lost1) + len(lost2),
            lost_ids=(lost1 + lost2)[:8],
            recovery_ms=round(recovery_ms, 1),
            recovery_bound_ms=RECOVERY_BOUND_S * 1e3,
            steady_recompiles=recompiles,
            gates={
                "zero_acked_loss": not lost1 and not lost2,
                "digest_clean": clean1 and clean2,
                "recovery_bounded": recovery_ms <= RECOVERY_BOUND_S * 1e3,
                "writable_after_recovery": writable,
                "zero_steady_recompiles": recompiles == 0,
            })


SCENARIOS: Dict[str, Callable[[int], Dict[str, Any]]] = {
    "kill_restart": scenario_kill_restart,
    "leader_failover": scenario_leader_failover,
    "partition_heal": scenario_partition_heal,
    "oom_storm": scenario_oom_storm,
    "bitflip": scenario_bitflip,
    "tier_kill": scenario_tier_kill,
}


def run_scenarios(names: Optional[List[str]] = None,
                  seed: int = 0) -> Dict[str, Any]:
    """Run the named scenarios (default: all) and aggregate the verdict.
    An exception inside a scenario is a FAIL, not a crash of the suite."""
    picked = names or list(SCENARIOS)
    results: List[Dict[str, Any]] = []
    for name in picked:
        fn = SCENARIOS[name]
        _log(f"running {name} (seed={seed})")
        try:
            results.append(fn(seed))
        except Exception as e:  # noqa: BLE001 — scenario verdict
            _log(f"{name}: ERROR {type(e).__name__}: {e}")
            results.append({"name": name, "seed": seed, "passed": False,
                            "error": f"{type(e).__name__}: {e}",
                            "gates": {"completed": False}})
    return {
        "seed": seed,
        "scenarios": results,
        "passed": all(r["passed"] for r in results),
        # bench_diff-gated aggregates: worst-case recovery + goodput floor
        "max_recovery_ms": max(
            (r.get("recovery_ms", 0.0) for r in results), default=0.0),
        "min_goodput": min(
            (r["goodput"] for r in results if "goodput" in r), default=1.0),
    }


def main(argv: List[str]) -> int:
    seed = 0
    names: List[str] = []
    emit_json = False
    it = iter(argv)
    for a in it:
        if a == "--seed":
            seed = int(next(it))
        elif a == "--json":
            emit_json = True
        elif a in SCENARIOS:
            names.append(a)
        else:
            print(f"unknown scenario {a!r}; known: {', '.join(SCENARIOS)}",
                  file=sys.stderr)
            return 2
    out = run_scenarios(names or None, seed=seed)
    if emit_json:
        print(json.dumps(out, indent=2, default=str))
    else:
        for r in out["scenarios"]:
            status = "PASS" if r["passed"] else "FAIL"
            extra = f" error={r['error']}" if "error" in r else ""
            print(f"{r['name']:<18} {status}"
                  f"  recovery={r.get('recovery_ms', '-')}ms"
                  f"  goodput={r.get('goodput', '-')}{extra}")
        print("chaos:", "PASS" if out["passed"] else "FAIL")
    return 0 if out["passed"] else 1


if __name__ == "__main__":
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main(sys.argv[1:]))
