"""Device (HBM) memory accounting.

Two views, both best-effort and safe on any backend:

- device_memory_stats(): process-level gauges from the JAX runtime's
  per-device allocator stats (bytes_in_use / limit / peak summed over
  local devices). TPU/GPU report real HBM; the CPU backend may return
  nothing — callers get zeros, never an exception.
- live_device_bytes(*roots): per-object accounting — walk an index (or
  wrapper) object graph and sum the nbytes of every distinct live
  jax.Array reachable from it. This is the per-index HBM footprint the
  allocator stats can't attribute.

The walker recurses only into dingo_tpu-defined objects and plain
containers, and skips engine/storage types by name — a MemEngine holds
the whole keyspace as Python bytes and walking it would be O(dataset)
per metrics tick.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable

#: object types the walker must not enter (big host-side payload holders —
#: the data CF is not device memory, and walking it costs O(keys))
_SKIP_TYPE_NAMES = frozenset({
    "MemEngine", "WalEngine", "LsmRawEngine", "RawEngine", "SortedKv",
    "RaftStoreEngine", "Storage", "StoreMetaManager", "RaftLog",
    "VectorIndexManager", "StoreNode", "Region",
})


def device_memory_stats() -> Dict[str, Any]:
    """Summed allocator stats over local devices ({} of zeros when the
    backend exposes none — e.g. CPU builds without allocator stats)."""
    out = {
        "devices": 0,
        "bytes_in_use": 0,
        "bytes_limit": 0,
        "peak_bytes_in_use": 0,
        "per_device_bytes_in_use": {},
    }
    try:
        import jax

        devices = jax.local_devices()
    except Exception:  # noqa: BLE001 — no runtime at all
        return out
    for d in devices:
        try:
            ms = d.memory_stats()
        except Exception:  # noqa: BLE001
            ms = None
        if not ms:
            continue
        out["devices"] += 1
        out["per_device_bytes_in_use"][d.id] = int(ms.get("bytes_in_use", 0))
        out["bytes_in_use"] += int(ms.get("bytes_in_use", 0))
        out["bytes_limit"] += int(ms.get("bytes_limit", 0))
        out["peak_bytes_in_use"] += int(ms.get("peak_bytes_in_use", 0))
    return out


def _children(obj) -> Iterable:
    d = getattr(obj, "__dict__", None)
    if d:
        yield from d.values()
    for slots_of in type(obj).__mro__:
        for name in getattr(slots_of, "__slots__", ()):
            try:
                yield getattr(obj, name)
            except AttributeError:
                continue


def live_device_bytes(*roots, max_depth: int = 4) -> int:
    """Sum of nbytes of distinct jax.Arrays reachable from `roots`
    (deduped by id — a shared/sibling index counted once)."""
    try:
        import jax
    except Exception:  # noqa: BLE001
        return 0
    return _sum_live_bytes(jax, roots, set(), max_depth)


def live_device_bytes_by_owner(owned_roots, max_depth: int = 4):
    """Per-owner device-byte attribution over a SHARED dedup set: walk the
    (owner, root) pairs in order and charge each distinct jax.Array to the
    FIRST owner that reaches it. This is the hbm ledger's region view —
    owners overlap (an IVF view holds gathered copies, a rerank cache
    shares the store's lock but not its buffers) and the shared `seen` set
    is what keeps the owner columns summable without double-booking."""
    try:
        import jax
    except Exception:  # noqa: BLE001
        return {owner: 0 for owner, _ in owned_roots}
    seen: set = set()
    return {
        owner: _sum_live_bytes(jax, (root,), seen, max_depth)
        for owner, root in owned_roots
    }


def _sum_live_bytes(jax, roots, seen, max_depth: int) -> int:
    total = 0
    stack = [(r, 0) for r in roots if r is not None]
    while stack:
        obj, depth = stack.pop()
        if obj is None or id(obj) in seen:
            continue
        seen.add(id(obj))
        if isinstance(obj, jax.Array):
            try:
                total += int(obj.nbytes)
            except Exception:  # noqa: BLE001 — deleted/donated buffer
                pass
            continue
        if depth >= max_depth:
            continue
        if isinstance(obj, (list, tuple, set, frozenset)):
            stack.extend((x, depth + 1) for x in obj)
            continue
        if isinstance(obj, dict):
            stack.extend((x, depth + 1) for x in obj.values())
            continue
        cls = type(obj)
        if cls.__name__ in _SKIP_TYPE_NAMES:
            continue
        if (cls.__module__ or "").startswith("dingo_tpu"):
            stack.extend((c, depth + 1) for c in _children(obj))
    return total
