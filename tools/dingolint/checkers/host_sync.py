"""host-sync: no device->host synchronization on the hot search path.

The serving contract (PR 3, re-stated by the ROADMAP's stall-free-
pipeline item): ``search_async`` DISPATCHES — it uploads, launches
kernels, starts async D2H copies — and the returned ``resolve()`` thunk
is the single designated sync point, one ``device_get`` per reply. Any
other host sync inside the dispatch path serializes the device against
the host mid-flight: concurrent searches stop pipelining, the coalescer
batch behind the sync stalls, and sustained QPS collapses by exactly the
device round trip the async design exists to hide. KBest (PAPERS.md) ties
sustained throughput to keeping the kernel path fed; one stray
``np.asarray(jnp_value)`` un-feeds it.

Mechanics: the checker roots at every ``search`` / ``search_async`` def
in the index and parallel tiers, walks the call graph (exact + capped
fuzzy edges), and flags sync primitives in the closure:

- ``jax.device_get`` / ``jax.block_until_ready`` /
  ``<x>.block_until_ready()``;
- ``np.asarray(x)`` / ``float(x)`` where ``x`` is locally tainted by a
  ``jnp.*`` / ``jax.*`` producer (a host round-trip hidden in a cast).

Sanctioned sync points are excluded by construction, not baselined:

- nested defs named ``resolve`` (the contract's sync point) and
  anything only they call;
- the obs plane (``dingo_tpu/obs``) — its lanes are async/head-sampled
  by their own tested discipline (quality scoring, integrity scrub);
- ``copy_to_host_async`` is the opposite of a sync and never flagged.

A sampled-trace guard is NOT a sanction: a traced request that blocks
until ready (it once did, under the store's device lock on the IVF
path) measures another regime than the one it samples. Device waits are
timed by ``ops/distance.device_wait_begin``, whose span ends at
resolve()'s one fetch and never synchronises.

What's left is either a genuine stall (fix it) or a deliberate
synchronous design (the mesh tier's collective merge) that belongs in
the baseline with its rationale.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from tools.dingolint.callgraph import dotted_name
from tools.dingolint.core import Checker, Finding, Module, Repo

#: where search roots live (server/services funnels into these)
_ROOT_MODULE_PREFIXES = ("dingo_tpu.index.", "dingo_tpu.parallel.")
_ROOT_BASENAMES = {"search", "search_async"}

#: admission-path subsystems where EVERY def is hot: the serving-edge
#: cache is consulted BEFORE QoS queuing on the caller thread and its
#: dedupe plan runs on the flush thread — a device sync anywhere in the
#: package stalls admission itself, so the whole package roots (not just
#: defs named search)
_ADMISSION_MODULE_PREFIXES = ("dingo_tpu.cache.",)

#: bulk-build plane (ISSUE 18): construction is off the serving path,
#: but its own throughput contract is the same shape — insert_batch and
#: every per-batch helper must dispatch without waiting, so the pow2
#: insert ladder pipelines; the ONE sanctioned sync is finish() (read
#: the entry slot + drop counters once per whole build), which belongs
#: in the baseline with that rationale, exactly like resolve()
_BUILD_MODULE_PREFIXES = ("dingo_tpu.ops.graph_build",)

#: traversal never descends into these (their own discipline applies)
_SKIP_MODULE_PREFIXES = ("dingo_tpu.obs.", "dingo_tpu.trace.",
                         "dingo_tpu.metrics.")
_SKIP_BASENAMES = {"resolve"}

#: taint producers: a local assigned from one of these roots holds a
#: device value; float()/np.asarray() on it is a hidden sync
_DEVICE_ROOTS = {"jnp", "jax"}


def _tainted_names(module: Module, fn: ast.AST, qual: str) -> Set[str]:
    """Local names assigned from jnp./jax.-rooted expressions (minus
    jax.device_get, whose result is already host-side)."""
    tainted: Set[str] = set()
    for node in ast.walk(fn):
        if module.qualname_of(node) != qual:
            continue
        if not isinstance(node, ast.Assign):
            continue
        has_device_call = False
        for sub in ast.walk(node.value):
            if isinstance(sub, ast.Call):
                parts = dotted_name(sub.func)
                if parts and parts[0] in _DEVICE_ROOTS \
                        and parts[-1] != "device_get":
                    has_device_call = True
        if not has_device_call:
            continue
        for tgt in node.targets:
            for sub in ast.walk(tgt):
                if isinstance(sub, ast.Name):
                    tainted.add(sub.id)
    return tainted


class HostSyncChecker(Checker):
    name = "host-sync"
    description = ("no device->host sync on the search dispatch path "
                   "outside resolve()")

    def _hot_set(self, repo: Repo) -> Set[str]:
        cg = repo.callgraph()
        roots = [
            q for q, info in cg.funcs.items()
            if (q.rsplit(".", 1)[-1] in _ROOT_BASENAMES
                and info.module.name.startswith(_ROOT_MODULE_PREFIXES))
            or info.module.name.startswith(_ADMISSION_MODULE_PREFIXES)
            or info.module.name.startswith(_BUILD_MODULE_PREFIXES)
        ]

        def skip(qual: str) -> bool:
            base = qual.rsplit(".", 1)[-1]
            if base in _SKIP_BASENAMES:
                return True
            return qual.startswith(_SKIP_MODULE_PREFIXES)

        return cg.reachable(roots, fuzzy=True, skip=skip)

    def check_repo(self, repo: Repo) -> List[Finding]:
        hot = self._hot_set(repo)
        cg = repo.callgraph()
        out: List[Finding] = []
        for gqual in sorted(hot):
            info = cg.funcs[gqual]
            module = info.module
            local = gqual[len(module.name) + 1:]
            fn = info.node
            tainted = _tainted_names(module, fn, local)
            for node in ast.walk(fn):
                if module.qualname_of(node) != local:
                    continue
                msg = self._sync_kind(node, tainted)
                if msg is None:
                    continue
                f = module.finding(self.name, node, msg)
                if f:
                    out.append(f)
        return out

    @staticmethod
    def _sync_kind(node: ast.AST, tainted: Set[str]) -> Optional[str]:
        if not isinstance(node, ast.Call):
            return None
        parts = dotted_name(node.func)
        if parts:
            tail = parts[-1]
            if tail == "device_get" and parts[0] == "jax":
                return ("jax.device_get on the search dispatch path — "
                        "the hot path must stay async; sync only inside "
                        "resolve() (one device_get per reply) or behind "
                        "a sampled-trace guard")
            if tail == "block_until_ready":
                return ("block_until_ready on the search dispatch path — "
                        "time the wait with device_wait_begin (its span "
                        "ends at resolve()'s fetch) and never block here")
            if tail == "asarray" and parts[0] in ("np", "numpy") \
                    and node.args:
                arg = node.args[0]
                if HostSyncChecker._arg_is_device(arg, tainted):
                    return ("np.asarray of a device value on the search "
                            "dispatch path — this is a hidden "
                            "device_get; keep the value on device or "
                            "sync inside resolve()")
        elif isinstance(node.func, ast.Name) and node.func.id == "float" \
                and node.args:
            if HostSyncChecker._arg_is_device(node.args[0], tainted):
                return ("float() of a device value on the search "
                        "dispatch path — this blocks on the kernel; "
                        "keep the scalar on device or sync inside "
                        "resolve()")
        return None

    @staticmethod
    def _arg_is_device(arg: ast.AST, tainted: Set[str]) -> bool:
        for sub in ast.walk(arg):
            if isinstance(sub, ast.Name) and sub.id in tainted:
                return True
            if isinstance(sub, ast.Call):
                parts = dotted_name(sub.func)
                if parts and parts[0] in _DEVICE_ROOTS \
                        and parts[-1] != "device_get":
                    return True
        return False
