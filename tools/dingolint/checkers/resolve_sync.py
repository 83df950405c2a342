"""resolve-sync: exactly one host sync per reply, on the right thread.

The serving pipeline (stall-free serving PR) sharpened the host-sync
contract: ``search_async`` chains the whole epilogue — rerank, prune
stats, top-k — on device and starts ONE async D2H group
(``ops/topk.begin_host_fetch``); the ``resolve()`` thunk then performs
exactly one ``jax.device_get`` over that group. A second sync inside
resolve re-serializes the reply against the device and silently halves
the overlap the pipeline exists to buy: while resolve waits on the
straggler transfer, the completion lane can't drain and the next
batch's staging slot stays leased.

Two rules:

1. **resolve() thunks** (any def named ``resolve`` in the index /
   parallel tiers, plus helpers only they reach):

   - ``block_until_ready`` is always flagged — resolve should *fetch*,
     not barrier; the fetch itself is the wait.
   - the FIRST ``jax.device_get`` is the sanctioned sync; any second
     one on the same execution path is flagged. Two ``device_get``
     calls that diverge at the same ``if`` into different arms are
     branch-exclusive — only one runs per reply — and stay clean
     (the quantized families' rerank/no-rerank arms).
   - reachable helpers (minus the obs/trace/metrics planes) are
     flagged on ANY explicit sync: resolve
     already fetched, so a helper sync is by construction a second one.

2. **the coalescer flush thread**: methods of ``SearchCoalescer``
   (which run on the flush thread or a caller thread) must never sync
   — they dispatch and hand off. Syncs belong to the completion lane
   (``_Handoff.resolve``, a different class, exempt by scoping) where
   a wait only delays *that* reply, never the next dispatch.

Deliberate exceptions (e.g. a host-side exact rerank whose gather
cannot chain on device) go in the baseline with a rationale, not
inline suppressions — the two-sync shape is an economics judgment, and
the baseline is where judgments are recorded.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from tools.dingolint.callgraph import dotted_name
from tools.dingolint.core import Checker, Finding, Module, Repo

#: where resolve() thunks live (same tiers host-sync roots at)
_ROOT_MODULE_PREFIXES = ("dingo_tpu.index.", "dingo_tpu.parallel.",
                         "dingo_tpu.cache.")

#: admission-path modules: every def runs on a caller or flush thread
#: (cache lookup precedes QoS queuing; the dedupe plan forms batches),
#: so ANY device sync is flagged — there is no sanctioned first fetch
_ADMISSION_MODULE_PREFIXES = ("dingo_tpu.cache.",)

#: traversal never descends into these (their own discipline applies)
_SKIP_MODULE_PREFIXES = ("dingo_tpu.obs.", "dingo_tpu.trace.",
                         "dingo_tpu.metrics.")

#: the flush-thread class; the completion lane's handoff class is
#: intentionally NOT here — its resolve() runs on the lane thread
_FLUSH_CLASSES = {"SearchCoalescer"}


def _is_device_get(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    parts = dotted_name(node.func)
    return bool(parts) and parts[-1] == "device_get" \
        and parts[0] == "jax"


def _is_block_until_ready(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    parts = dotted_name(node.func)
    return bool(parts) and parts[-1] == "block_until_ready"


def _branch_arms(module: Module, node: ast.AST) -> Dict[int, str]:
    """id(If ancestor) -> which arm this node sits in."""
    arms: Dict[int, str] = {}
    child = node
    cur = module.parent(node)
    while cur is not None:
        if isinstance(cur, ast.If):
            if any(child is c for c in cur.body):
                arms[id(cur)] = "body"
            elif any(child is c for c in cur.orelse):
                arms[id(cur)] = "orelse"
            else:
                arms[id(cur)] = "test"
        child = cur
        cur = module.parent(cur)
    return arms


def _branch_exclusive(module: Module, a: ast.AST, b: ast.AST) -> bool:
    """True when a and b diverge at some shared ``if`` into different
    arms — at most one of them runs per call."""
    arms_a = _branch_arms(module, a)
    arms_b = _branch_arms(module, b)
    for if_id, arm in arms_a.items():
        other = arms_b.get(if_id)
        if other is not None and other != arm \
                and {arm, other} == {"body", "orelse"}:
            return True
    return False


class ResolveSyncChecker(Checker):
    name = "resolve-sync"
    description = ("one device_get per resolve(); no syncs on the "
                   "coalescer flush thread")

    def check_repo(self, repo: Repo) -> List[Finding]:
        out: List[Finding] = []
        out.extend(self._check_resolve_thunks(repo))
        out.extend(self._check_flush_thread(repo))
        return out

    # -- rule 1: resolve() thunks -----------------------------------------

    def _check_resolve_thunks(self, repo: Repo) -> List[Finding]:
        cg = repo.callgraph()
        roots = [
            q for q, info in cg.funcs.items()
            if q.rsplit(".", 1)[-1] == "resolve"
            and info.module.name.startswith(_ROOT_MODULE_PREFIXES)
        ]

        def skip(qual: str) -> bool:
            return qual.startswith(_SKIP_MODULE_PREFIXES)

        hot = cg.reachable(roots, fuzzy=True, skip=skip)
        out: List[Finding] = []
        for gqual in sorted(hot):
            info = cg.funcs[gqual]
            module = info.module
            local = gqual[len(module.name) + 1:]
            if local.rsplit(".", 1)[-1] == "resolve":
                out.extend(self._check_one_resolve(module, info.node,
                                                   local))
            else:
                out.extend(self._check_helper(module, info.node, local))
        return out

    def _check_one_resolve(self, module: Module, fn: ast.AST,
                           local: str) -> List[Finding]:
        out: List[Finding] = []
        gets: List[ast.Call] = []
        for node in ast.walk(fn):
            if module.qualname_of(node) != local:
                continue
            if _is_block_until_ready(node):
                f = module.finding(
                    self.name, node,
                    "block_until_ready inside resolve() — resolve "
                    "performs ONE jax.device_get over the "
                    "begin_host_fetch group; the fetch is the wait",
                )
                if f:
                    out.append(f)
            elif _is_device_get(node):
                gets.append(node)
        gets.sort(key=lambda n: (n.lineno, n.col_offset))
        for i, g in enumerate(gets):
            if any(not _branch_exclusive(module, g, earlier)
                   for earlier in gets[:i]):
                f = module.finding(
                    self.name, g,
                    "second jax.device_get inside resolve() after the "
                    "first fetch — chain the epilogue on device and "
                    "join the reply's single begin_host_fetch group "
                    "(one device_get per reply), or baseline with a "
                    "rationale if the host round-trip is inherent",
                )
                if f:
                    out.append(f)
        return out

    def _check_helper(self, module: Module, fn: ast.AST,
                      local: str) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(fn):
            if module.qualname_of(node) != local:
                continue
            msg: Optional[str] = None
            if _is_device_get(node):
                msg = ("jax.device_get in a helper reachable from "
                       "resolve() — resolve already performed the "
                       "reply's one fetch; return device values and "
                       "let resolve's begin_host_fetch group carry "
                       "them, or baseline with a rationale")
            elif _is_block_until_ready(node):
                msg = ("block_until_ready in a helper reachable from "
                       "resolve() — a barrier under the reply's sync "
                       "point stalls the completion lane; drop it or "
                       "baseline with a rationale")
            if msg is None:
                continue
            f = module.finding(self.name, node, msg)
            if f:
                out.append(f)
        return out

    # -- rule 2: the coalescer flush thread --------------------------------

    def _check_flush_thread(self, repo: Repo) -> List[Finding]:
        out: List[Finding] = []
        for module in repo.modules:
            admission = module.name.startswith(_ADMISSION_MODULE_PREFIXES)
            for local, fn in sorted(module.funcs.items()):
                if admission:
                    # cache/ admission path: no sanctioned sync anywhere
                    for node in ast.walk(fn):
                        if module.qualname_of(node) != local:
                            continue
                        if _is_device_get(node) \
                                or _is_block_until_ready(node):
                            f = module.finding(
                                self.name, node,
                                "device sync in the serving-edge cache — "
                                "the admission-path lookup/fill runs on "
                                "the caller thread before QoS queuing and "
                                "the dedupe plan on the flush thread; "
                                "cache code must stay host-only (keys, "
                                "dicts, numpy over host arrays)",
                            )
                            if f:
                                out.append(f)
                    continue
                cnode = module.enclosing_class(fn)
                if cnode is None or cnode.name not in _FLUSH_CLASSES:
                    continue
                for node in ast.walk(fn):
                    if module.qualname_of(node) != local:
                        continue
                    if _is_device_get(node) \
                            or _is_block_until_ready(node):
                        f = module.finding(
                            self.name, node,
                            "device sync in a SearchCoalescer method — "
                            "the flush thread dispatches and hands off; "
                            "syncs belong on the completion lane "
                            "(_Handoff.resolve), where a wait delays "
                            "one reply instead of every queued batch",
                        )
                        if f:
                            out.append(f)
        return out
