"""Search request coalescing: merge concurrent same-shaped searches into
one device batch — grown into the QoS admission/batch-forming layer.

The reference absorbs request-level parallelism with bthread worker sets
(runnable.h:138-291, index_service.cc:362-365) — more threads, same
per-request kernel. On a TPU the economics invert: one [64, d] matmul
costs barely more than one [1, d], so the win is filling the batch
dimension. A coalescer queues requests for the same (region, topk, search
params) key inside a small time window and launches ONE kernel; each
caller gets its slice back.

Latency math: the window pays where the device->host hop dwarfs a ~2 ms
collection window — the hop's cost is not measured on the current machine
(ROADMAP C9), so neither is the window's worth.

QoS (``qos.enabled``, obs/pressure.py is the sensor/policy home): the
queue in front of the kernel is the ONLY place admission can act, so the
coalescer is where the loop closes:

- **admission** — a request whose budget is already spent is rejected
  before it queues (its future carries ``DeadlineExceeded``; no kernel is
  ever dispatched for it). Under pressure (estimated wait beyond
  ``qos.max_queue_ms``) low-priority work is shed at admission, and any
  request that could not finish inside its own remaining budget anyway
  is shed as hopeless — serving it late would burn capacity that an
  in-deadline request needs. A per-tenant queued-row cap
  (``qos.tenant_queue_rows``) bounds any one tenant's share of the queue.
- **priority batch forming** — entries dispatch highest-priority-first
  inside a batch, and the full-batch flush threshold sits ON the pow2
  pad ladder (index/flat._pad_batch), so a full batch is exactly a warm
  program shape: batch forming never mints a compile (the PR 5 sentinel
  makes this a tested invariant).
- **expiry before dispatch** — entries whose deadline passed while
  queued (or whose remaining budget cannot cover the estimated run) are
  failed at flush time and their queries EXCLUDED from the stacked
  batch; a batch of only dead entries skips the kernel entirely.
- **accounting** — queue-wait, per-stage budget fractions, demand,
  shed/expired counters all land in the ``qos.*`` family via PRESSURE.

Every QoS decision is budget-driven; with ``qos.enabled = false`` submit
takes the exact pre-QoS path (one flag read).

Tracing: each submit opens a ``coalesce.wait`` span (queue time) as a
child of the caller's current span; the batch run opens ``coalesce.run``
parented to the FIRST sampled waiter and attaches it on the flush thread,
so device-side spans nest into that caller's trace across the handoff.
The batch size and co-batched trace ids ride as span attributes. The
request BUDGET makes the same handoff: captured from the contextvar at
submit, carried on the entry, consulted on the flush thread.

Shutdown contract: ``submit()`` never raises and never hangs — it always
returns a Future, and every returned Future resolves deterministically.
A submit racing ``stop(drain=False)`` gets a ``CoalescerStopped`` future:
the admitted-vs-stopped decision happens atomically under the queue lock
(the pre-QoS code checked the stop flag and appended in one critical
section too, but ANY admission work between the check and the append —
exactly what QoS adds — would have opened a window where a request could
slip into a queue nobody will ever flush; the decision is now made at
append time, where it cannot be stale).

Stall-free pipeline (``pipeline.enabled``, common/pipeline.py): when a
``dispatch_fn`` is provided and the tri-state flag resolves on, the
flush loop splits dispatch from resolve. Each due batch's kernels are
dispatched (priority order, same device_lock discipline — dispatch_fn
returns a resolve thunk without syncing), so region B's kernel overlaps
region A's D2H fetch; the thunks then drain FIFO on a CompletionLane
thread, the only place the pipelined path calls ``jax.device_get``.
Query staging (pad + H2D upload) moves into a per-key StagingRing of
``pipeline.depth`` pow2-ladder host buffers so batch N+1's upload
overlaps batch N's compute. Expiry-before-dispatch runs inside
``_dispatch`` — i.e. at REAL dispatch time even for cap-displaced
batches — and per-stage accounting books the enqueue cost under a
``dispatch`` stage instead of inflating kernel time. The shutdown
contract extends to the lane: stop(drain=True) resolves queued
handoffs, stop(drain=False) abandons them (futures fail fast, but the
fetch still runs so device-side SearchLeases are released).

In-flight dedupe (``cache.enabled``, dingo_tpu/cache/): identical query
rows inside one flush collapse to a single kernel row fanned out to
every waiter's future — entries in a batch already share the (region,
topn, params) key, so row identity is the query bytes (PR 11 row
fingerprints). The plan is built from the POST-expiry, priority-sorted
survivors: an expired member has already failed its own future and
cannot drag duplicate siblings down, first occurrence wins the kernel
slot (the collapsed row dispatches at its highest-priority member's
position), and the hopeless-shed estimate in ``_expire_dead`` prices
the batch at its DEDUPED row count — the kernel cost actually being
bought — so a duplicate-heavy flush is never shed on a phantom row
count (each member's own deadline is still checked individually). The
batch shrinks BEFORE padding/staging, so the pow2 ladder, staging rings
and the one-sync-per-reply contract are untouched.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from dingo_tpu.trace import NOOP_SPAN, TRACER


class CoalescerStopped(RuntimeError):
    """Set on futures whose batch was discarded by stop(drain=False) or
    that arrived after (or concurrently with) stop()."""


#: dispatch-time safety factor on the estimated batch run: an entry whose
#: remaining budget cannot cover ~2x the estimated run would expire
#: mid-flight more often than not — serving it is wasted capacity AND a
#: late reply, the worst of both (2x covers run-time variance on a
#: contended host; the EWMA itself tracks the mean, and under overload
#: shedding a marginal request is strictly cheaper than serving it late)
_EXPIRY_RUN_MARGIN = 2.0


def _prev_pow2(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


class _Entry:
    """One submit: its queries plus everything the flush thread needs."""

    __slots__ = ("queries", "future", "wait_span", "budget", "priority",
                 "tenant", "region_id", "t0", "qos")

    def __init__(self, queries, future, wait_span, budget, region_id,
                 qos=False):
        self.queries = queries
        self.future = future
        self.wait_span = wait_span
        self.budget = budget
        self.priority = budget.priority if budget is not None else 1
        self.tenant = budget.tenant if budget is not None else "default"
        self.region_id = region_id
        self.t0 = time.monotonic()
        #: admitted under QoS accounting: dequeue/row-release must mirror
        #: the admit-side bookkeeping even if the flag flips mid-flight
        self.qos = qos


class _PendingBatch:
    __slots__ = ("entries", "created")

    def __init__(self):
        self.entries: List[_Entry] = []
        self.created = time.monotonic()

    def rows(self) -> int:
        return sum(len(e.queries) for e in self.entries)


class SearchCoalescer:
    """Batches `search(queries) -> per-query results` calls per key.

    run_fn(key, queries[batch, d]) must return a list of per-query result
    rows; callers receive exactly their rows. run_fn may optionally accept
    a ``stage_us`` dict kwarg (the VectorReader stage-timing contract) —
    when it does, the coalescer reads kernel/rerank stage splits out of it
    for the per-stage budget accounting. Flush happens when the window
    expires or the batch hits max_batch. One daemon timer thread serves all
    keys, sleeping until the earliest pending deadline; a caller whose own
    submission fills a batch runs that batch inline (its results are in
    it), while a cap-displaced previous batch is flushed on its own thread
    so the new caller never pays for a search it is not part of and the
    timer thread stays free for other keys' expiries.
    """

    def __init__(self, run_fn: Callable[[Any, np.ndarray], Sequence],
                 window_ms: float = 2.0, max_batch: int = 256,
                 dispatch_fn: Optional[Callable] = None):
        self.run_fn = run_fn
        self.dispatch_fn = dispatch_fn
        self.window_s = window_ms / 1000.0
        self.max_batch = max_batch
        import inspect

        try:
            self._run_takes_stages = "stage_us" in inspect.signature(
                run_fn).parameters
        except (TypeError, ValueError):
            self._run_takes_stages = False
        self._dispatch_params = frozenset()
        if dispatch_fn is not None:
            try:
                self._dispatch_params = frozenset(
                    inspect.signature(dispatch_fn).parameters)
            except (TypeError, ValueError):
                pass
        # pipelined-path state: the lane thread starts lazily on the
        # first handoff; staging rings materialize per key on first use
        from dingo_tpu.common.pipeline import CompletionLane

        self._lane = CompletionLane()
        self._staging = None
        #: cumulative per-stage wall time (ms) across all pipelined
        #: flushes — bench reads dispatch_overhead_fraction from here
        #: without needing QoS budget plumbing
        self.stage_totals_ms: Dict[str, float] = {}
        self._lock = threading.Lock()
        self._pending: Dict[Any, _PendingBatch] = {}
        #: cap-displaced batches awaiting the timer thread (QoS mode):
        #: serialized dispatch keeps the service-rate model honest —
        #: ad-hoc flush threads racing each other would make every run
        #: slower than the EWMA the admission estimates are built on
        self._ready: List = []
        #: queued query rows per tenant (admission cap bookkeeping)
        self._tenant_rows: Dict[str, int] = {}
        #: EWMA of per-row service time / per-batch run time. Zero until
        #: the first measurement — but the admission estimate no longer
        #: reads that zero as "service is free": estimated_wait_ms
        #: prices unmeasured queues at the cost model's conservative
        #: ``cost.prior_row_ms`` prior, so even the FIRST overload burst
        #: sheds. The per-(kernel, shape) surface in obs/cost.py refines
        #: these scalars as real timings land.
        self._ewma_row_ms = 0.0
        self._ewma_run_ms = 0.0
        self._wake = threading.Event()
        self._stop = False
        self._thread = threading.Thread(
            target=self._flush_loop, name="search-coalescer", daemon=True
        )
        self._thread.start()

    # -- QoS helpers ---------------------------------------------------------
    def _queued_rows(self) -> int:
        # the backlog is BOTH queues: window-pending batches AND cap-
        # displaced batches awaiting the timer thread — under overload
        # most of the real wait sits in _ready, and an estimate that
        # ignored it would under-shed exactly when shedding matters
        return (sum(b.rows() for b in self._pending.values())
                + sum(b.rows() for _, b in self._ready))

    def estimated_wait_ms(self, extra_rows: int = 0,
                          key: Any = None) -> float:
        """Admission estimate: rows ahead priced by the per-shape cost
        model (obs/cost.py) when this key's kernel has been measured,
        the scalar per-row EWMA otherwise, plus one batch run (the one
        possibly in flight). Before ANY sample has landed the estimate
        is the conservative ``cost.prior_row_ms`` prior — never 0 — so
        the first overload burst sheds instead of riding in on a figure
        nobody measured (the old cold-start hole). Cost model off +
        nothing measured keeps the legacy 0.0 answer."""
        with self._lock:
            rows = self._queued_rows()
        total = rows + extra_rows
        try:
            from dingo_tpu.obs import cost as _cost
        except ImportError:  # pragma: no cover — obs always present
            _cost = None
        if _cost is not None and _cost.cost_enabled():
            kid = _cost.kernel_id(key) if key is not None else None
            if _cost.COST.has_model(kid):
                return (_cost.COST.estimate_run_ms(kid, total)
                        + self._ewma_run_ms)
            if self._ewma_row_ms <= 0:
                return total * _cost.prior_row_ms()
        if self._ewma_row_ms <= 0:
            return 0.0
        return total * self._ewma_row_ms + self._ewma_run_ms

    def _est_run_ms(self, rows: int, key: Any = None) -> float:
        """Expected run time for a batch of `rows`: the key's measured
        per-shape surface when the cost model has one; otherwise the
        per-batch EWMA floor (fixed dispatch overhead) scaled up by the
        per-row cost for batches larger than recent history — a 256-row
        batch must not be judged by the run time of the 8-row batches
        that preceded it."""
        if key is not None:
            try:
                from dingo_tpu.obs import cost as _cost

                if _cost.cost_enabled():
                    kid = _cost.kernel_id(key)
                    if _cost.COST.has_model(kid):
                        return _cost.COST.estimate_run_ms(kid, rows)
            except ImportError:  # pragma: no cover
                pass
        if self._ewma_row_ms <= 0:
            return self._ewma_run_ms
        return max(self._ewma_run_ms, rows * self._ewma_row_ms)

    def _note_run(self, rows: int, run_ms: float,
                  key: Any = None) -> None:
        if rows <= 0 or run_ms <= 0:
            return
        row_ms = run_ms / rows
        a = 0.3
        self._ewma_row_ms = (row_ms if self._ewma_row_ms == 0
                             else a * row_ms + (1 - a) * self._ewma_row_ms)
        self._ewma_run_ms = (run_ms if self._ewma_run_ms == 0
                             else a * run_ms + (1 - a) * self._ewma_run_ms)
        if key is not None:
            try:
                from dingo_tpu.obs import cost as _cost

                _cost.COST.note(_cost.kernel_id(key), rows, run_ms,
                                region_id=_cost.kernel_region(key))
            except ImportError:  # pragma: no cover
                pass

    def _admission_reject(self, budget, n_rows: int, region_id: int,
                          key: Any = None):
        """QoS admission decision for one submit. Returns an exception to
        set on the future (after counting it), or None = admit. Called
        OUTSIDE the queue lock — only estimates are read here."""
        from dingo_tpu.obs import pressure as qp

        if budget is not None and budget.expired():
            qp.PRESSURE.on_expired("admission", region_id, budget)
            return qp.DeadlineExceeded(
                f"deadline exceeded at admission "
                f"({-budget.remaining_ms():.1f}ms past)"
            )
        policy_drops = qp.shed_policy() in ("drop", "degrade_drop")
        if not policy_drops:
            return None
        from dingo_tpu.common.config import FLAGS

        tenant_cap = int(FLAGS.get("qos_tenant_queue_rows"))
        if tenant_cap > 0 and budget is not None:
            with self._lock:
                queued = self._tenant_rows.get(budget.tenant, 0)
            if queued + n_rows > tenant_cap:
                qp.PRESSURE.on_shed("tenant_limit", region_id, budget)
                return qp.RequestShed(
                    f"tenant {budget.tenant} over queue cap "
                    f"({queued}+{n_rows} > {tenant_cap} rows)"
                )
        est_ms = self.estimated_wait_ms(extra_rows=n_rows, key=key)
        if budget is not None and budget.deadline_ms > 0 \
                and est_ms > budget.remaining_ms():
            # hopeless: it would expire in queue — serving it late only
            # burns capacity an in-deadline request needs
            qp.PRESSURE.on_shed("hopeless", region_id, budget)
            return qp.RequestShed(
                f"estimated wait {est_ms:.0f}ms exceeds remaining "
                f"budget {budget.remaining_ms():.0f}ms"
            )
        max_queue_ms = float(FLAGS.get("qos_max_queue_ms"))
        if max_queue_ms > 0:
            # pressure shed by priority: batch/background (0) sheds at
            # half the bound, default (1) at the bound, interactive
            # (>= 2) never pressure-sheds (hopeless-shed still applies)
            prio = budget.priority if budget is not None else 1
            allowed = (0.5 * max_queue_ms if prio <= 0
                       else max_queue_ms if prio == 1
                       else float("inf"))
            if est_ms > allowed:
                qp.PRESSURE.on_shed("pressure", region_id, budget)
                return qp.RequestShed(
                    f"queue pressure {est_ms:.0f}ms over bound "
                    f"{allowed:.0f}ms (priority {prio})"
                )
        return None

    # -- submission ----------------------------------------------------------
    def submit(self, key: Any, queries: np.ndarray,
               max_batch: int = 0, region_id: int = 0) -> Future:
        """Queue queries [n, d] under key; resolves to n result rows.
        max_batch (0 = the coalescer default) caps the STACKED row count
        for this key — merging must never build a batch that would trip a
        limit each request individually respects.

        Never raises, never hangs: admission rejections
        (DeadlineExceeded/RequestShed), shutdown (CoalescerStopped), and
        run errors all resolve the returned future deterministically."""
        cap = min(self.max_batch, max_batch or self.max_batch)
        fut: Future = Future()
        wait_span = TRACER.start_span("coalesce.wait")
        qos = False
        budget = None
        try:
            from dingo_tpu.obs import pressure as qp

            qos = qp.qos_enabled()
            if qos:
                budget = qp.current_budget()
        except ImportError:  # pragma: no cover — obs package always present
            pass
        if qos:
            rejection = self._admission_reject(budget, len(queries),
                                               region_id, key=key)
            if rejection is not None:
                wait_span.end()
                fut.set_exception(rejection)
                return fut
            # a full-ladder batch pads to itself: flushing AT a pow2 row
            # count hands the kernel an exactly-warm shape
            cap = _prev_pow2(cap)
        entry = _Entry(np.asarray(queries), fut, wait_span, budget,
                       region_id, qos=qos)
        flush_now = None
        flush_first = None
        with self._lock:
            if self._stop:
                # the submit-vs-stop(drain=False) race resolved: the
                # stopped check and the append are ONE atomic decision, so
                # this future fails deterministically instead of entering
                # a queue whose flush thread is already gone
                wait_span.end()
                fut.set_exception(CoalescerStopped("coalescer stopped"))
                return fut
            batch = self._pending.get(key)
            if batch is not None and batch.rows() + len(queries) > cap:
                # adding would exceed the cap: flush the queued batch
                # elsewhere (running it HERE would charge the previous
                # batch's whole search to this caller's latency) and
                # start fresh for this request. QoS mode hands it to the
                # timer thread's ready queue — one dispatcher, honest
                # service-rate accounting, expiry checked at the moment
                # it actually runs; classic mode spawns a thread so the
                # timer stays free for other keys' window expiries
                displaced = self._pending.pop(key)
                if qos:
                    self._ready.append((key, displaced))
                    displaced = None
                flush_first = displaced
                batch = None
            if batch is None:
                batch = self._pending[key] = _PendingBatch()
            batch.entries.append(entry)
            if qos:
                self._tenant_rows[entry.tenant] = (
                    self._tenant_rows.get(entry.tenant, 0) + len(queries)
                )
                # admit accounting INSIDE the queue lock: a flush can
                # only pop this batch under the same lock, so on_dequeue
                # can never be observed before its on_admit (an
                # admit-after-release race left phantom queue depth)
                from dingo_tpu.obs.pressure import PRESSURE

                PRESSURE.on_admit(region_id, len(queries), budget)
            if batch.rows() >= cap:
                flush_now = self._pending.pop(key)
        if flush_first is not None:
            threading.Thread(
                target=self._run, args=(key, flush_first),
                name="coalescer-flush", daemon=True,
            ).start()
        if flush_now is not None:
            # the caller's own batch is full: run it inline (lowest
            # latency for everyone already in it); wake the timer too —
            # a QoS-displaced batch may be sitting in the ready queue
            self._wake.set()
            self._run(key, flush_now)
        else:
            self._wake.set()
        return fut

    # -- flushing ------------------------------------------------------------
    def _release_rows(self, entries: List[_Entry]) -> None:
        with self._lock:
            for e in entries:
                if not e.qos:
                    continue
                left = self._tenant_rows.get(e.tenant, 0) - len(e.queries)
                if left > 0:
                    self._tenant_rows[e.tenant] = left
                else:
                    self._tenant_rows.pop(e.tenant, None)

    def _expire_dead(self, entries: List[_Entry], region_id: int,
                     now: float, key: Any = None) -> List[_Entry]:
        """Expiry before dispatch: fail entries that died in queue (or
        whose remaining budget cannot cover the estimated run — they
        WOULD die mid-flight) and return the survivors."""
        from dingo_tpu.obs import pressure as qp

        # pure expiry (the deadline contract) always applies; the
        # hopeless-shed arm is a DROP and obeys the same policy gate as
        # admission ('off'/'degrade' must never fail a live request)
        drops = qp._policy_drops()
        rows = sum(len(e.queries) for e in entries)
        if drops:
            try:
                from dingo_tpu.cache import policy as cache_policy

                if cache_policy.dedupe_enabled():
                    # price the batch at the row count dedupe will
                    # actually dispatch: a duplicate-heavy flush must
                    # not be hopeless-shed on phantom rows (the count
                    # here may still include rows about to expire —
                    # over-counting only errs conservative)
                    from dingo_tpu.cache.dedupe import deduped_rows

                    rows = deduped_rows(entries)
            except ImportError:  # pragma: no cover
                pass
        est_run = _EXPIRY_RUN_MARGIN * self._est_run_ms(rows, key=key)
        live: List[_Entry] = []
        for e in entries:
            if e.budget is None or e.budget.deadline_ms <= 0:
                live.append(e)
                continue
            remaining = e.budget.remaining_ms(now)
            if remaining <= 0:
                qp.PRESSURE.on_expired("queue", region_id, e.budget)
                e.future.set_exception(qp.DeadlineExceeded(
                    f"expired in queue ({-remaining:.1f}ms past deadline)"
                ))
            elif drops and est_run > 0 and remaining < est_run:
                qp.PRESSURE.on_shed("hopeless", region_id, e.budget)
                e.future.set_exception(qp.RequestShed(
                    f"remaining {remaining:.0f}ms cannot cover the "
                    f"~{est_run:.0f}ms batch run"
                ))
            else:
                live.append(e)
        return live

    def _begin_flush(self, key: Any, batch: _PendingBatch,
                     flush_t0: float):
        """Shared flush prologue for the serial and pipelined arms:
        end queue-wait spans, mirror QoS dequeue accounting, expire dead
        entries (this runs at REAL dispatch time — cap-displaced batches
        included), priority-sort the survivors, and open the run span
        parented to the first sampled waiter. Returns
        (entries, region_id, run_span, waits_ms, qos); an empty entries
        list means everything expired (the span is already closed and no
        kernel must dispatch)."""
        qos = False
        try:
            from dingo_tpu.obs import pressure as qp

            qos = qp.qos_enabled()
        except ImportError:  # pragma: no cover
            pass
        entries = batch.entries
        region_id = entries[0].region_id if entries else 0
        run_span = NOOP_SPAN
        links = []
        waits_ms: Dict[int, float] = {}
        for e in entries:
            e.wait_span.end()
            waits_ms[id(e)] = (flush_t0 - e.t0) * 1000.0
            if e.wait_span.sampled:
                if run_span is NOOP_SPAN:
                    run_span = TRACER.start_span(
                        "coalesce.run", parent=e.wait_span.context
                    )
                else:
                    links.append(f"{e.wait_span.trace_id:016x}")
        if any(e.qos for e in entries):
            from dingo_tpu.obs.pressure import PRESSURE

            self._release_rows(entries)
            for e in entries:
                if not e.qos:
                    continue
                PRESSURE.on_dequeue(e.region_id, len(e.queries), e.budget)
                PRESSURE.observe_wait(e.region_id, waits_ms[id(e)],
                                      e.budget)
        if qos:
            entries = self._expire_dead(entries, region_id, flush_t0,
                                        key=key)
            if not entries:
                # a batch of only dead requests dispatches NO kernel
                if run_span is not NOOP_SPAN:
                    run_span.set_attr("all_expired", True)
                    run_span.end()
                return [], region_id, NOOP_SPAN, waits_ms, qos
            # priority batch forming: highest priority first (stable), so
            # the result slicing below follows the dispatch order
            entries = sorted(entries, key=lambda e: -e.priority)
        if run_span is not NOOP_SPAN:
            run_span.set_attr("batch_size",
                              sum(len(e.queries) for e in entries))
            run_span.set_attr("requests", len(entries))
            run_span.set_attr(
                "queue_wait_us",
                int((flush_t0 - batch.created) * 1e6),
            )
            if links:
                run_span.set_attr("cobatched_traces", links)
        return entries, region_id, run_span, waits_ms, qos

    def _form_batch(self, entries: List[_Entry], region_id: int):
        """Stack the survivors' queries, collapsing in-flight duplicates
        when dedupe is on. Returns (stacked, plan): plan is None on the
        plain path (contiguous offset slicing) and a DedupePlan when
        rows collapsed — result fan-out then goes through
        ``plan.rows_for``. Runs AFTER expiry and the priority sort, so
        an expired member never holds a kernel slot and a shared row
        dispatches at its most urgent member's position."""
        plan = None
        try:
            from dingo_tpu.cache import policy as cache_policy

            if cache_policy.dedupe_enabled():
                from dingo_tpu.cache.dedupe import build_plan

                plan = build_plan(entries)
        except ImportError:  # pragma: no cover
            pass
        if plan is None:
            return (np.concatenate([e.queries for e in entries], axis=0),
                    None)
        try:
            from dingo_tpu.cache.edge import CACHE

            CACHE.on_dedup(region_id, plan.collapsed)
        except ImportError:  # pragma: no cover
            pass
        return plan.stacked, plan

    @staticmethod
    def _fan_out(entries: List[_Entry], results, plan) -> None:
        """Resolve every entry's future from the batch results — plan
        fan-out when rows collapsed, contiguous slices otherwise."""
        if plan is not None:
            for i, e in enumerate(entries):
                e.future.set_result(plan.rows_for(i, results))
            return
        off = 0
        for e in entries:
            n = len(e.queries)
            e.future.set_result(list(results[off:off + n]))
            off += n

    def _note_stage_totals(self, **stages_ms) -> None:
        with self._lock:
            for name, ms in stages_ms.items():
                if ms > 0:
                    self.stage_totals_ms[name] = (
                        self.stage_totals_ms.get(name, 0.0) + ms)

    def stage_totals(self) -> Dict[str, float]:
        with self._lock:
            return dict(self.stage_totals_ms)

    def _pipelined(self) -> bool:
        if self.dispatch_fn is None:
            return False
        from dingo_tpu.common.config import serving_pipeline_enabled

        return serving_pipeline_enabled()

    def _run(self, key: Any, batch: _PendingBatch) -> None:
        # queue-wait ends here; the run span parents to the first sampled
        # waiter so the device work lands in ITS trace, with the rest of
        # the batch recorded as co-batched trace links
        flush_t0 = time.monotonic()
        entries, region_id, run_span, waits_ms, qos = self._begin_flush(
            key, batch, flush_t0)
        if not entries:
            return
        token = run_span.attach()
        stage_us: Optional[Dict[str, int]] = (
            {} if (qos and self._run_takes_stages) else None
        )
        try:
            stacked, plan = self._form_batch(entries, region_id)
            form_ms = (time.monotonic() - flush_t0) * 1000.0
            run_t0 = time.monotonic()
            if stage_us is not None:
                results = self.run_fn(key, stacked, stage_us=stage_us)
            else:
                results = self.run_fn(key, stacked)
            run_ms = (time.monotonic() - run_t0) * 1000.0
            self._note_run(len(stacked), run_ms, key=key)
            self._fan_out(entries, results, plan)
            if qos:
                self._account_stages(entries, waits_ms, form_ms, run_ms,
                                     stage_us)
        except Exception as exc:  # noqa: BLE001
            run_span.set_error(exc)
            for e in entries:
                if not e.future.done():
                    e.future.set_exception(exc)
        finally:
            run_span.detach(token)
            run_span.end()

    @staticmethod
    def _account_stages(entries, waits_ms, form_ms, run_ms, stage_us,
                        dispatch_ms: Optional[float] = None):
        """Per-stage time-budget accounting: queue / batch_form / kernel /
        rerank as fractions of each entry's deadline. The kernel/rerank
        split comes from the reader's stage_us dict when the run callback
        exposes it (search_us = the device scan+topk, postfilter+backfill
        = the rerank/materialize tail); otherwise the whole run counts as
        kernel time. On the pipelined path ``dispatch_ms`` (the kernel
        enqueue + staging cost, during which the flush thread — not the
        device — was the bottleneck) books under its own ``dispatch``
        stage so overlapped-dispatch wait is not misbooked as kernel
        time."""
        from dingo_tpu.obs.pressure import PRESSURE

        kernel_ms, rerank_ms = run_ms, 0.0
        if stage_us:
            k = stage_us.get("search_us", 0) / 1000.0
            r = (stage_us.get("postfilter_us", 0)
                 + stage_us.get("backfill_us", 0)) / 1000.0
            if k > 0:
                kernel_ms, rerank_ms = k, min(r, run_ms - k)
        for e in entries:
            if e.budget is None:
                continue
            stages = {
                "queue": waits_ms.get(id(e), 0.0),
                "batch_form": form_ms,
                "kernel": kernel_ms,
                "rerank": rerank_ms,
            }
            if dispatch_ms is not None:
                stages["dispatch"] = dispatch_ms
            PRESSURE.observe_stages(e.budget, stages)

    # -- pipelined arm -------------------------------------------------------
    def _dispatch(self, key: Any, batch: _PendingBatch):
        """Dispatch one due batch's kernels WITHOUT resolving: stage the
        stacked queries (reusable pinned ring buffer, upload started
        here so the next batch's H2D overlaps this one's compute), call
        dispatch_fn for the resolve thunk, and return a _Handoff for the
        completion lane. Returns None when the batch fully expired or
        dispatch itself failed (futures are resolved either way). Runs
        on the flush thread; MUST NOT block on device results — the one
        sanctioned ``device_get`` of this path lives in
        _Handoff.resolve() on the lane thread (dingolint: resolve-sync
        enforces this split)."""
        flush_t0 = time.monotonic()
        entries, region_id, run_span, waits_ms, qos = self._begin_flush(
            key, batch, flush_t0)
        if not entries:
            return None
        token = run_span.attach()
        staged = None
        stage_us: Optional[Dict[str, int]] = (
            {} if "stage_us" in self._dispatch_params else None
        )
        try:
            stacked, plan = self._form_batch(entries, region_id)
            if "staged" in self._dispatch_params:
                if self._staging is None:
                    from dingo_tpu.common.config import pipeline_depth
                    from dingo_tpu.common.pipeline import KeyedStaging

                    self._staging = KeyedStaging(pipeline_depth())
                staged = self._staging.ring(key).stage(stacked)
            form_ms = (time.monotonic() - flush_t0) * 1000.0
            dispatch_t0 = time.monotonic()
            kw: Dict[str, Any] = {}
            if staged is not None:
                kw["staged"] = staged
            if stage_us is not None:
                kw["stage_us"] = stage_us
            thunk = self.dispatch_fn(key, stacked, **kw)
            dispatch_ms = (time.monotonic() - dispatch_t0) * 1000.0
            self._note_stage_totals(batch_form=form_ms,
                                    dispatch=dispatch_ms)
            run_span.detach(token)
            return _Handoff(self, key, entries, waits_ms, form_ms,
                            dispatch_ms, run_span, staged, thunk,
                            stage_us, qos, plan, len(stacked))
        except Exception as exc:  # noqa: BLE001
            run_span.set_error(exc)
            run_span.detach(token)
            run_span.end()
            if staged is not None:
                staged.release()
            for e in entries:
                if not e.future.done():
                    e.future.set_exception(exc)
            return None

    def _flush_loop(self) -> None:
        timeout = None   # nothing pending: sleep until a submit wakes us
        while True:
            # wait until the EARLIEST pending batch's deadline (not a
            # fixed half-window poll, which stretched worst-case wait to
            # 1.5x the configured window)
            self._wake.wait(timeout=timeout)
            self._wake.clear()
            if self._stop:
                return
            now = time.monotonic()
            timeout = None
            with self._lock:
                # QoS-displaced batches first: they are strictly older
                # than anything still inside its window
                due = self._ready
                self._ready = []
                for key in list(self._pending):
                    age = now - self._pending[key].created
                    if age >= self.window_s:
                        due.append((key, self._pending.pop(key)))
                    else:
                        remain = self.window_s - age
                        timeout = remain if timeout is None else min(
                            timeout, remain)
            # under pressure several keys come due in one sweep: dispatch
            # the most important batch first (its waiters are the ones a
            # deadline will kill first among equals)
            due.sort(key=lambda kb: -max(
                (e.priority for e in kb[1].entries), default=0
            ))
            if self._pipelined():
                # overlapped dispatch: EVERY due batch's kernels enqueue
                # before ANY resolve runs — batch B's kernel overlaps
                # batch A's D2H fetch; the completion lane drains the
                # thunks FIFO so this thread never blocks on device_get
                handoffs = []
                for key, batch in due:
                    h = self._dispatch(key, batch)
                    if h is not None:
                        handoffs.append(h)
                for h in handoffs:
                    if not self._lane.submit(h):
                        # lane already stopped (stop racing a flush):
                        # resolve inline — the futures must still settle
                        h.resolve()
            else:
                for key, batch in due:
                    self._run(key, batch)

    def stop(self, drain: bool = True) -> None:
        """Shut down. drain=True runs pending batches to completion so
        in-flight callers get results; drain=False fails their futures
        with CoalescerStopped. Either way every pending future resolves
        deterministically — nobody is left hung on a dead timer thread."""
        with self._lock:
            self._stop = True
            # ready-queue batches (QoS cap displacement) resolve under the
            # same contract as window-pending ones
            leftovers = self._ready + list(self._pending.items())
            self._ready = []
            self._pending.clear()
            self._tenant_rows.clear()
        self._wake.set()
        for key, batch in leftovers:
            if drain:
                self._run(key, batch)
            else:
                exc = CoalescerStopped("coalescer stopped before flush")
                for e in batch.entries:
                    e.wait_span.end()
                    if e.qos:
                        # mirror _run's dequeue accounting: a discarded
                        # entry must not leave phantom queue depth in the
                        # pressure plane (heartbeats ship region_stats)
                        from dingo_tpu.obs.pressure import PRESSURE

                        PRESSURE.on_dequeue(e.region_id, len(e.queries),
                                            e.budget)
                    if not e.future.done():
                        e.future.set_exception(exc)
        # the completion lane honors the same contract: drain resolves
        # queued handoffs to real results, no-drain abandons them (their
        # futures fail fast but the fetch still runs so device leases
        # release — see _Handoff.abandon)
        self._lane.stop(drain=drain)
        if self._staging is not None:
            self._staging.close()
        self._thread.join(timeout=2)


class _Handoff:
    """One dispatched-but-unresolved batch riding the completion lane.

    ``resolve()`` is the single sanctioned host-sync point of the
    pipelined path: it runs the dispatch_fn's thunk (one ``device_get``
    inside), slices results to the waiters' futures, and closes the
    accounting the dispatch half opened. ``abandon()`` is the
    stop(drain=False) arm: futures fail fast with CoalescerStopped, but
    the thunk still runs — a dropped fetch must not leak the SlotStore
    SearchLeases the dispatch acquired."""

    __slots__ = ("coalescer", "key", "entries", "waits_ms", "form_ms",
                 "dispatch_ms", "run_span", "staged", "thunk", "stage_us",
                 "qos", "plan", "rows")

    def __init__(self, coalescer, key, entries, waits_ms, form_ms,
                 dispatch_ms, run_span, staged, thunk, stage_us, qos,
                 plan=None, rows=0):
        self.coalescer = coalescer
        self.key = key
        self.entries = entries
        self.waits_ms = waits_ms
        self.form_ms = form_ms
        self.dispatch_ms = dispatch_ms
        self.run_span = run_span
        self.staged = staged
        self.thunk = thunk
        self.stage_us = stage_us
        self.qos = qos
        #: dedupe fan-out plan (None = contiguous slices) and the row
        #: count actually dispatched (deduped) — the EWMA must track the
        #: kernel's true service rate, not the pre-collapse demand
        self.plan = plan
        self.rows = rows

    def resolve(self) -> None:
        c = self.coalescer
        token = self.run_span.attach()
        t0 = time.monotonic()
        try:
            results = self.thunk()
            resolve_ms = (time.monotonic() - t0) * 1000.0
            rows = self.rows or sum(len(e.queries) for e in self.entries)
            c._note_run(rows, self.dispatch_ms + resolve_ms,
                        key=self.key)
            kernel_ms, rerank_ms = resolve_ms, 0.0
            if self.stage_us:
                k = self.stage_us.get("search_us", 0) / 1000.0
                r = (self.stage_us.get("postfilter_us", 0)
                     + self.stage_us.get("backfill_us", 0)) / 1000.0
                if k > 0:
                    kernel_ms = k
                    rerank_ms = min(r, max(0.0, resolve_ms - k))
            c._note_stage_totals(kernel=kernel_ms, rerank=rerank_ms,
                                 resolve=resolve_ms)
            c._fan_out(self.entries, results, self.plan)
            if self.qos:
                c._account_stages(self.entries, self.waits_ms,
                                  self.form_ms, resolve_ms, self.stage_us,
                                  dispatch_ms=self.dispatch_ms)
        except Exception as exc:  # noqa: BLE001
            self.run_span.set_error(exc)
            for e in self.entries:
                if not e.future.done():
                    e.future.set_exception(exc)
        finally:
            self.run_span.detach(token)
            self.run_span.end()
            if self.staged is not None:
                self.staged.release()

    def abandon(self) -> None:
        exc = CoalescerStopped("coalescer stopped before resolve")
        for e in self.entries:
            if not e.future.done():
                e.future.set_exception(exc)
        try:
            # run the fetch anyway: the dispatch half acquired device-
            # side leases (SlotStore begin_search) that only the thunk's
            # finally releases — dropping it would strand limbo slots
            self.thunk()
        except Exception:  # noqa: BLE001
            pass
        finally:
            self.run_span.end()
            if self.staged is not None:
                self.staged.release()
