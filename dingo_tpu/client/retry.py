"""RetryPolicy: the one client-side resilience policy.

Every gRPC client call site — the SDK's leader routing (client/client.py),
the coordinator group channel (common/coord_channel.py) and, through it,
the store's remote heartbeat — routes its attempts through this policy
instead of a bespoke loop (the thundering-herd fix: before this, every
client retried immediately with no jitter, so a coordinator failover got
hit by the whole fleet at once).

The policy is:

- **error-class-aware** — a request the server never served (grpc
  UNAVAILABLE / CANCELLED, connection refused) is always safe to re-send;
  DEADLINE_EXCEEDED is ambiguous (the first attempt may have committed)
  and re-sends only for idempotent calls; in-band application verdicts
  (NotLeader and friends) are the caller's to classify via `classify`.
- **backoff with equal jitter** — sleep ~ d/2 + U(0, d/2), d = min(cap, base·2^round)
  between full rotation rounds, so a fleet retrying the same dead
  endpoint decorrelates instead of herding.
- **per-target circuit breaker** — consecutive connection-level failures
  open the breaker; while open the target is skipped (other targets
  absorb the traffic); after a cooldown one half-open probe decides.
  In-band responses (even NotLeader) count as SUCCESS — the endpoint is
  alive, it just isn't the leader.
- **strictly budget-aware** — retries and hedges spend the request's
  deadline budget (obs/pressure.py, PR 10) and never outlive it: each
  attempt checks ``current_budget()``, and backoff sleeps are clamped to
  the remaining budget. Exhaustion raises the caller's error class and
  bumps ``fault.budget_exhausted``.
- **hedged reads** — ``call_hedged`` fires a second attempt at the next
  target after a p99-derived delay (tracked per target); first success
  wins. Hedges are for idempotent reads ONLY and are budget-gated (no
  hedge when the remaining budget can't fit one). Every attempt is
  stamped with ``x-dingo-attempt`` metadata so servers can identify and
  dedupe hedged duplicates.
"""

from __future__ import annotations

import queue
import random
import threading
import time
from typing import Callable, Optional, Sequence, Tuple

import grpc

from dingo_tpu.common.log import get_logger
from dingo_tpu.common.metrics import METRICS

_log = get_logger("retry")

#: metadata key carrying the 0-based attempt number (0 = primary,
#: >= 1 = retry or hedge) — servers log/dedupe on it
ATTEMPT_METADATA_KEY = "x-dingo-attempt"

#: grpc codes that mean "never served here" — always safe to re-send
NEVER_SERVED_CODES = (
    grpc.StatusCode.UNAVAILABLE,
    grpc.StatusCode.CANCELLED,
)

#: classify() verdicts
OK = "ok"
ROTATE = "rotate"
FATAL = "fatal"


def attempt_metadata(attempt: int, metadata=None):
    """Stamp (or pass through) call metadata with the attempt number."""
    if attempt <= 0:
        return metadata
    return [*(metadata or ()), (ATTEMPT_METADATA_KEY, str(attempt))]


class _TargetState:
    __slots__ = ("failures", "state", "opened_at", "lat_ms", "lock")

    CLOSED, OPEN, HALF_OPEN = 0, 2, 1

    def __init__(self):
        self.failures = 0
        self.state = self.CLOSED
        self.opened_at = 0.0
        self.lat_ms: list = []        # recent latency samples (ring)
        self.lock = threading.Lock()


class CircuitBreaker:
    """Per-target consecutive-failure breaker with one half-open probe."""

    def __init__(self, threshold: int = 5, cooldown_s: float = 5.0,
                 registry=METRICS):
        self.threshold = threshold
        self.cooldown_s = cooldown_s
        self._targets: dict = {}
        self._lock = threading.Lock()
        self._reg = registry

    def _state(self, target: str) -> _TargetState:
        with self._lock:
            st = self._targets.get(target)
            if st is None:
                st = self._targets[target] = _TargetState()
            return st

    def allow(self, target: str) -> bool:
        st = self._state(target)
        with st.lock:
            if st.state == st.CLOSED:
                return True
            if st.state == st.OPEN:
                if time.monotonic() - st.opened_at >= self.cooldown_s:
                    st.state = st.HALF_OPEN   # admit ONE probe
                    return True
                return False
            return False   # half-open probe already in flight

    def on_success(self, target: str) -> None:
        st = self._state(target)
        with st.lock:
            st.failures = 0
            st.state = st.CLOSED

    def on_failure(self, target: str) -> None:
        st = self._state(target)
        with st.lock:
            st.failures += 1
            was_open = st.state != st.CLOSED
            if st.failures >= self.threshold or st.state == st.HALF_OPEN:
                st.state = st.OPEN
                st.opened_at = time.monotonic()
                if not was_open:
                    self._reg.counter(
                        "fault.breaker_opens", labels={"target": target}
                    ).add(1)

    def state_of(self, target: str) -> int:
        return self._state(target).state


class RetryPolicy:
    def __init__(self, *, rounds: int = 3, base_backoff_ms: float = 25.0,
                 max_backoff_ms: float = 1000.0,
                 breaker_threshold: int = 5,
                 breaker_cooldown_s: float = 5.0,
                 hedge_min_delay_ms: float = 5.0,
                 seed: Optional[int] = None,
                 registry=METRICS):
        self.rounds = rounds
        self.base_backoff_ms = base_backoff_ms
        self.max_backoff_ms = max_backoff_ms
        self.hedge_min_delay_ms = hedge_min_delay_ms
        self.breaker = CircuitBreaker(breaker_threshold, breaker_cooldown_s,
                                      registry)
        self._rng = random.Random(seed)
        self._reg = registry

    @classmethod
    def from_flags(cls, **overrides) -> "RetryPolicy":
        """Policy tuned by the retry.* conf keys (common/config.py)."""
        from dingo_tpu.common.config import FLAGS

        kw = dict(
            rounds=int(FLAGS.get("retry_rounds")),
            base_backoff_ms=float(FLAGS.get("retry_base_backoff_ms")),
            max_backoff_ms=float(FLAGS.get("retry_max_backoff_ms")),
            breaker_threshold=int(FLAGS.get("retry_breaker_threshold")),
            breaker_cooldown_s=float(FLAGS.get("retry_breaker_cooldown_s")),
            hedge_min_delay_ms=float(FLAGS.get("retry_hedge_min_delay_ms")),
        )
        kw.update(overrides)
        return cls(**kw)

    # -- budget ------------------------------------------------------------
    @staticmethod
    def _budget():
        from dingo_tpu.obs.pressure import current_budget

        return current_budget()

    def _check_budget(self, op: str, error_cls, attempt: int) -> None:
        b = self._budget()
        if b is not None and b.expired():
            self._reg.counter("fault.budget_exhausted").add(1)
            raise error_cls(
                f"{op}: deadline budget exhausted after {attempt} attempt(s)"
            )

    def backoff(self, round_i: int, op: str, error_cls, attempt: int,
                 base_ms: Optional[float] = None) -> None:
        """Equal-jitter sleep between rotation rounds — d/2 + U(0, d/2):
        the deterministic half guarantees the wait a rotation exists to
        buy (a raft election is O(100ms); a pure full-jitter roll can
        come back near zero and burn every round before the cluster can
        possibly have changed state), the random half spreads the herd.
        Clamped to (and never outliving) the remaining deadline budget."""
        cap = min(self.max_backoff_ms,
                  (base_ms if base_ms is not None else self.base_backoff_ms)
                  * (2.0 ** round_i))
        sleep_ms = cap / 2.0 + self._rng.uniform(0.0, cap / 2.0)
        b = self._budget()
        if b is not None:
            remaining = b.remaining_ms()
            if remaining <= 1.0:
                self._reg.counter("fault.budget_exhausted").add(1)
                raise error_cls(
                    f"{op}: deadline budget exhausted after "
                    f"{attempt} attempt(s)"
                )
            sleep_ms = min(sleep_ms, remaining * 0.5)
        if sleep_ms > 0:
            time.sleep(sleep_ms / 1000.0)

    # -- latency tracking (hedging sensor) ---------------------------------
    def note_latency(self, target: str, ms: float) -> None:
        st = self.breaker._state(str(target))
        with st.lock:
            st.lat_ms.append(ms)
            if len(st.lat_ms) > 128:
                del st.lat_ms[:64]

    def p99_ms(self, target: str) -> Optional[float]:
        st = self.breaker._state(str(target))
        with st.lock:
            if len(st.lat_ms) < 8:
                return None
            samples = sorted(st.lat_ms)
        return samples[min(len(samples) - 1, int(len(samples) * 0.99))]

    def hedge_delay_ms(self, target: str) -> float:
        """p99 of the primary target's recent latency; the floor covers
        the cold start before enough samples exist."""
        p99 = self.p99_ms(target)
        return max(self.hedge_min_delay_ms, p99 if p99 is not None else 0.0)

    # -- exception classification ------------------------------------------
    @staticmethod
    def classify_exception(exc: BaseException, idempotent: bool) -> str:
        """ROTATE when the request was provably never served (or the call
        is idempotent and the failure is ambiguous), FATAL otherwise."""
        if isinstance(exc, grpc.RpcError):
            code = exc.code() if hasattr(exc, "code") else None
            if code in NEVER_SERVED_CODES:
                return ROTATE
            if code is grpc.StatusCode.DEADLINE_EXCEEDED and idempotent:
                # ambiguous: may have been served. A mutation must NOT be
                # blindly re-sent (at-least-once); a read may.
                return ROTATE
        return FATAL

    # -- the retry loop ----------------------------------------------------
    def call(self, targets: Sequence, fn: Callable,
             *, classify: Optional[Callable] = None, op: str = "",
             error_cls=RuntimeError, idempotent: bool = True,
             rounds: Optional[int] = None,
             base_backoff_ms: Optional[float] = None):
        """Run ``fn(target, attempt)`` over `targets` with rotation,
        backoff, breaker, and budget discipline.

        `base_backoff_ms` overrides the policy's backoff base for this
        call — callers whose rotation waits on a known process (leader
        election) scale the round gap to that process, not the default
        transport-blip base.

        `fn` raises on transport failure and returns a response otherwise.
        `classify(resp)` returns OK (done), (ROTATE, msg) to move to the
        next target, or (FATAL, msg) to raise error_cls(msg); None means
        every response is success. Exceptions are classified by grpc code:
        never-served rotates, anything else re-raises (ambiguous failures
        rotate only when `idempotent`).
        """
        if not targets:
            raise error_cls(f"{op}: empty target list")
        rounds = rounds if rounds is not None else self.rounds
        last_err = "no target reachable"
        attempt = 0
        for round_i in range(rounds):
            attempted = False
            for t in targets:
                tgt = str(t)
                if not self.breaker.allow(tgt):
                    last_err = f"{tgt}: circuit open"
                    continue
                self._check_budget(op, error_cls, attempt)
                if attempt > 0:
                    self._reg.counter("fault.retries",
                                      labels={"target": tgt}).add(1)
                attempted = True
                t0 = time.perf_counter()
                try:
                    resp = fn(t, attempt)
                except Exception as e:  # noqa: BLE001 — classified below
                    attempt += 1
                    verdict = self.classify_exception(e, idempotent)
                    self.breaker.on_failure(tgt)
                    if verdict is not ROTATE:
                        raise
                    last_err = f"{tgt}: {type(e).__name__}"
                    continue
                self.note_latency(tgt, (time.perf_counter() - t0) * 1e3)
                attempt += 1
                # an in-band answer means the endpoint is HEALTHY even if
                # the verdict says rotate (NotLeader) — close the breaker
                self.breaker.on_success(tgt)
                v = classify(resp) if classify is not None else OK
                if v is OK or v is None:
                    return resp
                kind, msg = v
                if kind == FATAL:
                    raise error_cls(f"{op}: {msg}")
                last_err = f"{tgt}: {msg}"
            if not attempted and round_i == rounds - 1:
                # every target's breaker is open on the final round:
                # availability beats purity — force one probe so a fully
                # failed-then-recovered cluster isn't unreachable until
                # the cooldown lapses
                for t in targets:
                    tgt = str(t)
                    self._check_budget(op, error_cls, attempt)
                    try:
                        resp = fn(t, attempt)
                    except Exception:  # noqa: BLE001
                        attempt += 1
                        continue
                    attempt += 1
                    self.breaker.on_success(tgt)
                    v = classify(resp) if classify is not None else OK
                    if v is OK or v is None:
                        return resp
            if round_i < rounds - 1:
                self.backoff(round_i, op, error_cls, attempt,
                              base_ms=base_backoff_ms)
        raise error_cls(f"{op}: retries exhausted: {last_err}")

    # -- hedged reads ------------------------------------------------------
    def call_hedged(self, targets: Sequence, fn: Callable,
                    *, classify: Optional[Callable] = None, op: str = "",
                    error_cls=RuntimeError):
        """Idempotent-read call with one hedge: fire targets[0]; if it
        hasn't answered within the p99-derived delay, fire targets[1]
        (stamped as attempt 1); first success wins. Falls back to the
        plain retry loop when hedging can't help (single target, or the
        remaining budget can't fit the hedge delay)."""
        if len(targets) < 2:
            return self.call(targets, fn, classify=classify, op=op,
                             error_cls=error_cls, idempotent=True)
        primary, backup = targets[0], targets[1]
        delay_ms = self.hedge_delay_ms(str(primary))
        b = self._budget()
        if b is not None and b.remaining_ms() <= delay_ms * 2:
            return self.call(targets, fn, classify=classify, op=op,
                             error_cls=error_cls, idempotent=True)

        results: "queue.Queue" = queue.Queue()
        # contextvars don't cross threads: carry the span + budget to the
        # primary worker explicitly (the PR 1/PR 10 coalescer discipline)
        from dingo_tpu.obs.pressure import attach_budget, detach_budget
        from dingo_tpu.trace.span import current_span

        span = current_span()
        budget = b

        def _attempt(target, attempt_no, tag):
            t0 = time.perf_counter()
            try:
                resp = fn(target, attempt_no)
            except Exception as e:  # noqa: BLE001 — surfaced via queue
                self.breaker.on_failure(str(target))
                results.put((tag, None, e))
                return
            self.note_latency(str(target),
                              (time.perf_counter() - t0) * 1e3)
            self.breaker.on_success(str(target))
            results.put((tag, resp, None))

        def _primary_worker():
            token = span.attach() if span is not None else None
            btoken = attach_budget(budget) if budget is not None else None
            try:
                _attempt(primary, 0, "primary")
            finally:
                if btoken is not None:
                    detach_budget(btoken)
                if token is not None:
                    span.detach(token)

        worker = threading.Thread(target=_primary_worker, daemon=True,
                                  name="hedge-primary")
        worker.start()
        try:
            tag, resp, exc = results.get(timeout=delay_ms / 1000.0)
        except queue.Empty:
            tag = None
        hedged = False
        if tag is None or exc is not None:
            # primary slow (or failed): fire the hedge inline
            hedged = True
            self._reg.counter("fault.hedges",
                              labels={"target": str(backup)}).add(1)
            _attempt(backup, 1, "hedge")
            tag, resp, exc = results.get()
        outcomes = [(tag, resp, exc)]
        while resp is None and not results.empty():
            outcomes.append(results.get())
            tag, resp, exc = outcomes[-1]
        if resp is None:
            # both in flight can still answer: wait for the other leg
            try:
                outcomes.append(results.get(timeout=5.0))
                tag, resp, exc = outcomes[-1]
            except queue.Empty:
                pass
        if resp is not None:
            if hedged and tag == "hedge":
                self._reg.counter("fault.hedge_wins").add(1)
            v = classify(resp) if classify is not None else OK
            if v is OK or v is None:
                return resp
            kind, msg = v
            raise error_cls(f"{op}: {msg}")
        raise error_cls(f"{op}: hedged read failed: "
                        f"{type(exc).__name__ if exc else 'timeout'}: {exc}")


#: shared default policy for call sites without their own tuning (the
#: coordinator channel and SDK construct their own from flags; this one
#: serves ad-hoc callers and tests)
DEFAULT_POLICY = RetryPolicy()
