"""Failover-aware client channel to the replicated coordinator group.

One rotation protocol shared by the SDK (client/client.py) and the store's
remote heartbeat (server/remote_heartbeat.py): hold the raft group's
endpoint list and route every call through the shared RetryPolicy
(client/retry.py) — rotate on NotLeader (errcode 20001) or
connection-level grpc failure, back off with equal jitter between full
rotations (the thundering-herd fix: the old loop slept a fixed 0.2s, so
every client in the fleet re-hit a recovering leader in lockstep), skip
endpoints whose circuit breaker is open, and never outlive the request's
deadline budget.

Retry semantics: UNAVAILABLE / CANCELLED (request never served) and
DEADLINE_EXCEEDED (hung endpoint — rotating is the whole point of the
group) rotate and re-send; every other RpcError and every in-band
application error surfaces to the caller. Caveat a client cannot remove:
a re-sent call whose first attempt committed before the deadline makes
mutations at-least-once — idempotent coordinator ops (create returns
"exists", acks dedupe by cmd_id) absorb this; callers doing
non-idempotent mutations should treat an "exists" answer after a retry
as success.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Type

import grpc

from dingo_tpu.common.config import grpc_options
from dingo_tpu.common.log import get_logger
from dingo_tpu.server.rpc import ServiceStub

_log = get_logger("coord_channel")

_ERR_NOT_LEADER = 20001


class RotatingCoordinatorChannel:
    """Thread-safe; one instance backs every coordinator-side service stub
    so a failover discovered by one call benefits the rest."""

    def __init__(self, addrs: str, error_cls: Type[Exception],
                 timeout_s: float = 10.0, rounds: int = 3,
                 policy=None):
        # deferred: client.retry lives under the client package whose
        # __init__ imports the SDK, which imports THIS module
        from dingo_tpu.client.retry import RetryPolicy

        self._addrs = [a.strip() for a in addrs.split(",") if a.strip()]
        if not self._addrs:
            raise error_cls("empty coordinator address list")
        self._error_cls = error_cls
        self._timeout_s = timeout_s
        self._active = 0
        self._lock = threading.Lock()
        self._channel: Optional[grpc.Channel] = None
        self._stubs: Dict[str, ServiceStub] = {}
        self._policy = policy if policy is not None else \
            RetryPolicy.from_flags(rounds=rounds)
        self._connect(0)

    @property
    def addrs(self):
        return list(self._addrs)

    def _connect(self, idx: int) -> None:
        if self._channel is not None:
            self._channel.close()
        self._active = idx % len(self._addrs)
        self._channel = grpc.insecure_channel(
            self._addrs[self._active], options=grpc_options())
        self._stubs = {}

    def _stub_for(self, service: str):
        stub = self._stubs.get(service)
        if stub is None:
            stub = self._stubs[service] = ServiceStub(self._channel, service)
        return stub

    def call(self, service: str, method: str, req,
             timeout_s: Optional[float] = None):
        """Invoke over the group via the RetryPolicy, starting from the
        last-known-good endpoint, with a per-attempt deadline (a hung
        leader must not disable rotation). Application errors other than
        NotLeader return in-band for the caller to interpret; exhaustion
        raises error_cls. The lock guards only channel state — a
        long-poll must not serialize other calls."""
        deadline = timeout_s if timeout_s is not None else self._timeout_s
        with self._lock:
            start = self._active
        n = len(self._addrs)
        # rotation order starts at the shared active endpoint: a failover
        # discovered by one thread re-points every caller
        order = [self._addrs[(start + i) % n] for i in range(n)]

        from dingo_tpu.client.retry import OK, ROTATE, attempt_metadata

        def _attempt(addr, attempt):
            idx = self._addrs.index(addr)
            with self._lock:
                if self._active != idx:
                    self._connect(idx)
                    _log.info("rotating coordinator endpoint -> %s", addr)
                stub = self._stub_for(service)
            return getattr(stub, method)(
                req, timeout=deadline,
                metadata=attempt_metadata(attempt))

        def _classify(resp):
            err = getattr(resp, "error", None)
            if err is not None and err.errcode == _ERR_NOT_LEADER:
                return (ROTATE, err.errmsg)
            return OK

        return self._policy.call(
            order, _attempt, classify=_classify,
            op=f"coordinator group: {method}",
            error_cls=self._error_cls, idempotent=True)

    def close(self) -> None:
        with self._lock:
            if self._channel is not None:
                self._channel.close()
                self._channel = None
                self._stubs = {}
