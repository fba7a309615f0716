"""Protocol actor base + broadcaster interface.

The reference runs one OS thread + blocking queue per protocol instance
(/root/reference/src/Lachain.Consensus/AbstractProtocol.cs:11-168). The
TPU-native runtime is single-threaded and event-driven instead: a protocol is
a plain object whose `receive(envelope)` runs to completion, and ordering/
concurrency live entirely in the router (era.py) and the delivery layer
(simulator for tests, asyncio network for the node). That makes every
consensus execution deterministic and replayable from a seed — the property
the reference's test DeliveryService only approximates
(test/Lachain.ConsensusTest/DeliverySerivce.cs:10-124).

Exception semantics mirror the reference (AbstractProtocol.cs:137-146): an
exception terminates the protocol instance; the router logs and drops further
traffic to it.
"""
from __future__ import annotations

import logging
from typing import Any, Optional

from ..utils import tracing
from . import messages as M

logger = logging.getLogger("lachain.consensus")


class Broadcaster:
    """What a protocol needs from its environment
    (reference seam: IConsensusBroadcaster, IConsensusBroadcaster.cs:7-37)."""

    @property
    def my_id(self) -> int:
        raise NotImplementedError

    @property
    def n_validators(self) -> int:
        raise NotImplementedError

    @property
    def f(self) -> int:
        raise NotImplementedError

    def broadcast(self, payload) -> None:
        """Send an external payload to every validator (including self)."""
        raise NotImplementedError

    def send_to(self, validator: int, payload) -> None:
        raise NotImplementedError

    def internal_request(self, req: "M.Request") -> None:
        """Route a Request to the target protocol (creating it if needed)."""
        raise NotImplementedError

    def internal_response(self, res: "M.Result") -> None:
        """Route a protocol's Result to its parent."""
        raise NotImplementedError


class Protocol:
    """Base class for consensus protocol instances."""

    # Which label of consensus_engine_dispatch_seconds_total{family} a
    # message handled by this class is charged to (tracing.DISPATCH_FAMILIES,
    # the native engine's five). A class that names none lands in the loop's
    # part `other`; every protocol of this package names one
    # (tests/test_loop_ledger.py).
    family = "other"

    def __init__(self, pid, broadcaster: Broadcaster):
        self.id = pid
        self.broadcaster = broadcaster
        self.terminated = False
        self.result: Any = None
        self._result_emitted = False
        self._parent: Optional[Any] = None
        # liveness breadcrumbs for the 60s stall watchdog (reference
        # AbstractProtocol._lastMessage, AbstractProtocol.cs:36-38, 113-135).
        # Only interned type-name strings are kept (an f-string per message
        # costs more than most handlers at N=64 scale; retaining the raw
        # envelope would pin its payload for the protocol's lifetime)
        import time as _time

        self.started_at = _time.monotonic()
        self.last_activity = self.started_at
        self._last_kind: Optional[tuple] = None
        # consecutive watchdog strikes with no progress; reset on receive()
        self.stall_count = 0
        # lifetime span: closed on emit_result / exception termination, or
        # by the era GC sweep for instances an era's outcome never needed
        self._span_id = tracing.begin(
            type(self).__name__,
            cat="protocol",
            era=getattr(pid, "era", None),
            pid=str(pid),
        )

    def record_stall(self) -> int:
        """Watchdog strike: bump and return the consecutive-stall count.
        The escalation ladder (report → re-request → reconnect) is keyed
        off the returned value; any received message resets it."""
        self.stall_count += 1
        return self.stall_count

    # -- runtime ------------------------------------------------------------
    def receive(self, envelope) -> None:
        """Process one envelope to completion. Exceptions terminate the
        protocol (reference: AbstractProtocol.cs:137-146)."""
        if self.terminated:
            return
        from ..utils import metrics

        metrics.MESSAGES_PROCESSED[0] += 1
        self.last_activity = metrics.monotonic()
        self.stall_count = 0
        self._last_kind = (
            type(envelope).__name__,
            type(envelope.payload).__name__
            if isinstance(envelope, M.External)
            else None,
        )
        # exclusive seconds by family: a protocol that answers its parent
        # re-enters receive() beneath this scope, and the child's time is
        # then the child's family's alone
        with tracing.account(self.family):
            try:
                if isinstance(envelope, M.External):
                    self.handle_external(envelope.sender, envelope.payload)
                elif isinstance(envelope, M.Request):
                    self._parent = envelope.from_id
                    if self._result_emitted:
                        # completed before the parent asked (instance was
                        # created by external traffic): replay the result
                        # to the parent
                        self.broadcaster.internal_response(
                            M.Result(
                                from_id=self.id,
                                to_id=self._parent,
                                value=self.result,
                            )
                        )
                    else:
                        self.handle_input(envelope.input)
                elif isinstance(envelope, M.Result):
                    self.handle_child_result(envelope.from_id, envelope.value)
                else:
                    raise TypeError(f"bad envelope {type(envelope)}")
            except Exception:
                logger.exception("protocol %s terminated by exception", self.id)
                self.terminated = True
                self.close_span(outcome="exception")

    def close_span(self, outcome: str = "done") -> None:
        """Close this instance's lifetime span (idempotent) and record its
        duration in the per-protocol-type histogram."""
        from ..utils import metrics

        tracing.end(self._span_id, outcome=outcome)
        if outcome == "done":
            metrics.observe_hist(
                "consensus_protocol_duration_seconds",
                metrics.monotonic() - self.started_at,
                labels={"protocol": type(self).__name__},
            )

    def emit_result(self, value) -> None:
        """Report the protocol's output to the parent, once."""
        if self._result_emitted:
            return
        self._result_emitted = True
        self.result = value
        self.close_span()
        self.broadcaster.internal_response(
            M.Result(from_id=self.id, to_id=self._parent, value=value)
        )

    # -- to override --------------------------------------------------------
    def handle_input(self, value) -> None:
        raise NotImplementedError

    def handle_external(self, sender: int, payload) -> None:
        raise NotImplementedError

    def handle_child_result(self, child_id, value) -> None:
        pass

    @property
    def last_message(self) -> str:
        """Watchdog breadcrumb, rendered on demand."""
        if self._last_kind is None:
            return "<created>"
        kind, payload = self._last_kind
        return kind if payload is None else f"{kind}:{payload}"

    # -- helpers ------------------------------------------------------------
    @property
    def n(self) -> int:
        return self.broadcaster.n_validators

    @property
    def f(self) -> int:
        return self.broadcaster.f

    @property
    def me(self) -> int:
        return self.broadcaster.my_id

    def request(self, to_id, value) -> None:
        self.broadcaster.internal_request(
            M.Request(from_id=self.id, to_id=to_id, input=value)
        )
