"""Deterministic in-process multi-validator simulator with adversarial
delivery.

Parity with the reference's test harness (SURVEY.md §4.1):
  * DeliveryService w/ TAKE_FIRST / TAKE_LAST / TAKE_RANDOM reordering and
    duplicate injection (test/Lachain.ConsensusTest/DeliverySerivce.cs:10-124)
  * BroadcastSimulator auto-instantiating protocols
    (BroadcastSimulator.cs:16-225)
  * muted ("crashed") players (DeliverySerivce.cs:45-48)

Unlike the reference's thread-based router, delivery here is a single seeded
loop: identical seeds replay identical executions, including adversarial
reorderings — the determinism requirement called out in SURVEY.md §7
("hard parts" #3).

Beyond the legacy ad-hoc knobs (mode / repeat_probability / muted), a
`FaultPlan` (network/faults.py) injects seeded drop/delay/duplicate/reorder
faults plus scheduled crash/restart windows and healing partitions; the
virtual clock is the delivered-message count. Lost messages are repaired the
same way the real node repairs them — replay from each router's per-era
outbox — triggered here on quiescence (the in-process analogue of the
message_request wire exchange).
"""
from __future__ import annotations

import enum
import heapq
import random
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

from ..utils import metrics, tracing
from . import messages as M
from .era import EraRouter
from .keys import PrivateConsensusKeys, PublicConsensusKeys


class DeliveryMode(enum.Enum):
    TAKE_FIRST = "first"
    TAKE_LAST = "last"
    TAKE_RANDOM = "random"


class SimulatedNetwork:
    """N validators, one EraRouter each, a shared adversarial delivery queue."""

    def __init__(
        self,
        public_keys: PublicConsensusKeys,
        private_keys: List[PrivateConsensusKeys],
        era: int = 0,
        seed: int = 0,
        mode: DeliveryMode = DeliveryMode.TAKE_FIRST,
        repeat_probability: float = 0.0,
        muted: Optional[Set[int]] = None,
        extra_factories: Optional[Dict[type, Callable]] = None,
        router_cls=EraRouter,
        use_crypto_batcher: bool = True,
        use_rbc_batcher: bool = False,
        fault_plan=None,
        max_recovery_rounds: int = 16,
    ):
        self.n = public_keys.n
        self.rng = random.Random(seed)
        self.mode = mode
        self.repeat_probability = repeat_probability
        self.muted = muted or set()
        # seeded fault schedule: clocked by delivered-message count so two
        # runs with one seed replay bit-identical fault sequences
        self.fault_plan = fault_plan
        self._vtime = 0.0
        self.faults = (
            fault_plan.session(clock=lambda: self._vtime)
            if fault_plan is not None
            else None
        )
        self.recovery_rounds = 0
        self.max_recovery_rounds = max_recovery_rounds
        # (sender, target, payload). Container picked per mode so every
        # _pop is O(1) at 2M-message eras (N=64): deque for FIFO/LIFO
        # (popleft/pop), plain list for RANDOM (indexed swap-with-last +
        # pop from the end — deque middle indexing is O(n))
        self._queue = (
            [] if mode is DeliveryMode.TAKE_RANDOM else deque()
        )
        # time-armed copies (fault delays + LinkShaper latency): a heap of
        # (ready_at, seq, sender, target, payload) surfaced once the
        # virtual clock reaches ready_at. The seq tiebreak keeps pops
        # deterministic and keeps payloads out of heap comparisons.
        self._delayed: List[Tuple[float, int, int, int, Any]] = []
        self._delay_seq = 0
        self.routers: List[EraRouter] = []
        for i in range(self.n):
            self.routers.append(
                router_cls(
                    era=era,
                    my_id=i,
                    public_keys=public_keys,
                    private_keys=private_keys[i],
                    send=self._make_send(i),
                    extra_factories=extra_factories,
                )
            )
        self.delivered_count = 0
        # router-level TPKE flush batcher (crypto_batcher.py): flushed once
        # every queued DecryptedMessage has been delivered, fusing every
        # validator's pending verify+combine work into one backend call
        self.crypto_batcher = None
        self._decrypted_in_queue = 0
        if use_crypto_batcher:
            from .crypto_batcher import TpkeEraBatcher

            self.crypto_batcher = TpkeEraBatcher()
            for r in self.routers:
                r.crypto_batcher = self.crypto_batcher
        # router-level RBC flush batcher (rbc_batcher.py): every pending
        # Reed-Solomon encode/interpolate flushes as one batched matrix
        # product at quiescence. Opt-in (default off) so seed-pinned
        # message schedules in existing tests stay byte-identical.
        self.rbc_batcher = None
        if use_rbc_batcher:
            from .rbc_batcher import RbcEraBatcher

            self.rbc_batcher = RbcEraBatcher()
            for r in self.routers:
                r.rbc_batcher = self.rbc_batcher

    def _make_send(self, sender: int):
        def send(target: Optional[int], payload) -> None:
            if sender in self.muted:
                return  # crashed player: no outbound traffic
            if self.faults is not None and self.faults.crashed(sender):
                return  # scheduled crash window: no outbound traffic
            if type(payload) is M.DecryptedMessage:
                self._decrypted_in_queue += self.n if target is None else 1
            if target is None:
                for t in range(self.n):
                    self._queue.append((sender, t, payload))
            else:
                self._queue.append((sender, target, payload))

        return send

    def inject(self, sender: int, target: Optional[int], payload) -> None:
        """Adversary-layer injection: enqueue a payload AS IF `sender` sent
        it, bypassing the sender's router (and its no-self-equivocation
        journal latch). target None = broadcast. Keeps the DecryptedMessage
        flush accounting coherent so the crypto batcher still fires."""
        if type(payload) is M.DecryptedMessage:
            self._decrypted_in_queue += self.n if target is None else 1
        if target is None:
            for t in range(self.n):
                self._queue.append((sender, t, payload))
        else:
            self._queue.append((sender, target, payload))

    # -- adversarial queue ----------------------------------------------------
    def _pop(self) -> Tuple[int, int, Any]:
        if self.mode is DeliveryMode.TAKE_FIRST:
            item = self._queue.popleft()
        elif self.mode is DeliveryMode.TAKE_LAST:
            item = self._queue.pop()
        else:
            # uniform random choice via swap-with-last + list pop: O(1);
            # surviving order is irrelevant under random selection
            idx = self.rng.randrange(len(self._queue))
            last = self._queue.pop()
            if idx < len(self._queue):
                item = self._queue[idx]
                self._queue[idx] = last
            else:
                item = last
        if self.repeat_probability > 0 and self.rng.random() < self.repeat_probability:
            if type(item[2]) is M.DecryptedMessage:
                self._decrypted_in_queue += 1
            self._queue.append(item)  # duplicate injection
        if (
            self.faults is not None
            and self._queue
            and self.faults.reorder_hit()
        ):
            # fault-plan reordering: swap the picked message with a random
            # queued one (composes with any DeliveryMode)
            idx = self.faults.rng.randrange(len(self._queue))
            item, self._queue[idx] = self._queue[idx], item
        return item

    # -- execution ------------------------------------------------------------
    def post_request(self, validator: int, pid, value) -> None:
        """Inject a top-level ProtocolRequest into one validator."""
        self.routers[validator].internal_request(
            M.Request(from_id=None, to_id=pid, input=value)
        )

    def run(
        self,
        done: Callable[[], bool],
        max_messages: int = 1_000_000,
    ) -> bool:
        """Deliver until `done()` or quiescence/cap. True iff done() held."""
        while not done():
            if self._delayed and self._delayed[0][0] <= self._vtime:
                # a time-armed copy's moment has come: deliver it directly —
                # its link decision was already made when it was armed, so
                # WAN latency defers a message without re-rolling its fate
                if self.delivered_count >= max_messages:
                    raise RuntimeError(
                        f"message cap {max_messages} exceeded — livelock?"
                    )
                _, _, sender, target, payload = heapq.heappop(self._delayed)
                self.delivered_count += 1
                self._vtime += 1.0
                if type(payload) is M.DecryptedMessage:
                    self._decrypted_in_queue -= 1
                if target not in self.muted and not (
                    self.faults is not None and self.faults.crashed(target)
                ):
                    self.routers[target].dispatch_external(sender, payload)
                self._maybe_flush()
                continue
            if not self._queue:
                if self._delayed:
                    # every undelivered message is still in flight on a
                    # shaped/delayed link: advance the virtual clock to the
                    # earliest arrival (latency passing, not quiescence)
                    self._vtime = max(self._vtime, self._delayed[0][0])
                    continue
                metrics.set_gauge("consensus_dispatch_queue_depth", 0)
                # RBC before TPKE: interpolation verdicts unblock READY /
                # delivery traffic that feeds the ACS, whose completions are
                # what make decrypt-share batches grow — flushing RBC first
                # keeps the later crypto flush as large as possible
                if self.rbc_batcher is not None and self.rbc_batcher.pending:
                    self.rbc_batcher.flush()
                    continue
                if self.crypto_batcher is not None and self.crypto_batcher.pending:
                    self.crypto_batcher.flush()
                    continue
                if self.faults is not None:
                    # outbox replay is the in-process stand-in for the
                    # message_request wire exchange: waiting on it is a
                    # network receive wait
                    with tracing.wait("net", kind="recover"):
                        recovered = self._recover()
                    if recovered:
                        continue
                return done()
            if self.delivered_count >= max_messages:
                raise RuntimeError(
                    f"message cap {max_messages} exceeded — livelock?"
                )
            sender, target, payload = self._pop()
            self.delivered_count += 1
            self._vtime += 1.0
            if type(payload) is M.DecryptedMessage:
                self._decrypted_in_queue -= 1
            deliver = True
            if self.faults is not None and sender != target:
                # self-delivery never traverses the network: only link
                # traffic is subject to loss/dup/delay/partition/shaping
                delays = self.faults.decide(sender, target)
                deliver = bool(delays) and delays[0] <= 0
                for d in delays[1:] if deliver else delays:
                    if type(payload) is M.DecryptedMessage:
                        self._decrypted_in_queue += 1
                    if d <= 0:
                        # duplicate: a second full traversal of the link,
                        # re-rolling the dice like any fresh send
                        self._queue.append((sender, target, payload))
                    else:
                        # delayed/shaped copy: armed to surface once the
                        # clock reaches its delivery time
                        self._delay_seq += 1
                        heapq.heappush(
                            self._delayed,
                            (
                                self._vtime + d,
                                self._delay_seq,
                                sender,
                                target,
                                payload,
                            ),
                        )
            elif self.faults is not None and self.faults.crashed(target):
                deliver = False  # crashed: not even self-delivery
            if deliver and target not in self.muted:
                # crashed player: no inbound processing either
                self.routers[target].dispatch_external(sender, payload)
            self._maybe_flush()
        return True

    def _maybe_flush(self) -> None:
        """Flush the TPKE batcher once every queued DecryptedMessage has
        been delivered: the cross-validator batch is at its largest — flush
        NOW, before BinaryAgreement lag rounds spawn fresh coin work."""
        b = self.crypto_batcher
        if b is not None and b.pending and self._decrypted_in_queue == 0:
            b.flush()

    def _recover(self) -> bool:
        """Quiescent but not done under a fault plan: the wedged-era state
        the recovery protocol exists for. Jump the virtual clock to the next
        schedule boundary (healing partitions / restarting crashed nodes
        needs time to pass, and quiescence means no deliveries advance it),
        then replay every live router's per-era outbox across every
        currently-unblocked link — the in-process model of the
        message_request/outbox-replay wire exchange. Returns True when any
        message was re-enqueued; bounded by max_recovery_rounds so a
        genuinely unrecoverable plan (f+1 permanent crashes) terminates."""
        f = self.faults
        if self.recovery_rounds >= self.max_recovery_rounds:
            return False
        boundary = f.next_boundary(self._vtime)
        if boundary is not None:
            self._vtime = max(self._vtime, boundary)
        self.recovery_rounds += 1
        requeued = 0
        for requester in range(self.n):
            if requester in self.muted or f.crashed(requester):
                continue
            for responder in range(self.n):
                if (
                    responder == requester
                    or responder in self.muted
                    or f.crashed(responder)
                    or f.partitioned(responder, requester)
                ):
                    continue
                router = self.routers[responder]
                requeued += router.replay_outbox(router.era, requester)
        return requeued > 0

    def close(self) -> None:
        """Nothing to release here; the native network frees its engine."""

    def results(self, pid) -> List[Any]:
        return [r.result_of(pid) for r in self.routers]
