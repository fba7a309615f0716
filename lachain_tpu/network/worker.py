"""Per-peer send worker: priority queue + size/time batching.

Parity with the reference's ClientWorker
(/root/reference/src/Lachain.Networking/Hub/ClientWorker.cs:38-143): one
worker per peer, an interval-heap priority queue, batches capped at 64 KiB
flushed at ~4 Hz — but as an asyncio task instead of a thread.
"""
from __future__ import annotations

import asyncio
import logging
import random
import zlib
from collections import deque
from typing import Callable, List, Optional

from ..utils import metrics, tracing
from .hub import Hub, PeerAddress
from .wire import MessageFactory, NetworkMessage, PRIORITY

MAX_BATCH_BYTES = 64 * 1024
FLUSH_INTERVAL = 0.25
# bound on bytes a dead peer's queue may hold before low-priority traffic
# is shed (reconnect storms must not OOM the node); consensus messages are
# the highest priority so they shed last
MAX_QUEUE_BYTES = 8 * 1024 * 1024
BACKOFF_MAX = 8.0


logger = logging.getLogger(__name__)


def durable_before_wire(barrier: Optional[Callable[[], None]]) -> bool:
    """Persist-before-transmit, the frame's half (consensus/journal.py):
    the step in front of every write to a socket. `barrier` returns once
    whatever the node submitted to its store's WAL is durable: the
    journal's records of the payloads a frame carries, and the pool's rows
    of the transactions it gossips or proposes (core/tx_pool.py). None
    means the owner submits nothing, or waits inside its own record and
    add. False when the barrier raised
    (a WAL that cannot fsync): the frame must not leave, and the caller
    treats it as a send that failed — keeps the messages and tries again."""
    if barrier is None:
        return True
    try:
        barrier()
    except Exception:
        logger.exception("durability barrier failed: frame held back")
        metrics.inc("network_barrier_failures_total")
        return False
    return True


class ClientWorker:
    def __init__(
        self,
        peer: PeerAddress,
        factory: MessageFactory,
        hub: Hub,
        *,
        flush_interval: float = FLUSH_INTERVAL,
        max_batch_bytes: int = MAX_BATCH_BYTES,
        transport=None,
        barrier: Optional[Callable[[], None]] = None,
    ):
        self.peer = peer
        self._factory = factory
        self._hub = hub
        # transport(peer, batch_bytes) -> bool; None dials the peer
        # directly (_transmit). Relay-routed peers get a transport that
        # wraps the signed batch in a relay_forward envelope instead (the
        # envelope preserves end-to-end authentication — the inner batch
        # carries OUR signature and only the target verifies it).
        self._transport = transport
        # run before every batch leaves (durable_before_wire)
        self._barrier = barrier
        self._flush_interval = flush_interval
        self._max_batch_bytes = max_batch_bytes
        # one FIFO deque per priority level (PRIORITY values are a small
        # fixed set): O(1) enqueue, O(1) priority-ordered drain, O(1) shed
        # from the least-important tail — a heap paid O(n) scans per
        # message once a dead peer's queue hit the cap
        self._queues = {p: deque() for p in sorted(set(PRIORITY.values()))}
        self._wakeup = asyncio.Event()
        # set by reset_backoff(): cuts a backoff sleep short (the wakeup
        # above must not — a dead peer's queue passes a batch's worth at
        # once and would turn the backoff into a redial loop)
        self._retry_now = asyncio.Event()
        self._task: Optional[asyncio.Task] = None
        self._stopped = False
        self._queued_bytes = 0
        # when the oldest message not yet drained was enqueued (None: the
        # queues were empty since): what a frame waited for its flush
        self._waiting_since: Optional[float] = None
        self._backoff = flush_interval
        # WAN hint (manager/rtt): redial pacing should start near the
        # link's actual RTT — on a 300 ms link a flush-interval-paced
        # first retry burns a dial that cannot have completed yet
        self.backoff_floor = 0.0
        self.consecutive_failures = 0
        # ±25% reconnect jitter, seeded per (us, peer) pair: deterministic
        # for replay, yet different across peers — after a relay blip every
        # worker fleet-wide would otherwise redial in lockstep at exactly
        # backoff*2^k and re-stampede the returning host
        jitter_seed = zlib.crc32(
            factory.public_key
            + (peer.public_key if peer is not None else b"")
        )
        self._jitter = random.Random(jitter_seed)

    def start(self) -> None:
        self._task = asyncio.get_running_loop().create_task(self._run())

    async def stop(self) -> None:
        self._stopped = True
        self._wakeup.set()
        if self._task is not None:
            await self._task

    def _pending(self) -> bool:
        return any(self._queues.values())

    def reset_backoff(self) -> None:
        """Stall-escalation hook: the peer is believed back — retry NOW
        (the queued/undelivered buffer drains on the first successful
        flush) instead of sleeping out the current backoff window."""
        self._backoff = self._flush_interval
        self._retry_now.set()
        self._wakeup.set()

    def enqueue(self, msg: NetworkMessage) -> None:
        if self._waiting_since is None:
            self._waiting_since = metrics.monotonic()
        self._queues[PRIORITY[msg.kind]].append(msg)
        self._queued_bytes += len(msg.body) + 6
        # shed the least-important traffic (numerically largest priority,
        # newest first) when a dead peer's queue passes the cap; consensus
        # outlives pool gossip
        while self._queued_bytes > MAX_QUEUE_BYTES:
            victim = None
            for p in sorted(self._queues, reverse=True):
                if self._queues[p]:
                    victim = self._queues[p].pop()
                    break
            if victim is None:
                break
            self._queued_bytes -= len(victim.body) + 6
            # shedding must be visible: a fast-sync serving peer whose
            # client went away sheds multi-MB snapshot/trie replies here,
            # and a silent drop looks identical to a wire bug
            metrics.inc(
                "network_worker_shed_total",
                labels={"priority": str(PRIORITY[victim.kind])},
            )
        # wake immediately once a batch's worth is pending
        if self._queued_bytes >= self._max_batch_bytes:
            self._wakeup.set()

    def _drain_batch(self) -> List[NetworkMessage]:
        out: List[NetworkMessage] = []
        size = 0
        for p in sorted(self._queues):
            q = self._queues[p]
            while q and size < self._max_batch_bytes:
                msg = q.popleft()
                out.append(msg)
                size += len(msg.body) + 6
            if size >= self._max_batch_bytes:
                break
        self._queued_bytes = max(0, self._queued_bytes - size)
        return out

    async def _run(self) -> None:
        while not self._stopped:
            try:
                await asyncio.wait_for(
                    self._wakeup.wait(), timeout=self._flush_interval
                )
            except asyncio.TimeoutError:
                pass
            self._wakeup.clear()
            while self._pending():
                msgs = self._drain_batch()
                ok = await self._transmit(msgs)
                if ok:
                    self._backoff = self._flush_interval
                    if self.consecutive_failures:
                        # the first frame through after failed dials: the
                        # peer was gone (or unreachable) and is back
                        metrics.inc("network_peer_reconnects_total")
                    self.consecutive_failures = 0
                else:
                    # peer unreachable: requeue and back off EXPONENTIALLY
                    # (a down peer must not be re-dialed 4x/s forever);
                    # every send_raw re-dials, so recovery is the first
                    # successful dial after the peer returns
                    self.consecutive_failures += 1
                    metrics.inc("network_reconnect_attempts_total")
                    for m in reversed(msgs):
                        # requeue at the FRONT of each priority queue so
                        # ordering within a priority is preserved
                        self._queues[PRIORITY[m.kind]].appendleft(m)
                        self._queued_bytes += len(m.body) + 6
                    pause = max(self._backoff, self.backoff_floor)
                    began = metrics.monotonic()
                    try:
                        await asyncio.wait_for(
                            self._retry_now.wait(),
                            timeout=pause * (0.75 + 0.5 * self._jitter.random()),
                        )
                    except asyncio.TimeoutError:
                        self._backoff = min(pause * 2, BACKOFF_MAX)
                    self._retry_now.clear()
                    # seconds this worker sat out between dials of a peer
                    # it could not reach (a wait, not thread time)
                    metrics.inc(
                        "network_backoff_seconds_total",
                        metrics.monotonic() - began,
                    )
                    break
        # final flush on stop
        if self._pending():
            await self._transmit(self._drain_batch())

    async def _transmit(self, msgs: List[NetworkMessage]) -> bool:
        """Where a worker's frame leaves: sign the batch, wait for the
        journal, hand it to the wire (tools/check_invariants.py rule P
        holds the order). A barrier that fails is a send that failed: the
        caller requeues the batch and backs off."""
        since, self._waiting_since = self._waiting_since, None
        if since is not None:
            # a wait, not thread time: the flush interval in front of a hop
            # (a batch requeued after a failed send is not counted again)
            metrics.inc(
                "network_flush_wait_seconds_total", metrics.monotonic() - since
            )
        # part `frame_out`: encode, compress, trailer; the signature inside
        # is `frame_sign`'s, and the awaits below stay outside any scope
        with tracing.account("frame_out"):
            data = self._factory.batch(msgs).encode()
        if not durable_before_wire(self._barrier):
            return False
        if self._transport is None:
            return await self._hub.send_raw(self.peer, data)
        return await self._transport(self.peer, data)
