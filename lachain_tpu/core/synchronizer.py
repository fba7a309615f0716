"""Block synchronizer: follow-the-chain sync + multisig quorum verification.

Parity with the reference's sync path
(/root/reference/src/Lachain.Core/Network/BlockSynchronizer.cs:28-236:
PingWorker tracks peer heights, BlockSyncWorker requests block ranges from
the best peer, each block's validator multisig is quorum-checked and then
executed through the exact producer commit path) and MultisigVerifier
(Blockchain/Operations/MultisigVerifier.cs:1-67).
"""
from __future__ import annotations

import asyncio
import logging
from typing import Dict, List, Optional, Tuple

from ..consensus.keys import PublicConsensusKeys
from ..crypto import ecdsa
from ..network import wire
from ..network.manager import NetworkManager
from ..utils import metrics, tracing
from .block_manager import BlockManager
from .tx_pool import TransactionPool
from .types import Block, SignedTransaction

logger = logging.getLogger(__name__)

MAX_BLOCKS_PER_REQUEST = 32


def verify_block_multisig(
    block: Block, public_keys: PublicConsensusKeys
) -> bool:
    """N-F distinct valid validator signatures over the header hash
    (reference MultisigVerifier.cs:1-67)."""
    header_hash = block.header.hash()
    seen = set()
    valid = 0
    for idx, sig in block.multisig.signatures:
        if idx in seen or not 0 <= idx < public_keys.n:
            continue
        seen.add(idx)
        pub = public_keys.ecdsa_pub_keys[idx]
        if ecdsa.verify_hash(pub, header_hash, sig):
            valid += 1
    return valid >= public_keys.n - public_keys.f


class BlockSynchronizer:
    """Keeps a node's chain caught up with its peers."""

    def __init__(
        self,
        block_manager: BlockManager,
        pool: TransactionPool,
        network: NetworkManager,
        public_keys: PublicConsensusKeys,
        *,
        ping_interval: float = 1.0,
        keys_provider=None,
    ):
        self.bm = block_manager
        self.pool = pool
        self.network = network
        self.public_keys = public_keys
        # height -> PublicConsensusKeys: with on-chain validator rotation the
        # multisig quorum for block H must be checked against the set that
        # governed era H (ValidatorManager role). The default reads
        # self.public_keys dynamically so assigning that attribute stays
        # meaningful for fixed-set users.
        self.keys_provider = keys_provider or (
            lambda height: self.public_keys
        )
        self.ping_interval = ping_interval
        self.peer_heights: Dict[bytes, int] = {}
        self._tasks: List[asyncio.Task] = []
        self._stopped = False
        self._new_block = asyncio.Event()
        self._request_inflight = False
        self._request_peer: Optional[bytes] = None
        self._request_start = 0
        self._request_time = 0.0
        # an unanswered request is abandoned after this long so
        # _maybe_request rotates to the next best peer instead of wedging
        # forever (reference BlockSynchronizer re-polls; a single lost reply
        # must not stall sync)
        self.request_timeout = max(3.0, 4 * ping_interval)
        # peers that timed out or served nothing useful are benched for a
        # window; pings keep updating their height but _best_peer skips them.
        # Without this, a ping-responsive but sync-useless top-height peer
        # re-enters the height table ~1s after being dropped and throttles
        # sync to one batch per timeout period (or, for an always-empty
        # replier, spins an unthrottled request/empty-reply hot loop).
        self.peer_cooldown = 4 * self.request_timeout
        self._benched: Dict[bytes, float] = {}
        # a restarted node's clock (core/recovery.RecoveryClock), told of
        # the first request and of every block applied; None on a node
        # that never went away
        self.recovery = None
        # wire handlers (the serving side lives here too)
        network.on_ping_reply = self._on_ping_reply
        network.on_sync_blocks_request = self._on_blocks_request
        network.on_sync_blocks_reply = self._on_blocks_reply
        network.on_sync_pool_request = self._on_pool_request

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        loop = asyncio.get_running_loop()
        self._tasks = [loop.create_task(self._ping_loop())]

    async def stop(self) -> None:
        self._stopped = True
        for t in self._tasks:
            t.cancel()
        await asyncio.gather(*self._tasks, return_exceptions=True)

    async def _ping_loop(self) -> None:
        while not self._stopped:
            self.network.broadcast(
                wire.ping_request(self.bm.current_height())
            )
            self._maybe_request()
            await asyncio.sleep(self.ping_interval)

    # -- peer state --------------------------------------------------------

    def _on_ping_reply(self, sender: bytes, height: int) -> None:
        self.peer_heights[sender] = height
        self._maybe_request()

    def _best_peer(self) -> Optional[Tuple[bytes, int]]:
        now = asyncio.get_event_loop().time()
        live = [
            (pub, h)
            for pub, h in self.peer_heights.items()
            if self._benched.get(pub, 0.0) <= now
        ]
        if not live:
            return None
        return max(live, key=lambda kv: kv[1])

    def _bench_peer(self, pub: bytes) -> None:
        self._benched[pub] = (
            asyncio.get_event_loop().time() + self.peer_cooldown
        )

    def best_peers(self, k: int = 4) -> List[bytes]:
        """Up to `k` un-benched peers ordered by advertised height (ties
        broken by pubkey for determinism) — the serving-peer candidate
        set for multi-peer fast sync."""
        now = asyncio.get_event_loop().time()
        live = [
            (h, pub)
            for pub, h in self.peer_heights.items()
            if self._benched.get(pub, 0.0) <= now
        ]
        live.sort(key=lambda hv: (-hv[0], hv[1]))
        return [pub for _, pub in live[:k]]

    def _request_timeout_for(self, pub: Optional[bytes]) -> float:
        """Per-request abandon threshold: the fixed request_timeout floor,
        widened to 8x the serving peer's RTO when it measures slower —
        benching a healthy-but-distant peer for serving at the speed of
        light would thrash the peer rotation on every WAN batch."""
        if pub is None:
            return self.request_timeout
        rtt = getattr(self.network, "rtt", None)
        if rtt is None:
            return self.request_timeout
        return max(self.request_timeout, 8.0 * rtt.rto(pub))

    def _maybe_request(self) -> None:
        if self._request_inflight:
            now = asyncio.get_event_loop().time()
            timeout = self._request_timeout_for(self._request_peer)
            if now - self._request_time < timeout:
                return
            # request timed out: bench the unresponsive peer and rotate
            if self._request_peer is not None:
                self._bench_peer(self._request_peer)
            self._request_inflight = False
            self._request_peer = None
        best = self._best_peer()
        if best is None:
            return
        pub, their = best
        mine = self.bm.current_height()
        if their <= mine:
            return
        count = min(their - mine, MAX_BLOCKS_PER_REQUEST)
        self._request_inflight = True
        self._request_peer = pub
        self._request_start = mine + 1
        self._request_time = asyncio.get_event_loop().time()
        metrics.inc("sync_requests_total")
        if self.recovery is not None:
            self.recovery.sync_requested()
        self.network.send_to(pub, wire.sync_blocks_request(mine + 1, count))

    # -- serving -----------------------------------------------------------

    def _on_blocks_request(self, sender: bytes, start: int, count: int) -> None:
        count = min(count, MAX_BLOCKS_PER_REQUEST)
        out: List[Tuple[Block, List[SignedTransaction]]] = []
        for height in range(start, start + count):
            block = self.bm.block_by_height(height)
            if block is None:
                break
            txs = []
            missing = False
            for h in block.tx_hashes:
                stx = self.bm.transaction_by_hash(h)
                if stx is None:
                    missing = True
                    break
                txs.append(stx)
            if missing:
                break
            out.append((block, txs))
        # always reply, even with no blocks — the requester uses the reply to
        # clear its inflight flag; silence would otherwise wedge its sync
        if out:
            metrics.inc("sync_blocks_served_total", len(out))
        self.network.send_to(sender, wire.sync_blocks_reply(out))

    def _on_pool_request(self, sender: bytes, hashes: List[bytes]) -> None:
        txs = [stx for h in hashes if (stx := self.pool.get(h)) is not None]
        if txs:
            self.network.send_to(sender, wire.sync_pool_reply(txs))

    # -- applying ----------------------------------------------------------

    def _on_blocks_reply(
        self, sender: bytes, blocks: List[Tuple[Block, List[SignedTransaction]]]
    ) -> None:
        awaited = self._request_inflight and sender == self._request_peer
        mine_before = self.bm.current_height()
        applied = 0
        for block, txs in blocks:
            if self.handle_block(block, txs):
                applied += 1
            else:
                break
        if applied:
            self._new_block.set()
        if not awaited:
            # stale or unsolicited reply: blocks above were still applied if
            # valid, but it must not cancel a live request to another peer
            # (that would spawn duplicate concurrent requests)
            return
        req_start = self._request_start
        self._request_inflight = False
        self._request_peer = None
        if self.bm.current_height() > mine_before:
            pass  # real progress
        elif any(
            req_start <= blk.header.index <= mine_before for blk, _ in blocks
        ):
            # we raced ahead of the request (our own consensus committed the
            # blocks first); the peer honestly served what we asked for —
            # benching it would starve sync of its best peers at the tip
            pass
        elif self.peer_heights.get(sender, 0) > mine_before:
            # the peer advertises more blocks than us but served nothing
            # usable (empty reply, gap, bad multisig, stale spam): bench it
            # so the next request rotates instead of hot-looping against it
            self._bench_peer(sender)
        self._maybe_request()

    def handle_block(
        self, block: Block, txs: List[SignedTransaction]
    ) -> bool:
        """Verify + execute one synced block at the current tip
        (reference HandleBlockFromPeer, BlockSynchronizer.cs:110-180)."""
        mine = self.bm.current_height()
        if block.header.index <= mine:
            return True  # already have it
        if block.header.index != mine + 1:
            return False  # gap; re-request from tip
        # one span a synced block (its quorum check, its execution and
        # commit, the pool's eviction), never one a transaction
        with tracing.span(
            "sync.apply", cat="sync", height=block.header.index, txs=len(txs)
        ):
            return self._apply(block, txs, mine)

    def _apply(
        self, block: Block, txs: List[SignedTransaction], mine: int
    ) -> bool:
        prev = self.bm.block_by_height(mine)
        if prev is not None and block.header.prev_block_hash != prev.hash():
            logger.warning("synced block %d does not link", block.header.index)
            return False
        if not verify_block_multisig(
            block, self.keys_provider(block.header.index)
        ):
            logger.warning(
                "synced block %d lacks a signature quorum", block.header.index
            )
            return False
        if {t.hash() for t in txs} != set(block.tx_hashes):
            logger.warning("synced block %d tx set mismatch", block.header.index)
            return False
        try:
            self.bm.execute_block(
                block.header, txs, block.multisig, check_state_hash=True
            )
        except ValueError:
            logger.exception("synced block %d failed execution", block.header.index)
            return False
        self.pool.remove_included(block.tx_hashes)
        metrics.inc("sync_blocks_applied_total")
        if self.recovery is not None:
            self.recovery.block_synced(len(txs))
        return True

    async def wait_for_height(self, height: int, timeout: float = 60.0) -> None:
        """Block until the local chain reaches `height`."""
        deadline = asyncio.get_running_loop().time() + timeout
        while self.bm.current_height() < height:
            remaining = deadline - asyncio.get_running_loop().time()
            if remaining <= 0:
                raise TimeoutError(
                    f"sync stalled at {self.bm.current_height()} < {height}"
                )
            self._new_block.clear()
            try:
                await asyncio.wait_for(self._new_block.wait(), min(remaining, 1.0))
            except asyncio.TimeoutError:
                pass
